import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


@pytest.fixture(autouse=True, scope="session")
def _compile_cache_outside_checkout(tmp_path_factory):
    """Tests keep their CPU compile cache out of the checkout's."""
    from bench import harness
    harness.CACHE_DIR = tmp_path_factory.mktemp("jax_cache")
