import functools
import inspect
import os
import sys
import types

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the 512-device mesh belongs to dryrun.py
# only, which is its own process).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# hypothesis guard: property tests SKIP (not error) when hypothesis is
# absent. The shim replaces @given-decorated tests with a skipper whose
# signature hides the strategy-bound parameters from pytest's fixture
# resolution; everything else in the module still collects and runs.
# Install dev deps (requirements-dev.txt) to run the property tests.
# ---------------------------------------------------------------------------
try:
    import hypothesis  # noqa: F401
except ImportError:
    def _given(*strategies, **kw_strategies):
        def deco(fn):
            @functools.wraps(fn)
            def skipper(*args, **kwargs):
                pytest.skip("hypothesis not installed (see requirements-dev.txt)")
            sig = inspect.signature(fn)
            names = list(sig.parameters)
            bound = set(kw_strategies)
            if strategies:                 # positional strategies fill from the right
                bound |= set(names[len(names) - len(strategies):])
            skipper.__signature__ = sig.replace(
                parameters=[p for n, p in sig.parameters.items() if n not in bound])
            return skipper
        return deco

    def _settings(*args, **kwargs):
        if args and callable(args[0]):     # bare @settings
            return args[0]
        return lambda fn: fn

    class _Strategies(types.ModuleType):
        def __getattr__(self, name):       # any strategy -> inert placeholder
            return lambda *a, **k: None

    _st = _Strategies("hypothesis.strategies")
    _hyp = types.ModuleType("hypothesis")
    _hyp.given, _hyp.settings, _hyp.strategies = _given, _settings, _st
    _hyp.example = lambda *a, **k: (lambda fn: fn)
    _hyp.HealthCheck = types.SimpleNamespace(too_slow=None, data_too_large=None)
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st


@pytest.fixture
def key():
    return jax.random.key(0)


@pytest.fixture
def x64():
    from repro.mpc.ring import x64_scope
    with x64_scope():
        yield
