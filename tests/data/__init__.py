"""Test data: pinned probe streams (see probe_streams.py)."""
