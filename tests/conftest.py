"""Shared pytest policy: the `slow` tier.

Tier-1 (`pytest` with the default ``-m "not slow"`` from pyproject.toml)
takes 2-2.5 minutes on 6 xdist workers of an 8-CPU host, and runs the same
tests every time (no test draws a new random example per run); the
heavyweight cases below — the largest smoke-model (jamba's 8-layer hybrid
block) and the long-convergence runtime tests — run in CI's separate,
non-blocking ``-m slow`` job.  Tests can also opt in explicitly with
``@pytest.mark.slow``.
"""
import pytest

SLOW_NODEID_PARTS = (
    "jamba-1.5-large-398b",                      # slowest smoke arch (~95 s)
    "test_restart_continues_identically",        # trainer restart (~14 s)
    "test_int8_training_convergence_parity",     # convergence run (~8 s)
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if any(part in item.nodeid for part in SLOW_NODEID_PARTS):
            item.add_marker(pytest.mark.slow)
