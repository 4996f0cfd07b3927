"""Shared pytest config.

NOTE: no XLA_FLAGS here — smoke tests and benches must see ONE device;
only launch/dryrun.py (and subprocess tests driving it) force the
512/8-device placeholder fleet.

The persistent compile cache is off for the whole suite: the entry
points that tests start as subprocesses (rl_train, serve) would turn it
on at the checkout's ``.jax_cache/``, and a test's result must not
depend on what an earlier run left there.  Set before any test module
builds its subprocess environment from ``os.environ``.
"""
import os

import pytest

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


def pytest_configure(config):
    config.addinivalue_line("markers",
                            "slow: long-running (subprocess dry-runs, e2e)")
