import os
import sys

# Smoke tests and benches must see ONE device — the 512-device flag is set
# ONLY inside launch/dryrun.py (per the brief). Nothing to do here except
# make sure a stray environment doesn't leak in.
os.environ.pop("XLA_FLAGS", None) if "force_host_platform_device_count" in \
    os.environ.get("XLA_FLAGS", "") else None

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as CFG
from repro.data import generate_log, LogConfig


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running per-architecture smoke / perf-variant / "
        "bf16-dtype sweep / cross-engine integration tests; the fast loop "
        "(-m 'not slow', 90 s budget enforced by scripts/ci.sh) excludes "
        "them — see ROADMAP.md 'Verification loops'")


# The serving test selection runs under the runtime lock-order witness
# (repro.analysis.witness): every lock the serving classes construct is
# wrapped in a recording proxy, and an acquisition order that closes a
# cycle — the deadlock precondition — fails the test at teardown even when
# the unlucky interleaving never happened. This is the dynamic half of the
# static CL002 graph (python -m repro.analysis), catching orders built
# through dynamic dispatch (depth_fn, injected clocks) the AST cannot see.
_WITNESS_MODULES = {
    "test_session", "test_pump", "test_router", "test_faults",
    "test_determinism", "test_serving_batching", "test_spans",
}


@pytest.fixture(autouse=True)
def _lock_order_witness(request):
    if getattr(request.module, "__name__", "") not in _WITNESS_MODULES:
        yield
        return
    from repro.analysis.witness import install_witness
    witness, uninstall = install_witness()
    try:
        yield witness
        witness.assert_clean()
    finally:
        uninstall()


@pytest.fixture(scope="session")
def small_log():
    return generate_log(LogConfig(n_queries=300, items_per_query=32, seed=11))


@pytest.fixture(scope="session")
def split_log(small_log):
    return small_log.split(0.8, seed=0)


def smoke_cfg(arch: str):
    """Reduced config in float32 for CPU numerics."""
    return dataclasses.replace(CFG.get_smoke(arch), dtype=jnp.float32)
