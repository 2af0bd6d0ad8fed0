"""Test configuration: force JAX onto 8 virtual CPU devices.

This exercises the same Mesh/pjit code paths as a v5e-8 slice without TPU
hardware (SURVEY.md §4). ``JAX_PLATFORMS=cpu`` in the environment is what
selects the backend; the ``jax.config.update`` below is belt and braces
for a session where something imported jax before this file ran — it is
harmless when the variable already took, and this is the only place in
the repo that forces the platform in code.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Zero-egress container: stop transformers/huggingface_hub from attempting
# (and retry-looping on) network fetches.
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# Shared tiny-checkpoint builders (tools/tiny_checkpoints.py) back both the
# oracle capture tools and the checkpoint-based differentials.
_TOOLS = str(Path(__file__).resolve().parent.parent / "tools")
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

REFERENCE_DATA = "/root/reference/data"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy tests (virtual-mesh TP/PP/seq-parallel, "
        "executed-reference differentials, torch differentials at size) — "
        "excluded from the fast inner loop")
    config.addinivalue_line(
        "markers", "fast: auto-applied complement of slow; "
        "`pytest -m fast` is the inner loop (measured 163s on the 1-core build container)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker("slow") is None:
            item.add_marker(pytest.mark.fast)


@pytest.fixture(scope="session")
def reference_data_dir():
    """Golden reference CSVs; skip golden-parity tests when not mounted."""
    if not os.path.isdir(REFERENCE_DATA):
        pytest.skip("reference data not available")
    return REFERENCE_DATA


@pytest.fixture()
def rng():
    return np.random.default_rng(42)
