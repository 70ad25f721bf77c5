import importlib.util
import os
import sys
from pathlib import Path

# Smoke tests and benches must see the real (single) CPU device — the 512-way
# host-device override belongs ONLY to repro.launch.dryrun.
assert "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""), \
    "do not set the dry-run XLA_FLAGS globally"

try:
    import hypothesis  # noqa: F401 — prefer the real library when present
except ImportError:
    from _hypothesis_fallback import install as _install_hypothesis_fallback
    _install_hypothesis_fallback()

import pytest

from repro.core import MemoryObjectStore, Namespace


@pytest.fixture(scope="session")
def chip_smoke():
    """The repository-root ``chip_smoke.py`` script, loaded by path."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module   # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def store():
    return MemoryObjectStore()


@pytest.fixture
def ns(store):
    return Namespace(store, "runs/test")
