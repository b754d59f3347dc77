import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """Runs here compile for the CPU; keep them out of the checkout's
    compilation cache, which is the chip's."""
    from bench import harness

    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
