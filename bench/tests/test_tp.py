"""The tensor-parallel cell's path on four host devices, in a process of
its own (the device count is fixed when JAX starts)."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).with_name("tp_run.py")


def _run(*args):
    p = subprocess.run([sys.executable, str(SCRIPT), *args],
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [None, "--drop-exchange"])
def test_tp_run(fault):
    out = _run(*([fault] if fault else []))
    assert out["device"]["count"] == 4
    if fault is None:
        assert out["correct"], out["checks"]
    else:
        assert not out["correct"]
        gap = out["checks"]["max_logit_gap"]
        assert gap["value"] > gap["limit"]
