"""Run one benchmark cell:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the run's result (one JSON object);
the numbers compared for ``correct`` are the last lines of standard error.
Exits 2, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime's own logs go under the run's temporary directory
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                  "tpu_logs"))

_ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
