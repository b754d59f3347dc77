"""Batched serving driver: serve a small model with requests sampled from
the paper's HumanEval length profile through the continuous-batching
scheduler (KV slot-pool + slot-recycling admission; core/scheduler.py).

Pass ``--policy fixed`` to see the run-to-completion baseline on the same
trace; speed is measured on the chip by ``bench/run.py``.

  PYTHONPATH=src python examples/serve_batched.py
"""
from repro.launch import serve


def main():
    serve.main([
        "--arch", "llama3.2-1b", "--smoke",
        "--n-requests", "8", "--batch-slots", "4", "--max-new", "16",
        "--profile", "llama_humaneval",
        "--policy", "continuous", "--arrival-rate", "20",
    ])


if __name__ == "__main__":
    main()
