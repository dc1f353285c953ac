"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    # The checkout root replaces this script's directory on the path, so
    # that bench/trace.py cannot shadow the standard library's ``trace``.
    sys.path[0:1] = [str(root), str(root / "src")]
    from bench.harness import main

    sys.exit(main(t_start=T_START))
