"""Run one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout. The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace
1``), then ``checks``, each number compared with its limit. Off a TPU,
or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness.cell import run  # noqa: E402

if __name__ == "__main__":
    run(sys.argv[1:], t_start=T_START)
