"""Run one cell of the chip benchmark once.

  python3 benchmarks/chip/run.py --workload rgg2d-n20-k16.batch \\
      --seed 7 --seconds 40 --trace 0

Reads ``BENCHMARK.json`` at the root of the checkout, finds the cell's
configuration, traffic and metric files by name under ``benchmarks/chip``
and prints one JSON result line last on standard output. ``--trace 1``
reports the per-layer metrics from a profiled run instead of the
end-to-end ones. Exits non-zero, with no result line, where JAX finds no
TPU or fewer chips than the cell asks for. See ``chipbench/cell.py``.
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import cell  # noqa: E402

if __name__ == "__main__":
    sys.exit(cell.main(t_process=T_PROCESS))
