"""Readings of the program, the control and planted faults at a cell's
size, one JSON line per seed; what the limits of ``correct`` are set
from (see ``chipbench/probe.py``). Runs on the chip, and like ``run.py``
exits with no readings where JAX finds no TPU:

  python3 benchmarks/chip/probe.py --workload rgg2d-n20-k16.batch \\
      --seeds 1,2,3 [--kinds program,control,altered,unrefined]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import cell, probe  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default=",".join(probe.KINDS))
    args = ap.parse_args()
    try:
        _, wl, cfg, _ = cell.load_cell(args.workload)
        cell.use_program()
        cell.use_compile_cache()
        cell.chip_devices(int(wl["chips"]))
    except cell.SetupError as e:
        print(f"probe: {e}", file=sys.stderr)
        return e.code
    probe.readings(cfg, [int(s) for s in args.seeds.split(",")],
                   tuple(args.kinds.split(",")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
