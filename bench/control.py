#!/usr/bin/env python3
"""The control: a run of a cell with its guarantee broken underneath,
which the check has to read as not correct.

    python3 bench/control.py --workload <name> --seconds <s> \\
        --seeds <n> <n> <n> [--fault truncated]

The configurations state exact answers.  The control (``truncated``,
see ``faults.py``) serves approximate ones: every answer of more than one
row loses its last row.  Each seed is one whole run of the cell on the
chip, at the cell's own size and load, with the fault planted in the
window's scheduler; it prints the compared numbers and ``correct``.  The
exit code is 0 only if every run reads ``correct`` false.  The
benchmark's own runs never plant a fault.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default="truncated")
    args = ap.parse_args()
    import harness
    from faults import FAULTS
    from run import compile_cache, devices, log

    cell = harness.load_cell(args.workload)
    devices(cell.chips)
    compile_cache()
    caught = True
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False,
                               t_start=time.perf_counter(), log=log,
                               fault=FAULTS[args.fault])
        caught &= not out["correct"]
        print(json.dumps({"workload": cell.name, "fault": args.fault,
                          "seed": seed, "correct": out["correct"],
                          "compared": out["compared"]}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
