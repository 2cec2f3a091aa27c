#!/usr/bin/env python3
"""Read the control at a cell's own size on the chip, on several seeds.

    python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Each seed runs the cell as ``bench/run.py`` would, with the control
(``harness/control.py``: the reference, forward pass only) in the program's
place, and prints one line of the numbers compared with their limits.  The
benchmark's own runs never run it.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from harness import control, device, runner
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = runner.load_cell(args.workload)
    devices = device.check_devices(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = runner.execute(
            cell, seed, args.seconds, False, time.perf_counter(), devices,
            lambda m: print(f"[control {args.workload}] {m}", file=sys.stderr, flush=True),
            wrap_parser=control.wrap(cell.config["regex"]),
        )
        print(json.dumps({"workload": args.workload, "seed": seed, "control": True,
                          "correct": out["correct"], "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
