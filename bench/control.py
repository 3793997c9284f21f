"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1,2,3

For each seed, in one process: a whole run of the cell (set-up, priming
ticks, window), then on its seeded sample of finished requests the
program's reading and the control's (the reference one precision step
below the configuration, read at the same positions), each judged against
the configuration's limit as a run judges the program.  The benchmark's
own runs never read the control.  One JSON line per seed (``setup_s``
after the first seed counts the earlier seeds too); the program's
``correct`` has to be true and the control's false.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for seed in [int(x) for x in args.seeds.split(",")]:
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           control=True)
        print(json.dumps({"seed": seed,
                          "program": {"correct": res["correct"],
                                      "compared": res["compared"]},
                          "control": res["control"],
                          "metrics": res["metrics"],
                          "device": res["device"]}), flush=True)
        del res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
