#!/usr/bin/env python3
"""Readings that a cell's limits are set from, many seeds in one process.

    python bench/calibrate.py --workload <cell> --seconds <s> --seeds 11 12 ...
    python bench/calibrate.py --workload <cell> --seconds <s> --seeds 11 \
        --vary rate_per_s 20 40 80      # the knee sweep of a serving cell

For each seed it makes a whole run of the cell (set-up, a window of
``--seconds`` at the cell's own load, the check) and prints one JSON
line: the program's numbers (``values``), and the control's: the plain
reference computed in the precision below the one the configuration
states (its ``control`` entry), put in the program's place and compared
in the same way.  The benchmark's own runs never run the control.  With
``--vary KEY V...`` it repeats that for each value of one traffic
parameter (a serving cell's knee is found so, once, when the cell is
defined).  It needs the chip, as a run does.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--vary", nargs="+", metavar=("KEY", "VALUE"))
    args = ap.parse_args(argv)
    try:
        prepared = run.prepare(args.workload)
    except run.Refused as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    key = args.vary[0] if args.vary else None
    values = [json.loads(v) for v in args.vary[1:]] if args.vary else [None]
    base = dict(prepared["traffic"])
    for value, seed in [(v, s) for v in values for s in args.seeds]:
        if key is not None:
            prepared["traffic"] = {**base, key: value}
        t0 = time.perf_counter()
        result, _checks, out = run.run_cell(
            args.workload, seed, args.seconds, False, prepared=prepared,
            t_start=t0, control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "vary": {key: value} if key else None,
                          "correct": result["correct"],
                          "values": out["values"],
                          "control": out["control_values"],
                          "metrics": {k: v["value"] for k, v in
                                      result["metrics"].items()},
                          "notes": out["notes"],
                          "seconds": time.perf_counter() - t0}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
