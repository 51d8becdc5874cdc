#!/usr/bin/env python3
"""Readings that set a cell's correctness limit and its traffic, in one
process per call (set-up compiles once; every seed builds its own
weights, engine and traffic).

    # the program's served_logit_gap on many seeds; with --control, the
    # float8 control's tokens compared in the served tokens' place (each
    # such run has to read correct: false)
    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 \
        [--control]
    # an open-loop cell's sustained rate: one window per arrival rate
    python3 bench/calibrate.py --workload <cell> --seeds 1 --seconds 40 \
        --rates 0.4,0.6,0.8

Prints one JSON line per run.  The benchmark's own runs (``run.py``)
never run the control.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

from run import load_cell, run  # noqa: E402  (bench/ is on sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default=None)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else [None])
    for rate in rates:
        c = cell
        if rate is not None:
            c = dataclasses.replace(cell, mix=dict(cell.mix, rate_per_s=rate))
        for seed in (int(s) for s in args.seeds.split(",")):
            out = run(c, seed, args.seconds, trace=False,
                      control="float8" if args.control else None)
            print(json.dumps({"seed": seed, "rate": rate, **out}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
