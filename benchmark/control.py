#!/usr/bin/env python3
"""Runs a cell with the control or a planted fault in place of the timed
path, once per seed, all in one process (one process holds the chip):

    python3 benchmark/control.py --workload l4_dict.churn \
        --seeds 11,12,13 --seconds 5 --patch control

Each run prints its result line as `run.py` does; `correct` has to come
out false. A last line `control: {...}` gives each seed's verdict; the
exit code is 0 only where every seed ran and came out not correct. The
patches are in harness/faults.py. The benchmark's own runs never apply
one.
"""

import argparse
import json
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from harness import faults  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--patch", choices=sorted(faults.PATCHES),
                    default="control")
    args = ap.parse_args(argv)
    verdicts = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run(SimpleNamespace(workload=args.workload, seed=seed,
                                      seconds=args.seconds, trace=0),
                      patch=faults.PATCHES[args.patch])
        verdicts[seed] = None if out is None else out["correct"]
    print("control: " + json.dumps({"patch": args.patch,
                                    "correct": verdicts}),
          file=sys.stderr, flush=True)
    return 0 if all(v is False for v in verdicts.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
