#!/usr/bin/env python3
"""A cell's control: the run that `correct` has to refuse.

    python benchmarks/control.py --workload <cell> --seed <n> --seconds <s>

The same set-up, window and comparison as run.py, with the driver's
`control` in the program's place: one guarantee of the configuration
broken, the short cut a later PR would be tempted by (PERF.md section 2
lists them).  The benchmark's own runs never run it; it was run on the
chip at each cell's own size when the limits were set, and tests/ keeps
it at a small size.  Exits 0 when `correct` came out false.
"""

from __future__ import annotations

import argparse
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    result = run.run_cell(args.workload, args.seed, args.seconds, False,
                          control=True)
    run.report(result)
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main())
