"""Readings for the correctness limit, on the chip.

    python3 bench/control.py --workload <name> --seed <n> --seconds <s>

One run of the cell exactly as ``bench/run.py`` makes it (the timed
path, at the cell's sizes), whose sample is then compared twice: the
program's served logit rows against the float32 reference (the lower
reading of the limit), and the control's, the reference computed with
int4 operands (one step below the configuration's FP8) at the same
positions of the same tokens (the upper reading). Both are the widest
relative logit error of ``bench/correct.py``. Prints one JSON line: the
run's whole result (its end-to-end metrics included) with the control's
reading added. Not run by the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as bench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    bench_run.configure_jax()
    seed = args.seed
    r = bench_run.run_cell(types.SimpleNamespace(
        workload=args.workload, seed=seed, seconds=args.seconds, trace=0),
        control=True)
    print(json.dumps(dict(r, workload=args.workload, seed=seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
