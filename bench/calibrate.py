"""Readings that a cell's correctness limit is set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds 10]

One process serves the cell once per seed — the cell's own mix and load,
a short window — and for each run prints the widest logit gap of the
served tokens under the float32 reference (`max_logit_gap`, the number a
run compares) and the same checks with the control in the program's
place: the reference computed with float8 (e4m3) matmul inputs, whose
first choice at each position of the same prompts and served tokens is
read under the float32 reference.  `control_correct` is what `correct`
reads with the control's tokens; it has to be false.  The limit lies
above the largest program reading and below the smallest control
reading.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate.py: needs a TPU")
    bench = run.benchmark()
    cell = run.cell_of(bench, args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = run.run_cell(cell, seed, args.seconds, False, bench=bench,
                           control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"],
                          "control_correct": res.get("control_correct"),
                          "control_checks": res.get("control_checks"),
                          "metrics": {k: v["value"] for k, v
                                      in res["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
