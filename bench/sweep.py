"""Knee sweep of an open-loop cell: serve the cell's mix at each of a few
fixed rates and print what the system sustained.

    python3 bench/sweep.py --workload <cell> --rates 1,2,3 [--seconds 20]

One process, one run of the cell per rate (seed 0, the mix's own lengths,
the rate replaced).  For each rate it prints one JSON line: the offered
rate, the end-to-end metrics, the requests due in the window and those
that got no first token within the mix's tail, and the window's completed
requests per second.  The knee is the highest rate whose queue does not
grow: every request due in the window served, and completions keeping up
with arrivals.  Run it once, on the chip, when a cell is defined; the
cell's rate is then fixed in its traffic file.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep.py: needs a TPU")
    bench = run.benchmark()
    cell = run.cell_of(bench, args.workload)
    mix = run.traffic_mod.load(cell["traffic"])
    for rate in [float(r) for r in args.rates.split(",")]:
        mix["arrival"]["rate_per_s"] = rate
        res, r = run.run_cell(cell, args.seed, args.seconds, False,
                              bench=bench, mix=mix, keep_run=True)
        done = [q for q in r.requests
                if q.finished is not None and r.t0 < q.finished <= r.t1]
        print(json.dumps({
            "rate_per_s": rate,
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "due_in_window": res["attempted"],
            "no_first_token": res["failed"],
            "completed_per_s": len(done) / (r.t1 - r.t0),
            "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
