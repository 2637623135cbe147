"""Sweep the offered rate of an open-loop cell to find its knee.

    python3 perfbench/sweep.py --workload <open-loop cell> \
        --rates 0.04,0.06,0.08 --seed 5 --seconds 51

Runs the cell once per rate in one process and prints one JSON line per
rate: the end-to-end metrics, the median and the 90th percentile of the
queue wait, and how the wait grew over the window (the wait of the last
third of the requests due over that of the first third).  The knee is the
highest rate at which the wait does not grow through the window.  The
cell's rate is then fixed in its traffic file, at about four fifths of the
knee; the benchmark's own runs never sweep.
"""
import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

from pb import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()
    for rate in [float(r) for r in args.rates.split(",")]:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                False, t_start=time.monotonic(), rate=rate)
        run = line.pop("_run")
        due = sorted(run.due_in_window(), key=lambda s: s.due)
        wait = [(s.req.admit_t - s.due) if s.req.admit_t else np.inf
                for s in due]
        third = max(1, len(wait) // 3)
        print(json.dumps({
            "rate": rate, "due": len(due), "correct": line["correct"],
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            "wait_p50_s": float(np.percentile(wait, 50)) if wait else None,
            "wait_p90_s": float(np.percentile(wait, 90)) if wait else None,
            "wait_growth": float(np.mean(wait[-third:])
                                 / max(np.mean(wait[:third]), 1e-3))
            if wait else None,
            "log": run.log}), flush=True)
        del run, line
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
