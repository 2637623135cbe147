"""The lower-precision control of a cell, run on the chip.

    python3 perfbench/control.py --workload yi-6b.decode \
        --seeds 11,12,13 --seconds 51

For each seed this runs the cell as the benchmark does and then judges
the served requests twice by the harness's own comparison
(``harness.judge``): once with the program's served tokens, which decide
the run's ``correct``, and once for each lower precision with the token
that the plain reference computed in int8, and in float8, puts first at
each of the same positions in their place.  It prints one JSON line per
seed, with each side's ``correct`` and its numbers beside their limits;
the control has to come out ``correct: false``.  The benchmark's own runs
never run it.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

from pb import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        line = harness.run_cell(args.workload, seed, args.seconds, False,
                                t_start=t0, control=True)
        run = line.pop("_run")
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": {"correct": line["correct"],
                        "checks": line["checks"]},
            "control": line["control"], "log": run.log}), flush=True)
        for q, c in line["control"].items():
            g = c["checks"]["logit_gap"]
            print(f"seed {seed} control {q} logit_gap {g['value']!r} limit "
                  f"{g['limit']!r} correct {c['correct']}", file=sys.stderr,
                  flush=True)
        del run, line
    return 0


if __name__ == "__main__":
    sys.exit(main())
