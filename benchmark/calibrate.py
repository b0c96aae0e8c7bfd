"""Read, on the chip, the numbers the ``correct`` limits are set from.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: a short window of the cell at its own load,
the sound program's compared numbers, and the control's (the reference in
int8, judged on the same prompts and served tokens). Prints both per seed
and, at the end, the sound runs' largest and the control's smallest of each
number. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control", default="int8")
    p.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    p.add_argument("--rehearse-on-cpu", action="store_true")
    args = p.parse_args(argv)
    from benchmark import harness
    from benchmark.spec import Spec

    cell = Spec(args.benchmark).cell(args.workload)
    sound, ctl = [], []

    def say(line) -> None:
        print(json.dumps(line), flush=True)
        if line.get("info") == "compared":
            sound.append({r["number"]: r["value"] for r in line["rows"]})
        if line.get("info") == "control":
            ctl.append(line)

    for seed in (int(s) for s in args.seeds.split(",")):
        line = harness.run_cell(
            cell, seed, args.seconds, False, t_start=time.monotonic(),
            require_tpu=not args.rehearse_on_cpu, say=say,
            control=args.control)
        say({"info": "seed", "seed": seed, "correct": line["correct"],
             "attempted": line["attempted"], "failed": line["failed"]})
    summary = {"info": "calibration", "workload": cell.name,
               "seeds": args.seeds, "control": args.control}
    for name in ("gap_max", "gap_mean"):
        summary[name] = {
            "sound_largest": max(s[name] for s in sound),
            "control_smallest": min(c[name] for c in ctl),
            "sound": [s[name] for s in sound],
            "control": [c[name] for c in ctl],
        }
    say(summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
