"""Find the knee of a paced cell once, on the chip.

    python3 benchmark/sweep.py --workload <cell> --rates 0.6,0.8,1.0 --seconds <s>

Runs the cell at each rate (requests per second, in place of the traffic
file's ``rate_per_s``) in one process and prints, per rate, what was left
waiting when the window closed, the time to first token and the tokens per
second. The knee is the highest rate at which the backlog does not grow;
the cell's own rate, a number in its traffic file, is set from it by hand.
The benchmark's own runs never run this.
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
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    p.add_argument("--rehearse-on-cpu", action="store_true")
    args = p.parse_args(argv)
    from benchmark import harness, reduce
    from benchmark.spec import Spec

    cell = Spec(args.benchmark).cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        seen = {}

        def say(line) -> None:
            if line.get("info") == "window":
                seen.update(line)

        line = harness.run_cell(
            cell, args.seed, args.seconds, False, t_start=time.monotonic(),
            require_tpu=not args.rehearse_on_cpu, say=say, e2e_all=True)
        print(json.dumps({
            "rate_per_s": rate, "correct": line["correct"],
            "waiting_at_end": seen.get("waiting_at_end"),
            "requests_released": seen.get("requests_released"),
            "first_half_tok_s": seen.get("first_half_tok_s"),
            "second_half_tok_s": seen.get("second_half_tok_s"),
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
