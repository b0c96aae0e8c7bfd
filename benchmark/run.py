"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell once on the machine it is started on and prints, as the last
line of its standard output, one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` and, last in it, ``compared``: each
number the ``correct`` gate compared beside its limit, which are also the last
lines of its standard error. Exits with another code than 0, and
prints no result, where JAX finds no TPU or too few chips, or where the
program is not in the checkout.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"),
                   help="the benchmark file (default: the checkout's)")
    p.add_argument("--rehearse-on-cpu", action="store_true",
                   help="run the control flow without a chip; the result "
                        "line then carries no metric")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seconds <= 0:
        print("--seconds must be > 0", file=sys.stderr)
        return 2
    from benchmark.spec import Spec, SpecError

    def say(line) -> None:
        print(json.dumps(line), flush=True)

    try:
        cell = Spec(args.benchmark).cell(args.workload)
        import tree_attention_tpu  # noqa: F401  the system under test
        from benchmark import harness
    except (SpecError, ImportError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
    try:
        line = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            t_start=_T_START, require_tpu=not args.rehearse_on_cpu, say=say,
            trace_dir=trace_dir)
    except harness.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    except SpecError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    if args.rehearse_on_cpu:
        # A CPU's timings are never written under a metric's name.
        line["rehearsal"] = {"metrics_not_reported": sorted(line["metrics"])}
        line["metrics"] = {}
        line["compared"] = line.pop("compared")         # stays last
    say(line)
    # The same numbers as the last lines of standard error, which is what a
    # record of a run that was not correct keeps.
    for name, row in line["compared"].items():
        print(f"compared {name} {row['value']} limit {row['limit']}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
