#!/usr/bin/env python3
"""Readings behind the limits of ``correct``, and load sweeps, in one
process (set-up is paid once for the compiles; every seed still makes
its own weights, inputs and engine).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--control] [--fault token] [--set rate=3.5] [--out results.jsonl]

For each seed it runs the cell as ``bench/run.py`` does and prints one
JSON line: the end-to-end metrics, each number compared, and with
``--control`` the same number read from the control (the plain
reference computed one precision lower), which must come out above the
limit.  ``--fault`` plants one of ``harness/faults.py``'s faults in the
program for every seed.  ``--set`` overrides a value of the cell's
settings file, for a sweep of the offered load.  The benchmark's own runs never run the
control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control", action="store_true")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--set", action="append", default=[])
    p.add_argument("--fault", help="plant a fault of harness/faults.py")
    p.add_argument("--out")
    args = p.parse_args(argv)

    import run as entry
    from harness import cells
    from harness.faults import planted
    sys.path.insert(0, entry.program_path())

    overrides = {}
    for item in args.set:
        key, _, value = item.partition("=")
        overrides[key] = json.loads(value)
    if overrides:
        load = cells.load_cell

        def load_with_overrides(name, root=cells.ROOT):
            cell = load(name, root)
            cell.settings = dict(cell.settings, **overrides)
            return cell
        cells.load_cell = load_with_overrides

    out = open(args.out, "a") if args.out else None
    try:
        t_start = T_START
        for seed in (int(s) for s in args.seeds.split(",")):
            run_args = argparse.Namespace(workload=args.workload, seed=seed,
                                          seconds=args.seconds,
                                          trace=args.trace)
            with (planted(args.fault) if args.fault
                  else contextlib.nullcontext()):
                result, outcome = entry.execute(run_args, t_start=t_start,
                                                control=args.control)
            line = {"workload": args.workload, "seed": seed,
                    "settings": overrides, "fault": args.fault,
                    "correct": result["correct"],
                    "attempted": result["attempted"],
                    "failed": result["failed"],
                    "metrics": {k: v["value"]
                                for k, v in result["metrics"].items()},
                    "device": result["device"],
                    "check": result["check"], "control": outcome.controls}
            if "breakdown" in result:
                line["breakdown"] = result["breakdown"]
            text = json.dumps(line)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
            t_start = time.perf_counter()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
