#!/usr/bin/env python3
"""Runs one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic
mix, settings, driver and metric readers are found by name under
``bench/`` (see ``harness/cells.py``).  The run needs a TPU with as many
chips as the cell asks for: elsewhere it exits non-zero and prints no
result.  It warms up every shape the cell uses (set-up), measures for
``--seconds``, then checks what the timed path produced against the plain
reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, last,
``check``: each number compared beside its limit.  The same numbers are
the last lines on standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# JAX's persistent compilation cache: a fixed directory in the checkout,
# so only the first run of a cell there compiles
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
EXIT_NO_DEVICE = 3


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def configure_jax(cache_dir: str = CACHE_DIR) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def program_path(root: str = ROOT) -> str:
    """The system under test lives in ``src/`` of the checkout."""
    return os.path.join(root, "src")


def breakdown(reduced) -> dict:
    idle = reduced.idle_by_span(("wait_arrival", "engine.run", "submit",
                                 "call", "block"))
    return {"device_ops": reduced.top_ops(10),
            "idle_gaps": [[n, s] for n, s in
                          sorted(idle.items(), key=lambda kv: -kv[1])[:10]]}


def execute(args, root: str = ROOT, devices=None, t_start: float = T_START,
            control: bool = False):
    """Run the cell; returns (result dict, outcome).  ``devices`` is
    ``jax.devices()`` unless given (tests pass stand-ins)."""
    sys.path.insert(0, BENCH_DIR)
    from harness import device as hw
    from harness.cells import load_cell
    from harness.run_context import MetricContext, RunContext, Tracer

    cell = load_cell(args.workload, root)
    if devices is None:
        configure_jax()
        import jax
        devices = jax.devices()
        device = hw.check_devices(devices, cell.chips)
    else:
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
    devices = devices[:cell.chips]
    peaks = hw.PEAKS.get(device["kind"], {})
    src = program_path(root)
    if src not in sys.path:
        sys.path.insert(0, src)
    tracer = Tracer(bool(args.trace))
    ctx = RunContext(cell=cell, seed=args.seed, seconds=args.seconds,
                     t_start=t_start, devices=devices, peaks=peaks,
                     tracer=tracer, control=control)
    outcome = cell.driver.run(ctx)
    device["memory_peak_bytes"] = outcome.memory_peak_bytes
    metrics = {}
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed}
    if args.trace:
        tracer.reduce()
        reduced = tracer.reduced
        mctx = MetricContext(trace=reduced, readings=outcome.readings,
                             peaks=peaks, cell=cell)
        for m in cell.per_layer:
            value = cell.module("metrics", m["name"]).read(mctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = breakdown(reduced)
    else:
        values = dict(outcome.end_to_end, setup_s=outcome.setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["check"] = outcome.check
    return result, outcome


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    from harness.device import DeviceError
    try:
        result, _ = execute(args)
    except DeviceError as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    for name, c in result["check"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
