"""Driver of the kernel-op cells: one caller, calls back to back.

The mix (``traffic/<mix>.json``) lists ops and sizes; every call works on
``total_elems`` elements (batch = total_elems / n, the paper's rule).
Each call goes through the program's public op, jitted once per shape,
with the config its default tuning session resolves.  The calls run in
rounds, every (op, n) once per round in a seeded order, each waited for.

``batch_ms`` is the window divided by the calls completed in it.  The
outputs of a seeded sample of the first round's calls are kept and,
after the window, compared with the plain reference.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Tuple

from harness import device as hw
from harness.run_context import Outcome, RunContext
from harness.traffic import call_rounds, jax_key, rng_for

Key = Tuple[str, int]


def _program_ops() -> Dict[str, object]:
    from repro.kernels.fft.ops import fft
    from repro.kernels.scan.ops import linear_recurrence, prefix_sum
    from repro.kernels.tridiag.ops import solve
    return {"prefix_sum.ks": functools.partial(prefix_sum, variant="ks"),
            "prefix_sum.lf": functools.partial(prefix_sum, variant="lf"),
            "linear_recurrence": linear_recurrence,
            "fft": fft,
            "tridiag.pcr": functools.partial(solve, variant="pcr")}


def _resolved_config(op: str, n: int, batch: int) -> str:
    """The config the program's default session resolves for one shape,
    for the log; the call itself resolves it the same way."""
    try:
        from repro.core.space import Workload
        from repro.tuning import default_session
        if op.startswith("prefix_sum."):
            wl = Workload("scan", n, batch, variant=op.split(".")[1])
        elif op == "linear_recurrence":
            wl = Workload("scan", n, batch, variant="linrec")
        elif op == "fft":
            from repro.core.multikernel import max_resident_tile
            wl = Workload("fft", n, batch, variant="stockham")
            if n > max_resident_tile(wl):
                wl = Workload("large_fft", n, batch, variant="stockham")
        else:
            wl = Workload("tridiag", n, batch, variant=op.split(".")[1])
        return str(default_session().resolve(wl))
    except Exception as e:          # the log line only; the run goes on
        return f"unavailable ({type(e).__name__}: {e})"


def _schedule(keys: List[Key], seed: int):
    """(round, key) without end: every key once a round, seeded order."""
    r = 0
    while True:
        for k in call_rounds(keys, seed + r, 1):
            yield r, k
        r += 1


def run(ctx: RunContext) -> Outcome:
    import jax

    ref = ctx.cell.module("reference", ctx.cell.config["reference"])
    total = int(ctx.cell.config["total_elems"])
    keys: List[Key] = [(c["op"], int(n)) for c in ctx.cell.traffic["calls"]
                       for n in c["sizes"]]
    program = _program_ops()

    # inputs: every (kind, n) made on the device in one jitted call
    kinds = sorted({(ref.INPUT_KIND[op], n) for op, n in keys})

    def make_all(key):
        return {f"{kind}:{n}": ref.make_inputs(kind, n, total // n,
                                               jax.random.fold_in(key, i))
                for i, (kind, n) in enumerate(kinds)}
    inputs = jax.block_until_ready(jax.jit(make_all)(jax_key(ctx.seed)))

    def args_of(k: Key):
        return inputs[f"{ref.INPUT_KIND[k[0]]}:{k[1]}"]

    calls = {}
    for k in keys:
        op, n = k
        calls[k] = jax.jit(program[op])
        ctx.log(f"[config] {op} n={n} batch={total // n} "
                f"config={_resolved_config(op, n, total // n)}")
        jax.block_until_ready(calls[k](*args_of(k)))      # warm: compile

    # the calls whose outputs are checked: per op, a seeded sample of its
    # sizes, from the first round
    rng = rng_for(ctx.seed, 4)
    per_op = int(ctx.cell.traffic["check"]["sample_per_op"])
    sampled = set()
    for c in ctx.cell.traffic["calls"]:
        sizes = [int(n) for n in c["sizes"]]
        for i in rng.permutation(len(sizes))[:per_op]:
            sampled.add((c["op"], sizes[i]))
    kept: Dict[Key, object] = {}

    span = ctx.tracer.span
    ctx.tracer.start()
    log: List[Key] = []
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    deadline = t0 + ctx.seconds
    for round_index, k in _schedule(keys, ctx.seed):
        with span("call"):
            out = calls[k](*args_of(k))
        with span("block"):
            out.block_until_ready()
        log.append(k)
        if round_index == 0 and k in sampled:
            kept[k] = out
        del out
        # the first round always completes: it holds the checked calls
        if round_index > 0 and time.perf_counter() >= deadline:
            break
    t_end = time.perf_counter()
    done = len(log)
    ctx.tracer.stop()
    window_s = t_end - t0
    memory = hw.memory_peak_bytes(ctx.devices)

    # check after the window: the kept outputs against the reference
    del calls
    needed = {f"{ref.INPUT_KIND[op]}:{n}" for op, n in kept}
    for name in [n for n in inputs if n not in needed]:
        del inputs[name]
    limits = ctx.cell.settings["limits"]
    worst: Dict[str, float] = {}
    controls: Dict[str, float] = {}
    for (op, n), got in sorted(kept.items()):
        args = args_of((op, n))
        want = jax.jit(ref.REFERENCE[op])(*args)
        err = float(ref.rel_err(got, want))
        err = err if err == err else float("inf")
        name = f"rel_err.{op}"
        worst[name] = max(worst.get(name, 0.0), err)
        if ctx.control:
            ctl = jax.jit(functools.partial(ref.control, op))(args)
            controls[name] = max(controls.get(name, 0.0),
                                 float(ref.rel_err(ctl, want)))
        del want
    check = {name: {"value": v, "limit": float(limits[name])}
             for name, v in sorted(worst.items())}
    return Outcome(
        attempted=done, failed=0, setup_s=setup_s,
        end_to_end={"batch_ms": window_s / done * 1e3},
        check=check, memory_peak_bytes=memory,
        readings={"calls": [(op, n, total // n) for op, n in log]},
        controls=controls)
