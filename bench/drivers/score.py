"""Driver of the scoring cells: one caller scores documents back to back
through the program's ``Model.forward``.

Each call takes one document of ``length`` token ids (uniform over the
vocabulary, drawn from the seed; ``documents`` of them are made on the
device at set-up and cycled) and returns, from the same jitted call, the
log-likelihood of every next token and the full logits at ``positions``
seeded positions.  The model's kernels resolve their configs through the
program's default tuning session.  Calls run back to back, each waited
for; ``batch_ms`` is the window divided by the calls completed in it, the
window ending at the last call's completion.

After the window the program's weights are freed and the outputs of two
calls are compared with the plain reference: the last call's, and that of
a seeded call of the first round.  The numbers read are the widest and
the mean |difference| of the log-likelihoods (``max_loglik_gap``,
``mean_loglik_gap``) and of the logit rows (``max_logit_gap``,
``mean_logit_gap``); the cell's settings name the ones compared and their
limits.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Tuple

import numpy as np

from harness import device as hw
from harness.run_context import Outcome, RunContext
from harness.traffic import jax_key, rng_for


def _serve(ctx: RunContext):
    """The serving driver, whose layout check and static config dict the
    scoring cells share."""
    return ctx.cell.module("drivers", "serve")


def _build(ctx: RunContext):
    """The model, its seeded weights and the jitted scoring call."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_arch
    from repro.models.model import build_model

    cfg = ctx.cell.config
    ref = ctx.cell.module("reference", cfg["reference"])
    on_tpu = ctx.devices[0].platform == "tpu"
    mcfg = dataclasses.replace(get_arch(cfg["program_arch"]),
                               **ref.program_fields(cfg), use_pallas=on_tpu)
    model = build_model(mcfg)
    make = jax.jit(lambda key: ref.to_program(ref.make_weights(cfg, key)))
    key = jax_key(ctx.seed)
    _serve(ctx)._check_layout(jax.eval_shape(make, key),
                              jax.eval_shape(model.init, key))
    params = jax.block_until_ready(make(key))

    def score(params, tokens, positions):
        logits, _ = model.forward(params, tokens[None])
        logits = logits[0]                                   # (L, V) f32
        picked = jnp.take_along_axis(logits[:-1], tokens[1:, None],
                                     axis=-1)[:, 0]
        return (picked - jax.nn.logsumexp(logits[:-1], axis=-1),
                logits[positions])
    return params, jax.jit(score)


def _documents(ctx: RunContext):
    """The documents (each its own device array) and the seeded positions
    whose logits every call returns."""
    import jax
    settings = ctx.cell.settings
    n, length = int(settings["documents"]), int(settings["length"])
    vocab = int(ctx.cell.config["vocab_size"])
    docs = jax.jit(lambda key: jax.random.randint(
        key, (n, length), 0, vocab, jax.numpy.int32))(jax_key(ctx.seed, 1))
    docs = [jax.block_until_ready(docs[i]) for i in range(n)]
    rng = rng_for(ctx.seed, 6)
    positions = np.sort(rng.choice(length, int(settings["positions"]),
                                   replace=False)).astype(np.int32)
    return docs, jax.numpy.asarray(positions)


def _compare(got, want) -> Dict[str, float]:
    """The gap numbers of the program's (log-likelihoods, logit rows)
    against the reference's."""
    ll = np.abs(np.asarray(got[0], np.float64) - np.asarray(want[0]))
    rows = np.abs(np.asarray(got[1], np.float64) - np.asarray(want[1]))
    out = {"max_loglik_gap": float(ll.max()),
           "mean_loglik_gap": float(ll.mean()),
           "max_logit_gap": float(rows.max()),
           "mean_logit_gap": float(rows.mean())}
    return {k: v if v == v else float("inf") for k, v in out.items()}


def _reference_check(ctx: RunContext, docs, positions,
                     kept: List[Tuple[int, object]]):
    """The kept calls' outputs against the reference run on their
    documents; returns (numbers, control numbers)."""
    import jax
    import jax.numpy as jnp
    cfg = ctx.cell.config
    ref = ctx.cell.module("reference", cfg["reference"])
    block = int(ctx.cell.settings["ssd_block"])
    tokens = jnp.stack([docs[i] for i, _ in kept])
    got = (np.stack([np.asarray(out[0]) for _, out in kept]),
           np.stack([np.asarray(out[1]) for _, out in kept]))
    frozen = _serve(ctx)._Frozen(cfg)
    w = jax.jit(ref.make_weights, static_argnums=0)(frozen,
                                                    jax_key(ctx.seed))
    fn = jax.jit(ref.score, static_argnums=(1, 4, 5))
    want = fn(w, frozen, tokens, positions, False, block)
    controls = {}
    if ctx.control:
        controls = _compare(fn(w, frozen, tokens, positions, True, block),
                            want)
    return _compare(got, want), controls


def run(ctx: RunContext) -> Outcome:
    import jax

    settings = ctx.cell.settings
    params, call = _build(ctx)
    docs, positions = _documents(ctx)
    n = len(docs)
    jax.block_until_ready(call(params, docs[0], positions))     # compile
    checked = int(rng_for(ctx.seed, 7).integers(n))   # first-round call kept

    span = ctx.tracer.span
    trace_s = float(settings.get("trace_seconds") or ctx.seconds)
    trace_from = 0.5 * max(ctx.seconds - trace_s, 0.0)
    marks: Dict[str, float] = {}
    traced_calls = 0
    kept: Dict[str, Tuple[int, object]] = {}
    done = 0
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    deadline = t0 + ctx.seconds
    while True:
        now = time.perf_counter()
        # the profiler starts and stops between calls, so every call
        # issued in the traced window completes in it
        if "trace_start" not in marks and now - t0 >= trace_from:
            ctx.tracer.start()
            marks["trace_start"] = now
        elif "trace_end" not in marks and "trace_start" in marks and \
                now - marks["trace_start"] >= trace_s:
            ctx.tracer.stop()
            marks["trace_end"] = now
        doc = done % n
        with span("call"):
            out = call(params, docs[doc], positions)
        with span("block"):
            jax.block_until_ready(out)
        if "trace_start" in marks and "trace_end" not in marks:
            traced_calls += 1
        if done == checked:
            kept["first_round"] = (doc, out)
        kept["last"] = (doc, out)
        done += 1
        # the first round always completes: it holds the checked call
        if done >= n and time.perf_counter() >= deadline:
            break
        del out
    t_end = time.perf_counter()
    if "trace_start" in marks and "trace_end" not in marks:
        ctx.tracer.stop()
    window_s = t_end - t0
    memory = hw.memory_peak_bytes(ctx.devices)
    ctx.log(f"[score] window={window_s:.3f}s calls={done} "
            f"traced_calls={traced_calls}")

    # the program's weights go before the reference runs
    del params, call
    gc.collect()
    numbers, controls = _reference_check(
        ctx, docs, positions, [kept["first_round"], kept["last"]])
    ctx.log("[check] " + ", ".join(f"{k} {v!r}" for k, v in numbers.items()))
    check = {name: {"value": numbers[name], "limit": float(limit)}
             for name, limit in settings["limits"].items()}
    length = int(settings["length"])
    return Outcome(
        attempted=done, failed=0, setup_s=setup_s,
        end_to_end={"batch_ms": window_s / done * 1e3},
        check=check, memory_peak_bytes=memory,
        readings={"traced_calls": traced_calls, "tokens_per_call": length},
        controls={k: v for k, v in controls.items() if k in check})
