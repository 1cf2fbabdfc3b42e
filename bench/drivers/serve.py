"""Driver of the serving cells: a served model under an open or a closed
loop of requests, through the program's ``ServeEngine``.

The harness drives the engine only through ``submit`` (when a request is
due) and ``run(max_steps=...)`` between arrivals, and stamps each output
token when the host sees it.  Open loop: requests are due on a seeded
Poisson schedule at the cell's fixed rate, and a request's latency
counts from when it was due.  Closed loop: each client sends its next
request when it has seen the previous one finish.

A request that fell due while the engine's last call of the window ran
is sent when the window closes.  After the window, the first token of
every request due in it is waited for (at most ``drain_s``; one that
never comes has failed).  Then the program's state is freed, and a
seeded sample of the finished requests, the longest among them, is run
through the plain reference.  At each position it reads the gap by which
the served token's logit lies below the reference's best logit; the
cell's settings name the summaries compared (the widest or the mean gap)
and their limits.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from harness import device as hw
from harness import traffic as gen
from harness.run_context import Outcome, RunContext
from harness.stats import percentile, rate


@dataclasses.dataclass
class Track:
    """A request as the client sees it."""
    spec: gen.Request
    due: float
    obj: object                        # the engine's Request
    sent: float = 0.0                  # when the client submitted it
    stamps: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None


def _check_layout(params_shape, program_shape) -> None:
    import jax
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params_shape)
    want = jax.tree.map(lambda x: (x.shape, str(x.dtype)), program_shape)
    if jax.tree.structure(got) != jax.tree.structure(want) or \
            jax.tree.leaves(got) != jax.tree.leaves(want):
        raise RuntimeError("the reference's weights do not fit the "
                           f"program's parameter tree: {got} vs {want}")


def _build(ctx: RunContext):
    """Model, seeded weights and a warmed engine (all set-up)."""
    import jax
    from repro.configs.base import get_arch
    from repro.models.model import build_model
    from repro.serve.engine import ServeEngine

    cfg = ctx.cell.config
    ref = ctx.cell.module("reference", cfg["reference"])
    mcfg = dataclasses.replace(get_arch(cfg["program_arch"]),
                               **ref.program_fields(cfg))
    model = build_model(mcfg)
    make = jax.jit(lambda key: ref.to_program(ref.make_weights(cfg, key)))
    key = gen.jax_key(ctx.seed)
    _check_layout(jax.eval_shape(make, key),
                  jax.eval_shape(model.init, key))
    params = jax.block_until_ready(make(key))
    settings = ctx.cell.settings
    engine = ServeEngine(model, params, **settings["engine"])
    engine.warmup()
    # one throwaway request through the real loop: the harvest batches of
    # every size and the lane helpers compile here, not in the window
    steps = int(settings["steps_per_run"])
    engine.submit(np.arange(2 * engine.prefill_chunk + 1, dtype=np.int32),
                  max_new_tokens=4 * steps)
    for k in range(1, steps + 1):
        engine.run(max_steps=k)
    engine.run(max_steps=10_000)
    return engine, params


class _Loop:
    """The timed loop: submissions, engine steps, token stamps."""

    def __init__(self, ctx: RunContext, engine):
        self.ctx = ctx
        self.engine = engine
        self.steps = int(ctx.cell.settings["steps_per_run"])
        self.tracks: List[Track] = []
        self.live: List[Track] = []

    def submit(self, spec: gen.Request, due: float) -> Track:
        self.engine.submit(spec.prompt, max_new_tokens=spec.max_new_tokens)
        track = Track(spec, due, self.engine.queue[-1],
                      sent=time.perf_counter())
        self.tracks.append(track)
        self.live.append(track)
        return track

    def busy(self) -> bool:
        return bool(self.engine.queue) or any(
            r is not None for r in self.engine.slot_req)

    def step(self) -> List[Track]:
        """One ``run`` call, then stamp what the host now sees; returns
        the requests seen to finish."""
        with self.ctx.tracer.span("engine.run"):
            self.engine.run(max_steps=self.steps)
        now = time.perf_counter()
        finished, still = [], []
        for t in self.live:
            n = len(t.obj.output)
            if n > len(t.stamps):
                t.stamps.extend([now] * (n - len(t.stamps)))
            if t.obj.done:
                t.finished = now
                finished.append(t)
            else:
                still.append(t)
        self.live = still
        return finished


def _window(ctx: RunContext, loop: _Loop) -> Dict[str, float]:
    """Run the window; returns its marks on the host clock."""
    mix, settings = ctx.cell.traffic, ctx.cell.settings
    vocab = int(ctx.cell.config["vocab_size"])
    span = ctx.tracer.span
    trace_s = float(settings.get("trace_seconds") or ctx.seconds)
    trace_from = 0.5 * max(ctx.seconds - trace_s, 0.0)
    marks: Dict[str, float] = {}
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    marks["t0"] = t0
    if mix["loop"] == "open":
        pending = gen.open_loop(mix, float(settings["rate"]), ctx.seconds,
                                vocab, ctx.seed)
        queues = None
    else:
        queues = gen.closed_loop(mix, int(settings["clients"]),
                                 int(settings["rounds"]), vocab, ctx.seed)
        pending = []
        for q in queues:
            loop.submit(q.pop(0), t0)
    i = 0
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        if "trace_start" not in marks and now - t0 >= trace_from:
            ctx.tracer.start()
            marks["trace_start"] = time.perf_counter()
        if "trace_start" in marks and "trace_end" not in marks and \
                now - marks["trace_start"] >= trace_s:
            marks["trace_end"] = time.perf_counter()
            ctx.tracer.stop()
        with span("submit"):
            while i < len(pending) and t0 + pending[i].arrival <= now:
                loop.submit(pending[i], t0 + pending[i].arrival)
                i += 1
        if loop.busy():
            for t in loop.step():
                if queues is not None and queues[t.spec.client] and \
                        time.perf_counter() < deadline:
                    loop.submit(queues[t.spec.client].pop(0), t.finished)
        else:
            nxt = t0 + pending[i].arrival if i < len(pending) else deadline
            with span("wait_arrival"):
                time.sleep(max(min(nxt, deadline) - time.perf_counter(), 0))
    marks["end"] = time.perf_counter()
    marks["queued"] = len(loop.engine.queue)
    # requests that fell due while the last engine call ran are sent now,
    # late; their latency counts from when they were due
    for spec in pending[i:]:
        loop.submit(spec, t0 + spec.arrival)
    if "trace_start" in marks and "trace_end" not in marks:
        marks["trace_end"] = marks["end"]
        ctx.tracer.stop()
    marks["deadline"] = deadline
    return marks


def _reference_check(ctx: RunContext, loop: _Loop) -> Dict[str, object]:
    """Logit gaps of a seeded sample of finished requests: per compared
    position, how far the reference's logit of the served token lies below
    its best; summed up as the widest and the mean gap."""
    import jax
    import jax.numpy as jnp

    cfg, check = ctx.cell.config, ctx.cell.settings["check"]
    ref = ctx.cell.module("reference", cfg["reference"])
    done = [t for t in loop.tracks if t.obj.done and t.obj.output]
    if not done:
        return {"tokens": 0, "numbers": {n: float("inf") for n in GAPS},
                "control": {}}
    size = lambda t: len(t.spec.prompt) + len(t.obj.output)  # noqa: E731
    longest = max(done, key=size)
    rest = [t for t in done if t is not longest]
    rng = gen.rng_for(ctx.seed, 5)
    picks = [rest[j] for j in rng.permutation(len(rest))]
    sample = [longest] + picks[:int(check["sample"]) - 1]
    bucket = int(check["bucket"])
    tokens = np.zeros((len(sample), bucket), np.int32)
    targets = np.full((len(sample), bucket), -1, np.int32)
    for row, t in enumerate(sample):
        seq = np.concatenate([t.spec.prompt,
                              np.asarray(t.obj.output, np.int32)])
        if len(seq) > bucket + 1:
            raise RuntimeError(f"request of {len(seq)} tokens exceeds the "
                               f"check bucket {bucket}")
        plen = len(t.spec.prompt)
        tokens[row, :len(seq) - 1] = seq[:-1]
        targets[row, plen - 1:len(seq) - 1] = seq[plen:]
    w = jax.jit(ref.make_weights, static_argnums=0)(
        _Frozen(cfg), gen.jax_key(ctx.seed))
    fn = jax.jit(ref.gaps, static_argnums=(1, 4))
    # the config dict is static: pass a hashable copy
    gap, ctl = fn(w, _Frozen(cfg), jnp.asarray(tokens), jnp.asarray(targets),
                  ctx.control)
    mask = jnp.asarray(targets >= 0)
    count = int(mask.sum())
    numbers = _summarise(gap, mask, count)
    return {"tokens": count, "numbers": numbers,
            "control": {} if ctl is None else _summarise(ctl, mask, count)}


# the gaps read: the widest gap catches a single wrong token; the mean
# separates a lower precision, whose near-ties flip far more often
GAPS = ("max_logit_gap", "mean_logit_gap")


def _summarise(gap, mask, count) -> Dict[str, float]:
    import jax.numpy as jnp
    out = {"max_logit_gap": float(jnp.max(gap)),
           "mean_logit_gap": float(jnp.sum(jnp.where(mask, gap, 0.0)))
           / max(count, 1)}
    return {k: v if v == v else float("inf") for k, v in out.items()}


class _Frozen(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def run(ctx: RunContext) -> Outcome:
    settings = ctx.cell.settings
    engine, params = _build(ctx)
    loop = _Loop(ctx, engine)
    marks = _window(ctx, loop)
    setup_s = marks["t0"] - ctx.t_start
    t0, end, deadline = marks["t0"], marks["end"], marks["deadline"]

    # wait for the first token of every request due in the window
    due = [t for t in loop.tracks if t.due < deadline]
    drain_until = end + float(settings["drain_s"])
    while any(not t.stamps for t in due) and loop.busy() and \
            time.perf_counter() < drain_until:
        loop.step()
    drained = time.perf_counter() - end
    memory = hw.memory_peak_bytes(ctx.devices)
    failed = sum(1 for t in due if not t.stamps)

    window = end - t0
    # tokens/s: output tokens the host saw by the deadline over the window's
    # set length, so that where the deadline falls in the last engine call
    # (a decode step or a long prefill) does not move the rate
    tokens = sum(sum(1 for s in t.stamps if s <= deadline)
                 for t in loop.tracks)
    ttft = [t.stamps[0] - t.due if t.stamps else float("inf") for t in due]
    itl = [b - a for t in loop.tracks
           for a, b in zip(t.stamps, t.stamps[1:]) if b <= end]
    e2e = {"tokens_per_s": rate(tokens, ctx.seconds),
           "ttft_p95_ms": percentile(ttft, 95) * 1e3 if ttft else float("inf"),
           "itl_p95_ms": percentile(itl, 95) * 1e3 if itl else float("inf")}
    ctx.log(f"[serve] window={window:.3f}s due={len(due)} "
            f"submitted={len(loop.tracks)} finished="
            f"{sum(1 for t in loop.tracks if t.obj.done)} tokens={tokens} "
            f"failed={failed} queued_at_close={marks['queued']} "
            f"drain={drained:.3f}s prefill_calls={engine.prefill_calls} "
            f"host_transfers={engine.host_transfers}")
    lag = [t.sent - t.due for t in loop.tracks]
    if lag:
        ctx.log(f"[serve] generator lateness: max "
                f"{max(lag) * 1e3:.3f} ms, p95 {percentile(lag, 95) * 1e3:.3f}"
                " ms")

    # queue time of each request due in the window: from when it was due
    # (not when the client got round to submitting it) to its admission
    readings = {"queue_wait_s": [t.obj.t_admit - t.due for t in due
                                 if t.obj.t_admit is not None]}
    if "trace_start" in marks:
        a, b = marks["trace_start"], marks["trace_end"]
        out_tokens = sum(1 for t in loop.tracks for s in t.stamps
                         if a <= s <= b)
        prompt = sum(len(t.spec.prompt) - 1 for t in loop.tracks
                     if t.stamps and a <= t.stamps[0] <= b)
        readings["traced_tokens"] = {"prompt": prompt, "output": out_tokens}

    # the program's state goes before the reference runs
    loop.engine = loop.live = None
    del engine, params
    gc.collect()
    result = _reference_check(ctx, loop)
    numbers = result["numbers"]
    ctx.log(f"[check] compared {result['tokens']} served tokens; gaps: "
            + ", ".join(f"{k} {v!r}" for k, v in numbers.items()))
    # the cell's settings name the numbers compared, each with its limit
    check = {name: {"value": numbers[name], "limit": float(limit)}
             for name, limit in settings["limits"].items()}
    controls = {name: v for name, v in result["control"].items()
                if name in check}
    return Outcome(attempted=len(due), failed=failed, setup_s=setup_s,
                   end_to_end=e2e, check=check, memory_peak_bytes=memory,
                   readings=readings, controls=controls)
