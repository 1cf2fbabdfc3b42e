"""Serving engine: single-dispatch batched prefill + donated decode loop.

A slot-based continuous-batching scheduler rebuilt for throughput.  The
engine owns ``max_batch`` slots, each slot one lane of the KV/state cache:

* **Prefill** bulk-writes a prompt's KV/state into its slot's cache lanes
  via :meth:`repro.models.model.Model.prefill` — a ``lax.scan`` over the
  decode step inside one jitted call, so a chunk of ``prefill_chunk``
  prompt tokens costs **one** device dispatch instead of one per token.
  All newly admitted slots prefill *together* (per-lane write masks let
  lanes with different prompt lengths share the scan), so a burst of
  admissions pays ``ceil(max(prompt_len) / chunk)`` dispatches rather
  than ``sum(prompt_len)``.  Per-lane results are bit-identical to the
  per-token replay path (:class:`repro.serve.reference.ReferenceEngine`),
  proven by ``tests/test_serve_prefill.py``.
* **Decode** is a fused jitted step over device-resident state: tokens,
  positions, per-slot active flags, remaining-token budgets, and the
  sampling PRNG key all live on device; sampling (argmax, or categorical
  at ``temperature > 0``) happens inside the step; the cache and the
  token-state pytree are donated (``donate_argnums``), so steady-state
  decode allocates no second cache copy and performs **at most one small
  host transfer per step** — the (B, 2) [token, finish-code] row.  With
  no listeners registered those rows are harvested in batches of
  ``harvest_every`` steps, letting dispatch run ahead asynchronously.
* **Admission** pops a :class:`collections.deque` under a
  ``max_prefill_tokens``-per-step budget, so one long prompt cannot
  starve active decoders: prefill yields to decode between chunks.

Inactive/prefilling lanes ride decode and prefill dispatches as padding
work but are *lane-masked out* of every cache merge, so their
recurrent/SSM state never advances on padding steps — the seed engine's
cross-request state pollution (see ``reference.py``) is gone, and a
freed lane is zeroed before its next tenant prefills.

Online-tuning hooks (see ``repro.tuning.online``) are unchanged from the
pre-rework engine: an injectable ``step_timer``, per-step
:class:`StepRecord` reports to listeners (a timed engine harvests every
step so the duration covers real device work), and an override-provider
whose config fragments select a per-fragment jitted variant — now held
in an LRU-capped table (``max_variants``) with the baseline and the
live variant pinned.  With no listeners the loop takes the exact
pre-hook path — an untimed engine pays nothing for the hooks.

Operators read the engine through :meth:`ServeEngine.stats` (counters,
decode and prefill occupancy, queue wait) and, under a profiler trace,
through four spans that do not overlap: ``serve.admit`` (queue pop,
lane reset, lane activation), ``serve.prefill`` (one per chunk
dispatch), ``serve.step`` (one per decode dispatch) and
``serve.harvest`` (the blocking fetch and its row loop).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Callable, Deque, Dict, List, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.model import Model
from repro.tuning.overrides import overrides as _tuning_overrides

PyTree = Any

FINISH_STOP = "stop"        # produced max_new_tokens naturally
FINISH_LENGTH = "length"    # truncated at the cache ceiling (max_len - 1)
# device-side finish codes in the harvested (B, 2) row; 0 = still going.
# "stop" wins when a request hits both bounds on the same token.
_FINISH_REASONS = {1: FINISH_STOP, 2: FINISH_LENGTH}


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (len,) int32
    max_new_tokens: int = 16
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: Optional[str] = None   # "stop" | "length" once done
    # ServeEngine.stamp_clock stamps: submitted, popped off the queue
    # into a lane, first output token seen by the host
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None


@dataclasses.dataclass
class EngineStats:
    """The engine's counters, all monotone over its life."""

    prefill_calls: int = 0        # prefill device dispatches
    host_transfers: int = 0       # device->host reads (via _fetch)
    decode_steps: int = 0         # decode device dispatches
    lane_steps: int = 0           # sum over harvested decode steps of the
    #                               lanes that emitted a token
    prefill_positions: int = 0    # sum of dispatched prefill chunk lengths
    prefill_writes: int = 0       # prompt tokens written by prefill
    # t_admit - t_submit of every admitted request that went through submit
    queue_waits: List[float] = dataclasses.field(default_factory=list)

    def snapshot(self, max_batch: int) -> Dict[str, float]:
        """The counters, with decode occupancy (``lane_steps`` over
        ``decode_steps * max_batch``; exact once ``run`` has returned, as
        it harvests every dispatched step), prefill occupancy (prompt
        tokens written over ``prefill_positions * max_batch``; the rest
        is padding) and the p50 / p95 queue wait in seconds."""
        out: Dict[str, float] = {
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self) if f.name != "queue_waits"}
        out["decode_occupancy"] = self.lane_steps / max(
            self.decode_steps * max_batch, 1)
        out["prefill_occupancy"] = self.prefill_writes / max(
            self.prefill_positions * max_batch, 1)
        waits = self.queue_waits
        out["admitted"] = len(waits)
        out["queue_wait_p50_s"] = float(np.percentile(waits, 50)) \
            if waits else 0.0
        out["queue_wait_p95_s"] = float(np.percentile(waits, 95)) \
            if waits else 0.0
        return out


@dataclasses.dataclass(frozen=True)
class StepRecord:
    """One timed decode step, as reported to step listeners."""

    index: int          # monotonically increasing decode-step counter
    duration_s: float   # wall-clock (or fake-clock) duration of the step
    active: int         # slots that were occupied during the step


def _lane_where(mask: jnp.ndarray, new: jnp.ndarray,
                old: jnp.ndarray) -> jnp.ndarray:
    """Per-lane select on a cache leaf; batch is axis 1 of every leaf."""
    return jnp.where(mask.reshape((1, -1) + (1,) * (new.ndim - 2)), new, old)


def _build_step_fn(model: Model, temperature: float, max_len: int):
    """Fused decode step: decode + sample + bookkeeping, one dispatch.

    Takes and returns the full device state; the cache and state pytrees
    are donated, so XLA updates them in place.  Emits a small (B, 2)
    int32 row — [sampled token or -1, finish code] — the only thing the
    host ever needs to read back.
    """
    def step(params, cache, state):
        tokens, pos, active = state["tokens"], state["pos"], state["active"]
        logits, new_cache = model.decode_step(params, tokens, cache, pos)
        row = logits.reshape((tokens.shape[0], -1))
        if temperature > 0.0:
            key, sub = jax.random.split(state["key"])
            nxt = jax.random.categorical(
                sub, row.astype(jnp.float32) / temperature, axis=-1)
        else:
            key = state["key"]
            nxt = jnp.argmax(row, axis=-1)
        nxt = nxt.astype(jnp.int32)
        act = active.astype(jnp.int32)
        emitted = jnp.where(active, nxt, -1)
        new_tokens = jnp.where(active, nxt, tokens[:, 0])[:, None]
        new_pos = pos + act[:, None]
        remaining = state["remaining"] - act
        hit_stop = remaining <= 0
        hit_len = new_pos[:, 0] >= max_len - 1
        finished = active & (hit_stop | hit_len)
        codes = jnp.where(finished,
                          jnp.where(hit_stop, 1, 2), 0).astype(jnp.int32)
        out = jnp.stack([emitted, codes], axis=-1)
        # inactive lanes keep their cache/state bit-exactly: padding
        # compute never pollutes a parked or prefilling tenant
        merged = jax.tree.map(
            lambda n, o: _lane_where(active, n, o), new_cache, cache)
        new_state = {"tokens": new_tokens, "pos": new_pos,
                     "active": active & ~finished,
                     "remaining": remaining, "key": key}
        return merged, new_state, out
    return jax.jit(step, donate_argnums=(1, 2))


def _build_prefill_fn(model: Model):
    """Jitted chunk prefill; retraces per chunk length (bounded: chunk
    lengths are powers of two capped at ``prefill_chunk``)."""
    def prefill(params, cache, toks, poss, writes):
        return model.prefill(params, toks, cache, poss, writes)
    return jax.jit(prefill, donate_argnums=(1,))


def _build_lane_reset_fn():
    # the template is a batch-1 init cache: its (n_groups, 1, ...) leaves
    # broadcast against the engine's (n_groups, B, ...) lanes, restoring
    # each reset lane to its *init* value (not zero — ring-buffer caches
    # init their position leaf to a "never written" sentinel)
    def reset(cache, template, mask):
        return jax.tree.map(
            lambda leaf, init: _lane_where(mask, init, leaf),
            cache, template)
    return jax.jit(reset, donate_argnums=(0,))


def _build_activate_fn():
    # full-batch masked update (not a gather by slot index): one traced
    # shape regardless of how many lanes activate together, so a server
    # never recompiles on a new admission-group size
    def activate(state, mask, tok, pos, rem):
        return {"tokens": jnp.where(mask[:, None], tok[:, None],
                                    state["tokens"]),
                "pos": jnp.where(mask[:, None], pos[:, None], state["pos"]),
                "active": state["active"] | mask,
                "remaining": jnp.where(mask, rem, state["remaining"]),
                "key": state["key"]}
    return jax.jit(activate, donate_argnums=(0,))


class _DecodeVariant:
    """The jitted step/prefill pair traced under one override fragment.

    Decode is jitted, so kernel configs resolved at TRACE time are baked
    into the compiled executable — an overrides() frame around later
    calls cannot reach it.  Each distinct override fragment therefore
    gets its own variant, re-traced (and its config re-resolved) under
    that frame on first call; revisits are cache hits.
    """

    __slots__ = ("step", "prefill")

    def __init__(self, model: Model, temperature: float, max_len: int):
        self.step = _build_step_fn(model, temperature, max_len)
        self.prefill = _build_prefill_fn(model)


def _pow2_chunk(need: int, cap: int) -> int:
    """Smallest power-of-two scan length covering ``need``, capped.

    Quantizing chunk lengths bounds jit retraces to log2(cap) shapes
    while wasting < 2x padding steps on the final partial chunk.
    """
    c = 1
    while c < need and c < cap:
        c *= 2
    return min(c, cap)


class ServeEngine:
    def __init__(self, model: Model, params: PyTree, max_batch: int = 8,
                 max_len: int = 512, temperature: float = 0.0, seed: int = 0,
                 step_timer: Optional[Callable[[], float]] = None,
                 prefill_chunk: int = 32, harvest_every: int = 4,
                 max_prefill_tokens: Optional[int] = None,
                 admit_threshold: int = 1, max_variants: int = 8,
                 cache_dtype=jnp.float32):
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if harvest_every < 1:
            raise ValueError(f"harvest_every must be >= 1, got {harvest_every}")
        if not 1 <= admit_threshold <= max_batch:
            raise ValueError(f"admit_threshold must be in [1, {max_batch}], "
                             f"got {admit_threshold}")
        if max_variants < 2:
            # must at least hold the pinned baseline + one live variant
            raise ValueError(f"max_variants must be >= 2, got {max_variants}")
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.temperature = temperature
        self.prefill_chunk = prefill_chunk
        self.harvest_every = harvest_every
        self.max_prefill_tokens = max_prefill_tokens
        # throughput knob: hold admissions until this many slots are free,
        # so co-admitted prompts share prefill scans (1 = admit eagerly,
        # latency-first; the serving benchmark raises it to batch prefill)
        self.admit_threshold = admit_threshold
        self.max_variants = max_variants
        self.cache_dtype = cache_dtype
        self.cache = model.init_cache(max_batch, max_len, dtype=cache_dtype)
        self._cache_template = model.init_cache(1, max_len, dtype=cache_dtype)
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.slot_pos = np.zeros(max_batch, np.int32)
        # device-resident token state (donated through every decode step)
        self._state: Dict[str, jax.Array] = {
            "tokens": jnp.zeros((max_batch, 1), jnp.int32),
            "pos": jnp.zeros((max_batch, 1), jnp.int32),
            "active": jnp.zeros((max_batch,), bool),
            "remaining": jnp.zeros((max_batch,), jnp.int32),
            "key": jax.random.PRNGKey(seed),
        }
        self._lane_reset = _build_lane_reset_fn()
        self._activate_lanes = _build_activate_fn()
        self._decode_variants: "collections.OrderedDict[object, _DecodeVariant]" \
            = collections.OrderedDict()
        self._active_overrides: Optional[Dict] = None
        self._active_key: object = None
        self._decode = self._get_variant(None)
        self.queue: Deque[Request] = collections.deque()
        self.completed: List[Request] = []
        # slot -> prompt tokens already written (mid-prefill slots)
        self._prefilling: Dict[int, int] = {}
        self._pending_out: List[jax.Array] = []
        self.counters = EngineStats()
        # clock of the Request stamps and queue waits: the one the serve
        # driver stamps tokens with.  Not step_timer, which an untimed
        # engine never reads; tests replace it to fake time.
        self.stamp_clock: Callable[[], float] = time.perf_counter
        obs.watch_compiles()
        # -- step hooks (timing is only paid when a listener is registered)
        self.step_timer: Callable[[], float] = step_timer or time.perf_counter
        self._step_listeners: List[Callable[[StepRecord], None]] = []
        self._override_provider: Optional[
            Callable[[], Optional[Mapping[str, Mapping[str, int]]]]] = None
        self._step_index = 0

    @property
    def prefill_calls(self) -> int:
        return self.counters.prefill_calls

    @property
    def host_transfers(self) -> int:
        return self.counters.host_transfers

    def stats(self) -> Dict[str, float]:
        """A snapshot of the counters and the ratios derived from them
        (:meth:`EngineStats.snapshot`)."""
        return self.counters.snapshot(self.max_batch)

    def add_step_listener(self, fn: Callable[[StepRecord], None]) -> None:
        """Register a callback invoked after every timed decode step."""
        self._step_listeners.append(fn)

    def set_override_provider(
            self, fn: Optional[
                Callable[[], Optional[Mapping[str, Mapping[str, int]]]]],
    ) -> None:
        """Install a provider of per-op config overrides, consulted before
        each step and applied (via the thread-local override stack) around
        it — how an online tuner's active trial reaches the kernels."""
        self._override_provider = fn

    # -- public API --
    def warmup(self) -> None:
        """Pre-trace the active variant's decode step, every prefill chunk
        shape, and the admission helpers, so a live server (or a timed
        benchmark) never pays a jit compile mid-traffic.  Runs against
        throwaway buffers — engine state, caches, and the sampling PRNG
        stream are untouched."""
        cache = self.model.init_cache(self.max_batch, self.max_len,
                                      dtype=self.cache_dtype)
        state = {
            "tokens": jnp.zeros((self.max_batch, 1), jnp.int32),
            "pos": jnp.zeros((self.max_batch, 1), jnp.int32),
            "active": jnp.zeros((self.max_batch,), bool),
            "remaining": jnp.zeros((self.max_batch,), jnp.int32),
            "key": jax.random.PRNGKey(0),
        }
        cache, state, out = self._decode.step(self.params, cache, state)
        c = 1
        while True:
            toks = jnp.zeros((c, self.max_batch), jnp.int32)
            writes = jnp.zeros((c, self.max_batch), bool)
            cache = self._decode.prefill(self.params, cache, toks, toks,
                                         writes)
            if c >= self.prefill_chunk:
                break
            c = min(c * 2, self.prefill_chunk)
        mask = jnp.zeros((self.max_batch,), bool)
        zeros = jnp.zeros((self.max_batch,), jnp.int32)
        state = self._activate_lanes(state, mask, zeros, zeros, zeros)
        cache = self._lane_reset(cache, self._cache_template, mask)
        jax.block_until_ready((cache, state, out))

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> int:
        prompt = np.asarray(prompt, np.int32)
        if prompt.size == 0:
            # an empty prompt has no last token to decode from; reject at
            # the door instead of poisoning the batch
            raise ValueError("empty prompt: need at least one token")
        rid = len(self.queue) + len(self.completed) + sum(
            r is not None for r in self.slot_req)
        self.queue.append(Request(rid, prompt, max_new_tokens,
                                  t_submit=self.stamp_clock()))
        return rid

    def run(self, max_steps: int = 1000) -> List[Request]:
        """Serve until the queue drains (or ``max_steps``).

        Returns completed requests in **submission order** (ascending
        ``rid``) — a stable contract that deterministic consumers (trace
        replay, batched clients zipping prompts with results) rely on.
        ``self.completed`` retains completion order for schedulers that
        care about finishing sequence.
        """
        steps = 0
        while (self.queue or any(self.slot_req)) and steps < max_steps:
            ov = self._override_provider() if self._override_provider else None
            if ov != self._active_overrides:
                self._select_decode_variant(ov)
            ctx = _tuning_overrides(**ov) if ov else contextlib.nullcontext()
            with ctx:
                self._admit()
                active = sum(r is not None for r in self.slot_req)
                if self._step_listeners and active:
                    # timed mode: harvest inside the timed window so the
                    # duration covers the device step (not just its async
                    # dispatch) — exactly two timer reads per step
                    t0 = self.step_timer()
                    self._dispatch_step()
                    self._harvest()
                    record = StepRecord(self._step_index,
                                        self.step_timer() - t0, active)
                    for listener in self._step_listeners:
                        listener(record)
                else:
                    self._dispatch_step()
                    if len(self._pending_out) >= self.harvest_every:
                        self._harvest()
            self._step_index += 1
            steps += 1
        self._harvest()
        return sorted(self.completed, key=lambda r: r.rid)

    # -- internals --
    def _get_variant(self, key: object) -> _DecodeVariant:
        variant = self._decode_variants.get(key)
        if variant is None:
            variant = _DecodeVariant(self.model, self.temperature,
                                     self.max_len)
            self._decode_variants[key] = variant
        self._decode_variants.move_to_end(key)
        return variant

    def _select_decode_variant(self, ov: Optional[Dict]) -> None:
        """Switch to (or build) the jitted variant traced under ``ov``.

        First use of a config pays one re-trace/compile — landing inside
        that trial's first timed step, which the online tuner's
        first-sample baseline discard absorbs; returning to a previously
        seen config (the incumbent after a rollback) is a dict hit.  The
        table is LRU-capped at ``max_variants``: the baseline (``None``)
        and the variant being selected are pinned, the least recently
        used of the rest is evicted.
        """
        self._active_overrides = None if ov is None \
            else {op: dict(frag) for op, frag in ov.items()}
        key = None if ov is None else tuple(
            (op, tuple(sorted(frag.items())))
            for op, frag in sorted(ov.items()))
        self._decode = self._get_variant(key)
        self._active_key = key
        while len(self._decode_variants) > self.max_variants:
            victim = next((k for k in self._decode_variants
                           if k is not None and k != key), None)
            if victim is None:
                break
            del self._decode_variants[victim]

    def _fetch(self, x: jax.Array) -> np.ndarray:
        """The one device->host chokepoint (counted; fake-able in tests)."""
        self.counters.host_transfers += 1
        return np.asarray(x)

    # -- harvest: drain emitted tokens back to host ------------------------

    def _harvest(self) -> None:
        if not self._pending_out:
            return
        with obs.span("serve.harvest"):
            outs, self._pending_out = self._pending_out, []
            rows = self._fetch(jnp.stack(outs))   # (k, B, 2), ONE transfer
            seen = self.stamp_clock()
            for row in rows:
                for s, req in enumerate(self.slot_req):
                    if req is None:
                        continue
                    tok, code = int(row[s, 0]), int(row[s, 1])
                    if tok < 0:
                        continue    # lane was prefilling / already finished
                    self.counters.lane_steps += 1
                    if not req.output:
                        req.t_first = seen
                    req.output.append(tok)
                    self.slot_pos[s] += 1
                    if code:
                        req.done = True
                        req.finish_reason = _FINISH_REASONS[code]
                        self.completed.append(req)
                        self.slot_req[s] = None

    # -- admission + prefill ----------------------------------------------

    def _admit(self) -> None:
        if self.queue and self._pending_out and (
                len(self._pending_out) >= self.harvest_every
                or not self._any_decoding()):
            # a backlog is waiting on freed slots: sync the host view.
            # (Prefill itself tolerates stale mirrors — padding lanes are
            # write-masked — so no other path forces an early harvest.)
            self._harvest()
        free = [s for s in range(self.max_batch) if self.slot_req[s] is None]
        busy = self.max_batch - len(free)
        # hold admissions until a worthwhile prefill group has formed;
        # with nothing in flight there is no reason (or way) to wait
        want = min(self.admit_threshold, len(self.queue)) if busy else 1
        if not self.queue or len(free) < want:
            self._run_prefill()
            return
        with obs.span("serve.admit"):
            self._pop_into(free)
        self._run_prefill()

    def _pop_into(self, free: List[int]) -> None:
        """Move queued requests into the free slots and reset those lanes."""
        newly: List[int] = []
        now = self.stamp_clock()
        for slot in free:
            while self.queue:
                req = self.queue.popleft()
                if np.asarray(req.prompt).size == 0:
                    # hand-built Request bypassing submit(): complete it
                    # empty rather than poisoning the whole batch
                    req.done = True
                    req.finish_reason = FINISH_STOP
                    self.completed.append(req)
                    continue
                req.t_admit = now
                if req.t_submit is not None:
                    self.counters.queue_waits.append(now - req.t_submit)
                self.slot_req[slot] = req
                self.slot_pos[slot] = 0
                self._prefilling[slot] = 0
                newly.append(slot)
                break
        if newly:
            # evict the previous tenant's state from the reused lanes in
            # one dispatch (stale KV is position-masked anyway, but
            # SSM/recurrent state is not position-indexed)
            mask = np.zeros(self.max_batch, bool)
            mask[newly] = True
            self.cache = self._lane_reset(self.cache, self._cache_template,
                                          jnp.asarray(mask))

    def _run_prefill(self) -> None:
        """Advance all mid-prefill slots, chunked and budgeted.

        Every pending slot shares each scan (per-lane write masks), so a
        burst of admissions costs ceil(max(prompt_len)/chunk) dispatches,
        not sum(prompt_len).  At most ``max_prefill_tokens`` prompt
        tokens are written per engine step (always at least one chunk, so
        long prompts keep making progress), then control returns to the
        decode loop — active slots never starve behind a long prompt.
        """
        budget = self.max_prefill_tokens
        spent = 0
        while self._prefilling:
            ready = [s for s, filled in self._prefilling.items()
                     if filled >= len(self.slot_req[s].prompt) - 1]
            if ready:
                with obs.span("serve.admit"):
                    self._activate_slots(ready)
            if not self._prefilling:
                break
            if budget is not None and spent >= budget:
                break
            with obs.span("serve.prefill"):
                spent += self._prefill_chunk()

    def _prefill_chunk(self) -> int:
        """Dispatch one prefill chunk for every mid-prefill slot; returns
        the prompt tokens it writes."""
        need = {s: len(self.slot_req[s].prompt) - 1 - filled
                for s, filled in self._prefilling.items()}
        c = _pow2_chunk(max(need.values()), self.prefill_chunk)
        toks = np.zeros((c, self.max_batch), np.int32)
        poss = np.tile(np.maximum(self.slot_pos, 0).astype(np.int32),
                       (c, 1))
        writes = np.zeros((c, self.max_batch), bool)
        written = 0
        for s, n in need.items():
            filled = self._prefilling[s]
            take = min(c, n)
            prompt = self.slot_req[s].prompt
            idx = np.arange(take)
            toks[idx, s] = prompt[filled:filled + take]
            poss[idx, s] = filled + idx
            if take < c:
                # masked tail steps: hold a valid position, write=False
                poss[take:, s] = max(filled + take - 1, 0)
            writes[:take, s] = True
            self._prefilling[s] = filled + take
            written += take
        self.cache = self._decode.prefill(
            self.params, self.cache, jnp.asarray(toks),
            jnp.asarray(poss), jnp.asarray(writes))
        self.counters.prefill_calls += 1
        self.counters.prefill_positions += c
        self.counters.prefill_writes += written
        return written

    def _activate_slots(self, slots: List[int]) -> None:
        """Prompts fully written: arm the lanes to decode from their last
        token, one device update for the whole group (a 1-token prompt
        activates with no prefill at all)."""
        mask = np.zeros(self.max_batch, bool)
        toks = np.zeros(self.max_batch, np.int32)
        poss = np.zeros(self.max_batch, np.int32)
        rems = np.zeros(self.max_batch, np.int32)
        for slot in slots:
            req = self.slot_req[slot]
            plen = len(req.prompt)
            self.slot_pos[slot] = plen - 1
            del self._prefilling[slot]
            mask[slot] = True
            toks[slot] = int(req.prompt[-1])
            poss[slot] = plen - 1
            rems[slot] = req.max_new_tokens
        self._state = self._activate_lanes(
            self._state, jnp.asarray(mask), jnp.asarray(toks),
            jnp.asarray(poss), jnp.asarray(rems))

    # -- decode ------------------------------------------------------------

    def _any_decoding(self) -> bool:
        return any(r is not None and s not in self._prefilling
                   for s, r in enumerate(self.slot_req))

    def _dispatch_step(self) -> None:
        if not self._any_decoding():
            return
        with obs.span("serve.step"):
            self.cache, self._state, out = self._decode.step(
                self.params, self.cache, self._state)
            self._pending_out.append(out)
        self.counters.decode_steps += 1
