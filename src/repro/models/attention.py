"""Attention blocks: GQA/MQA self-attention (+RoPE, local windows, KV cache)
and cross-attention (enc-dec, VLM)."""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.attention.ops import attention as attention_op
from repro.models.layers import dense, init_dense, rope


def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False) -> Dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "wq": init_dense(kq, d, cfg.n_heads * hd, dtype, bias=cfg.qkv_bias),
        "wk": init_dense(kk, d, cfg.n_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wv": init_dense(kv, d, cfg.n_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wo": init_dense(ko, cfg.n_heads * hd, d, dtype),
    }


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    b, l, _ = x.shape
    return x.reshape(b, l, n, hd)


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return x
    b, l, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, l, h, n_rep, d)
                            ).reshape(b, l, h * n_rep, d)


def _score_constraint(h: int, lq: int, model_axis: int) -> Optional[P]:
    """Sharding for the (B, H, Lq, Lk) score tensor — the largest activation
    in every attention cell. Prefer head (TP) sharding; archs whose head
    count doesn't divide the model axis (gemma-2b: 8, minitron: 24,
    whisper: 20) fall back to query-sequence sharding (context-parallel
    style), which is always divisible for the assigned shapes.

    Non-constrained dims stay UNCONSTRAINED so the batch sharding keeps
    propagating (a None here would *replicate* the batch dim — a hard
    constraint, measured as a 16x memory blow-up)."""
    if not model_axis:
        return None
    U = P.UNCONSTRAINED
    if h % model_axis == 0:
        return P(U, "model", U, U)
    if lq % model_axis == 0:
        return P(U, U, "model", U)
    return None


def softmax_scale(cfg: ModelConfig, head_dim: int):
    """The configured softmax scale, else 1/sqrt(head_dim)."""
    if cfg.attn_scale:
        return jnp.asarray(cfg.attn_scale, jnp.float32)
    return 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))


def _attention_core(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, window: Optional[int], compute_dtype,
                    model_axis: int, q_offset, scale=None) -> jax.Array:
    """One (B, Lq, H, D) x (B, Lk, H, D) attention tile; q_offset is the
    global position of q[0] minus kpos[0] (supports q-chunking)."""
    bq, lq, h, d = q.shape
    lk = k.shape[1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    cons = _score_constraint(h, lq, model_axis)
    if cons is not None:
        s = jax.lax.with_sharding_constraint(s, cons)
    qpos = jnp.arange(lq) + q_offset
    kpos = jnp.arange(lk)
    mask = jnp.ones((lq, lk), bool)
    if causal:
        mask = mask & (qpos[:, None] >= kpos[None, :])
    if window is not None:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(compute_dtype)
    if cons is not None:
        p = jax.lax.with_sharding_constraint(p, cons)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# chunk the query dim once the full (Lq, Lk) score tensor would exceed this
# many elements per (batch, head) — softmax is per-q-row, so q-chunking is
# EXACT (flash-attention's insight, realized with lax.scan + remat in XLA)
_SCORE_ELEMS_LIMIT = 4096 * 4096
_Q_CHUNK = 1024


def _attention_4d(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool, window: Optional[int],
                  compute_dtype, model_axis: int = 0,
                  scale=None) -> jax.Array:
    """XLA-path attention keeping (B, L, H, D) layout end-to-end.

    Never merges the data-sharded batch dim with the model-sharded head dim
    (a (B*H, ...) reshape defeats GSPMD propagation and replicates the
    (S, S) score tensors — measured 500+ GiB/device on train_4k cells).
    Long sequences scan over q-chunks so only a (chunk, Lk) score block is
    ever live; each chunk is rematted in the backward pass.
    """
    bq, lq, h, d = q.shape
    lk = k.shape[1]
    base_offset = lk - lq
    if lq * lk <= _SCORE_ELEMS_LIMIT or lq % _Q_CHUNK or lq == lk == 0:
        return _attention_core(q, k, v, causal=causal, window=window,
                               compute_dtype=compute_dtype,
                               model_axis=model_axis, q_offset=base_offset,
                               scale=scale)

    nc = lq // _Q_CHUNK
    qr = jnp.moveaxis(q.reshape(bq, nc, _Q_CHUNK, h, d), 1, 0)

    def body(_, xs):
        idx, qb = xs

        def run(qb):
            return _attention_core(
                qb, k, v, causal=causal, window=window,
                compute_dtype=compute_dtype, model_axis=model_axis,
                q_offset=idx * _Q_CHUNK + base_offset, scale=scale)

        return None, jax.checkpoint(run)(qb)

    _, ob = jax.lax.scan(body, None, (jnp.arange(nc), qr))
    return jnp.moveaxis(ob, 0, 1).reshape(bq, lq, h, d)


def self_attention(p: Dict, x: jax.Array, cfg: ModelConfig, *,
                   positions: jax.Array,
                   cache: Optional[Dict] = None,
                   window: Optional[int] = None,
                   compute_dtype=jnp.bfloat16
                   ) -> Tuple[jax.Array, Optional[Dict]]:
    """x: (B, L, D).

    cache layouts:
      full:   {"k","v": (B, L_max, Hkv, hd)} — slot index == position;
      window: additionally {"pos": (B, W) int32} — ring buffer of W slots
              holding the absolute position written into each slot.
    Training/prefill: cache None (pure forward). Decode: L == 1; the cache
    is updated at `positions` and attention masks by true positions, so
    uninitialized slots never reach the softmax.
    """
    b, l, _ = x.shape
    hd, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = _split_heads(dense(p["wq"], x, compute_dtype), hq, hd)
    k = _split_heads(dense(p["wk"], x, compute_dtype), hkv, hd)
    v = _split_heads(dense(p["wv"], x, compute_dtype), hkv, hd)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    n_rep = hq // max(hkv, 1)
    scale = softmax_scale(cfg, hd)

    if cache is not None and l == 1:
        pos = positions[:, 0]                                    # (B,)
        cache_len = cache["k"].shape[1]
        barange = jnp.arange(b)
        if "pos" in cache:                                       # ring buffer
            slot = jnp.mod(pos, cache_len)
            slot_pos = cache["pos"].at[barange, slot].set(pos)
        else:
            slot = pos
            slot_pos = jnp.arange(cache_len)[None, :] * jnp.ones(
                (b, 1), jnp.int32)
        ck = cache["k"].at[barange, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[barange, slot].set(v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}
        if "pos" in cache:
            new_cache["pos"] = slot_pos

        kk = _repeat_kv(ck.astype(compute_dtype), n_rep)         # (B,S,H,hd)
        vv = _repeat_kv(cv.astype(compute_dtype), n_rep)
        s = jnp.einsum("bhd,bshd->bhs", q[:, 0], kk).astype(jnp.float32)
        s = s * scale
        mask = slot_pos <= pos[:, None]                          # causal/valid
        if window is not None:
            mask &= slot_pos > (pos[:, None] - window)
        s = jnp.where(mask[:, None, :], s, -jnp.inf)
        pattn = jax.nn.softmax(s, axis=-1).astype(compute_dtype)
        o = jnp.einsum("bhs,bshd->bhd", pattn, vv)[:, None]      # (B,1,H,hd)
        o = o.reshape(b, l, hq * hd)
        return dense(p["wo"], o, compute_dtype), new_cache

    # training / prefill full-sequence path
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    if cfg.use_pallas:
        # real-TPU path: flash kernel over flattened rows (shard_mapped on
        # device; block sizes from the TuningDB)
        qf = q.transpose(0, 2, 1, 3).reshape(b * hq, l, hd)
        kf = k.transpose(0, 2, 1, 3).reshape(b * hq, -1, hd)
        vf = v.transpose(0, 2, 1, 3).reshape(b * hq, -1, hd)
        of = attention_op(qf, kf, vf, causal=True, window=window,
                          scale=cfg.attn_scale or None, use_pallas=True)
        o = of.reshape(b, hq, l, hd).transpose(0, 2, 1, 3)
    else:
        o = _attention_4d(q, k, v, causal=True, window=window,
                          compute_dtype=compute_dtype,
                          model_axis=cfg.model_axis_size, scale=scale)
    o = o.reshape(b, l, hq * hd)
    return dense(p["wo"], o, compute_dtype), None


def cross_attention(p: Dict, x: jax.Array, memory: jax.Array,
                    cfg: ModelConfig, compute_dtype=jnp.bfloat16) -> jax.Array:
    """x: (B, L, D) queries over encoder/vision memory (B, M, D)."""
    b, l, _ = x.shape
    m = memory.shape[1]
    hd, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = _split_heads(dense(p["wq"], x, compute_dtype), hq, hd)
    k = _split_heads(dense(p["wk"], memory, compute_dtype), hkv, hd)
    v = _split_heads(dense(p["wv"], memory, compute_dtype), hkv, hd)
    n_rep = hq // max(hkv, 1)
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    if cfg.use_pallas:
        qf = q.transpose(0, 2, 1, 3).reshape(b * hq, l, hd)
        kf = k.transpose(0, 2, 1, 3).reshape(b * hq, m, hd)
        vf = v.transpose(0, 2, 1, 3).reshape(b * hq, m, hd)
        of = attention_op(qf, kf, vf, causal=False,
                          scale=cfg.attn_scale or None, use_pallas=True)
        o = of.reshape(b, hq, l, hd).transpose(0, 2, 1, 3)
    else:
        o = _attention_4d(q, k, v, causal=False, window=None,
                          compute_dtype=compute_dtype,
                          model_axis=cfg.model_axis_size,
                          scale=softmax_scale(cfg, hd))
    o = o.reshape(b, l, hq * hd)
    return dense(p["wo"], o, compute_dtype)
