"""Shared neural-net layers (pure functional JAX; params are dict pytrees).

Initializers return (params, ...) dicts; apply functions are pure. Sharding
is attached externally by repro.distributed.sharding from parameter paths.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    # keep the full-tensor math in the input dtype: an upfront
    # x.astype(f32) gives XLA a full-width convert it will hoist ABOVE the
    # upstream TP all-reduce, doubling every residual all-reduce to f32
    # (measured on granite-34b: 2x collective bytes). Only the variance
    # reduction runs in f32 (fused, never materialized).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * (1.0 + scale.astype(x.dtype))


def init_dense(key, d_in: int, d_out: int, dtype, bias: bool = False,
               scale: Optional[float] = None) -> Dict:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32)
               * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Dict, x: jax.Array, compute_dtype=None) -> jax.Array:
    w = p["w"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
        x = x.astype(compute_dtype)
    y = x @ w
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def _act(name: str, x: jax.Array) -> jax.Array:
    if name in ("swiglu", "silu"):
        return jax.nn.silu(x)
    if name in ("geglu", "gelu"):
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


def init_mlp(key, d_model: int, d_ff: int, activation: str, dtype) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": init_dense(k1, d_model, d_ff, dtype),       # gate proj
        "wu": init_dense(k2, d_model, d_ff, dtype),       # up proj
        "wo": init_dense(k3, d_ff, d_model, dtype),
    }


def mlp(p: Dict, x: jax.Array, activation: str, compute_dtype) -> jax.Array:
    g = _act(activation, dense(p["wi"], x, compute_dtype))
    u = dense(p["wu"], x, compute_dtype)
    return dense(p["wo"], g * u, compute_dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (B, L, H, D); positions: (B, L) int."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (B, L, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def init_embedding(key, vocab: int, d_model: int, dtype) -> Dict:
    return {"table": (jax.random.normal(key, (vocab, d_model), jnp.float32)
                      * (1.0 / math.sqrt(d_model))).astype(dtype)}


def embed(p: Dict, tokens: jax.Array, compute_dtype,
          one_hot: bool = False) -> jax.Array:
    if one_hot:
        # distributed path: the gather's backward is a scatter-add that the
        # SPMD partitioner replicates to a full (V, D) per device; a one-hot
        # einsum keeps both forward and backward partitioned (vocab stays on
        # "model"), and XLA fuses the iota-compare into the matmul.
        v = p["table"].shape[0]
        oh = jax.nn.one_hot(tokens, v, dtype=compute_dtype)
        return jnp.einsum("blv,vd->bld", oh,
                          p["table"].astype(compute_dtype))
    return p["table"].astype(compute_dtype)[tokens]


def unembed(p: Dict, x: jax.Array, softcap: float = 0.0) -> jax.Array:
    logits = jnp.einsum("bld,vd->blv", x.astype(jnp.float32),
                        p["table"].astype(jnp.float32))
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


def causal_conv1d(x: jax.Array, w: jax.Array, cache: Optional[jax.Array] = None,
                  bias: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. x: (B, L, D); w: (K, D); bias: (D,) or None.

    Returns (y, new_cache) with cache = last K-1 inputs (for decode)."""
    K = w.shape[0]
    if cache is None:
        ctx = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        ctx = jnp.concatenate([cache.astype(x.dtype), x], axis=1)
    y = sum(ctx[:, i:i + x.shape[1], :] * w[i][None, None, :]
            for i in range(K))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    new_cache = ctx[:, -(K - 1):, :] if K > 1 else ctx[:, :0, :]
    return y.astype(x.dtype), new_cache
