"""Mamba-2 (SSD) block — the flagship consumer of the tuned scan/SSD kernels."""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ssd.ops import ssd as ssd_op
from repro.models.layers import causal_conv1d, dense, init_dense, rms_norm


def _dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads, cfg.ssm_state


def init_ssd_block(key, cfg: ModelConfig, dtype) -> Dict:
    d_inner, n_heads, s = _dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    # fused input projection: [x (d_inner), z (d_inner), B (s), C (s), dt (H)]
    d_proj = 2 * d_inner + 2 * s + n_heads
    p = {
        "in_proj": init_dense(ks[0], d, d_proj, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_width,
                                             d_inner + 2 * s), jnp.float32)
                   * (1.0 / math.sqrt(cfg.conv_width))).astype(dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "norm_scale": jnp.zeros((d_inner,), dtype),
        "out_proj": init_dense(ks[2], d_inner, d, dtype),
    }
    if cfg.ssm_d_skip:
        p["d_skip"] = jnp.ones((n_heads,), jnp.float32)
    if cfg.ssm_conv_bias:
        p["conv_b"] = jnp.zeros((d_inner + 2 * s,), dtype)
    return p


def ssd_block(p: Dict, x: jax.Array, cfg: ModelConfig, *,
              cache: Optional[Dict] = None, compute_dtype=jnp.bfloat16
              ) -> Tuple[jax.Array, Optional[Dict]]:
    """x: (B, L, D). cache (decode): {"conv": (B,K-1,chan), "state": (B,H,S,P)}."""
    bsz, L, _ = x.shape
    d_inner, n_heads, s = _dims(cfg)
    P = cfg.ssm_head_dim

    proj = dense(p["in_proj"], x, compute_dtype)
    xz, z, bc, dt_raw = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + 2 * s], axis=-1)
    conv_in = jnp.concatenate([xz, bc], axis=-1)
    conv_out, conv_cache = causal_conv1d(
        conv_in, p["conv_w"].astype(compute_dtype),
        cache=None if cache is None else cache["conv"], bias=p.get("conv_b"))
    conv_out = jax.nn.silu(conv_out)
    xs, b_in, c_in = jnp.split(conv_out, [d_inner, d_inner + s], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])          # (B, L, H)
    a = jnp.exp(-jnp.exp(p["a_log"])[None, None, :] * dt)        # decay in (0,1)
    xh = xs.reshape(bsz, L, n_heads, P).astype(jnp.float32)
    # the state takes x * dt where the mixer discretizes its input
    x_in = xh * dt[..., None] if cfg.ssm_dt_input else xh

    if cache is None or L > 1:
        y = ssd_op(x_in, a, b_in.astype(jnp.float32),
                   c_in.astype(jnp.float32),
                   use_pallas=cfg.use_pallas or None)
        new_state = None  # prefill state capture handled by decode-from-scratch
    else:
        # O(1) decode step: h = a h + b x^T ; y = c . h
        h = cache["state"]
        x_t = x_in[:, 0]                                         # (B, H, P)
        a_t = a[:, 0]                                            # (B, H)
        b_t = b_in[:, 0].astype(jnp.float32)                     # (B, S)
        c_t = c_in[:, 0].astype(jnp.float32)
        h = (a_t[..., None, None] * h
             + jnp.einsum("bs,bhp->bhsp", b_t, x_t))
        y = jnp.einsum("bs,bhsp->bhp", c_t, h)[:, None]          # (B,1,H,P)
        new_state = h

    if "d_skip" in p:
        y = y + p["d_skip"][:, None] * xh
    y = y.reshape(bsz, L, d_inner).astype(compute_dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
    out = dense(p["out_proj"], y, compute_dtype)
    new_cache = None
    if cache is not None:
        new_cache = {"conv": conv_cache.astype(cache["conv"].dtype),
                     "state": new_state if new_state is not None
                     else cache["state"]}
    return out, new_cache


def init_ssd_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> Dict:
    d_inner, n_heads, s = _dims(cfg)
    chan = d_inner + 2 * s
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, chan), dtype),
        "state": jnp.zeros((batch, n_heads, s, cfg.ssm_head_dim), jnp.float32),
    }
