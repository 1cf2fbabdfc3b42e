"""Plain float32 reference of the served Mamba-2 language model, and the
seeded weights both it and the program are given.

Written from the Mamba-2 description (arXiv:2405.21060, the SSD layer)
as the configuration file states it, importing nothing of the program:

  x = embed[token] * sqrt(d_model)
  per layer:  h = rmsnorm(x) * (1 + ln)
              [x_in, z, B, C, dt] = h @ in_proj
              [x_in, B, C] = silu(causal depthwise conv, width d_conv)
              dt = softplus(dt + dt_bias);  a = exp(-exp(a_log) * dt)
              state_t = a_t state_(t-1) + B_t x_t^T ;  y_t = C_t . state_t
              y = rmsnorm(y * silu(z)) * (1 + norm_scale)
              x = x + y @ out_proj
  logits = rmsnorm(x) * (1 + final_norm) @ embed^T      (tied)

The state recurrence runs token by token (``lax.scan``), every matmul at
``Precision.HIGHEST``.  The control is the same forward with every
matmul's operands rounded to 8-bit floats (e4m3, one scale per tensor), the
precision below the bfloat16 that the configuration serves in.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: Dict) -> Dict[str, int]:
    d_inner = cfg["expand"] * cfg["d_model"]
    heads = d_inner // cfg["headdim"]
    return {"D": cfg["d_model"], "DI": d_inner, "H": heads,
            "S": cfg["d_state"], "P": cfg["headdim"], "K": cfg["d_conv"],
            "G": cfg["n_layer"], "V": cfg["vocab_rows"],
            "PROJ": 2 * d_inner + 2 * cfg["d_state"] + heads}


def make_weights(cfg: Dict, key) -> Dict[str, jax.Array]:
    """Random weights from ``key``, in the types they are served in
    (bfloat16; the decay and step parameters float32).  Jit it: one call
    makes them all on the device."""
    d = dims(cfg)
    G, D, DI, H, S, K = d["G"], d["D"], d["DI"], d["H"], d["S"], d["K"]
    ks = jax.random.split(key, 9)
    bf16 = jnp.bfloat16

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(bf16)
    dt = jnp.exp(jax.random.uniform(ks[5], (G, H), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        # a quarter of the unit scale: the token's own embedding then
        # no longer outweighs what the layers add, so the served tokens
        # depend on the state and not only on the last token
        "embed": normal(ks[0], (d["V"], D), 0.25 / math.sqrt(D)),
        "ln": normal(ks[1], (G, D), 0.1),
        "in_proj": normal(ks[2], (G, D, d["PROJ"]), 1.0 / math.sqrt(D)),
        "conv_w": normal(ks[3], (G, K, DI + 2 * S), 1.0 / math.sqrt(K)),
        "a_log": jnp.log(jax.random.uniform(ks[4], (G, H), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
        "norm_scale": normal(ks[6], (G, DI), 0.1),
        "out_proj": normal(ks[7], (G, DI, D), 1.0 / math.sqrt(DI)),
        "final_norm": normal(ks[8], (D,), 0.1),
    }


def to_program(w: Dict[str, jax.Array]) -> Dict:
    """The same arrays in the parameter tree the program serves from."""
    return {"embed": {"table": w["embed"]},
            "blocks": {"ln": w["ln"],
                       "ssd": {"in_proj": {"w": w["in_proj"]},
                               "conv_w": w["conv_w"], "a_log": w["a_log"],
                               "dt_bias": w["dt_bias"],
                               "norm_scale": w["norm_scale"],
                               "out_proj": {"w": w["out_proj"]}},
                       "ln2": jnp.zeros_like(w["ln"]), "mlp": None},
            "final_norm": w["final_norm"]}


def program_fields(cfg: Dict) -> Dict:
    """The program's model-config fields for this configuration."""
    return {"n_layers": cfg["n_layer"], "d_model": cfg["d_model"],
            "vocab": cfg["vocab_rows"], "ssm_state": cfg["d_state"],
            "ssm_head_dim": cfg["headdim"], "ssm_expand": cfg["expand"],
            "conv_width": cfg["d_conv"], "norm_eps": cfg["norm_eps"],
            "d_ff": 0, "param_dtype": cfg["dtype"],
            "compute_dtype": cfg["dtype"]}


def _fp8(x: jax.Array) -> jax.Array:
    """Round to an 8-bit float (4 exponent, 3 mantissa bits) with one
    scale for the tensor, its largest magnitude at the format's top
    (240).  ``reduce_precision`` and not a pair of casts, which the
    compiler may drop as excess precision."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _matmul(a, b, fp8: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _layer(x, lw, d, eps, fp8):
    B, L = x.shape[:2]
    DI, S, H, P, K = d["DI"], d["S"], d["H"], d["P"], d["K"]
    proj = _matmul(_rms(x, lw["ln"], eps), lw["in_proj"], fp8)
    x_in, z, bc, dt = jnp.split(proj, [DI, 2 * DI, 2 * DI + 2 * S], axis=-1)
    conv_in = jnp.concatenate([x_in, bc], axis=-1)
    ctx = jnp.pad(conv_in, ((0, 0), (K - 1, 0), (0, 0)))
    w = lw["conv_w"].astype(jnp.float32)
    conv = sum(ctx[:, i:i + L] * w[i] for i in range(K))
    conv = jax.nn.silu(conv)
    xs, bm, cm = jnp.split(conv, [DI, DI + S], axis=-1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    a = jnp.exp(-jnp.exp(lw["a_log"]) * dt)                   # (B, L, H)
    xh = xs.reshape(B, L, H, P)

    def step(state, inp):
        a_t, b_t, c_t, x_t = inp
        state = (a_t[:, :, None, None] * state
                 + b_t[:, None, :, None] * x_t[:, :, None, :])
        return state, jnp.sum(c_t[:, None, :, None] * state, axis=2)
    state0 = jnp.zeros((B, H, S, P), jnp.float32)
    _, y = jax.lax.scan(step, state0, (jnp.moveaxis(a, 1, 0),
                                       jnp.moveaxis(bm, 1, 0),
                                       jnp.moveaxis(cm, 1, 0),
                                       jnp.moveaxis(xh, 1, 0)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, L, DI)
    y = _rms(y * jax.nn.silu(z), lw["norm_scale"], eps)
    return x + _matmul(y, lw["out_proj"], fp8)


def hidden(w: Dict, cfg: Dict, tokens: jax.Array, fp8: bool = False
           ) -> jax.Array:
    """Final normed hidden states (B, L, D) of the token rows."""
    d = dims(cfg)
    eps = cfg["norm_eps"]
    table = w["embed"].astype(jnp.float32)
    x = table[tokens] * math.sqrt(d["D"])
    layers = {k: w[k] for k in ("ln", "in_proj", "conv_w", "a_log",
                                "dt_bias", "norm_scale", "out_proj")}

    def body(x, lw):
        return _layer(x, lw, d, eps, fp8), None
    x, _ = jax.lax.scan(body, x, layers)
    return _rms(x, w["final_norm"], eps)


def gaps(w: Dict, cfg: Dict, tokens: jax.Array, targets: jax.Array,
         control: bool = False, block: int = 256
         ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Per position, how far the reference's logit of ``targets`` (the
    token served next; -1 where nothing is compared) lies below the
    reference's best logit.  With ``control``, also the same gap of the
    token that the float8 forward puts first.  Jit it."""
    table = w["embed"].astype(jnp.float32)
    B, L = tokens.shape
    rows = B * L
    pad = (-rows) % block

    def flat(h):
        h = h.reshape(rows, -1)
        return jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, block, h.shape[-1])
    h_ref = flat(hidden(w, cfg, tokens))
    tgt = jnp.pad(targets.reshape(rows), (0, pad),
                  constant_values=-1).reshape(-1, block)
    if control:
        h_ctl = flat(hidden(w, cfg, tokens, fp8=True))
    else:
        h_ctl = h_ref

    def one(args):
        hr, hc, t = args
        logits = jnp.matmul(hr, table.T, precision=HIGHEST)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, jnp.maximum(t, 0)[:, None],
                                  axis=-1)[:, 0]
        gap = jnp.where(t >= 0, best - got, 0.0)
        if not control:
            return gap, gap
        pick = jnp.argmax(_matmul(hc, table.T, fp8=True), axis=-1)
        ctl = jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
        return gap, jnp.where(t >= 0, best - ctl, 0.0)
    gap, ctl = jax.lax.map(one, (h_ref, h_ctl, tgt))
    gap = gap.reshape(-1)[:rows].reshape(B, L)
    ctl = ctl.reshape(-1)[:rows].reshape(B, L)
    return gap, (ctl if control else None)
