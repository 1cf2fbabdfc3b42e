"""Mixture-of-Experts blocks: GShard top-k dispatch (EP-shardable), and the
expert share of one chip (``expert_share_block``).

Routing is computed per GROUP of `moe_group_size` tokens (GShard's S):
dispatch/combine tensors are (G, S, E, C) with the group dim inheriting the
data sharding and experts on "model" (EP). A flat (T, E, C) formulation is
quadratic in tokens (C ~ T/E) and measured 676 GiB/device on the train_4k
cells; grouping makes C ~ S/E and the whole object linear in T.

With tokens on ("pod","data") and experts on "model", XLA lowers the
dispatch einsums to all-to-alls (verified by the roofline parser).

The expert share is told which experts it holds and routes over all of
them: the (token, choice) pairs routed to its experts are ranked by expert
(a counting sort), laid out one padded group of row tiles per expert, and
run through two grouped-matmul launches (up with the activation, down),
with no capacity and no pair dropped; each token then sums its pairs'
rows, weighted, and the shared expert.  What the experts held elsewhere
add is left out: on one chip the layer runs without its exchange.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.matmul.ops import grouped_config, grouped_matmul
from repro.models.layers import UNGATED, _act, dense, init_dense, init_mlp, mlp


def padded_experts(cfg: ModelConfig) -> int:
    """Physical expert count, padded to a model-axis multiple so EP shards
    evenly (qwen2-moe: 60 -> 64 on a 16-way axis; pads never receive
    tokens — the router only emits real indices)."""
    e, m = cfg.n_experts, cfg.model_axis_size
    if m and e % m:
        return ((e + m - 1) // m) * m
    return e


def init_moe(key, cfg: ModelConfig, dtype) -> Dict:
    kr, ke, ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff_expert
    e = padded_experts(cfg)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": init_dense(kr, d, cfg.n_experts, dtype),
        "wi": (jax.random.normal(ke, (e, d, f), jnp.float32) * scale).astype(dtype),
        "wu": (jax.random.normal(jax.random.fold_in(ke, 1), (e, d, f),
                                 jnp.float32) * scale).astype(dtype),
        "wo": (jax.random.normal(jax.random.fold_in(ke, 2), (e, f, d),
                                 jnp.float32) / math.sqrt(f)).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks, d, cfg.d_ff_expert * cfg.n_shared_experts,
                               cfg.activation, dtype)
    return p


def _expert_constraint(t: jax.Array, cfg: ModelConfig, e_dim: int):
    """Shard the expert dim on "model" (EP) when divisible; group dim on the
    batch axes. UNCONSTRAINED elsewhere (see attention._score_constraint)."""
    if not cfg.batch_axes or not cfg.model_axis_size or (
            cfg.batch_shards and t.shape[0] % cfg.batch_shards):
        return t
    U = P.UNCONSTRAINED
    b = cfg.batch_axes if len(cfg.batch_axes) > 1 else cfg.batch_axes[0]
    e = t.shape[e_dim]
    axes = [U] * t.ndim
    axes[0] = b
    if e % cfg.model_axis_size == 0:
        axes[e_dim] = "model"
    return jax.lax.with_sharding_constraint(t, P(*axes))


def moe_block(p: Dict, x: jax.Array, cfg: ModelConfig, compute_dtype
              ) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_loss). x: (B, L, D)."""
    bsz, l, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    ep = padded_experts(cfg)          # physical (padded) expert-bank size

    # ---- grouping: (B, L, D) -> (G, S, D), G inherits batch sharding ----
    s = min(getattr(cfg, "moe_group_size", 1024) or 1024, l)
    while l % s:
        s //= 2
    s = max(s, 1)
    g = bsz * (l // s)
    xg = x.reshape(g, s, d)

    probs, gate_w, gate_idx = route(p, xg, cfg)                  # (G, S, k)

    # load-balance aux (Switch): E * mean_e(f_e * P_e), averaged over groups
    me = jnp.mean(probs, axis=1)                                 # (G, E)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(gate_idx, e), axis=2), axis=1)
    aux = e * jnp.mean(jnp.sum(me * ce, axis=-1))

    capacity = max(int(math.ceil(s * k / e * cfg.capacity_factor)), 1)

    # position of each (token, choice) in its expert queue, per group
    onehot_e = jax.nn.one_hot(gate_idx, ep, dtype=jnp.int32)     # (G, S, k, Ep)
    flat = onehot_e.reshape(g, s * k, ep)
    pos = jnp.cumsum(flat, axis=1) - flat                        # (G, S*k, E)
    pos = jnp.sum(pos * flat, axis=-1).reshape(g, s, k)
    keep = pos < capacity

    disp_e = (onehot_e.astype(compute_dtype)
              * keep[..., None].astype(compute_dtype))           # (G, S, k, Ep)
    pos_c = jax.nn.one_hot(pos, capacity, dtype=compute_dtype)   # (G, S, k, C)
    dispatch = jnp.einsum("gske,gskc->gsec", disp_e, pos_c)      # (G, S, E, C)
    dispatch = _expert_constraint(dispatch, cfg, 2)
    combine_w = jnp.einsum("gsk,gske,gskc->gsec",
                           gate_w.astype(compute_dtype), disp_e, pos_c)

    expert_in = jnp.einsum("gsd,gsec->gecd", xg.astype(compute_dtype),
                           dispatch)                             # (G, E, C, D)
    expert_in = _expert_constraint(expert_in, cfg, 1)

    gih = _act(cfg.activation, jnp.einsum(
        "gecd,edf->gecf", expert_in, p["wi"].astype(compute_dtype)))
    u = jnp.einsum("gecd,edf->gecf", expert_in, p["wu"].astype(compute_dtype))
    expert_out = jnp.einsum("gecf,efd->gecd", gih * u,
                            p["wo"].astype(compute_dtype))       # (G, E, C, D)
    expert_out = _expert_constraint(expert_out, cfg, 1)

    out = jnp.einsum("gsec,gecd->gsd", combine_w, expert_out)
    out = out.reshape(bsz, l, d)

    if "shared" in p:
        out = out + mlp(p["shared"], x, cfg.activation, compute_dtype)
    return out.astype(x.dtype), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# routing, shared by both blocks
# ---------------------------------------------------------------------------

def route(p: Dict, x: jax.Array, cfg: ModelConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(scores (..., E), weights (..., k), experts (..., k)) of x (..., D).

    softmax: the top k of the softmax.  sigmoid: scores sigmoid(logits)
    from an f32 router at full precision; the top k of score +
    ``router_bias`` are picked and weighted by their unbiased scores.
    Either way the k weights are normalised to sum 1, then scaled by
    ``moe_routed_scale``.
    """
    k = cfg.moe_top_k
    if cfg.moe_router == "sigmoid":
        logits = jnp.matmul(x.astype(jnp.float32), p["router"]["w"],
                            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["router_bias"], k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        scores = jax.nn.softmax(dense(p["router"], x, jnp.float32), axis=-1)
        w, idx = jax.lax.top_k(scores, k)
    w = w / jnp.clip(w.sum(-1, keepdims=True), 1e-9)
    if cfg.moe_routed_scale != 1.0:
        w = w * cfg.moe_routed_scale
    return scores, w, idx


# ---------------------------------------------------------------------------
# expert share
# ---------------------------------------------------------------------------

def held_experts(cfg: ModelConfig) -> int:
    return cfg.moe_held or cfg.n_experts


def init_expert_share(key, cfg: ModelConfig, dtype) -> Dict:
    """The router over all n_experts (f32), the held experts' up and down
    weights, and the shared expert."""
    if cfg.activation not in UNGATED:
        raise ValueError("the expert share runs experts without a gate")
    kr, ke, ks = jax.random.split(key, 3)
    d, f, held = cfg.d_model, cfg.d_ff_expert, held_experts(cfg)
    p = {
        "router": init_dense(kr, d, cfg.n_experts, jnp.float32),
        "wu": (jax.random.normal(ke, (held, d, f), jnp.float32)
               / math.sqrt(d)).astype(dtype),
        "wo": (jax.random.normal(jax.random.fold_in(ke, 1), (held, f, d),
                                 jnp.float32) / math.sqrt(f)).astype(dtype),
    }
    if cfg.moe_router == "sigmoid":
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks, d, cfg.d_ff_shared
                               or cfg.d_ff_expert * cfg.n_shared_experts,
                               cfg.activation, dtype)
    return p


def expert_share_block(p: Dict, x: jax.Array, cfg: ModelConfig,
                       compute_dtype) -> jax.Array:
    """The held experts' part of the layer, plus the shared expert.
    x: (B, L, D)."""
    bsz, l, d = x.shape
    xf = x.reshape(bsz * l, d)
    t, k = xf.shape[0], cfg.moe_top_k
    held = p["wu"].shape[0]
    _, w, idx = route(p, xf, cfg)                                # (T, k)
    local = idx - cfg.moe_held_offset
    mine = (local >= 0) & (local < held)
    expert = jnp.where(mine, local, held).reshape(-1)            # held: elsewhere

    # counting sort of the pairs by expert: each pair's rank among its
    # expert's pairs, each expert's group of rows padded to whole tiles
    cap = t * min(k, held)                       # the most pairs held here
    up = grouped_config(cap, d, p["wu"].shape[-1])
    bm = up["block_m"]
    down = grouped_config(cap, p["wu"].shape[-1], d,
                          config={"block_m": bm})
    onehot = jax.nn.one_hot(expert, held + 1, dtype=jnp.int32)   # (T k, E+1)
    ranks = jnp.cumsum(onehot, axis=0)
    counts = ranks[-1, :held]
    rank = jnp.take_along_axis(ranks, expert[:, None], axis=1)[:, 0] - 1
    group_tiles = (counts + bm - 1) // bm
    starts = jnp.append((jnp.cumsum(group_tiles) - group_tiles) * bm, 0)
    rows = (-(-cap // bm) + held) * bm          # the padded groups fit
    row = jnp.where(expert < held, starts[expert] + rank, rows)  # per pair
    token = jnp.zeros((rows,), jnp.int32).at[row].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")

    h = grouped_matmul(xf.astype(compute_dtype)[token],
                       p["wu"].astype(compute_dtype), group_tiles,
                       config=up, activation=cfg.activation,
                       name="moe_gmm_up", use_pallas=cfg.use_pallas or None)
    y = grouped_matmul(h, p["wo"].astype(compute_dtype), group_tiles,
                       config=down, name="moe_gmm_down",
                       use_pallas=cfg.use_pallas or None)
    # rows of pairs held elsewhere (and tiles never computed) read garbage
    got = jnp.take(y, row, axis=0, mode="clip").reshape(t, k, d)
    got = jnp.where(mine[..., None], got, 0)
    out = jnp.einsum("tk,tkd->td", w.astype(compute_dtype), got)
    if "shared" in p:
        out = out + mlp(p["shared"], xf, cfg.activation, compute_dtype)
    return out.reshape(bsz, l, d).astype(x.dtype)
