"""Plain float32 reference of NVIDIA-Nemotron-3-Nano-30B-A3B (model_type
nemotron_h) on one chip's expert share, and the seeded weights both it
and the program are given.

Written from the published description (the model's config.json, the
Mamba-2 layer of arXiv:2405.21060 and the Nemotron-H layer kinds),
importing nothing of the program.  Every number comes from the
configuration dict:

  x = embed[token]                                 (no multiplier)
  per layer, its kind read off hybrid_override_pattern (no period):
    x = x + mixer(rmsnorm(x)),  one mixer a layer and no MLP
    M (Mamba-2, H heads of P, G groups of state S):
      [xs, z, B, C, dt] = h @ in_proj                (B, C: G x S each)
      [xs, B, C] = silu(causal depthwise conv(width conv_kernel) + bias)
      dt = softplus(dt + dt_bias)   (time_step_limit (0, inf): no clamp)
      log a = -exp(A_log) * dt
      head k reads the B and C of group k // (H / G):
      state_t = a_t state_(t-1) + B_t (dt_t xs_t)^T;  y_t = C_t . state_t
      y = y + D * xs
      y = rmsnorm_per_group(y * silu(z)) @ out_proj  (groups of H P / G)
    E (MoE over n_routed_experts_published experts, this chip's share):
      s = sigmoid(h @ router)                          (router in f32)
      the top num_experts_per_tok of s + e_score_correction_bias
      w = the picked s, normalised to sum 1 (norm_topk_prob), times
          routed_scaling_factor
      y = sum over held experts e of w_e down_e(relu(up_e(h))^2)
          + down_sh(relu(up_sh(h))^2)                  (the shared expert)
    * (attention):  q, k, v = h @ wq, wk, wv  (GQA, head_dim), no rotary
      y = softmax(q k^T / sqrt(head_dim), causal) v @ wo
  logits = rmsnorm(x) @ lm_head^T                      (untied)

This chip holds experts [held_experts_offset, held_experts_offset +
n_routed_experts) of every MoE layer: the router scores all of them, and
each token's output holds what the held experts give it, computed
densely (for every token and held expert a weight, 0 unless the expert is
among the token's picks).  What the other experts would add is left out,
in the program and here alike.  The vocabulary is the slice of
vocab_size rows held here.

RMSNorm weights are drawn as 1 + delta: the reference multiplies by
(1 + delta), the program keeps delta as its norm scale.  The columns of
``in_proj`` are laid out x, z, B, C, dt (a layout of random weights; the
published checkpoint keeps z first).  Nemotron-H's attention layer
applies no rotary embedding, so rope_theta is not read.

The state recurrence runs token by token (``block=None``) or in blocks
by the SSD dual form (``block=Q``), attention in blocks of queries and
the logits in blocks of positions, so that no (L, L) or (L, vocab) array
is ever whole.  Every matmul is at ``Precision.HIGHEST``.  The control is
the same forward with every weight matmul's operands rounded to 8-bit
floats (e4m3, one scale per tensor), the precision below the bfloat16 the
configuration serves in.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
# the program's names of the layer kinds (its Mamba-2 layer is "ssd")
PROGRAM_KINDS = {"M": "ssd", "E": "moe", "*": "attn"}


def kinds(cfg: Dict) -> List[str]:
    return [KINDS[c] for c in cfg["hybrid_override_pattern"]]


def dims(cfg: Dict) -> Dict:
    heads, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, S = cfg["n_groups"], cfg["ssm_state_size"]
    if heads % G:
        raise ValueError("mamba_num_heads is not a multiple of n_groups")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("only n_group 1, topk_group 1 is written out")
    DI = heads * P
    return {"D": cfg["hidden_size"], "V": cfg["vocab_size"], "H": heads,
            "P": P, "G": G, "S": S, "DI": DI, "K": cfg["conv_kernel"],
            "PROJ": 2 * DI + 2 * G * S + heads,
            "HQ": cfg["num_attention_heads"],
            "HKV": cfg["num_key_value_heads"], "HD": cfg["head_dim"],
            "E": cfg["n_routed_experts_published"],
            "HELD": cfg["n_routed_experts"],
            "OFF": cfg["held_experts_offset"],
            "TOPK": cfg["num_experts_per_tok"],
            "F": cfg["moe_intermediate_size"],
            "FS": cfg["moe_shared_expert_intermediate_size"]
            * cfg["n_shared_experts"]}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def make_weights(cfg: Dict, key) -> Dict:
    """Random weights from ``key``, in the types they are served in
    (bfloat16; the router, its bias and the decay, step and skip
    parameters float32), one dict a layer.  Jit it: one call makes them
    all on the device.

    Scales: the embedding's rows are of unit spread and each mixer's
    output projection gives an output of about that spread, so that
    every layer moves the residual stream, but for the routed experts'
    down projections, a quarter of that: the bfloat16 program and this
    reference pick differently wherever a token's last pick is nearly
    tied with the next, and each such pick on a held expert moves the
    token's stream by a routed expert's output; q and k are 3^(1/2) larger
    than unit scale, so the softmax meets scores that spread by 3 and
    attention picks out a few positions; the router gives logits of unit
    spread and the correction bias (uniform in +-0.25) moves which
    experts a token picks; the lm_head gives logits a spread of about 2
    over the vocabulary.  Decay and step are drawn as the Mamba-2 layer
    initialises them (A in [1, 16], dt log-uniform in [time_step_min,
    time_step_max]); D around its initial ones, the conv bias as a
    conv's default.
    """
    d = dims(cfg)
    D = d["D"]
    bf16 = jnp.bfloat16

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(bf16)

    def mamba(k):
        ks = jax.random.split(k, 10)
        H, DI, K = d["H"], d["DI"], d["K"]
        chan = DI + 2 * d["G"] * d["S"]
        dt = jnp.exp(jax.random.uniform(
            ks[5], (H,), jnp.float32, math.log(cfg["time_step_min"]),
            math.log(cfg["time_step_max"])))
        return {
            "in_proj": normal(ks[1], (D, d["PROJ"]), 1 / math.sqrt(D)),
            "conv_w": normal(ks[2], (K, chan), 1 / math.sqrt(K)),
            "conv_b": (jax.random.uniform(ks[3], (chan,), jnp.float32,
                                          -1.0, 1.0)
                       / math.sqrt(K)).astype(bf16),
            "a_log": jnp.log(jax.random.uniform(ks[4], (H,), jnp.float32,
                                                1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            "d": jax.random.uniform(ks[6], (H,), jnp.float32, 0.5, 1.5),
            "norm": normal(ks[7], (DI,), 0.1),
            "out_proj": normal(ks[8], (DI, D), 1 / math.sqrt(DI))}

    def moe(k):
        ks = jax.random.split(k, 7)
        F, FS, HELD = d["F"], d["FS"], d["HELD"]
        return {
            "router": jax.random.normal(ks[1], (D, d["E"]), jnp.float32)
            / math.sqrt(D),
            "bias": jax.random.uniform(ks[2], (d["E"],), jnp.float32,
                                       -0.25, 0.25),
            "up": normal(ks[3], (HELD, D, F), 1 / math.sqrt(D)),
            "down": normal(ks[4], (HELD, F, D), 0.25 / math.sqrt(F)),
            "shared_up": normal(ks[5], (D, FS), 1 / math.sqrt(D)),
            "shared_down": normal(ks[6], (FS, D), 1 / math.sqrt(FS))}

    def attn(k):
        ks = jax.random.split(k, 5)
        q, kv = d["HQ"] * d["HD"], d["HKV"] * d["HD"]
        qk = math.sqrt(3.0) / math.sqrt(D)
        return {"wq": normal(ks[1], (D, q), qk),
                "wk": normal(ks[2], (D, kv), qk),
                "wv": normal(ks[3], (D, kv), 1 / math.sqrt(D)),
                "wo": normal(ks[4], (q, D), 3.0 / math.sqrt(q))}

    make = {"mamba": mamba, "moe": moe, "attn": attn}
    k_embed, k_head, k_final, k_layers = jax.random.split(key, 4)
    layers = []
    for i, kind in enumerate(kinds(cfg)):
        k = jax.random.fold_in(k_layers, i)
        layers.append(dict(make[kind](k),
                           ln=normal(jax.random.fold_in(k, 99), (D,), 0.1)))
    return {"embed": normal(k_embed, (d["V"], D), 1.0),
            "lm_head": normal(k_head, (d["V"], D), 2.0 / math.sqrt(D)),
            "layers": layers,
            "final_norm": normal(k_final, (D,), 0.1)}


def to_program(w: Dict) -> Dict:
    """The same arrays in the parameter tree the program serves from: the
    whole stack as one group of layers (every array with a leading group
    axis of 1), the Mamba-2 and attention layers with the unused second
    norm and no MLP the program's layers keep where there is no MLP."""
    layers = []
    for lw in w["layers"]:
        no_mlp = {"ln2": jnp.zeros_like(lw["ln"]), "mlp": None}
        if "in_proj" in lw:
            layers.append(dict(no_mlp, ln=lw["ln"], ssd={
                "in_proj": {"w": lw["in_proj"]}, "conv_w": lw["conv_w"],
                "conv_b": lw["conv_b"], "a_log": lw["a_log"],
                "dt_bias": lw["dt_bias"], "d_skip": lw["d"],
                "norm_scale": lw["norm"],
                "out_proj": {"w": lw["out_proj"]}}))
        elif "router" in lw:
            layers.append({"ln": lw["ln"], "moe": {
                "router": {"w": lw["router"]}, "router_bias": lw["bias"],
                "wu": lw["up"], "wo": lw["down"],
                "shared": {"wu": {"w": lw["shared_up"]},
                           "wo": {"w": lw["shared_down"]}}}})
        else:
            layers.append(dict(no_mlp, ln1=lw["ln"], attn={
                k: {"w": lw[k]} for k in ("wq", "wk", "wv", "wo")}))
    group = jax.tree.map(lambda a: a[None], {"blocks": layers})
    return {"embed": {"table": w["embed"]},
            "lm_head": {"table": w["lm_head"]},
            "blocks": group, "final_norm": w["final_norm"]}


def program_fields(cfg: Dict) -> Dict:
    """The program's model-config fields for this configuration."""
    d = dims(cfg)
    if cfg["mlp_hidden_act"] != "relu2" or cfg["residual_in_fp32"]:
        raise ValueError("only relu2 experts and a bf16 residual stream "
                         "are written out")
    return {"n_layers": cfg["num_hidden_layers"],
            "block_pattern": tuple(PROGRAM_KINDS[c] for c in
                                   cfg["hybrid_override_pattern"]),
            "d_model": d["D"],
            "vocab": d["V"], "n_heads": d["HQ"], "n_kv_heads": d["HKV"],
            "head_dim": d["HD"], "activation": cfg["mlp_hidden_act"],
            "n_experts": d["E"], "moe_top_k": d["TOPK"],
            "d_ff_expert": d["F"], "n_shared_experts": cfg["n_shared_experts"],
            "d_ff_shared": d["FS"], "moe_router": "sigmoid",
            "moe_routed_scale": float(cfg["routed_scaling_factor"]),
            "moe_held": d["HELD"], "moe_held_offset": d["OFF"],
            "ssm_heads": d["H"], "ssm_head_dim": d["P"],
            "ssm_state": d["S"], "ssm_groups": d["G"], "conv_width": d["K"],
            "ssm_dt_input": True, "ssm_d_skip": True,
            "ssm_conv_bias": bool(cfg["use_conv_bias"]),
            "use_rope": False, "embed_scale": 1.0,
            "norm_eps": cfg["layer_norm_epsilon"],
            "tie_embeddings": bool(cfg["tie_word_embeddings"]),
            "param_dtype": cfg["dtype"], "compute_dtype": cfg["dtype"]}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

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


def _rms(x, delta, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + delta.astype(jnp.float32))


def ssd_sequential(xdt, log_a, b, c):
    """state_t = a_t state_(t-1) + b_t xdt_t^T;  y_t = c_t . state_t,
    token by token.  xdt: (B, L, H, P); log_a: (B, L, H); b, c: (B, L,
    H, S), one row a head."""
    Bn, _, H, P = xdt.shape
    S = b.shape[-1]

    def step(state, inp):
        la, b_t, c_t, x_t = inp
        state = (jnp.exp(la)[:, :, None, None] * state
                 + b_t[:, :, :, None] * x_t[:, :, None, :])
        return state, jnp.einsum("bhs,bhsp->bhp", c_t, state,
                                 precision=HIGHEST)
    state0 = jnp.zeros((Bn, H, S, P), jnp.float32)
    _, y = jax.lax.scan(step, state0,
                        tuple(jnp.moveaxis(v, 1, 0)
                              for v in (log_a, b, c, xdt)))
    return jnp.moveaxis(y, 0, 1)


def ssd_blocks(xdt, log_a, b, c, block: int):
    """The same recurrence by the SSD dual form in blocks of ``block``
    positions: inside a block y_t = sum_{s<=t} (c_t . b_s) exp(sum_{s<u<=t}
    log a_u) xdt_s, plus the state carried in from earlier blocks."""
    Bn, L, H, P = xdt.shape
    S = b.shape[-1]
    Q = min(block, L)
    if L % Q:
        raise ValueError(f"length {L} is not a multiple of the block {Q}")
    nb = L // Q

    def blocks(v):
        return jnp.moveaxis(v.reshape((Bn, nb, Q) + v.shape[2:]), 1, 0)
    mask = jnp.tril(jnp.ones((Q, Q), bool))

    def step(state, inp):
        x_k, la_k, b_k, c_k = inp                   # (B,Q,H,P) (B,Q,H) ...
        cum = jnp.cumsum(la_k, axis=1)              # (B, Q, H)
        diff = cum[:, :, None, :] - cum[:, None, :, :]        # (B, t, s, H)
        decay = jnp.exp(jnp.where(mask[None, :, :, None], diff, -jnp.inf))
        cb = jnp.einsum("bths,buhs->btuh", c_k, b_k, precision=HIGHEST)
        y = jnp.einsum("btuh,buhp->bthp", cb * decay, x_k,
                       precision=HIGHEST)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bths,bhsp->bthp", c_k, state, precision=HIGHEST)
        to_end = jnp.exp(cum[:, -1:, :] - cum)      # (B, Q, H)
        state = (jnp.exp(cum[:, -1, :])[:, :, None, None] * state
                 + jnp.einsum("buhs,buhp->bhsp",
                              b_k * to_end[..., None], x_k,
                              precision=HIGHEST))
        return state, y
    state0 = jnp.zeros((Bn, H, S, P), jnp.float32)
    _, y = jax.lax.scan(step, state0, tuple(blocks(v) for v in
                                            (xdt, log_a, b, c)))
    return jnp.moveaxis(y, 0, 1).reshape(Bn, L, H, P)


def _mamba_mixer(h, lw, d, cfg, fp8, block):
    Bn, L = h.shape[:2]
    DI, G, S, H, P, K = d["DI"], d["G"], d["S"], d["H"], d["P"], d["K"]
    proj = _matmul(h, lw["in_proj"], fp8)
    xs, z, bc, dt = jnp.split(proj, [DI, 2 * DI, 2 * DI + 2 * G * S],
                              axis=-1)
    conv_in = jnp.concatenate([xs, bc], axis=-1)
    ctx = jnp.pad(conv_in, ((0, 0), (K - 1, 0), (0, 0)))
    w = lw["conv_w"].astype(jnp.float32)
    conv = sum(ctx[:, i:i + L] * w[i] for i in range(K))
    conv = jax.nn.silu(conv + lw["conv_b"].astype(jnp.float32))
    xs, bm, cm = jnp.split(conv, [DI, DI + G * S], axis=-1)
    bm = per_head(bm.reshape(Bn, L, G, S), H)
    cm = per_head(cm.reshape(Bn, L, G, S), H)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                   # (B, L, H)
    log_a = -jnp.exp(lw["a_log"]) * dt
    xh = xs.reshape(Bn, L, H, P)
    xdt = xh * dt[..., None]
    if block is None:
        y = ssd_sequential(xdt, log_a, bm, cm)
    else:
        y = ssd_blocks(xdt, log_a, bm, cm, block)
    y = (y + lw["d"][:, None] * xh).reshape(Bn, L, DI)
    y = gated_norm(y, z, lw["norm"], G, cfg["layer_norm_epsilon"])
    return _matmul(y, lw["out_proj"], fp8)


def per_head(v, heads: int):
    """(B, L, G, S) group rows -> (B, L, heads, S): head k reads group
    k // (heads / G)."""
    return jnp.repeat(v, heads // v.shape[2], axis=2)


def gated_norm(y, z, delta, groups: int, eps):
    """rmsnorm(y * silu(z)) over each group of d_inner / groups channels
    apart (norm_before_gate false)."""
    Bn, L, DI = y.shape
    y = (y * jax.nn.silu(z)).reshape(Bn, L, groups, DI // groups)
    y = _rms(y, delta.reshape(groups, DI // groups), eps)
    return y.reshape(Bn, L, DI)


def pick(logits, bias, k):
    """(weights, experts) of each token's k picks: sigmoid scores, the top
    k of score + correction bias, weighted by their unbiased scores
    normalised to sum 1 (norm_topk_prob)."""
    scores = jax.nn.sigmoid(logits)
    _, picked = jax.lax.top_k(scores + bias, k)
    w = jnp.take_along_axis(scores, picked, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20), picked


def route(h, lw, d, cfg, fp8):
    """(weights (B, L, HELD)) of the held experts for each token: the
    routing weight where the expert is among the token's picks, else 0."""
    w, picked = pick(_matmul(h, lw["router"], fp8), lw["bias"], d["TOPK"])
    w = w * cfg["routed_scaling_factor"]
    held = d["OFF"] + jnp.arange(d["HELD"])
    return jnp.sum(jnp.where(picked[..., None] == held, w[..., None], 0.0),
                   axis=-2)


def _relu2_mlp(h, up, down, fp8):
    return _matmul(jnp.square(jax.nn.relu(_matmul(h, up, fp8))), down, fp8)


def _moe_mixer(h, lw, d, cfg, fp8):
    weight = route(h, lw, d, cfg, fp8)                         # (B, L, HELD)
    y = _relu2_mlp(h, lw["shared_up"], lw["shared_down"], fp8)
    for e in range(d["HELD"]):
        y = y + weight[..., e:e + 1] * _relu2_mlp(h, lw["up"][e],
                                                  lw["down"][e], fp8)
    return y


def _attention_mixer(h, lw, d, cfg, fp8, q_block=256):
    Bn, L = h.shape[:2]
    HQ, HKV, HD = d["HQ"], d["HKV"], d["HD"]
    q = _matmul(h, lw["wq"], fp8).reshape(Bn, L, HQ, HD)
    k = _matmul(h, lw["wk"], fp8).reshape(Bn, L, HKV, HD)
    v = _matmul(h, lw["wv"], fp8).reshape(Bn, L, HKV, HD)
    rep = HQ // HKV
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    Qb = min(q_block, L)
    if L % Qb:
        raise ValueError(f"length {L} is not a multiple of {Qb}")
    kpos = jnp.arange(L)

    def one(args):
        q_blk, start = args                         # (B, Qb, HQ, HD)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k, precision=HIGHEST)
        s = s / math.sqrt(HD)
        qpos = start + jnp.arange(Qb)
        s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    qs = jnp.moveaxis(q.reshape(Bn, L // Qb, Qb, HQ, HD), 1, 0)
    o = jax.lax.map(one, (qs, jnp.arange(0, L, Qb)))
    o = jnp.moveaxis(o, 0, 1).reshape(Bn, L, HQ * HD)
    return _matmul(o, lw["wo"], fp8)


def hidden(w: Dict, cfg: Dict, tokens: jax.Array, fp8: bool = False,
           block: Optional[int] = None) -> jax.Array:
    """Final normed hidden states (B, L, D) of the token rows; ``block``
    as the module says."""
    d = dims(cfg)
    eps = cfg["layer_norm_epsilon"]
    x = w["embed"].astype(jnp.float32)[tokens]
    for kind, lw in zip(kinds(cfg), w["layers"]):
        h = _rms(x, lw["ln"], eps)
        if kind == "mamba":
            x = x + _mamba_mixer(h, lw, d, cfg, fp8, block)
        elif kind == "moe":
            x = x + _moe_mixer(h, lw, d, cfg, fp8)
        else:
            x = x + _attention_mixer(h, lw, d, cfg, fp8)
    return _rms(x, w["final_norm"], eps)


def _logits(h, w, fp8):
    return _matmul(h, w["lm_head"].T, fp8)


def _position_blocks(h, rows: int):
    """(B, L, D) -> (n, rows, D) blocks of positions (zero padded)."""
    flat = h.reshape(-1, h.shape[-1])
    pad = (-flat.shape[0]) % rows
    return jnp.pad(flat, ((0, pad), (0, 0))).reshape(-1, rows, h.shape[-1])


def score(w: Dict, cfg: Dict, tokens: jax.Array, positions: jax.Array,
          fp8: bool = False, block: Optional[int] = None, rows: int = 512
          ) -> Tuple[jax.Array, jax.Array]:
    """The scoring pass the program's calls make: per row of ``tokens``
    (B, L), the log-likelihood of each next token (B, L - 1), and the
    full logits at ``positions`` (B, n, V).  Jit it."""
    Bn, L = tokens.shape
    h = hidden(w, cfg, tokens, fp8=fp8, block=block)
    nxt = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1))).reshape(-1)
    pad = (-nxt.shape[0]) % rows
    nxt = jnp.pad(nxt, (0, pad)).reshape(-1, rows)

    def one(args):
        hb, tb = args
        logits = _logits(hb, w, fp8)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return picked - jax.nn.logsumexp(logits, axis=-1)
    ll = jax.lax.map(one, (_position_blocks(h, rows), nxt))
    ll = ll.reshape(-1)[:Bn * L].reshape(Bn, L)[:, :-1]
    return ll, _logits(h[:, positions], w, fp8)


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def param_counts(cfg: Dict) -> Dict[str, int]:
    """Matmul parameters by part: each Mamba-2 layer (in_proj, out_proj),
    each attention layer (q, k, v, o), each MoE layer's router, one
    routed expert and the shared expert, and the embedding (the lm_head
    as many)."""
    d = dims(cfg)
    D = d["D"]
    return {"mamba": D * d["PROJ"] + d["DI"] * D,
            "attention": D * (d["HQ"] + 2 * d["HKV"]) * d["HD"]
            + d["HQ"] * d["HD"] * D,
            "router": D * d["E"], "expert": 2 * D * d["F"],
            "shared": 2 * D * d["FS"], "embed": d["V"] * D}


def routed_rows(cfg: Dict, tokens: int) -> float:
    """The (token, choice) pairs an MoE layer routes to the held experts
    on average: tokens x picks x held / experts."""
    d = dims(cfg)
    return tokens * d["TOPK"] * d["HELD"] / d["E"]


def moe_counts(cfg: Dict, tokens: int) -> Tuple[float, float]:
    """(flops, bytes) of the held experts' routed work in one scoring
    call, over every MoE layer: each expected routed row through up and
    down (2 flops a parameter), and the bfloat16 bytes that work must
    move: the held experts' weights read once, each routed row read in
    and written out by both matmuls.  Padding never counts."""
    d = dims(cfg)
    n_moe = kinds(cfg).count("moe")
    rows = routed_rows(cfg, tokens)
    flops = 2.0 * rows * param_counts(cfg)["expert"]
    weights = d["HELD"] * param_counts(cfg)["expert"]
    moved = weights + rows * 2 * (d["D"] + d["F"])
    return n_moe * flops, n_moe * 2.0 * moved


def counts(cfg: Dict, tokens: int) -> Tuple[float, float]:
    """(flops, bytes) of one scoring call over ``tokens`` positions.

    Flops: every matmul parameter twice a position (the lm_head at every
    position), the routed experts at their expected rows (``routed_rows``)
    and the router and shared expert at every position; attention's
    causal q k^T and p v (2 L d a position and layer, d = heads x head
    size); the SSD state update and readout in their recurrent form (5 H S
    P a position and layer).  Bytes: the bfloat16 weights read once, the
    token ids in, and the outputs written (L - 1 log-likelihoods; the
    64-row logits are left out, under 1 %).  Recomputed or padded work
    never counts.
    """
    d = dims(cfg)
    p = param_counts(cfg)
    ks = kinds(cfg)
    n_mamba, n_moe, n_attn = (ks.count(k) for k in ("mamba", "moe", "attn"))
    dense = (n_mamba * p["mamba"] + n_attn * p["attention"]
             + n_moe * (p["router"] + p["shared"]) + p["embed"])
    flops = 2.0 * dense * tokens + moe_counts(cfg, tokens)[0]
    flops += n_attn * 2.0 * tokens * tokens * d["HQ"] * d["HD"]
    flops += n_mamba * 5.0 * d["H"] * d["S"] * d["P"] * tokens
    weights = dense + n_moe * d["HELD"] * p["expert"] + p["embed"]
    return flops, float(2 * weights + 4 * tokens + 4 * (tokens - 1))
