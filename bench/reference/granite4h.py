"""Plain float32 reference of granite-4.0-h-micro (model_type
granitemoehybrid), and the seeded weights both it and the program are
given.

Written from the published description (the model's config.json and the
Mamba-2 layer of arXiv:2405.21060), importing nothing of the program.
Every number comes from the configuration dict:

  x = embed[token] * embedding_multiplier
  per layer (layer_types), each followed by its MLP:
    mamba:      h = rmsnorm(x)
                [xs, z, B, C, dt] = h @ in_proj
                [xs, B, C] = silu(causal depthwise conv(width d_conv) + bias)
                dt = softplus(dt + dt_bias);  log a = -exp(A_log) * dt
                state_t = a_t state_(t-1) + B_t (dt_t xs_t)^T
                y_t = C_t . state_t + D * xs_t
                y = rmsnorm(y * silu(z)) @ out_proj
    attention:  h = rmsnorm(x);  q, k, v = h @ wq, wk, wv  (GQA)
                no rotary where position_embedding_type is "nope"
                y = softmax(q k^T * attention_multiplier, causal) v @ wo
    x = x + y * residual_multiplier
    x = x + (silu(h2 @ w_gate) * (h2 @ w_up)) @ w_down * residual_multiplier,
        h2 = rmsnorm(x)
  logits = rmsnorm(x) @ embed^T / logits_scaling          (tied)

RMSNorm weights are drawn as 1 + delta: the reference multiplies by
(1 + delta), the program keeps delta as its norm scale.  The columns of
``in_proj`` are laid out x, z, B, C, dt (a layout of random weights;
the published checkpoint keeps z first).  n_groups is 1: B and C are
shared by every head.

The state recurrence runs either token by token (``block=None``: a
``lax.scan`` over positions, the ground truth of the CPU tests) or in
blocks by the SSD dual form (``block=Q``: within a block the masked
decay matrix, between blocks a ``lax.scan`` that carries the state), which
the full-width check on the chip uses to fit its time.  Attention runs in
blocks of queries, the logits in blocks of positions, so that no
(L, L) or (L, vocab) array is ever whole.  Every matmul is at
``Precision.HIGHEST``.  The control is the same forward with every weight
matmul's operands rounded to 8-bit floats (e4m3, one scale per tensor),
the precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MAMBA, ATTENTION = "mamba", "attention"


def dims(cfg: Dict) -> Dict:
    D = cfg["hidden_size"]
    heads = cfg["mamba_n_heads"]
    P = cfg["mamba_d_head"]
    if heads * P != cfg["mamba_expand"] * D:
        raise ValueError("mamba_n_heads * mamba_d_head != mamba_expand * "
                         "hidden_size")
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("only mamba_n_groups 1 is written out")
    S = cfg["mamba_d_state"]
    DI = heads * P
    return {"D": D, "V": cfg["vocab_size"], "H": heads, "P": P, "S": S,
            "DI": DI, "K": cfg["mamba_d_conv"], "PROJ": 2 * DI + 2 * S + heads,
            "HQ": cfg["num_attention_heads"],
            "HKV": cfg["num_key_value_heads"],
            "HD": D // cfg["num_attention_heads"],
            "F": cfg["shared_intermediate_size"],
            "period": period(cfg["layer_types"]),
            "repeats": len(cfg["layer_types"])
            // len(period(cfg["layer_types"]))}


def period(kinds: List[str]) -> List[str]:
    """The shortest run of layer kinds that ``kinds`` repeats."""
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and list(kinds) == list(kinds[:p]) * (n // p):
            return list(kinds[:p])
    raise AssertionError("unreachable")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def make_weights(cfg: Dict, key) -> Dict:
    """Random weights from ``key``, in the types they are served in
    (bfloat16; the decay, step and skip parameters float32).  Layers are
    held by their place in the period: ``layers[i]`` stacks the i-th layer
    of every repeat of the period.  Jit it: one call makes them all on the
    device.

    Scales are chosen so that a wrong layer shows in the logits: output
    projections (out_proj, wo, w_down) are 1 / residual_multiplier larger
    than unit scale, so each layer adds to the residual stream about as
    much as the embedding holds, and wo 3 times more again, since
    attention's output averages v over several positions and comes out
    that much smaller (without it a departure in the attention layers,
    rotary or the softmax scale, moves the logits less than bfloat16
    rounding does); q and k are (3 / (attention_multiplier *
    sqrt(head_dim)))^(1/2) larger, so the published softmax scale meets
    scores that spread by 3 and attention picks out a few positions, where
    an average over many would hide its errors; the embedding's scale gives logits (after the
    divisor) a spread of about 2 over the vocabulary.  Decay and step are
    drawn as the Mamba-2 layer initialises them (A in [1, 16], dt
    log-uniform in [1e-3, 1e-1]); D around its initial ones, the conv
    bias as a conv's default.
    """
    d = dims(cfg)
    D, R = d["D"], d["repeats"]
    bf16 = jnp.bfloat16
    out_scale = 1.0 / cfg["residual_multiplier"]
    qk_scale = (3.0 / (cfg["attention_multiplier"]
                       * math.sqrt(d["HD"]))) ** 0.5

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(bf16)

    def mlp(ks):
        return {"ln2": normal(ks[0], (R, D), 0.1),
                "w_gate": normal(ks[1], (R, D, d["F"]), 1 / math.sqrt(D)),
                "w_up": normal(ks[2], (R, D, d["F"]), 1 / math.sqrt(D)),
                "w_down": normal(ks[3], (R, d["F"], D),
                                 out_scale / math.sqrt(d["F"]))}

    def mamba(k):
        ks = jax.random.split(k, 13)
        H, DI, K = d["H"], d["DI"], d["K"]
        chan = DI + 2 * d["S"]
        dt = jnp.exp(jax.random.uniform(ks[5], (R, H), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {
            "ln": normal(ks[0], (R, D), 0.1),
            "in_proj": normal(ks[1], (R, D, d["PROJ"]), 1 / math.sqrt(D)),
            "conv_w": normal(ks[2], (R, K, chan), 1 / math.sqrt(K)),
            "conv_b": (jax.random.uniform(ks[3], (R, chan), jnp.float32,
                                          -1.0, 1.0)
                       / math.sqrt(K)).astype(bf16),
            "a_log": jnp.log(jax.random.uniform(ks[4], (R, H), jnp.float32,
                                                1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            "d": jax.random.uniform(ks[6], (R, H), jnp.float32, 0.5, 1.5),
            "norm": normal(ks[7], (R, DI), 0.1),
            "out_proj": normal(ks[8], (R, DI, D), out_scale / math.sqrt(DI)),
            **mlp(ks[9:13])}

    def attention(k):
        ks = jax.random.split(k, 9)
        q, kv = d["HQ"] * d["HD"], d["HKV"] * d["HD"]
        return {"ln": normal(ks[0], (R, D), 0.1),
                "wq": normal(ks[1], (R, D, q), qk_scale / math.sqrt(D)),
                "wk": normal(ks[2], (R, D, kv), qk_scale / math.sqrt(D)),
                "wv": normal(ks[3], (R, D, kv), 1 / math.sqrt(D)),
                "wo": normal(ks[4], (R, q, D),
                             3.0 * out_scale / math.sqrt(q)),
                **mlp(ks[5:9])}

    k_embed, k_final, k_layers = jax.random.split(key, 3)
    layers = [mamba(jax.random.fold_in(k_layers, i)) if kind == MAMBA
              else attention(jax.random.fold_in(k_layers, i))
              for i, kind in enumerate(d["period"])]
    return {"embed": normal(k_embed, (d["V"], D),
                            2.0 * cfg["logits_scaling"] / math.sqrt(D)),
            "layers": layers,
            "final_norm": normal(k_final, (D,), 0.1)}


def to_program(w: Dict) -> Dict:
    """The same arrays in the parameter tree the program serves from: one
    group per repeat of the period, the layer's kind read off its keys."""
    def mlp(lw):
        return {"wi": {"w": lw["w_gate"]}, "wu": {"w": lw["w_up"]},
                "wo": {"w": lw["w_down"]}}

    blocks = []
    for lw in w["layers"]:
        if "in_proj" in lw:
            blocks.append({
                "ln": lw["ln"],
                "ssd": {"in_proj": {"w": lw["in_proj"]},
                        "conv_w": lw["conv_w"], "conv_b": lw["conv_b"],
                        "a_log": lw["a_log"], "dt_bias": lw["dt_bias"],
                        "d_skip": lw["d"], "norm_scale": lw["norm"],
                        "out_proj": {"w": lw["out_proj"]}},
                "ln2": lw["ln2"], "mlp": mlp(lw)})
        else:
            blocks.append({
                "ln1": lw["ln"],
                "attn": {"wq": {"w": lw["wq"]}, "wk": {"w": lw["wk"]},
                         "wv": {"w": lw["wv"]}, "wo": {"w": lw["wo"]}},
                "ln2": lw["ln2"], "mlp": mlp(lw)})
    return {"embed": {"table": w["embed"]}, "blocks": {"blocks": blocks},
            "final_norm": w["final_norm"]}


def program_fields(cfg: Dict) -> Dict:
    """The program's model-config fields for this configuration."""
    d = dims(cfg)
    kinds = {MAMBA: "ssd", ATTENTION: "attn"}
    return {"n_layers": len(cfg["layer_types"]), "d_model": d["D"],
            "vocab": d["V"], "n_heads": d["HQ"], "n_kv_heads": d["HKV"],
            "head_dim": d["HD"], "d_ff": d["F"],
            "block_pattern": tuple(kinds[k] for k in d["period"]),
            "ssm_state": d["S"], "ssm_head_dim": d["P"],
            "ssm_expand": cfg["mamba_expand"], "conv_width": d["K"],
            "ssm_dt_input": True, "ssm_d_skip": True,
            "ssm_conv_bias": bool(cfg["mamba_conv_bias"]),
            "use_rope": cfg["position_embedding_type"] == "rope",
            "rope_theta": float(cfg["rope_theta"]),
            "attn_scale": float(cfg["attention_multiplier"]),
            "embed_scale": float(cfg["embedding_multiplier"]),
            "residual_scale": float(cfg["residual_multiplier"]),
            "logits_scaling": float(cfg["logits_scaling"]),
            "norm_eps": cfg["rms_norm_eps"],
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
    token by token.  xdt: (B, L, H, P); log_a: (B, L, H); b, c: (B, L, S)."""
    Bn, _, H, P = xdt.shape
    S = b.shape[-1]

    def step(state, inp):
        la, b_t, c_t, x_t = inp
        state = (jnp.exp(la)[:, :, None, None] * state
                 + b_t[:, None, :, None] * x_t[:, :, None, :])
        return state, jnp.einsum("bs,bhsp->bhp", c_t, state,
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
        cb = jnp.einsum("bts,bus->btu", c_k, b_k, precision=HIGHEST)
        y = jnp.einsum("btuh,buhp->bthp", cb[..., None] * decay, x_k,
                       precision=HIGHEST)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bts,bhsp->bthp", c_k, state, precision=HIGHEST)
        to_end = jnp.exp(cum[:, -1:, :] - cum)      # (B, Q, H)
        state = (jnp.exp(cum[:, -1, :])[:, :, None, None] * state
                 + jnp.einsum("bush,buhp->bhsp",
                              b_k[..., None] * to_end[:, :, None, :], x_k,
                              precision=HIGHEST))
        return state, y
    state0 = jnp.zeros((Bn, H, S, P), jnp.float32)
    _, y = jax.lax.scan(step, state0, tuple(blocks(v) for v in
                                            (xdt, log_a, b, c)))
    return jnp.moveaxis(y, 0, 1).reshape(Bn, L, H, P)


def _mamba_mixer(x, lw, d, cfg, fp8, block):
    Bn, L = x.shape[:2]
    DI, S, H, P, K = d["DI"], d["S"], d["H"], d["P"], d["K"]
    eps = cfg["rms_norm_eps"]
    proj = _matmul(_rms(x, lw["ln"], eps), lw["in_proj"], fp8)
    xs, z, bc, dt = jnp.split(proj, [DI, 2 * DI, 2 * DI + 2 * S], axis=-1)
    conv_in = jnp.concatenate([xs, bc], axis=-1)
    ctx = jnp.pad(conv_in, ((0, 0), (K - 1, 0), (0, 0)))
    w = lw["conv_w"].astype(jnp.float32)
    conv = sum(ctx[:, i:i + L] * w[i] for i in range(K))
    conv = jax.nn.silu(conv + lw["conv_b"].astype(jnp.float32))
    xs, bm, cm = jnp.split(conv, [DI, DI + S], axis=-1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                   # (B, L, H)
    log_a = -jnp.exp(lw["a_log"]) * dt
    xh = xs.reshape(Bn, L, H, P)
    xdt = xh * dt[..., None]
    if block is None:
        y = ssd_sequential(xdt, log_a, bm, cm)
    else:
        y = ssd_blocks(xdt, log_a, bm, cm, block)
    y = y + lw["d"][:, None] * xh
    y = y.reshape(Bn, L, DI)
    y = _rms(y * jax.nn.silu(z), lw["norm"], eps)
    return _matmul(y, lw["out_proj"], fp8)


def _rope(x, theta):
    """Rotary embedding over (B, L, heads, hd), halves rotated."""
    L, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs    # (L, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention_mixer(x, lw, d, cfg, fp8, q_block=256):
    Bn, L = x.shape[:2]
    HQ, HKV, HD = d["HQ"], d["HKV"], d["HD"]
    h = _rms(x, lw["ln"], cfg["rms_norm_eps"])
    q = _matmul(h, lw["wq"], fp8).reshape(Bn, L, HQ, HD)
    k = _matmul(h, lw["wk"], fp8).reshape(Bn, L, HKV, HD)
    v = _matmul(h, lw["wv"], fp8).reshape(Bn, L, HKV, HD)
    if cfg["position_embedding_type"] == "rope":
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    rep = HQ // HKV
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    Qb = min(q_block, L)
    if L % Qb:
        raise ValueError(f"length {L} is not a multiple of {Qb}")
    kpos = jnp.arange(L)

    def one(args):
        q_blk, start = args                         # (B, Qb, HQ, HD)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k, precision=HIGHEST)
        s = s * cfg["attention_multiplier"]
        qpos = start + jnp.arange(Qb)
        s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    qs = jnp.moveaxis(q.reshape(Bn, L // Qb, Qb, HQ, HD), 1, 0)
    o = jax.lax.map(one, (qs, jnp.arange(0, L, Qb)))
    o = jnp.moveaxis(o, 0, 1).reshape(Bn, L, HQ * HD)
    return _matmul(o, lw["wo"], fp8)


def _mlp(x, lw, cfg, fp8):
    h = _rms(x, lw["ln2"], cfg["rms_norm_eps"])
    g = jax.nn.silu(_matmul(h, lw["w_gate"], fp8))
    return _matmul(g * _matmul(h, lw["w_up"], fp8), lw["w_down"], fp8)


def hidden(w: Dict, cfg: Dict, tokens: jax.Array, fp8: bool = False,
           block: Optional[int] = None) -> jax.Array:
    """Final normed hidden states (B, L, D) of the token rows; ``block``
    as the module says."""
    d = dims(cfg)
    mult = cfg["residual_multiplier"]
    x = w["embed"].astype(jnp.float32)[tokens] * cfg["embedding_multiplier"]

    def one_period(x, layers):
        for kind, lw in zip(d["period"], layers):
            if kind == MAMBA:
                y = _mamba_mixer(x, lw, d, cfg, fp8, block)
            else:
                y = _attention_mixer(x, lw, d, cfg, fp8)
            x = x + y * mult
            x = x + _mlp(x, lw, cfg, fp8) * mult
        return x, None
    x, _ = jax.lax.scan(one_period, x, w["layers"])
    return _rms(x, w["final_norm"], cfg["rms_norm_eps"])


def _logits(h, w, cfg, fp8):
    return _matmul(h, w["embed"].T, fp8) / cfg["logits_scaling"]


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
        logits = _logits(hb, w, cfg, fp8)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return picked - jax.nn.logsumexp(logits, axis=-1)
    ll = jax.lax.map(one, (_position_blocks(h, rows), nxt))
    ll = ll.reshape(-1)[:Bn * L].reshape(Bn, L)[:, :-1]
    return ll, _logits(h[:, positions], w, cfg, fp8)


def gaps(w: Dict, cfg: Dict, tokens: jax.Array, targets: jax.Array,
         control: bool = False, block: int = 256
         ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Per position, how far the reference's logit of ``targets`` (the
    token served next; -1 where nothing is compared) lies below the
    reference's best logit.  With ``control``, also the same gap of the
    token that the float8 forward puts first.  ``block`` is the number of
    positions whose logits are made at a time; the state runs token by
    token.  Jit it."""
    Bn, L = tokens.shape
    h_ref = _position_blocks(hidden(w, cfg, tokens), block)
    h_ctl = (_position_blocks(hidden(w, cfg, tokens, fp8=True), block)
             if control else h_ref)
    tgt = targets.reshape(-1)
    tgt = jnp.pad(tgt, (0, (-tgt.shape[0]) % block),
                  constant_values=-1).reshape(-1, block)

    def one(args):
        hr, hc, t = args
        logits = _logits(hr, w, cfg, False)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, jnp.maximum(t, 0)[:, None],
                                  axis=-1)[:, 0]
        gap = jnp.where(t >= 0, best - got, 0.0)
        if not control:
            return gap, gap
        pick = jnp.argmax(_logits(hc, w, cfg, True), axis=-1)
        ctl = jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
        return gap, jnp.where(t >= 0, best - ctl, 0.0)
    gap, ctl = jax.lax.map(one, (h_ref, h_ctl, tgt))
    gap = gap.reshape(-1)[:Bn * L].reshape(Bn, L)
    ctl = ctl.reshape(-1)[:Bn * L].reshape(Bn, L)
    return gap, (ctl if control else None)


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def param_counts(cfg: Dict) -> Dict[str, int]:
    """Parameters by part: each Mamba-2 layer (in_proj, out_proj), each
    attention layer (q, k, v, o), each layer's MLP, and the embedding."""
    d = dims(cfg)
    D = d["D"]
    return {"mamba": D * d["PROJ"] + d["DI"] * D,
            "attention": D * (d["HQ"] + 2 * d["HKV"]) * d["HD"]
            + d["HQ"] * d["HD"] * D,
            "mlp": 3 * D * d["F"], "embed": d["V"] * D}


def counts(cfg: Dict, tokens: int) -> Tuple[float, float]:
    """(flops, bytes) of one scoring call over ``tokens`` positions.

    Flops: every matmul parameter twice a position (the tied unembedding
    at every position), attention's causal q k^T and p v (2 L d a position
    and layer, d = heads x head size), and the SSD state update and readout
    in their recurrent form (5 H S P a position and layer).  Bytes: the
    bfloat16 weights read once, the token ids in, and the outputs written
    (L - 1 log-likelihoods; the 64-row logits are left out, under 1 %).
    Recomputed or padded work never counts.
    """
    d = dims(cfg)
    p = param_counts(cfg)
    kinds = cfg["layer_types"]
    n_mamba = sum(1 for k in kinds if k == MAMBA)
    n_attn = len(kinds) - n_mamba
    matmul = (n_mamba * p["mamba"] + n_attn * p["attention"]
              + len(kinds) * p["mlp"] + p["embed"])
    flops = 2.0 * matmul * tokens
    flops += n_attn * 2.0 * tokens * tokens * d["HQ"] * d["HD"]
    flops += n_mamba * 5.0 * d["H"] * d["S"] * d["P"] * tokens
    weights = matmul * 2
    return flops, float(weights + 4 * tokens + 4 * (tokens - 1))
