"""Model builder: config -> init / forward / prefill / decode functions.

Layer stacks are `lax.scan`ned over parameter groups so HLO size is O(1) in
depth (critical for 88–100-layer archs in the 512-device dry-run). A "group"
is the architecture's repeating pattern:
  dense/moe: 1 block;  hybrid: its block_pattern, e.g. (rec, rec,
  local-attn) or (ssd x5, attn, ssd x4);  vlm: 4 standard +
  1 cross-attn block;  ssm: 1 SSD block;  audio: enc stack + dec stack.
A hybrid whose stack has no period gives it whole as one period (one
group); without d_ff its layers are x + mixer(rmsnorm(x)) with no MLP of
their own, the "moe" kind an expert-share MoE.

Caches are pytrees with a leading group dimension threaded through the same
scan. Modality frontends (whisper conv, vision patching) are STUBS per the
assignment: forward takes precomputed frame/patch embeddings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import recurrent as rec_mod
from repro.models import ssm as ssm_mod
from repro.models.moe import (expert_share_block, init_expert_share,
                              init_moe, moe_block)

PyTree = Any


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _constrain_batch(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Pin the batch sharding of the residual stream. The embedding gather
    (vocab-sharded table) otherwise replicates its output batch dim, and
    the whole stack inherits the replication (measured 40x memory)."""
    if not cfg.batch_axes or (cfg.batch_shards
                              and x.shape[0] % cfg.batch_shards):
        return x
    b = cfg.batch_axes if len(cfg.batch_axes) > 1 else cfg.batch_axes[0]
    U = P.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(x, P(b, *([U] * (x.ndim - 1))))


def _constrain_residual(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Sequence-parallel (SP) sharding of the residual stream at layer
    boundaries: (B, L, D) -> P(batch, "model", None).

    The tensor saved per scanned layer for the backward pass is the block
    input; without SP it is only batch-sharded, and deep/wide archs blow
    past HBM (granite-34b: 88 x (16, 4096, 6144) bf16 = 66 GiB/device).
    With SP the saves shrink by the model-axis size; GSPMD inserts the
    all-gather at attention entry / reduce-scatter after (Korthikanti et
    al.-style SP, GSPMD-native)."""
    if (cfg.activation_strategy != "sp" or not cfg.batch_axes
            or not cfg.model_axis_size or x.ndim != 3
            or x.shape[1] % cfg.model_axis_size
            or (cfg.batch_shards and x.shape[0] % cfg.batch_shards)):
        return x
    b = cfg.batch_axes if len(cfg.batch_axes) > 1 else cfg.batch_axes[0]
    return jax.lax.with_sharding_constraint(
        x, P(b, "model", P.UNCONSTRAINED))


def _residual(x: jax.Array, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x + h, with h scaled by the residual multiplier where one is set."""
    if cfg.residual_scale != 1.0:
        h = h * jnp.asarray(cfg.residual_scale, h.dtype)
    return x + h


def _embed_scale(cfg: ModelConfig, dtype) -> jax.Array:
    if cfg.embed_scale:
        return jnp.asarray(cfg.embed_scale, dtype)
    return jnp.sqrt(jnp.asarray(cfg.d_model, dtype))


def _logits(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final norm, unembedding (tied, or the lm_head), softcap and logits
    divisor."""
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params.get("lm_head", params["embed"]), x,
                       cfg.logits_softcap)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _init_std_block(key, cfg: ModelConfig, dtype, cross: bool = False) -> Dict:
    ka, km, kc = jax.random.split(key, 3)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "attn": attn_mod.init_attention(ka, cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
    }
    if cfg.family == "moe":
        p["moe"] = init_moe(km, cfg, dtype)
    else:
        p["mlp"] = L.init_mlp(km, cfg.d_model, cfg.d_ff, cfg.activation,
                              dtype) if cfg.d_ff else None
    if cross:
        p["lnx"] = jnp.zeros((cfg.d_model,), dtype)
        p["xattn"] = attn_mod.init_attention(kc, cfg, dtype, cross=True)
    return p


def _std_block(p: Dict, x, cfg: ModelConfig, *, positions, cache=None,
               window=None, memory=None, compute_dtype=None):
    cd = compute_dtype or _cdtype(cfg)
    aux = jnp.zeros((), jnp.float32)
    h, new_cache = attn_mod.self_attention(
        p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps), cfg,
        positions=positions, cache=cache, window=window, compute_dtype=cd)
    x = _residual(x, h, cfg)
    if "xattn" in p and memory is not None:
        x = _residual(x, attn_mod.cross_attention(
            p["xattn"], L.rms_norm(x, p["lnx"], cfg.norm_eps), memory, cfg,
            cd), cfg)
    if p.get("mlp") is None and "moe" not in p:
        return x, new_cache, aux
    y = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        h, aux = moe_block(p["moe"], y, cfg, cd)
    else:
        h = L.mlp(p["mlp"], y, cfg.activation, cd)
    return _residual(x, h, cfg), new_cache, aux


def _init_ssd_group(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {"ln": jnp.zeros((cfg.d_model,), dtype),
            "ssd": ssm_mod.init_ssd_block(k1, cfg, dtype),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
            "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff or 2 * cfg.d_model,
                              cfg.activation, dtype)
            if cfg.d_ff else None}


def _ssd_group(p, x, cfg, *, cache=None, compute_dtype=None):
    cd = compute_dtype or _cdtype(cfg)
    h, new_cache = ssm_mod.ssd_block(
        p["ssd"], L.rms_norm(x, p["ln"], cfg.norm_eps), cfg, cache=cache,
        compute_dtype=cd)
    x = _residual(x, h, cfg)
    if p.get("mlp") is not None:
        x = _residual(x, L.mlp(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps),
                               cfg.activation, cd), cfg)
    return x, new_cache, jnp.zeros((), jnp.float32)


def _init_hybrid_group(key, cfg, dtype):
    ks = jax.random.split(key, len(cfg.block_pattern))
    group = []
    for k, kind in zip(ks, cfg.block_pattern):
        if kind == "ssd":
            # a Mamba-2 mixer, each with its own MLP where there is d_ff
            group.append(_init_ssd_group(k, cfg, dtype))
        elif kind == "moe":
            group.append({"ln": jnp.zeros((cfg.d_model,), dtype),
                          "moe": init_expert_share(k, cfg, dtype)})
        elif kind == "rec":
            k1, k2 = jax.random.split(k)
            group.append({"ln1": jnp.zeros((cfg.d_model,), dtype),
                          "rec": rec_mod.init_recurrent_block(k1, cfg, dtype),
                          "ln2": jnp.zeros((cfg.d_model,), dtype),
                          "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff,
                                            cfg.activation, dtype)})
        else:
            group.append(_init_std_block(k, cfg, dtype))
    return {"blocks": group}


def _hybrid_group(p, x, cfg, *, positions, cache=None, compute_dtype=None):
    cd = compute_dtype or _cdtype(cfg)
    new_caches = []
    for i, blk in enumerate(p["blocks"]):
        sub_cache = None if cache is None else cache[i]
        if "ssd" in blk:
            def run_ssd(xx, blk=blk):
                y, nc, _ = _ssd_group(blk, xx, cfg, cache=sub_cache,
                                      compute_dtype=cd)
                return y, nc
            if cache is None and cfg.remat == "full":
                run_ssd = jax.checkpoint(run_ssd)
            x, nc = run_ssd(x)
        elif "rec" in blk:
            def run_rec(xx, blk=blk):
                h, nc = rec_mod.recurrent_block(
                    blk["rec"], L.rms_norm(xx, blk["ln1"], cfg.norm_eps), cfg,
                    cache=sub_cache, compute_dtype=cd)
                xx = _residual(xx, h, cfg)
                xx = _residual(xx, L.mlp(
                    blk["mlp"], L.rms_norm(xx, blk["ln2"], cfg.norm_eps),
                    cfg.activation, cd), cfg)
                return xx, nc
            if cache is None and cfg.remat == "full":
                # per-layer remat: without it the whole group's forward
                # stays live during the group's backward replay
                run_rec = jax.checkpoint(run_rec)
            x, nc = run_rec(x)
        elif "moe" in blk:
            def run_moe(xx, blk=blk):
                return _residual(xx, expert_share_block(
                    blk["moe"], L.rms_norm(xx, blk["ln"], cfg.norm_eps), cfg,
                    cd), cfg)
            if cache is None and cfg.remat == "full":
                run_moe = jax.checkpoint(run_moe)
            x, nc = run_moe(x), sub_cache
        else:
            def run_att(xx, blk=blk):
                return _std_block(blk, xx, cfg, positions=positions,
                                  cache=sub_cache, window=cfg.attn_window,
                                  compute_dtype=cd)
            if cache is None and cfg.remat == "full":
                run_att = jax.checkpoint(run_att)
            x, nc, _ = run_att(x)
        new_caches.append(nc)
    return x, (new_caches if cache is not None else None), \
        jnp.zeros((), jnp.float32)


def _init_vlm_group(key, cfg, dtype):
    period = cfg.cross_attn_every
    ks = jax.random.split(key, period)
    group = [_init_std_block(k, cfg, dtype, cross=(i == period - 1))
             for i, k in enumerate(ks)]
    return {"blocks": group}


def _vlm_group(p, x, cfg, *, positions, memory, cache=None,
               compute_dtype=None):
    new_caches = []
    for i, blk in enumerate(p["blocks"]):
        sub_cache = None if cache is None else cache[i]

        def run(xx, blk=blk, sub_cache=sub_cache):
            return _std_block(blk, xx, cfg, positions=positions,
                              cache=sub_cache, memory=memory,
                              compute_dtype=compute_dtype)
        if cache is None and cfg.remat == "full":
            # per-layer remat inside the 5-layer group (see _hybrid_group)
            run = jax.checkpoint(run)
        x, nc, _ = run(x)
        new_caches.append(nc)
    return x, (new_caches if cache is not None else None), \
        jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # --- structure ---
    @property
    def group_period(self) -> int:
        if self.cfg.family == "hybrid":
            return len(self.cfg.block_pattern)
        if self.cfg.family == "vlm":
            return self.cfg.cross_attn_every
        return 1

    @property
    def n_groups(self) -> int:
        return self.cfg.n_layers // self.group_period

    # --- init ---
    def init(self, key) -> PyTree:
        cfg = self.cfg
        dtype = _pdtype(cfg)
        k_embed, k_blocks, k_enc = jax.random.split(key, 3)

        def init_group(k):
            if cfg.family == "ssm":
                return _init_ssd_group(k, cfg, dtype)
            if cfg.family == "hybrid":
                return _init_hybrid_group(k, cfg, dtype)
            if cfg.family == "vlm":
                return _init_vlm_group(k, cfg, dtype)
            return _init_std_block(k, cfg, dtype)

        params = {
            "embed": L.init_embedding(k_embed, cfg.vocab, cfg.d_model, dtype),
            "blocks": jax.vmap(init_group)(
                jax.random.split(k_blocks, self.n_groups)),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.init_embedding(
                jax.random.fold_in(k_embed, 1), cfg.vocab, cfg.d_model, dtype)
        if cfg.is_enc_dec:
            n_enc = cfg.n_enc_layers or cfg.n_layers
            enc_cfg = dataclasses.replace(cfg, family="dense")

            def init_enc(k):
                return _init_std_block(k, enc_cfg, dtype)

            params["enc_blocks"] = jax.vmap(init_enc)(
                jax.random.split(k_enc, n_enc))
            params["enc_norm"] = jnp.zeros((cfg.d_model,), dtype)
        return params

    # --- encoder (whisper stub frontend) ---
    def encode(self, params, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        cd = _cdtype(cfg)
        enc_cfg = dataclasses.replace(cfg, family="dense")
        x = _constrain_batch(frames.astype(cd), cfg)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])

        def body(h, blk):
            def run(h):
                y, _, _ = _std_block(blk, h, enc_cfg, positions=positions,
                                     compute_dtype=cd)
                return y
            if cfg.remat == "full":
                run = jax.checkpoint(run)
            return run(h), None

        x, _ = jax.lax.scan(body, x, params["enc_blocks"])
        return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)

    # --- training / prefill-style forward (no cache) ---
    def forward(self, params, tokens: jax.Array,
                memory: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
        """tokens: (B, L) -> (logits (B, L, V) fp32, aux loss scalar)."""
        cfg = self.cfg
        cd = _cdtype(cfg)
        b, l = tokens.shape
        x = L.embed(params["embed"], tokens, cd,
                    one_hot=bool(cfg.batch_axes)) * _embed_scale(cfg, cd)
        x = _constrain_batch(x, cfg)
        positions = jnp.broadcast_to(jnp.arange(l)[None], (b, l))
        if cfg.is_enc_dec and memory is not None:
            memory = memory.astype(cd)

        def body(carry, blk):
            h, aux = carry
            h = _constrain_residual(h, cfg)

            def run(h):
                if cfg.family == "ssm":
                    y, _, a = _ssd_group(blk, h, cfg, compute_dtype=cd)
                elif cfg.family == "hybrid":
                    y, _, a = _hybrid_group(blk, h, cfg, positions=positions,
                                            compute_dtype=cd)
                elif cfg.family == "vlm":
                    y, _, a = _vlm_group(blk, h, cfg, positions=positions,
                                         memory=memory, compute_dtype=cd)
                else:
                    mem = memory if cfg.is_enc_dec else None
                    ed_cfg = (dataclasses.replace(cfg, family="dense")
                              if cfg.is_enc_dec else cfg)
                    blk2 = dict(blk)
                    y, _, a = _std_block(blk2, h, ed_cfg, positions=positions,
                                         memory=mem, window=cfg.attn_window,
                                         compute_dtype=cd)
                return y, a

            if cfg.remat == "full":
                run = jax.checkpoint(run)
            y, a = run(h)
            return (y, aux + a), None

        n_dec = self.n_groups
        dec_blocks = params["blocks"]
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   dec_blocks)
        return _logits(params, x, cfg), aux / max(n_dec, 1)

    # --- KV / state caches ---
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16
                   ) -> PyTree:
        cfg = self.cfg

        def kv_cache(length, ring=False):
            out = {"k": jnp.zeros((batch, length, cfg.n_kv_heads,
                                   cfg.head_dim), dtype),
                   "v": jnp.zeros((batch, length, cfg.n_kv_heads,
                                   cfg.head_dim), dtype)}
            if ring:
                out["pos"] = jnp.full((batch, length), -2**30, jnp.int32)
            return out

        def one_group():
            if cfg.family == "ssm":
                return ssm_mod.init_ssd_cache(cfg, batch, dtype)
            if cfg.family == "hybrid":
                out = []
                ring = cfg.attn_window is not None and cfg.attn_window < max_len
                for kind in cfg.block_pattern:
                    if kind == "ssd":
                        out.append(ssm_mod.init_ssd_cache(cfg, batch, dtype))
                    elif kind == "rec":
                        out.append(rec_mod.init_recurrent_cache(cfg, batch,
                                                                dtype))
                    elif kind == "moe":
                        out.append({})
                    else:
                        out.append(kv_cache(min(max_len,
                                                cfg.attn_window or max_len),
                                            ring=ring))
                return out
            if cfg.family == "vlm":
                return [kv_cache(max_len) for _ in range(cfg.cross_attn_every)]
            return kv_cache(max_len)

        proto = one_group()
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (self.n_groups,) + x.shape),
            proto)

    # --- single-token decode step ---
    def decode_step(self, params, token: jax.Array, cache: PyTree,
                    pos: jax.Array, memory: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, PyTree]:
        """token: (B, 1); pos: (B, 1) absolute positions."""
        cfg = self.cfg
        cd = _cdtype(cfg)
        x = L.embed(params["embed"], token, cd) * _embed_scale(cfg, cd)
        x = _constrain_batch(x, cfg)
        if memory is not None:
            memory = memory.astype(cd)

        def body(h, inp):
            blk, cache_g = inp
            if cfg.family == "ssm":
                y, nc, _ = _ssd_group(blk, h, cfg, cache=cache_g,
                                      compute_dtype=cd)
            elif cfg.family == "hybrid":
                y, nc, _ = _hybrid_group(blk, h, cfg, positions=pos,
                                         cache=cache_g, compute_dtype=cd)
            elif cfg.family == "vlm":
                y, nc, _ = _vlm_group(blk, h, cfg, positions=pos,
                                      memory=memory, cache=cache_g,
                                      compute_dtype=cd)
            else:
                mem = memory if cfg.is_enc_dec else None
                ed_cfg = (dataclasses.replace(cfg, family="dense")
                          if cfg.is_enc_dec else cfg)
                y, nc, _ = _std_block(blk, h, ed_cfg, positions=pos,
                                      cache=cache_g, memory=mem,
                                      window=cfg.attn_window,
                                      compute_dtype=cd)
            return y, nc

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
        return _logits(params, x, cfg), new_cache

    # --- bulk prompt ingestion (single-dispatch prefill) ---
    def prefill(self, params, tokens: jax.Array, cache: PyTree,
                positions: jax.Array, write_mask: jax.Array) -> PyTree:
        """Bulk-write a block of prompt tokens into the decode cache.

        ``tokens``/``positions``/``write_mask``: (steps, batch) time-major.
        Scans :meth:`decode_step` over the leading axis inside one traced
        computation, so a whole prompt chunk lands in the cache in a single
        device dispatch.  ``write_mask[t, b]`` selects, per step and lane,
        whether lane ``b``'s cache advances at step ``t``; masked-off lanes
        keep their cache/state **bit-exactly** (their decode_step output is
        discarded), which is what lets lanes with different prompt lengths
        — and lanes that are mid-decode or empty — ride along as padding
        work without cross-request state pollution.  Per-lane results are
        bit-identical to replaying the same (token, position) sequence
        through :meth:`decode_step` one step at a time.  Logits are never
        materialized.
        """
        def body(c, inp):
            tok, pos, write = inp
            _, c_new = self.decode_step(params, tok[:, None], c,
                                        pos[:, None])
            merged = jax.tree.map(
                lambda n, o: jnp.where(
                    write.reshape((1, -1) + (1,) * (n.ndim - 2)), n, o),
                c_new, c)
            return merged, None
        cache, _ = jax.lax.scan(body, cache,
                                (tokens, positions, write_mask))
        return cache


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
