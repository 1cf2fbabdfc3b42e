"""Oracle for the Mamba-2 SSD (state-space dual) core.

Per (batch, head): state h in R^{d_state x d_head}; for t = 1..L:
    h_t = a_t * h_{t-1} + b_t x_t^T        (a_t scalar decay per head-step)
    y_t = c_t^T h_t
with b_t, c_t in R^{d_state}, x_t in R^{d_head}; head h reads the b and c
of its group h // (H / G).

Shapes (grouped layout):
    x: (B, L, H, P)   a: (B, L, H)   b: (B, L, G, S)   c: (B, L, G, S)
    y: (B, L, H, P)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def per_head(v: jax.Array, heads: int) -> jax.Array:
    """(..., G, S) group rows -> (..., heads, S): head h reads group
    h // (heads / G)."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssd_ref(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array) -> jax.Array:
    """Sequential lax.scan ground truth."""
    B, L, H, P = x.shape
    S = b.shape[-1]

    def step(h, inp):
        x_t, a_t, b_t, c_t = inp          # (B,H,P), (B,H), (B,H,S), (B,H,S)
        h = a_t[..., None, None] * h + jnp.einsum("bhs,bhp->bhsp", b_t, x_t)
        y = jnp.einsum("bhs,bhsp->bhp", c_t, h)
        return h, y

    h0 = jnp.zeros((B, H, S, P), x.dtype)
    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(a, 1, 0),
          jnp.moveaxis(per_head(b, H), 1, 0),
          jnp.moveaxis(per_head(c, H), 1, 0))
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1)


def ssd_chunked_ref(x, a, b, c, chunk: int = 64):
    """Chunked (quadratic-intra + scanned-inter) formulation in pure jnp.

    The parallel algorithm the Pallas kernel implements:
      intra: y_intra[t] = sum_{s<=t, same chunk} (prod_{u in (s,t]} a_u)
                          * (c_t . b_s) * x_s
      inter: chunk states scanned with the linear-recurrence monoid, then
             broadcast into each chunk through the decay prefix.
    """
    B, L, H, P = x.shape
    G, S = b.shape[-2:]
    R = H // G                                                  # heads a group
    Q = chunk
    nc = L // Q
    xc = x.reshape(B, nc, Q, G, R, P)
    ac = a.reshape(B, nc, Q, H)
    bc = b.reshape(B, nc, Q, G, S)
    cc = c.reshape(B, nc, Q, G, S)

    # cumulative log-decay within chunk: A[t] = prod_{u<=t} a_u
    la = jnp.cumsum(jnp.log(jnp.maximum(ac, 1e-30)), axis=2)   # (B,nc,Q,H)
    A = jnp.exp(la)
    # decay from s+1..t: A[t]/A[s]; the mask is applied INSIDE the exp —
    # exp of a masked-out positive difference overflows to inf and the
    # backward pass hits 0 * inf = NaN otherwise
    diff = la[:, :, :, None, :] - la[:, :, None, :, :]            # (B,nc,t,s,H)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    ratio = jnp.exp(jnp.where(mask[None, None, :, :, None], diff, -1e30))
    cb = jnp.einsum("bntgs,bnqgs->bngtq", cc, bc)               # (B,nc,G,t,s)
    ratio = ratio.reshape(B, nc, Q, Q, G, R)
    y_intra = jnp.einsum("bngtq,bntqgr,bnqgrp->bntgrp", cb, ratio, xc)

    # chunk-exit states: sum_s (prod_{u>s} a) b_s x_s^T
    decay_to_end = jnp.exp(la[:, :, -1:, :] - la).reshape(B, nc, Q, G, R)
    state = jnp.einsum("bnqgs,bnqgr,bnqgrp->bngrsp", bc, decay_to_end, xc)
    state = state.reshape(B, nc, H, S, P)
    a_chunk = A[:, :, -1, :]                                    # (B,nc,H)

    # inter-chunk linear recurrence over chunk index
    def combine(l, r):
        al, sl = l
        ar, sr = r
        return al * ar, ar[..., None, None] * sl + sr

    a_scan, s_scan = jax.lax.associative_scan(
        combine, (jnp.moveaxis(a_chunk, 1, 0), jnp.moveaxis(state, 1, 0)),
        axis=0)
    entry = jnp.concatenate(
        [jnp.zeros_like(s_scan[:1]), s_scan[:-1]], axis=0)      # state entering chunk
    entry = jnp.moveaxis(entry, 0, 1)                           # (B,nc,H,S,P)

    # inter contribution: y[t] += c_t . (A[t] * entry)
    y_inter = jnp.einsum("bnqgs,bnqgr,bngrsp->bnqgrp", cc,
                         A.reshape(B, nc, Q, G, R),
                         entry.reshape(B, nc, G, R, S, P))
    return (y_intra + y_inter).reshape(B, L, H, P)
