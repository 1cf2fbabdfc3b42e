"""Tuned SSD op: chunked state-space dual as a planned chain.

`ssd(x, a, b, c)` with shapes (B, L, H, P), (B, L, H), (B, L, S), (B, L, S).
The chunk length comes from the TunerSession (op="ssd" shares the scan
space; tile_n -> chunk). On CPU hosts the pure-jnp chunked formulation runs
(same math, XLA-fused); the Pallas path is exercised in interpret mode by
tests and compiled on real TPUs.

The op executes the intra → linrec → apply *chain* the planner lays out
(``plan_for_chain``): unfused (``fuse=0``), phase B runs on the shared
``driver.linrec_rows`` building block with the enclosing resolution
threaded into it — ``ssd(config=...)`` and ``overrides(ssd=...)`` reach
the embedded block's radix instead of silently re-resolving under
``config=None``; fused (``fuse=1``), phases B + C collapse into the
sequential ``ssd_state_apply_pallas`` launch whose VMEM carry holds the
inter-chunk state (no HBM roundtrip, and odd chunk counts need no
radix-space fallback). Every launch is recorded against the chain plan,
so ``capture_launches`` traces equal ``chain.launches``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.space import Workload, fit_block, scan_space
from repro.kernels.blocks import driver
from repro.kernels.blocks.plan import plan_for_chain
from repro.kernels.ssd.kernel import (chunk_log_decay,
                                      ssd_apply_entry_pallas,
                                      ssd_intra_pallas,
                                      ssd_state_apply_pallas)
from repro.kernels.ssd.ref import ssd_chunked_ref
from repro.tuning import default_session, plan_execution, tuned_kernel


def _normalize(cfg, wl, dims=None):
    """Launch knobs: the chunk length (tuned tile_n fit to L), the radix
    the chain threads into the embedded phase-B scan, and the chain-fusion
    boundary."""
    return {"chunk": fit_block(cfg.get("tile_n", 128), wl.n),
            "radix": cfg.get("radix", 2),
            "fuse": cfg.get("fuse", 0)}


@tuned_kernel("ssd", space=scan_space, pallas=ssd_intra_pallas,
              reference=ssd_chunked_ref, normalize=_normalize,
              variants=("chunked",))
def ssd(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
        config: Optional[dict] = None, interpret: Optional[bool] = None,
        use_pallas: Optional[bool] = None) -> jax.Array:
    B, L, H, P = x.shape
    S = b.shape[-1]
    wl = Workload(op="ssd", n=L, batch=B * H, variant="chunked")
    cfg = default_session().resolve(wl, config=config)
    chunk = cfg["chunk"]
    radix = int(cfg.get("radix", 2))
    fuse = int(cfg.get("fuse", 0))
    use_pallas, interpret = plan_execution(use_pallas, interpret)
    if not use_pallas:
        return ssd_chunked_ref(x, a, b, c, chunk=chunk)

    # the chain plan (exact: the runtime state dims pin the embedded
    # phase-B launches) — what the conformance suite compares traces to
    chain = plan_for_chain(
        wl, {"tile_n": chunk, "radix": radix, "fuse": fuse}, dims=(S, P))

    # reshape to (BH, L, ...) rows; broadcast b/c over heads (n_groups=1)
    xbh = jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, L, P)
    abh = jnp.transpose(a, (0, 2, 1)).reshape(B * H, L)
    bbh = jnp.broadcast_to(b[:, None], (B, H, L, S)).reshape(B * H, L, S)
    cbh = jnp.broadcast_to(c[:, None], (B, H, L, S)).reshape(B * H, L, S)

    la = chunk_log_decay(abh, chunk)
    y_intra, state = driver.launch(
        ssd_intra_pallas, chain.launches[0], xbh, la, bbh, cbh,
        chunk=chunk, interpret=interpret)
    nc = L // chunk
    if nc <= 1:
        # single chunk: the entry state is identically zero — the intra
        # kernel alone IS the answer (the plan's one-launch "fused" kind)
        return jnp.transpose(y_intra.reshape(B, H, L, P), (0, 2, 1, 3))

    if fuse:
        # phases B + C in one sequential launch: the (S, P) VMEM carry is
        # the inter-chunk recurrence state — chunk states never round-trip
        # through HBM between the recurrence and the apply
        y = driver.launch(ssd_state_apply_pallas, chain.launches[-1],
                          y_intra, la, cbh, state, chunk=chunk,
                          interpret=interpret)
        return jnp.transpose(y.reshape(B, H, L, P), (0, 2, 1, 3))

    # phase B: inter-chunk linear recurrence (rows = BH*S*P, length nc) on
    # the shared carry-chain building block — the tuned scan kernel where
    # the (op="scan", variant="linrec") space has a valid config for nc,
    # the XLA reference otherwise (odd nc).  The enclosing resolution is
    # threaded in: the embedded block runs under the chain's radix, not a
    # fresh ``config=None`` resolution that overrides could never reach.
    a_chunk = jnp.exp(la.reshape(B * H, nc, chunk)[..., -1])   # (BH, nc)
    a_rows = jnp.broadcast_to(a_chunk[:, None, None, :], (B * H, S, P, nc))
    s_rows = jnp.transpose(state, (0, 2, 3, 1))          # (BH, S, P, nc)
    h = driver.linrec_rows(a_rows.reshape(-1, nc), s_rows.reshape(-1, nc),
                           use_pallas=True, interpret=interpret,
                           config={"tile_n": nc, "radix": radix},
                           name="ssd_carry")
    h = h.reshape(B * H, S, P, nc)
    entry = jnp.concatenate(
        [jnp.zeros_like(h[..., :1]), h[..., :-1]], axis=-1)
    entry = jnp.transpose(entry, (0, 3, 1, 2))           # (BH, nc, S, P)

    y = driver.launch(ssd_apply_entry_pallas, chain.launches[-1],
                      y_intra, la, cbh, entry, chunk=chunk,
                      interpret=interpret)
    return jnp.transpose(y.reshape(B, H, L, P), (0, 2, 1, 3))
