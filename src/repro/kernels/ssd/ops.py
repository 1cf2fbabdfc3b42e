"""Tuned SSD op: chunked state-space dual as two planned launches.

`ssd(x, a, b, c)` with shapes (B, L, H, P), (B, L, H), (B, L, S), (B, L, S).
The chunk length comes from the TunerSession (op="ssd" shares the scan
space; tile_n -> chunk). On CPU hosts the pure-jnp chunked formulation runs
(same math, XLA-fused); the Pallas path is exercised in interpret mode by
tests and compiled on real TPUs.

The op executes the launches of its ``plan_for`` plan: the intra-chunk
kernel, then — when there is more than one chunk — the sequential
``ssd_state_apply_pallas`` launch, whose VMEM carry holds the inter-chunk
state, so chunk states never round-trip through HBM between the
recurrence and the apply, and any chunk count runs.  Every launch is
recorded against the plan, so ``capture_launches`` traces equal
``plan.launches``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.space import Workload, fit_block, scan_space
from repro.kernels.blocks import driver
from repro.kernels.blocks.plan import plan_for
from repro.kernels.ssd.kernel import (chunk_log_decay, ssd_intra_pallas,
                                      ssd_state_apply_pallas)
from repro.kernels.ssd.ref import ssd_chunked_ref
from repro.tuning import default_session, plan_execution, tuned_kernel


def _normalize(cfg, wl, dims=None):
    """Launch knob: the chunk length (tuned tile_n fit to L)."""
    return {"chunk": fit_block(cfg.get("tile_n", 128), wl.n)}


@tuned_kernel("ssd", space=scan_space, pallas=ssd_intra_pallas,
              reference=ssd_chunked_ref, normalize=_normalize,
              variants=("chunked",))
def ssd(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
        config: Optional[dict] = None, interpret: Optional[bool] = None,
        use_pallas: Optional[bool] = None) -> jax.Array:
    B, L, H, P = x.shape
    S = b.shape[-1]
    wl = Workload(op="ssd", n=L, batch=B * H, variant="chunked")
    chunk = default_session().resolve(wl, config=config)["chunk"]
    use_pallas, interpret = plan_execution(use_pallas, interpret)
    if not use_pallas:
        return ssd_chunked_ref(x, a, b, c, chunk=chunk)

    plan = plan_for(wl, {"tile_n": chunk})

    # reshape to (BH, L, ...) rows; broadcast b/c over heads (n_groups=1)
    xbh = jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, L, P)
    abh = jnp.transpose(a, (0, 2, 1)).reshape(B * H, L)
    bbh = jnp.broadcast_to(b[:, None], (B, H, L, S)).reshape(B * H, L, S)
    cbh = jnp.broadcast_to(c[:, None], (B, H, L, S)).reshape(B * H, L, S)

    la = chunk_log_decay(abh, chunk)
    y, state = driver.launch(
        ssd_intra_pallas, plan.launches[0], xbh, la, bbh, cbh,
        chunk=chunk, interpret=interpret)
    if len(plan.launches) > 1:
        # the inter-chunk recurrence and its apply in one sequential
        # launch: the (S, P) VMEM carry is the recurrence state.  With a
        # single chunk the entry state is zero and the intra kernel alone
        # is the answer.
        y = driver.launch(ssd_state_apply_pallas, plan.launches[1],
                          y, la, cbh, state, chunk=chunk,
                          interpret=interpret)
    return jnp.transpose(y.reshape(B, H, L, P), (0, 2, 1, 3))
