"""Tuned attention entry point with GQA + decode handling."""
from __future__ import annotations

from typing import Optional

import jax

from repro.core.space import Workload, attention_space, fit_block
from repro.kernels.attention.kernel import flash_attention_pallas
from repro.kernels.attention.ref import attention_ref
from repro.tuning import default_session, plan_execution, tuned_kernel


def _normalize(cfg, wl, dims=None):
    """Fit flash block sizes to the actual (Lq, Lk); wl.n only carries Lk,
    so the entry point passes both lengths through ``dims``."""
    dims = dims or {}
    lq = int(dims.get("lq", wl.n))
    lk = int(dims.get("lk", wl.n))
    return {"block_q": fit_block(cfg.get("block_q", 256), lq),
            "block_k": fit_block(cfg.get("block_k", 256), lk)}


@tuned_kernel("attention", space=attention_space,
              pallas=flash_attention_pallas, reference=attention_ref,
              normalize=_normalize, variants=("flash",))
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None,
              config: Optional[dict] = None,
              interpret: Optional[bool] = None,
              use_pallas: Optional[bool] = None) -> jax.Array:
    """Multi-head attention core on flattened (B*H, L, D) tensors.

    GQA callers repeat KV heads before the call. Decode (Lq == 1) always
    takes the XLA path — it is a GEMV-shaped, memory-bound op where flash
    tiling has nothing to add.  ``scale`` is the softmax scale (None:
    1/sqrt(D)).
    """
    BH, lq, d = q.shape
    lk = k.shape[1]
    use_pallas, interpret = plan_execution(use_pallas, interpret, gate=lq > 1)
    if not use_pallas or lq == 1:
        return attention_ref(q, k, v, causal=causal, window=window,
                             scale=scale)
    cfg = default_session().resolve(
        Workload(op="attention", n=lk, batch=BH, variant="flash"),
        config=config, dims={"lq": lq, "lk": lk})
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  scale=scale, interpret=interpret, **cfg)
