"""Tuned matmul entry points (TunerSession-driven block shapes): the tiled
``matmul`` and the ``grouped_matmul`` of the MoE expert share."""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.space import Workload, fit_block, matmul_space
from repro.hw.profiles import active_profile
from repro.kernels.matmul.kernel import gmm_pallas, matmul_pallas
from repro.kernels.matmul.ref import gmm_ref, matmul_ref
from repro.tuning import default_session, plan_execution, tuned_kernel


def _gmm_vmem(bm: int, bn: int, k: int, eb: int = 2) -> int:
    """Scoped VMEM of one grouped launch: double-buffered row, weight and
    output blocks, plus the f32 product tile."""
    return 2 * (bm * k + k * bn + bm * bn) * eb + 4 * bm * bn


def _normalize_grouped(cfg, wl, m: int, k: int) -> Dict[str, int]:
    """The row tile from the config; the output columns whole where they
    fit (every row tile is then read once), else the widest lane-aligned
    block of them that fits the scoped VMEM."""
    bm = fit_block(cfg.get("block_m", 128), m)
    widths = [wl.n] + [d for d in range(wl.n - 128, 0, -128)
                       if d % 128 == 0 and wl.n % d == 0]
    budget = active_profile().vmem_budget
    bn = next((d for d in widths if _gmm_vmem(bm, d, k) <= budget),
              widths[-1])
    return {"block_m": bm, "block_n": bn}


def _normalize(cfg, wl, dims=None):
    """Fit block shapes to (M, N, K); wl carries batch=M, n=N and the entry
    point passes K through ``dims``."""
    dims = dims or {}
    m = int(dims.get("m", wl.batch))
    k = int(dims.get("k", wl.n))
    if wl.variant == "grouped":
        return _normalize_grouped(cfg, wl, m, k)
    return {"block_m": fit_block(cfg.get("block_m", 256), m),
            "block_n": fit_block(cfg.get("block_n", 256), wl.n),
            "block_k": fit_block(cfg.get("block_k", 256), k)}


@tuned_kernel("matmul", space=matmul_space, pallas=matmul_pallas,
              reference=matmul_ref, normalize=_normalize, variants=("tiled",))
def matmul(a: jax.Array, b: jax.Array, config: Optional[dict] = None,
           interpret: Optional[bool] = None,
           use_pallas: Optional[bool] = None) -> jax.Array:
    m, k = a.shape
    _, n = b.shape
    use_pallas, interpret = plan_execution(use_pallas, interpret)
    if not use_pallas:
        return matmul_ref(a, b)
    cfg = default_session().resolve(
        Workload(op="matmul", n=n, batch=m, variant="tiled"),
        config=config, dims={"m": m, "k": k})
    return matmul_pallas(a, b, interpret=interpret, **cfg)


def grouped_config(m: int, k: int, n: int,
                   config: Optional[dict] = None) -> Dict[str, int]:
    """The grouped launch's blocks for (M, K) rows times (K, N) weights,
    as the default session resolves them: ``block_m`` is the row tile
    every group is padded to."""
    return default_session().resolve(
        Workload(op="matmul", n=n, batch=m, dtype="bfloat16",
                 variant="grouped"),
        config=config, dims={"m": m, "k": k})


@tuned_kernel("matmul", space=matmul_space, pallas=gmm_pallas,
              reference=gmm_ref, variants=("grouped",))
def grouped_matmul(x: jax.Array, w: jax.Array, group_tiles: jax.Array, *,
                   config: Dict[str, int], activation: Optional[str] = None,
                   name: str = "moe_gmm", interpret: Optional[bool] = None,
                   use_pallas: Optional[bool] = None) -> jax.Array:
    """Rows of ``x`` (M, K) times their group's ``w`` (E, K, N).

    Group e holds ``group_tiles[e]`` consecutive row tiles of
    ``config["block_m"]`` rows (from ``grouped_config``), the groups in
    order from row 0; rows past the last group are not computed and read
    as garbage.  ``activation`` ("relu2" or None) is applied to the
    product.  The launch is named ``name``.
    """
    use_pallas, interpret = plan_execution(use_pallas, interpret)
    bm = config["block_m"]
    if not use_pallas:
        return gmm_ref(x, w, group_tiles * bm, activation)
    n_tiles = x.shape[0] // bm
    ends = jnp.cumsum(group_tiles)
    tile_groups = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_tiles), side="right"),
        w.shape[0] - 1).astype(jnp.int32)
    return gmm_pallas(x, w, tile_groups, ends[-1:].astype(jnp.int32),
                      block_m=bm, block_n=config["block_n"],
                      activation=activation, interpret=interpret, name=name)
