"""StagePlan invariants + kernel/model conformance.

The building-block contract (ISSUE 5 acceptance): for every config in a
space's ``enumerate_valid()``, the StagePlan's ``passes``/``vmem_bytes``/
grid must match what the rebuilt scan/fft/tridiag kernels actually launch
— counted through ``driver.capture_launches`` and re-derived here from
the kernels' own BlockSpec arithmetic, so the plan cannot drift from the
execution without failing this file.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core.space import Workload, build_space
from repro.hw.profiles import TPU_V5E as V5E
from repro.kernels.blocks import driver
from repro.kernels.blocks.plan import (DEFAULT_SEQ_LIMIT, build_plan,
                                       plan_for, shift_fold_counts,
                                       stage_radices, stage_strides,
                                       wm_chunk)
from repro.tuning.registry import normalizer_for


# ---------------------------------------------------------------------------
# stage_radices invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 8, 12, 96, 97, 128, 384, 768, 1024])
@pytest.mark.parametrize("radix", [2, 3, 4, 8, 16])
def test_stage_radices_product_is_n(n, radix):
    stages = stage_radices(n, radix)
    assert math.prod(stages) == max(n, 1)
    assert all(r >= 2 for r in stages)
    # strides are the running product (the KS window after each level)
    strides = stage_strides(stages)
    for (r, s0), s1 in zip(zip(stages, strides), strides[1:]):
        assert s1 == s0 * r


def test_stage_radices_prefers_nominal_fan_in():
    assert stage_radices(512, 8) == (8, 8, 8)
    assert stage_radices(96, 8) == (8, 6, 2)      # ragged mixed-radix tail
    assert stage_radices(96, 3) == (3, 2, 2, 2, 2, 2)
    assert stage_radices(97, 2) == (97,)          # prime falls through whole


@pytest.mark.parametrize("tile,radix,folds", [
    (512, 8, (15, 6)),      # offsets 1-7, 8-56, 64 | 128-448
    (4096, 2, (7, 5)),      # offsets 1-64 | 128-2048
    (256, 4, (10, 2)),      # 1-3, 4-12, 16-48, 64 | 128, 192
    (1024, 4, (10, 5)),
    (128, 2, (7, 0)),
    (96, 8, (13, 0)),       # ragged (8, 6, 2): strides 1, 8, 48
])
def test_shift_fold_counts(tile, radix, folds):
    assert shift_fold_counts(stage_radices(tile, radix), V5E.lane_count) \
        == folds


def test_plans_report_shift_folds_of_their_fold_circuit():
    scan = Workload(op="scan", n=512, batch=2**17, variant="linrec")
    plan = plan_for(scan, {"tile_n": 512, "rows_per_program": 8,
                           "radix": 8}, profile=V5E)
    assert plan.shift_folds == (15, 6)
    res = plan.resources()
    assert (res["shift_circuit"], res["lane_folds"], res["vreg_folds"]) \
        == (1.0, 15.0, 6.0)
    # multi-pass: the resident chunk's circuit, not the carry scan's
    multi = plan_for(Workload(op="scan", n=2**16, batch=4, variant="ks"),
                     {"tile_n": 256, "rows_per_program": 2, "radix": 4},
                     profile=V5E)
    assert multi.kind == "multipass"
    assert multi.shift_folds == shift_fold_counts((4, 4, 4, 4),
                                                  V5E.lane_count)
    # butterflies, PCR, the SSD's chunk kernels and stage-less plans carry
    # no fold circuit
    for wl in (Workload(op="ssd", n=512, batch=16, variant=""),
               Workload(op="fft", n=128, batch=8, variant="stockham"),
               Workload(op="tridiag", n=128, batch=8, variant="pcr"),
               Workload(op="attention", n=256, batch=4, variant="flash")):
        cfg = build_space(wl, V5E).enumerate_valid()[0]
        plan = plan_for(wl, cfg, profile=V5E)
        assert plan.shift_folds is None
        assert plan.resources()["shift_circuit"] == 0.0


# ---------------------------------------------------------------------------
# Plan invariants over whole spaces
# ---------------------------------------------------------------------------

_WORKLOADS = [
    Workload(op="scan", n=256, batch=8, variant="ks"),
    Workload(op="scan", n=256, batch=8, variant="linrec"),
    Workload(op="tridiag", n=128, batch=8, variant="pcr"),
    Workload(op="tridiag", n=128, batch=8, variant="wm"),
    Workload(op="fft", n=128, batch=8, variant="stockham"),
    Workload(op="large_fft", n=2**15, batch=4, variant="stockham"),
    Workload(op="ssd", n=512, batch=16, variant=""),
    Workload(op="rglru", n=256, batch=32, variant=""),
]


@pytest.mark.parametrize("wl", _WORKLOADS, ids=lambda w: w.key)
def test_plan_invariants_over_valid_space(wl):
    space = build_space(wl)
    cfgs = space.enumerate_valid()
    assert cfgs
    for cfg in cfgs:
        plan = plan_for(wl, cfg)
        # the resident tile's stage sequence factors it exactly
        assert math.prod(plan.stages) == max(plan.tile_n, 1) \
            or plan.op in ("tridiag",)   # pcr/xla stage over n, radix 2
        if plan.op == "tridiag":
            assert math.prod(plan.stages) >= plan.n
        # valid configs fit the VMEM limit the kernels compile under
        assert plan.vmem_bytes <= V5E.vmem_budget
        # HBM pass count == launch count for pallas-backed plans
        if plan.launches:
            assert plan.passes == len(plan.launches)
        assert plan.seq_tiles >= 1 and plan.grid_size >= 1
        res = plan.resources()
        assert res["passes"] == plan.passes
        assert res["vmem"] == plan.vmem_bytes


@pytest.mark.parametrize("op,variant", [("scan", "ks"), ("scan", "lf"),
                                        ("scan", "linrec"), ("rglru", "")])
@pytest.mark.parametrize("n", [128, 256, 512, 1024, 2048, 4096])
def test_paper_sizes_plan_within_compile_vmem_limit(op, variant, n):
    """Regression: at the paper's 2^26-element batches every config the
    tpu_v5e space admits plans each launch within the scoped VMEM the
    kernels compile under, and the space's bound has teeth (the largest
    raw blocks are rejected from n = 2048 up, as the compiler rejected
    them)."""
    wl = Workload(op=op, n=n, batch=2 ** 26 // n, variant=variant)
    space = build_space(wl, V5E)
    valid = space.enumerate_valid()
    assert valid
    for cfg in valid:
        plan = plan_for(wl, cfg, profile=V5E)
        assert plan.check(V5E) == []
        assert all(l.vmem_bytes <= V5E.vmem_budget for l in plan.launches)
    widest = {"tile_n": n, "rows_per_program": 512, "radix": 8, "unroll": 1,
              "in_register": 0}
    widest = {k: v for k, v in widest.items()
              if k in {p.name for p in space.params}}
    over = plan_for(wl, widest, profile=V5E).vmem_bytes > V5E.vmem_budget
    assert space.is_valid(widest) == (not over)
    assert over or n < 2048


def test_multipass_triggers_past_seq_limit():
    wl = Workload(op="scan", n=1024, batch=4, variant="ks")
    cfg = {"tile_n": 64, "rows_per_program": 2, "radix": 2, "unroll": 1}
    fused = build_plan(wl, cfg)
    assert fused.kind == "fused" and fused.passes == 1
    assert fused.seq_tiles == 16 <= DEFAULT_SEQ_LIMIT
    multi = build_plan(wl, cfg, seq_limit=8)
    assert multi.kind == "multipass" and multi.passes == 3
    assert [l.name for l in multi.launches] == \
        ["chunk-scan", "carry-scan", "apply-entry"]


def test_rglru_space_prunes_unroll_without_kernel_import():
    """The static _SPACE_BUILDERS entry and the @tuned_kernel registration
    must agree: the numpy-only ML path builds rglru spaces without ever
    importing the jax kernel module, and must see the pruned space."""
    space = build_space(Workload(op="rglru", n=512, batch=1024))
    assert space.param("unroll").domain == (1,)
    assert all(c["unroll"] == 1 for c in space.enumerate_valid())


def test_wm_chunk_single_source():
    """The normalizer's chunk and the plan's chunk are the same function —
    the resolved config uniquely determines the executed kernel."""
    wl = Workload(op="tridiag", n=256, batch=8, variant="wm")
    norm = normalizer_for("tridiag")({"radix": 8}, wl, None)
    assert norm == {"radix": 8, "chunk": wm_chunk(8, 256)}


# ---------------------------------------------------------------------------
# Launch conformance: what runs is what the plan promised
# ---------------------------------------------------------------------------

def _vmem_tile(rows, cols, itemsize=4):
    """A VMEM buffer padded to the v5e (8, 128) tile."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * itemsize


def _expected_scan_vmem(rows, tile, planes, stages):
    # double-buffered f32 io blocks + the carry column + the widest fold
    # stage's f32 temporaries (r + 1 for a prefix sum, 2 r + 4 for linrec)
    fan = max(stages)
    temps = fan + 1 if planes == 2 else 2 * fan + 4
    return (2 * planes * _vmem_tile(rows, tile) + _vmem_tile(rows, 1)
            + temps * _vmem_tile(rows, tile))


def test_scan_conformance_every_valid_config():
    import jax.numpy as jnp

    from repro.kernels.scan.ops import prefix_sum
    from repro.kernels.scan.ref import scan_add_ref
    wl = Workload(op="scan", n=128, batch=4, variant="ks")
    space = build_space(wl)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 128)),
                    jnp.float32)
    ref = scan_add_ref(x)
    for cfg in space.enumerate_valid():
        norm = normalizer_for("scan")(cfg, wl, None)
        plan = plan_for(wl, norm)
        with driver.capture_launches() as rec:
            got = prefix_sum(x, config=cfg, interpret=True, use_pallas=True)
        assert len(rec) == plan.passes == 1
        launch = rec[0]
        rows, tile = norm["rows_per_program"], norm["tile_n"]
        # grid re-derived from the kernel's own BlockSpec arithmetic
        assert launch.grid == (4 // rows, 128 // tile) == plan.launches[0].grid
        assert launch.block_shape == (rows, tile)
        assert math.prod(launch.stages) == tile
        assert launch.vmem_bytes \
            == _expected_scan_vmem(rows, tile, 2, launch.stages) \
            == plan.vmem_bytes
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-4)


def test_fft_conformance_every_valid_config():
    import jax.numpy as jnp

    from repro.kernels.fft.ops import fft
    from repro.kernels.fft.ref import fft_ref
    wl = Workload(op="fft", n=64, batch=4, variant="stockham")
    space = build_space(wl)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 64)) + 1j * rng.normal(size=(4, 64)),
                    jnp.complex64)
    ref = np.asarray(fft_ref(x))
    for cfg in space.enumerate_valid():
        norm = normalizer_for("fft")(cfg, wl, None)
        plan = plan_for(wl, norm)
        with driver.capture_launches() as rec:
            got = fft(x, config=cfg, interpret=True)
        assert len(rec) == plan.passes == 1
        launch = rec[0]
        rows = plan.rows
        assert launch.grid == (4 // rows,) == plan.launches[0].grid
        assert math.prod(launch.stages) == 64
        # re/im in+out double-buffered, the coefficient tables, the
        # widest stage's 4 r + 10 temporaries, and the in-VMEM digit
        # reversal: the (64, rows) scratch and one chunk each way
        coef_rows = sum(2 * r - 1 for r in launch.stages)
        assert launch.reorder == "kernel"
        assert launch.vmem_bytes == (
            (8 + 10 + 4 * max(launch.stages)) * _vmem_tile(rows, 64)
            + 4 * _vmem_tile(coef_rows, 64)
            + 3 * _vmem_tile(64, rows) + _vmem_tile(rows, 64)) \
            == plan.vmem_bytes
        err = np.max(np.abs(np.asarray(got) - ref)) / np.max(np.abs(ref))
        assert err < 1e-4


def test_pcr_conformance_every_valid_config():
    import jax

    from repro.kernels.tridiag import ops
    from repro.kernels.tridiag.ref import random_system, thomas_ref
    wl = Workload(op="tridiag", n=64, batch=4, variant="pcr")
    space = build_space(wl)
    a, b, c, d = random_system(jax.random.PRNGKey(7), 4, 64)
    ref = np.asarray(thomas_ref(a, b, c, d))
    for cfg in space.enumerate_valid():
        norm = normalizer_for("tridiag")(cfg, wl, None)
        plan = plan_for(wl, norm)
        with driver.capture_launches() as rec:
            got = ops.solve(a, b, c, d, variant="pcr", config=cfg,
                            interpret=True)
        assert len(rec) == plan.passes == 1
        launch = rec[0]
        rows = norm["rows_per_program"]
        assert launch.grid == (4 // rows,) == plan.launches[0].grid
        # five double-buffered planes + one PCR step's 18 temporaries
        assert launch.vmem_bytes == (2 * 5 + 18) * _vmem_tile(rows, 64) \
            == plan.vmem_bytes
        assert len(launch.stages) == math.ceil(math.log2(64))
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-3,
                                   atol=1e-3)


def test_multipass_scan_add_three_launches_match_reference():
    import jax.numpy as jnp

    from repro.kernels.scan.ref import scan_add_ref
    wl = Workload(op="scan", n=512, batch=4, variant="ks")
    cfg = {"tile_n": 64, "rows_per_program": 2, "radix": 4, "unroll": 2}
    plan = build_plan(wl, cfg, seq_limit=4)
    assert plan.kind == "multipass"
    x = jnp.asarray(np.random.default_rng(2).normal(size=(4, 512)),
                    jnp.float32)
    with driver.capture_launches() as rec:
        got = driver.multipass_scan_add(x, plan, interpret=True)
    assert [l.name for l in rec] == [l.name for l in plan.launches]
    assert [l.grid for l in rec] == [l.grid for l in plan.launches]
    np.testing.assert_allclose(np.asarray(got), np.asarray(scan_add_ref(x)),
                               rtol=2e-5, atol=2e-4)


def test_multipass_scan_public_entry_bf16_single_quantization():
    """Past the seq limit the PUBLIC prefix_sum routes multipass; sub-f32
    dtypes must carry inter-launch state in f32 and quantize once at the
    output (parity with the fused path's f32 VMEM carry scratch)."""
    import jax.numpy as jnp

    from repro.kernels.scan.ops import prefix_sum

    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 16384)),
                    jnp.bfloat16)
    with driver.capture_launches() as rec:
        got = prefix_sum(x, config={"tile_n": 128, "radix": 4,
                                    "rows_per_program": 2, "unroll": 2},
                         interpret=True, use_pallas=True)
    assert len(rec) == 3 and got.dtype == jnp.bfloat16
    ref = np.cumsum(np.asarray(x, np.float64), axis=1)
    rel = np.max(np.abs(np.asarray(got, np.float64) - ref)
                 / np.maximum(np.abs(ref), 1))
    assert rel < 2e-2, rel


def test_multipass_linrec_three_launches_match_reference():
    import jax.numpy as jnp

    from repro.kernels.scan.ref import scan_linrec_assoc_ref
    wl = Workload(op="scan", n=512, batch=4, variant="linrec")
    cfg = {"tile_n": 64, "rows_per_program": 2, "radix": 2}
    plan = build_plan(wl, cfg, seq_limit=4)
    assert plan.kind == "multipass" and plan.passes == 3
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.uniform(0.8, 0.99, size=(4, 512)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(4, 512)), jnp.float32)
    with driver.capture_launches() as rec:
        got = driver.multipass_linrec(a, b, plan, interpret=True)
    assert len(rec) == 3
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(scan_linrec_assoc_ref(a, b)),
        rtol=2e-4, atol=2e-4)


def test_four_step_fft_launches_match_plan():
    import jax.numpy as jnp

    from repro.kernels.fft.ops import fft
    from repro.kernels.fft.ref import fft_ref
    n = 768                                   # past the resident tile cap
    wl = Workload(op="large_fft", n=n, batch=2, variant="stockham")
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, n)) + 1j * rng.normal(size=(2, n)),
                    jnp.complex64)
    cfg = {"radix": 4, "rows_per_program": 4, "tile_n": 4096}
    from repro.core.multikernel import max_resident_tile
    plan = plan_for(wl, normalizer_for("large_fft")(cfg, wl, None),
                    max_tile=max_resident_tile(
                        Workload(op="fft", n=n, batch=2, variant="stockham")))
    with driver.capture_launches() as rec:
        got = fft(x, config=cfg, interpret=True)
    assert len(rec) == plan.passes == len(plan.launches)
    assert [l.grid for l in rec] == [l.grid for l in plan.launches]
    ref = np.asarray(fft_ref(x))
    err = np.max(np.abs(np.asarray(got) - ref)) / np.max(np.abs(ref))
    assert err < 1e-3


def test_lf_multipass_matches_lf():
    import jax

    from repro.kernels.tridiag import ops
    from repro.kernels.tridiag.ref import random_system
    a, b, c, d = random_system(jax.random.PRNGKey(11), 4, 256)
    base = np.asarray(ops.lf_solve(a, b, c, d))
    got = np.asarray(ops.lf_solve_multipass(a, b, c, d, use_pallas=True,
                                            interpret=True))
    np.testing.assert_allclose(got, base, rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------------------
# Composite ops: one chain each
# ---------------------------------------------------------------------------

def test_chain_plans_check_clean_over_valid_spaces():
    """Every plan over the valid ssd and rglru spaces passes ``check``:
    its passes are its launches, each within the compile limit."""
    from repro.hw.profiles import get_profile
    spec = get_profile("tpu_v5e")
    for wl in (Workload(op="rglru", n=256, batch=32),
               Workload(op="ssd", n=512, batch=16, variant="chunked")):
        space = build_space(wl)
        cfgs = space.enumerate_valid()
        assert cfgs
        for cfg in cfgs:
            norm = normalizer_for(wl.op)(cfg, wl, None)
            plan = plan_for(wl, dict(cfg, **norm)
                            if wl.op == "rglru" else cfg)
            assert plan.check(spec) == []
            assert plan.passes == len(plan.launches)


def test_rglru_plan_runs_the_gate_in_the_scan_launches():
    """The gate is the scan kernel's first stage: an rglru plan has the
    launches (but for their name) and passes of the plain
    linear-recurrence plan."""
    def shapes(plan):
        return [dataclasses.replace(l, name="") for l in plan.launches]

    cfg = {"tile_n": 128, "rows_per_program": 8, "radix": 2}
    for n in (256, 2**14):            # fused, then past the seq limit
        rglru = plan_for(Workload(op="rglru", n=n, batch=32), cfg)
        scan = plan_for(Workload(op="scan", n=n, batch=32,
                                 variant="linrec"), cfg)
        assert shapes(rglru) == shapes(scan)
        assert rglru.passes == scan.passes == len(rglru.launches)


def test_ssd_plan_has_one_launch_per_phase():
    """One chunk: the intra launch alone.  Several: the intra launch and
    the sequential recurrence-and-apply launch, two HBM passes."""
    wl = Workload(op="ssd", n=512, batch=16, variant="chunked")
    one = plan_for(wl, {"tile_n": 512})
    assert one.kind == "fused" and one.passes == 1
    assert [l.name for l in one.launches] == ["ssd-intra"]
    two = plan_for(wl, {"tile_n": 128})
    assert two.kind == "two-phase" and two.passes == 2
    assert [l.name for l in two.launches] == ["ssd-intra", "ssd-state-apply"]
    assert all(l.grid == (16, 4) for l in two.launches)


def test_ssd_plan_odd_chunk_count_has_two_launches():
    """nc = 3: the sequential carry walks any chunk count, so the plan is
    the same two launches as at an even count."""
    wl = Workload(op="ssd", n=384, batch=16, variant="chunked")
    plan = plan_for(wl, {"tile_n": 128})
    assert plan.kind == "two-phase" and plan.seq_tiles == 3
    assert [l.grid for l in plan.launches] == [(16, 3), (16, 3)]


def test_ssd_plan_models_intra_work_against_per_chunk_cost():
    """The SSD plan's two modelled terms: the intra-chunk matrix work
    grows with the chunk (six bf16 passes of 2·(2·Q·128 + 128²) flops an
    element), the chunk's own costs shrink per element; other ops report
    neither."""
    wl = Workload(op="ssd", n=8192, batch=64, variant="chunked")
    res = [plan_for(wl, {"tile_n": q}, profile=V5E).resources()
           for q in (128, 256, 512, 1024)]
    intra = [r["intra_s"] for r in res]
    chunk = [r["chunk_s"] for r in res]
    assert intra == sorted(intra) and chunk == sorted(chunk, reverse=True)
    assert intra[-1] == pytest.approx(
        6 * 2 * (2 * 1024 * 128 + 128 ** 2) / V5E.peak_bf16_flops)
    assert chunk[0] == pytest.approx(
        (2 * 128 * 128 * 4 / V5E.hbm_bandwidth + 2 * V5E.grid_step_s) / 128)
    scan = plan_for(Workload(op="scan", n=8192, batch=64, variant="linrec"),
                    {"tile_n": 256}, profile=V5E).resources()
    assert scan["intra_s"] == scan["chunk_s"] == 0.0


def test_multipass_carry_unroll_clamped_at_extreme_seq_tiles():
    """Satellite fix: the workload-tuned unroll rides into the carry scan
    (l2) whose tile length is seq_tiles, not tile_n — at extreme
    seq_tiles/unroll combinations the driver must clamp, and the executed
    launches must still match the plan."""
    import jax.numpy as jnp

    from repro.kernels.scan.ref import scan_add_ref
    rng = np.random.default_rng(6)
    for tile, unroll in ((256, 8), (512, 8), (256, 4)):
        wl = Workload(op="scan", n=1024, batch=8, variant="ks")
        cfg = {"tile_n": tile, "rows_per_program": 8, "radix": 2,
               "unroll": unroll, "in_register": 0}
        plan = build_plan(wl, cfg, seq_limit=1)
        assert plan.kind == "multipass"
        assert plan.seq_tiles < unroll * 2   # the extreme corner
        x = jnp.asarray(rng.normal(size=(8, 1024)), jnp.float32)
        with driver.capture_launches() as rec:
            got = driver.multipass_scan_add(x, plan, unroll=unroll,
                                            interpret=True)
        assert [l.name for l in rec] == [l.name for l in plan.launches]
        assert [l.grid for l in rec] == [l.grid for l in plan.launches]
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(scan_add_ref(x)),
                                   rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------------------
# Model conformance: analytical + featurizer read the plan
# ---------------------------------------------------------------------------

def test_resources_are_plan_resources():
    from repro.core.analytical import resources
    for wl in _WORKLOADS:
        space = build_space(wl)
        for cfg in space.enumerate_valid()[:8]:
            assert resources(space, cfg) == plan_for(wl, cfg).resources()


def test_features_expose_plan_fields():
    from repro.tuning.ml.features import FEATURE_NAMES, featurize
    wl = Workload(op="scan", n=256, batch=8, variant="ks")
    space = build_space(wl)
    cfg = {"tile_n": 128, "rows_per_program": 2, "radix": 8, "unroll": 1,
           "in_register": 0}
    row = dict(zip(FEATURE_NAMES, featurize(space, cfg)))
    plan = plan_for(wl, cfg)
    assert row["log2_passes"] == math.log2(plan.passes) if plan.passes > 1 \
        else row["log2_passes"] == 0.0
    assert row["log2_seq_tiles"] == math.log2(plan.seq_tiles)
    assert row["ragged_tail"] == (1.0 if plan.ragged else 0.0)
    assert row["steps_per_pass"] == plan.stage_count
