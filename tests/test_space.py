"""Search-space construction, validity, and encoding."""
import pytest

pytest.importorskip("hypothesis")  # optional dep: property tests
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core import Workload, build_space
from repro.core.space import pow2_range
from repro.kernels.blocks.plan import plan_for


def test_pow2_range():
    assert pow2_range(1, 8) == (1, 2, 4, 8)
    assert pow2_range(128, 128) == (128,)


@pytest.mark.parametrize("op,variant", [
    ("scan", "lf"), ("scan", "ks"), ("tridiag", "wm"), ("tridiag", "pcr"),
    ("tridiag", "cr"), ("tridiag", "lf"), ("fft", "stockham"),
    ("attention", "flash"), ("matmul", "tiled"),
])
def test_spaces_nonempty_and_valid(op, variant):
    wl = Workload(op=op, n=1024, batch=4096, variant=variant)
    space = build_space(wl)
    cfgs = space.enumerate_valid()
    assert cfgs, f"{op} space empty"
    for cfg in cfgs[:50]:
        assert space.is_valid(cfg)


def test_constraints_reject_oversized_vmem():
    wl = Workload(op="scan", n=4096, batch=2**20)
    space = build_space(wl)
    huge = {"tile_n": 4096, "rows_per_program": 512, "radix": 2,
            "unroll": 1, "in_register": 0}
    small = dict(huge, rows_per_program=8)
    # the planned launch (double-buffered blocks + fold temporaries) is
    # what the compiler is asked to fit into the scoped VMEM limit
    budget = space.spec.vmem_budget
    assert plan_for(wl, huge).vmem_bytes > budget
    assert plan_for(wl, small).vmem_bytes <= budget
    assert not space.is_valid(huge) and space.is_valid(small)


def test_in_register_rule():
    wl = Workload(op="scan", n=2048, batch=4096)
    space = build_space(wl)
    cfg = {"tile_n": 2048, "rows_per_program": 1, "radix": 2,
           "unroll": 1, "in_register": 1}
    assert not space.is_valid(cfg)   # 2048 > lane*sublane budget


def test_wm_only_tridiag_radix():
    for variant, radices in [("wm", {2, 4, 8}), ("pcr", {2})]:
        wl = Workload(op="tridiag", n=256, batch=1024, variant=variant)
        space = build_space(wl)
        seen = {c["radix"] for c in space.enumerate_valid()}
        assert seen <= radices


def test_encode_in_unit_cube():
    wl = Workload(op="fft", n=1024, batch=8192, variant="stockham")
    space = build_space(wl)
    for cfg in space.enumerate_valid():
        for c in space.encode(cfg):
            assert -1e-9 <= c <= 1 + 1e-9


@given(n=st.sampled_from([128, 256, 512, 1024, 2048]),
       batch=st.sampled_from([256, 4096, 65536]))
@settings(max_examples=10, deadline=None)
def test_scan_space_valid_configs_satisfy_constraints(n, batch):
    wl = Workload(op="scan", n=n, batch=batch)
    space = build_space(wl)
    for cfg in space.enumerate_valid():
        assert cfg["tile_n"] <= n and n % cfg["tile_n"] == 0
        assert batch % cfg["rows_per_program"] == 0
