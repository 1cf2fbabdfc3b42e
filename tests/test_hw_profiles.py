"""Hardware-profile subsystem conformance (docs/hardware.md).

Three contracts:

  1. **tpu_v5e is bit-identical to the pre-profile stack** — every scalar
     and batched cost reproduces the fixture captured before the
     refactor, down to the float bit pattern (``float.hex``); the scan,
     ssd and rglru records were recaptured when the profile's per-stage
     barrier (``stage_sync_s``) went to 0.
  2. **Every registered profile is usable end to end** — for each op the
     registry knows, the profile-bounded space is non-empty and every
     sampled StagePlan / cost-model quantity is finite.
  3. **Persistence never crosses devices** — TuningDB entries and sweep
     journals recorded under one profile are invisible (DB) or rejected
     (journal) under another, and legacy records migrate to tpu_v5e.
"""
import json
import os

import numpy as np
import pytest

from repro.core.objective import CostModelObjective, TPUCostModelObjective
from repro.core.space import Workload, build_space
from repro.hw.profiles import (CPU_INTERPRET, GPU_SM, TPU_V5E,
                               HardwareProfile, active_profile, get_profile,
                               profile_distance, profiles, register_profile)
from repro.kernels.blocks.plan import plan_for
from repro.tuning.db import SCHEMA_VERSION, TuningDB
from repro.tuning.ml.dataset import SUITE
from repro.tuning.registry import known_ops
from repro.tuning.sweep import SweepJournal, run_sweep

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "cost_model_tpu_v5e.json")


def _fixture():
    with open(FIXTURE) as f:
        return json.load(f)


def _wl(rec) -> Workload:
    w = rec["workload"]
    return Workload(op=w["op"], n=w["n"], batch=w["batch"],
                    dtype=w["dtype"], variant=w["variant"])


# ---------------------------------------------------------------------------
# 1. tpu_v5e bit-identity vs the pre-refactor fixture
# ---------------------------------------------------------------------------

def test_fixture_signature_unchanged(monkeypatch):
    monkeypatch.delenv("REPRO_HW_PROFILE", raising=False)
    fx = _fixture()
    assert TPUCostModelObjective(noise=0.0).signature() == fx["signature"]
    # the alias and the profile-parameterized class are the same object
    assert TPUCostModelObjective is CostModelObjective
    assert CostModelObjective(TPU_V5E, noise=0.0).signature() \
        == fx["signature"]


@pytest.mark.parametrize("rec", _fixture()["records"],
                         ids=lambda r: r["workload"]["op"] + "_n"
                         + str(r["workload"]["n"]))
def test_tpu_v5e_costs_bit_identical(rec):
    wl = _wl(rec)
    space = build_space(wl, TPU_V5E)
    obj = CostModelObjective(TPU_V5E, noise=rec["noise"])
    cands = space.enumerate_valid()
    assert len(cands) == rec["space_size"]

    # scalar path: each sampled config reproduces its captured bits
    cfgs = [s["cfg"] for s in rec["scalar"]]
    for s in rec["scalar"]:
        assert obj(space, s["cfg"]).time_s.hex() == s["t_hex"]

    # batch path: same samples through batch_eval, plus whole-space
    # sum/min (any arithmetic drift anywhere in the space moves these)
    ts = obj.batch_eval(space, cfgs, assume_valid=True)
    assert [float(t).hex() for t in ts] == rec["batch_sample_hex"]
    all_ts = obj.batch_eval(space, cands, assume_valid=True)
    assert float(np.sum(all_ts)).hex() == rec["batch_sum_hex"]
    assert float(np.min(all_ts)).hex() == rec["batch_min_hex"]


def test_default_profile_is_tpu_v5e(monkeypatch):
    monkeypatch.delenv("REPRO_HW_PROFILE", raising=False)
    assert active_profile() is TPU_V5E
    # and the default-constructed objective/space bind to it
    assert CostModelObjective().spec is TPU_V5E
    wl = Workload(op="scan", n=256, batch=256, variant="lf")
    assert build_space(wl).spec is TPU_V5E


def test_active_profile_env_retargets(monkeypatch):
    monkeypatch.setenv("REPRO_HW_PROFILE", "gpu_sm")
    assert active_profile() is GPU_SM
    wl = Workload(op="scan", n=256, batch=256, variant="lf")
    assert build_space(wl).spec is GPU_SM
    assert CostModelObjective().signature().startswith("cost:gpu_sm:")


def test_legacy_tpu_shim_is_retired():
    """repro.hw.tpu is gone: importing it fails with a pointer at
    repro.hw.profiles (the machine model as data)."""
    with pytest.raises(ImportError, match="repro.hw.profiles"):
        import repro.hw.tpu  # noqa: F401


# ---------------------------------------------------------------------------
# 2. Every profile x every registered op: valid space, finite costs
# ---------------------------------------------------------------------------

def _representative(op: str) -> Workload:
    spec = SUITE[op]
    n = spec["train"][len(spec["train"]) // 2]
    batch = int(spec.get("batch") or max(2 ** 20 // n, 1))
    return Workload(op=op, n=n, batch=batch, variant=spec["variants"][0])


@pytest.mark.parametrize("profile_name", profiles())
@pytest.mark.parametrize("op", known_ops())
def test_profile_yields_valid_space_and_finite_plans(profile_name, op):
    prof = get_profile(profile_name)
    wl = _representative(op)
    space = build_space(wl, prof)
    assert space.spec is prof
    cands = space.enumerate_valid()
    assert cands, f"{op} space empty under {profile_name}"

    obj = CostModelObjective(prof)
    sample = cands[:: max(len(cands) // 8, 1)]
    ts = obj.batch_eval(space, sample, assume_valid=True)
    assert np.all(np.isfinite(ts)) and np.all(np.asarray(ts) > 0)
    for cfg in sample[:4]:
        plan = plan_for(wl, cfg, profile=prof)
        res = plan.resources()
        for key, val in res.items():
            assert np.isfinite(val), (op, profile_name, key, val)
        assert plan.passes >= 1
        m = obj(space, cfg)
        assert m.valid and np.isfinite(m.time_s) and m.time_s > 0


def test_profiles_produce_distinct_costs():
    """The profile actually reaches the arithmetic: the same workload is
    costed differently on different machines."""
    wl = _representative("scan")
    times = {}
    for name in profiles():
        prof = get_profile(name)
        space = build_space(wl, prof)
        cfg = space.enumerate_valid()[0]
        times[name] = CostModelObjective(prof)(space, cfg).time_s
    assert len(set(times.values())) == len(times), times


def test_profile_distance_properties():
    assert profile_distance(TPU_V5E, TPU_V5E) == 0.0
    assert profile_distance(GPU_SM, GPU_SM) == 0.0
    d = profile_distance(TPU_V5E, GPU_SM)
    assert d > 0
    assert profile_distance(GPU_SM, TPU_V5E) == pytest.approx(d)
    # the CI host model is "farther" from the TPU than the server GPU is
    assert profile_distance(TPU_V5E, CPU_INTERPRET) > d


def test_register_profile_roundtrip():
    custom = HardwareProfile(name="test_dev", lane_count=16)
    register_profile(custom)
    try:
        assert get_profile("test_dev") is custom
        assert "test_dev" in profiles()
        wl = _representative("scan")
        assert build_space(wl, custom).enumerate_valid()
    finally:
        import sys
        sys.modules["repro.hw.profiles"]._PROFILES.pop("test_dev", None)


def test_unknown_profile_raises():
    with pytest.raises(ValueError, match="unknown hardware profile"):
        get_profile("nonexistent_device")


# ---------------------------------------------------------------------------
# 3. Cross-profile persistence isolation
# ---------------------------------------------------------------------------

def test_db_entries_never_resolve_across_profiles(tmp_path):
    path = str(tmp_path / "db.json")
    wl = Workload(op="scan", n=256, batch=256, variant="lf")
    gpu_db = TuningDB(path=path, platform="gpu_sm")
    gpu_db.store(wl, {"radix": 4}, 1e-3, "bayesian", 5)

    assert TuningDB(path=path, platform="gpu_sm").lookup(wl) is not None
    assert TuningDB(path=path, platform="tpu_v5e").lookup(wl) is None
    assert TuningDB(path=path, platform="cpu_interpret").lookup(wl) is None

    # both devices' winners coexist in one file (lookup returns the config)
    tpu_db = TuningDB(path=path, platform="tpu_v5e")
    tpu_db.store(wl, {"radix": 8}, 2e-3, "bayesian", 5)
    assert TuningDB(path=path, platform="gpu_sm").lookup(wl) == {"radix": 4}
    assert TuningDB(path=path, platform="tpu_v5e").lookup(wl) == {"radix": 8}


def test_db_schema2_migrates_to_tpu_v5e(tmp_path):
    path = str(tmp_path / "db.json")
    wl = Workload(op="scan", n=256, batch=256, variant="lf")
    legacy = {"schema": 2, "entries": {
        f"tpu_v5e|{wl.key}": {"config": {"radix": 4}, "time_s": 1e-3,
                              "method": "bayesian", "evaluations": 5}}}
    with open(path, "w") as f:
        json.dump(legacy, f)

    db = TuningDB(path=path, platform="tpu_v5e")
    assert db.lookup(wl) == {"radix": 4}
    assert all(e["profile"] == "tpu_v5e" for e in db.entries().values())
    assert TuningDB(path=path, platform="gpu_sm").lookup(wl) is None
    # the next store persists the migrated envelope
    db.store(wl, {"radix": 8}, 5e-4, "bayesian", 3)
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk["schema"] == SCHEMA_VERSION
    assert all("profile" in e for e in on_disk["entries"].values())


def test_db_bare_legacy_key_rekeys_under_tpu_v5e(tmp_path):
    """Pre-platform entries had no device prefix at all; they must re-key
    under tpu_v5e on load or ``lookup`` (which always prefixes the
    session platform) could never resolve them."""
    path = str(tmp_path / "db.json")
    wl = Workload(op="scan", n=256, batch=256, variant="lf")
    with open(path, "w") as f:
        json.dump({wl.key: {"config": {"radix": 2}, "time_s": 1e-3,
                            "method": "bayesian", "evaluations": 5}}, f)

    db = TuningDB(path=path, platform="tpu_v5e")
    assert db.lookup(wl) == {"radix": 2}
    entry = db.entries()[f"tpu_v5e|{wl.key}"]
    assert entry["profile"] == "tpu_v5e"
    assert TuningDB(path=path, platform="gpu_sm").lookup(wl) is None


def test_journal_rejects_cross_profile_resume(tmp_path):
    wl = Workload(op="scan", n=128, batch=512, variant="lf")
    tpu_obj = CostModelObjective(TPU_V5E)
    space = build_space(wl, TPU_V5E)
    journal = SweepJournal.for_workload(str(tmp_path), wl, tpu_obj)
    run_sweep(space, tpu_obj, journal=journal)

    header = journal.read_header()
    assert header["profile"] == "tpu_v5e"

    # same path, different device: the header check refuses to resume
    gpu_obj = CostModelObjective(GPU_SM)
    with pytest.raises(ValueError):
        SweepJournal(journal.path).load(wl, gpu_obj)

    # the natural flow never collides: signatures differ, so the gpu
    # sweep journals to a different file in the same directory
    gpu_space = build_space(wl, GPU_SM)
    gpu_journal = SweepJournal.for_workload(str(tmp_path), wl, gpu_obj)
    assert gpu_journal.path != journal.path
    res = run_sweep(gpu_space, gpu_obj, journal=gpu_journal)
    assert res.evaluations > 0 and gpu_journal.read_header()["profile"] \
        == "gpu_sm"


def test_session_is_profile_keyed(tmp_path):
    from repro.tuning.session import TunerSession

    path = str(tmp_path / "db.json")
    wl = Workload(op="scan", n=256, batch=256, variant="lf")
    gpu = TunerSession(db_path=path, platform="gpu_sm")
    assert gpu.spec is GPU_SM
    gpu.tune(wl, method="analytical")
    assert gpu.db.lookup(wl) is not None

    tpu = TunerSession(db_path=path, platform="tpu_v5e")
    assert tpu.db.lookup(wl) is None           # other device's winner
    # resolve still answers (analytical fallback on its own profile)
    assert tpu.resolve(wl)


# ---------------------------------------------------------------------------
# 4. On a TPU backend the attached chip picks the profile
# ---------------------------------------------------------------------------

class _FakeDevice:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def _attach_tpu(monkeypatch, kind):
    import jax
    monkeypatch.delenv("REPRO_HW_PROFILE", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice(kind)])


def test_tpu_backend_profile_comes_from_device_kind(monkeypatch):
    _attach_tpu(monkeypatch, "TPU v5 lite")
    assert active_profile() is TPU_V5E


def test_tpu_backend_unknown_device_kind_raises(monkeypatch):
    _attach_tpu(monkeypatch, "TPU v99 imaginary")
    with pytest.raises(ValueError, match="TPU v99 imaginary"):
        active_profile()


def test_env_profile_still_wins_on_tpu_backend(monkeypatch):
    _attach_tpu(monkeypatch, "TPU v99 imaginary")
    monkeypatch.setenv("REPRO_HW_PROFILE", "gpu_sm")
    assert active_profile() is GPU_SM


def test_tpu_backend_runs_compiled_pallas_unless_asked(monkeypatch):
    """No silent interpret/reference routing on a TPU: the default is the
    compiled kernel; interpret mode or the reference only when asked."""
    from repro.tuning.dispatch import plan_execution
    _attach_tpu(monkeypatch, "TPU v5 lite")
    assert plan_execution(None, None) == (True, False)
    assert plan_execution(None, True) == (True, True)
    assert plan_execution(False, None) == (False, False)


def test_kernels_compile_under_the_profile_vmem_limit():
    """The scoped-VMEM limit handed to Mosaic is the budget the plans and
    spaces are bounded by."""
    from repro.kernels.blocks.primitives import compiler_params
    params = compiler_params("parallel", "arbitrary")
    assert params.vmem_limit_bytes == TPU_V5E.vmem_budget
    assert params.dimension_semantics == ("parallel", "arbitrary")
