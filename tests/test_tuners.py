"""The three tuning methodologies + TuningDB (paper core behaviours)."""
import pytest

from repro.core import (AnalyticalTuner, BayesianTuner, CachedObjective,
                        ExhaustiveSearch, RandomSearch, TPUCostModelObjective,
                        TuningDB, Workload, build_space)
from repro.core.objective import PENALTY_TIME


def _space(n=512, batch=2**17, op="scan", variant="lf"):
    return build_space(Workload(op=op, n=n, batch=batch, variant=variant))


def test_analytical_returns_valid_config():
    for op, variant in [("scan", "lf"), ("tridiag", "wm"),
                        ("fft", "stockham"), ("attention", "flash")]:
        space = _space(op=op, variant=variant)
        cfg = AnalyticalTuner().suggest(space)
        assert space.is_valid(cfg)


def test_analytical_zero_evaluations():
    space = _space()
    obj = CachedObjective(TPUCostModelObjective())
    AnalyticalTuner().suggest(space)
    assert obj.evaluations == 0    # online methodology: no measurements


def test_exhaustive_finds_global_optimum():
    space = _space(n=256, batch=2**18)
    obj = CachedObjective(TPUCostModelObjective())
    res = ExhaustiveSearch().tune(space, obj)
    times = [obj(space, c).time_s for c in space.enumerate_valid()]
    assert res.best_time == pytest.approx(min(times))


def test_bayesian_beats_random_at_equal_budget():
    """Aggregate over several sizes/seeds: BO efficiency >= random's."""
    wins, total = 0, 0
    for n in [256, 512, 1024]:
        space = _space(n=n)
        ExhaustiveSearch().tune(
            space, CachedObjective(TPUCostModelObjective(noise=0.02)))
        for seed in range(3):
            bo = BayesianTuner(seed=seed, max_evals=20).tune(
                space, CachedObjective(TPUCostModelObjective(noise=0.02)))
            rnd = RandomSearch(max_evals=bo.evaluations, seed=seed).tune(
                space, CachedObjective(TPUCostModelObjective(noise=0.02)))
            wins += bo.best_time <= rnd.best_time + 1e-12
            total += 1
    assert wins >= total * 0.6


def test_bayesian_sliding_window_stop():
    space = _space(n=256)
    bo = BayesianTuner(seed=0, max_evals=1000, patience=5).tune(
        space, CachedObjective(TPUCostModelObjective()))
    assert bo.evaluations < space.size()
    assert bo.stopped_by in ("sliding_window", "exhausted")


def test_invalid_configs_get_penalty():
    space = _space(n=256)
    obj = TPUCostModelObjective()
    bad = {"tile_n": 999, "rows_per_program": 1, "radix": 2, "unroll": 1,
           "in_register": 0}
    m = obj(space, bad)
    assert not m.valid and m.time_s == PENALTY_TIME


def test_tuning_db_roundtrip(tmp_path):
    db = TuningDB(path=str(tmp_path / "db.json"))
    wl = Workload(op="scan", n=512, batch=1024, variant="lf")
    assert db.lookup(wl) is None
    db.store(wl, {"tile_n": 512}, 1e-4, "bayesian", 12)
    assert db.lookup(wl) == {"tile_n": 512}
    db2 = TuningDB(path=str(tmp_path / "db.json"))
    assert db2.lookup(wl) == {"tile_n": 512}   # persisted


def test_resolve_online_fallback(tmp_path):
    from repro.tuning import TunerSession
    db = TuningDB(path=str(tmp_path / "db.json"))
    wl = Workload(op="scan", n=256, batch=4096, variant="ks")
    cfg = TunerSession(db=db).resolve_raw(wl)  # miss -> analytical, instant
    assert build_space(wl).is_valid(cfg)


def test_session_tune_populates_db(tmp_path):
    from repro.tuning import TunerSession
    db = TuningDB(path=str(tmp_path / "db.json"))
    wl = Workload(op="fft", n=256, batch=2**18, variant="stockham")
    res = TunerSession(db=db).tune(wl, method="bayesian")
    assert db.lookup(wl) == res.best_config
    assert res.evaluations > 0


# ---------------------------------------------------------------------------
# Rule 4 on a barrier-free profile: the stage circuit with the fewest
# lane-shifted folds (the paper's 2^26-element scan sizes)
# ---------------------------------------------------------------------------

_PAPER_SCAN_NS = (128, 256, 512, 1024, 2048, 4096)


def _paper_suggestion(profile, op, variant, n):
    from repro.hw.profiles import get_profile
    wl = Workload(op=op, n=n, batch=2**26 // n, variant=variant)
    space = build_space(wl, get_profile(profile))
    return space, AnalyticalTuner().suggest(space)


@pytest.mark.parametrize("n", _PAPER_SCAN_NS)
@pytest.mark.parametrize("variant", ["ks", "lf", "linrec"])
def test_tpu_scan_suggestion_has_fewest_in_vreg_folds(variant, n):
    from repro.kernels.blocks.plan import plan_for
    space, cfg = _paper_suggestion("tpu_v5e", "scan", variant, n)
    wl, spec = space.workload, space.spec
    folds = {c["radix"]: plan_for(wl, c, profile=spec).shift_folds[0]
             for c in space.enumerate_valid() if c["tile_n"] == n}
    assert plan_for(wl, cfg, profile=spec).shift_folds[0] \
        == min(folds.values())
    assert cfg["radix"] == 2 and folds[2] < min(folds[4], folds[8])


@pytest.mark.parametrize("n", (64,) + _PAPER_SCAN_NS)
def test_tpu_fft_suggestion_has_fewest_butterfly_terms(n):
    """A butterfly stage's work is its complex terms, so on a barrier-free
    profile every power-of-two n resolves radix 2, with the most rows the
    smaller coefficient tables leave room for."""
    from repro.kernels.blocks.plan import plan_for
    space, cfg = _paper_suggestion("tpu_v5e", "fft", "stockham", n)
    wl, spec = space.workload, space.spec
    counts = {c["radix"]: plan_for(wl, c, profile=spec).butterfly
              for c in space.enumerate_valid()}
    best = plan_for(wl, cfg, profile=spec).butterfly
    assert best == min(counts.values())
    assert cfg["radix"] == 2
    assert all(best[0] <= t[0] and best[1] <= t[1] for t in counts.values())
    assert cfg["rows_per_program"] == max(
        c["rows_per_program"] for c in space.enumerate_valid()
        if c["radix"] == 2)


@pytest.mark.parametrize("n", _PAPER_SCAN_NS)
def test_tpu_ks_and_lf_resolve_one_config(n, tmp_path):
    """ks and lf run the same kernel, so one config compiles one program."""
    from repro.hw.profiles import get_profile
    from repro.tuning import TunerSession
    session = TunerSession(db=TuningDB(path=str(tmp_path / "db.json")),
                           spec=get_profile("tpu_v5e"))
    ks, lf = (session.resolve(
        Workload(op="scan", n=n, batch=2**26 // n, variant=v))
        for v in ("ks", "lf"))
    assert ks == lf


# (profile, op, variant, n) -> (tile_n, rows_per_program, radix, unroll,
# in_register): the suggestions rule 4 gave before it ranked fold and
# butterfly circuits.  The GPU profile pays a barrier per stage and keeps
# the larger radix; tridiagonal stages keep it on every profile.
_RULE4_PINNED = {
    ("gpu_sm", "scan", "ks", 128): (128, 512, 2, 8, 1),
    ("gpu_sm", "scan", "ks", 256): (256, 512, 4, 8, 0),
    ("gpu_sm", "scan", "ks", 512): (512, 256, 8, 8, 0),
    ("gpu_sm", "scan", "ks", 1024): (1024, 128, 4, 8, 0),
    ("gpu_sm", "scan", "ks", 2048): (2048, 128, 2, 8, 0),
    ("gpu_sm", "scan", "ks", 4096): (4096, 32, 8, 8, 0),
    ("gpu_sm", "scan", "lf", 128): (128, 512, 2, 8, 1),
    ("gpu_sm", "scan", "lf", 256): (256, 512, 4, 8, 0),
    ("gpu_sm", "scan", "lf", 512): (512, 256, 8, 8, 0),
    ("gpu_sm", "scan", "lf", 1024): (1024, 128, 4, 8, 0),
    ("gpu_sm", "scan", "lf", 2048): (2048, 128, 2, 8, 0),
    ("gpu_sm", "scan", "lf", 4096): (4096, 32, 8, 8, 0),
    ("gpu_sm", "scan", "linrec", 128): (128, 512, 2, 1, 1),
    ("gpu_sm", "scan", "linrec", 256): (256, 256, 4, 1, 0),
    ("gpu_sm", "scan", "linrec", 512): (512, 128, 8, 1, 0),
    ("gpu_sm", "scan", "linrec", 1024): (1024, 64, 4, 1, 0),
    ("gpu_sm", "scan", "linrec", 2048): (2048, 64, 2, 1, 0),
    ("gpu_sm", "scan", "linrec", 4096): (4096, 16, 8, 1, 0),
    ("gpu_sm", "fft", "stockham", 64): (64, 256, 8, 4, 0),
    ("gpu_sm", "fft", "stockham", 128): (128, 256, 2, 4, 0),
    ("gpu_sm", "fft", "stockham", 256): (256, 64, 16, 4, 0),
    ("gpu_sm", "fft", "stockham", 512): (512, 64, 8, 4, 0),
    ("gpu_sm", "fft", "stockham", 1024): (1024, 32, 4, 4, 0),
    ("gpu_sm", "fft", "stockham", 2048): (2048, 32, 2, 4, 0),
    ("gpu_sm", "fft", "stockham", 4096): (4096, 4, 8, 4, 0),
    ("tpu_v5e", "tridiag", "pcr", 64): (64, 256, 2, 4, 1),
    ("tpu_v5e", "tridiag", "pcr", 128): (128, 256, 2, 4, 1),
    ("tpu_v5e", "tridiag", "pcr", 256): (256, 256, 2, 4, 1),
    ("tpu_v5e", "tridiag", "pcr", 512): (512, 256, 2, 4, 1),
    ("tpu_v5e", "tridiag", "pcr", 1024): (1024, 256, 2, 4, 1),
    ("tpu_v5e", "tridiag", "wm", 64): (64, 1, 8, 1, 0),
    ("tpu_v5e", "tridiag", "wm", 128): (128, 1, 2, 1, 0),
    ("tpu_v5e", "tridiag", "wm", 256): (256, 1, 4, 1, 0),
    ("tpu_v5e", "tridiag", "wm", 512): (512, 1, 8, 1, 0),
    ("tpu_v5e", "tridiag", "wm", 1024): (1024, 1, 4, 1, 0),
    ("tpu_v5e", "rglru", "", 128): (128, 512, 2, 1, 1),
    ("tpu_v5e", "rglru", "", 256): (256, 512, 2, 1, 1),
    ("tpu_v5e", "rglru", "", 512): (512, 512, 2, 1, 1),
    ("tpu_v5e", "rglru", "", 1024): (1024, 512, 2, 1, 1),
    ("tpu_v5e", "rglru", "", 2048): (2048, 256, 2, 1, 0),
    ("tpu_v5e", "rglru", "", 4096): (4096, 128, 2, 1, 0),
}


@pytest.mark.parametrize("profile,op,variant,n", sorted(_RULE4_PINNED))
def test_rule4_suggestions_unchanged_where_stages_sync_or_do_not_fold(
        profile, op, variant, n):
    _, cfg = _paper_suggestion(profile, op, variant, n)
    knobs = ("tile_n", "rows_per_program", "radix", "unroll", "in_register")
    assert tuple(cfg[k] for k in knobs) == _RULE4_PINNED[profile, op,
                                                         variant, n]


# (profile, op, n, batch) -> (tile_n, rows_per_program, radix, unroll,
# in_register): the SSD and RG-LRU suggestions at the registered configs'
# shapes — granite-4.0-h-micro (64 heads), mamba2-130m (24 heads) and
# recurrentgemma-9b (lru width 4096) — as the model ranked them when each
# op still carried a second, unfused chain.
_CHAIN_PINNED = {
    ("cpu_interpret", "ssd", 8192, 64): (128, 64, 2, 8, 0),
    ("cpu_interpret", "ssd", 256, 24): (256, 4, 4, 8, 0),
    ("cpu_interpret", "ssd", 1024, 24): (128, 8, 2, 8, 0),
    ("cpu_interpret", "ssd", 8192, 24): (128, 8, 2, 8, 0),
    ("cpu_interpret", "rglru", 256, 4096): (256, 128, 4, 1, 0),
    ("cpu_interpret", "rglru", 2048, 4096): (2048, 32, 2, 1, 0),
    ("cpu_interpret", "rglru", 8192, 4096): (8192, 8, 2, 1, 0),
    ("gpu_sm", "ssd", 8192, 64): (128, 64, 2, 8, 0),
    ("gpu_sm", "ssd", 256, 24): (256, 4, 4, 8, 0),
    ("gpu_sm", "ssd", 1024, 24): (128, 8, 2, 8, 0),
    ("gpu_sm", "ssd", 8192, 24): (128, 8, 2, 8, 0),
    ("gpu_sm", "rglru", 256, 4096): (256, 256, 4, 1, 0),
    ("gpu_sm", "rglru", 2048, 4096): (2048, 64, 2, 1, 0),
    ("gpu_sm", "rglru", 8192, 4096): (8192, 16, 2, 1, 0),
    ("tpu_v5e", "ssd", 8192, 64): (256, 64, 4, 8, 0),
    ("tpu_v5e", "ssd", 256, 24): (128, 8, 2, 8, 1),
    ("tpu_v5e", "ssd", 1024, 24): (256, 8, 4, 8, 1),
    ("tpu_v5e", "ssd", 8192, 24): (256, 8, 4, 8, 0),
    ("tpu_v5e", "rglru", 256, 4096): (256, 512, 2, 1, 1),
    ("tpu_v5e", "rglru", 2048, 4096): (2048, 256, 2, 1, 0),
    ("tpu_v5e", "rglru", 8192, 4096): (8192, 64, 2, 1, 0),
}


@pytest.mark.parametrize("profile,op,n,batch", sorted(_CHAIN_PINNED))
def test_ssd_and_rglru_suggestions_pinned(profile, op, n, batch):
    from repro.hw.profiles import get_profile
    variant = "chunked" if op == "ssd" else ""
    space = build_space(Workload(op, n=n, batch=batch, variant=variant),
                        get_profile(profile))
    cfg = AnalyticalTuner().suggest(space)
    knobs = ("tile_n", "rows_per_program", "radix", "unroll", "in_register")
    assert tuple(cfg[k] for k in knobs) == _CHAIN_PINNED[profile, op, n,
                                                         batch]


def test_tpu_ssd_chunk_ranks_by_intra_chunk_work():
    """granite-4.0-h-micro's SSD at 8,192 tokens (64 heads): chunk 256,
    the fastest of 128 ... 1024 on a TPU v5e (PERF.md).  Fewest chunks
    (1024) would quadruple the intra-chunk matmuls."""
    from repro.hw.profiles import get_profile
    space = build_space(Workload("ssd", n=8192, batch=64, variant="chunked"),
                        get_profile("tpu_v5e"))
    cfg = AnalyticalTuner().suggest(space)
    assert cfg["tile_n"] == 256
