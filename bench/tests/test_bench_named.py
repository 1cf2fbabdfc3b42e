"""Readers of what the program names itself: its spans (``serve.*``,
``repro.compile``) and its kernels (``scan_ks``, ``scan_lf``,
``scan_linrec``).

On the CPU: the program's spans survive the trace reduction.  On two
small traces recorded on one TPU v5e:

``ops_scan_variants.xplane.pb``: ``prefix_sum`` ks and lf and
``linear_recurrence`` at n=1024 (2^26 elements), each called twice under
``call`` / ``block`` spans, then one fresh jit compile, all inside a
``bench.window`` span.
``serve_mamba2_spans.xplane.pb.gz``: mamba2-130m at 32 lanes, three
``engine.run(max_steps=4)`` calls that admit six requests, with the
output tokens the host saw in the trace in ``serve_mamba2_spans.json``.
"""
import json
import os
import sys
import tempfile
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
sys.path.insert(0, BENCH)

import trace_reduce as tr  # noqa: E402
from harness import counts, named  # noqa: E402
from harness.cells import load_module  # noqa: E402
from harness.device import PEAKS  # noqa: E402
from harness.run_context import MetricContext  # noqa: E402

V5E = PEAKS["TPU v5 lite"]
SERVE_SPANS = ("serve.admit", "serve.prefill", "serve.step", "serve.harvest")


def read(metric, trace, readings=None, max_batch=32, config=None):
    cell = types.SimpleNamespace(settings={"engine": {"max_batch": max_batch}},
                                 config=config)
    ctx = MetricContext(trace=trace, readings=readings or {}, peaks=V5E,
                        cell=cell)
    return load_module(BENCH, "metrics", metric).read(ctx)


@pytest.fixture(scope="module")
def variants_trace():
    return tr.reduce_trace(os.path.join(DATA, "ops_scan_variants.xplane.pb"))


@pytest.fixture(scope="module")
def spans_trace():
    return tr.reduce_trace(os.path.join(DATA,
                                        "serve_mamba2_spans.xplane.pb.gz"))


@pytest.fixture(scope="module")
def spans_readings():
    with open(os.path.join(DATA, "serve_mamba2_spans.json")) as f:
        return json.load(f)


def test_program_spans_survive_the_reduction():
    """A tiny engine under a CPU profiler trace: every engine span and the
    compile span reach the reduced trace by name."""
    import jax
    from repro import obs
    from repro.configs.base import get_arch
    from repro.models.model import build_model
    from repro.serve.engine import ServeEngine

    cfg = get_arch("qwen1.5-0.5b").reduced()
    model = build_model(cfg)
    engine = ServeEngine(model, model.init(jax.random.PRNGKey(0)),
                         max_batch=2, max_len=32)
    engine.submit(np.asarray([3, 1, 4], np.int32), max_new_tokens=3)
    trace_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            engine.run()
            jax.jit(lambda v: v * 5.0 - 2.0)(np.ones(4, np.float32))
    finally:
        jax.profiler.stop_trace()
    reduced = tr.reduce_trace(trace_dir)
    names = {name for name, _, _ in reduced.spans}
    assert set(SERVE_SPANS) <= names
    assert obs.COMPILE_SPAN in names
    assert read("compiles.chat", reduced) >= 1


def test_variant_shares_weigh_up_to_pallas_roofline(variants_trace):
    """ks and lf compile to one program at n=1024, so the trace names all
    four of their runs after one of them; each variant takes half."""
    calls = variants_trace.pallas_calls()
    names = sorted(tr.op_name(op.hlo) for op, _ in calls)
    assert names == ["scan_ks.1"] * 4 + ["scan_linrec.1"] * 2
    ks, lf, linrec = (read(f"pallas_roofline.{v}", variants_trace)
                      for v in ("ks", "lf", "linrec"))
    assert ks == lf and 0 < linrec < ks < 100
    add_s = sum(op.dur for op, _ in calls if "scan_ks" in op.hlo)
    linrec_s = sum(op.dur for op, _ in calls if "scan_linrec" in op.hlo)
    weighted = (ks * add_s / 2 + lf * add_s / 2 + linrec * linrec_s) \
        / (add_s + linrec_s)
    assert weighted == pytest.approx(read("pallas_roofline", variants_trace),
                                     rel=1e-9)


def _kernel(name, rows, dur):
    shape = f"f32[{rows},128]{{1,0}}"
    hlo = (f"%{name} = {shape} custom-call({shape} %x), "
           'custom_call_target="tpu_custom_call"')
    return tr.Op(0, "jit__unknown", hlo, 0.0, dur)


def test_twin_variants_split_only_shared_launches():
    """Where ks and lf show under their own names, each reads its own
    kernels; a launch that shows under one name only is shared."""
    ops = [_kernel("scan_ks.1", 8, 1e-3), _kernel("scan_lf.1", 8, 2e-3),
           _kernel("scan_ks.1", 16, 4e-3)]
    trace = tr.Reduced(window=(0.0, 1.0), devices=[0], busy={0: []},
                       ops=ops, modules=[], spans=[])
    nbytes = 2 * 8 * 128 * 4
    floor = nbytes / V5E["hbm_bytes_per_s"]
    ks = read("pallas_roofline.ks", trace)
    lf = read("pallas_roofline.lf", trace)
    # n=8: each its own; n=16 shows as ks only: half to each
    assert ks == pytest.approx(100 * 2 * floor / (1e-3 + 2e-3))
    assert lf == pytest.approx(100 * 2 * floor / (2e-3 + 2e-3))
    assert read("pallas_roofline.linrec", trace) is None


def test_compile_spans_counted(variants_trace, spans_trace):
    from repro import obs
    obs.watch_compiles()
    # one fresh jit compile in the ops trace, none in the engine's
    assert read("compiles.ops", variants_trace) == 1
    assert read("compiles.gen", spans_trace) == 0


def test_serving_readers_on_engine_trace(spans_trace, spans_readings):
    names = {name for name, _, _ in spans_trace.spans}
    assert set(SERVE_SPANS) <= names
    readings = {"traced_tokens": spans_readings["traced_tokens"]}
    lanes = spans_readings["max_batch"]
    for cell in ("chat", "gen"):
        occupancy = read(f"decode_occupancy_pct.{cell}", spans_trace,
                         readings, lanes)
        assert 0 < occupancy <= 100
        steps = len(named.spans(spans_trace, "serve.step"))
        assert occupancy == pytest.approx(
            100 * readings["traced_tokens"]["output"] / (steps * lanes))
        wait = read(f"harvest_wait_pct.{cell}", spans_trace)
        assert 0 < wait < 100
        assert wait == pytest.approx(
            100 * named.span_seconds(spans_trace, "serve.harvest")
            / spans_trace.window_s)


@pytest.mark.parametrize("cell", ["gen"])
def test_serving_mfu_on_engine_trace(spans_trace, spans_readings, cell):
    """Traced prompt and output tokens times the model's flops a token,
    over the window and the bf16 peak.  The recorded run's prompt tokens
    are the ones its engine wrote (``prefill_writes``)."""
    with open(os.path.join(BENCH, "configs", "mamba2-130m.json")) as f:
        cfg = json.load(f)
    tokens = {"prompt": spans_readings["stats"]["prefill_writes"],
              "output": spans_readings["traced_tokens"]["output"]}
    value = read(f"mfu_pct.{cell}", spans_trace, {"traced_tokens": tokens},
                 config=cfg)
    flops = (tokens["prompt"] * counts.mamba2_flops_per_token(cfg, False)
             + tokens["output"] * counts.mamba2_flops_per_token(cfg, True))
    assert value == pytest.approx(
        100 * flops / spans_trace.window_s / V5E["bf16_flops_per_s"])
    assert 0 < value < 100
    assert read(f"mfu_pct.{cell}", spans_trace, {}, config=cfg) is None


def test_readers_silent_without_the_program_marks(monkeypatch):
    """On traces of a program that names neither its kernels nor its
    engine work, and counts no compiles, the readers return None."""
    ops = tr.reduce_trace(os.path.join(DATA, "ops_scan1024_fft256.xplane.pb"))
    serve = tr.reduce_trace(os.path.join(DATA,
                                         "serve_mamba2_b32.xplane.pb.gz"))
    for v in ("ks", "lf", "linrec"):
        assert read(f"pallas_roofline.{v}", ops) is None
    tokens = {"traced_tokens": {"output": 128, "prompt": 0}}
    for cell in ("chat", "gen"):
        assert read(f"decode_occupancy_pct.{cell}", serve, tokens) is None
        assert read(f"harvest_wait_pct.{cell}", serve) is None
    from repro import obs
    monkeypatch.setattr(obs, "_watching", False)
    for cell in ("ops", "chat", "gen"):
        assert read(f"compiles.{cell}", ops) is None


def test_kernel_families():
    assert named.in_family("scan_ks.1", "scan_ks")
    assert named.in_family("scan_ks_chunk.3", "scan_ks")
    assert not named.in_family("scan_ks.1", "scan_lf")
    assert not named.in_family("scan_linrec_apply.1", "scan_lf")
    assert not named.in_family("scan_add_pallas.1", "scan_ks")
    # an unnamed linear_recurrence launch is not the named family
    assert not named.in_family("scan_linrec_pallas.1", "scan_linrec")
