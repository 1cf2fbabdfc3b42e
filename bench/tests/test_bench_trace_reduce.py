"""The trace reduction, on two small traces recorded on one TPU v5e.

``ops_scan1024_fft256.xplane.pb``: three jitted ``prefix_sum`` (ks) calls
at n=1024 and two ``fft`` calls at n=256, 2^26 elements each, every call
under a ``call`` span and its wait under a ``block`` span.
``serve_mamba2_b32.xplane.pb.gz``: four decode steps and one 4-position
prefill of mamba2-130m at 32 lanes under one ``engine.run`` span.
"""
import os
import sys

import pytest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce as tr  # noqa: E402

GiB = 1 << 30


@pytest.fixture(scope="module")
def ops_trace():
    return tr.reduce_trace(os.path.join(DATA, "ops_scan1024_fft256.xplane.pb"))


@pytest.fixture(scope="module")
def serve_trace():
    return tr.reduce_trace(os.path.join(DATA,
                                        "serve_mamba2_b32.xplane.pb.gz"))


def test_shape_bytes_reads_every_array():
    hlo = ('%k = (f32[8,128]{1,0:T(8,128)}, bf16[4]{0}) custom-call('
           'f32[8,128]{1,0} %a, c64[2,2]{1,0} %b), '
           'custom_call_target="tpu_custom_call"')
    assert tr.custom_call_bytes(hlo) == 8 * 128 * 4 * 2 + 4 * 2 + 2 * 2 * 8
    assert tr.custom_call_bytes("%f = f32[8]{0} fusion(f32[8]{0} %a)") == 0
    assert tr.op_name(hlo) == "k"
    assert tr.module_name("jit_step(15052006900383930815)") == "jit_step"


def test_pallas_calls_found_from_hlo_metadata(ops_trace):
    calls = ops_trace.pallas_calls()
    names = sorted(tr.op_name(op.hlo) for op, _ in calls)
    assert names == ["fft_pallas.1"] * 2 + ["scan_add_pallas.1"] * 3
    for op, nbytes in calls:
        if op.hlo.startswith("%scan_add_pallas"):
            # f32[65536,1024] in and out
            assert nbytes == 2 * 65536 * 1024 * 4
            assert op.dur == pytest.approx(1.2393e-3, rel=1e-3)
        else:
            # two f32[262144,256] planes in and out, two (62,256) tables
            assert nbytes == 4 * 262144 * 256 * 4 + 2 * 62 * 256 * 4
    # the XLA custom calls of the FFT (re/im split) are not Pallas
    assert all("X64Split" not in op.hlo for op, _ in calls)


def test_busy_window_and_programs(ops_trace):
    assert ops_trace.devices == [0]
    # no bench.window span in this trace: the window is the device extent
    assert ops_trace.window_s == pytest.approx(0.0579376, rel=1e-4)
    assert 0 < ops_trace.busy_s < ops_trace.window_s
    fft_s, fft_runs = ops_trace.module_time("jit_fft")
    assert fft_runs == 2 and fft_s == pytest.approx(0.0508152, rel=1e-4)
    top = ops_trace.top_ops(3)
    assert top[0][0] == "jit_fft:fft_pallas.1"
    assert top[0][1] == pytest.approx(0.0270367, rel=1e-4)


def test_idle_gaps_attributed_to_harness_spans(ops_trace):
    idle = ops_trace.idle_by_span(["call", "block"])
    total = sum(idle.values())
    assert total == pytest.approx(ops_trace.window_s - ops_trace.busy_s,
                                  rel=1e-6)
    # the host waited in block_until_ready between calls most of the time
    assert max(idle, key=idle.get) == "block"
    assert set(idle) <= {"call", "block", "no span", "between ops"}


def test_serve_programs_and_containers(serve_trace):
    step_s, steps = serve_trace.module_time("jit_step")
    prefill_s, prefills = serve_trace.module_time("jit_prefill")
    assert steps == 4 and prefills == 1
    assert step_s / steps == pytest.approx(12.12e-3, rel=1e-2)
    assert prefill_s == pytest.approx(50.09e-3, rel=1e-3)
    # while loops enclose their body's ops and are not counted twice
    assert all(not name.split(":")[1].startswith("while")
               for name, _ in serve_trace.top_ops(10))
    idle = serve_trace.idle_by_span(["engine.run"])
    assert sum(idle.values()) == pytest.approx(
        serve_trace.window_s - serve_trace.busy_s, rel=1e-6)


def test_window_span_clips_everything():
    reduced = tr.Reduced(window=(1.0, 2.0), devices=[0],
                         busy={0: tr._union([(1.2, 1.5), (1.4, 1.6)])},
                         ops=[], modules=[],
                         spans=[("call", 0.9, 1.3), ("block", 1.6, 2.0)])
    assert reduced.busy_s == pytest.approx(0.4)
    idle = reduced.idle_by_span(["call", "block"])
    assert idle["call"] == pytest.approx(0.2)
    assert idle["block"] == pytest.approx(0.4)
