"""What ServeEngine tells an operator: the counters of ``EngineStats``,
the per-request stamps, the four engine spans, and the names of the
jitted programs that device traces find it by."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import get_arch
from repro.models.model import build_model
from repro.serve.engine import ServeEngine

KEY = jax.random.PRNGKey(0)

ENGINE_SPANS = ("serve.admit", "serve.prefill", "serve.step",
                "serve.harvest")


@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("qwen1.5-0.5b").reduced()
    model = build_model(cfg)
    return cfg, model, model.init(KEY)


@pytest.fixture
def span_log(monkeypatch):
    """The engine's spans in the order they open; fails if one opens
    inside another (compile spans, which land inside whatever dispatch
    compiled, are left out)."""
    log, depth = [], [0]

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            if self.name == obs.COMPILE_SPAN:
                return
            assert depth[0] == 0, f"{self.name} opened inside another span"
            depth[0] += 1
            log.append(self.name)

        def __exit__(self, *exc):
            if self.name != obs.COMPILE_SPAN:
                depth[0] -= 1

    monkeypatch.setattr(obs, "span", Recorder)
    return log


def _fake_clock(engine):
    ticks = itertools.count(1.0)
    engine.stamp_clock = lambda: float(next(ticks))


def test_engine_stats_count_the_work(small_model, span_log):
    cfg, model, params = small_model
    eng = ServeEngine(model, params, max_batch=4, max_len=64,
                      prefill_chunk=8)
    _fake_clock(eng)
    rng = np.random.default_rng(3)
    plens, budgets = (5, 9, 2), (3, 4, 2)
    for plen, new in zip(plens, budgets):
        eng.submit(rng.integers(0, cfg.vocab, size=plen).astype(np.int32),
                   max_new_tokens=new)
    done = eng.run()
    st = eng.stats()
    # every decoded token is one lane of one decode step
    assert st["lane_steps"] == sum(len(r.output) for r in done) == 9
    assert st["decode_steps"] >= max(budgets)
    assert st["decode_occupancy"] == pytest.approx(
        9 / (st["decode_steps"] * 4))
    # one 8-position chunk wrote all 4 + 8 + 1 prompt tokens
    assert st["prefill_calls"] == eng.prefill_calls == 1
    assert st["prefill_positions"] == 8
    assert st["prefill_writes"] == sum(p - 1 for p in plens) == 13
    assert st["prefill_occupancy"] == pytest.approx(13 / (8 * 4))
    assert st["host_transfers"] == eng.host_transfers
    # one span per dispatch and per fetch, none nested in another
    assert set(span_log) == set(ENGINE_SPANS)
    assert span_log.count("serve.step") == st["decode_steps"]
    assert span_log.count("serve.prefill") == st["prefill_calls"]
    assert span_log.count("serve.harvest") == st["host_transfers"]
    # submitted at ticks 1-3, all popped at tick 4
    assert st["admitted"] == 3
    assert st["queue_wait_p50_s"] == 2.0
    assert st["queue_wait_p95_s"] == pytest.approx(2.9)
    for r in done:
        assert r.t_submit < r.t_admit < r.t_first


def test_queue_wait_counts_time_behind_a_full_batch(small_model):
    cfg, model, params = small_model
    eng = ServeEngine(model, params, max_batch=1, max_len=64)
    _fake_clock(eng)
    eng.submit(np.asarray([3, 1, 4], np.int32), max_new_tokens=3)
    eng.submit(np.asarray([1, 5], np.int32), max_new_tokens=2)
    first, second = eng.run()
    # the second request waits for the only lane: admitted after the
    # host saw the first one's tokens
    assert first.t_admit < first.t_first < second.t_admit < second.t_first
    waits = sorted([first.t_admit - first.t_submit,
                    second.t_admit - second.t_submit])
    assert eng.stats()["queue_wait_p50_s"] == pytest.approx(
        np.percentile(waits, 50))
    assert eng.counters.queue_waits[1] > eng.counters.queue_waits[0]


def test_jitted_program_names_are_pinned(small_model):
    """Device traces find the engine's programs by these names
    (``jit_step``, ``jit_prefill``)."""
    cfg, model, params = small_model
    eng = ServeEngine(model, params, max_batch=2, max_len=32)
    step = eng._decode.step.lower(eng.params, eng.cache, eng._state)
    assert "module @jit_step " in step.as_text()
    toks = jnp.zeros((2, 2), jnp.int32)
    prefill = eng._decode.prefill.lower(eng.params, eng.cache, toks, toks,
                                        jnp.zeros((2, 2), bool))
    assert "module @jit_prefill " in prefill.as_text()
