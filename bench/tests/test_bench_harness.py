"""The harness on the CPU: generators, statistics, lookup by name, and
the device check."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import cells, device, stats, traffic  # noqa: E402

BIG_SEED = 2 ** 31 + 12345


def _mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_open_loop_is_seeded_and_keeps_its_clips():
    """Token ids come from the seed; sizes and arrivals never do."""
    mix = _mix("chat")
    a = traffic.open_loop(mix, 3.0, 50.0, 50277, BIG_SEED)
    b = traffic.open_loop(mix, 3.0, 50.0, 50277, BIG_SEED)
    c = traffic.open_loop(mix, 3.0, 50.0, 50277, BIG_SEED + 1)
    assert [(r.arrival, r.max_new_tokens) for r in a] == \
        [(r.arrival, r.max_new_tokens) for r in b]
    assert all(np.array_equal(r.prompt, s.prompt) for r, s in zip(a, b))
    # another seed: the same sizes and arrivals, other token ids
    assert [(r.arrival, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.arrival, len(r.prompt), r.max_new_tokens) for r in c]
    assert not all(np.array_equal(r.prompt, s.prompt) for r, s in zip(a, c))
    for reqs in (a, c):
        assert all(16 <= len(r.prompt) <= 512 for r in reqs)
        assert all(16 <= r.max_new_tokens <= 256 for r in reqs)
        assert all(0 <= t < 50277 for r in reqs for t in r.prompt)
        assert all(0 <= r.arrival < 50.0 for r in reqs)
    # the sizes are the distribution's quantiles, whatever the order
    one = traffic.stratified_lengths(mix["prompt"], 200, traffic.rng_for(1))
    two = traffic.stratified_lengths(mix["prompt"], 200, traffic.rng_for(2))
    assert sorted(one) == sorted(two) and list(one) != list(two)
    med = np.median([len(r.prompt) for r in a])
    assert 100 <= med <= 160


def test_closed_loop_rounds_are_the_same_work_for_every_seed():
    mix = _mix("gen")
    a = traffic.closed_loop(mix, 64, 4, 50277, BIG_SEED)
    b = traffic.closed_loop(mix, 64, 4, 50277, 7)
    assert len(a) == 64 and all(len(q) == 4 for q in a)
    for r in range(4):
        outs_a = sorted(q[r].max_new_tokens for q in a)
        outs_b = sorted(q[r].max_new_tokens for q in b)
        assert outs_a == outs_b
        assert all(256 <= o <= 4096 for o in outs_a)
        assert all(16 <= len(q[r].prompt) <= 64 for q in a)
    assert [[x.max_new_tokens for x in q] for q in a] == \
        [[x.max_new_tokens for x in q] for q in b]
    again = traffic.closed_loop(mix, 64, 4, 50277, BIG_SEED)
    assert all(np.array_equal(x.prompt, y.prompt)
               for qa, qb in zip(a, again) for x, y in zip(qa, qb))


def test_call_rounds_hold_every_call_once_a_round():
    calls = [("fft", n) for n in (64, 128, 256)] + [("tridiag.pcr", 64)]
    sched = traffic.call_rounds(calls, BIG_SEED, 5)
    for r in range(5):
        assert sorted(sched[4 * r:4 * r + 4]) == sorted(calls)
    assert sched == traffic.call_rounds(calls, BIG_SEED, 5)


def test_jax_key_uses_all_the_seed():
    k1 = traffic.jax_key(BIG_SEED)
    k2 = traffic.jax_key(BIG_SEED + (1 << 32))
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    assert np.array_equal(np.asarray(k1), np.asarray(traffic.jax_key(BIG_SEED)))


def test_percentile_and_rate_use_every_sample():
    values = list(range(1, 101))
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 100) == 100
    assert stats.percentile([5.0], 95) == 5.0
    # 19 fast samples and one slow: the p95 is a fast one, the p100 not
    assert stats.percentile([1.0] * 19 + [9.0], 95) == 1.0
    assert stats.percentile([1.0] * 19 + [9.0], 100) == 9.0
    assert stats.rate(300, 30.0) == 10.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def _copy_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return str(tmp_path)


def test_new_cell_config_and_metric_are_found_by_name(tmp_path):
    root = _copy_benchmark(tmp_path)
    before = {p: open(p).read() for p in _files(root)}
    bench_dir = os.path.join(root, "bench")
    with open(os.path.join(bench_dir, "configs", "other-ops.json"), "w") as f:
        json.dump({"total_elems": 1024, "reference": "prefix_ops"}, f)
    with open(os.path.join(bench_dir, "traffic", "tiny.json"), "w") as f:
        json.dump({"driver": "ops", "calls": [{"op": "fft", "sizes": [8]}],
                   "check": {"sample_per_op": 1}}, f)
    with open(os.path.join(bench_dir, "workloads", "ops.tiny.json"), "w") as f:
        json.dump({"limits": {"rel_err.fft": 1e-4}}, f)
    with open(os.path.join(bench_dir, "metrics", "calls_seen.py"), "w") as f:
        f.write("def read(ctx):\n    return float(len(ctx.readings['calls']))\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "other-ops", "source": "test",
                             "file": "bench/configs/other-ops.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "ops.tiny", "config": "other-ops",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "batch_ms":
            m["workloads"].append("ops.tiny")
    bench["per_layer"].append({"name": "calls_seen", "unit": "calls",
                               "better": "higher", "source": "program_span",
                               "layer": "kernel ops", "moves": "batch_ms",
                               "workloads": ["ops.tiny"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = cells.load_cell("ops.tiny", root)
    assert cell.config["total_elems"] == 1024
    assert cell.traffic["calls"][0]["op"] == "fft"
    assert cell.settings["limits"] == {"rel_err.fft": 1e-4}
    assert cell.driver.__file__.endswith(os.path.join("drivers", "ops.py"))
    assert [m["name"] for m in cell.per_layer] == ["calls_seen"]
    assert [m["name"] for m in cell.end_to_end] == ["batch_ms", "setup_s"]
    reader = cell.module("metrics", "calls_seen")
    ctx = type("C", (), {"readings": {"calls": [1, 2, 3]}})()
    assert reader.read(ctx) == 3.0
    # no file that was there before changed, BENCHMARK.json apart
    for path, text in before.items():
        if not path.endswith("BENCHMARK.json"):
            assert open(path).read() == text, path
    # the real cells are still found as they were
    assert cells.load_cell("ops.scan", root).traffic["driver"] == "ops"


def _files(root):
    for base, _, names in os.walk(root):
        for n in names:
            if not n.endswith(".pyc"):
                yield os.path.join(base, n)


class _Dev:
    def __init__(self, platform, kind="TPU v5 lite"):
        self.platform, self.device_kind = platform, kind


def test_device_check_refuses_the_cpu_and_unknown_chips():
    with pytest.raises(device.DeviceError, match="no TPU"):
        device.check_devices([_Dev("cpu", "cpu")], 1)
    with pytest.raises(device.DeviceError, match="needs 4 chips"):
        device.check_devices([_Dev("tpu")], 4)
    with pytest.raises(device.DeviceError, match="no published peaks"):
        device.check_devices([_Dev("tpu", "TPU v9")], 1)
    with pytest.raises(device.DeviceError):
        device.check_devices([], 1)
    info = device.check_devices([_Dev("tpu")], 1)
    assert info == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert device.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "ops.scan", "--seed", str(BIG_SEED), "--seconds", "1",
         "--trace", "0"], env=env, cwd=str(tmp_path), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
    assert not os.path.exists(os.path.join(ROOT, ".bench_cache", "jax",
                                           "should-not-exist"))


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    root = _copy_benchmark(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "ops.scan", "--seed", "1", "--seconds", "1"],
        env=env, cwd=root, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
