"""Tiny cells for the CPU tests: the benchmark's own drivers, readers and
references under configurations small enough for a test run."""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

TINY_OPS = {"total_elems": 4096, "dtype": "float32", "fft_dtype": "complex64",
            "reference": "prefix_ops"}
TINY_MAMBA = {"d_model": 64, "n_layer": 2, "vocab_size": 500,
              "vocab_rows": 512, "d_state": 16, "d_conv": 4, "expand": 2,
              "headdim": 16, "norm_eps": 1e-5, "dtype": "bfloat16",
              "reference": "mamba2", "program_arch": "mamba2-130m"}
TRAFFIC = {
    "scan": {"driver": "ops", "check": {"sample_per_op": 2}, "calls": [
        {"op": "prefix_sum.ks", "sizes": [16, 64]},
        {"op": "prefix_sum.lf", "sizes": [16, 64]},
        {"op": "linear_recurrence", "sizes": [16, 64]}]},
    "fft_pcr": {"driver": "ops", "check": {"sample_per_op": 1}, "calls": [
        {"op": "fft", "sizes": [16, 64]},
        {"op": "tridiag.pcr", "sizes": [16]}]},
    "chat": {"driver": "serve", "loop": "open", "arrivals": "poisson",
             "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.7,
                        "min": 4, "max": 16},
             "output": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                        "min": 4, "max": 24}},
    "gen": {"driver": "serve", "loop": "closed",
            "prompt": {"dist": "uniform", "min": 4, "max": 8},
            "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 4, "max": 16}},
}
SERVE_COMMON = {"steps_per_run": 4, "drain_s": 30, "trace_seconds": None}
SETTINGS = {
    "ops.scan": {"limits": {"rel_err.prefix_sum.ks": 1e-4,
                            "rel_err.prefix_sum.lf": 1e-4,
                            "rel_err.linear_recurrence": 1e-4}},
    "ops.fft_pcr": {"limits": {"rel_err.fft": 1e-4,
                               "rel_err.tridiag.pcr": 1e-4}},
    "serve.tiny.chat": dict(SERVE_COMMON, rate=40.0,
                            engine={"max_batch": 4, "max_len": 64},
                            check={"sample": 12, "bucket": 48},
                            limits={"mean_logit_gap": 0.002}),
    "serve.tiny.gen": dict(SERVE_COMMON, clients=4, rounds=8,
                           engine={"max_batch": 4, "max_len": 64},
                           check={"sample": 2, "bucket": 32},
                           limits={"mean_logit_gap": 0.002}),
}
CELLS = [("ops.scan", "tiny-ops", "scan"), ("ops.fft_pcr", "tiny-ops", "fft_pcr"),
         ("serve.tiny.chat", "tiny-mamba", "chat"),
         ("serve.tiny.gen", "tiny-mamba", "gen")]


def _write(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(tmp: str) -> str:
    """A checkout at ``tmp`` with the tiny cells: the real drivers,
    readers and references, tiny data files, and the program's ``src``."""
    bench = os.path.join(tmp, "bench")
    for kind in ("drivers", "metrics", "reference", "harness"):
        os.makedirs(bench, exist_ok=True)
        os.symlink(os.path.join(BENCH_DIR, kind), os.path.join(bench, kind))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(tmp, "src"))
    _write(os.path.join(bench, "configs", "tiny-ops.json"), TINY_OPS)
    _write(os.path.join(bench, "configs", "tiny-mamba.json"), TINY_MAMBA)
    for name, mix in TRAFFIC.items():
        _write(os.path.join(bench, "traffic", f"{name}.json"), mix)
    for name, settings in SETTINGS.items():
        _write(os.path.join(bench, "workloads", f"{name}.json"), settings)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    bench_json = {
        "command": real["command"], "paths": ["bench"], "run_seconds": 1,
        "configs": [{"name": n, "source": "test", "file": f"bench/configs/{n}.json",
                     "reduced": [], "why": "test"}
                    for n in ("tiny-ops", "tiny-mamba")],
        "workloads": [{"name": c, "config": cfg, "traffic": t, "chips": 1,
                       "why": "test"} for c, cfg, t in CELLS],
        "end_to_end": [_scoped(m) for m in real["end_to_end"]],
        "per_layer": [],
    }
    _write(os.path.join(tmp, "BENCHMARK.json"), bench_json)
    return tmp


def _scoped(metric):
    """The real end-to-end metric, scoped to the tiny cells of its kind."""
    out = {k: v for k, v in metric.items() if k != "workloads"}
    if metric["name"] == "batch_ms":
        out["workloads"] = [c for c, cfg, _ in CELLS if cfg == "tiny-ops"]
    elif metric["name"] != "setup_s":
        out["workloads"] = [c for c, cfg, _ in CELLS if cfg == "tiny-mamba"]
    return out


class CpuDevice:
    """Stands in for a chip: the harness's device check is skipped and the
    program runs on JAX's CPU backend."""
    platform = "cpu"
    device_kind = "cpu"
    id = 0

    @staticmethod
    def memory_stats():
        return {}


def run_cell(root: str, cell: str, seed: int = 7, seconds: float = 0.5,
             control: bool = False):
    """Run ``cell`` of ``root`` through the harness on the CPU; returns
    (result dict, outcome)."""
    run = _run_module()
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    return run.execute(args, root=root, devices=[CpuDevice()],
                       control=control)


def _run_module() -> types.ModuleType:
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bench_run_entry", os.path.join(BENCH_DIR, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
