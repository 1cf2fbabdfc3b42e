"""Benchmark harness — one section per paper table/figure.

Prints ``name,...`` CSV rows:
  fig5/fig6/fig7/fig8 — tridiag / scan / FFT / large-FFT throughput per
      tuning methodology (+ `-host` rows: genuine wall-clock on this host);
  table2              — average performance + Phi per (op, methodology);
  fig4 / fig4d        — BO candidate-evaluation counts (+ control vs random);
  roofline            — per (arch x shape) three-term roofline summary;
  resolve             — TunerSession online hot-path vs seed miss path;
  blocks              — StagePlan construction + plan-aware resolve path;
  sweep               — vectorized sweep engine vs seed per-config loop;
  ml_predict          — learned-predictor rank latency + holdout accuracy;
  online              — OnlineTuner per-decode-step overhead vs untimed;
  transfer            — cross-device warm-start vs cold evals-to-optimum
      (the BENCH_transfer gate: warm must halve cold's evaluation bill);
  pareto              — per-policy sweep winners + Pareto-front sizes
      (the BENCH_pareto gate: the energy policy must flip at least one
      winner with strictly lower modeled joules);
  analysis            — static-analysis pass timing per stage
      (the BENCH_analysis gate: the full zero-execution lint — AST rules,
      fingerprints, op x profile invariants — must finish under 10 s and
      come back clean);
  serving             — multi-tenant trace through the optimized serving
      engine vs the per-token replay baseline (the BENCH_serving gates:
      >= 3x tokens/sec on full runs, prefill dispatches and host
      transfers structurally bounded, fleet warm start strictly cheaper
      than cold).

``--seed`` flows into every stochastic section so CI runs are
reproducible; ``--json-dir`` writes one BENCH_<SECTION>.json per section
(the artifact the CI bench-smoke job uploads).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.launch.compile_cache import place_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: prefix_ops,convergence,roofline,"
                         "resolve,blocks,sweep,ml_predict,online,transfer,"
                         "pareto,analysis,serving")
    ap.add_argument("--no-host-wallclock", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the stochastic sections (reproducible CI)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced workloads/reps where supported")
    ap.add_argument("--json-dir", default=None,
                    help="write BENCH_<SECTION>.json files here")
    args = ap.parse_args()
    place_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    section_rows = {}
    current = [None]

    def emit(row: str) -> None:
        if current[0] is not None:
            section_rows.setdefault(current[0], []).append(row)
        print(row, flush=True)

    def begin(name: str) -> bool:
        active = only is None or name in only
        current[0] = name if active else None
        return active

    t0 = time.time()
    emit("table,op,variant,N,method,metric,value,extra")
    if begin("prefix_ops"):
        from benchmarks.bench_prefix_ops import run as run_ops
        run_ops(emit, host_wallclock=not args.no_host_wallclock)
    if begin("convergence"):
        from benchmarks.bench_convergence import run as run_conv
        run_conv(emit)
    if begin("roofline"):
        from benchmarks.bench_roofline import run as run_roof
        run_roof(emit)
    if begin("resolve"):
        from benchmarks.bench_resolve import run as run_resolve
        run_resolve(emit)
    if begin("blocks"):
        from benchmarks.bench_blocks import run as run_blocks
        run_blocks(emit)
    if begin("sweep"):
        from benchmarks.bench_sweep import run as run_sweep_bench
        run_sweep_bench(emit)
    if begin("ml_predict"):
        from benchmarks.bench_ml_predict import run as run_ml
        run_ml(emit, seed=args.seed, smoke=args.smoke)
    if begin("online"):
        from benchmarks.bench_online import run as run_online
        run_online(emit, seed=args.seed, smoke=args.smoke)
    gate_failures = []
    if begin("transfer"):
        from benchmarks.bench_transfer import run as run_transfer
        gate_failures += run_transfer(emit, seed=args.seed,
                                      smoke=args.smoke)
    if begin("pareto"):
        from benchmarks.bench_pareto import run as run_pareto
        gate_failures += run_pareto(emit, seed=args.seed, smoke=args.smoke)
    if begin("analysis"):
        from benchmarks.bench_analysis import run as run_analysis
        gate_failures += run_analysis(emit, seed=args.seed,
                                      smoke=args.smoke)
    if begin("serving"):
        from benchmarks.bench_serving import run as run_serving
        gate_failures += run_serving(emit, seed=args.seed, smoke=args.smoke)

    if args.json_dir:
        os.makedirs(args.json_dir, exist_ok=True)
        for name, rows in section_rows.items():
            path = os.path.join(args.json_dir, f"BENCH_{name.upper()}.json")
            with open(path, "w") as f:
                json.dump({"bench": name, "seed": args.seed,
                           "smoke": bool(args.smoke), "rows": rows},
                          f, indent=1, sort_keys=True)
            print(f"# wrote {path}", file=sys.stderr)
    print(f"# benchmarks done in {time.time()-t0:.1f}s", file=sys.stderr)
    for failure in gate_failures:
        print(f"# FAIL: {failure}", file=sys.stderr)
    if gate_failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
