"""Offline tuning CLI — populates the TuningDB and trains the ML predictor.

Search (legacy flag style, unchanged):

  PYTHONPATH=src python -m repro.launch.tune --op scan --variant lf \
      --sizes 128,256,512 --method bayesian
  PYTHONPATH=src python -m repro.launch.tune --paper-suite   # all paper ops

ML-based methodology (paper's offline-train / online-predict flow):

  PYTHONPATH=src python -m repro.launch.tune train-model \
      --out artifacts/ml_model.npz --db artifacts/ci_tuning_db.json --seed 0
  PYTHONPATH=src python -m repro.launch.tune eval-model \
      --model artifacts/ml_model.npz --min-top1 0.70 --max-slowdown 1.15

``train-model`` sweeps the training suite exhaustively on the TPU cost
model (and, with ``--db``, persists each sweep's winner into that TuningDB
— the synthetic fixture CI trains against — and folds any pre-existing
records in as extra training rows).  ``eval-model`` reports top-1 config
match rate and predicted-vs-best slowdown against the exhaustive optimum
on held-out problem sizes, exiting non-zero when the pinned floors are
violated (the CI regression gate for the learned strategy).

Methodology comparison (the paper's Table II as a CI artifact):

  PYTHONPATH=src python -m repro.launch.tune compare-methods \
      --json BENCH_methods.json [--model artifacts/ml_model.npz]

runs analytical/ml/online/bayesian/random against the exhaustive optimum
on the holdout suite and exits non-zero if exhaustive is ever beaten
(Phi > 1 is a sweep/objective bug, not a better methodology).
``--policies latency,energy,edp`` re-scores every method per tuning
policy (see docs/tuning.md, "Multi-objective tuning & policies"); Phi > 1
in ANY (method, policy) cell fails the same way.  With
``--device-matrix`` the comparison runs once per hardware profile
(default tpu_v5e,gpu_sm,cpu_interpret — see docs/hardware.md) sharing one
journal directory, so ``strategy="transfer"`` on later devices warm-starts
from earlier devices' sweeps; Phi > 1 in ANY (device, method) cell fails.

Online tuning replay (the deployment mode's deterministic test bench):

  PYTHONPATH=src python -m repro.launch.tune online-replay \
      --trace artifacts/serve_trace.jsonl [--db tuning_db.json] [--budget 32]

replays a recorded (config, step latency) trace — e.g. from
``repro.launch.serve --record-trace`` — through the OnlineTuner state
machine: same trace + same knobs -> same trials, same rollbacks, same
winner. With ``--db`` the promoted winner persists exactly as it would in
production.

Static analysis (zero-execution; the CI ``lint-analysis`` gate):

  PYTHONPATH=src python -m repro.launch.tune lint [--json REPORT] \
      [--baseline tests/fixtures/analysis_baseline.json] [--no-invariants]

runs the full :mod:`repro.analysis` pass — repo-convention AST lint,
version-drift fingerprints, and plan/space invariants for every op x
profile (see docs/analysis.md) — and exits non-zero on any finding not
suppressed by the baseline. ``--write-fingerprints`` refreshes the pinned
contract fixture after a deliberate, version-bumped schema change.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

from repro.configs.paper_ops import PREFIX_OPS, TOTAL_ELEMS
from repro.core import CostModelObjective, Workload
from repro.launch.compile_cache import place_compile_cache
from repro.tuning import TunerSession, default_session, strategies


def tune_suite(method: str, noise: float = 0.02, verbose: bool = True,
               session: Optional[TunerSession] = None) -> None:
    session = session or default_session()
    for op, spec in PREFIX_OPS.items():
        for variant in spec["variants"]:
            for n in spec["sizes"]:
                wl = Workload(op=op, n=n, batch=max(TOTAL_ELEMS // n, 1),
                              variant=variant)
                res = session.tune(wl, method=method,
                                   objective=CostModelObjective(noise=noise))
                if verbose:
                    print(f"[tune] {wl.key}: {res.best_config} "
                          f"t={res.best_time*1e6:.1f}us "
                          f"evals={res.evaluations}", flush=True)


# ---------------------------------------------------------------------------
# ML model subcommands
# ---------------------------------------------------------------------------

def _parse_ops(arg: Optional[str]) -> Optional[List[str]]:
    return [s for s in arg.split(",") if s] if arg else None


def train_model_main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(prog="tune train-model",
                                 description="Train the ML config predictor")
    ap.add_argument("--out", required=True, help="model artifact (.npz) path")
    ap.add_argument("--ops", default=None,
                    help="comma list of ops (default: the full suite)")
    ap.add_argument("--db", default=None,
                    help="TuningDB fixture: sweep winners are stored here and "
                         "existing records join the training set")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trees", type=int, default=48)
    ap.add_argument("--depth", type=int, default=12)
    ap.add_argument("--noise", type=float, default=0.0,
                    help="cost-model jitter while sweeping (default off)")
    ap.add_argument("--journal-dir", default=None,
                    help="checkpoint the exhaustive sweeps as JSONL journals "
                         "here; an interrupted train-model rerun resumes "
                         "instead of re-evaluating (see docs/tuning.md)")
    args = ap.parse_args(argv)

    import numpy as np

    from repro.tuning.db import TuningDB
    from repro.tuning.ml import (build_dataset, dataset_from_db, merge,
                                 suite_workloads, train_bundle)
    from repro.tuning.ml.dataset import POOLED_OPS

    objective = CostModelObjective(noise=args.noise)
    try:
        workloads = suite_workloads("train", ops=_parse_ops(args.ops))
    except ValueError as e:
        ap.error(str(e))
    print(f"[train-model] sweeping {len(workloads)} workloads ...", flush=True)

    prior = None
    on_sweep = None
    if args.db:
        db = TuningDB(path=args.db)
        prior = dataset_from_db(db)

        def on_sweep(wl, cfgs, times):   # persist each winner: the fixture
            i = int(np.argmin(times))
            db.store(wl, cfgs[i], float(times[i]), "exhaustive", len(cfgs))

    ds = build_dataset(workloads, objective, on_sweep=on_sweep,
                       journal_dir=args.journal_dir)
    if prior is not None and len(prior):
        print(f"[train-model] +{len(prior)} rows from TuningDB {args.db}",
              flush=True)
        ds = merge(ds, prior)

    print(f"[train-model] {len(ds)} rows; training "
          f"(trees={args.trees}, depth={args.depth}, seed={args.seed})",
          flush=True)
    bundle = train_bundle(ds.by_op(), n_trees=args.trees,
                          max_depth=args.depth, seed=args.seed,
                          meta={"aliases": POOLED_OPS})
    path = bundle.save(args.out)
    for op, rows in sorted(bundle.meta["train_rows"].items()):
        print(f"[train-model]   {op}: {rows} rows")
    print(f"[train-model] saved {path}")
    return 0


def online_replay_main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(prog="tune online-replay",
                                 description="Replay a recorded serving "
                                             "trace through the OnlineTuner")
    ap.add_argument("--trace", required=True,
                    help="JSONL trace from launch.serve --record-trace")
    ap.add_argument("--db", default=None,
                    help="TuningDB to persist the promoted winner into "
                         "(default: replay only, nothing stored)")
    ap.add_argument("--journal-dir", default=None,
                    help="journal trial EWMAs here (sweep-journal format)")
    ap.add_argument("--budget", type=int, default=32)
    ap.add_argument("--guard-band", type=float, default=0.25)
    ap.add_argument("--min-samples", type=int, default=3)
    ap.add_argument("--samples-per-trial", type=int, default=8)
    ap.add_argument("--json", default=None, help="write the summary here")
    args = ap.parse_args(argv)

    from repro.core.analytical import AnalyticalTuner
    from repro.core.space import build_space
    from repro.tuning import OnlineTuner, ReplayTrace, TunerSession, replay
    from repro.tuning.online import replay_candidates
    from repro.tuning.sweep import config_key

    trace = ReplayTrace.load(args.trace)
    wl = trace.workload.canonical()
    session = TunerSession(db_path=args.db) if args.db else None
    store = session is not None

    prior = session.resolve_raw(wl) if session is not None \
        else AnalyticalTuner().suggest(build_space(wl))
    if config_key(prior) not in trace.times:
        # the trace never measured the configured prior (e.g. a DB-less
        # replay of someone else's traffic): start from the config the
        # traffic actually ran, so the baseline is a real measurement
        first = next(iter(trace.configs))
        print(f"[online-replay] prior not in trace; using recorded config "
              f"{trace.configs[first]} as incumbent")
        prior = trace.configs[first]
    # trial only configs the trace can answer for — every recorded config
    # stays in the queue (expert-ranked, never truncated: the trace's
    # measured winner may rank poorly analytically and must still run)
    space = build_space(wl)
    candidates = replay_candidates(space, trace, prior)

    tuner = OnlineTuner(wl, session, prior=prior, candidates=candidates,
                        budget=args.budget, guard_band=args.guard_band,
                        min_samples=args.min_samples,
                        samples_per_trial=args.samples_per_trial,
                        journal_dir=args.journal_dir, store=store,
                        source=trace.source)
    res = replay(tuner, trace)
    s = tuner.summary()
    print(f"[online-replay] {wl.key}: {trace.steps()} recorded steps, "
          f"{len(candidates)} candidates")
    print(f"[online-replay] stopped_by={res.stopped_by} "
          f"measured={s['measured']}/{s['budget']} "
          f"promotions={s['promotions']}")
    for t in s["trials"]:
        ewma = f"{t['ewma_s']*1e3:.3f}ms" if t["ewma_s"] else "-"
        print(f"[online-replay]   {t['config']} -> {t['state']} "
              f"(samples={t['samples']}, ewma={ewma})")
    print(f"[online-replay] winner {res.best_config} "
          f"ewma={res.best_time*1e3:.3f}ms"
          + (f" (persisted to {args.db})" if store and s["promotions"]
             else ""))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(s, f, indent=1, sort_keys=True)
        print(f"[online-replay] summary written to {args.json}")
    return 0


def compare_methods_main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(prog="tune compare-methods",
                                 description="Score every methodology "
                                             "against the exhaustive optimum")
    ap.add_argument("--json", default="BENCH_methods.json",
                    help="report artifact path (default BENCH_methods.json)")
    ap.add_argument("--ops", default=None,
                    help="comma list of ops (default: the full suite)")
    ap.add_argument("--split", default="holdout", choices=("train", "holdout"),
                    help="which suite split to score (default holdout)")
    ap.add_argument("--methods", default=",".join(
                        ("analytical", "ml", "online", "bayesian", "random")),
                    help="comma list of strategies to compare")
    ap.add_argument("--model", default=None,
                    help="ML model artifact for strategy='ml' (sets "
                         "$REPRO_ML_MODEL; default: the session default)")
    ap.add_argument("--max-evals", type=int, default=20,
                    help="per-workload budget for the search strategies")
    ap.add_argument("--noise", type=float, default=0.0,
                    help="cost-model jitter (deterministic, hash-seeded)")
    ap.add_argument("--journal-dir", default=None,
                    help="checkpoint/resume the exhaustive sweeps here")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--device-matrix", action="store_true",
                    help="run the comparison once per hardware profile and "
                         "gate every (device, method) cell on Phi <= 1; "
                         "overrides --methods with the matrix defaults "
                         "unless --methods is given explicitly")
    ap.add_argument("--profiles", default=None,
                    help="comma list of hardware profiles for --device-matrix "
                         "(default: tpu_v5e,gpu_sm,cpu_interpret; order "
                         "matters — earlier devices' journals seed "
                         "strategy='transfer' on later ones)")
    ap.add_argument("--policies", default="latency",
                    help="comma list of tuning policies to score per method "
                         "(latency, energy, edp, memory_cap[:bytes]); any "
                         "(method, policy) Phi > 1 fails")
    args = ap.parse_args(argv)

    import os
    import tempfile

    from repro.evaluation import (check_matrix, check_report, compare_methods,
                                  compare_methods_matrix, format_matrix,
                                  format_report)
    from repro.tuning.ml import suite_workloads

    if args.model:
        os.environ["REPRO_ML_MODEL"] = args.model
    try:
        workloads = suite_workloads(args.split, ops=_parse_ops(args.ops))
    except ValueError as e:
        ap.error(str(e))

    if args.device_matrix:
        from repro.evaluation.compare import (DEFAULT_MATRIX_METHODS,
                                              DEFAULT_MATRIX_PROFILES)
        explicit_methods = any(a == "--methods" or a.startswith("--methods=")
                               for a in argv)
        methods = tuple(m for m in args.methods.split(",") if m) \
            if explicit_methods else DEFAULT_MATRIX_METHODS
        profiles = tuple(p for p in args.profiles.split(",") if p) \
            if args.profiles else DEFAULT_MATRIX_PROFILES
        # transfer needs cross-device journals: default to a scratch dir so
        # a bare invocation still exercises the warm-start path
        journal_dir = args.journal_dir or tempfile.mkdtemp(
            prefix="repro_matrix_journals_")
        print(f"[compare-methods] device matrix: {len(workloads)} "
              f"{args.split} workloads x {len(methods)} methodologies x "
              f"{len(profiles)} profiles ...", flush=True)
        matrix = compare_methods_matrix(
            workloads, methods, profiles, seed=args.seed,
            max_evals=args.max_evals, journal_dir=journal_dir,
            policies=tuple(p for p in args.policies.split(",") if p))
        matrix["suite"] = {"split": args.split, "seed": args.seed,
                           "noise": args.noise, "max_evals": args.max_evals}
        print(format_matrix(matrix))
        with open(args.json, "w") as f:
            json.dump(matrix, f, indent=1, sort_keys=True)
        print(f"[compare-methods] matrix report written to {args.json}")
        failures = check_matrix(matrix)
        for failure in failures:
            print(f"[compare-methods] FAIL: {failure}", file=sys.stderr)
        return 1 if failures else 0

    methods = tuple(m for m in args.methods.split(",") if m)
    print(f"[compare-methods] {len(workloads)} {args.split} workloads x "
          f"{len(methods)} methodologies ...", flush=True)
    report = compare_methods(
        workloads, methods,
        objective_factory=lambda: CostModelObjective(noise=args.noise),
        seed=args.seed, max_evals=args.max_evals,
        journal_dir=args.journal_dir,
        policies=tuple(p for p in args.policies.split(",") if p))
    report["suite"] = {"split": args.split, "seed": args.seed,
                       "noise": args.noise, "max_evals": args.max_evals}
    print(format_report(report))
    with open(args.json, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"[compare-methods] report written to {args.json}")

    failures = check_report(report)
    for failure in failures:
        print(f"[compare-methods] FAIL: {failure}", file=sys.stderr)
    return 1 if failures else 0


def eval_model_main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(prog="tune eval-model",
                                 description="Evaluate the ML config "
                                             "predictor on held-out sizes")
    ap.add_argument("--model", required=True, help="model artifact (.npz)")
    ap.add_argument("--ops", default=None,
                    help="comma list of ops (default: the full holdout suite)")
    ap.add_argument("--min-top1", type=float, default=None,
                    help="fail when top-1 match rate drops below this floor")
    ap.add_argument("--max-slowdown", type=float, default=None,
                    help="fail when mean slowdown exceeds this ceiling")
    ap.add_argument("--min-ml-rate", type=float, default=None,
                    help="fail when the fraction of workloads answered by "
                         "the learned rungs (vs fallbacks) drops below this")
    ap.add_argument("--min-rank-corr", type=float, default=None,
                    help="fail when the forest's mean predicted-vs-true "
                         "rank correlation drops below this (catches a "
                         "degenerate model hiding behind analytical defers)")
    ap.add_argument("--json", default=None, help="write the full report here")
    ap.add_argument("--seed", type=int, default=0,
                    help="accepted for CLI uniformity; evaluation is "
                         "deterministic")
    args = ap.parse_args(argv)

    from repro.tuning.ml import (ModelBundle, check_floors, evaluate_model,
                                 suite_workloads)

    bundle = ModelBundle.load(args.model)
    try:
        workloads = suite_workloads("holdout", ops=_parse_ops(args.ops))
    except ValueError as e:
        ap.error(str(e))
    report = evaluate_model(bundle, workloads)

    print(f"[eval-model] {report['n_scored']} holdout workloads scored; "
          f"rungs: {report.get('rungs', {})}")
    for op, r in sorted(report.get("per_op", {}).items()):
        print(f"[eval-model]   {op:<10} top1={r['top1_rate']:5.1%}  "
              f"mean={r['mean_slowdown']:.3f}x  max={r['max_slowdown']:.3f}x  "
              f"(n={r['n']})")
    if report["n_scored"]:
        print(f"[eval-model] overall    top1={report['top1_rate']:5.1%}  "
              f"mean={report['mean_slowdown']:.3f}x  "
              f"max={report['max_slowdown']:.3f}x  "
              f"ml_rate={report['ml_rate']:5.1%}  "
              f"rank_corr={report['mean_rank_corr']:.3f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"[eval-model] report written to {args.json}")

    failures = check_floors(report, min_top1=args.min_top1,
                            max_mean_slowdown=args.max_slowdown,
                            min_ml_rate=args.min_ml_rate,
                            min_rank_corr=args.min_rank_corr)
    for failure in failures:
        print(f"[eval-model] FAIL: {failure}", file=sys.stderr)
    return 1 if failures else 0


def lint_main(argv: List[str]) -> int:
    import os

    from repro.analysis import (apply_baseline, default_fixture_path,
                                load_baseline, report_dict, run_lint,
                                write_fingerprints)
    ap = argparse.ArgumentParser(
        prog="tune lint",
        description="zero-execution static analysis: AST conventions, "
                    "contract fingerprints, plan/space invariants "
                    "(docs/analysis.md)")
    ap.add_argument("--json", default=None,
                    help="write the full machine-readable report here")
    ap.add_argument("--baseline", default=None,
                    help="suppression file (default: "
                         "tests/fixtures/analysis_baseline.json when "
                         "present)")
    ap.add_argument("--write-fingerprints", action="store_true",
                    help="refresh the pinned contract fixture from the "
                         "live tree (after a deliberate, version-bumped "
                         "schema change)")
    ap.add_argument("--no-invariants", action="store_true",
                    help="skip the op x profile semantic sweep (fast "
                         "pre-commit mode; CI runs everything)")
    ap.add_argument("--root", default=None,
                    help="package root to AST-lint (default: the "
                         "installed repro package)")
    args = ap.parse_args(argv)

    fixture = default_fixture_path()
    if args.write_fingerprints:
        write_fingerprints(fixture)
        print(f"[lint] fingerprints written to {fixture}")

    findings = run_lint(pkg_root=args.root, fingerprint_path=fixture,
                        invariants=not args.no_invariants)
    baseline = args.baseline
    if baseline is None:
        cand = os.path.join(os.path.dirname(fixture),
                            "analysis_baseline.json")
        baseline = cand if os.path.exists(cand) else None
    fresh, suppressed = apply_baseline(findings, load_baseline(baseline))
    for f in fresh:
        print(f.render())
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report_dict(fresh, suppressed), fh, indent=1,
                      sort_keys=True)
        print(f"[lint] report written to {args.json}")
    print(f"[lint] {len(fresh)} finding(s), {len(suppressed)} baselined")
    return 1 if fresh else 0


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    place_compile_cache()
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if argv and argv[0] == "lint":
        return lint_main(argv[1:])
    if argv and argv[0] == "train-model":
        return train_model_main(argv[1:])
    if argv and argv[0] == "eval-model":
        return eval_model_main(argv[1:])
    if argv and argv[0] == "compare-methods":
        return compare_methods_main(argv[1:])
    if argv and argv[0] == "online-replay":
        return online_replay_main(argv[1:])

    ap = argparse.ArgumentParser()
    ap.add_argument("--op", default=None)
    ap.add_argument("--variant", default="")
    ap.add_argument("--sizes", default="")
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--method", default="bayesian", choices=list(strategies()))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policy", default="latency",
                    help="tuning policy: latency (default), energy, edp, or "
                         "memory_cap[:bytes] — see docs/tuning.md")
    ap.add_argument("--db", default=None,
                    help="path to the tuning DB (default: the session DB)")
    ap.add_argument("--paper-suite", action="store_true")
    args = ap.parse_args(argv)

    session = TunerSession(db_path=args.db, policy=args.policy) if args.db \
        else default_session()
    if args.paper_suite:
        tune_suite(args.method, session=session)
        return 0
    assert args.op and args.sizes
    for n in [int(s) for s in args.sizes.split(",")]:
        wl = Workload(op=args.op, n=n,
                      batch=args.batch or max(TOTAL_ELEMS // n, 1),
                      variant=args.variant)
        res = session.tune(wl, method=args.method, seed=args.seed,
                           policy=args.policy)
        if args.policy == "latency":
            score = f"t={res.best_time*1e6:.1f}us"
        else:   # best_time is the policy scalar, not seconds
            score = f"{args.policy}={res.best_time:.6g}"
        print(f"[tune] {wl.key}: {res.best_config} "
              f"{score} evals={res.evaluations}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
