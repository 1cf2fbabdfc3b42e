#!/usr/bin/env python3
"""Smoke test of the main path on one TPU chip.

    python3 chip_smoke.py

Runs in one process, phase by phase; any failure raises and exits non-zero:

  device   JAX must see a TPU (never falls back to the CPU);
  kernels  the paper's prefix kernels at 2^26 elements per call, each
           compiled (interpret=False, a ``tpu_custom_call`` in the compiled
           program) with the config the tuning session resolves from
           committed inputs only (an empty tuning DB, so the analytical
           model answers), checked against its XLA reference at the
           tolerances of ``tests/conftest.py``;
  serving  qwen1.5-0.5b at its published widths (24 layers, vocabulary
           151936, bf16 weights from a seed) through ``ServeEngine``:
           8 requests, checked against ``ReferenceEngine`` and, for one
           prompt, decode-through-cache logits against ``model.forward``.

Wall times printed here are smoke timings (one call, compile excluded),
not benchmark metrics.  The last line of standard output is the JSON
result ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
TOTAL = 2 ** 26                 # paper: batch = 2^26 / N problems per call
DECODE_REL_TOL = 2e-2           # tests/test_models.py decode-vs-forward bound


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase():
    import jax
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r}")
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _load_tolerances():
    """``DTYPE_TOL`` / ``assert_kernel_close`` from tests/conftest.py, so the
    chip is held to the same bounds as the interpret-mode tests."""
    spec = importlib.util.spec_from_file_location(
        "kernel_tolerances", os.path.join(ROOT, "tests", "conftest.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module          # its dataclasses look it up
    spec.loader.exec_module(module)
    return module


def _kernel_cases(jax, jnp):
    """(label, workload, entry, reference, make_args, dtype, tol scale);
    inputs are built per case, so only one case's arrays are alive."""
    from repro.configs.paper_ops import PREFIX_OPS
    from repro.core.multikernel import max_resident_tile
    from repro.core.space import Workload
    from repro.kernels.fft.ops import fft
    from repro.kernels.fft.ref import fft_ref
    from repro.kernels.scan.ops import linear_recurrence, prefix_sum
    from repro.kernels.scan.ref import scan_add_ref, scan_linrec_assoc_ref
    from repro.kernels.ssd.ops import ssd
    from repro.kernels.ssd.ref import ssd_ref
    from repro.kernels.tridiag.ops import solve
    from repro.kernels.tridiag.ref import random_system, thomas_ref

    key = jax.random.PRNGKey(SEED)
    scan_sizes = PREFIX_OPS["scan"]["sizes"]
    pcr_sizes = PREFIX_OPS["tridiag"]["sizes"]
    cases = []
    for variant in PREFIX_OPS["scan"]["variants"]:
        for n in (scan_sizes[0], scan_sizes[-1]):
            cases.append((
                f"prefix_sum[{variant}] n={n}",
                Workload("scan", n, TOTAL // n, variant=variant),
                lambda x, v=variant: prefix_sum(x, variant=v), scan_add_ref,
                lambda n=n: (jax.random.normal(key, (TOTAL // n, n)),),
                "float32", 1.0))

    def linrec_args(n):
        ka, kb = jax.random.split(jax.random.fold_in(key, n))
        return (jax.random.uniform(ka, (TOTAL // n, n), jnp.float32, 0.8,
                                   0.99),
                jax.random.normal(kb, (TOTAL // n, n), jnp.float32))

    for n in (scan_sizes[0], scan_sizes[-1]):
        cases.append((f"linear_recurrence n={n}",
                      Workload("scan", n, TOTAL // n, variant="linrec"),
                      linear_recurrence, scan_linrec_assoc_ref,
                      lambda n=n: linrec_args(n), "float32", 1.0))
    for n in (pcr_sizes[0], pcr_sizes[-1]):
        cases.append((f"tridiag[pcr] n={n}",
                      Workload("tridiag", n, TOTAL // n, variant="pcr"),
                      lambda a, b, c, d: solve(a, b, c, d, variant="pcr"),
                      thomas_ref,
                      lambda n=n: random_system(jax.random.fold_in(key, n),
                                                TOTAL // n, n),
                      "float32", 50.0))

    def fft_args(n):
        kr, ki = jax.random.split(jax.random.fold_in(key, n))
        shape = (TOTAL // n, n)
        return (jax.lax.complex(jax.random.normal(kr, shape),
                                jax.random.normal(ki, shape)),)

    fft_sizes = PREFIX_OPS["fft"]["sizes"]
    large_sizes = PREFIX_OPS["large_fft"]["sizes"]
    for n in (fft_sizes[0], fft_sizes[-1], large_sizes[0], large_sizes[-1]):
        wl = Workload("fft", n, TOTAL // n, variant="stockham")
        if n > max_resident_tile(wl):
            wl = Workload("large_fft", n, TOTAL // n, variant="stockham")
        path = "resident" if wl.op == "fft" else "four-step"
        cases.append((f"fft n={n} ({path})", wl, fft, fft_ref,
                      lambda n=n: fft_args(n), "complex64", 1.0))
    # mamba2-130m widths: 24 heads x head_dim 64, state 128, L = 2048
    L, H, P, S = 2048, 24, 64, 128
    B = max(TOTAL // (L * H * P), 1)

    def ssd_args():
        ks = jax.random.split(jax.random.fold_in(key, 7), 4)
        return (jax.random.normal(ks[0], (B, L, H, P), jnp.float32),
                jax.random.uniform(ks[1], (B, L, H), jnp.float32, 0.85,
                                   0.999),
                jax.random.normal(ks[2], (B, L, 1, S), jnp.float32) * 0.3,
                jax.random.normal(ks[3], (B, L, 1, S), jnp.float32) * 0.3)

    cases.append((f"ssd mamba2-130m B={B} L={L} H={H} P={P} S={S}",
                  Workload("ssd", L, B * H, variant="chunked"),
                  ssd, ssd_ref, ssd_args, "float32", 10.0))
    return cases


def kernel_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.tuning import TunerSession, TuningDB, set_default_session
    from repro.tuning.dispatch import plan_execution

    tol = _load_tolerances()
    # committed inputs only: a DB that does not exist (and is never
    # written), so every config comes from the analytical model
    db_path = os.path.join(ROOT, ".smoke-empty-tuning-db.json")
    check(not os.path.exists(db_path), f"{db_path} must not exist")
    session = TunerSession(db=TuningDB(path=db_path))
    set_default_session(session)
    check(plan_execution(None, None) == (True, False),
          "kernels would not run compiled Pallas (interpret=False) here")
    print(f"[kernels] profile={session.spec.name} "
          f"vmem_limit={session.spec.vmem_budget} interpret=False", flush=True)

    for label, wl, entry, ref, make_args, dtype, scale in _kernel_cases(
            jax, jnp):
        method = "db" if session.lookup(wl) is not None else "analytical"
        cfg = session.resolve(wl)
        args = make_args()
        compiled = jax.jit(entry).lower(*args).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{label}: no Pallas kernel in the compiled program")
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        wall = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(ref)(*args))
        got, want = np.asarray(out), np.asarray(want)
        check(bool(np.all(np.isfinite(got))), f"{label}: non-finite output")
        err = float(np.max(np.abs(got - want)))
        rel = err / max(float(np.max(np.abs(want))), 1.0)
        tol.assert_kernel_close(got, want, dtype, scale=scale)
        print(f"[kernels] {label}: config={cfg} method={method} "
              f"max_abs_err={err:.3e} rel_err={rel:.3e} "
              f"smoke_time={wall * 1e3:.3f}ms", flush=True)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serving_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_arch
    from repro.models.model import build_model
    from repro.serve.engine import ServeEngine
    from repro.serve.reference import ReferenceEngine

    cfg = get_arch("qwen1.5-0.5b")           # published widths, no .reduced()
    check(cfg.n_layers == 24 and cfg.vocab == 151936
          and cfg.param_dtype == "bfloat16", f"unexpected config {cfg}")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"[serving] {cfg.arch}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab} params={n_params} dtype={cfg.param_dtype}",
          flush=True)

    rng = np.random.default_rng(SEED)
    max_new, n_req = 32, 8
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(16, 129)))
               .astype(np.int32) for _ in range(n_req)]

    engine = ServeEngine(model, params, max_batch=8, max_len=512)
    t0 = time.perf_counter()
    engine.warmup()
    warm = time.perf_counter() - t0
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    t0 = time.perf_counter()
    done = engine.run(max_steps=10_000)
    wall = time.perf_counter() - t0
    check(len(done) == n_req, f"{len(done)} of {n_req} requests finished")
    for r in done:
        check(len(r.output) == max_new and r.finish_reason == "stop",
              f"request {r.rid}: {len(r.output)} tokens, {r.finish_reason}")
        check(all(0 <= t < cfg.vocab for t in r.output),
              f"request {r.rid}: token outside the vocabulary")
    tokens = sum(len(r.output) for r in done)
    print(f"[serving] requests={len(done)} tokens={tokens} "
          f"prompt_lens={[len(p) for p in prompts]} "
          f"smoke_time={wall:.3f}s warmup={warm:.3f}s "
          f"prefill_calls={engine.prefill_calls} "
          f"host_transfers={engine.host_transfers}", flush=True)

    reference = ReferenceEngine(model, params, max_batch=8, max_len=512)
    for p in prompts:
        reference.submit(p, max_new_tokens=max_new)
    ref_done = reference.run(max_steps=10_000)
    mismatched = [r.rid for r, q in zip(done, ref_done)
                  if r.output != q.output]
    check(not mismatched,
          f"ServeEngine and ReferenceEngine disagree on requests {mismatched}")
    print(f"[serving] ReferenceEngine: same greedy tokens on all "
          f"{len(ref_done)} requests", flush=True)

    # decode through the cache, token by token, against the full forward
    prompt = jnp.asarray(prompts[0])[None]
    length = prompt.shape[1]
    full, _ = jax.jit(model.forward)(params, prompt)

    @jax.jit
    def decode_all(params, tokens):
        cache = model.init_cache(1, max_len=length, dtype=jnp.float32)

        def body(cache, t):
            tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
            logits, cache = model.decode_step(
                params, tok, cache, jnp.full((1, 1), t, jnp.int32))
            return cache, logits[:, 0]
        _, logits = jax.lax.scan(body, cache, jnp.arange(length))
        return jnp.moveaxis(logits, 0, 1)

    dec = decode_all(params, prompt)
    full, dec = np.asarray(full, np.float32), np.asarray(dec, np.float32)
    rel = float(np.max(np.abs(full - dec))) / (float(np.max(np.abs(full)))
                                               + 1e-9)
    check(rel < DECODE_REL_TOL,
          f"decode-vs-forward relative error {rel:.3e} >= {DECODE_REL_TOL}")
    print(f"[serving] decode-vs-forward: prompt_len={length} "
          f"rel_err={rel:.3e} (bound {DECODE_REL_TOL})", flush=True)


def main() -> int:
    device = device_phase()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import place_compile_cache
    print(f"[device] compile cache: {place_compile_cache()}", flush=True)
    kernel_phase()
    serving_phase()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
