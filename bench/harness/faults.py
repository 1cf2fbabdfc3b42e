"""Faults planted in the program underneath a run, to show that the check
of ``correct`` catches them.  Used by the fault tests (CPU, tiny cells)
and by ``calibrate.py --fault`` (the chip, the cells' own sizes); the
benchmark's own runs never plant one.

  answer      every op's output altered in one element where it is made
  half_batch  every op's output with the second half of its rows left out
  token       every token the engine's decode step emits altered (+1)
  state       the model's step returns its state unchanged
"""
from __future__ import annotations

import contextlib
import functools

FAULTS = ("answer", "half_batch", "token", "state")


def _op_targets():
    import repro.kernels.fft.ops as fft_ops
    import repro.kernels.scan.ops as scan_ops
    import repro.kernels.tridiag.ops as tri_ops
    return ((scan_ops, "prefix_sum"), (scan_ops, "linear_recurrence"),
            (fft_ops, "fft"), (tri_ops, "solve"))


def _wrap_ops(alter):
    saved = []
    for module, name in _op_targets():
        original = getattr(module, name)

        def broken(*args, _original=original, **kwargs):
            return alter(_original(*args, **kwargs))
        saved.append((module, name, original))
        setattr(module, name, functools.wraps(original)(broken))
    return saved


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` for the duration of the context."""
    import jax.numpy as jnp
    saved = []
    if fault == "answer":
        saved = _wrap_ops(lambda y: y.at[0, 1].add(1.0))
    elif fault == "half_batch":
        saved = _wrap_ops(lambda y: y.at[y.shape[0] // 2:].set(0))
    elif fault == "token":
        import repro.serve.engine as engine
        build = engine._build_step_fn

        def broken_build(model, temperature, max_len):
            step = build(model, temperature, max_len)
            vocab = model.cfg.vocab

            def wrapped(params, cache, state):
                cache, state, out = step(params, cache, state)
                tok = out[:, 0]
                return cache, state, out.at[:, 0].set(
                    jnp.where(tok >= 0, (tok + 1) % vocab, tok))
            return wrapped
        saved = [(engine, "_build_step_fn", build)]
        engine._build_step_fn = broken_build
    elif fault == "state":
        from repro.models.model import Model
        decode_step = Model.decode_step

        def stale(self, params, token, cache, pos, memory=None):
            logits, _ = decode_step(self, params, token, cache, pos, memory)
            return logits, cache
        saved = [(Model, "decode_step", decode_step)]
        Model.decode_step = stale
    else:
        raise ValueError(f"unknown fault {fault!r} (known: {FAULTS})")
    try:
        yield
    finally:
        for owner, name, original in reversed(saved):
            setattr(owner, name, original)
