"""Plain references of the paper's prefix ops, and their inputs.

Written from the definitions, importing nothing of the program:

* prefix sum: ``jnp.cumsum`` along the row;
* linear recurrence h_t = a_t h_(t-1) + b_t: a sequential ``lax.scan``;
* FFT: ``jnp.fft.fft`` along the row;
* tridiagonal solve: the sequential Thomas algorithm.

All in float32 (complex64 for the FFT), as the configuration states.
The control is the same reference with its inputs and its output rounded
to bfloat16, the precision below float32.
"""
from __future__ import annotations

from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

# which input set each op reads; ops that share a kind share the arrays
INPUT_KIND = {"prefix_sum.ks": "x", "prefix_sum.lf": "x",
              "linear_recurrence": "linrec", "fft": "complex",
              "tridiag.pcr": "tridiag"}


def make_inputs(kind: str, n: int, batch: int, key) -> Tuple[jax.Array, ...]:
    """Seeded inputs of one (kind, n); well-posed for every op."""
    if kind == "x":
        return (jax.random.normal(key, (batch, n), jnp.float32),)
    if kind == "linrec":
        ka, kb = jax.random.split(key)
        return (jax.random.uniform(ka, (batch, n), jnp.float32, 0.8, 0.99),
                jax.random.normal(kb, (batch, n), jnp.float32))
    if kind == "complex":
        kr, ki = jax.random.split(key)
        return (jax.lax.complex(jax.random.normal(kr, (batch, n)),
                                jax.random.normal(ki, (batch, n))),)
    if kind == "tridiag":
        # diagonally dominant: |b| > |a| + |c| + 1, so Thomas is stable
        ka, kb, kc, kd = jax.random.split(key, 4)
        a = jax.random.uniform(ka, (batch, n), jnp.float32, 0.1, 1.0)
        c = jax.random.uniform(kc, (batch, n), jnp.float32, 0.1, 1.0)
        a = a.at[:, 0].set(0.0)
        c = c.at[:, -1].set(0.0)
        b = a + c + jax.random.uniform(kb, (batch, n), jnp.float32, 1.0, 2.0)
        d = jax.random.normal(kd, (batch, n), jnp.float32)
        return a, b, c, d
    raise KeyError(f"unknown input kind {kind!r}")


def cumsum(x):
    return jnp.cumsum(x, axis=-1)


def linrec_sequential(a, b):
    def step(h, ab):
        h = ab[0] * h + ab[1]
        return h, h
    _, hs = jax.lax.scan(step, jnp.zeros_like(a[:, 0]), (a.T, b.T))
    return hs.T


def fft(x):
    return jnp.fft.fft(x, axis=-1)


def thomas(a, b, c, d):
    def forward(carry, abcd):
        cp_prev, dp_prev = carry
        ai, bi, ci, di = abcd
        denom = bi - ai * cp_prev
        cp = ci / denom
        dp = (di - ai * dp_prev) / denom
        return (cp, dp), (cp, dp)

    zeros = jnp.zeros_like(a[:, 0])
    _, (cp, dp) = jax.lax.scan(forward, (zeros, zeros),
                               (a.T, b.T, c.T, d.T))

    def backward(x_next, cpdp):
        x = cpdp[1] - cpdp[0] * x_next
        return x, x
    _, xs = jax.lax.scan(backward, zeros, (cp, dp), reverse=True)
    return xs.T


REFERENCE: Dict[str, Callable] = {
    "prefix_sum.ks": cumsum, "prefix_sum.lf": cumsum,
    "linear_recurrence": linrec_sequential, "fft": fft,
    "tridiag.pcr": thomas,
}


def _to_bf16(x):
    """Round to bfloat16 and keep float32 storage.  ``reduce_precision``
    and not a pair of casts: the compiler may drop a cast pair as excess
    precision, and the control would then read the float32 reference."""
    if jnp.iscomplexobj(x):
        return jax.lax.complex(_to_bf16(jnp.real(x)), _to_bf16(jnp.imag(x)))
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def control(op: str, args: Sequence[jax.Array]) -> jax.Array:
    """The reference computed at bfloat16: inputs and output rounded."""
    return _to_bf16(REFERENCE[op](*(_to_bf16(a) for a in args)))


def rel_err(got: jax.Array, want: jax.Array) -> jax.Array:
    """max |got - want| over max |want|, on the device."""
    return (jnp.max(jnp.abs(got - want))
            / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))
