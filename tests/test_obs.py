"""repro.obs: the compile count and its listener."""
import jax
import jax.numpy as jnp
from jax._src import monitoring

from repro import obs


def test_compile_count_counts_a_fresh_compile_not_a_cached_call():
    obs.watch_compiles()
    x = jax.block_until_ready(jnp.arange(8.0))
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    before = obs.compiles()
    f(x).block_until_ready()
    assert obs.compiles() == before + 1
    f(x).block_until_ready()
    assert obs.compiles() == before + 1


def test_watch_compiles_registers_one_listener():
    obs.watch_compiles()
    obs.watch_compiles()
    assert obs.watching()
    listeners = monitoring.get_event_duration_listeners()
    assert sum(fn is obs._on_duration for fn in listeners) == 1
