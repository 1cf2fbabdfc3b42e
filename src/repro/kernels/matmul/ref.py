"""Oracles for the tiled and the grouped matmul kernels."""
import jax
import jax.numpy as jnp


def matmul_ref(a, b):
    return jnp.dot(a, b, preferred_element_type=a.dtype)


def gmm_ref(x, w, group_rows, activation=None):
    """Rows [r_e, r_e + group_rows[e]) of x times w[e], the groups in order
    from row 0; rows past them read 0."""
    y = jax.lax.ragged_dot(x, w, group_rows.astype(jnp.int32),
                           preferred_element_type=jnp.float32)
    if activation == "relu2":
        y = jnp.square(jnp.maximum(y, 0.0))
    return y.astype(x.dtype)
