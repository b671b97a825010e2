"""Pure-jnp oracle for the batched L2 distance kernel."""

import jax
import jax.numpy as jnp


def l2_distance_ref(queries: jnp.ndarray, candidates: jnp.ndarray) -> jnp.ndarray:
    """Squared L2 distances.  queries [Q, d], candidates [N, d] -> [Q, N].

    Computed in f32 regardless of input dtype, with the cross term at full
    f32 precision on every backend (the kernel accumulates in f32 on the
    MXU; a default-precision TPU matmul would round operands to bf16).
    """
    q = queries.astype(jnp.float32)
    c = candidates.astype(jnp.float32)
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)        # [Q, 1]
    c2 = jnp.sum(c * c, axis=-1, keepdims=True).T      # [1, N]
    cross = jnp.matmul(q, c.T, precision=jax.lax.Precision.HIGHEST)  # [Q, N]
    return jnp.maximum(q2 + c2 - 2.0 * cross, 0.0)
