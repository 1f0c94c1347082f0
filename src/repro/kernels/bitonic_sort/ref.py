"""Pure-jnp oracle for bitonic_sort."""
import jax
import jax.numpy as jnp


def sort_ref(keys: jnp.ndarray, values: jnp.ndarray):
    return jax.lax.sort((keys, values), dimension=-1, num_keys=2)
