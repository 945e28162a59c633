"""Good fixture: x64-scoping — JAX float64 only under enable_x64."""
import jax
import jax.numpy as jnp
import numpy as np


def exact_distances(refs):
    with jax.enable_x64():
        xs = jnp.asarray(refs, jnp.float64)
        return jnp.cumsum(xs)


def host_side(refs):
    # host numpy float64 never needs the JAX x64 switch
    return np.asarray(refs, dtype=np.float64).sum()
