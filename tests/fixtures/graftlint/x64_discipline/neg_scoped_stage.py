"""NEGATIVE [x64-discipline]: the routing/device.py idiom — every
msat/int64 staging crosses jnp.asarray inside enable_x64; host numpy
is always 64-bit and exempt."""
import jax.numpy as jnp
import numpy as np
from jax import enable_x64


def stage_query(amount_msat, fee_base, n):
    host = np.asarray(amount_msat, np.int64)      # host np: exempt
    with enable_x64():
        a = jnp.asarray(amount_msat)
        b = jnp.asarray(fee_base)
        z = jnp.zeros((n,), jnp.int64)
    return host, a, b, z


def stage_shapes(blocks, counts):
    # no money semantics, no int64: plain staging needs no scope
    return jnp.asarray(blocks), jnp.asarray(counts)
