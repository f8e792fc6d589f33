"""NEGATIVE [x64-discipline]: kernel-builder bodies trace under their
call site's x64 scope — the staging rule applies to eager code, not
traced code (the invocation sites are checked instead)."""
import functools

import jax
import jax.numpy as jnp
from jax import enable_x64


def fee_kernel(amounts, rates):
    # traced: dtype decided by the invoking scope
    fees = jnp.asarray(amounts) * rates
    risk = jnp.zeros_like(fees, jnp.int64)
    return fees + risk


@functools.lru_cache(maxsize=1)
def _jit_fees():
    return jax.jit(fee_kernel)


def solve(amounts, rates):
    with enable_x64():
        return _jit_fees()(jnp.asarray(amounts), jnp.asarray(rates))
