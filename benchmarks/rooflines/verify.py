"""The least time the chip could take to verify gossip signatures:
the work of the problem, from shapes alone, whatever implements it.

Per signature, the textbook count:
  * sha256d of the signed region -- bytes only, no multiplies;
  * one inverse of s modulo n by Fermat: 256 squarings + 128 products;
  * u1 = z/s, u2 = r/s: 2 products;
  * Shamir's dual multiplication u1*G + u2*Q over 256 bits in Jacobian
    coordinates: 256 doublings (2M + 5S = 7 products each) and, a point
    being added wherever either scalar has a bit, 3/4 * 256 = 192 mixed
    additions (7M + 4S = 11 products each);
  * back to affine x: one field inverse (255 squarings + 15 products)
    and 2 products.
Every 256x256-bit product as 32x32 multiply-accumulates of 8-bit
limbs: 1,024 MACs = 2,048 integer operations, held against the chip's
published int8 peak (the only integer peak it publishes).  Reductions,
additions and table lookups are not counted, so the count is a floor.

Bytes: the signed regions, signatures and keys in, one validity byte
out, against the HBM peak.
"""
from __future__ import annotations

PRODUCTS_PER_SIG = (256 + 128) + 2 + 256 * 7 + 192 * 11 + (255 + 15) + 2
OPS_PER_PRODUCT = 2 * 32 * 32
OPS_PER_SIG = PRODUCTS_PER_SIG * OPS_PER_PRODUCT
BYTES_PER_SIG = 64 + 33 + 1


def work(n_sigs: float, signed_bytes: float) -> tuple[float, float]:
    """(integer operations, bytes moved)."""
    return n_sigs * OPS_PER_SIG, n_sigs * BYTES_PER_SIG + signed_bytes


def least_seconds(n_sigs: float, signed_bytes: float,
                  peaks: dict) -> tuple[float, str]:
    """(seconds, which peak bounds it)."""
    ops, nbytes = work(n_sigs, signed_bytes)
    t_ops = ops / peaks["int8_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "int8_ops_per_s") if t_ops >= t_mem \
        else (t_mem, "hbm_bytes_per_s")
