"""Device-mesh and sharding helpers for the batched-crypto data plane.

CLN's "distributed backend" is a fleet of single-purpose processes wired by
socketpairs (SURVEY.md §2.5); the TPU-native equivalent moves the heavy
math (signature verify/sign fan-out) onto a device mesh and keeps the
protocol plane on host.  Scaling axis:

* ``batch``: data-parallel over signatures.  A verify batch of B sigs is
  sharded (B/n per device); each device runs the identical branchless
  kernel; the only collective is the boolean gather at the end (and a
  psum for the "all valid" fast path) — pure ICI traffic, no host hop.

This mirrors how the reference scales gossip verification across...
nothing (it is serial, gossipd/sigcheck.c) — the mesh IS the delta.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# public on purpose: __graft_entry__.py wraps its own programs with it
shard_map = jax.shard_map

BATCH_AXIS = "batch"


def make_mesh(devices=None) -> Mesh:
    """1-D data-parallel mesh over all (or the given) devices."""
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), (BATCH_AXIS,))


def usable_device_count(batch: int, limit: int | None = None) -> int:
    """Largest device count ≤ limit (default: all devices) that divides
    the batch evenly — shard_map rejects ragged shards, so a bucket
    must split exactly.  Returns 1 when no multi-device split fits
    (callers fall back to the single-device program)."""
    try:
        n = len(jax.devices())
    except Exception:
        return 1
    if limit is not None:
        n = min(n, limit)
    while n > 1 and batch % n:
        n -= 1
    return max(1, n)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(BATCH_AXIS))


def pad_to_multiple(arr: np.ndarray, multiple: int, axis: int = 0):
    """Pad with trailing zeros so shape[axis] % multiple == 0.
    Returns (padded, original_length)."""
    n = arr.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return arr, n
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, rem)
    return np.pad(arr, pad), n


def shard_batch(mesh: Mesh, *arrays):
    """device_put each array with leading-axis sharding over the mesh.
    Arrays must already be padded to a multiple of the mesh size."""
    # named fault seam (doc/resilience.md): the resharding device_put is
    # the first mesh-only step of a sharded dispatch, so an injected
    # failure here exercises the mesh breaker's mesh→fused degradation
    from ..resilience import faultinject as _fault
    from ..utils import trace

    _fault.fire("mesh", "mesh")
    sh = batch_sharding(mesh)
    # the reshard cost is a span of its own: in a profile session it
    # lies next to the shard_map program (doc/tracing.md)
    with trace.span("mesh/reshard"):
        return tuple(jax.device_put(a, sh) for a in arrays)


@functools.lru_cache(maxsize=16)
def sharded_verify_fn(mesh: Mesh):
    """jit-compiled ECDSA verify step sharded over the mesh's batch axis.

    Inputs: z, r, s, qx (B, NLIMBS) uint32 limb planes; parity (B,)
    uint32 — B divisible by the mesh size.  Output: bool (B,) with the
    same sharding, plus a replicated scalar count of valid sigs (forces
    a psum collective, which doubles as the aggregate "how many failed"
    signal gossipd wants).

    Production consumer: gossip/verify.py verify_items routes replay
    buckets here when the process has >1 device (the mesh path of the
    streaming pipeline, doc/replay_pipeline.md); __graft_entry__'s
    multichip dryrun exercises the same program on the virtual CPU
    mesh."""
    from ..crypto import secp256k1 as S

    def step(z, r, s, qx, parity):
        ok = S.ecdsa_verify_kernel(z, r, s, qx, parity)
        return ok, jax.lax.psum(jnp.sum(ok.astype(jnp.uint32)), BATCH_AXIS)

    # shard_map (not GSPMD auto-partitioning): the verify kernel's batch
    # inversion is an associative_scan over the batch axis, which GSPMD
    # would implement with cross-device collectives; per-shard it is a
    # pure-local Montgomery product tree, and the ONLY collective left is
    # the explicit psum of the valid-count.
    sm = shard_map(step, mesh=mesh,
                   in_specs=(P(BATCH_AXIS),) * 5,
                   out_specs=(P(BATCH_AXIS), P()))
    return jax.jit(sm)
