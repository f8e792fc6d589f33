"""Pallas dual-mul kernel vs the XLA path and the exact-int oracle.

Runs in interpret mode on the CPU mesh (the TPU path compiles the same
program through Mosaic; the bench exercises that for real).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightning_tpu.crypto import field as F
from lightning_tpu.crypto import pallas_secp as PS
from lightning_tpu.crypto import ref_python as ref
from lightning_tpu.crypto import secp256k1 as S

B = 8


def _rand_stored(rng, shape):
    return rng.integers(0, F.STORED_LIMB_MAX + 1, shape).astype(np.uint32)


def test_field_ops_match_xla():
    rng = np.random.default_rng(3)
    a = _rand_stored(rng, (B, F.NLIMBS))
    b = _rand_stored(rng, (B, F.NLIMBS))
    for mod in (F.FP, F.FN):
        for name, fT, fX in (
            ("add", PS.addT, F.add),
            ("sub", PS.subT, F.sub),
            ("mul", PS.mulT, F.mul),
        ):
            got = jax.jit(lambda x, y, m=mod, f=fT: f(m, x, y))(a.T, b.T).T
            want = jax.jit(lambda x, y, m=mod, f=fX: f(m, x, y))(a, b)
            gn = np.asarray(jax.jit(
                lambda v, m=mod: F.normalize(m, v))(got))
            wn = np.asarray(jax.jit(
                lambda v, m=mod: F.normalize(m, v))(want))
            assert np.array_equal(gn, wn), f"{mod.name} {name}"


def test_point_ops_match_oracle():
    rng = np.random.default_rng(4)
    ks = [int.from_bytes(rng.bytes(32), "big") % ref.N or 1
          for _ in range(B)]
    pts = [ref.pubkey_create(k) for k in ks]
    X = np.stack([F.int_to_limbs(p.x) for p in pts])
    Y = np.stack([F.int_to_limbs(p.y) for p in pts])
    Z = np.stack([F.int_to_limbs(1) for _ in pts])

    def run(f, *args):
        out = jax.jit(f)(*args)
        return tuple(np.asarray(jax.jit(
            lambda v: F.normalize(F.FP, v))(o.T)) for o in out)

    gx, gy, gz = run(lambda x, y, z: PS.point_doubleT((x, y, z)),
                     X.T, Y.T, Z.T)
    for i, p in enumerate(pts):
        d = ref.point_double(p)
        zi = F.limbs_to_int(gz[i])
        assert F.limbs_to_int(gx[i]) % ref.P == d.x * zi % ref.P
    ax, ay, az = run(
        lambda x, y, z, u, v, w: PS.point_addT((x, y, z), (u, v, w)),
        X.T, Y.T, Z.T,
        np.roll(X, 1, 0).T, np.roll(Y, 1, 0).T, Z.T)
    for i, p in enumerate(pts):
        q = pts[(i - 1) % B]
        sm = ref.point_add(p, q)
        zi = F.limbs_to_int(az[i])
        assert F.limbs_to_int(ax[i]) % ref.P == sm.x * zi % ref.P


def test_dual_mul_pallas_awkward_batch():
    """Batch sizes with no supported tile divisor (advisor round-3 low
    finding: B=600 raised ValueError) must pad-and-slice, not crash.
    Scaled-down twin: tile=4 with B=6 exercises the same pad path."""
    rng = np.random.default_rng(9)
    n = 6
    u1 = np.stack([F.int_to_limbs(
        int.from_bytes(rng.bytes(32), "big") % ref.N) for _ in range(n)])
    u2 = np.stack([F.int_to_limbs(
        int.from_bytes(rng.bytes(32), "big") % ref.N) for _ in range(n)])
    pts = [ref.pubkey_create(
        int.from_bytes(rng.bytes(32), "big") % ref.N or 1)
        for _ in range(n)]
    qx = np.stack([F.int_to_limbs(p.x) for p in pts])
    qy = np.stack([F.int_to_limbs(p.y) for p in pts])

    got = PS.dual_mul_pallas(u1, u2, qx, qy, tile=4)
    assert got[0].shape[0] == n
    gx, _gy = jax.jit(S.point_to_affine)(got)
    for i in range(n):
        k1 = F.limbs_to_int(u1[i])
        k2 = F.limbs_to_int(u2[i])
        expect = ref.point_add(ref.point_mul(k1, ref.G),
                               ref.point_mul(k2, pts[i]))
        x_aff = F.limbs_to_int(
            np.asarray(jax.jit(lambda v: F.normalize(F.FP, v))(gx))[i])
        assert x_aff == expect.x


def test_dual_mul_pallas_matches_xla():
    rng = np.random.default_rng(5)
    u1 = np.stack([F.int_to_limbs(
        int.from_bytes(rng.bytes(32), "big") % ref.N) for _ in range(B)])
    u2 = np.stack([F.int_to_limbs(
        int.from_bytes(rng.bytes(32), "big") % ref.N) for _ in range(B)])
    pts = [ref.pubkey_create(
        int.from_bytes(rng.bytes(32), "big") % ref.N or 1)
        for _ in range(B)]
    qx = np.stack([F.int_to_limbs(p.x) for p in pts])
    qy = np.stack([F.int_to_limbs(p.y) for p in pts])

    want = jax.jit(S.dual_mul)(u1, u2, qx, qy)
    got = jax.jit(
        lambda a, b, c, d: PS.dual_mul_pallas(a, b, c, d, tile=B))(
        u1, u2, qx, qy)
    # same projective point up to normalization: compare affine x/y
    wz = jax.jit(lambda p: S.point_to_affine(p))(want)
    gz = jax.jit(lambda p: S.point_to_affine(p))(got)
    for w, g in zip(wz, gz):
        assert np.array_equal(
            np.asarray(jax.jit(lambda v: F.normalize(F.FP, v))(w)),
            np.asarray(jax.jit(lambda v: F.normalize(F.FP, v))(g)))
    # and against the exact-int oracle
    for i in range(B):
        k1 = F.limbs_to_int(u1[i])
        k2 = F.limbs_to_int(u2[i])
        expect = ref.point_add(ref.point_mul(k1, ref.G),
                               ref.point_mul(k2, pts[i]))
        x_aff = F.limbs_to_int(
            np.asarray(jax.jit(lambda v: F.normalize(F.FP, v))(gz[0]))[i])
        assert x_aff == expect.x
