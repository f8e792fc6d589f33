"""Batched secp256k1 in JAX: point arithmetic + ECDSA/Schnorr verify/sign.

This replaces the reference's serial libsecp256k1 call sites —
check_signed_hash (/root/reference/bitcoin/signature.c:174, used by
gossipd/sigcheck.c for every gossip message), check_schnorr_sig
(signature.c:408) and sign_hash (signature.c:97, low-R grinding) — with
data-parallel kernels over a whole batch of signatures at once.

TPU-first design choices:

* Points are homogeneous projective (X:Y:Z) over the redundant limb
  engine in ``field.py``; infinity is (0:1:0).  All point ops use the
  Renes–Costello–Batina *complete* formulas (EUROCRYPT 2016, a=0
  specialization): exception-free and branchless — no selects, no
  equality tests, no special cases anywhere in the hot loop, so one
  traced program serves every input including ∞, P=Q and P=-Q.
* The double-scalar multiply u1·G + u2·Q (the ECDSA/Schnorr hot loop)
  interleaves a constant 4-bit window table for G with a per-element
  4-bit window table for Q as a 64-step ``lax.scan``: 4 doublings + two
  table adds per step, all batched.
* Signing grinds low-R the batched way: GRIND_CANDIDATES nonce candidates
  per signature are evaluated in one fixed-base batch and the first low-R
  candidate is chosen branchlessly (the reference loops+retries serially,
  signature.c:102-117).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import field as F
from . import ref_python as ref
from .field import FP, FN, NLIMBS

P_INT = F.P_INT
N_INT = F.N_INT
B3 = 21  # 3·b for y² = x³ + 7

_add = functools.partial(F.add, FP)
_add3 = functools.partial(F.add3, FP)
_sub = functools.partial(F.sub, FP)
_mul = functools.partial(F.mul, FP)
_sqr = functools.partial(F.sqr, FP)


def _b3(a):
    return F.mul_small(FP, a, B3)


WINDOW = 4
NDIGITS = 256 // WINDOW  # 64


# ---------------------------------------------------------------------------
# Constant tables (host-side precompute with the exact-int oracle)


@functools.lru_cache(maxsize=1)
def _comb_table() -> np.ndarray:
    """(NDIGITS, 16, 2, NLIMBS) uint32: entry [j][v] = affine (x, y) of
    v * 2^(4j) * G.  v=0 entries are dummies (masked at use in the comb
    path; replaced by (0:1:0) in the projective window path)."""
    table = np.zeros((NDIGITS, 16, 2, NLIMBS), dtype=np.uint32)
    base = ref.G
    for j in range(NDIGITS):
        acc = ref.INFINITY
        for v in range(1, 16):
            acc = ref.point_add(acc, base)
            table[j, v, 0] = F.int_to_limbs(acc.x)
            table[j, v, 1] = F.int_to_limbs(acc.y)
        for _ in range(WINDOW):
            base = ref.point_double(base)
    return table


@functools.lru_cache(maxsize=1)
def _g_window_proj() -> np.ndarray:
    """(16, 3, NLIMBS): projective window table for G — entry v = v·G with
    Z=1, entry 0 = (0:1:0)."""
    comb = _comb_table()
    out = np.zeros((16, 3, NLIMBS), dtype=np.uint32)
    out[0, 1, 0] = 1  # infinity (0:1:0)
    for v in range(1, 16):
        out[v, 0] = comb[0, v, 0]
        out[v, 1] = comb[0, v, 1]
        out[v, 2, 0] = 1
    return out


# ---------------------------------------------------------------------------
# Complete projective point ops (RCB, a=0).  A point is a tuple (X, Y, Z).


def point_select(cond, p, q):
    return tuple(F.select(cond, a, b) for a, b in zip(p, q))


def point_inf(shape=()):
    return (F.zero(shape), F.one(shape), F.zero(shape))


def point_is_inf(p):
    return F.is_zero(FP, p[2])


def point_double(p):
    """RCB complete doubling, a=0 (alg 9): 3M + 2S + 1 small. Handles ∞."""
    X, Y, Z = p
    t0 = _sqr(Y)
    Z3 = _add(t0, t0)
    Z3 = _add(Z3, Z3)
    Z3 = _add(Z3, Z3)
    t1 = _mul(Y, Z)
    t2 = _sqr(Z)
    t2 = _b3(t2)
    X3 = _mul(t2, Z3)
    Y3 = _add(t0, t2)
    Z3 = _mul(t1, Z3)
    t1 = _add(t2, t2)
    t2 = _add(t1, t2)
    t0 = _sub(t0, t2)
    Y3 = _mul(t0, Y3)
    Y3 = _add(X3, Y3)
    t1 = _mul(X, Y)
    X3 = _mul(t0, t1)
    X3 = _add(X3, X3)
    return (X3, Y3, Z3)


def point_add(p1, p2):
    """RCB complete addition, a=0 (alg 7): 12M + 2 small.  Exception-free:
    covers ∞ operands, P=Q (acts as doubling) and P=-Q (yields ∞)."""
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    t0 = _mul(X1, X2)
    t1 = _mul(Y1, Y2)
    t2 = _mul(Z1, Z2)
    t3 = _add(X1, Y1)
    t4 = _add(X2, Y2)
    t3 = _mul(t3, t4)
    t4 = _add(t0, t1)
    t3 = _sub(t3, t4)
    t4 = _add(Y1, Z1)
    X3 = _add(Y2, Z2)
    t4 = _mul(t4, X3)
    X3 = _add(t1, t2)
    t4 = _sub(t4, X3)
    X3 = _add(X1, Z1)
    Y3 = _add(X2, Z2)
    X3 = _mul(X3, Y3)
    Y3 = _add(t0, t2)
    Y3 = _sub(X3, Y3)
    X3 = _add(t0, t0)
    t0 = _add(X3, t0)
    t2 = _b3(t2)
    Z3 = _add(t1, t2)
    t1 = _sub(t1, t2)
    Y3 = _b3(Y3)
    X3 = _mul(t4, Y3)
    t2 = _mul(t3, t1)
    X3 = _sub(t2, X3)
    Y3 = _mul(Y3, t0)
    t1 = _mul(t1, Z3)
    Y3 = _add(t1, Y3)
    t0 = _mul(t0, t3)
    Z3 = _mul(Z3, t4)
    Z3 = _add(Z3, t0)
    return (X3, Y3, Z3)


def point_to_affine(p):
    """(x, y) with (0, 0) for infinity (inv(0)=0 convention)."""
    X, Y, Z = p
    zi = F.inv_batch(FP, Z)
    return _mul(X, zi), _mul(Y, zi)


# ---------------------------------------------------------------------------
# Scalar digit machinery


def _digits4(scalar):
    """CANONICAL (normalized) scalar limbs → (..., 64) 4-bit digits,
    little-endian digit order."""
    bits = F.canonical_bits(scalar, 256)  # (..., 256) LSB-first
    nib = bits.reshape(*bits.shape[:-1], NDIGITS, 4)
    w = jnp.asarray(np.array([1, 2, 4, 8], np.uint32))
    return jnp.einsum("...ij,j->...i", nib, w)


def _table_lookup(table, idx):
    """table: (B, 16, 3, NLIMBS); idx: (B,) → 3 coords (B, NLIMBS).

    Selection is a one-hot contraction, not a gather: per-row dynamic
    gathers serialize on the TPU VPU (measured 34× slower than the
    16-way masked sum below, and they were the single largest cost of
    the whole ECDSA verify program)."""
    B, nv, k, nl = table.shape
    oh = (idx[:, None] == jnp.arange(nv, dtype=idx.dtype)).astype(jnp.uint32)
    flat = table.reshape(B, nv, k * nl)
    out = jnp.einsum("bv,bvk->bk", oh, flat).reshape(B, k, nl)
    return out[:, 0], out[:, 1], out[:, 2]


def _shared_table_lookup(table, idx):
    """table: (nv, 3, NLIMBS) shared across the batch; idx: (B,) →
    3 coords (B, NLIMBS).  One-hot contraction for the same reason as
    _table_lookup."""
    nv = table.shape[0]
    oh = (idx[:, None] == jnp.arange(nv, dtype=idx.dtype)).astype(jnp.uint32)
    out = jnp.einsum("bv,vk->bk", oh, table.reshape(nv, -1))
    out = out.reshape(-1, 3, NLIMBS)
    return out[:, 0], out[:, 1], out[:, 2]


def _build_window(qx, qy):
    """Per-element projective window table T[v] = v·Q, v = 0..15:
    (B, 16, 3, NLIMBS)."""
    Bsz = qx.shape[0]
    entries = [point_inf((Bsz,)), (qx, qy, F.one((Bsz,)))]
    for v in range(2, 16):
        entries.append(point_add(entries[v - 1], entries[1]))
    return jnp.stack([jnp.stack(e, axis=-2) for e in entries], axis=-3)


def dual_mul(u1, u2, qx, qy):
    """u1·G + u2·Q batched (u1, u2 canonical limbs; qx, qy affine limbs).
    Returns a projective point tuple."""
    qtab = _build_window(qx, qy)
    gtab = jnp.asarray(_g_window_proj())  # (16, 3, NLIMBS)
    d1 = _digits4(u1)
    d2 = _digits4(u2)
    xs = (jnp.flip(d1, axis=-1).T, jnp.flip(d2, axis=-1).T)  # (64, B)

    def body(acc, x):
        dg1, dg2 = x
        for _ in range(WINDOW):
            acc = point_double(acc)
        acc = point_add(acc, _table_lookup(qtab, dg2))
        acc = point_add(acc, _shared_table_lookup(gtab, dg1))
        return acc, None

    inf0 = tuple(F.match_variance(c, u1) for c in point_inf((u1.shape[0],)))
    acc, _ = lax.scan(body, inf0, xs)
    return acc


def fixed_base_mul(k):
    """k·G batched via the doubling-free comb (64 adds of precomputed
    v·2^(4j)·G windows).  k: canonical limbs."""
    Bsz = k.shape[0]
    comb = _comb_table()  # (64, 16, 2, NLIMBS) affine
    proj = np.zeros((NDIGITS, 16, 3, NLIMBS), dtype=np.uint32)
    proj[:, :, 0] = comb[:, :, 0]
    proj[:, :, 1] = comb[:, :, 1]
    proj[:, 1:, 2, 0] = 1
    proj[:, 0, 1, 0] = 1
    proj[:, 0, 0] = 0
    proj = jnp.asarray(proj)
    digits = _digits4(k)  # (B, 64)

    def body(acc, x):
        tg, dg = x  # tg: (16, 3, NLIMBS)
        acc = point_add(acc, _shared_table_lookup(tg, dg))
        return acc, None

    inf0 = tuple(F.match_variance(c, k) for c in point_inf((Bsz,)))
    acc, _ = lax.scan(body, inf0, (proj, digits.T))
    return acc


# ---------------------------------------------------------------------------
# Curve / pubkey helpers


def _nonzero(a):
    return jnp.any(a != 0, axis=-1)


def _pow2k(a, k: int):
    """a^(2^k) mod p; rolled loop for long squaring runs."""
    if k <= 4:
        for _ in range(k):
            a = _sqr(a)
        return a
    return lax.fori_loop(0, k, lambda _, v: _sqr(v), a)


def sqrt_p(a):
    """a^((p+1)/4) mod p via a repunit addition chain: the exponent is
    [223 ones] 0 [22 ones] 0000 11 00, so building x^(2^k - 1) blocks by
    doubling-composition needs ~253 squarings + 14 multiplies — vs ~247
    data-dependent multiplies for the generic bit-scan pow_const
    (measured ~9 ms of the 52 ms batched verify @4096 on TPU).
    test_field pins it against pow_const and the int oracle."""
    r1 = a
    r2 = _mul(_pow2k(r1, 1), r1)        # x^(2^2-1)
    r4 = _mul(_pow2k(r2, 2), r2)
    r6 = _mul(_pow2k(r4, 2), r2)
    r8 = _mul(_pow2k(r4, 4), r4)
    r16 = _mul(_pow2k(r8, 8), r8)
    r22 = _mul(_pow2k(r16, 6), r6)
    r44 = _mul(_pow2k(r22, 22), r22)
    r88 = _mul(_pow2k(r44, 44), r44)
    r176 = _mul(_pow2k(r88, 88), r88)
    r220 = _mul(_pow2k(r176, 44), r44)
    r222 = _mul(_pow2k(r220, 2), r2)
    r223 = _mul(_pow2k(r222, 1), r1)
    acc = _pow2k(r223, 1)               # append 0
    acc = _mul(_pow2k(acc, 22), r22)    # append 22 ones
    acc = _pow2k(acc, 4)                # append 0000
    acc = _mul(_pow2k(acc, 2), r2)      # append 11
    return _pow2k(acc, 2)               # append 00


def _sqrt_chain_exponent() -> int:
    """The exponent sqrt_p actually computes (mirrors its structure in
    exact ints) — asserted equal to (p+1)/4 in tests."""
    e1 = 1
    e2 = (e1 << 1) + e1
    e4 = (e2 << 2) + e2
    e6 = (e4 << 2) + e2
    e8 = (e4 << 4) + e4
    e16 = (e8 << 8) + e8
    e22 = (e16 << 6) + e6
    e44 = (e22 << 22) + e22
    e88 = (e44 << 44) + e44
    e176 = (e88 << 88) + e88
    e220 = (e176 << 44) + e44
    e222 = (e220 << 2) + e2
    e223 = (e222 << 1) + e1
    acc = e223 << 1
    acc = (acc << 22) + e22
    acc <<= 4
    acc = (acc << 2) + e2
    return acc << 2


def decompress(qx, parity):
    """Canonical x, parity bit → (y, on_curve)."""
    y2 = _add(_mul(_sqr(qx), qx), F.from_const(7, qx.shape[:-1]))
    y = sqrt_p(y2)
    on_curve = F.eq(FP, _sqr(y), y2)
    yn = F.normalize(FP, y)
    flip = (yn[..., 0] & 1) != parity.astype(jnp.uint32)
    y = F.select(flip, F.sub(FP, F.zero(qx.shape[:-1]), y), y)
    return y, on_curve


# ---------------------------------------------------------------------------
# ECDSA


def ecdsa_verify_kernel(z, r, s, qx, q_parity, dual_mul_impl=None,
                        prep_impl=None):
    """Batched ECDSA verify.

    z: (B, 20) hash limbs (raw 256-bit value, reduced mod n implicitly)
    r, s: (B, 20) canonical signature scalar limbs
    qx: (B, 20) canonical pubkey x limbs; q_parity: (B,) y parity (0/1)
    Returns bool (B,).  Fully branchless; invalid encodings yield False.
    dual_mul_impl: alternate u1·G+u2·Q engine (the fused Pallas kernel
    in crypto.pallas_secp); default = the XLA scan.
    prep_impl: alternate (decompress, s-inverse) engine with signature
    (qx, parity, s) -> (qy, on_curve, w); default = XLA decompress +
    Montgomery inv_batch.
    """
    # the phases carry jax.named_scope names (doc/tracing.md): they ride
    # the ops' metadata into a device trace and survive a refactor that
    # renumbers `%while.18`
    with jax.named_scope("verify_scalar_prep"):
        r_ok = F.lt_const(r, N_INT) & _nonzero(r)
        # libsecp256k1's secp256k1_ecdsa_verify (bitcoin/signature.c:174
        # path) rejects high-S outright: accept only s ≤ (n-1)/2
        s_ok = F.lt_const(s, (N_INT + 1) // 2) & _nonzero(s)
        q_ok = F.lt_const(qx, P_INT)
        if prep_impl is not None:
            qy, on_curve, w = prep_impl(qx, q_parity, s)
        else:
            qy, on_curve = decompress(qx, q_parity)
            w = F.inv_batch(FN, s)
        u1 = F.normalize(FN, F.mul(FN, z, w))
        u2 = F.normalize(FN, F.mul(FN, r, w))
    with jax.named_scope("verify_dual_mul"):
        R = (dual_mul_impl or dual_mul)(u1, u2, qx, qy)
    with jax.named_scope("verify_compare"):
        Rx, _, Rz = R
        not_inf = ~F.is_zero(FP, Rz)
        # projective x(R) ≡ r (mod n) check without inversion:
        # x(R) = Rx/Rz; candidates r' ∈ {r, r+n} with r' < p
        chk1 = F.eq(FP, Rx, _mul(r, Rz))
        r_plus_n = _add(r, F.from_const(N_INT, r.shape[:-1]))
        small_r = F.lt_const(r, P_INT - N_INT)
        chk2 = small_r & F.eq(FP, Rx, _mul(r_plus_n, Rz))
        return r_ok & s_ok & q_ok & on_curve & not_inf & (chk1 | chk2)


GRIND_CANDIDATES = 4


def _low_r(r_norm):
    """low-R ⇔ r < 2^255 ⇔ bit 255 (bit 8 of limb 19) clear."""
    return ((r_norm[..., NLIMBS - 1] >> (255 - 13 * 19)) & 1) == 0


def ecdsa_sign_kernel(z, d, ks):
    """Batched ECDSA sign with batched low-R grinding.

    z: (B, 20) hash limbs; d: (B, 20) secret key limbs (< n);
    ks: (B, C, 20) canonical nonce candidates (RFC6979 stream, host-made).
    Returns (r, s, ok, grind_ok).  Picks the first low-R candidate
    branchlessly (reference grinds serially, bitcoin/signature.c:97-118);
    falls back to candidate 0 (valid but non-low-R) if none qualifies.
    """
    B, C, _ = ks.shape
    kf = ks.reshape(B * C, NLIMBS)
    rx, _ = point_to_affine(fixed_base_mul(kf))
    r_all = F.normalize(FN, F.normalize(FP, rx)).reshape(B, C, NLIMBS)
    low_r = _low_r(r_all) & _nonzero(r_all)  # (B, C)
    choice = jnp.argmax(low_r, axis=1)  # first True, else 0
    ok_grind = jnp.any(low_r, axis=1)
    take = lambda arr: jnp.take_along_axis(
        arr, choice[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    r_sel = take(r_all)
    k_sel = take(ks)
    ki = F.inv_batch(FN, k_sel)
    s = F.mul(FN, ki, F.add(FN, z, F.mul(FN, r_sel, d)))
    s = F.normalize(FN, s)
    s_ok = _nonzero(s)
    # low-S normalization (matching libsecp sign output)
    high = ~F.lt_const(s, (N_INT + 1) // 2)
    s = F.select(high, F.normalize(FN, F.sub(FN, F.zero((B,)), s)), s)
    r_ok = _nonzero(r_sel)
    return r_sel, s, r_ok & s_ok, ok_grind


def ecdsa_sign_simple_kernel(z, d, k):
    """Single-nonce sign (no low-R grinding): r = x(k·G) mod n,
    s = k⁻¹(z + r·d) mod n, low-S normalized.  Used for bulk synthesis."""
    rx, _ = point_to_affine(fixed_base_mul(k))
    r = F.normalize(FN, F.normalize(FP, rx))
    ki = F.inv_batch(FN, k)
    s = F.mul(FN, ki, F.add(FN, z, F.mul(FN, r, d)))
    s = F.normalize(FN, s)
    high = ~F.lt_const(s, (N_INT + 1) // 2)
    s = F.select(high, F.normalize(FN, F.sub(FN, F.zero(z.shape[:-1]), s)), s)
    ok = _nonzero(r) & _nonzero(s)
    return r, s, ok


def derive_pubkeys_kernel(d):
    """d·G → (x, y) normalized affine limbs (batch pubkey derivation)."""
    x, y = point_to_affine(fixed_base_mul(d))
    return F.normalize(FP, x), F.normalize(FP, y)


def derive_pubkeys(seckeys: np.ndarray) -> np.ndarray:
    """(B, 20) canonical seckey limbs → (B, 33) compressed SEC1 pubkeys."""
    x, y = _jit_derive()(jnp.asarray(seckeys))
    xb = F.to_bytes_be(np.asarray(x))
    parity = (np.asarray(y)[:, 0] & 1).astype(np.uint8)
    out = np.empty((len(xb), 33), np.uint8)
    out[:, 0] = 2 + parity
    out[:, 1:] = xb
    return out


# ---------------------------------------------------------------------------
# BIP340 Schnorr


def schnorr_verify_kernel(e, rx, s, px):
    """Batched BIP340 verify given precomputed challenge e (raw 256-bit).

    e = int(tagged_hash("BIP0340/challenge", rx || px || msg)); computing
    it is the caller's job (see crypto.sha256 — it's a batched hash too).
    """
    r_ok = F.lt_const(rx, P_INT)
    s_ok = F.lt_const(s, N_INT)
    p_ok = F.lt_const(px, P_INT)
    py, on_curve = decompress(px, jnp.zeros(px.shape[:-1], jnp.uint32))
    e_n = F.normalize(FN, e)
    u = F.normalize(FN, F.sub(FN, F.zero(e.shape[:-1]), e_n))  # n - e
    R = dual_mul(F.normalize(FN, s), u, px, py)
    not_inf = ~F.is_zero(FP, R[2])
    x_aff, y_aff = point_to_affine(R)
    yn = F.normalize(FP, y_aff)
    even = (yn[..., 0] & 1) == 0
    x_eq = F.eq(FP, x_aff, rx)
    return r_ok & s_ok & p_ok & on_curve & not_inf & even & x_eq


# ---------------------------------------------------------------------------
# Host-facing numpy APIs.  All pad to a fixed bucket so each kernel
# compiles exactly once per (bucket, platform) and is served from the
# persistent cache afterwards.  Env-overridable: protocol tests verify
# ONE signature at a time, and on a 1-core CPU box the wasted pad lanes
# of a 64-bucket dominate the whole suite's wall-clock.

import os as _os

VERIFY_BUCKET = int(_os.environ.get("LIGHTNING_TPU_VERIFY_BUCKET", "64"))


def _pad_rows(a: np.ndarray, n: int) -> np.ndarray:
    if a.shape[0] == n:
        return a
    return np.pad(a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


# Batches at or below this size verify on the HOST via the exact-int
# oracle instead of the device: a single-signature "batch" costs one
# full kernel dispatch (0.3 s on a 1-core CPU backend; not measured
# on a local chip) versus ~4 ms of host bigint math.
# The batched pipelines (gossip ingest/store replay, HTLC fan-out)
# always exceed it; the protocol paths' one-off checks never should
# have paid the kernel tax.  Mirrors the device kernel's semantics
# exactly (low-S enforcement, tag/curve checks).
HOST_VERIFY_MAX = int(_os.environ.get("LIGHTNING_TPU_HOST_VERIFY_MAX",
                                      "2"))


def host_verify_batch(msg_hashes: np.ndarray, sigs64: np.ndarray,
                      pubkeys33: np.ndarray) -> np.ndarray:
    """The host verification oracle (exact-int ECDSA, kernel-parity
    semantics incl. the high-S reject): the micro-batch branch of
    ecdsa_verify_batch and hsmd's check-sig breaker fallback both
    route here, so device and fallback verdicts can never diverge."""
    return _host_verify(msg_hashes, sigs64, pubkeys33)


def _host_verify(msg_hashes: np.ndarray, sigs64: np.ndarray,
                 pubkeys33: np.ndarray) -> np.ndarray:
    out = np.zeros(msg_hashes.shape[0], bool)
    for i in range(msg_hashes.shape[0]):
        pk = bytes(pubkeys33[i])
        if pk[0] not in (2, 3):
            continue
        r = int.from_bytes(bytes(sigs64[i, :32]), "big")
        s = int.from_bytes(bytes(sigs64[i, 32:]), "big")
        if not (1 <= r < N_INT and 1 <= s <= (N_INT - 1) // 2):
            continue   # kernel parity: high-S rejected outright
        try:
            q = ref.pubkey_parse(pk)
        except Exception:
            continue
        out[i] = ref.ecdsa_verify(bytes(msg_hashes[i]), r, s, q)
    return out


def resolve_dual_mul(name: str | None = None):
    """Select the u1·G+u2·Q engine by name (or the
    LIGHTNING_TPU_DUAL_MUL env var).  Variants, all bit-identical
    (tests pin them to the exact-int oracle):

      xla        — the 64-window lax.scan below
      glv        — GLV endomorphism split, 33-window scan (crypto.glv)
      pallas     — fused Mosaic kernel, streamed pre-selected planes
      pallas_v2  — fused kernel, VMEM-resident tables
      pallas_glv — GLV + VMEM-resident tables (fewest HBM bytes + FLOPs)
      pallas_fb  — pallas_glv + IN-KERNEL window-table build (scratch
                   VMEM); remaining XLA prep is split/digits only
      pallas_fbj — pallas_fb + pre-summed 1024-entry joint G/φG table:
                   one fixed-base add per window instead of two
    """
    import os

    name = name or os.environ.get("LIGHTNING_TPU_DUAL_MUL", "glv")
    if name in ("xla", "scan"):
        return None                      # kernel default
    if name == "glv":
        from .glv import dual_mul_glv
        return dual_mul_glv
    from . import pallas_secp as PS

    return {"pallas": PS.dual_mul_pallas,
            "pallas_v2": PS.dual_mul_pallas_v2,
            "pallas_glv": PS.dual_mul_pallas_glv,
            "pallas_fb": PS.dual_mul_pallas_fb,
            "pallas_fbj": PS.dual_mul_pallas_fbj}[name]


def resolve_prep(name: str | None = None):
    """Select the (decompress, s-inverse) prep engine:
      xla    — XLA decompress + Montgomery inv_batch (default)
      pallas — fused limbs-first kernel (crypto.pallas_secp
               verify_prep_pallas: in-kernel sqrt chain + Fermat inv)
    """
    import os

    name = name or os.environ.get("LIGHTNING_TPU_VERIFY_PREP", "xla")
    if name == "pallas":
        from . import pallas_secp as PS
        return PS.verify_prep_pallas
    if name != "xla":
        # loud failure: a typo'd engine name must not silently measure
        # the XLA prep under a fused-prep label
        raise KeyError(f"unknown verify-prep engine {name!r}")
    return None


def _resolve_engine_names(impl_name: str | None, prep_name: str | None):
    """Resolve env defaults and the "impl+suffix" form to concrete
    (impl, prep) names.  Done OUTSIDE every jit cache: the cache key
    must be the resolved names, or an env change mid-process would keep
    serving the previously-built program under the new label."""
    if impl_name is None:
        impl_name = _os.environ.get("LIGHTNING_TPU_DUAL_MUL", "glv")
    if "+" in impl_name:
        impl_name, suffix = impl_name.split("+", 1)
        prep_name = {"pp": "pallas"}.get(suffix, suffix)
    if prep_name is None:
        prep_name = _os.environ.get("LIGHTNING_TPU_VERIFY_PREP", "xla")
    return impl_name, prep_name


def _jit_verify(impl_name: str | None = None,
                prep_name: str | None = None):
    return _jit_verify_resolved(*_resolve_engine_names(impl_name, prep_name))


@functools.lru_cache(maxsize=16)
def _jit_verify_resolved(impl_name: str, prep_name: str):
    impl = resolve_dual_mul(impl_name)
    prep = resolve_prep(prep_name)
    return jax.jit(functools.partial(ecdsa_verify_kernel,
                                     dual_mul_impl=impl,
                                     prep_impl=prep))


@functools.lru_cache(maxsize=1)
def _jit_gather_rows():
    """Device-side row gather (z limbs by per-signature row index): the
    mesh replay's handoff between its hash stage and the sharded EC
    program (gossip.verify._mesh_device_fn), which keeps the z plane on
    the device.  The single-device replay gathers inside its fused
    program instead."""
    return jax.jit(lambda z_rows, idx: jnp.take(z_rows, idx, axis=0))


def ecdsa_verify_batch(msg_hashes: np.ndarray, sigs64: np.ndarray,
                       pubkeys33: np.ndarray, bucket: int = VERIFY_BUCKET):
    """msg_hashes: (B, 32) uint8; sigs64: (B, 64) compact r||s;
    pubkeys33: (B, 33) SEC1 compressed. Returns np bool (B,)."""
    B = msg_hashes.shape[0]
    if B <= HOST_VERIFY_MAX:
        return _host_verify(msg_hashes, sigs64, pubkeys33)
    z = F.from_bytes_be(msg_hashes)
    r = F.from_bytes_be(sigs64[:, :32])
    s = F.from_bytes_be(sigs64[:, 32:])
    qx = F.from_bytes_be(pubkeys33[:, 1:])
    parity = (pubkeys33[:, 0] & 1).astype(np.uint32)
    tag_ok = (pubkeys33[:, 0] == 2) | (pubkeys33[:, 0] == 3)
    out = np.zeros(B, bool)
    kern = _jit_verify()
    for start in range(0, B, bucket):
        end = min(start + bucket, B)
        sl = slice(start, end)
        ok = kern(
            jnp.asarray(_pad_rows(z[sl], bucket)),
            jnp.asarray(_pad_rows(r[sl], bucket)),
            jnp.asarray(_pad_rows(s[sl], bucket)),
            jnp.asarray(_pad_rows(qx[sl], bucket)),
            jnp.asarray(_pad_rows(parity[sl], bucket)),
        )
        out[sl] = np.asarray(ok)[: end - start]
    return out & tag_ok


@functools.lru_cache(maxsize=2)
def _jit_schnorr():
    return jax.jit(schnorr_verify_kernel)


def schnorr_verify_batch(msgs32: np.ndarray, sigs64: np.ndarray,
                         pubkeys32: np.ndarray, bucket: int = VERIFY_BUCKET):
    """BIP340 over 32-byte messages (the reference only signs hashes)."""
    import hashlib

    from . import sha256 as H

    B = msgs32.shape[0]
    th = hashlib.sha256(b"BIP0340/challenge").digest()
    msgs = [
        th + th + bytes(sigs64[i, :32]) + bytes(pubkeys32[i]) + bytes(msgs32[i])
        for i in range(B)
    ]
    blocks, nblocks = H.pack_messages(msgs)
    e_words = H.sha256_blocks(jnp.asarray(blocks), jnp.asarray(nblocks))
    e = np.asarray(H.digest_words_to_limbs(e_words))
    rx = F.from_bytes_be(sigs64[:, :32])
    s = F.from_bytes_be(sigs64[:, 32:])
    px = F.from_bytes_be(pubkeys32)
    out = np.zeros(B, bool)
    kern = _jit_schnorr()
    for start in range(0, B, bucket):
        end = min(start + bucket, B)
        sl = slice(start, end)
        ok = kern(
            jnp.asarray(_pad_rows(e[sl], bucket)),
            jnp.asarray(_pad_rows(rx[sl], bucket)),
            jnp.asarray(_pad_rows(s[sl], bucket)),
            jnp.asarray(_pad_rows(px[sl], bucket)),
        )
        out[sl] = np.asarray(ok)[: end - start]
    return out


SIGN_BUCKET = int(_os.environ.get("LIGHTNING_TPU_SIGN_BUCKET", "16"))


@functools.lru_cache(maxsize=1)
def _jit_sign():
    """Module-level cached jit of the grinding sign kernel (same pattern
    as _jit_verify_resolved): re-wrapping jax.jit per ecdsa_sign_batch
    call discarded the trace cache, so every batched sign re-traced the
    whole EC program before the executable-cache lookup."""
    return jax.jit(ecdsa_sign_kernel)


@functools.lru_cache(maxsize=1)
def _jit_sign_simple():
    return jax.jit(ecdsa_sign_simple_kernel)


@functools.lru_cache(maxsize=1)
def _jit_derive():
    return jax.jit(derive_pubkeys_kernel)


def host_sign_batch(msg_hashes: np.ndarray,
                    seckeys: list[int]) -> np.ndarray:
    """The host signing oracle: ref RFC6979 + low-R/low-S grinding,
    bit-identical to the device grinding-sign kernel.  The single place
    host-signed compact sigs are produced — the micro-batch branch of
    ecdsa_sign_batch and hsmd's sign-breaker fallback both route here,
    so their wire bytes can never diverge."""
    B = msg_hashes.shape[0]
    out = np.empty((B, 64), np.uint8)
    for i in range(B):
        r, s = ref.ecdsa_sign(bytes(msg_hashes[i]), seckeys[i])
        out[i, :32] = np.frombuffer(r.to_bytes(32, "big"), np.uint8)
        out[i, 32:] = np.frombuffer(s.to_bytes(32, "big"), np.uint8)
    return out


def ecdsa_sign_batch(msg_hashes: np.ndarray, seckeys: list[int],
                     bucket: int = SIGN_BUCKET):
    """Batched deterministic ECDSA sign (RFC6979 nonces host-side, point
    math + low-R grinding on device). Returns (B, 64) compact sigs.
    Micro-batches sign on the host (same rationale as HOST_VERIFY_MAX)."""
    B = msg_hashes.shape[0]
    if B <= HOST_VERIFY_MAX:
        return host_sign_batch(msg_hashes, seckeys)
    ks = np.zeros((B, GRIND_CANDIDATES, NLIMBS), np.uint32)
    for i in range(B):
        h = bytes(msg_hashes[i])
        for c in range(GRIND_CANDIDATES):
            extra = None if c == 0 else c.to_bytes(32, "little")
            ks[i, c] = F.int_to_limbs(ref.rfc6979_nonce(h, seckeys[i], extra))
    z = F.from_bytes_be(msg_hashes)
    d = F.from_int_array(seckeys)
    kern = _jit_sign()
    out = np.empty((B, 64), np.uint8)
    for start in range(0, B, bucket):
        end = min(start + bucket, B)
        sl = slice(start, end)
        kpad = np.tile(
            F.int_to_limbs(1), (bucket, GRIND_CANDIDATES, 1)
        ).astype(np.uint32)
        kpad[: end - start] = ks[sl]
        r, s, ok, _ = kern(
            jnp.asarray(_pad_rows(z[sl], bucket)),
            jnp.asarray(_pad_rows(d[sl], bucket)),
            jnp.asarray(kpad),
        )
        got = end - start
        assert bool(np.all(np.asarray(ok)[:got])), "degenerate nonce"
        out[sl, :32] = F.to_bytes_be(np.asarray(r))[:got]
        out[sl, 32:] = F.to_bytes_be(np.asarray(s))[:got]
    return out
