"""The in-kernel-selection Pallas dual-mul engines vs the exact-int
oracle, one engine per case (interpret mode on the CPU mesh).

Split from test_pallas_secp.py: each engine traces and compiles for
minutes in interpret mode, and under ``--dist loadfile`` a file is one
worker's job — in one file the Pallas tests were a 29-minute critical
path (PR 23).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax

from lightning_tpu.crypto import field as F
from lightning_tpu.crypto import pallas_secp as PS
from lightning_tpu.crypto import ref_python as ref
from lightning_tpu.crypto import secp256k1 as S

B = 8


@pytest.mark.parametrize("impl_name", [
    "dual_mul_pallas_v2", "dual_mul_pallas_glv", "dual_mul_pallas_fb",
    "dual_mul_pallas_fbj"])
def test_dual_mul_pallas_v2_and_glv_match_oracle(impl_name):
    """The in-kernel-selection (v2) and GLV (v3) kernels are bit-
    identical to the XLA path / exact-int oracle, including edge
    scalars (0, 1, n-1) that exercise infinity table entries and the
    split's sign handling."""
    impl = getattr(PS, impl_name)
    rng = np.random.default_rng(8)
    k1s = [0, 1, ref.N - 1] + [
        int.from_bytes(rng.bytes(32), "big") % ref.N for _ in range(B - 3)]
    k2s = [1, 0, ref.N - 1] + [
        int.from_bytes(rng.bytes(32), "big") % ref.N for _ in range(B - 3)]
    u1 = np.stack([F.int_to_limbs(x) for x in k1s])
    u2 = np.stack([F.int_to_limbs(x) for x in k2s])
    pts = [ref.pubkey_create(
        int.from_bytes(rng.bytes(32), "big") % ref.N or 1)
        for _ in range(B)]
    qx = np.stack([F.int_to_limbs(p.x) for p in pts])
    qy = np.stack([F.int_to_limbs(p.y) for p in pts])

    norm = jax.jit(lambda v: F.normalize(F.FP, v))
    got = impl(u1, u2, qx, qy, tile=B)
    gx, gy = jax.jit(S.point_to_affine)(got)
    gxn = np.asarray(norm(gx))
    gyn = np.asarray(norm(gy))
    for i in range(B):
        e = ref.point_add(ref.point_mul(k1s[i], ref.G),
                          ref.point_mul(k2s[i], pts[i]))
        if e.inf:
            assert not np.any(np.asarray(got[2]).T[i]), impl_name
            continue
        assert F.limbs_to_int(gxn[i]) == e.x, f"{impl_name} {i}"
        assert F.limbs_to_int(gyn[i]) == e.y, f"{impl_name} {i}"
