"""End-to-end ECDSA verify through the fused Pallas engine + fused prep
(interpret mode on the CPU mesh).

Split from test_pallas_secp.py: this one test is ~15 minutes of
interpret-mode tracing and compiling, and under ``--dist loadfile`` a
file is one worker's job (PR 23).
"""
from __future__ import annotations

import numpy as np

from lightning_tpu.crypto import field as F
from lightning_tpu.crypto import ref_python as ref
from lightning_tpu.crypto import secp256k1 as S

B = 8


def test_full_verify_fused_engines():
    """End-to-end ecdsa_verify_kernel with the fused dual-mul + fused
    prep ('pallas_fb+pp') agrees with the default engine on valid,
    corrupted, off-curve-pubkey and s=0 signatures.  The prep kernel's
    (qy, on_curve, w) parity is pinned transitively through these
    outcomes — a standalone prep-parity test would compile the same
    ~600-op sqrt/inverse chains a second time, and one interpret-mode
    compile of them already costs minutes on CPU."""
    rng = np.random.default_rng(12)
    n = B
    msgs = rng.integers(0, 256, (n, 32)).astype(np.uint8)
    keys = [int.from_bytes(rng.bytes(32), "big") % ref.N or 1
            for i in range(n)]
    import hashlib
    sigs = np.zeros((n, 64), np.uint8)
    pubs = np.zeros((n, 33), np.uint8)
    for i, k in enumerate(keys):
        h = hashlib.sha256(bytes(msgs[i])).digest()
        r, sv = ref.ecdsa_sign(h, k)
        sigs[i, :32] = np.frombuffer(r.to_bytes(32, "big"), np.uint8)
        sigs[i, 32:] = np.frombuffer(sv.to_bytes(32, "big"), np.uint8)
        p = ref.pubkey_create(k)
        pubs[i, 0] = 2 + (p.y & 1)
        pubs[i, 1:] = np.frombuffer(p.x.to_bytes(32, "big"), np.uint8)
    sigs[2, 40] ^= 0xFF  # corrupt one signature
    pubs[4, 1:] = 0
    pubs[4, 33 - 1] = 5   # x=5 is not on secp256k1
    sigs[5, 32:] = 0      # s=0 must fail (inv(0)=0 convention)
    hashes = np.stack([np.frombuffer(
        hashlib.sha256(bytes(m)).digest(), np.uint8) for m in msgs])

    z = F.from_bytes_be(hashes)
    r = F.from_bytes_be(sigs[:, :32])
    sv = F.from_bytes_be(sigs[:, 32:])
    qx = F.from_bytes_be(pubs[:, 1:])
    par = (pubs[:, 0] & 1).astype(np.uint32)

    want = np.asarray(S._jit_verify()(z, r, sv, qx, par))
    got = np.asarray(S._jit_verify("pallas_fb+pp")(z, r, sv, qx, par))
    expect = np.ones(n, bool)
    expect[[2, 4, 5]] = False
    assert np.array_equal(want, expect)
    assert np.array_equal(got, expect)
