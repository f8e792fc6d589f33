#!/usr/bin/env python3
"""Stage-by-stage profile of the batched ECDSA verify pipeline on the
live backend (single-tenant: run only when nothing else is using the
TPU).  Times each stage with the N-dispatch + one-readback protocol
(queue order serializes the dispatches; the readback ends the window).

Stages:
  hash        sha256d message schedule + digest kernel
  decompress  y from (x, parity): sqrt via pow_const chain
  inv_s       Fermat inversion of s mod n
  u1u2        the two scalar muls + normalize
  build_win   per-element 16-entry window table (14 point adds, XLA)
  digits/glv  digit decomposition (+ GLV split/sign prep for glv)
  kernel      the pallas_call itself (pre-built operands)
  full        the production composition end to end
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    bucket = int(os.environ.get("PROF_BUCKET", "4096"))
    iters = int(os.environ.get("PROF_ITERS", "6"))
    impl = os.environ.get("PROF_IMPL", "pallas_glv")

    import jax
    import jax.numpy as jnp

    from lightning_tpu.crypto import field as F
    from lightning_tpu.crypto import secp256k1 as S
    from lightning_tpu.gossip import synth, verify

    platform = jax.devices()[0].platform
    print(f"# profile on {platform}, bucket={bucket}, impl={impl}",
          flush=True)

    rng = np.random.default_rng(42)
    rows, nb, sigs, pubs = synth.make_signed_batch(bucket, rng)
    blocks = verify._bytes_to_blocks(rows, verify.MAX_BLOCKS)
    blocks = jnp.asarray(blocks)
    nb = jnp.asarray(nb.astype(np.int32))
    r = jnp.asarray(F.from_bytes_be(sigs[:, :32]))
    s = jnp.asarray(F.from_bytes_be(sigs[:, 32:]))
    qx = jnp.asarray(F.from_bytes_be(pubs[:, 1:]))
    par = jnp.asarray((pubs[:, 0] & 1).astype(np.uint32))

    def timed(name, fn, *args):
        out = fn(*args)          # compile + warm
        leaves = jax.tree_util.tree_leaves(out)
        np.asarray(leaves[0])
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        leaves = jax.tree_util.tree_leaves(out)
        np.asarray(leaves[0])    # ONE readback fences the queue
        dt = (time.perf_counter() - t0) / iters
        print(json.dumps({"stage": name, "ms": round(dt * 1e3, 2),
                          "per_elem_us": round(dt * 1e6 / bucket, 3)}),
              flush=True)
        return out

    z = timed("hash", verify._jit_hash(), blocks, nb)

    decompress = jax.jit(lambda x, p: S.decompress(x, p))
    qy, _ = timed("decompress", decompress, qx, par)

    inv_s = jax.jit(lambda a: F.inv(F.FN, a))
    w = timed("inv_s", inv_s, s)

    u1u2 = jax.jit(lambda z, r, w: (
        F.normalize(F.FN, F.mul(F.FN, z, w)),
        F.normalize(F.FN, F.mul(F.FN, r, w))))
    u1, u2 = timed("u1u2", u1u2, z, r, w)

    build = jax.jit(lambda x, y: S._build_window(x, y))
    timed("build_win", build, qx, qy)

    if impl == "pallas_glv":
        from lightning_tpu.crypto import glv as GLV

        prep = jax.jit(lambda u: GLV.split(u))
        timed("glv_split", prep, u2)

    from lightning_tpu.crypto import pallas_secp as PS

    if os.environ.get("PROF_PREP") == "pallas":
        prep = jax.jit(lambda x, p, sv: PS.verify_prep_pallas(x, p, sv))
        timed("prep_pallas[sqrt+inv]", prep, qx, par, s)

    dual = {
        "pallas": PS.dual_mul_pallas,
        "pallas_v2": PS.dual_mul_pallas_v2,
        "pallas_glv": PS.dual_mul_pallas_glv,
        "pallas_fb": PS.dual_mul_pallas_fb,
        "pallas_fbj": PS.dual_mul_pallas_fbj,
    }.get(impl)
    if dual is not None:
        dj = jax.jit(lambda a, b, x, y: dual(a, b, x, y))
        timed("dual_mul[" + impl + "]", dj, u1, u2, qx, qy)

    vj = S._jit_verify(impl if impl != "xla" else None)
    timed("verify_full", vj, z, r, s, qx, par)

    full = lambda: vj(verify._jit_hash()(blocks, nb), r, s, qx, par)
    timed("hash+verify", lambda _: full(), 0)


if __name__ == "__main__":
    main()
