#!/usr/bin/env python3
"""Ask the TPU's compiler, without a chip, whether it accepts the main
path's programs at the shapes chip_smoke.py drives them.

libtpu compiles for a device that is *described* and not attached, so
this runs in a CPU-only sandbox (keep ``JAX_PLATFORMS=cpu``).  Nothing
executes: a compile that passes says the chip's compiler takes the
program, never that it is right or fast.

    JAX_PLATFORMS=cpu python tools/chip_compile.py            # all
    JAX_PLATFORMS=cpu python tools/chip_compile.py route mcf  # some
    JAX_PLATFORMS=cpu python tools/chip_compile.py --list

One JSON line per program: name, ok, lower/compile seconds, the device
bytes the compiler reports, or the compiler's message.  Exit code 1 if
any program was refused.  tests/test_chip_compile.py keeps the quick
ones in tier-1 (it imports PROGRAMS from here); the minutes-long scan
programs are run from this script by hand.

The route program at the benchmark's configurations (temporaries, 64
lanes): 454,580,736 and 453,983,744 bytes at 25,000 channels
(`route`, `steps` 5, one-level segmented minimum; `route_hubs`,
`steps` 10, five passes over 32-row block winners), 4,411,954,176 at
the repo's mainnet preset of 250,000 (`route_mainnet_hubs`, `steps`
14, nine such passes); the one-level program before the block passes
took 456,306,176 and 4,410,341,376 at the two hub keys.  Measured once and
kept out of the table (PR 34): at the source's million channels
(240,000 nodes, `n_pad` 262,144, `e_pad` 2,097,152, `steps` 16) the
compiler refuses the 64-lane program, "Used 16.78G of 15.75G hbm",
and needs 8,966,581,248 bytes at 32 lanes; 500,000 channels (`steps`
15) needs 8,966,323,200 at 64.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOPOLOGY = "v5e:2x2"

# the smoke's graph: --mainnet --scale 0.1 → 6,000 nodes, 25,000
# channels = 50,000 directed edges (planes pad to powers of two)
SMOKE_NODES = 6_000
SMOKE_CHANNELS = 25_000
PALLAS_TILE = 512           # every pallas_secp entry point's documented tile


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _verify_args(bucket, mb, sh):
    import jax.numpy as jnp

    return (_sds((bucket, mb, 16), jnp.uint32, sh),
            _sds((bucket,), jnp.int32, sh), _sds((bucket,), jnp.int32, sh),
            _sds((bucket, 64), jnp.uint8, sh), _sds((bucket, 33), jnp.uint8, sh))


def _fused(mb):
    def build(sh):
        from lightning_tpu.crypto import secp256k1 as S
        from lightning_tpu.gossip import verify

        # the daemon's default engines, donate=True: the program it
        # builds off-CPU, at the bucket a boot replay runs on a TPU
        # (not verify.replay_bucket(): here jax reports the CPU)
        return (verify._jit_fused_resolved(
            *S._resolve_engine_names(None, None), True),
            _verify_args(verify.REPLAY_BUCKET_TPU, mb, sh))
    return build


def _limbs(n, sh, *lead):
    import jax.numpy as jnp

    from lightning_tpu.crypto import field as F

    return _sds((n, *lead, F.NLIMBS), jnp.uint32, sh)


def _sign_simple(sh):
    from lightning_tpu.crypto import secp256k1 as S
    from lightning_tpu.gossip import synth

    a = _limbs(synth.SIGN_BUCKET, sh)
    return S._jit_sign_simple(), (a, a, a)


def _sign_grind(sh):
    from lightning_tpu.crypto import secp256k1 as S

    a = _limbs(S.SIGN_BUCKET, sh)
    return S._jit_sign(), (a, a, _limbs(S.SIGN_BUCKET, sh,
                                        S.GRIND_CANDIDATES))


def _derive(sh):
    from lightning_tpu.crypto import secp256k1 as S

    return S._jit_derive(), (_limbs(SMOKE_NODES, sh),)


# the smoke graph's doubling steps: uniform endpoints give a largest
# out-degree of about 25, under 2^5
SMOKE_ROUTE_STEPS = 5
# the same counts with mainnet's degrees (the benchmark's
# `mainnet-tenth-hubs`: the largest hub holds 916 channels, under 2^10)
HUBS_ROUTE_STEPS = 10
# the repo's own mainnet preset (gossip/synth.py MAINNET_*) with
# mainnet's degrees, the benchmark's `mainnet-hubs`: the largest hub
# holds 8,951 of 250,000 channels, under 2^14
MAINNET_NODES = 60_000
MAINNET_CHANNELS = 250_000
MAINNET_HUBS_ROUTE_STEPS = 14


def _route(steps, nodes=SMOKE_NODES, channels=SMOKE_CHANNELS):
    def build(sh):
        from lightning_tpu.routing import device as RD
        from lightning_tpu.routing import planes as RP

        n_pad = RP._pow2_pad(nodes, RP._MIN_NODE_PAD)
        e_pad = RP._pow2_pad(2 * channels, RP._MIN_EDGE_PAD)
        return (RD._jit_route(n_pad, RD.DEFAULT_MAX_HOPS, steps),
                RD.program_operands(
                    RD.ROUTE_BATCH, n_pad, e_pad,
                    lambda shape, dtype: _sds(shape, dtype, sh)))
    return build


def _mcf(sh):
    import jax.numpy as jnp

    from lightning_tpu.routing import mcf_device as MD

    n_pad = MD._pow2(SMOKE_NODES, MD._MIN_NODE_PAD)
    a_pad = MD._pow2(2 * MD.NUM_PIECES * SMOKE_CHANNELS, MD._MIN_ARC_PAD)
    b = MD.MCF_BATCH
    a32 = _sds((2 * a_pad,), jnp.int32, sh)
    b32 = _sds((b,), jnp.int32, sh)
    return (MD._jit_mcf(n_pad, a_pad),
            (a32, a32, _sds((b, a_pad), jnp.float64, sh),
             _sds((b, a_pad), jnp.int64, sh), b32, b32,
             _sds((b,), jnp.int64, sh), b32))


def _pallas_dual_mul(name):
    def build(sh):
        import functools

        import jax

        from lightning_tpu.crypto import pallas_secp as PS

        fn = functools.partial(getattr(PS, name), tile=PALLAS_TILE,
                               interpret=False)
        a = _limbs(PALLAS_TILE, sh)
        return jax.jit(fn), (a, a, a, a)
    return build


def _pallas_prep(sh):
    import functools

    import jax
    import jax.numpy as jnp

    from lightning_tpu.crypto import pallas_secp as PS

    fn = functools.partial(PS.verify_prep_pallas, tile=PALLAS_TILE,
                           interpret=False)
    a = _limbs(PALLAS_TILE, sh)
    return jax.jit(fn), (a, _sds((PALLAS_TILE,), jnp.uint32, sh), a)


# name -> (builder(sharding) -> (jitted, arg shapes), needs the x64 scope)
PROGRAMS = {
    "fused_verify_mb4": (_fused(4), False),
    "fused_verify_mb8": (_fused(8), False),
    "sign_simple": (_sign_simple, False),
    "sign_grind": (_sign_grind, False),
    "derive_pubkeys": (_derive, False),
    "route": (_route(SMOKE_ROUTE_STEPS), True),
    "route_hubs": (_route(HUBS_ROUTE_STEPS), True),
    "route_mainnet_hubs": (_route(MAINNET_HUBS_ROUTE_STEPS, MAINNET_NODES,
                                  MAINNET_CHANNELS), True),
    "mcf": (_mcf, True),
    "pallas_prep": (_pallas_prep, False),
    "pallas": (_pallas_dual_mul("dual_mul_pallas"), False),
    "pallas_v2": (_pallas_dual_mul("dual_mul_pallas_v2"), False),
    "pallas_glv": (_pallas_dual_mul("dual_mul_pallas_glv"), False),
    "pallas_fb": (_pallas_dual_mul("dual_mul_pallas_fb"), False),
    "pallas_fbj": (_pallas_dual_mul("dual_mul_pallas_fbj"), False),
}


def describe_topology():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)


def compile_program(name: str, sharding):
    """Lower and compile one program for the described device; returns
    (compiled, lower_seconds, compile_seconds).  Raises what the chip's
    compiler raises."""
    import contextlib

    import jax

    build, x64 = PROGRAMS[name]
    with jax.enable_x64() if x64 else contextlib.nullcontext():
        fn, args = build(sharding)
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
    return compiled, t1 - t0, t2 - t1


def main(argv) -> int:
    if "--list" in argv:
        print("\n".join(PROGRAMS))
        return 0
    names = [a for a in argv if not a.startswith("-")] or list(PROGRAMS)
    import jax
    from jax.sharding import SingleDeviceSharding

    # a described-device executable cannot be read back without a chip:
    # keep these compiles out of the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    one_chip = SingleDeviceSharding(describe_topology().devices[0])
    bad = 0
    for name in names:
        row = {"program": name, "topology": TOPOLOGY}
        try:
            compiled, t_lower, t_compile = compile_program(name, one_chip)
            mem = compiled.memory_analysis()
            row.update(ok=True, lower_s=round(t_lower, 1),
                       compile_s=round(t_compile, 1),
                       temp_bytes=getattr(mem, "temp_size_in_bytes", None),
                       arg_bytes=getattr(mem, "argument_size_in_bytes", None))
        except Exception as e:
            bad += 1
            row.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
        print(json.dumps(row), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
