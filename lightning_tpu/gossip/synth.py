"""Synthetic gossip-network generator (tests + benchmarks + loadgen).

Produces a spec-valid gossip_store with n_channels channel_announcements
(4 real ECDSA sigs each), 2 channel_updates per channel and one
node_announcement per node — the same shape of workload as the reference's
"million channels project" store used by tools/bench-gossipd.sh.

Signing runs on-device in bulk (ecdsa_sign_simple_kernel); hashing at
generation time uses hashlib so test data is independent of the JAX SHA
kernel under test.

Mainnet scale: generation STREAMS — messages are built, signed, and
appended to the store in bounded chunks (``chunk`` messages at a time),
so memory stays flat no matter the graph size.  The CLI's ``--mainnet``
preset generates a ~60k-node / ~250k-channel store (the LN topology
snapshot scale the GNN-benchmarking literature works from); ``--scale``
cuts a proportional slice of the preset for smoke tests::

    python -m lightning_tpu.gossip.synth /tmp/mainnet.gs --mainnet
    python -m lightning_tpu.gossip.synth /tmp/slice.gs --mainnet --scale 0.01

The heavyweight crypto imports (jax, the sign kernels) load lazily, so
``sign=False`` graph generation — routing/topology workloads — never
pays them.
"""
from __future__ import annotations

import hashlib

import numpy as np

from . import wire
from .store import StoreWriter

SIGN_BUCKET = 1 << 12  # production/bench default; tests pass a small one

# the --mainnet preset: current-mainnet-shaped topology scale
MAINNET_CHANNELS = 250_000
MAINNET_NODES = 60_000
DEFAULT_CHUNK = 16384


def _sha256d(b: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(b).digest()).digest()


def _rand_scalars(rng: np.random.Generator, n: int) -> list[int]:
    from ..crypto import ref_python as ref

    return [int.from_bytes(rng.bytes(32), "big") % (ref.N - 1) + 1
            for _ in range(n)]


def _sign_bulk(hashes: list[bytes], keys: list[int], rng,
               bucket: int = SIGN_BUCKET) -> np.ndarray:
    """Batched device sign → (N, 64) compact sigs."""
    import jax.numpy as jnp

    from ..crypto import field as F
    from ..crypto import secp256k1 as S

    N = len(hashes)
    out = np.empty((N, 64), np.uint8)
    kern = S._jit_sign_simple()   # cached: re-wrapping loses the traces
    for start in range(0, N, bucket):
        end = min(start + bucket, N)
        B = bucket
        zs = np.zeros((B, F.NLIMBS), np.uint32)
        ds = np.zeros((B, F.NLIMBS), np.uint32)
        ks = np.zeros((B, F.NLIMBS), np.uint32)
        for i in range(start, end):
            zs[i - start] = F.int_to_limbs(int.from_bytes(hashes[i], "big"))
            ds[i - start] = F.int_to_limbs(keys[i])
            ks[i - start] = F.int_to_limbs(int.from_bytes(rng.bytes(32), "big") % (F.N_INT - 1) + 1)
        r, s, ok = kern(jnp.asarray(zs), jnp.asarray(ds), jnp.asarray(ks))
        assert bool(np.asarray(ok)[: end - start].all())
        out[start:end, :32] = F.to_bytes_be(np.asarray(r))[: end - start]
        out[start:end, 32:] = F.to_bytes_be(np.asarray(s))[: end - start]
    return out


def make_signed_batch(n: int, rng: np.random.Generator | None = None):
    """n signed channel_update-sized messages for kernel-only benches.
    Returns (rows, n_blocks, sigs, pubs): rows are sha-padded signed
    regions in the (n, MAX_BLOCKS*64) layout verify_items consumes."""
    from ..crypto import field as F
    from ..crypto import secp256k1 as S
    from ..utils import native
    from .verify import MAX_BLOCKS

    rng = rng or np.random.default_rng(0)
    keys = _rand_scalars(rng, n)
    pubs = S.derive_pubkeys(
        np.stack([F.int_to_limbs(k) for k in keys]).astype(np.uint32))
    msg_len = 130           # typical channel_update signed-region size
    raw = rng.integers(0, 256, n * msg_len).astype(np.uint8)
    offs = (np.arange(n, dtype=np.int64) * msg_len)
    lens = np.full(n, msg_len, np.int64)
    rows, nb = native.sha256_pack(raw, offs, lens, MAX_BLOCKS)
    hashes = [_sha256d(raw[i * msg_len:(i + 1) * msg_len].tobytes())
              for i in range(n)]
    sigs = _sign_bulk(hashes, keys, rng, min(SIGN_BUCKET, max(64, n)))
    return rows, nb, sigs, np.asarray(pubs)


def _scid_for(i: int) -> int:
    return (500000 + i // 2016) << 40 | (i % 2016) << 16 | 0


def make_network_store(
    path: str,
    n_channels: int,
    n_nodes: int | None = None,
    updates_per_channel: int = 2,
    node_announcements: bool = True,
    seed: int = 7,
    sign_bucket: int = SIGN_BUCKET,
    sign: bool = True,
    chunk: int = DEFAULT_CHUNK,
):
    """Generate and write a synthetic gossip store; returns counts.

    Streaming: messages are built, signed, and appended in chunks of
    ``chunk`` messages, so peak memory is O(chunk + n_nodes) no matter
    the graph size — a --mainnet store generates flat at a few tens of
    MB instead of materializing ~700k message buffers.

    sign=False writes zero signatures and derives pubkeys host-side —
    right for graph/routing tests and topology benches that never verify
    (no device kernels touched at all)."""
    from ..crypto import ref_python as ref

    rng = np.random.default_rng(seed)
    n_nodes = n_nodes or max(2, n_channels // 8)
    seckeys = _rand_scalars(rng, n_nodes)
    if sign:
        from ..crypto import field as F
        from ..crypto import secp256k1 as S

        pubs = S.derive_pubkeys(
            np.stack([F.int_to_limbs(k) for k in seckeys]).astype(np.uint32)
        )
        pub_bytes = [bytes(p) for p in pubs]
    else:
        pub_bytes = [ref.pubkey_serialize(ref.pubkey_create(k))
                     for k in seckeys]

    # channel endpoints; BOLT7: node_id_1 is the lexically lesser key
    a = rng.integers(0, n_nodes, n_channels)
    b = (a + 1 + rng.integers(0, n_nodes - 1, n_channels)) % n_nodes
    swap = np.array([pub_bytes[x] > pub_bytes[y] for x, y in zip(a, b)])
    n1 = np.where(swap, b, a)
    n2 = np.where(swap, a, b)
    chunk = max(1, chunk)

    n_cu = 0
    n_na = 0
    with StoreWriter(path) as w:

        def _write(msgs: list, ts0: int) -> None:
            w.append_many([bytes(m) for m in msgs],
                          [ts0 + k for k in range(len(msgs))])

        # --- channel_announcements: build, hash, bulk-sign, patch,
        # append — one bounded chunk at a time
        for start in range(0, n_channels, chunk):
            end = min(start + chunk, n_channels)
            ca_msgs = []
            for i in range(start, end):
                ca = wire.ChannelAnnouncement(
                    short_channel_id=_scid_for(i),
                    node_id_1=pub_bytes[n1[i]],
                    node_id_2=pub_bytes[n2[i]],
                    bitcoin_key_1=pub_bytes[n1[i]],
                    bitcoin_key_2=pub_bytes[n2[i]],
                )
                ca_msgs.append(bytearray(ca.serialize()))
            if sign:
                ca_hashes = [_sha256d(bytes(m[wire.CA_SIGNED_OFFSET:]))
                             for m in ca_msgs]
                sig_jobs_h, sig_jobs_k, patch = [], [], []
                for i in range(start, end):
                    for j, signer in enumerate((n1[i], n2[i],
                                                n1[i], n2[i])):
                        sig_jobs_h.append(ca_hashes[i - start])
                        sig_jobs_k.append(seckeys[signer])
                        patch.append((i - start, wire.CA_SIG_OFFSETS[j]))
                sigs = _sign_bulk(sig_jobs_h, sig_jobs_k, rng, sign_bucket)
                for (i, off), sig in zip(patch, sigs):
                    ca_msgs[i][off: off + 64] = bytes(sig)
            _write(ca_msgs, 1700000000 + start)

        # --- channel_updates, chunked over messages
        cu_msgs, cu_hashes, cu_keys = [], [], []

        def _flush_cu() -> None:
            nonlocal cu_msgs, cu_hashes, cu_keys, n_cu
            if not cu_msgs:
                return
            if sign:
                sigs = _sign_bulk(cu_hashes, cu_keys, rng, sign_bucket)
                for m, sig in zip(cu_msgs, sigs):
                    m[wire.CU_SIG_OFFSET: wire.CU_SIG_OFFSET + 64] = \
                        bytes(sig)
            _write(cu_msgs, 1700000000 + n_cu)
            n_cu += len(cu_msgs)
            cu_msgs, cu_hashes, cu_keys = [], [], []

        for i in range(n_channels):
            for d in range(updates_per_channel):
                direction = d % 2
                cu = wire.ChannelUpdate(
                    short_channel_id=_scid_for(i),
                    timestamp=1700000000 + i,
                    channel_flags=direction,
                    htlc_maximum_msat=int(rng.integers(1, 1 << 40)),
                    fee_base_msat=int(rng.integers(0, 5000)),
                    fee_proportional_millionths=int(rng.integers(0, 10000)),
                )
                m = bytearray(cu.serialize())
                cu_msgs.append(m)
                if sign:
                    cu_hashes.append(
                        _sha256d(bytes(m[wire.CU_SIGNED_OFFSET:])))
                    cu_keys.append(
                        seckeys[(n1 if direction == 0 else n2)[i]])
            if len(cu_msgs) >= chunk:
                _flush_cu()
        _flush_cu()

        # --- node_announcements, chunked over messages
        if node_announcements:
            for start in range(0, n_nodes, chunk):
                end = min(start + chunk, n_nodes)
                na_msgs, na_hashes, na_keys = [], [], []
                for i in range(start, end):
                    na = wire.NodeAnnouncement(
                        timestamp=1700000000 + i,
                        node_id=pub_bytes[i],
                        alias=(b"tpu-node-%06d" % i).ljust(32, b"\x00"),
                    )
                    m = bytearray(na.serialize())
                    na_msgs.append(m)
                    if sign:
                        na_hashes.append(
                            _sha256d(bytes(m[wire.NA_SIGNED_OFFSET:])))
                        na_keys.append(seckeys[i])
                if sign:
                    sigs = _sign_bulk(na_hashes, na_keys, rng, sign_bucket)
                    for m, sig in zip(na_msgs, sigs):
                        m[wire.NA_SIG_OFFSET: wire.NA_SIG_OFFSET + 64] = \
                            bytes(sig)
                _write(na_msgs, 1700000000 + start)
                n_na += len(na_msgs)

    return {
        "channels": n_channels,
        "nodes": n_nodes,
        "channel_updates": n_cu,
        "node_announcements": n_na,
        "sigs": 4 * n_channels + n_cu + n_na,
        "seckeys": seckeys,
    }


def main(argv=None) -> int:
    """CLI front-end: stream a synthetic gossip_store to disk."""
    import argparse
    import json

    ap = argparse.ArgumentParser(
        prog="python -m lightning_tpu.gossip.synth",
        description="Generate a synthetic (spec-valid) gossip_store. "
        "Generation streams in bounded chunks, so --mainnet-sized "
        "stores build with flat memory.")
    ap.add_argument("path", help="output gossip_store file")
    ap.add_argument("--channels", type=int, default=1000)
    ap.add_argument("--nodes", type=int, default=0,
                    help="0 = channels // 8")
    ap.add_argument("--updates-per-channel", type=int, default=2)
    ap.add_argument("--mainnet", action="store_true",
                    help=f"preset: ~{MAINNET_NODES} nodes / "
                    f"~{MAINNET_CHANNELS} channels")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="scale factor applied to the --mainnet preset "
                    "(smoke-test slices, e.g. --scale 0.01)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--no-sign", action="store_true",
                    help="zero signatures, host-derived pubkeys (no jax)")
    ap.add_argument("--sign-bucket", type=int, default=SIGN_BUCKET)
    ap.add_argument("--chunk", type=int, default=DEFAULT_CHUNK,
                    help="messages generated+written per streamed chunk")
    args = ap.parse_args(argv)
    channels, nodes = args.channels, args.nodes or None
    if args.mainnet:
        channels = max(1, int(MAINNET_CHANNELS * args.scale))
        nodes = max(2, int(MAINNET_NODES * args.scale))
    if not args.no_sign:
        # the sign and derive programs compile for minutes cold
        from ..utils.jaxcfg import setup_cache

        setup_cache()
    info = make_network_store(
        args.path, channels, nodes,
        updates_per_channel=args.updates_per_channel, seed=args.seed,
        sign=not args.no_sign, sign_bucket=args.sign_bucket,
        chunk=args.chunk)
    info.pop("seckeys")
    print(json.dumps(info))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
