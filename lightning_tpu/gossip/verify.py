"""Batched gossip-signature verification: the primary TPU offload.

The reference verifies each gossip message inline and serially as it is
processed (gossipd/sigcheck.c:45 sigcheck_channel_announcement does 4
ECDSA verifies per channel_announcement; :9 and :118 do one each for
channel_update / node_announcement; each preceded by a sha256d).  Here the
whole store (or any batch of messages) becomes flat arrays:

  host:   mmap store → native scan → vectorized field gathers
  device: fused sha256d + z-gather + batched ECDSA verify
          (ONE jit program per bucket)

The replay is a streaming bucket pipeline (doc/replay_pipeline.md):
signatures are sorted by message row and cut into self-contained
buckets (a bucket's signatures reference only the bucket's own rows),
so each bucket is one fused device dispatch with no inter-bucket data
flow.  Host-side bucket prep (extraction slice, byte→block pack, pad)
runs on a producer thread ahead of the dispatch loop — while bucket i
verifies on device, bucket i+1 is being packed — and the only
device→host transfer of the whole replay is the final boolean
readback.  With >1 device the EC stage routes through the
parallel/mesh.py batch sharding (sharded_verify_fn).
"""
from __future__ import annotations

import functools
import logging
import queue as _queue
import threading
import time
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..crypto import field as F
from ..crypto import secp256k1 as S
from ..crypto import sha256 as H
from ..obs import attribution as _attr
from ..obs import families as _families
from ..obs import flight as _flight
from ..resilience import breaker as _breaker
from ..resilience import deadline as _deadline
from ..resilience import faultinject as _fault
from ..resilience import quarantine as _quarantine
from ..utils import native, trace
from . import wire
from .store import StoreIndex

log = logging.getLogger("lightning_tpu.gossip.verify")

# Verify buckets: a fixed batch shape, so one compiled program serves
# any number of signatures (the remainder is padded with dummy
# always-False rows that are masked out host-side).  The one knob is
# LIGHTNING_TPU_VERIFY_BUCKET; where it is unset there are two
# defaults, because two needs conflict.  A live flush (GossipIngest,
# Gossipd, warmup(), and S.VERIFY_BUCKET for hsmd's checks) carries 256
# signatures or fewer and waits on its own dispatch, so its bucket is a
# question of latency and stays DEFAULT_BUCKET = 64.  A boot replay
# (verify_store) is one stream of every signature in the store, so its
# bucket is a question of throughput: replay_bucket() below.  Neither
# is derived from the other and no rule adapts one to the other.
import os as _os

DEFAULT_BUCKET = int(_os.environ.get("LIGHTNING_TPU_VERIFY_BUCKET", str(S.VERIFY_BUCKET)))
MAX_BLOCKS = 8  # 512-byte signed regions cover all standard gossip msgs

# Lanes of one dispatch of a boot replay on a TPU.  A constant of the
# platform and not of the store: a node with a small store pads one
# dispatch, and builds or loads the very program a full store runs.
# The sweep behind it (one v5e, `glv` + `xla`, PERF.md §5, PR 33): ms
# an execution of the fused program alone, and signatures a second of
# a crash boot over 156,000 signatures (benchmark cell
# mainnet-tenth.crashboot):
#
#    lanes   ms alone   sigs/s in the cell
#       64     13.0         5,023
#      256     15.5       not run
#      512     17.9        28,714
#    1,024     31.6        32,073
#    2,048     57.3       not run (32,900 by the curve)
#    4,096     88.2       not run (41,000 by the curve)
#
# Up to 512 lanes an execution is bound by the issue of its ops and
# lanes are nearly free; from there on it is linear in its lanes.
# 1,024 is the best of the buckets the benchmark's 0.1 s traced
# sub-window can read (it needs two whole dispatches); the larger ones
# wait for that window to be sized in dispatches (ROADMAP S1).
REPLAY_BUCKET_TPU = 1024


def replay_bucket() -> int:
    """Lanes per dispatch of a boot replay that was given no bucket:
    LIGHTNING_TPU_VERIFY_BUCKET where it is set, else the platform's
    constant (64, the live path's, on any backend that was not swept).
    Asked when a replay starts, not at import: jax.default_backend()
    starts the backend."""
    env = _os.environ.get("LIGHTNING_TPU_VERIFY_BUCKET", "")
    if env:
        return int(env)
    return REPLAY_BUCKET_TPU if jax.default_backend() == "tpu" else 64

# -- observability (doc/observability.md) ----------------------------------
_M_FLUSH_SECONDS = obs.histogram(
    "clntpu_verify_flush_seconds",
    "End-to-end wall time of one verify_items replay (plan + stream + "
    "readback + host fallback)")
_M_BATCH_SIGS = obs.histogram(
    "clntpu_verify_batch_sigs",
    "Signatures per verify_items call", buckets=obs.SIZE_BUCKETS)
_M_OCCUPANCY = obs.histogram(
    "clntpu_verify_batch_occupancy_ratio",
    "Real lanes / padded lanes per verify_items call "
    "(1.0 = no bucket padding waste)", buckets=obs.RATIO_BUCKETS)
_M_LANES = obs.counter(
    "clntpu_verify_lanes_total",
    "Device lanes dispatched (real + pad), by kind",
    labelnames=("kind",))
_M_DEVICE_BYTES = obs.counter(
    "clntpu_verify_device_bytes_total",
    "Host->device bytes staged for verify dispatches")
_M_OVERSIZED = obs.counter(
    "clntpu_verify_oversized_host_total",
    "Oversized rows (n_blocks == 0) verified on the host fallback path")
_M_COMPILE = obs.counter(
    "clntpu_verify_compile_events_total",
    "New program shapes compiled (warmup or live), by program",
    labelnames=("program",))

# -- streaming-replay pipeline stages (doc/replay_pipeline.md) -------------
# Vocabulary: "prep" is host bucket build (extraction slice + byte→block
# pack + pad), "stall" is the slice of prep that was VISIBLE on the
# dispatch thread's critical path (waiting on the prepared-bucket queue;
# in serial mode stall == prep by definition), "dispatch" is upload +
# program enqueue, "readback" is the single end-of-replay block on the
# device booleans.  overlap_ratio = 1 - stall/prep: the fraction of host
# prep wall time hidden behind device compute.  The stage counters, the
# lane, byte and signature counters move bucket by bucket, so a window
# cut mid-replay reads them whole; the per-replay histograms (batch
# sigs, occupancy, overlap, flush seconds) observe once.  Families are DECLARED
# in obs/families.py (jax-free) so the attribution model and capture
# tools see them without this module's crypto-stack import.
_M_R_PREP = _families.REPLAY_PREP
_M_R_STALL = _families.REPLAY_STALL
_M_R_DISPATCH = _families.REPLAY_DISPATCH
_M_R_READBACK = _families.REPLAY_READBACK
_M_R_OVERLAP = _families.REPLAY_OVERLAP
_M_R_QDEPTH = _families.REPLAY_QDEPTH
_M_R_BUCKETS = _families.REPLAY_BUCKETS
_M_R_SIGS = _families.REPLAY_SIGS
_M_TRANSFER = _families.TRANSFER_BYTES

# every (program, shape) jax compiles exactly once per process; tracking
# first-sights here turns "did the live path hit a compile stall?" into
# a scrape (warmup pre-populates the expected shapes, so a LIVE
# increment means a flush paid a compile)
_seen_shapes: set = set()


def _note_shape(program: str, key: tuple) -> None:
    if (program, key) not in _seen_shapes:
        _seen_shapes.add((program, key))
        _M_COMPILE.labels(program).inc()
        # the retrace detector (obs/attribution.py): once warmup() has
        # completed, a first-sight here means a LIVE flush paid a
        # compile — clntpu_retrace_total fires + the `retrace` topic
        _attr.note_program(program, key)


def gossip_hash_kernel(blocks, n_blocks):
    """sha256d(signed region) → z limbs.  A standalone jit program for
    the mesh hash stage and tools/profile_verify.py; the single-device
    replay runs the fused bucket program below instead."""
    digest = H.sha256d_blocks(blocks, n_blocks)
    return H.digest_words_to_limbs(digest)


@functools.lru_cache(maxsize=2)
def _jit_hash():
    return jax.jit(gossip_hash_kernel)


def fused_verify_kernel(blocks, n_blocks, roi, sig_bytes, pub_bytes,
                        dual_mul_impl=None, prep_impl=None):
    """ONE device program per bucket: sha256d(signed regions) → z-row
    gather by local row index → byte→limb unpack → batched ECDSA verify.

    Buckets are self-contained (a bucket's signatures reference only
    the bucket's own rows), so the gather's operand shape is the static
    (bucket, NLIMBS).  A cold XLA:CPU compile of this
    program takes ~4 min at full opt — warmup() covers both quantized
    block widths, and the persistent cache serves every later process.
    """
    with jax.named_scope("verify_sha256d"):
        z_rows = H.digest_words_to_limbs(
            H.sha256d_blocks(blocks, n_blocks))
        z = jnp.take(z_rows, roi, axis=0)
    with jax.named_scope("verify_unpack"):
        r = F.from_bytes_be_dev(sig_bytes[:, :32])
        s = F.from_bytes_be_dev(sig_bytes[:, 32:])
        qx = F.from_bytes_be_dev(pub_bytes[:, 1:])
        parity = (pub_bytes[:, 0] & 1).astype(jnp.uint32)
    return S.ecdsa_verify_kernel(z, r, s, qx, parity,
                                 dual_mul_impl=dual_mul_impl,
                                 prep_impl=prep_impl)


@functools.lru_cache(maxsize=8)
def _jit_fused_resolved(impl_name: str, prep_name: str, donate: bool):
    impl = S.resolve_dual_mul(impl_name)
    prep = S.resolve_prep(prep_name)
    kern = functools.partial(fused_verify_kernel,
                             dual_mul_impl=impl, prep_impl=prep)
    # donate the big upload buffers (blocks/sigs/pubs) so the device
    # runtime can reuse their memory inside the program; donation is a
    # no-op (plus a per-call warning) on the CPU backend, so only ask
    # for it where it does something
    return jax.jit(kern, donate_argnums=(0, 3, 4) if donate else ())


def _jit_fused():
    donate = jax.default_backend() not in ("cpu",)
    return _jit_fused_resolved(*S._resolve_engine_names(None, None), donate)


def warmup(bucket: int = DEFAULT_BUCKET) -> None:
    """Compile (or load from the persistent cache) the replay programs
    at the given bucket, off the live path.  A cold XLA:CPU compile of
    an EC program takes minutes; a daemon that first compiles one
    inside a live flush stalls gossip acceptance far past peer/test
    timeouts (found via test_gossip_origination on a fresh cache).
    Call from startup — idempotent and cheap once the jit caches are
    warm.

    The replay needs exactly TWO programs per bucket: the fused
    sha256d+gather+verify program at both quantized SHA block widths
    (the bucket planner guarantees those are the only live shapes).

    Runs inside attribution.warmup_scope(): the shapes compiled here
    are EXPECTED first-sights, and the scope's exit arms the retrace
    detector — any program-shape first-sight after this call is a live
    compile stall and fires clntpu_retrace_total (doc/perf.md)."""
    with _attr.warmup_scope():
        _warmup_inner(bucket)


def _warmup_inner(bucket: int) -> None:
    nb = jnp.ones((bucket,), jnp.int32)
    idx = jnp.zeros((bucket,), jnp.int32)
    for mb in (4, MAX_BLOCKS):
        _note_shape("fused", (bucket, mb))
        # fresh operand arrays EVERY call: the production program
        # donates blocks/sigs/pubs on accelerators, so a reused
        # array would be a deleted buffer on the second iteration
        np.asarray(_jit_fused()(
            jnp.zeros((bucket, mb, 16), jnp.uint32), nb, idx,
            jnp.zeros((bucket, 64), jnp.uint8),
            jnp.zeros((bucket, 33), jnp.uint8)))
    # if flushes would route through the mesh (>1 usable device and not
    # opted out), warm THAT path's programs too — hash at both widths,
    # the local z gather, and the sharded EC program — by pushing dummy
    # prepared buckets through the real dispatcher (metrics suppressed:
    # warmup buckets are not replay dispatches); otherwise the first
    # multi-device flush pays the multi-minute cold compile this
    # function exists to keep off the live path.
    if _os.environ.get("LIGHTNING_TPU_MESH_VERIFY", "auto") != "off":
        mesh_fn = _mesh_device_fn(bucket, count_metrics=False)
        if mesh_fn is not None:
            for mb in (4, MAX_BLOCKS):
                np.asarray(mesh_fn(_PreparedBucket(
                    sel=np.arange(bucket), n_real=bucket, mb=mb,
                    blocks=np.zeros((bucket, mb, 16), np.uint32),
                    n_blocks=np.ones(bucket, np.int32),
                    roi_local=np.zeros(bucket, np.int32),
                    sigs=np.zeros((bucket, 64), np.uint8),
                    pubkeys=np.zeros((bucket, 33), np.uint8),
                    staged_bytes=0, prep_seconds=0.0)))
    # the hsmd batched-sign path (sign_htlc_batch / sign_withdrawal)
    # shares the startup warmup: one grinding-sign compile per process
    # at the production SIGN_BUCKET, so a channel's first commitment
    # fan-out never pays a cold EC compile mid-dance
    one = F.int_to_limbs(1).astype(np.uint32)
    zb = np.tile(one, (S.SIGN_BUCKET, 1))
    kb = np.tile(one, (S.SIGN_BUCKET, S.GRIND_CANDIDATES, 1))
    _note_shape("sign", (S.SIGN_BUCKET,))
    np.asarray(S._jit_sign()(
        jnp.asarray(zb), jnp.asarray(zb), jnp.asarray(kb))[0])


def _bytes_to_blocks(rows: np.ndarray, max_blocks: int) -> np.ndarray:
    """(B, max_blocks*64) uint8 → (B, max_blocks, 16) uint32 big-endian."""
    B = rows.shape[0]
    w = rows.reshape(B, max_blocks, 16, 4).astype(np.uint32)
    return (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) | w[..., 3]


@dataclass
class VerifyItems:
    """One flat signature-check workload (possibly many sigs per message).

    ``rows``/``n_blocks``/``z_host`` are per unique MESSAGE (M rows);
    sigs/pubkeys are per SIGNATURE (N items).  ``row_of_item`` maps each
    signature to its message row — None means 1:1 (M == N).  Hashing per
    unique row instead of per signature matters: channel_announcements
    carry 4 signatures over ONE signed region, so the per-item layout
    hashed (and uploaded) every CA region 4×."""

    rows: np.ndarray  # (M, MAX_BLOCKS*64) uint8 pre-padded signed regions
    n_blocks: np.ndarray  # (M,) uint32; 0 = oversized, hashed host-side
    sigs: np.ndarray  # (N, 64) uint8
    pubkeys: np.ndarray  # (N, 33) uint8
    msg_index: np.ndarray  # (N,) int64 — row in the originating batch
    z_host: np.ndarray | None = None  # (M, 32) host sha256d where n_blocks==0
    row_of_item: np.ndarray | None = None  # (N,) int64; None = identity

    @staticmethod
    def concat(items: list["VerifyItems"]) -> "VerifyItems":
        if any(x.z_host is not None for x in items):
            zh = np.concatenate([
                x.z_host if x.z_host is not None
                else np.zeros((x.rows.shape[0], 32), np.uint8)
                for x in items
            ])
        else:
            zh = None
        rois, base = [], 0
        for x in items:
            roi = (np.arange(len(x), dtype=np.int64)
                   if x.row_of_item is None else x.row_of_item)
            rois.append(roi + base)
            base += x.rows.shape[0]
        return VerifyItems(
            np.concatenate([x.rows for x in items]),
            np.concatenate([x.n_blocks for x in items]),
            np.concatenate([x.sigs for x in items]),
            np.concatenate([x.pubkeys for x in items]),
            np.concatenate([x.msg_index for x in items]),
            zh,
            np.concatenate(rois),
        )

    def __len__(self):
        return len(self.sigs)


def _host_hash_oversized(buf: np.ndarray, offsets: np.ndarray,
                         lengths: np.ndarray, nb: np.ndarray):
    """sha256d for rows the packer flagged oversized (n_blocks == 0).
    Rare (long node_announcements) — returns None when there are none, so
    the common-case 1M-record replay allocates nothing here."""
    import hashlib

    which = np.nonzero(nb == 0)[0]
    if len(which) == 0:
        return None
    z = np.zeros((len(nb), 32), np.uint8)
    for i in which:
        o, l = int(offsets[i]), int(lengths[i])
        d = hashlib.sha256(
            hashlib.sha256(buf[o : o + l].tobytes()).digest()
        ).digest()
        z[i] = np.frombuffer(d, np.uint8)
    return z


def extract_channel_announcements(idx: StoreIndex) -> VerifyItems:
    """4 (sig, key) pairs per channel_announcement (sigcheck.c:45-113)."""
    n = len(idx)
    if n == 0:
        return _empty_items()
    off = idx.offsets
    sr_off = off + wire.CA_SIGNED_OFFSET
    sr_len = idx.lengths - wire.CA_SIGNED_OFFSET
    rows, nb = native.sha256_pack(idx.buf, sr_off, sr_len, MAX_BLOCKS)
    z_host = _host_hash_oversized(idx.buf, sr_off, sr_len, nb)
    flen_raw = native.gather_fields(idx.buf, off, wire.CA_FLEN_OFFSET, 2)
    flen = (flen_raw[:, 0].astype(np.uint64) << 8) | flen_raw[:, 1]
    key_base = wire.CA_FLEN_OFFSET + 2 + flen + 32 + 8
    sigs, keys = [], []
    for i, sig_off in enumerate(wire.CA_SIG_OFFSETS):
        sigs.append(native.gather_fields(idx.buf, off, sig_off, 64))
        keys.append(native.gather_fields(idx.buf, off + key_base, 33 * i, 33))
    # rows stay per-MESSAGE: the 4 signatures share one signed region,
    # and row_of_item maps them back — tiling the 512-byte rows 4× made
    # the hash phase (and its upload) 4× bigger for nothing
    return VerifyItems(
        rows,
        nb,
        np.concatenate(sigs),
        np.concatenate(keys),
        np.tile(np.arange(n, dtype=np.int64), 4),
        z_host,
        np.tile(np.arange(n, dtype=np.int64), 4),
    )


def extract_node_announcements(idx: StoreIndex) -> VerifyItems:
    n = len(idx)
    if n == 0:
        return _empty_items()
    off = idx.offsets
    sr_off = off + wire.NA_SIGNED_OFFSET
    sr_len = idx.lengths - wire.NA_SIGNED_OFFSET
    rows, nb = native.sha256_pack(idx.buf, sr_off, sr_len, MAX_BLOCKS)
    z_host = _host_hash_oversized(idx.buf, sr_off, sr_len, nb)
    flen_raw = native.gather_fields(idx.buf, off, 66, 2)
    flen = (flen_raw[:, 0].astype(np.uint64) << 8) | flen_raw[:, 1]
    sigs = native.gather_fields(idx.buf, off, wire.NA_SIG_OFFSET, 64)
    keys = native.gather_fields(idx.buf, off + flen, 68 + 4, 33)
    return VerifyItems(rows, nb, sigs, keys, np.arange(n, dtype=np.int64),
                       z_host)


def extract_channel_updates(idx: StoreIndex, scid_to_nodes) -> VerifyItems:
    """channel_update is signed by the direction-selected channel node
    (sigcheck.c:9-43); scid_to_nodes maps scid → (node_id_1, node_id_2)."""
    n = len(idx)
    if n == 0:
        return _empty_items()
    off = idx.offsets
    sr_off = off + wire.CU_SIGNED_OFFSET
    sr_len = idx.lengths - wire.CU_SIGNED_OFFSET
    rows, nb = native.sha256_pack(idx.buf, sr_off, sr_len, MAX_BLOCKS)
    z_host = _host_hash_oversized(idx.buf, sr_off, sr_len, nb)
    sigs = native.gather_fields(idx.buf, off, wire.CU_SIG_OFFSET, 64)
    scid_raw = native.gather_fields(idx.buf, off, wire.CU_SCID_OFFSET, 8)
    scids = scid_raw.astype(np.uint64)
    scid = np.zeros(n, np.uint64)
    for b in range(8):
        scid = (scid << np.uint64(8)) | scids[:, b]
    chan_flags = native.gather_fields(idx.buf, off, wire.CU_FLAGS_OFFSET + 1, 1)[:, 0]
    direction = chan_flags & 1
    keys = scid_to_nodes(scid, direction)  # (n, 33) uint8
    return VerifyItems(rows, nb, sigs, keys, np.arange(n, dtype=np.int64),
                       z_host)


def _empty_items() -> VerifyItems:
    return VerifyItems(
        np.zeros((0, MAX_BLOCKS * 64), np.uint8), np.zeros(0, np.uint32),
        np.zeros((0, 64), np.uint8), np.zeros((0, 33), np.uint8),
        np.zeros(0, np.int64),
    )


def make_scid_map(ca_idx: StoreIndex):
    """Vectorized scid → (node_id_1 | node_id_2) resolver built from the
    channel_announcement batch (sorted array + searchsorted)."""
    n = len(ca_idx)
    if n == 0:
        # no announcements: every update resolves to the zero key, which
        # fails verification (as it must)
        return lambda scids, direction: np.zeros((len(scids), 33), np.uint8)
    off = ca_idx.offsets
    flen_raw = native.gather_fields(ca_idx.buf, off, wire.CA_FLEN_OFFSET, 2)
    flen = (flen_raw[:, 0].astype(np.uint64) << 8) | flen_raw[:, 1]
    scid_raw = native.gather_fields(
        ca_idx.buf, off + flen, wire.CA_FLEN_OFFSET + 2 + 32, 8
    ).astype(np.uint64)
    scid = np.zeros(n, np.uint64)
    for b in range(8):
        scid = (scid << np.uint64(8)) | scid_raw[:, b]
    key_base = wire.CA_FLEN_OFFSET + 2 + flen + 40
    node1 = native.gather_fields(ca_idx.buf, off + key_base, 0, 33)
    node2 = native.gather_fields(ca_idx.buf, off + key_base, 33, 33)
    order = np.argsort(scid, kind="stable")
    scid_sorted = scid[order]
    node1s, node2s = node1[order], node2[order]

    def lookup(scids: np.ndarray, direction: np.ndarray) -> np.ndarray:
        pos = np.searchsorted(scid_sorted, scids)
        pos_c = np.clip(pos, 0, max(0, n - 1))
        found = (pos < n) & (scid_sorted[pos_c] == scids) if n else np.zeros(len(scids), bool)
        keys = np.where(
            (direction == 0)[:, None], node1s[pos_c], node2s[pos_c]
        )
        # unknown scid → zero key (fails verification, as it must)
        keys[~found] = 0
        return keys

    return lookup


# ---------------------------------------------------------------------------
# The streaming bucket pipeline (doc/replay_pipeline.md)


@dataclass
class _PreparedBucket:
    """One self-contained, fully host-prepped bucket: hash rows, local
    row indices and signature operands, all padded to the bucket."""

    sel: np.ndarray        # (n_real,) item indices, dispatch order
    n_real: int
    mb: int                # quantized SHA block width (4 or MAX_BLOCKS)
    blocks: np.ndarray     # (bucket, mb, 16) uint32
    n_blocks: np.ndarray   # (bucket,) int32
    roi_local: np.ndarray  # (bucket,) int32 — row index WITHIN the bucket
    sigs: np.ndarray       # (bucket, 64) uint8
    pubkeys: np.ndarray    # (bucket, 33) uint8
    staged_bytes: int
    prep_seconds: float


def _plan_buckets(roi_sorted: np.ndarray, bucket: int) -> list[tuple]:
    """Cut the row-sorted signature stream into self-contained buckets:
    ≤ bucket signatures AND ≤ bucket distinct rows each, so every
    bucket's fused program sees static (bucket, ·) shapes.  A message
    row whose signatures straddle a cut is simply hashed by both
    buckets (≤ 3 duplicate rows per cut — CAs carry 4 sigs/row).
    Returns [(sig_start, sig_end, row_start, row_end), ...]."""
    N = len(roi_sorted)
    out = []
    start = 0
    while start < N:
        cap = min(start + bucket, N)
        r0 = int(roi_sorted[start])
        # signatures referencing rows beyond r0 + bucket can't gather
        # from this bucket's (bucket,)-shaped z plane; cut before them
        end = start + int(np.searchsorted(roi_sorted[start:cap],
                                          r0 + bucket, side="left"))
        out.append((start, end, r0, int(roi_sorted[end - 1]) + 1))
        start = end
    return out


def _prep_bucket(items: VerifyItems, order: np.ndarray,
                 roi_sorted: np.ndarray, bucket: int,
                 chunk: tuple, corrs=()) -> _PreparedBucket:
    """Host side of one bucket: slice rows, byte→block pack, pad.  Runs
    on the producer thread in the overlapped pipeline (the corr
    carriers keep its spans causally linked to the enqueue point —
    contextvars don't follow us onto that thread)."""
    with trace.span("replay/prep", corr=corrs):
        return _prep_bucket_inner(items, order, roi_sorted, bucket, chunk)


def _prep_bucket_inner(items: VerifyItems, order: np.ndarray,
                       roi_sorted: np.ndarray, bucket: int,
                       chunk: tuple) -> _PreparedBucket:
    start, end, r0, r1 = chunk
    t0 = time.perf_counter()
    _fault.fire("prep", "verify")
    sel = order[start:end]
    nb = items.n_blocks[r0:r1]
    # rows arrive type-sorted (CA | NA | CU), so most buckets need far
    # fewer SHA blocks than the 8-block pad: channel_updates fit in 3,
    # node_announcements usually in 4.  Slicing the block axis halves
    # the host→device bytes for those buckets; quantizing to
    # {4, MAX_BLOCKS} bounds the fused-program shapes at two (both
    # precompiled by warmup).
    mbv = int(nb.max(initial=0))
    mb = 4 if 0 < mbv <= 4 else MAX_BLOCKS
    blocks = _bytes_to_blocks(
        S._pad_rows(items.rows[r0:r1], bucket)[:, :mb * 64], mb)
    nb_p = S._pad_rows(nb, bucket).astype(np.int32)
    roi_l = S._pad_rows((roi_sorted[start:end] - r0).astype(np.int32),
                        bucket)
    sigs = S._pad_rows(items.sigs[sel], bucket)
    pubs = S._pad_rows(items.pubkeys[sel], bucket)
    staged = (blocks.nbytes + nb_p.nbytes + roi_l.nbytes
              + sigs.nbytes + pubs.nbytes)
    return _PreparedBucket(sel, end - start, mb, blocks, nb_p, roi_l,
                           sigs, pubs, staged,
                           time.perf_counter() - t0)


def _fused_device_fn(bucket: int):
    """Default device path: one fused program per prepared bucket."""
    kern = _jit_fused()

    def dispatch(pb: _PreparedBucket):
        _note_shape("fused", (bucket, pb.mb))
        _M_R_BUCKETS.labels("fused").inc()
        return kern(jnp.asarray(pb.blocks), jnp.asarray(pb.n_blocks),
                    jnp.asarray(pb.roi_local), jnp.asarray(pb.sigs),
                    jnp.asarray(pb.pubkeys))

    return dispatch


@functools.lru_cache(maxsize=2)
def _cached_mesh(n_devices: int):
    from ..parallel import mesh as pmesh

    return pmesh.make_mesh(jax.devices()[:n_devices])


def _mesh_device_fn(bucket: int, count_metrics: bool = True):
    """Multi-device path: hash + local z gather stay single-device jit
    programs, the EC verify — ~99% of the device FLOPs — runs batch-
    sharded over the mesh via parallel/mesh.py sharded_verify_fn (the
    psum valid-count collective included).  Host converts sig/pubkey
    bytes to limbs (the sharded program's operand contract); the z
    plane moves device→mesh as a resharding device_put, never through
    numpy.  Returns None when no usable mesh exists (then the caller
    falls back to the fused single-device path).  count_metrics=False
    suppresses the bucket counter (warmup's dummy dispatches are not
    replay buckets; compile-event first-sights still record)."""
    from ..parallel import mesh as pmesh

    limit = _os.environ.get("LIGHTNING_TPU_MESH_DEVICES")
    n = pmesh.usable_device_count(bucket,
                                  int(limit) if limit else None)
    if n < 2:
        return None
    mesh = _cached_mesh(n)
    vfn = pmesh.sharded_verify_fn(mesh)

    def mesh_dispatch(pb: _PreparedBucket):
        _note_shape("hash", (bucket, pb.mb))
        _note_shape("gather", (bucket, bucket))
        _note_shape("mesh_verify", (bucket, n))
        if count_metrics:
            _M_R_BUCKETS.labels("mesh").inc()
        z_rows = _jit_hash()(jnp.asarray(pb.blocks),
                             jnp.asarray(pb.n_blocks))
        z = S._jit_gather_rows()(z_rows, jnp.asarray(pb.roi_local))
        r = F.from_bytes_be(pb.sigs[:, :32])
        s = F.from_bytes_be(pb.sigs[:, 32:])
        qx = F.from_bytes_be(pb.pubkeys[:, 1:])
        parity = (pb.pubkeys[:, 0] & 1).astype(np.uint32)
        zs, rs, ss, qxs, ps = pmesh.shard_batch(mesh, z, r, s, qx, parity)
        ok, _count = vfn(zs, rs, ss, qxs, ps)
        return ok

    # supervision: the mesh is an OPTIMIZATION over the fused
    # single-device program, so its breaker degrades mesh→fused (the
    # outer "verify" breaker still guards fused→host).  A failing
    # collective or dead mesh device trips this after N consecutive
    # failures and the replay keeps streaming on one device.
    fused = _fused_device_fn(bucket)

    def _supervised(pb: _PreparedBucket, rec: dict):
        brk = _breaker.get("mesh")
        rec["breaker_state"] = brk.state
        if not brk.allow():
            # mesh's fallback is the fused single-device program, not
            # the host; breaker_state="open" records the cause
            rec["outcome"] = "fused"
            return fused(pb)
        try:
            ok = mesh_dispatch(pb)
        except Exception as e:
            brk.record_failure()
            rec["outcome"] = "fused"
            rec["error"] = type(e).__name__
            log.warning("mesh-sharded verify failed (%s); this bucket "
                        "runs on the fused single-device program", e)
            return fused(pb)
        brk.record_success()
        rec["outcome"] = "ok"
        return ok

    def dispatch(pb: _PreparedBucket):
        if not count_metrics:      # warmup's dummy buckets: no records
            return _supervised(pb, {})
        # a nested flight record: the mesh shard links to its parent
        # verify dispatch via parent_dispatch_id (thread-local nesting)
        with _flight.dispatch("mesh", shape=(bucket, pb.mb),
                              n_real=pb.n_real, lanes=bucket) as rec:
            with trace.span("mesh/dispatch",
                            dispatch_id=rec["dispatch_id"]):
                return _supervised(pb, rec)

    return dispatch


def _select_device_fn(bucket: int, n_sigs: int):
    """Route buckets to the mesh-sharded EC stage when the process has
    >1 device and the batch is worth sharding; LIGHTNING_TPU_MESH_VERIFY
    = auto (default) | on | off.  The auto threshold
    (LIGHTNING_TPU_MESH_MIN_SIGS, default one full bucket) keeps
    protocol-path one-off checks on the single-device program."""
    mode = _os.environ.get("LIGHTNING_TPU_MESH_VERIFY", "auto")
    if mode != "off":
        try:
            ndev = len(jax.devices())
        except Exception:
            ndev = 1
        if ndev > 1:
            min_sigs = int(_os.environ.get("LIGHTNING_TPU_MESH_MIN_SIGS",
                                           str(bucket)))
            if mode == "on" or n_sigs >= min_sigs:
                fn = _mesh_device_fn(bucket)
                if fn is not None:
                    return fn
    return _fused_device_fn(bucket)


def _host_device_fn(items: "VerifyItems", roi: np.ndarray, bucket: int):
    """LIGHTNING_TPU_VERIFY_DEVICE=off: a bucket dispatcher that routes
    straight to the host oracle — the FULL pipeline still runs (producer
    overlap, breaker/quarantine supervision, fault seams, flight
    records), but no device program is ever compiled or dispatched.
    Bit-identical to the device path by the oracle's construction.

    For CPU-only daemons and subprocess harnesses (tools/crashmatrix.py
    children) where a one-core jax compile would stall startup for
    minutes; the kill-seam coverage of the verify pipeline depends on
    the real pipeline machinery running, which a verify_items() stub
    would bypass."""

    def dispatch(pb: "_PreparedBucket") -> np.ndarray:
        _M_R_BUCKETS.labels("host_off").inc()
        ok = np.zeros(bucket, bool)
        if pb.n_real:
            ok[:pb.n_real] = _host_verify_selected(
                items, roi, pb.sel[: pb.n_real])
        return ok

    return dispatch


_DONE = object()


def _host_verify_selected(items: VerifyItems, roi: np.ndarray,
                          idx: np.ndarray) -> np.ndarray:
    """The trustworthy host escape hatch: sha256d + exact-int ECDSA for
    the given signature indices, straight off the packed host rows.

    The packer (native.sha256_pack) stores standard SHA-256 padding —
    0x80, zeros, 64-bit big-endian bit length closing block n_blocks-1
    — so the original signed region is recoverable from the row itself
    and no extraction-time buffer needs to be retained.  Rows flagged
    oversized (n_blocks == 0) hash to zero here; verify_items re-checks
    those against items.z_host afterward, exactly as it does for the
    device result.  Bit-identical to the device path by construction
    (S._host_verify mirrors the kernel's low-S/tag semantics)."""
    import hashlib

    idx = np.asarray(idx, np.int64)
    z = np.zeros((len(idx), 32), np.uint8)
    cache: dict[int, bytes] = {}
    for j, r in enumerate(roi[idx]):
        r = int(r)
        d = cache.get(r)
        if d is None:
            nbr = int(items.n_blocks[r])
            if nbr == 0:
                d = b"\0" * 32
            else:
                row = items.rows[r]
                bitlen = int.from_bytes(
                    row[nbr * 64 - 8: nbr * 64].tobytes(), "big")
                msg = row[: bitlen // 8].tobytes()
                d = hashlib.sha256(hashlib.sha256(msg).digest()).digest()
            cache[r] = d
        z[j] = np.frombuffer(d, np.uint8)
    return S._host_verify(z, items.sigs[idx], items.pubkeys[idx])


def _subbucket(pb: _PreparedBucket, lanes: np.ndarray,
               bucket: int) -> _PreparedBucket:
    """Re-pad a subset of a prepared bucket's signature lanes into a
    dispatchable bucket (same static shapes, so no new compile).  The
    hash-row planes are shared — only the per-signature operands and
    their row indices narrow."""
    return _PreparedBucket(
        sel=pb.sel[lanes], n_real=len(lanes), mb=pb.mb,
        blocks=pb.blocks, n_blocks=pb.n_blocks,
        roi_local=S._pad_rows(pb.roi_local[lanes], bucket),
        sigs=S._pad_rows(pb.sigs[lanes], bucket),
        pubkeys=S._pad_rows(pb.pubkeys[lanes], bucket),
        staged_bytes=0, prep_seconds=0.0)


def _wrap_resilient(device_fn, items: VerifyItems, roi: np.ndarray,
                    bucket: int, corrs=(), sink: list | None = None,
                    dispatch_map: np.ndarray | None = None):
    """Supervise one bucket dispatcher with the "verify" circuit
    breaker and poisoned-batch quarantine (doc/resilience.md):

    * breaker open → the whole bucket verifies on the host oracle
      (metered as a `host_breaker` bucket), bit-identical results;
    * dispatch raises → breaker records the failure and the bucket
      bisects: clean halves complete on the device, isolated rows are
      quarantined + re-checked host-side.  The replay completes either
      way — a single poisoned row no longer fails the whole store.

    Every call is one flight-recorded dispatch (obs/flight.py): the
    record lands in ``sink`` (dispatch order) and its span carries the
    replay's corr carriers, so each bucket shows up once in the
    exported timeline with a flow arrow back to the enqueue span.
    Records of successful dispatches are NOT sealed here — the
    readback at end-of-replay decides the final outcome (a failed
    readback is ``readback_host``), so sealing/metering waits for it
    (flight.defer); only a raising dispatch seals immediately.
    """
    brk = _breaker.get("verify")
    corr_ids = _flight.corr_ids(corrs)

    def host_lanes(pb: _PreparedBucket, lanes: np.ndarray) -> np.ndarray:
        return _host_verify_selected(items, roi, pb.sel[lanes])

    def _dispatch_inner(pb: _PreparedBucket, rec: dict):
        if not brk.allow():
            rec["outcome"] = "host_breaker"
            _M_R_BUCKETS.labels("host_breaker").inc()
            ok = np.zeros(bucket, bool)
            if pb.n_real:
                ok[:pb.n_real] = host_lanes(pb, np.arange(pb.n_real))
            return ok
        try:
            _fault.fire("dispatch", "verify")
            # operand upload happens inside device_fn (jnp.asarray on
            # the packed planes): account the staged bytes against THIS
            # dispatch only when a device dispatch is actually attempted
            rec["h2d_bytes"] = pb.staged_bytes
            _M_TRANSFER.labels("verify", "h2d").inc(pb.staged_bytes)
            ok = device_fn(pb)
        except Exception as e:
            brk.record_failure()
            rec["outcome"] = "bisect"
            rec["error"] = type(e).__name__
            log.warning("verify bucket dispatch failed (%s); bisecting "
                        "%d lanes", e, pb.n_real)
            out = np.zeros(bucket, bool)
            parts, bad = _quarantine.bisect(
                np.arange(pb.n_real),
                lambda lanes: np.asarray(
                    device_fn(_subbucket(pb, lanes, bucket)))[:len(lanes)],
                family="verify")
            for lanes, res in parts:
                out[lanes] = res
            if bad:
                lanes = np.asarray(bad, np.int64)
                out[lanes] = host_lanes(pb, lanes)
            return out
        brk.record_success()
        rec["outcome"] = "ok"
        return ok

    def dispatch(pb: _PreparedBucket, queue_wait: float = 0.0):
        rec = _flight.begin(
            "verify", corr_ids=corr_ids, shape=(bucket, pb.mb),
            n_real=pb.n_real, lanes=bucket,
            queue_wait_ms=queue_wait * 1e3,
            prep_ms=pb.prep_seconds * 1e3, breaker_state=brk.state)
        if sink is not None:
            sink.append(rec)
        if dispatch_map is not None:
            # per-item provenance (doc/journeys.md): pb.sel holds the
            # ORIGINAL signature indices this bucket carries, so the
            # caller learns which flight record verified each item
            dispatch_map[pb.sel[:pb.n_real]] = rec["dispatch_id"]
        t0 = time.perf_counter()
        try:
            with trace.span("verify/dispatch", corr=corrs,
                            dispatch_id=rec["dispatch_id"]):
                ok = _dispatch_inner(pb, rec)
        except BaseException as e:
            if rec["outcome"] is None:
                rec["outcome"] = "error"
            _flight.finish(rec,
                           dispatch_ms=(time.perf_counter() - t0) * 1e3,
                           error=type(e).__name__)
            raise
        rec["dispatch_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        _flight.defer(rec)
        return ok

    return dispatch


def _run_pipeline(items: VerifyItems, roi: np.ndarray, bucket: int,
                  depth: int | None, device_fn,
                  corrs=(), dispatch_map: np.ndarray | None = None,
                  ) -> tuple[np.ndarray, int]:
    """Sort signatures by row, cut self-contained buckets, and stream
    them: a producer thread preps bucket i+1 while bucket i's fused
    program runs on device.  depth bounds the prepared-bucket queue
    (HBM staging for ~depth in-flight buckets); depth 0 = serial
    (prep inline on the dispatch thread — the measured baseline the
    overlap metrics are asserted against).  Returns (out, n_buckets)."""
    N = len(items)
    with trace.span("replay/sort", corr=corrs, sigs=N):
        order = np.argsort(roi, kind="stable")
        roi_sorted = roi[order]
        chunks = _plan_buckets(roi_sorted, bucket)
    if depth is None:
        depth = int(_os.environ.get("LIGHTNING_TPU_REPLAY_DEPTH", "2"))
    if device_fn is None:
        if _os.environ.get("LIGHTNING_TPU_VERIFY_DEVICE", "auto") == "off":
            device_fn = _host_device_fn(items, roi, bucket)
        else:
            device_fn = _select_device_fn(bucket, N)
    # every bucket dispatch (injected test doubles included) runs under
    # the verify breaker + quarantine supervision, and each is one
    # flight-recorded dispatch whose record lands in `flight_recs`
    # (dispatch order, so the readback loop below can set late fields)
    flight_recs: list[dict] = []
    device_fn = _wrap_resilient(device_fn, items, roi, bucket,
                                corrs=corrs, sink=flight_recs,
                                dispatch_map=dispatch_map)
    prep = functools.partial(_prep_bucket, items, order, roi_sorted,
                             bucket, corrs=corrs)

    out = np.zeros(N, bool)
    # pending holds only (sel, n_real, device_ok): keeping the whole
    # _PreparedBucket would pin every bucket's packed host arrays (≈ the
    # re-packed store) in memory until the final readback
    pending: list[tuple[np.ndarray, int, object]] = []
    t_prep = t_stall = 0.0       # this replay's, for the overlap ratio
    # dispatch-deadline on the prepared-bucket queue: a producer that
    # hangs (or dies without surfacing) must not park the replay forever
    prod_deadline = _deadline.deadline_for("verify")
    timed_out = False
    lanes_verify = _M_LANES.labels("verify")
    lanes_hash = _M_LANES.labels("hash")

    def dispatch_one(pb: _PreparedBucket, stall: float,
                     queue_wait: float = 0.0) -> None:
        """One bucket down the device queue; the stage, lane, byte and
        signature counters move here, bucket by bucket.  `stall` is the
        prep time the dispatch thread saw: the queue wait when
        streaming, the whole prep when prepping inline."""
        nonlocal t_prep, t_stall
        t0 = time.perf_counter()
        ok = device_fn(pb, queue_wait=queue_wait)
        _M_R_DISPATCH.inc(time.perf_counter() - t0)
        _M_R_PREP.inc(pb.prep_seconds)
        _M_R_STALL.inc(stall)
        _M_R_SIGS.inc(pb.n_real)
        lanes_verify.inc(bucket)
        lanes_hash.inc(bucket)
        _M_DEVICE_BYTES.inc(pb.staged_bytes)
        t_prep += pb.prep_seconds
        t_stall += stall
        pending.append((pb.sel, pb.n_real, ok))

    # replay/stream: the dispatch section, from the producer's start (a
    # bucket's prep before the first dispatch) to the last dispatch
    # returned
    with trace.span("replay/stream", corr=corrs, buckets=len(chunks)):
        if depth > 0 and len(chunks) > 1:
            q: _queue.Queue = _queue.Queue(maxsize=depth)
            stop = threading.Event()  # dispatch failed: stop prepping

            def _put(item) -> bool:
                # stop-aware put: a producer abandoned by the deadline path
                # (or raced by a dispatch failure) must never block forever
                # on a full queue nobody drains — at ANY depth
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.05)
                        return True
                    except _queue.Full:
                        pass
                return False

            def _producer():
                try:
                    for c in chunks:
                        if stop.is_set():
                            return
                        _fault.fire("producer", "verify")
                        if not _put(prep(c)):
                            return
                    _put(_DONE)
                except BaseException as e:  # surface on the dispatch thread
                    _put(e)

            th = threading.Thread(target=_producer, name="replay-prep",
                                  daemon=True)
            th.start()
            try:
                while True:
                    t0 = time.perf_counter()
                    try:
                        pb = q.get(timeout=prod_deadline)
                    except _queue.Empty:
                        _deadline.note_exceeded("verify", "producer",
                                                prod_deadline)
                        timed_out = True
                        break
                    wait = time.perf_counter() - t0
                    if pb is _DONE:
                        # (the wait for the end marker is stall time too)
                        _M_R_STALL.inc(wait)
                        t_stall += wait
                        break
                    if isinstance(pb, BaseException):
                        raise pb
                    _M_R_QDEPTH.observe(q.qsize() + 1)
                    dispatch_one(pb, wait, queue_wait=wait)
            finally:
                # the producer may be parked on a full queue if the
                # dispatch loop raised — tell it to stop and drain until it
                # exits (its puts are stop-aware, so it unparks on its
                # own).  A HUNG producer (deadline path) is abandoned
                # instead — a daemon thread stuck in prep that the join
                # below would wait on; when (if) its prep ever returns, the
                # stop-aware put lets it exit without a consumer.
                stop.set()
                while th.is_alive() and not timed_out:
                    try:
                        q.get_nowait()
                    except _queue.Empty:
                        pass
                    th.join(timeout=0.005)
                if timed_out:
                    while True:
                        try:
                            q.get_nowait()
                        except _queue.Empty:
                            break
        else:
            for c in chunks:
                pb = prep(c)
                dispatch_one(pb, pb.prep_seconds)  # serial: all prep visible

        if timed_out:
            # restart semantics for the replay: abandon the wedged producer
            # and prep the remaining buckets inline on this thread.  A
            # bucket the producer managed to deliver concurrently is simply
            # verified twice (idempotent) — never skipped, never hung.
            log.warning("replay producer missed its %.3fs deadline after "
                        "%d/%d buckets; prepping the rest inline",
                        prod_deadline, len(pending), len(chunks))
            for c in chunks[len(pending):]:
                pb = prep(c)
                dispatch_one(pb, pb.prep_seconds)

    # the ONLY device→host transfer of the replay: drain the enqueued
    # booleans in dispatch order.  A readback failure (an enqueued
    # program that died after dispatch) diverts just that bucket's rows
    # to the host oracle instead of failing the replay.
    brk = _breaker.get("verify")
    # the readback counter moves bucket by bucket, each increment from
    # where the last one ended, so a replay's increments add up to the
    # whole loop's wall time
    t_mark = time.perf_counter()
    try:
        with trace.span("replay/readback", corr=corrs,
                        buckets=len(pending)):
            for (sel, n_real, ok), rec in zip(pending, flight_recs):
                idx = sel[:n_real]
                t0b = time.perf_counter()
                try:
                    _fault.fire("readback", "verify")
                    ok_host = np.asarray(ok)
                    out[idx] = ok_host[:n_real]
                    if rec["outcome"] in ("ok", "bisect"):
                        # the replay's only device→host transfer: the
                        # boolean plane this bucket read back
                        rec["d2h_bytes"] = ok_host.nbytes
                        _M_TRANSFER.labels("verify",
                                           "d2h").inc(ok_host.nbytes)
                except Exception as e:
                    brk.record_failure()
                    _quarantine.note("verify", "readback", n_real)
                    rec["outcome"] = "readback_host"
                    rec["error"] = type(e).__name__
                    rec["quarantined"] += n_real
                    log.warning("replay readback failed (%s); re-checking "
                                "%d rows on the host", e, n_real)
                    out[idx] = _host_verify_selected(items, roi, idx)
                now = time.perf_counter()
                rec["readback_ms"] = round((now - t0b) * 1e3, 3)
                _M_R_READBACK.inc(now - t_mark)
                t_mark = now
                # the deferred seal: the final outcome (ok / bisect /
                # host_breaker from dispatch, or readback_host above) is
                # only known now, so the ring insert + counter + watchdog
                # all see it — listdispatches and clntpu_dispatches_total
                # reconcile even on readback failures
                _flight.finish(rec)
    finally:
        # a raising host re-check must not leave the remaining deferred
        # records unsealed and invisible to the ring (finish() is
        # idempotent, so already-sealed ones are untouched)
        for rec in flight_recs:
            _flight.finish(rec)

    if t_prep > 0:
        _M_R_OVERLAP.observe(max(0.0, 1.0 - t_stall / t_prep))
    return out, len(chunks)


def verify_items(items: VerifyItems, bucket: int = DEFAULT_BUCKET, *,
                 depth: int | None = None, device_fn=None,
                 corr=None,
                 dispatch_map: np.ndarray | None = None) -> np.ndarray:
    """Streaming fused-bucket replay (doc/replay_pipeline.md).

    Signatures are sorted by message row and cut into self-contained
    buckets; each bucket is ONE fused device program (sha256d → local
    z gather → ECDSA verify — sig/pubkey bytes unpack on-device), so
    the z plane never leaves the device and the whole replay is one
    enqueue stream with a SINGLE boolean readback at the end.  Host
    bucket prep runs on a producer thread `depth` buckets ahead of the
    dispatch loop (double-buffered by default), overlapping pack/pad
    work with device compute — observable via the clntpu_replay_*
    stage counters.  With >1 device, buckets route the EC stage
    through parallel/mesh.py batch sharding (LIGHTNING_TPU_MESH_VERIFY).

    Oversized rows (n_blocks == 0, hashed host-side at extraction) are
    re-checked on the host afterward.  `device_fn` injects a bucket
    dispatcher (tests); `depth` overrides LIGHTNING_TPU_REPLAY_DEPTH
    (0 = serial prep, the overlap baseline).  Returns bool (N,).

    Every bucket dispatch runs supervised (doc/resilience.md): the
    "verify" circuit breaker short-circuits to the host oracle when the
    device path is flapping, a raising dispatch bisects to quarantine
    the poisoned rows and complete the rest, readback failures re-check
    just their bucket host-side, and a hung producer thread trips the
    LIGHTNING_TPU_DEADLINE_VERIFY_S deadline into inline prep — so a
    replay COMPLETES, bit-identically, under any single-path failure.

    ``corr`` (a trace.Carrier or list of them, minted at the enqueue
    point — ingest submit, the store-replay span) rides every prep /
    dispatch / readback span and flight record of this replay, so the
    exported timeline links each bucket back to its enqueue span
    across the producer/dispatch threads (doc/tracing.md).  When
    LIGHTNING_TPU_PROFILE=<dir> is set the whole replay runs inside a
    jax.profiler session, in which every span is a TraceAnnotation.

    ``dispatch_map`` (caller-allocated int64 (N,), conventionally
    filled with -1) receives, per SIGNATURE index, the dispatch_id of
    the flight record whose bucket verified it — the per-item
    provenance link doc/journeys.md stitches journeys with."""
    N = len(items)
    if N == 0:
        return np.zeros(0, bool)
    corrs = trace.as_carriers(corr)
    t_start = time.perf_counter()
    roi = items.row_of_item
    if roi is None:
        roi = np.arange(N, dtype=np.int64)
    tag_ok = (items.pubkeys[:, 0] == 2) | (items.pubkeys[:, 0] == 3)

    with trace.profile_session():
        out, n_buckets = _run_pipeline(items, roi, bucket, depth,
                                       device_fn, corrs=corrs,
                                       dispatch_map=dispatch_map)

    # oversized rows: the device hashed garbage for them; their host
    # sha256d was computed at extraction — verify those few serially.
    # A builder that marks rows oversized MUST supply z_host, or valid
    # signatures would silently verify as False off the garbage hash.
    # An explicit raise, not assert: the contract must survive
    # `python -O` (stripped asserts made this fail as an incidental
    # TypeError on the None subscript).
    ovs = items.n_blocks[roi] == 0
    if ovs.any():
        if items.z_host is None:
            raise ValueError(
                "oversized rows (n_blocks == 0) require z_host")
        _M_OVERSIZED.inc(int(ovs.sum()))
        out[ovs] = S._host_verify(items.z_host[roi[ovs]],
                                  items.sigs[ovs], items.pubkeys[ovs])

    _M_BATCH_SIGS.observe(N)
    _M_OCCUPANCY.observe(N / (n_buckets * bucket))
    _M_FLUSH_SECONDS.observe(time.perf_counter() - t_start)
    return out & tag_ok


@dataclass
class StoreVerifyResult:
    n_records: int
    n_sigs: int
    ca_valid: np.ndarray  # per channel_announcement (all 4 sigs)
    cu_valid: np.ndarray
    na_valid: np.ndarray


def verify_store(idx: StoreIndex, bucket: int | None = None) -> StoreVerifyResult:
    """Replay-verify a full store: every signature on every alive gossip
    message (the reference's store *load* skips re-verification; its
    *ingest* path verifies serially — this is the ingest cost model run at
    load scale, the BASELINE.md target workload).  Without a `bucket`
    the dispatches carry replay_bucket() lanes."""
    if bucket is None:
        bucket = replay_bucket()
    alive = idx.select(idx.alive())
    ca = alive.select(alive.types == wire.MSG_CHANNEL_ANNOUNCEMENT)
    na = alive.select(alive.types == wire.MSG_NODE_ANNOUNCEMENT)
    cu = alive.select(alive.types == wire.MSG_CHANNEL_UPDATE)
    with trace.span("gossip/extract", records=int(len(alive.types))):
        items_ca = extract_channel_announcements(ca)
        items_na = extract_node_announcements(na)
        items_cu = extract_channel_updates(cu, make_scid_map(ca))
        all_items = VerifyItems.concat([items_ca, items_na, items_cu])
    with trace.span("gossip/verify", sigs=int(len(all_items.sigs))):
        # the replay's enqueue point: every bucket's prep/dispatch/
        # readback span flows back here in the exported timeline
        corr = trace.new_corr()
        ok = verify_items(all_items, bucket, corr=corr)
    n_ca, n_na, n_cu = len(items_ca), len(items_na), len(items_cu)
    ca_ok = ok[:n_ca].reshape(4, -1).all(axis=0) if n_ca else np.zeros(0, bool)
    na_ok = ok[n_ca : n_ca + n_na]
    cu_ok = ok[n_ca + n_na :]
    return StoreVerifyResult(
        n_records=len(alive), n_sigs=len(all_items),
        ca_valid=ca_ok, cu_valid=cu_ok, na_valid=na_ok,
    )
