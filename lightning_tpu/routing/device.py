"""Device-batched pathfinding: a backward Bellman-Ford sweep over
RoutePlanes for a whole batch of queries at once, plus the
micro-batching RouteService front-end.

The host dijkstra (routing/dijkstra.py) solves one query at a time with
a heapq; its SoA layout was always "device-shaped for a later jax
bellman-ford sweep" — this is that sweep.  The same move the paper
makes for signatures applies to routing: serial per-request work
becomes ONE XLA program over Q concurrent queries.

Kernel shape: ``max_hops`` Jacobi relaxation sweeps in a ``lax.scan``,
written batch-first (no ``vmap``).  The labels are one
``[n_pad, 2, lanes]`` table of (cost, amount), the lanes (queries) its
minor axis; a label carries no delay, because no output of the program
reads one: the answer's delays are summed on the host, exactly, by
``_reconstruct`` from the query's ``final_cltv`` and the planes.  Each
sweep fetches, for every edge, the row of its RECEIVING node — all
lanes' labels of the sweep before, one indexed row per edge, the only
indexed operation over edges — prices the edge in dense
``[lanes, edges]`` arrays with the exact integer cost model of
dijkstra.py (compounding msat fees + CLN risk cost; the two divisions
by a constant as an exact multiply-high, ``_div_const``), and folds
the candidates per FORWARDING node with a dense segmented minimum: the
edges stand on the device in source-major order
(``edge_order``: a stable sort of the RoutePlanes edges by
``edge_src``, padding rows last), so a node's out-edges are one run of
rows and the minimum is ``steps`` doubling passes
``x[i] = min(x[i], x[i - 2^k])`` inside a run, read at each run's last
row.  ``steps`` is the largest out-degree's power of two (5 on a
uniform 25,000-channel graph, 10 on the same counts with mainnet's
degrees, whose largest hub holds 916 channels), a static size of the
program read off the planes; the gauge ``clntpu_route_segmin_steps``
carries it (doc/routing.md §steps).  The minimum is two-level where
``steps`` > ``FOLD_STEPS`` (5): the first five passes run over every
row, the rest over the last rows of aligned 32-row blocks alone, and a
node reads its last row and, where its run reaches back past its
block, the block before; ties go to the earlier window, so the
tie-break below is unchanged (``_make_single``; the gauge
``clntpu_route_segmin_block_passes`` carries ``steps`` − 5).  At
``steps`` ≤ 5 the program is one level.  The winner's amount and edge
ride the same selects; no scatter of any width is in the
program.  After k sweeps a node's label is the cheapest ≤k-hop path to
the destination — identical to dijkstra's settled labels whenever the
hop cap doesn't bind (LN paths are ~5 hops against a cap of 20).

What ``via`` indexes: the program returns, per lane and node, the
winning edge's index in RoutePlanes order (the ``orig`` plane carries
it through the device order), so ``_reconstruct`` and
``RoutePlanes.edge_ok_mask`` never see the device order.  The
per-flush edge mask is packed in device order on the host.

Tie-break rule (stated, tested): among equal-cost candidate edges for
a node within one sweep, the LOWEST edge index in the destination-keyed
CSR wins; an existing label is only replaced by a STRICTLY cheaper one.
Total cost is tie-break-independent; the chosen hops may differ from
dijkstra's when distinct paths price identically.  The program before
PR 26 (two ``segment_min`` scatters under ``vmap``) is frozen as
tests/route_scatter_oracle.py; tests/test_zz_route_parity.py holds this
one equal to it label for label.

Exactness: all msat math runs in int64 under a scoped x64 context (the
crypto kernels' uint32-limb world is untouched).  Per-edge overflow
guards bound every product below 2^61; a query whose relaxation would
exceed them raises an overflow flag and the service re-solves it on the
host (Python bigints).  Every returned route re-validates host-side
with exact ints (hop cap, HTLC windows, total cost vs the kernel's
label) and falls back to the host on any mismatch — so a device "ok"
is always a valid route priced by dijkstra's exact cost model.  One
asymmetry remains when the 20-hop cap BINDS: dijkstra's hop limit is a
search prune (it can miss a costlier ≤20-hop path after labeling a
node via a cheap longer prefix), while the sweep solves the ≤20-edge
problem exactly — the device can then return a valid route where the
host reports NoRoute, i.e. it is strictly more complete, never
cost-divergent.  LN paths are ~5 hops; the parity corpus asserts
identical outcomes on graphs where the cap doesn't bind.

RouteService front-end (the gossip/ingest.py flush-loop shape):
concurrent ``getroute`` awaiters coalesce inside a flush window into
one device dispatch; flushes below ``HOST_ROUTE_MAX`` occupancy — and
queries the planes can't express (custom max_hops, oversized amounts)
— take the host dijkstra instead.  Knobs (see doc/routing.md):

  LIGHTNING_TPU_ROUTE_BATCH        device query bucket (default 64)
  LIGHTNING_TPU_ROUTE_FLUSH_MS     flush latency budget (default 2.0)
  LIGHTNING_TPU_ROUTE_HOST_MAX     ≤ this many queued → host (default 4)
  LIGHTNING_TPU_ROUTE_MAX_AMOUNT_MSAT  device amount cap (default 2^48)
  LIGHTNING_TPU_ROUTE_MAX_RISKFACTOR   device riskfactor cap (10^6)
  LIGHTNING_TPU_ROUTE_DEVICE       0 → host-only service (default 1)
  LIGHTNING_TPU_ROUTE_HIGH_WM      TRY_AGAIN admission watermark (256)
  LIGHTNING_TPU_ROUTE_LOW_WM       backlog-drained watermark (high/2)
"""
from __future__ import annotations

import asyncio
import functools
import logging
import os as _os
import time
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64

from ..obs import attribution as _attr
from ..obs import families as _families
from ..obs import flight as _flight
from ..resilience import breaker as _breaker
from ..resilience import deadline as _deadline
from ..resilience import faultinject as _fault
from ..resilience import overload as _overload
from ..utils import events, trace
from . import dijkstra as DJ
from .dijkstra import BLOCKS_PER_YEAR, NoRoute, RouteHop
from .planes import RoutePlanes

log = logging.getLogger("lightning_tpu.routing.device")

DEFAULT_MAX_HOPS = 20
# label sentinel: far above any real path cost, far below int64 overflow
# even after adding one more edge's fee+risk
INF_COST = 1 << 62
# per-edge products (amount×ppm, amount×cltv×riskfactor) stay below this
OVF_LIMIT = 1 << 61
_RISK_DENOM = BLOCKS_PER_YEAR * 100

ROUTE_BATCH = int(_os.environ.get("LIGHTNING_TPU_ROUTE_BATCH", "64"))
ROUTE_FLUSH_MS = float(_os.environ.get("LIGHTNING_TPU_ROUTE_FLUSH_MS", "2.0"))
HOST_ROUTE_MAX = int(_os.environ.get("LIGHTNING_TPU_ROUTE_HOST_MAX", "4"))
ROUTE_MAX_AMOUNT_MSAT = int(_os.environ.get(
    "LIGHTNING_TPU_ROUTE_MAX_AMOUNT_MSAT", str(1 << 48)))
# admission-control watermarks, in queued QUERIES (doc/overload.md):
# past the high watermark getroute/pay reject with a retryable
# TRY_AGAIN + retry-after hint instead of queueing unboundedly.
# LOW_WM=0 means "half of high".
ROUTE_HIGH_WM = int(_os.environ.get("LIGHTNING_TPU_ROUTE_HIGH_WM", "256"))
ROUTE_LOW_WM = (int(_os.environ.get("LIGHTNING_TPU_ROUTE_LOW_WM", "0"))
                or ROUTE_HIGH_WM // 2)
# riskfactor joins cd (≤ 2^16) in an int64 product INSIDE the overflow
# guard itself — an RPC-supplied rf ≥ ~2^45 would wrap cd·rf negative
# and disarm the guard entirely, so oversized values go to the host's
# bigints (CLN's default is 10; 10^6 is already absurd)
ROUTE_MAX_RISKFACTOR = int(_os.environ.get(
    "LIGHTNING_TPU_ROUTE_MAX_RISKFACTOR", "1000000"))

# instrument families live in obs.families so exposition-only
# consumers (tools/obs_snapshot.py) get them without importing jax
_M_FLUSH_SECONDS = _families.ROUTE_FLUSH_SECONDS
_M_BATCH = _families.ROUTE_BATCH_QUERIES
_M_OCCUPANCY = _families.ROUTE_OCCUPANCY
_M_QUERIES = _families.ROUTE_QUERIES
_M_FALLBACK = _families.ROUTE_FALLBACK
_M_QUEUE = _families.ROUTE_QUEUE
_M_QUEUE_WAIT = _families.ROUTE_QUEUE_WAIT
_M_SEGMIN_STEPS = _families.ROUTE_SEGMIN_STEPS
_M_SEGMIN_BLOCK_PASSES = _families.ROUTE_SEGMIN_BLOCK_PASSES
_M_PATH_HOPS = _families.ROUTE_PATH_HOPS
_M_PROGRAM_BYTES = _families.ROUTE_PROGRAM_BYTES

# fallback reasons (label values — observable in tests/doc/routing.md)
R_BELOW_OCCUPANCY = "below_occupancy"
R_DISABLED = "device_disabled"
R_AMOUNT_CAP = "amount_cap"
R_RISKFACTOR_CAP = "riskfactor_cap"
R_MAX_HOPS = "max_hops"
R_OVERFLOW = "overflow"
R_DEVICE_ERROR = "device_error"
R_RECONSTRUCT = "reconstruct"
R_NOT_RUNNING = "not_running"
R_BREAKER = "breaker_open"
R_DEADLINE = "deadline"


def _device_enabled() -> bool:
    return _os.environ.get("LIGHTNING_TPU_ROUTE_DEVICE", "1") != "0"


# ---------------------------------------------------------------------------
# The kernel


def _div_const(x, d: int):
    """floor(x / d) for int64 ``x`` in [0, 2^62) and a constant ``d``,
    exactly, without a division: the high half of x·m for the 64-bit
    magic m = floor(2^(62+l) / d) + 1, l = ceil(log2 d), shifted down.
    m·d exceeds 2^(62+l) by at most d ≤ 2^l, so x·m / 2^(62+l) lies
    less than 1/d above x/d for every x < 2^62 and their floors agree
    (Granlund & Montgomery 1994).  The 128-bit product is four 32×32
    partial products in uint64.  XLA's own int64 division is bit-serial
    on a TPU: the sweep's two quotients were its largest single cost
    (PERF.md §6, PR 26).  A wrapped (masked-out) ``x`` gives a defined
    quotient that nothing reads."""
    shift = 62 + (d - 1).bit_length()
    m = (1 << shift) // d + 1
    m0, m1 = jnp.uint64(m & 0xFFFFFFFF), jnp.uint64(m >> 32)
    low = jnp.uint64(0xFFFFFFFF)
    x = x.astype(jnp.uint64)
    x0, x1 = x & low, x >> 32
    p01, p10 = x0 * m1, x1 * m0
    mid = ((x0 * m0) >> 32) + (p01 & low) + (p10 & low)
    high = x1 * m1 + (p01 >> 32) + (p10 >> 32) + (mid >> 32)
    return (high >> (shift - 64)).astype(jnp.int64)


# The segmented minimum's fold point: the first FOLD_STEPS doubling
# passes run over every edge row, the rest over the last rows of
# aligned blocks of 2^FOLD_STEPS = 32 rows (`_make_single` says why 32).
FOLD_STEPS = 5
_BLOCK = 1 << FOLD_STEPS


def segmin_block_passes(steps: int) -> int:
    """Doubling passes the route program runs over block winners at
    `steps` (the gauge ``clntpu_route_segmin_block_passes``)."""
    return max(steps - FOLD_STEPS, 0)


def _make_single(n_nodes: int, max_hops: int, steps: int):
    """The whole batch's backward sweep; closed over the static node
    count, the sweep budget and the number of doubling steps the
    segmented minimum takes (`EdgeOrder.steps`).

    The segmented minimum is two-level where `steps` > FOLD_STEPS.
    Level 1 is the first FOLD_STEPS passes over all rows: row i then
    holds the earliest minimum of its run over rows
    [max(run start, i - 31), i], so block b's last row, 32b + 31, holds
    that of its run's part of block b.  Level 2 is the other
    `steps - FOLD_STEPS` passes over those block-end rows alone, the
    run boundaries taken from `edge_src` at the block ends: block b
    then holds the earliest minimum of its run over
    [run start, 32b + 31], since the first block whose end lies in a
    run starts at or after the run's first row.  A node whose last row
    j lies in block bj reads the level-1 row j, which covers its run
    back to max(run start, j - 31) ≥ 32(bj - 1), and, where its run
    reaches into block bj - 1, level 2's block bj - 1, which covers the
    run up to row 32bj - 1; the block wins on `<=`.  It holds the
    earlier rows, and where the two windows overlap it holds the
    overlap too, so the earliest row among equal costs wins, as in a
    single level of `steps` passes: the tie-break is unchanged.

    Why blocks of 32 rows.  Timed alone on one TPU v5e at 64 lanes, an
    execution at (`n_pad`, `e_pad`, `steps`) = (65,536, 524,288, 14)
    takes 913 ms with them, 860 ms with blocks of 16 and 976 ms with
    blocks of 64 (1,824 ms in one level); at (8,192, 65,536, 10) 74.3,
    70.9 and 78.2 ms (100.2 in one level).  Blocks of 16 are 5–6 %
    faster on those two graphs, but with one constant they put a second
    level at `steps` 5 as well, the uniform graph's, which was not
    timed; 32 is the smallest fold at which that graph runs the
    one-level program pass for pass, and a threshold apart from the
    fold would be a second constant."""

    def single(edge_src, edge_dst, base, ppm, cd, hmin, hmax, orig,
               seg_last, edge_ok, src, dst, amount, riskfactor):
        # labels start at the destination (a select, not a scatter):
        # one [n_nodes, 2, lanes] table of (cost, amount), a row of it
        # all lanes' labels of one node
        at_dst = jnp.arange(n_nodes, dtype=jnp.int32)[:, None] == dst
        dist0 = jnp.where(at_dst, jnp.int64(0), jnp.int64(INF_COST))
        if dist0.dtype != jnp.int64:
            raise RuntimeError(
                "route kernel traced outside an x64 scope — msat math "
                "would silently truncate to int32")
        table0 = jnp.stack(
            [dist0, jnp.where(at_dst, amount, jnp.int64(0))], axis=1)
        via0 = jnp.full((n_nodes, src.shape[0]), -1, jnp.int32)
        # the dense edge-level arrays are [lanes, edges], the edge axis
        # minor (a TPU pads a minor axis of 64 lanes to 128: measured,
        # PERF.md §6 PR 26), so an edge plane broadcasts as it stands.
        # Per-edge safe-amount ceiling: both int64 products stay < 2^61
        cdr = cd * riskfactor[:, None]
        thr = jnp.minimum(OVF_LIMIT // jnp.maximum(ppm, 1),
                          OVF_LIMIT // jnp.maximum(cdr, 1))
        # edge i and edge i - 2^k leave the same node: static per
        # topology revision, so it stays outside the sweeps
        at = jnp.arange(edge_src.shape[0])
        level1 = min(steps, FOLD_STEPS)
        same = [(at >= 1 << k) & (edge_src == jnp.roll(edge_src, 1 << k))
                for k in range(level1)]
        has_out = (seg_last >= 0)[:, None]
        seg_at = jnp.maximum(seg_last, 0)
        idx0 = jnp.broadcast_to(orig, edge_ok.shape)
        if steps > FOLD_STEPS:
            # the same, over the block-end rows, and which nodes' runs
            # reach back into the block before their last row's
            blk_src = edge_src[_BLOCK - 1::_BLOCK]
            blk_at = jnp.arange(blk_src.shape[0])
            same2 = [(blk_at >= 1 << k)
                     & (blk_src == jnp.roll(blk_src, 1 << k))
                     for k in range(steps - FOLD_STEPS)]
            blk_of = seg_at // _BLOCK
            prev_blk = jnp.maximum(blk_of - 1, 0)
            spans = ((blk_of >= 1)
                     & (edge_src[jnp.maximum(blk_of * _BLOCK - 1, 0)]
                        == edge_src[seg_at]))[:, None]

        def sweep(carry, _):
            table, via, ovf = carry
            # the sweep's one indexed operation over edges: all lanes'
            # labels of each edge's receiving node, one row per edge
            d_v, a_v = table[edge_dst].transpose(1, 2, 0)
            ok = edge_ok & (d_v < INF_COST)
            # the HTLC carried over u→v is a_v (what v receives) —
            # channel_update limits apply to it (dijkstra.py:107)
            ok &= (a_v >= hmin) & ((hmax == 0) | (a_v <= hmax))
            unsafe = a_v > thr
            ovf |= jnp.any(ok & unsafe, axis=1)
            ok &= ~unsafe
            fee = base + _div_const(a_v * ppm, 1_000_000)
            risk = 1 + _div_const(a_v * cdr, _RISK_DENOM)
            # what the forwarding node's labels become if this edge wins
            new = jnp.stack([jnp.where(ok, d_v + fee + risk, INF_COST),
                             a_v + fee])
            idx = idx0
            # per-source minimum as a prefix minimum inside each run of
            # equal edge_src; amount and edge ride the same selects.
            # Tie-break: lowest RoutePlanes edge index among the
            # winning cost — the order is a stable sort, so inside a
            # run that is the earlier row, which `<=` keeps
            for k in range(level1):
                new_k = jnp.roll(new, 1 << k, axis=2)
                take = same[k] & (new_k[0] <= new[0])
                new = jnp.where(take, new_k, new)
                idx = jnp.where(take, jnp.roll(idx, 1 << k, axis=1), idx)
            # each node's winner stands at the last of its edges
            rows = new.transpose(2, 0, 1)
            win = rows[seg_at]
            if steps > FOLD_STEPS:
                with jax.named_scope("route_segmin_blocks"):
                    # level 2 over the block ends, taken from the
                    # row-major form: a stride over its major axis
                    blk = rows[_BLOCK - 1::_BLOCK]
                    blk_idx = idx.T[_BLOCK - 1::_BLOCK]
                    for k in range(steps - FOLD_STEPS):
                        blk_k = jnp.roll(blk, 1 << k, axis=0)
                        take = same2[k][:, None] & (blk_k[:, 0] <= blk[:, 0])
                        blk = jnp.where(take[:, None], blk_k, blk)
                        blk_idx = jnp.where(
                            take, jnp.roll(blk_idx, 1 << k, axis=0), blk_idx)
                    before = blk[prev_blk]
                    use_blk = spans & (before[:, 0] <= win[:, 0])
                    win = jnp.where(use_blk[:, None], before, win)
            improved = has_out & (win[:, 0] < table[:, 0])
            table = jnp.where(improved[:, None], win, table)
            won = idx.T[seg_at]
            if steps > FOLD_STEPS:
                won = jnp.where(use_blk, blk_idx[prev_blk], won)
            via = jnp.where(improved, won, via)
            return (table, via, ovf), None

        # the phases carry jax.named_scope names (doc/tracing.md): they
        # ride the ops' metadata into a device trace, whatever number
        # XLA gives the while loop
        init = (table0, via0, jnp.zeros(src.shape, bool))
        with jax.named_scope("route_relax"):
            (table, via, ovf), _ = jax.lax.scan(
                sweep, init, None, length=max_hops)
        with jax.named_scope("route_extract"):
            dist_src = jnp.take_along_axis(table[:, 0], src[None], axis=0)
            return dist_src[0], via.T, ovf

    return single


@functools.lru_cache(maxsize=8)
def _jit_route(n_nodes: int, max_hops: int, steps: int):
    return jax.jit(_make_single(n_nodes, max_hops, steps))


@functools.lru_cache(maxsize=8)
def _route_program(n_pad: int, e_pad: int, batch: int, max_hops: int,
                   steps: int) -> tuple:
    """(executable, device bytes by part) of the route program at one
    full key, the retrace detector's: compiled ahead of time (or
    retrieved from the persistent cache) once, then run by the warm-up
    and by every flush, so the bytes `memory_analysis()` reports are
    those of the program the flushes run (the gauge
    ``clntpu_route_program_bytes``)."""
    with enable_x64():
        exe = _jit_route(n_pad, max_hops, steps).lower(
            *program_operands(batch, n_pad, e_pad,
                              jax.ShapeDtypeStruct)).compile()
    mem = exe.memory_analysis()
    parts = {} if mem is None else {
        "temp": mem.temp_size_in_bytes,
        "arguments": mem.argument_size_in_bytes}
    return exe, parts


def _route_executable(n_pad: int, e_pad: int, batch: int, max_hops: int,
                      steps: int):
    """The executable for a key, its bytes set on the gauge."""
    exe, parts = _route_program(n_pad, e_pad, batch, max_hops, steps)
    for part, nbytes in parts.items():
        _M_PROGRAM_BYTES.labels(part).set(nbytes)
    return exe


# ---------------------------------------------------------------------------
# The device-side edge order


@dataclass(frozen=True)
class EdgeOrder:
    """Where each RoutePlanes edge stands on the device: real edges in
    a stable sort by forwarding node, padding rows last."""

    perm: np.ndarray      # (e_pad,) int32 device position → RoutePlanes
    #                       index; on the device, the kernel's `orig`
    inv: np.ndarray       # (e_pad,) RoutePlanes index → device position
    seg_last: np.ndarray  # (n_pad,) int32 device position of a node's
    #                       last out-edge, -1 where it has none
    steps: int            # doubling steps: 2^steps ≥ largest out-degree


def edge_order(planes: RoutePlanes) -> EdgeOrder:
    """The planes' device order, made once per topology revision (it
    rides `planes.dev` with the topology uploads)."""
    order = planes.dev.get("order")
    if order is None:
        key = planes.edge_src.astype(np.int64)
        key[planes.e_real:] = planes.n_pad
        perm = np.argsort(key, kind="stable").astype(np.int32)
        inv = np.empty(planes.e_pad, np.int64)
        inv[perm] = np.arange(planes.e_pad)
        degree = np.bincount(key[:planes.e_real], minlength=planes.n_pad)
        seg_last = np.where(degree > 0, np.cumsum(degree) - 1,
                            -1).astype(np.int32)
        steps = max(int(degree.max(initial=1)) - 1, 0).bit_length()
        order = planes.dev["order"] = EdgeOrder(perm, inv, seg_last, steps)
    return order


_PLANE_ORDER = ("edge_src", "edge_dst", "edge_base", "edge_ppm",
                "edge_cltv", "edge_hmin", "edge_hmax", "perm", "seg_last")


# parameter planes a channel_update can change (patchable in place on
# device); the others are topology and only ever full-upload
_PARAM_PLANES = ("edge_base", "edge_ppm", "edge_cltv", "edge_hmin",
                 "edge_hmax")


def _device_plane_args(planes: RoutePlanes) -> tuple:
    """Upload (once per planes revision) and return (operands, edge_ok,
    staged_bytes): the shared device planes in `edge_order`, the host's
    enabled mask in the same order, and how many host bytes this call
    actually staged (zero when every plane was carried over; the
    perf-attribution transfer accounting, doc/perf.md).
    A param-refresh revision arrives with the topology uploads carried
    over, so only the missing planes stage; an incremental revision
    (planes.patch_idx set by with_patched_params) scatters JUST the
    touched lanes into the carried device planes, at the positions the
    order gives them — a channel_update burst costs O(changed) device
    traffic, not a full re-upload.
    int64 planes must cross jnp.asarray inside the x64 scope or they
    silently truncate to int32."""
    staged = 0
    order = edge_order(planes)
    patch = planes.patch_idx
    if patch is not None and len(patch):
        at = order.inv[patch]
        if "edge_ok" in planes.dev:
            edge_ok = planes.dev["edge_ok"].copy()
            edge_ok[at] = planes.edge_enabled[patch]
            planes.dev["edge_ok"] = edge_ok
        with enable_x64():
            ji = jnp.asarray(at)
            staged += at.nbytes
            for name in _PARAM_PLANES:
                if name in planes.dev:
                    host_vals = getattr(planes, name)[patch]
                    staged += host_vals.nbytes
                    vals = jnp.asarray(host_vals)
                    planes.dev[name] = planes.dev[name].at[ji].set(vals)
    planes.patch_idx = None
    if "edge_ok" not in planes.dev:
        planes.dev["edge_ok"] = planes.edge_enabled[order.perm]
    missing = [n for n in _PLANE_ORDER if n not in planes.dev]
    if missing:
        with enable_x64():
            for name in missing:
                # the order's own planes as they are, the edge planes
                # permuted into it
                host_plane = (getattr(order, name) if hasattr(order, name)
                              else getattr(planes, name)[order.perm])
                staged += host_plane.nbytes
                planes.dev[name] = jnp.asarray(host_plane)
    return (tuple(planes.dev[n] for n in _PLANE_ORDER),
            planes.dev["edge_ok"], staged)


# ---------------------------------------------------------------------------
# Batched solve + host-exact route reconstruction


@dataclass
class RouteQuery:
    """One getroute request (same semantics as dijkstra.getroute)."""

    source: bytes
    destination: bytes
    amount_msat: int
    final_cltv: int = 18
    riskfactor: int = DJ.DEFAULT_RISKFACTOR
    max_hops: int = DEFAULT_MAX_HOPS
    excluded_scids: set | None = None
    # (solve_batch always returns the payer-side (amount, delay) pair;
    # getroute's with_source only shapes ITS return value)
    future: object = None
    # correlation carrier minted in getroute's enqueue span — links the
    # caller's span to the coalesced flush dispatch (doc/tracing.md)
    corr: object = None
    # time.perf_counter() when the query joined the service's queue;
    # the flush that takes it observes the wait (doc/tracing.md)
    t_enqueue: float = 0.0


def _reconstruct(planes: RoutePlanes, via: np.ndarray, src: int, dst: int,
                 amount_msat: int, final_cltv: int, riskfactor: int,
                 dist_src: int, max_hops: int):
    """Walk the predecessor edges src→dst, then price the path backward
    with exact Python ints — the amounts/delays are bit-identical to
    what dijkstra.py labels along the same hops.

    The walk RE-VALIDATES what the kernel checked against its in-sweep
    labels: a Jacobi label can survive pointing at a downstream chain
    that a later sweep rewrote (retraction), so the final chain may be
    longer than the sweep count, carry amounts outside an edge's HTLC
    window, or price differently than dist[src].  Any mismatch raises
    — the caller diverts the query to the host solver, preserving the
    module contract (bit-identical to dijkstra or not returned)."""
    g = planes.g
    edges = []
    u = src
    while u != dst:
        e = int(via[u])
        # >= : dijkstra's hop cap is a hard contract
        if e < 0 or len(edges) >= max_hops:
            raise RuntimeError("predecessor walk diverged")
        edges.append(e)
        u = int(planes.edge_dst[e])
    amount, delay = amount_msat, final_cltv
    cost = 0
    amounts: list[tuple[int, int]] = []   # (amount, delay) at edge's dst
    for e in reversed(edges):
        amounts.append((amount, delay))
        if amount < int(planes.edge_hmin[e]):
            raise RuntimeError("reconstructed amount under htlc_min")
        hmax = int(planes.edge_hmax[e])
        if hmax and amount > hmax:
            raise RuntimeError("reconstructed amount over htlc_max")
        fee = DJ.hop_fee_msat(int(planes.edge_base[e]),
                              int(planes.edge_ppm[e]), amount)
        cost += fee + DJ._risk_msat(amount, int(planes.edge_cltv[e]),
                                    riskfactor)
        amount += fee
        delay += int(planes.edge_cltv[e])
    if cost != dist_src:
        raise RuntimeError("reconstructed cost disagrees with label")
    amounts.reverse()
    route = [
        RouteHop(
            node_id=bytes(g.node_ids[int(planes.edge_dst[e])]),
            scid=int(g.scids[int(planes.edge_chan[e])]),
            direction=int(planes.edge_dir[e]),
            amount_msat=amt, delay=dly,
        )
        for e, (amt, dly) in zip(edges, amounts)
    ]
    return route, (amount, delay)


def solve_batch(planes: RoutePlanes, queries: list[RouteQuery],
                batch: int = ROUTE_BATCH,
                max_hops: int = DEFAULT_MAX_HOPS,
                io_acct: dict | None = None) -> list[tuple]:
    """Solve every query on the device in ⌈Q/batch⌉ vmapped dispatches.

    Returns one tuple per query:
      ("ok", route, (src_amount, src_delay))  — reachable, exact
      ("noroute", message)                    — provably unreachable
      ("fallback", reason)                    — solve on the host instead

    Each dispatch is three stage spans on the calling thread
    (doc/tracing.md): ``route/pack`` (index lookup, masks, operand
    arrays), ``route/device`` (upload, execute and the readback of the
    labels, as one stage: nothing waits in between) and
    ``route/reconstruct``.  A plane refresh after a graph change runs
    before the first of them.

    ``io_acct`` (when given) accumulates the host<->device operand
    bytes this call staged under keys ``h2d_bytes``/``d2h_bytes``, and
    the pack and reconstruct stages' seconds under ``pack_s`` /
    ``reconstruct_s``, and the program's step count under ``steps``
    and its passes over block winners under ``block_passes`` —
    RouteService folds them into the flush's flight record; the
    clntpu_transfer_bytes_total{family="route"} counters are metered
    here either way (doc/perf.md).
    """
    g = planes.g
    out: list[tuple] = [None] * len(queries)
    idx_cache: dict[bytes, int] = {}

    def node_idx(nid: bytes) -> int:
        i = idx_cache.get(nid)
        if i is None:
            i = idx_cache[nid] = g.node_index(nid)
        return i

    plane_args, edge_ok, h2d = _device_plane_args(planes)
    order = edge_order(planes)
    d2h = 0
    # retrace detector: the traced program is keyed by EVERY static
    # operand shape — node pad, edge pad (e_pad grows independently of
    # n_pad on channel bursts and re-traces under the same lru_cache'd
    # jit callable), the query batch width, the sweep budget and the
    # segmented minimum's step count (it grows when the largest
    # out-degree crosses a power of two).  A first-sight of this full
    # key after warmup means this flush is paying a compile
    # (doc/perf.md)
    _attr.note_program("route", (planes.n_pad, planes.e_pad, batch,
                                 max_hops, order.steps))
    _M_SEGMIN_STEPS.set(order.steps)
    _M_SEGMIN_BLOCK_PASSES.set(segmin_block_passes(order.steps))
    kern = _route_executable(planes.n_pad, planes.e_pad, batch, max_hops,
                             order.steps)
    pack_ns = reconstruct_ns = 0
    for start in range(0, len(queries), batch):
        chunk = queries[start:start + batch]
        with trace.span("route/pack", queries=len(chunk)) as sp:
            ok_mat = np.zeros((batch, planes.e_pad), bool)
            src = np.zeros(batch, np.int32)
            dst = np.zeros(batch, np.int32)
            amount = np.ones(batch, np.int64)
            rf = np.ones(batch, np.int64)
            for i, q in enumerate(chunk):
                try:
                    src[i] = node_idx(q.source)
                    dst[i] = node_idx(q.destination)
                except KeyError as e:
                    # unknown node: this query's error, not the batch's —
                    # its lanes stay masked-off padding
                    out[start + i] = ("error", e)
                    continue
                if src[i] == dst[i]:
                    # dijkstra raises NoRoute here; a dst-initialized
                    # label would otherwise read as a zero-cost empty
                    # route
                    out[start + i] = ("noroute", "source is destination")
                    continue
                # belts for direct solve_batch callers (the service
                # screens these before dispatch): values outside
                # [0, cap] wrap the kernel's own int64 guard products,
                # and the compiled sweep count is static so a per-query
                # hop cap can't be honored
                if not 0 <= q.amount_msat <= ROUTE_MAX_AMOUNT_MSAT:
                    out[start + i] = ("fallback", R_AMOUNT_CAP)
                    continue
                if not 0 <= q.riskfactor <= ROUTE_MAX_RISKFACTOR:
                    out[start + i] = ("fallback", R_RISKFACTOR_CAP)
                    continue
                if q.max_hops != max_hops:
                    out[start + i] = ("fallback", R_MAX_HOPS)
                    continue
                amount[i] = q.amount_msat
                rf[i] = q.riskfactor
                # rows in the device's edge order (edge_order)
                ok_mat[i] = (
                    planes.edge_ok_mask(q.excluded_scids)[order.perm]
                    if q.excluded_scids else edge_ok)
        pack_ns += sp.duration_ns
        h2d += (ok_mat.nbytes + src.nbytes + dst.nbytes
                + amount.nbytes + rf.nbytes)
        with trace.span("route/device"), enable_x64():
            dist_src, via, ovf = kern(
                *plane_args, jnp.asarray(ok_mat), jnp.asarray(src),
                jnp.asarray(dst), jnp.asarray(amount), jnp.asarray(rf))
            dist_src = np.asarray(dist_src)
            via = np.asarray(via)
            ovf = np.asarray(ovf)
        d2h += dist_src.nbytes + via.nbytes + ovf.nbytes
        with trace.span("route/reconstruct") as sp:
            for i, q in enumerate(chunk):
                if out[start + i] is not None:
                    continue       # resolved as an error above
                if ovf[i]:
                    # int64 headroom exceeded somewhere reachable: the
                    # host bigint solver owns this query (exactness over
                    # speed)
                    out[start + i] = ("fallback", R_OVERFLOW)
                elif dist_src[i] >= INF_COST:
                    out[start + i] = ("noroute", _noroute_msg(q))
                else:
                    try:
                        route, src_info = _reconstruct(
                            planes, via[i], int(src[i]), int(dst[i]),
                            q.amount_msat, q.final_cltv, q.riskfactor,
                            int(dist_src[i]), max_hops)
                        _M_PATH_HOPS.observe(len(route))
                        out[start + i] = ("ok", route, src_info)
                    except Exception as e:
                        log.warning("route reconstruction diverged (%s); "
                                    "host re-solves", e)
                        out[start + i] = ("fallback", R_RECONSTRUCT)
        reconstruct_ns += sp.duration_ns
    _families.TRANSFER_BYTES.labels("route", "h2d").inc(h2d)
    _families.TRANSFER_BYTES.labels("route", "d2h").inc(d2h)
    if io_acct is not None:
        io_acct["h2d_bytes"] = io_acct.get("h2d_bytes", 0) + h2d
        io_acct["d2h_bytes"] = io_acct.get("d2h_bytes", 0) + d2h
        io_acct["pack_s"] = io_acct.get("pack_s", 0.0) + pack_ns / 1e9
        io_acct["reconstruct_s"] = (io_acct.get("reconstruct_s", 0.0)
                                    + reconstruct_ns / 1e9)
        io_acct["steps"] = order.steps
        io_acct["block_passes"] = segmin_block_passes(order.steps)
    return out


def _noroute_msg(q: RouteQuery) -> str:
    return DJ.noroute_msg(q.source, q.destination, q.amount_msat)


def route_cost_msat(g, route: list[RouteHop], riskfactor: int) -> int:
    """Total dijkstra-model cost (fees + risk) of a hop list — the
    parity currency between the host and device solvers."""
    cost = 0
    for h in route:
        c = g.channel_index(h.scid)
        d = h.direction
        fee = DJ.hop_fee_msat(int(g.fee_base_msat[d, c]),
                              int(g.fee_ppm[d, c]), h.amount_msat)
        risk = DJ._risk_msat(h.amount_msat, int(g.cltv_delta[d, c]),
                             riskfactor)
        cost += fee + risk
    return cost


def program_operands(batch: int, n_pad: int, e_pad: int, make) -> tuple:
    """The route program's operands at one quantized shape, in call
    order, each as `make(shape, dtype)` — zeros for a warm-up, shape
    structs for a compile without the chip (tools/chip_compile.py)."""
    e32, e64 = make((e_pad,), jnp.int32), make((e_pad,), jnp.int64)
    b32, b64 = make((batch,), jnp.int32), make((batch,), jnp.int64)
    return (e32, e32, e64, e64, e64, e64, e64, e32,
            make((n_pad,), jnp.int32), make((batch, e_pad), jnp.bool_),
            b32, b32, b64, b64)


def warmup(batch: int = ROUTE_BATCH, n_pad: int = 64, e_pad: int = 256,
           max_hops: int = DEFAULT_MAX_HOPS, steps: int = 4) -> None:
    """Compile (or load from the persistent cache) the route program at
    the given quantized shape, off the live path — same contract as
    gossip.verify.warmup — and run it once.  Daemons call
    RouteService.warmup() instead, which passes the live planes' actual
    padded shape and step count; the gauge
    ``clntpu_route_program_bytes`` then holds that program's bytes.

    Wrapped in attribution.warmup_scope(): this first-sight is the
    expected one; a LATER first-sight of a different key fires
    clntpu_retrace_total{program="route"} (doc/perf.md)."""
    with _attr.warmup_scope(), enable_x64():
        _attr.note_program("route", (n_pad, e_pad, batch, max_hops, steps))
        _M_SEGMIN_STEPS.set(steps)
        _M_SEGMIN_BLOCK_PASSES.set(segmin_block_passes(steps))
        exe = _route_executable(n_pad, e_pad, batch, max_hops, steps)
        np.asarray(exe(*program_operands(batch, n_pad, e_pad, jnp.zeros))[0])


# ---------------------------------------------------------------------------
# The micro-batching front-end


class RouteService:
    """Coalesce concurrent getroute/pay route queries into batched
    device dispatches (the gossip ingest flush-loop shape).

    ``getroute()`` is a drop-in awaitable for dijkstra.getroute: same
    arguments, same return shapes, same NoRoute/KeyError behavior —
    jsonrpc and the payer swap it in without reshaping results.
    """

    def __init__(self, get_map, *, flush_ms: float | None = None,
                 batch: int | None = None, host_max: int | None = None,
                 device: bool | None = None, now=time.monotonic,
                 high_wm: int | None = None, low_wm: int | None = None):
        self.get_map = get_map          # () -> Gossmap | None
        self.flush_ms = ROUTE_FLUSH_MS if flush_ms is None else flush_ms
        self.batch = batch or ROUTE_BATCH
        self.host_max = HOST_ROUTE_MAX if host_max is None else host_max
        # admission control + adaptive flush widening (doc/overload.md)
        self.overload = _overload.controller(
            "route",
            high_wm if high_wm is not None else ROUTE_HIGH_WM,
            low_wm if low_wm is not None else ROUTE_LOW_WM,
            breaker_family="route", now=now)
        # device=False pins the service host-only regardless of env
        # (a --cpu daemon: batched CPU-jax routing is slower than the
        # host dijkstra it would displace, and its warmup is skipped)
        self.device = _device_enabled() if device is None else device
        self.now = now
        self._planes: RoutePlanes | None = None
        self._queue: list[RouteQuery] = []
        self._inflight = 0               # queries inside a running flush
        self._t_idle = 0.0               # perf_counter() at the last flush's end
        self._flush_due: float | None = None
        self._wakeup = asyncio.Event()
        self._task: asyncio.Task | None = None
        self._closed = False

    # -- lifecycle --------------------------------------------------------

    def start(self) -> None:
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def warmup(self) -> None:
        """Pre-compile the route program for the live graph's padded
        shape (cold XLA compiles inside a payment's getroute would
        stall it — verify.warmup's postmortem applies verbatim)."""
        g = self.get_map()
        if g is None or not self.device:
            return
        self._planes = RoutePlanes.current(g, self._planes)
        p = self._planes
        await asyncio.to_thread(warmup, self.batch, p.n_pad, p.e_pad,
                                steps=edge_order(p).steps)

    async def close(self) -> None:
        self._closed = True
        self._wakeup.set()
        if self._task is not None:
            await self._task

    # -- submission -------------------------------------------------------

    async def getroute(self, source: bytes, destination: bytes,
                       amount_msat: int, final_cltv: int = 18,
                       riskfactor: int = DJ.DEFAULT_RISKFACTOR,
                       max_hops: int = DEFAULT_MAX_HOPS,
                       excluded_scids: set | None = None,
                       with_source: bool = False):
        g = self.get_map()
        if g is None:
            raise NoRoute("no gossip graph loaded")
        if source == destination:
            raise NoRoute("source is destination")
        # the enqueue span: the carrier minted here rides the query into
        # the coalesced flush, so the exported timeline flows this call
        # to the batched dispatch that solved it
        with trace.span("route/enqueue"):
            q = RouteQuery(
                source, destination, int(amount_msat),
                int(final_cltv), int(riskfactor), int(max_hops),
                excluded_scids,
                future=asyncio.get_running_loop().create_future(),
                corr=trace.new_corr())
            if self._closed or self._task is None or self._task.done():
                # no flush loop to resolve the future (pre-start,
                # shutdown teardown ordering, or a crashed task): behave
                # like the plain host dijkstra instead of queueing forever
                _M_FALLBACK.labels(R_NOT_RUNNING).inc()
                self._resolve(q, "host",
                              self._host_solve(g, q, R_NOT_RUNNING))
                route, src_info = await q.future
                return (route, src_info) if with_source else route
            # admission control (doc/overload.md): past the high
            # watermark this query is REJECTED retryably — metered as a
            # shed, surfaced to RPC callers as TRY_AGAIN with the
            # retry-after hint — instead of joining an unbounded queue
            # and wrecking every caller's tail latency
            if not self.overload.admit(_overload.PRIO_QUERY):
                self.overload.shed(_overload.PRIO_QUERY, "admission")
                raise self.overload.overloaded()
            q.t_enqueue = time.perf_counter()
            self._queue.append(q)
            self._note_backlog()
            if self._flush_due is None:
                # adaptive flush window: latency budget stretches as
                # pressure rises (throughput over latency under load)
                self._flush_due = self.now() + self.overload.window_s(
                    self.flush_ms)
                self._wakeup.set()
            if len(self._queue) >= self._flush_threshold():
                self._wakeup.set()
        route, src_info = await q.future
        if with_source:
            return route, src_info
        return route

    def _flush_threshold(self) -> int:
        """Adaptive size trigger: `batch` when calm, widening toward
        batch * LIGHTNING_TPU_FLUSH_WIDEN under pressure so one flush
        (and its thread hop + planes refresh) serves more queries."""
        return self.overload.flush_target(self.batch)

    def _note_backlog(self) -> None:
        _M_QUEUE.set(len(self._queue))
        self.overload.update(len(self._queue), self._inflight)

    # -- the flush loop ---------------------------------------------------

    async def _run(self) -> None:
        try:
            # supervised (flush() already resolves ITS batch's futures
            # on an exception; this layer keeps the loop itself alive —
            # a dead loop would strand every later getroute): escaped
            # errors meter a restart and the loop resumes with capped
            # backoff, queued queries intact for the next flush
            backoff = _deadline.RestartBackoff()
            while not self._closed:
                try:
                    await self._step()
                except asyncio.CancelledError:
                    raise
                except Exception as e:
                    delay = backoff.next()
                    _deadline.note_restart("route_flush", e, delay)
                    events.emit("route_flush_error",
                                {"error": repr(e),
                                 "restart_delay_s": round(delay, 3)})
                    await asyncio.sleep(delay)
                else:
                    backoff.reset()
            if self._queue:
                await self.flush()
        finally:
            # the loop can die by CANCELLATION (teardown cancelling
            # pending tasks), which flush()'s supervision never sees —
            # strand no queued caller on the way out
            batch, self._queue = self._queue, []
            for q in batch:
                if not q.future.done():
                    q.future.set_exception(
                        RuntimeError("route service stopped"))

    async def _step(self) -> None:
        """One flush-loop iteration."""
        if self._flush_due is None:
            await self._wakeup.wait()
            self._wakeup.clear()
            return
        timeout = self._flush_due - self.now()
        if timeout > 0 and len(self._queue) < self._flush_threshold():
            try:
                await asyncio.wait_for(self._wakeup.wait(), timeout)
            except asyncio.TimeoutError:
                pass
            self._wakeup.clear()
            return
        if self._queue:
            await self.flush()

    async def flush(self) -> None:
        batch, self._queue = self._queue, []
        self._flush_due = None
        self._inflight = len(batch)
        self._note_backlog()
        if not batch:
            self._inflight = 0
            return
        t0 = time.perf_counter()
        try:
            await self._flush_batch(batch)
        except Exception as e:
            # supervision: an escaping exception must neither kill the
            # _run task (every later getroute would hang forever) nor
            # strand this batch's futures (every CURRENT caller would)
            log.exception("route flush failed")
            for q in batch:
                if not q.future.done():
                    _M_QUERIES.labels("host", "error").inc()
                    q.future.set_exception(
                        RuntimeError(f"route flush failed: {e}"))
        finally:
            self._t_idle = time.perf_counter()
            dt = self._t_idle - t0
            _M_FLUSH_SECONDS.observe(dt)
            self._inflight = 0
            self.overload.note_drain(len(batch), dt)
            self._note_backlog()

    async def _flush_batch(self, batch: list[RouteQuery]) -> None:
        # every route flush is one flight-recorded dispatch: the record
        # carries the coalesced queries' corr ids and the outcome of
        # whichever path (device / host / breaker / deadline) ran, and
        # the flush span flow-links back to each route/enqueue span
        corrs = trace.as_carriers(q.corr for q in batch)
        brk = _breaker.get("route")
        # where a query's queue wait ends: one observation per query.
        # The flight record takes only the flusher's own coalescing
        # wait, from the later of the oldest query's arrival and the
        # last flush's end: a query's wait overlaps the flush before
        # it, and a serial family's stages lie end to end (doc/perf.md)
        t_flush = time.perf_counter()
        for q in batch:
            _M_QUEUE_WAIT.observe(t_flush - q.t_enqueue)
        own_wait = t_flush - max(batch[0].t_enqueue, self._t_idle)
        with _flight.dispatch(
                "route", corr_ids=_flight.corr_ids(corrs),
                n_real=len(batch), lanes=len(batch),
                queue_wait_ms=1e3 * own_wait,
                breaker_state=brk.state) as rec:
            with trace.span("route/flush", corr=corrs,
                            dispatch_id=rec["dispatch_id"],
                            queries=len(batch)) as sp:
                await self._flush_batch_inner(batch, brk, rec)
            # a flush that completed without a device dispatch ran the
            # host path; only set on success so a crashed flush seals
            # as "error", not "host"
            if rec["outcome"] is None:
                rec["outcome"] = "host"
            # sealed here with the flush span's own clock: pack and
            # reconstruct are the record's prep_ms and readback_ms, so
            # dispatch_ms is the rest and the three add up to the flush
            _flight.finish(rec, dispatch_ms=max(
                0.0, sp.duration_ns / 1e6 - rec["prep_ms"]
                - (rec["readback_ms"] or 0.0)))

    async def _flush_batch_inner(self, batch: list[RouteQuery], brk,
                                 rec: dict) -> None:
        _M_BATCH.observe(len(batch))
        g = self.get_map()
        host: list[tuple[RouteQuery, str]] = []
        device: list[RouteQuery] = []
        if g is None:
            for q in batch:
                self._resolve(q, "host", ("noroute",
                                          "no gossip graph loaded"))
            return
        if not self.device:
            host = [(q, R_DISABLED) for q in batch]
        elif len(batch) <= self.host_max:
            # a near-empty bucket costs a full device round-trip for a
            # few ms of host heapq — mirror crypto's HOST_VERIFY_MAX
            host = [(q, R_BELOW_OCCUPANCY) for q in batch]
        else:
            for q in batch:
                # [0, cap] screens: NEGATIVE values are as dangerous as
                # oversized ones (they slide under the kernel's a_v>thr
                # overflow test and wrap int64 silently)
                if not 0 <= q.amount_msat <= ROUTE_MAX_AMOUNT_MSAT:
                    host.append((q, R_AMOUNT_CAP))
                elif not 0 <= q.riskfactor <= ROUTE_MAX_RISKFACTOR:
                    host.append((q, R_RISKFACTOR_CAP))
                elif q.max_hops != DEFAULT_MAX_HOPS:
                    host.append((q, R_MAX_HOPS))
                else:
                    device.append(q)
        if device and not brk.allow():
            # route breaker open: the device share takes the host
            # dijkstra (bit-identical results, doc/resilience.md).
            # allow() is consulted only once a dispatch is certain to
            # follow — a half-open probe token must always be settled
            # by the record_success/record_failure below, or the
            # breaker would wedge half-open forever.
            rec["outcome"] = "host_breaker"
            host.extend((q, R_BREAKER) for q in device)
            device = []
        if device:
            lanes = (((len(device) + self.batch - 1) // self.batch)
                     * self.batch)
            rec["n_real"] = len(device)
            rec["lanes"] = lanes
            rec["occupancy"] = round(len(device) / lanes, 4)
            io_acct: dict = {}
            try:
                _fault.fire("dispatch", "route")
                self._planes = RoutePlanes.current(g, self._planes)
                # deadline (LIGHTNING_TPU_DEADLINE_ROUTE_S, off by
                # default): a hung solver thread fails THIS batch to the
                # host path instead of wedging every future getroute
                # (the worker inherits this span as its context, so
                # solve_batch's stage spans are its children)
                with trace.span("route/dispatch",
                                dispatch_id=rec["dispatch_id"]):
                    results = await _deadline.guard(
                        asyncio.to_thread(solve_batch, self._planes,
                                          device, self.batch,
                                          io_acct=io_acct),
                        family="route", seam="dispatch")
                _M_OCCUPANCY.observe(len(device) / lanes)
                brk.record_success()
                rec["outcome"] = "ok"
                rec["h2d_bytes"] = io_acct.get("h2d_bytes", 0)
                rec["d2h_bytes"] = io_acct.get("d2h_bytes", 0)
                # the program's third static size, beside the lanes
                rec["steps"] = io_acct.get("steps")
                rec["block_passes"] = io_acct.get("block_passes")
                # the stage clocks of solve_batch's spans: what is left
                # of the flush's wall time stays under dispatch_ms
                rec["prep_ms"] = round(
                    1e3 * io_acct.get("pack_s", 0.0), 3)
                rec["readback_ms"] = round(
                    1e3 * io_acct.get("reconstruct_s", 0.0), 3)
            except _deadline.DeadlineExceeded:
                brk.record_failure()
                rec["outcome"] = "deadline"
                log.warning("device route dispatch blew its deadline; "
                            "batch re-solves on host dijkstra")
                host.extend((q, R_DEADLINE) for q in device)
                results, device = [], []
            except Exception as e:
                brk.record_failure()
                # recovered on the host dijkstra below — "error" is
                # reserved for unrecovered failures
                rec["outcome"] = "host"
                rec["error"] = type(e).__name__
                log.exception("device route dispatch failed; "
                              "falling back to host dijkstra")
                host.extend((q, R_DEVICE_ERROR) for q in device)
                results, device = [], []
            with trace.span("route/resolve", queries=len(device)):
                for q, res in zip(device, results):
                    if res[0] == "fallback":
                        host.append((q, res[1]))
                    else:
                        self._resolve(q, "device", res)
        if host:
            for _, reason in host:
                _M_FALLBACK.labels(reason).inc()
            # ON the event loop, deliberately: accepted channel_updates
            # mutate the live Gossmap from the loop (gossipd._on_accept
            # → apply_channel_update, which can rebuild the adjacency
            # arrays non-atomically), and dijkstra reads those arrays
            # live — a worker thread would race a torn graph.  The
            # device path is immune (planes are immutable snapshots);
            # the host path keeps the same on-loop contract the inline
            # jsonrpc dijkstra always had.
            for q, reason in host:
                self._resolve(q, "host", self._host_solve(g, q, reason))
                # each solve must run ON the loop (torn-graph race with
                # apply_channel_update), but a 64-query host batch must
                # not stall every other callback for its full duration
                await asyncio.sleep(0)

    @staticmethod
    def _host_solve(g, q: RouteQuery, reason: str) -> tuple:
        """One query on the host dijkstra, as a span of its own: a host
        solve holds the event loop, and `reason` (an R_* constant) says
        why it was not the device's."""
        with trace.span("route/host_solve", reason=reason):
            try:
                route, src_info = DJ.getroute(
                    g, q.source, q.destination, q.amount_msat,
                    final_cltv=q.final_cltv, riskfactor=q.riskfactor,
                    max_hops=q.max_hops, excluded_scids=q.excluded_scids,
                    with_source=True)
                return ("ok", route, src_info)
            except NoRoute as e:
                return ("noroute", str(e))
            except Exception as e:
                return ("error", e)

    def _resolve(self, q: RouteQuery, path: str, res: tuple) -> None:
        fut = q.future
        if fut.done():
            return
        if res[0] == "ok":
            _M_QUERIES.labels(path, "ok").inc()
            fut.set_result((res[1], res[2]))
        elif res[0] == "noroute":
            _M_QUERIES.labels(path, "noroute").inc()
            fut.set_exception(NoRoute(res[1]))
        else:
            _M_QUERIES.labels(path, "error").inc()
            err = res[1]
            fut.set_exception(err if isinstance(err, BaseException)
                              else RuntimeError(str(err)))
