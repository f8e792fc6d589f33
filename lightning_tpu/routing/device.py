"""Device-batched pathfinding: a vmapped backward Bellman-Ford sweep
over RoutePlanes, plus the micro-batching RouteService front-end.

The host dijkstra (routing/dijkstra.py) solves one query at a time with
a heapq; its SoA layout was always "device-shaped for a later jax
bellman-ford sweep" — this is that sweep.  The same move the paper
makes for signatures applies to routing: serial per-request work
becomes ONE vmapped XLA program over Q concurrent queries.

Kernel shape: ``max_hops`` Jacobi relaxation sweeps in a ``lax.scan``.
Each sweep gathers the previous sweep's (cost, amount, delay) labels at
every edge's RECEIVING node, prices the edge with the exact integer
cost model of dijkstra.py (compounding msat fees + CLN risk cost), and
folds candidates per FORWARDING node with two segment-mins (cost, then
lowest-edge-index among cost ties).  After k sweeps a node's label is
the cheapest ≤k-hop path to the destination — identical to dijkstra's
settled labels whenever the hop cap doesn't bind (LN paths are ~5 hops
against a cap of 20).

Tie-break rule (stated, tested): among equal-cost candidate edges for
a node within one sweep, the LOWEST edge index in the destination-keyed
CSR wins; an existing label is only replaced by a STRICTLY cheaper one.
Total cost is tie-break-independent; the chosen hops may differ from
dijkstra's when distinct paths price identically.

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


def _make_single(n_nodes: int, max_hops: int):
    """One query's backward sweep; closed over the static node count
    (segment-min needs it) and the sweep budget."""

    def single(edge_src, edge_dst, base, ppm, cd, hmin, hmax,
               edge_ok, src, dst, amount, final_cltv, riskfactor):
        E = edge_src.shape[0]
        # labels start at the destination (a select; the same values
        # as zeros.at[dst].set(x) without a per-query scatter)
        at_dst = jnp.arange(n_nodes, dtype=jnp.int32) == dst
        dist0 = jnp.where(at_dst, jnp.int64(0), jnp.int64(INF_COST))
        if dist0.dtype != jnp.int64:
            raise RuntimeError(
                "route kernel traced outside an x64 scope — msat math "
                "would silently truncate to int32")
        amt0 = jnp.where(at_dst, amount, jnp.int64(0))
        dly0 = jnp.where(at_dst, final_cltv, jnp.int64(0))
        via0 = jnp.full((n_nodes,), -1, jnp.int32)
        # edge indices ride the tie-break min as int64, like the costs:
        # on the TPU (PR 23, v5e) this program's int32 scatter-min
        # returned indices that were not the winners', so amt/dly/via
        # followed a padding edge, every later sweep priced a zero
        # amount and no reconstruction agreed with its label.  Label for
        # label the int64 form equals the CPU backend there.
        eidx = jnp.arange(E, dtype=jnp.int64)
        # per-edge safe-amount ceiling: both int64 products stay < 2^61
        cdr = cd * riskfactor
        thr = jnp.minimum(OVF_LIMIT // jnp.maximum(ppm, 1),
                          OVF_LIMIT // jnp.maximum(cdr, 1))

        def sweep(carry, _):
            dist, amt, dly, via, ovf = carry
            d_v = dist[edge_dst]
            a_v = amt[edge_dst]
            ok = edge_ok & (d_v < INF_COST)
            # the HTLC carried over u→v is a_v (what v receives) —
            # channel_update limits apply to it (dijkstra.py:107)
            ok &= (a_v >= hmin) & ((hmax == 0) | (a_v <= hmax))
            unsafe = a_v > thr
            ovf |= jnp.any(ok & unsafe)
            ok &= ~unsafe
            fee = base + (a_v * ppm) // 1_000_000
            risk = 1 + (a_v * cdr) // _RISK_DENOM
            cand = jnp.where(ok, d_v + fee + risk, INF_COST)
            best = jax.ops.segment_min(cand, edge_src,
                                       num_segments=n_nodes)
            improved = best < dist
            # tie-break: lowest edge index among the winning cost
            e_cand = jnp.where(ok & (cand == best[edge_src]), eidx, E)
            best_e = jax.ops.segment_min(e_cand, edge_src,
                                         num_segments=n_nodes)
            e_star = jnp.minimum(best_e, E - 1).astype(jnp.int32)
            v_star = edge_dst[e_star]
            dist = jnp.where(improved, best, dist)
            amt = jnp.where(improved, amt[v_star] + fee[e_star], amt)
            dly = jnp.where(improved, dly[v_star] + cd[e_star], dly)
            via = jnp.where(improved, e_star, via)
            return (dist, amt, dly, via, ovf), None

        # the phases carry jax.named_scope names (doc/tracing.md): they
        # ride the ops' metadata into a device trace, whatever number
        # XLA gives the while loop
        init = (dist0, amt0, dly0, via0, jnp.asarray(False))
        with jax.named_scope("route_relax"):
            (dist, amt, dly, via, ovf), _ = jax.lax.scan(
                sweep, init, None, length=max_hops)
        with jax.named_scope("route_extract"):
            return dist[src], via, ovf

    return single


@functools.lru_cache(maxsize=8)
def _jit_route(n_nodes: int, max_hops: int):
    single = _make_single(n_nodes, max_hops)
    return jax.jit(jax.vmap(single, in_axes=(None,) * 7 + (0,) * 6))


_PLANE_ORDER = ("edge_src", "edge_dst", "edge_base", "edge_ppm",
                "edge_cltv", "edge_hmin", "edge_hmax")


# parameter planes a channel_update can change (patchable in place on
# device); src/dst are topology and only ever full-upload
_PARAM_PLANES = ("edge_base", "edge_ppm", "edge_cltv", "edge_hmin",
                 "edge_hmax")


def _device_plane_args(planes: RoutePlanes) -> tuple:
    """Upload (once per planes revision) and return (operands,
    staged_bytes) — the shared device planes plus how many host bytes
    this call actually staged (zero when every plane was carried over;
    the perf-attribution transfer accounting, doc/perf.md).
    A param-refresh revision arrives with the topology uploads carried
    over, so only the missing planes stage; an incremental revision
    (planes.patch_idx set by with_patched_params) scatters JUST the
    touched lanes into the carried device planes — a channel_update
    burst costs O(changed) device traffic, not a full re-upload.
    int64 planes must cross jnp.asarray inside the x64 scope or they
    silently truncate to int32."""
    staged = 0
    patch = planes.patch_idx
    if patch is not None and len(patch):
        with enable_x64():
            ji = jnp.asarray(patch)
            staged += patch.nbytes if hasattr(patch, "nbytes") \
                else len(patch) * 8
            for name in _PARAM_PLANES:
                if name in planes.dev:
                    host_vals = getattr(planes, name)[patch]
                    staged += host_vals.nbytes
                    vals = jnp.asarray(host_vals)
                    planes.dev[name] = planes.dev[name].at[ji].set(vals)
    planes.patch_idx = None
    missing = [n for n in _PLANE_ORDER if n not in planes.dev]
    if missing:
        with enable_x64():
            for name in missing:
                host_plane = getattr(planes, name)
                staged += host_plane.nbytes
                planes.dev[name] = jnp.asarray(host_plane)
    return tuple(planes.dev[n] for n in _PLANE_ORDER), staged


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
    ``reconstruct_s`` — RouteService folds them into the flush's flight
    record; the clntpu_transfer_bytes_total{family="route"} counters
    are metered here either way (doc/perf.md).
    """
    g = planes.g
    out: list[tuple] = [None] * len(queries)
    idx_cache: dict[bytes, int] = {}

    def node_idx(nid: bytes) -> int:
        i = idx_cache.get(nid)
        if i is None:
            i = idx_cache[nid] = g.node_index(nid)
        return i

    plane_args, h2d = _device_plane_args(planes)
    d2h = 0
    # retrace detector: the traced program is keyed by EVERY static
    # operand shape — node pad, edge pad (e_pad grows independently of
    # n_pad on channel bursts and re-traces under the same lru_cache'd
    # jit callable), the query batch width, and the sweep budget.  A
    # first-sight of this full key after warmup means this flush is
    # paying a compile (doc/perf.md)
    _attr.note_program("route",
                       (planes.n_pad, planes.e_pad, batch, max_hops))
    kern = _jit_route(planes.n_pad, max_hops)
    pack_ns = reconstruct_ns = 0
    for start in range(0, len(queries), batch):
        chunk = queries[start:start + batch]
        with trace.span("route/pack", queries=len(chunk)) as sp:
            ok_mat = np.zeros((batch, planes.e_pad), bool)
            src = np.zeros(batch, np.int32)
            dst = np.zeros(batch, np.int32)
            amount = np.ones(batch, np.int64)
            cltv = np.zeros(batch, np.int64)
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
                cltv[i] = q.final_cltv
                rf[i] = q.riskfactor
                ok_mat[i] = planes.edge_ok_mask(q.excluded_scids)
        pack_ns += sp.duration_ns
        h2d += (ok_mat.nbytes + src.nbytes + dst.nbytes
                + amount.nbytes + cltv.nbytes + rf.nbytes)
        with trace.span("route/device"), enable_x64():
            dist_src, via, ovf = kern(
                *plane_args, jnp.asarray(ok_mat), jnp.asarray(src),
                jnp.asarray(dst), jnp.asarray(amount), jnp.asarray(cltv),
                jnp.asarray(rf))
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


def warmup(batch: int = ROUTE_BATCH, n_pad: int = 64, e_pad: int = 256,
           max_hops: int = DEFAULT_MAX_HOPS) -> None:
    """Compile (or load from the persistent cache) the route program at
    the given quantized shape, off the live path — same contract as
    gossip.verify.warmup.  Daemons call RouteService.warmup() instead,
    which passes the live planes' actual padded shape.

    Wrapped in attribution.warmup_scope(): this first-sight is the
    expected one; a LATER first-sight of a different (n_pad, max_hops)
    fires clntpu_retrace_total{program="route"} (doc/perf.md)."""
    with _attr.warmup_scope(), enable_x64():
        _attr.note_program("route", (n_pad, e_pad, batch, max_hops))
        zeros_i64 = jnp.zeros((e_pad,), jnp.int64)
        np.asarray(_jit_route(n_pad, max_hops)(
            jnp.zeros((e_pad,), jnp.int32), jnp.zeros((e_pad,), jnp.int32),
            zeros_i64, zeros_i64, zeros_i64, zeros_i64, zeros_i64,
            jnp.zeros((batch, e_pad), bool), jnp.zeros((batch,), jnp.int32),
            jnp.zeros((batch,), jnp.int32), jnp.ones((batch,), jnp.int64),
            jnp.zeros((batch,), jnp.int64), jnp.ones((batch,), jnp.int64),
        )[0])


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
        await asyncio.to_thread(warmup, self.batch, p.n_pad, p.e_pad)

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
