"""Device-batched min-cost-flow: the askrene/renepay payment engine as
a vmapped successive-shortest-paths kernel, plus the McfService
micro-batching front-end.

The host solver (routing/mcf.py) was written kernel-shaped on purpose:
its relaxation step is already an edge-parallel Bellman-Ford sweep (one
vectorized scatter-min over every residual arc per round).  This module
is that solver lifted onto the device: ``max_parts``-bounded successive
shortest augmenting paths as a ``lax.scan`` whose body runs the SAME
sweep over the SAME residual-arc universe — so Q concurrent
getroutes/xpay queries become ONE vmapped XLA program instead of Q
serial numpy solves.

Arc universe (the cost/capacity plane extension over RoutePlanes'
per-edge world): every (direction, piece, channel) triple is one
forward arc in CANONICAL order — direction-major, then the NUM_PIECES
piecewise-linear cost lanes, then channel index ascending — interleaved
with its reverse arc (forward 2k, reverse 2k+1), exactly the layout
``mcf.build_arcs`` emits minus the per-query culling (unusable lanes
simply carry zero residual).  Canonical order is load-bearing: both
solvers tie-break equal-cost relaxations on LOWEST arc index, and the
flow decomposition tie-breaks on flow-map insertion order, so identical
arc order + identical float64 cost lanes + identical int64 capacities
⇒ byte-identical route-part sets.  The parity corpus
(tests/test_zz_mcf_parity.py) pins this across reservations, biases,
disabled scids/nodes and liquidity knowledge.

Per-query cost/capacity lanes are derived host-side (numpy, in the
dispatch worker, over COPIED parameter lanes a live channel_update
cannot tear) with bit-for-bit the arithmetic of ``mcf.build_arcs``; the
expensive part — up to ``4 * max_parts`` augmentations × MAX_ROUNDS
relaxation sweeps — runs on device; flow decomposition and fee
accounting return to the host (they are O(parts), not O(arcs)) and, in
the service, to the EVENT LOOP, where the live gossmap's in-place
parameter mutation cannot race them.  Anything the planes
cannot express — layer-created channels / per-direction layer updates
(a different topology), amounts past 2^48 (int64 headroom), max_parts
past the compiled augmentation budget — and any device anomaly (walk
cap, decomposition surprise, breaker-open, deadline) falls back to the
bit-identical host oracle ``mcf.getroutes``: a device answer is always
exactly the host's answer.

All msat math runs in int64 under a scoped ``enable_x64`` (the
x64-discipline contract); costs are float64 with the host's exact
operation order, so equal-cost ties resolve identically.

McfService (the RouteService/ingest flush-loop shape): concurrent
``getroutes``/``xpay`` queries coalesce inside a flush window into one
dispatch, supervised as a first-class "mcf" dispatch family — circuit
breaker, dispatch deadline, fault-injection seam, quarantine
accounting, flight records with correlation carriers, overload
admission (TRY_AGAIN + retry-after past the high watermark), and
``clntpu_mcf_*`` metrics declared jax-free in obs/families.py.  Knobs
(doc/knobs.md is canonical):

  LIGHTNING_TPU_MCF_BATCH        device query bucket (default 8)
  LIGHTNING_TPU_MCF_FLUSH_MS     flush latency budget (default 3.0)
  LIGHTNING_TPU_MCF_HOST_MAX     <= this many queued -> host (default 1)
  LIGHTNING_TPU_MCF_MAX_AMOUNT_MSAT  device amount cap (default 2^48)
  LIGHTNING_TPU_MCF_DEVICE       0 -> host-only service (default 1)
  LIGHTNING_TPU_MCF_HIGH_WM      TRY_AGAIN admission watermark (64)
  LIGHTNING_TPU_MCF_LOW_WM       backlog-drained watermark (high/2)
"""
from __future__ import annotations

import asyncio
import functools
import logging
import os as _os
import time
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64

from ..obs import attribution as _attr
from ..obs import families as _families
from ..obs import flight as _flight
from ..obs import journey as _journey
from ..resilience import breaker as _breaker
from ..resilience import deadline as _deadline
from ..resilience import faultinject as _fault
from ..resilience import overload as _overload
from ..resilience import quarantine as _quarantine
from ..utils import events, trace
from . import mcf as MCF

log = logging.getLogger("lightning_tpu.routing.mcf_device")

# canonical-universe constants (shared with the host solver)
NUM_PIECES = MCF.NUM_PIECES
MAX_ROUNDS = MCF.MAX_ROUNDS
# compiled augmentation budget: the kernel's outer scan length.  A
# query's own cap is 4*max_parts (host semantics); max_parts beyond
# MCF.MAX_PARTS is inexpressible and goes to the host oracle.
AUG_STEPS = 4 * MCF.MAX_PARTS
# predecessor-walk budget per augmentation: paths longer than this are
# absurd on LN topologies; a walk that has not reached the source in
# WALK_CAP steps (truncation cycle or pathological depth) flags the
# query back to the host oracle instead of augmenting a wrong path
WALK_CAP = 64
# host/device relaxation tolerance — mcf._shortest_path's epsilon
_EPS = 1e-9

_MIN_NODE_PAD = 64
_MIN_ARC_PAD = 256

MCF_BATCH = int(_os.environ.get("LIGHTNING_TPU_MCF_BATCH", "8"))
MCF_FLUSH_MS = float(_os.environ.get("LIGHTNING_TPU_MCF_FLUSH_MS", "3.0"))
MCF_HOST_MAX = int(_os.environ.get("LIGHTNING_TPU_MCF_HOST_MAX", "1"))
# int64 residual/remaining headroom: piece capacities can carry the
# "no bound at all" amount fill, and augmentation adds bottlenecks into
# reverse lanes — 2^48 msat (~2814 BTC) keeps every sum far below 2^62
MCF_MAX_AMOUNT_MSAT = int(_os.environ.get(
    "LIGHTNING_TPU_MCF_MAX_AMOUNT_MSAT", str(1 << 48)))
# admission-control watermarks in queued QUERIES (doc/overload.md):
# an MCF solve is ~an order heavier than a getroute, so the defaults
# sit well below the route family's
MCF_HIGH_WM = int(_os.environ.get("LIGHTNING_TPU_MCF_HIGH_WM", "64"))
MCF_LOW_WM = (int(_os.environ.get("LIGHTNING_TPU_MCF_LOW_WM", "0"))
              or MCF_HIGH_WM // 2)

# instrument families live in obs.families so exposition-only
# consumers (tools/obs_snapshot.py) get them without importing jax
_M_FLUSH_SECONDS = _families.MCF_FLUSH_SECONDS
_M_BATCH = _families.MCF_BATCH_QUERIES
_M_OCCUPANCY = _families.MCF_OCCUPANCY
_M_QUERIES = _families.MCF_QUERIES
_M_FALLBACK = _families.MCF_FALLBACK
_M_QUEUE = _families.MCF_QUEUE
_M_PARTS = _families.MCF_PARTS

# fallback reasons (label values — observable in tests/doc/routing.md)
R_BELOW_OCCUPANCY = "below_occupancy"
R_DISABLED = "device_disabled"
R_AMOUNT_CAP = "amount_cap"
R_MAX_PARTS = "max_parts_cap"
R_LAYERED = "layered_topology"
R_WALK_CAP = "walk_cap"
R_DECOMPOSE = "decompose"
R_DEVICE_ERROR = "device_error"
R_NOT_RUNNING = "not_running"
R_BREAKER = "breaker_open"
R_DEADLINE = "deadline"
R_NO_PLANES = "no_planes"
R_STALE_PLANES = "stale_planes"


def _device_enabled() -> bool:
    return _os.environ.get("LIGHTNING_TPU_MCF_DEVICE", "1") != "0"


# ---------------------------------------------------------------------------
# McfPlanes: the canonical arc universe + cached per-direction lanes


@dataclass
class _DirLanes:
    """Per-direction channel-major parameter lanes (the inputs
    mcf.build_arcs reads), cached as the dtypes it converts to so
    per-query prep skips the astype churn.  Copies, not views: a
    freshness bump re-derives them; in-place gossmap mutation between
    bumps cannot tear a prep."""

    u: np.ndarray          # (C,) int32 — forwarding node
    v: np.ndarray          # (C,) int32 — receiving node
    enabled: np.ndarray    # (C,) bool
    hmin: np.ndarray       # (C,) int64
    cap0: np.ndarray       # (C,) int64 — capacity after the hmax fold
    fee_ppm: np.ndarray    # (C,) float64
    base: np.ndarray       # (C,) float64
    cltv: np.ndarray       # (C,) float64


@dataclass
class McfPlanes:
    """The min-cost-flow plane extension: one Gossmap revision's full
    (direction × piece × channel) arc universe in canonical order.

    Topology (``i_src``/``i_dst``, the interleaved forward/reverse arc
    endpoints) uploads to the device once per topology revision; the
    per-direction parameter lanes refresh on a params bump and feed the
    per-query cost/capacity lane prep, which stays host-side (it is
    query-dependent: amount, part hint, layers)."""

    g: object
    topo_version: int
    params_version: int
    n_channels: int
    n_real: int
    n_pad: int
    a_fwd_real: int        # 2 * NUM_PIECES * n_channels
    a_fwd_pad: int
    # canonical forward-arc endpoints, padded; interleaved device view
    # (fwd 2k, rev 2k+1) is what the kernel consumes
    i_src: np.ndarray      # (2*a_fwd_pad,) int32
    i_dst: np.ndarray      # (2*a_fwd_pad,) int32
    dirs: tuple            # (_DirLanes, _DirLanes)
    dev: dict = field(default_factory=dict)
    # cursor into the gossmap's (channel, direction) change log at the
    # time the lanes were derived: current() reads the entries since to
    # name the scids a params refresh folded in (journey mcf_planes hop)
    params_log_pos: int = 0

    @classmethod
    def build(cls, g) -> "McfPlanes":
        C = g.n_channels
        n_pad = _pow2(max(g.n_nodes, 1), _MIN_NODE_PAD)
        a_fwd_real = 2 * NUM_PIECES * C
        a_fwd_pad = _pow2(max(a_fwd_real, 1), _MIN_ARC_PAD)

        fwd_src = np.zeros(a_fwd_pad, np.int32)
        fwd_dst = np.zeros(a_fwd_pad, np.int32)
        for d in (0, 1):
            u = (g.node1 if d == 0 else g.node2).astype(np.int32)
            v = (g.node2 if d == 0 else g.node1).astype(np.int32)
            for p in range(NUM_PIECES):
                lane = (d * NUM_PIECES + p) * C
                fwd_src[lane:lane + C] = u
                fwd_dst[lane:lane + C] = v
        i_src = np.empty(2 * a_fwd_pad, np.int32)
        i_dst = np.empty(2 * a_fwd_pad, np.int32)
        i_src[0::2], i_src[1::2] = fwd_src, fwd_dst
        i_dst[0::2], i_dst[1::2] = fwd_dst, fwd_src

        return cls(
            g=g,
            topo_version=getattr(g, "topology_version", 0),
            params_version=getattr(g, "params_version", 0),
            n_channels=C, n_real=g.n_nodes, n_pad=n_pad,
            a_fwd_real=a_fwd_real, a_fwd_pad=a_fwd_pad,
            i_src=i_src, i_dst=i_dst,
            dirs=tuple(cls._dir_lanes(g, d) for d in (0, 1)),
            params_log_pos=getattr(g, "param_log_pos", 0),
        )

    @staticmethod
    def _dir_lanes(g, d: int) -> _DirLanes:
        cap = (g.capacity_sat.astype(np.float64) * 1000).astype(np.int64)
        cap = cap.copy()
        hmax = g.htlc_max_msat[d].astype(np.int64)
        # unknown on-chain capacity: the direction's htlc_maximum is the
        # best bound; a present htlc_maximum always caps (build_arcs)
        unknown = cap == 0
        cap[unknown] = hmax[unknown]
        has_max = hmax > 0
        cap[has_max] = np.minimum(cap[has_max], hmax[has_max])
        return _DirLanes(
            u=(g.node1 if d == 0 else g.node2).astype(np.int32),
            v=(g.node2 if d == 0 else g.node1).astype(np.int32),
            enabled=g.enabled[d].copy(),
            hmin=g.htlc_min_msat[d].astype(np.int64),
            cap0=cap,
            fee_ppm=g.fee_ppm[d].astype(np.float64),
            base=g.fee_base_msat[d].astype(np.float64),
            cltv=g.cltv_delta[d].astype(np.float64),
        )

    def with_fresh_params(self) -> "McfPlanes":
        """Param-bump refresh: re-derive the per-direction lanes from
        the same topology revision, carrying the arc-endpoint arrays
        (and their device uploads) over unchanged."""
        import dataclasses

        return dataclasses.replace(
            self,
            params_version=getattr(self.g, "params_version", 0),
            params_log_pos=getattr(self.g, "param_log_pos", 0),
            dirs=tuple(self._dir_lanes(self.g, d) for d in (0, 1)),
        )

    @classmethod
    def current(cls, g, cached: "McfPlanes | None") -> "McfPlanes":
        """Freshness gate (RoutePlanes.current shape): rebuild on a
        topology bump or a different map object, refresh the parameter
        lanes on a params bump, reuse otherwise.  Never mutates
        ``cached``."""
        if (cached is None or cached.g is not g
                or cached.topo_version
                != getattr(g, "topology_version", 0)):
            return cls.build(g)
        if cached.params_version != getattr(g, "params_version", 0):
            fresh = cached.with_fresh_params()
            if _journey.enabled() and hasattr(g, "param_entries_since"):
                # journey terminus for the MCF view: the sampled
                # channel_update's parameters are now in the lanes the
                # next batched solve prices against (doc/journeys.md)
                entries = g.param_entries_since(cached.params_log_pos)
                if entries is not None:
                    for c, d in set(entries):
                        _journey.hop("mcf_planes", "channel",
                                     int(g.scids[int(c)]),
                                     outcome="fresh", direction=int(d))
            return fresh
        return cached


def _pow2(n: int, floor: int) -> int:
    p = floor
    while p < n:
        p <<= 1
    return p


# ---------------------------------------------------------------------------
# Per-query cost/capacity lanes (bit-matching mcf.build_arcs)


def _knowledge_max(layers, scid: int, d: int) -> int:
    k = layers.knowledge.get((scid, d))
    m = None if k is None else k.get("max")
    return (1 << 62) if m is None else m   # 0 IS a constraint


def query_lanes(planes: McfPlanes, amount_msat: int, layers,
                prob_weight: float = 1.0, delay_weight: float = 1.0,
                part_hint: int | None = None):
    """The per-query (cost, capacity) lanes over the canonical forward
    arc universe: float64 per-msat costs and int64 piece capacities,
    value-identical to what ``mcf.build_arcs`` would emit for the same
    query (unusable lanes carry zero capacity instead of being culled).

    Raises McfError("no usable channels") exactly when build_arcs
    would: when NO channel direction survives the enabled/hmin/
    disabled screens.  Enabled-but-zero-capacity universes (everything
    reserved, knowledge max=0) do NOT raise — build_arcs emits their
    empty piece arrays and the host solver answers "no residual path",
    so the kernel must see the zero-residual lanes and answer the
    same."""
    g = planes.g
    C = planes.n_channels
    layers = layers or MCF.Layers()
    part = max(1, amount_msat // (part_hint or MCF.MAX_PARTS))

    cost = np.zeros(planes.a_fwd_pad, np.float64)
    res = np.zeros(planes.a_fwd_pad, np.int64)
    if C == 0:
        raise MCF.McfError("no usable channels")

    dis = None
    if layers.disabled:
        dis = np.fromiter((int(s) in layers.disabled for s in g.scids),
                          bool, C)
    bad_nodes: list[int] = []
    if layers.disabled_nodes:
        for nid in layers.disabled_nodes:
            try:
                bad_nodes.append(g.node_index(nid))
            except KeyError:
                pass
    bias = None
    if layers.biases:
        bias = np.fromiter(
            (layers.biases.get(int(s), 0) for s in g.scids),
            np.float64, C)
    nb = None
    if layers.node_biases:
        nb = np.zeros(g.n_nodes)
        for nid, b in layers.node_biases.items():
            try:
                nb[g.node_index(nid)] = b
            except KeyError:
                pass

    any_enabled = False
    for d in (0, 1):
        lanes = planes.dirs[d]
        en = lanes.enabled & (lanes.hmin <= part)
        if dis is not None:
            en &= ~dis
        if bad_nodes:
            en &= ~(np.isin(lanes.u, bad_nodes)
                    | np.isin(lanes.v, bad_nodes))
        any_enabled = any_enabled or bool(np.any(en))
        cap = lanes.cap0.copy()
        cap[cap == 0] = amount_msat       # no bound at all: permissive
        if layers.reserved:
            rsv = np.fromiter(
                (layers.reserved.get((int(s), d), 0) for s in g.scids),
                np.int64, C)
            cap = np.maximum(cap - rsv, 0)
        if layers.knowledge:
            kmax = np.fromiter(
                (_knowledge_max(layers, int(s), d) for s in g.scids),
                np.int64, C)
            cap = np.minimum(cap, kmax)

        eff_ppm = lanes.fee_ppm + lanes.base * 1e6 / part
        eff_ppm = eff_ppm + lanes.cltv * delay_weight
        if bias is not None:
            eff_ppm = eff_ppm + bias
        if nb is not None:
            eff_ppm = eff_ppm + nb[lanes.u]

        piece_cap = cap // NUM_PIECES
        for p in range(NUM_PIECES):
            pc = piece_cap if p < NUM_PIECES - 1 \
                else cap - piece_cap * (NUM_PIECES - 1)
            prob_ppm = (PIECE_SLOPES_F64[p] * prob_weight * 1e6
                        / np.maximum(cap.astype(np.float64), 1.0))
            lane = (d * NUM_PIECES + p) * C
            res[lane:lane + C] = np.where(en & (pc > 0), pc, 0)
            cost[lane:lane + C] = eff_ppm + prob_ppm * part
    if not any_enabled:
        raise MCF.McfError("no usable channels")
    return cost, res


PIECE_SLOPES_F64 = tuple(float(s) for s in MCF.PIECE_SLOPES)


# ---------------------------------------------------------------------------
# The kernel


def _make_mcf_single(n_pad: int, a_fwd_pad: int):
    """One query's successive-shortest-paths solve, closed over the
    static node and arc pads.  Returns (flow per forward arc, remaining
    msat, no-path flag, walk-failure flag)."""
    A = 2 * a_fwd_pad

    def single(i_src, i_dst, fwd_cost, fwd_res, src, dst, amount,
               aug_cap):
        if fwd_res.dtype != jnp.int64:
            raise RuntimeError(
                "mcf kernel traced outside an x64 scope — msat math "
                "would silently truncate to int32")
        # interleave forward/reverse on device: arc 2k forward, 2k+1
        # its reverse (cost negated, zero initial residual) — the
        # host solver's exact residual-graph layout
        cost = jnp.stack([fwd_cost, -fwd_cost], axis=1).reshape(A)
        res0 = jnp.stack([fwd_res, jnp.zeros_like(fwd_res)],
                         axis=1).reshape(A)
        aidx = jnp.arange(A, dtype=jnp.int32)

        def bellman_ford(residual):
            """MAX_ROUNDS edge-parallel sweeps over live arcs; the
            converged prefix is a fixed point, so running the full
            budget is state-identical to the host's early break."""
            acost = jnp.where(residual > 0, cost, jnp.inf)
            dist0 = jnp.full((n_pad,), jnp.inf,
                             jnp.float64).at[src].set(0.0)
            pred0 = jnp.full((n_pad,), -1, jnp.int32)

            def sweep(carry, _):
                dist, pred = carry
                cand = dist[i_src] + acost
                better = cand < dist[i_dst] - _EPS
                candm = jnp.where(better, cand, jnp.inf)
                best = jax.ops.segment_min(candm, i_dst,
                                           num_segments=n_pad)
                improved = best < dist - _EPS
                # tie-break: lowest arc index among the winning cost
                # (the host's stable-sort-then-first-per-dst rule)
                e_cand = jnp.where(better & (cand == best[i_dst]),
                                   aidx, A)
                best_e = jax.ops.segment_min(e_cand, i_dst,
                                             num_segments=n_pad)
                dist = jnp.where(improved, best, dist)
                pred = jnp.where(improved, best_e, pred)
                return (dist, pred), None

            (dist, pred), _ = jax.lax.scan(sweep, (dist0, pred0), None,
                                           length=MAX_ROUNDS)
            return dist, pred

        def aug_step(carry, step):
            residual, remaining, nopath, walkfail = carry
            active = ((remaining > 0) & (step < aug_cap)
                      & ~nopath & ~walkfail)
            dist, pred = bellman_ford(residual)
            reachable = jnp.isfinite(dist[dst])

            def walk_step(v, _):
                # follow predecessor arcs dst -> src; freeze at src
                a = jnp.where(v == src, jnp.int32(-1), pred[v])
                nv = jnp.where(a >= 0, i_src[jnp.maximum(a, 0)], v)
                return nv, jnp.where(a >= 0, a, jnp.int32(-1))

            vend, path = jax.lax.scan(walk_step, dst, None,
                                      length=WALK_CAP)
            # not reaching src within WALK_CAP covers both truncation
            # cycles (the host's seen-set guard) and absurd depths
            walk_ok = vend == src
            pvalid = path >= 0
            psafe = jnp.maximum(path, 0)
            pres = jnp.where(pvalid, residual[psafe],
                             jnp.int64(1) << 62)
            bottleneck = jnp.minimum(remaining, jnp.min(pres))
            apply = active & reachable & walk_ok
            delta = jnp.where(pvalid & apply, -bottleneck,
                              jnp.int64(0))
            residual = residual.at[psafe].add(delta)
            residual = residual.at[psafe ^ 1].add(-delta)
            remaining = jnp.where(apply, remaining - bottleneck,
                                  remaining)
            nopath = nopath | (active & ~reachable)
            walkfail = walkfail | (active & reachable & ~walk_ok)
            return (residual, remaining, nopath, walkfail), None

        init = (res0, amount, jnp.asarray(False), jnp.asarray(False))
        (residual, remaining, nopath, walkfail), _ = jax.lax.scan(
            aug_step, init, jnp.arange(AUG_STEPS, dtype=jnp.int32))
        # reverse-lane residuals ARE the pushed flow per forward arc
        return residual[1::2], remaining, nopath, walkfail

    return single


@functools.lru_cache(maxsize=8)
def _jit_mcf(n_pad: int, a_fwd_pad: int):
    single = _make_mcf_single(n_pad, a_fwd_pad)
    return jax.jit(jax.vmap(single,
                            in_axes=(None, None) + (0,) * 6))


def _device_arc_args(planes: McfPlanes) -> tuple:
    """Upload (once per topology revision) and return ((i_src, i_dst),
    staged_bytes) — the shared arc-endpoint planes plus how many host
    bytes this call staged (zero on carry-over)."""
    staged = 0
    if "i_src" not in planes.dev:
        with enable_x64():
            staged += planes.i_src.nbytes + planes.i_dst.nbytes
            planes.dev["i_src"] = jnp.asarray(planes.i_src)
            planes.dev["i_dst"] = jnp.asarray(planes.i_dst)
    return (planes.dev["i_src"], planes.dev["i_dst"]), staged


# ---------------------------------------------------------------------------
# Batched solve: prep -> dispatch -> decompose


def _freeze_layers(layers):
    """Value snapshot of a live mcf.Layers for the queue: lane prep
    runs in the flush worker thread while askrene-reserve/-unreserve
    and inform() mutate the live object from the event loop — a query
    must solve against the layer state it was enqueued under, never a
    half-applied reservation sweep.  Containers are copied (knowledge's
    inner dicts too: inform() mutates them in place); both the device
    prep and a host-oracle fallback of the same query read this one
    frozen copy, so the two paths stay bit-comparable."""
    if layers is None:
        return None
    return MCF.Layers(
        disabled=set(layers.disabled),
        biases=dict(layers.biases),
        reserved=dict(layers.reserved),
        knowledge={k: dict(v) for k, v in layers.knowledge.items()},
        created=dict(layers.created),
        updates=dict(layers.updates),
        disabled_nodes=set(layers.disabled_nodes),
        node_biases=dict(layers.node_biases),
    )


@dataclass
class McfQuery:
    """One getroutes-class request (mcf.getroutes semantics).  The
    ``layers`` snapshot is the MERGED layer set the query solves
    against (attach_routing_commands merges named layers before
    enqueueing)."""

    source: bytes
    destination: bytes
    amount_msat: int
    layers: object = None              # mcf.Layers | None
    maxfee_msat: int | None = None
    final_cltv: int = 18
    max_parts: int = MCF.MAX_PARTS
    prob_weight: float = 1.0
    delay_weight: float = 1.0
    future: object = None
    # correlation carrier minted in the enqueue span (doc/tracing.md)
    corr: object = None
    # journey identity (doc/journeys.md): xpay passes its payment_hash
    # so the query's hops land on the payment's journey; None for
    # plain getroutes callers (no journey recorded)
    journey_key: object = None
    # enqueue time (service.now() at admission): the per-query
    # queue-wait anchor for the mcf_flush hop
    t_enq: float = 0.0


def _expressible(q: McfQuery) -> str | None:
    """None when the device universe can express the query, else the
    fallback reason label."""
    if not 0 < q.amount_msat <= MCF_MAX_AMOUNT_MSAT:
        return R_AMOUNT_CAP
    if not 0 < q.max_parts <= MCF.MAX_PARTS:
        return R_MAX_PARTS
    ly = q.layers
    if ly is not None and (ly.created or ly.updates):
        # layer-created channels / layer updates are a DIFFERENT
        # topology (graph_with_layers materializes a new gossmap);
        # the host oracle owns those queries
        return R_LAYERED
    return None


def _decompose_flow(planes: McfPlanes, q: McfQuery,
                    flow_lanes: np.ndarray):
    """Host-side flow decomposition from the kernel's per-forward-arc
    flows: rebuild the (channel, direction) flow map in canonical arc
    order (insertion order drives peel tie-breaks) and peel parts with
    the host solver's own code."""
    g = planes.g
    C = planes.n_channels
    used = np.nonzero(flow_lanes[:planes.a_fwd_real] > 0)[0]
    flow: dict[tuple[int, int], int] = {}
    for k in used:                      # ascending == canonical order
        c = int(k % C)
        d = int(k // C) // NUM_PIECES
        key = (c, d)
        flow[key] = flow.get(key, 0) + int(flow_lanes[k])
    src = g.node_index(q.source)
    dst = g.node_index(q.destination)
    return MCF.peel_parts(g, flow, src, dst, q.amount_msat)


def _finish_query(planes: McfPlanes, q: McfQuery,
                  flow_lanes: np.ndarray, remaining: int, nopath: bool,
                  walkfail: bool):
    """One query's post-readback resolution.  Returns
    ("ok", result_dict) / ("mcferr", message) / ("fallback", reason) /
    ("retry",) — retry = the fee budget blew and the host semantics
    call for a second solve with the reliability weight slashed."""
    if walkfail:
        return ("fallback", R_WALK_CAP)
    if nopath:
        # the host raises at the same remaining value (identical
        # residual evolution up to the failing augmentation)
        return ("mcferr",
                f"no residual path for remaining {remaining} msat")
    if remaining > 0:
        return ("mcferr", f"could not place {remaining} msat")
    try:
        parts = _decompose_flow(planes, q, flow_lanes)
        routes = MCF.routes_from_parts(planes.g, parts, q.destination,
                                       q.final_cltv)
    except Exception as e:
        log.warning("mcf flow decomposition diverged (%s); "
                    "host re-solves", e)
        return ("fallback", R_DECOMPOSE)
    fee = sum(r["path"][0].amount_msat for r in routes) - q.amount_msat
    if q.maxfee_msat is not None and fee > q.maxfee_msat:
        return ("retry", fee)
    return ("ok", {"routes": [MCF._route_rpc(r) for r in routes],
                   "fee_msat": fee, "parts": len(routes)})


def _prep_chunk(planes: McfPlanes, chunk: list[McfQuery], batch: int,
                prob_scale: float, out: list):
    """Stage one padded dispatch's operands; resolves screening
    failures (unknown node, src==dst, dead universe, inexpressible)
    into ``out`` (chunk-indexed) and masks their lanes off."""
    cost = np.zeros((batch, planes.a_fwd_pad), np.float64)
    res = np.zeros((batch, planes.a_fwd_pad), np.int64)
    src = np.zeros(batch, np.int32)
    dst = np.zeros(batch, np.int32)
    amount = np.ones(batch, np.int64)
    aug_cap = np.zeros(batch, np.int32)
    g = planes.g
    for i, q in enumerate(chunk):
        reason = _expressible(q)
        if reason is not None:
            out[i] = ("fallback", reason)
            continue
        try:
            src[i] = g.node_index(q.source)
            dst[i] = g.node_index(q.destination)
        except KeyError as e:
            out[i] = ("error", e)
            continue
        if src[i] == dst[i]:
            out[i] = ("mcferr", "source is destination")
            continue
        try:
            cost[i], res[i] = query_lanes(
                planes, q.amount_msat, q.layers,
                q.prob_weight * prob_scale, q.delay_weight,
                part_hint=q.max_parts)
        except MCF.McfError as e:
            out[i] = ("mcferr", str(e))
            continue
        amount[i] = q.amount_msat
        aug_cap[i] = 4 * q.max_parts
    return cost, res, src, dst, amount, aug_cap


def _dispatch_lanes(planes: McfPlanes, ops: tuple,
                    io_acct: dict | None = None):
    """The one jit call site: upload the chunk's lanes, run the batched
    solve, read back flows.  Callers reach this only behind the mcf
    breaker/flight seams (McfService) or warmup/bench harnesses."""
    cost, res, src, dst, amount, aug_cap = ops
    arc_args, h2d = _device_arc_args(planes)
    _attr.note_program("mcf", (planes.n_pad, planes.a_fwd_pad,
                               cost.shape[0]))
    kern = _jit_mcf(planes.n_pad, planes.a_fwd_pad)
    h2d += (cost.nbytes + res.nbytes + src.nbytes + dst.nbytes
            + amount.nbytes + aug_cap.nbytes)
    with enable_x64():
        flow, remaining, nopath, walkfail = kern(
            *arc_args, jnp.asarray(cost), jnp.asarray(res),
            jnp.asarray(src), jnp.asarray(dst), jnp.asarray(amount),
            jnp.asarray(aug_cap))
        flow = np.asarray(flow)
        remaining = np.asarray(remaining)
        nopath = np.asarray(nopath)
        walkfail = np.asarray(walkfail)
    d2h = (flow.nbytes + remaining.nbytes + nopath.nbytes
           + walkfail.nbytes)
    _families.TRANSFER_BYTES.labels("mcf", "h2d").inc(h2d)
    _families.TRANSFER_BYTES.labels("mcf", "d2h").inc(d2h)
    if io_acct is not None:
        io_acct["h2d_bytes"] = io_acct.get("h2d_bytes", 0) + h2d
        io_acct["d2h_bytes"] = io_acct.get("d2h_bytes", 0) + d2h
    return flow, remaining, nopath, walkfail


def _solve_indices(planes: McfPlanes, queries: list[McfQuery],
                   idx_list: list[int], batch: int, prob_scale: float,
                   out: list, io_acct: dict | None = None) -> list:
    """Prep + dispatch the named queries (blocking; runs in the flush
    worker).  Screening failures resolve straight into ``out``; device
    results come back as (index, flow_row, remaining, nopath, walkfail)
    readback tuples for the caller to judge — the service judges on the
    event loop, where live gossmap mutation cannot race the
    decomposition's graph reads."""
    readback: list = []
    for start in range(0, len(idx_list), batch):
        idxs = idx_list[start:start + batch]
        chunk = [queries[j] for j in idxs]
        sub: list = [None] * len(chunk)
        ops = _prep_chunk(planes, chunk, batch, prob_scale, sub)
        for i, j in enumerate(idxs):
            if sub[i] is not None:
                out[j] = sub[i]
        if all(r is not None for r in sub):
            continue
        flow, remaining, nopath, walkfail = _dispatch_lanes(
            planes, ops, io_acct)
        for i, j in enumerate(idxs):
            if out[j] is None:
                readback.append((j, flow[i], int(remaining[i]),
                                 bool(nopath[i]), bool(walkfail[i])))
    return readback


def _judge_round(planes: McfPlanes, queries: list[McfQuery],
                 readback: list, out: list,
                 final_attempt: bool) -> list[int]:
    """Resolve one dispatch round's readbacks; returns the indices that
    blew their maxfee budget and earn the host's second attempt (the
    reliability weight slashed 100x).  On the final attempt a blown
    budget is the host's exact terminal McfError."""
    retry: list[int] = []
    for j, fl, rem, nop, wf in readback:
        verdict = _finish_query(planes, queries[j], fl, rem, nop, wf)
        if verdict[0] == "retry":
            if final_attempt:
                out[j] = ("mcferr",
                          f"cheapest multi-part fee {verdict[1]} "
                          f"exceeds maxfee {queries[j].maxfee_msat}")
            else:
                retry.append(j)
        else:
            out[j] = verdict
    return retry


def solve_mcf_batch(planes: McfPlanes, queries: list[McfQuery],
                    batch: int = MCF_BATCH,
                    io_acct: dict | None = None) -> list[tuple]:
    """Solve every query on the device in ceil(Q/batch) vmapped
    dispatches, with host-side decomposition and the host's two-attempt
    maxfee semantics (a blown budget re-solves with the reliability
    weight slashed 100x before failing).

    Returns one tuple per query:
      ("ok", result_dict)   — the mcf.getroutes response shape, exact
      ("mcferr", message)   — unroutable (host raises McfError here)
      ("fallback", reason)  — solve on the host oracle instead
      ("error", exc)        — the query's own error (unknown node)

    This is the direct (bench/test-harness) entry; it carries its own
    breaker + flight-record seam — the McfService flush path supervises
    the per-round internals itself and never calls through here.  An
    open mcf breaker short-circuits the whole batch to ("fallback",
    breaker_open); callers own the host re-solve, exactly like every
    other fallback lane.
    """
    out: list = [None] * len(queries)
    brk = _breaker.get("mcf")
    with _flight.dispatch("mcf", n_real=len(queries),
                          lanes=len(queries),
                          breaker_state=brk.state) as rec:
        if not brk.allow():
            rec["outcome"] = "host_breaker"
            return [("fallback", R_BREAKER)] * len(queries)
        try:
            rb = _solve_indices(planes, queries,
                                list(range(len(queries))),
                                batch, 1.0, out, io_acct)
            retry = _judge_round(planes, queries, rb, out,
                                 final_attempt=False)
            if retry:
                rb2 = _solve_indices(planes, queries, retry, batch,
                                     1.0 / 100.0, out, io_acct)
                _judge_round(planes, queries, rb2, out,
                             final_attempt=True)
            brk.record_success()
            rec["outcome"] = "ok"
        except Exception:
            brk.record_failure()
            raise
    return out


def warmup(batch: int = MCF_BATCH, n_pad: int = 64,
           a_fwd_pad: int = 256) -> None:
    """Compile (or load from the persistent cache) the mcf program at
    the given quantized shape, off the live path — the route warmup
    contract.  Daemons call McfService.warmup() instead, which passes
    the live planes' actual padded shape."""
    with _attr.warmup_scope(), enable_x64():
        _attr.note_program("mcf", (n_pad, a_fwd_pad, batch))
        A = 2 * a_fwd_pad
        np.asarray(_jit_mcf(n_pad, a_fwd_pad)(
            jnp.zeros((A,), jnp.int32), jnp.zeros((A,), jnp.int32),
            jnp.zeros((batch, a_fwd_pad), jnp.float64),
            jnp.zeros((batch, a_fwd_pad), jnp.int64),
            jnp.zeros((batch,), jnp.int32), jnp.zeros((batch,), jnp.int32),
            jnp.ones((batch,), jnp.int64),
            jnp.full((batch,), 4, jnp.int32),
        )[0])


# ---------------------------------------------------------------------------
# The micro-batching front-end


class McfService:
    """Coalesce concurrent getroutes/xpay min-cost-flow queries into
    batched device dispatches (the RouteService flush-loop shape).

    ``getroutes()`` is a drop-in awaitable for mcf.getroutes: same
    result dict, same McfError/KeyError behavior — the askrene RPC
    surface and xpay swap it in without reshaping results."""

    def __init__(self, get_map, *, flush_ms: float | None = None,
                 batch: int | None = None, host_max: int | None = None,
                 device: bool | None = None, now=time.monotonic,
                 high_wm: int | None = None, low_wm: int | None = None):
        self.get_map = get_map          # () -> Gossmap | None
        self.flush_ms = MCF_FLUSH_MS if flush_ms is None else flush_ms
        self.batch = batch or MCF_BATCH
        self.host_max = MCF_HOST_MAX if host_max is None else host_max
        self.overload = _overload.controller(
            "mcf",
            high_wm if high_wm is not None else MCF_HIGH_WM,
            low_wm if low_wm is not None else MCF_LOW_WM,
            breaker_family="mcf", now=now)
        # device=False pins the service host-only (a --cpu daemon:
        # batched CPU-jax flow solving is slower than the numpy oracle
        # it would displace, and its warmup is skipped)
        self.device = _device_enabled() if device is None else device
        self.now = now
        self._planes: McfPlanes | None = None
        self._queue: list[McfQuery] = []
        self._inflight = 0
        self._flush_due: float | None = None
        self._wakeup = asyncio.Event()
        self._task: asyncio.Task | None = None
        self._closed = False
        # (t_flush0, t_svc0, flight rec) of the flush being resolved —
        # flushes are serialized on the loop, so one slot suffices;
        # None on the inline post-close host path (no batch, no
        # mcf_flush journey hop)
        self._flush_ctx: tuple | None = None

    # -- lifecycle --------------------------------------------------------

    def start(self) -> None:
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def warmup(self) -> None:
        """Pre-compile the mcf program for the live graph's padded arc
        universe (a cold XLA compile inside a payment's getroutes would
        stall it — verify.warmup's postmortem applies verbatim)."""
        g = self.get_map()
        if g is None or not self.device:
            return
        self._planes = McfPlanes.current(g, self._planes)
        p = self._planes
        await asyncio.to_thread(warmup, self.batch, p.n_pad, p.a_fwd_pad)

    async def close(self) -> None:
        self._closed = True
        self._wakeup.set()
        if self._task is not None:
            await self._task

    # -- submission -------------------------------------------------------

    async def getroutes(self, source: bytes, destination: bytes,
                        amount_msat: int, *, layers=None,
                        maxfee_msat: int | None = None,
                        final_cltv: int = 18,
                        max_parts: int = MCF.MAX_PARTS,
                        prob_weight: float = 1.0,
                        delay_weight: float = 1.0,
                        journey_key=None) -> dict:
        """``journey_key`` (a payment_hash, optional) attributes this
        query's pipeline hops to that payment's journey
        (doc/journeys.md); xpay threads it through automatically."""
        g = self.get_map()
        if g is None:
            raise MCF.McfError("no gossip graph loaded")
        with trace.span("mcf/enqueue"):
            q = McfQuery(
                source, destination, int(amount_msat),
                _freeze_layers(layers),
                maxfee_msat, int(final_cltv), int(max_parts),
                float(prob_weight), float(delay_weight),
                future=asyncio.get_running_loop().create_future(),
                corr=trace.new_corr(), journey_key=journey_key,
                t_enq=self.now())
            if journey_key is not None:
                _journey.hop("enqueue", "payment", journey_key,
                             outcome="ok", corr_id=q.corr.corr_id,
                             amount_msat=int(amount_msat))
            if self._closed or self._task is None or self._task.done():
                # no flush loop to resolve the future: behave like the
                # plain host oracle instead of queueing forever
                _M_FALLBACK.labels(R_NOT_RUNNING).inc()
                self._resolve(q, "host", self._host_solve(g, q))
                return await q.future
            # admission control (doc/overload.md): past the high
            # watermark the query is REJECTED retryably — surfaced to
            # RPC callers as TRY_AGAIN with the retry-after hint
            if not self.overload.admit(_overload.PRIO_QUERY):
                self.overload.shed(_overload.PRIO_QUERY, "admission")
                if journey_key is not None:
                    _journey.hop("shed", "payment", journey_key,
                                 outcome="overload",
                                 reason="admission")
                raise self.overload.overloaded()
            self._queue.append(q)
            self._note_backlog()
            if self._flush_due is None:
                self._flush_due = self.now() + self.overload.window_s(
                    self.flush_ms)
                self._wakeup.set()
            if len(self._queue) >= self._flush_threshold():
                self._wakeup.set()
        return await q.future

    def _flush_threshold(self) -> int:
        return self.overload.flush_target(self.batch)

    def _stale(self, g, planes: McfPlanes) -> bool:
        """True when the graph moved since ``planes`` was snapshotted
        (map swapped, or a topology/params bump landed mid-dispatch)."""
        return (self.get_map() is not g
                or planes.topo_version
                != getattr(g, "topology_version", 0)
                or planes.params_version
                != getattr(g, "params_version", 0))

    def _note_backlog(self) -> None:
        _M_QUEUE.set(len(self._queue))
        self.overload.update(len(self._queue), self._inflight)

    # -- the flush loop ---------------------------------------------------

    async def _run(self) -> None:
        try:
            backoff = _deadline.RestartBackoff()
            while not self._closed:
                try:
                    await self._step()
                except asyncio.CancelledError:
                    raise
                except Exception as e:
                    delay = backoff.next()
                    _deadline.note_restart("mcf_flush", e, delay)
                    events.emit("mcf_flush_error",
                                {"error": repr(e),
                                 "restart_delay_s": round(delay, 3)})
                    await asyncio.sleep(delay)
                else:
                    backoff.reset()
            if self._queue:
                await self.flush()
        finally:
            # cancellation teardown: strand no queued caller
            batch, self._queue = self._queue, []
            for q in batch:
                if not q.future.done():
                    q.future.set_exception(
                        RuntimeError("mcf service stopped"))

    async def _step(self) -> None:
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
            log.exception("mcf flush failed")
            for q in batch:
                if not q.future.done():
                    _M_QUERIES.labels("host", "error").inc()
                    q.future.set_exception(
                        RuntimeError(f"mcf flush failed: {e}"))
        finally:
            dt = time.perf_counter() - t0
            _M_FLUSH_SECONDS.observe(dt)
            self._inflight = 0
            self.overload.note_drain(len(batch), dt)
            self._note_backlog()

    async def _flush_batch(self, batch: list[McfQuery]) -> None:
        corrs = trace.as_carriers(q.corr for q in batch)
        brk = _breaker.get("mcf")
        t_flush0 = self.now()
        if _journey.enabled():
            # batch-level queue-wait over EVERY query — the
            # reconciliation target for summed per-item journey waits
            # (doc/journeys.md)
            _journey.note_batch_wait(
                "mcf", sum(max(0.0, t_flush0 - q.t_enq)
                           for q in batch if q.t_enq))
        t_svc0 = time.perf_counter()
        with _flight.dispatch(
                "mcf", corr_ids=_flight.corr_ids(corrs),
                n_real=len(batch), lanes=len(batch),
                breaker_state=brk.state) as rec:
            with trace.span("mcf/flush", corr=corrs,
                            dispatch_id=rec["dispatch_id"],
                            queries=len(batch)):
                self._flush_ctx = (t_flush0, t_svc0, rec)
                try:
                    await self._flush_batch_inner(batch, brk, rec)
                finally:
                    self._flush_ctx = None
            if rec["outcome"] is None:
                rec["outcome"] = "host"

    async def _flush_batch_inner(self, batch: list[McfQuery], brk,
                                 rec: dict) -> None:
        _M_BATCH.observe(len(batch))
        g = self.get_map()
        host: list[tuple[McfQuery, str]] = []
        device: list[McfQuery] = []
        if g is None:
            for q in batch:
                self._resolve(q, "host",
                              ("mcferr", "no gossip graph loaded"))
            return
        if not self.device:
            host = [(q, R_DISABLED) for q in batch]
        elif len(batch) <= self.host_max:
            # a near-empty bucket costs a full device round-trip for a
            # few ms of numpy — mirror the route service's floor
            host = [(q, R_BELOW_OCCUPANCY) for q in batch]
        else:
            for q in batch:
                reason = _expressible(q)
                if reason is not None:
                    host.append((q, reason))
                else:
                    device.append(q)
        if device and not brk.allow():
            # mcf breaker open: the device share takes the host oracle
            # (bit-identical results).  allow() is consulted only once
            # a dispatch is certain — a half-open probe token is always
            # settled by record_success/record_failure below.
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
                _fault.fire("dispatch", "mcf")
                self._planes = McfPlanes.current(g, self._planes)
                planes = self._planes
                results: list = [None] * len(device)
                # lane prep + the jit dispatch run in the worker (the
                # planes' dir lanes are COPIES a live channel_update
                # cannot tear); judging — flow decomposition + fee
                # accounting, which read the live gossmap — runs back
                # ON the loop between rounds; deadline guards each
                # dispatch round (LIGHTNING_TPU_DEADLINE_MCF_S)
                with trace.span("mcf/dispatch"):
                    rb = await _deadline.guard(
                        asyncio.to_thread(
                            _solve_indices, planes, device,
                            list(range(len(device))), self.batch, 1.0,
                            results, io_acct),
                        family="mcf", seam="dispatch")
                # judging prices hops off the LIVE gossmap arrays; a
                # channel_update applied during the dispatch would mix
                # the snapshot's flow with the new revision's fees — an
                # answer matching NEITHER revision's host solve.  Stale
                # readbacks divert to the oracle instead.
                if self._stale(g, planes):
                    for j, *_ in rb:
                        results[j] = ("fallback", R_STALE_PLANES)
                    retry = []
                else:
                    retry = _judge_round(planes, device, rb, results,
                                         final_attempt=False)
                if retry:
                    with trace.span("mcf/dispatch"):
                        rb2 = await _deadline.guard(
                            asyncio.to_thread(
                                _solve_indices, planes, device, retry,
                                self.batch, 1.0 / 100.0, results,
                                io_acct),
                            family="mcf", seam="dispatch")
                    if self._stale(g, planes):
                        for j, *_ in rb2:
                            results[j] = ("fallback", R_STALE_PLANES)
                    else:
                        _judge_round(planes, device, rb2, results,
                                     final_attempt=True)
                _M_OCCUPANCY.observe(len(device) / lanes)
                brk.record_success()
                rec["outcome"] = "ok"
                rec["h2d_bytes"] = io_acct.get("h2d_bytes", 0)
                rec["d2h_bytes"] = io_acct.get("d2h_bytes", 0)
            except _deadline.DeadlineExceeded:
                brk.record_failure()
                rec["outcome"] = "deadline"
                log.warning("device mcf dispatch blew its deadline; "
                            "batch re-solves on the host oracle")
                host.extend((q, R_DEADLINE) for q in device)
                results, device = [], []
            except Exception as e:
                brk.record_failure()
                # every diverted query is re-solved host-side below —
                # the quarantine posture: never silently failed
                _quarantine.note("mcf", "dispatch", rows=len(device))
                rec["outcome"] = "host"
                rec["error"] = type(e).__name__
                log.exception("device mcf dispatch failed; "
                              "falling back to the host oracle")
                host.extend((q, R_DEVICE_ERROR) for q in device)
                results, device = [], []
            for q, res in zip(device, results):
                if res[0] == "fallback":
                    host.append((q, res[1]))
                else:
                    self._resolve(q, "device", res)
        if host:
            for _, reason in host:
                _M_FALLBACK.labels(reason).inc()
            # ON the event loop, deliberately: the host oracle reads
            # the live gossmap arrays, which accepted channel_updates
            # mutate from the loop — a worker thread would race a torn
            # graph (the RouteService host-path contract)
            for q, _ in host:
                self._resolve(q, "host", self._host_solve(g, q))
                await asyncio.sleep(0)

    @staticmethod
    def _host_solve(g, q: McfQuery) -> tuple:
        try:
            res = MCF.getroutes(
                g, q.source, q.destination, q.amount_msat,
                layers=q.layers, maxfee_msat=q.maxfee_msat,
                final_cltv=q.final_cltv, max_parts=q.max_parts,
                prob_weight=q.prob_weight,
                delay_weight=q.delay_weight)
            return ("ok", res)
        except MCF.McfError as e:
            return ("mcferr", str(e))
        except Exception as e:
            return ("error", e)

    def _resolve(self, q: McfQuery, path: str, res: tuple) -> None:
        fut = q.future
        if fut is None or fut.done():
            return
        if q.journey_key is not None:
            ctx = self._flush_ctx
            if ctx is not None:
                # the batched-solve hop, stamped BEFORE the parts hop
                # so the journey reads in pipeline order (enqueue →
                # mcf_flush → parts); wait/service split per
                # doc/journeys.md §semantics
                t_flush0, t_svc0, rec = ctx
                _journey.hop(
                    "mcf_flush", "payment", q.journey_key,
                    outcome=path,
                    wait_s=max(0.0, t_flush0 - q.t_enq)
                    if q.t_enq else 0.0,
                    service_s=time.perf_counter() - t_svc0,
                    dispatch_id=rec["dispatch_id"],
                    corr_id=q.corr.corr_id if q.corr else None)
            _journey.hop(
                "parts", "payment", q.journey_key, outcome=res[0],
                corr_id=q.corr.corr_id if q.corr else None,
                path=path,
                **({"parts": res[1]["parts"]}
                   if res[0] == "ok" else {}))
        if res[0] == "ok":
            _M_QUERIES.labels(path, "ok").inc()
            _M_PARTS.observe(res[1]["parts"])
            fut.set_result(res[1])
        elif res[0] == "mcferr":
            _M_QUERIES.labels(path, "noroute").inc()
            fut.set_exception(MCF.McfError(res[1]))
        else:
            _M_QUERIES.labels(path, "error").inc()
            err = res[1]
            fut.set_exception(err if isinstance(err, BaseException)
                              else RuntimeError(str(err)))
