"""The route program as it stood before PR 26, frozen: one query's
backward Bellman-Ford sweep with two ``segment_min`` scatters by
``edge_src``, vmapped over the lanes.  tests/test_zz_route_parity.py
holds the dense program of routing/device.py equal to this one label
for label; nothing in the package imports it.

Operands are the seven edge planes in ``RoutePlanes`` order (length
``e_pad``), the ``[lanes, e_pad]`` edge mask in the same order and the
six per-lane vectors; it returns ``(dist[src], via, ovf)`` with
``via`` ``[lanes, n_nodes]`` holding ``RoutePlanes`` edge indices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from lightning_tpu.routing.device import INF_COST, OVF_LIMIT, _RISK_DENOM


def _make_single(n_nodes: int, max_hops: int):
    def single(edge_src, edge_dst, base, ppm, cd, hmin, hmax,
               edge_ok, src, dst, amount, final_cltv, riskfactor):
        E = edge_src.shape[0]
        at_dst = jnp.arange(n_nodes, dtype=jnp.int32) == dst
        dist0 = jnp.where(at_dst, jnp.int64(0), jnp.int64(INF_COST))
        assert dist0.dtype == jnp.int64, "trace under enable_x64()"
        amt0 = jnp.where(at_dst, amount, jnp.int64(0))
        dly0 = jnp.where(at_dst, final_cltv, jnp.int64(0))
        via0 = jnp.full((n_nodes,), -1, jnp.int32)
        eidx = jnp.arange(E, dtype=jnp.int64)
        cdr = cd * riskfactor
        thr = jnp.minimum(OVF_LIMIT // jnp.maximum(ppm, 1),
                          OVF_LIMIT // jnp.maximum(cdr, 1))

        def sweep(carry, _):
            dist, amt, dly, via, ovf = carry
            d_v = dist[edge_dst]
            a_v = amt[edge_dst]
            ok = edge_ok & (d_v < INF_COST)
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

        init = (dist0, amt0, dly0, via0, jnp.asarray(False))
        (dist, amt, dly, via, ovf), _ = jax.lax.scan(
            sweep, init, None, length=max_hops)
        return dist[src], via, ovf

    return single


@functools.lru_cache(maxsize=8)
def jit_scatter_route(n_nodes: int, max_hops: int):
    single = _make_single(n_nodes, max_hops)
    return jax.jit(jax.vmap(single, in_axes=(None,) * 7 + (0,) * 6))
