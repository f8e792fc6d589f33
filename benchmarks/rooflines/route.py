"""The least time the chip could take for one batch of cheapest-path
queries: what any solver must touch, from shapes alone.

Bytes: every directed edge's parameters read once (source, destination,
base fee, ppm, cltv delta, htlc minimum and maximum, enabled: 4 + 4 + 4
+ 4 + 4 + 8 + 8 + 1 = 37 bytes), and one label per node per query read
and written once (cost 8, amount 8, delay 4, next hop 4 = 24 bytes,
twice).  Operations: one relaxation per edge per query, its two 64-bit
products (fee, risk) as 8x8 multiply-accumulates of 8-bit limbs, 128
integer operations each, against the int8 peak.  A solver that sweeps
the edges twenty times does twenty times this; that is its own affair.
"""
from __future__ import annotations

EDGE_BYTES = 37
LABEL_BYTES = 2 * 24
OPS_PER_RELAX = 2 * 2 * 8 * 8


def work(edges: float, nodes: float, queries: float) -> tuple[float, float]:
    """(integer operations, bytes moved) of one batch."""
    return (edges * queries * OPS_PER_RELAX,
            edges * EDGE_BYTES + nodes * queries * LABEL_BYTES)


def least_seconds(edges: float, nodes: float, queries: float,
                  peaks: dict) -> tuple[float, str]:
    ops, nbytes = work(edges, nodes, queries)
    t_ops = ops / peaks["int8_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "int8_ops_per_s") if t_ops >= t_mem \
        else (t_mem, "hbm_bytes_per_s")
