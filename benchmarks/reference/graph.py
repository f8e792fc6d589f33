"""The reference's routing graph, built from the store file alone.

Same semantics as the program's gossmap (upstream `common/gossmap.c`):
nodes are the distinct keys of the channel announcements, sorted;
channels are sorted by short_channel_id; each direction holds the
newest channel_update for it; an edge exists in a direction that has
an update.  Direction d runs from node_{d+1}.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import storefile


def parse_scid(s) -> int:
    """`BLOCKxTXxOUT`, or the integer itself."""
    if isinstance(s, int):
        return s
    b, t, o = s.split("x")
    return (int(b) << 40) | (int(t) << 16) | int(o)


@dataclass
class Graph:
    node_ids: list            # sorted 33-byte keys
    scids: np.ndarray         # (C,) uint64 sorted
    node1: np.ndarray         # (C,) index into node_ids
    node2: np.ndarray
    has_update: np.ndarray    # (2, C) bool
    enabled: np.ndarray       # (2, C) bool
    cltv_delta: np.ndarray    # (2, C)
    htlc_min_msat: np.ndarray
    htlc_max_msat: np.ndarray
    fee_base_msat: np.ndarray
    fee_ppm: np.ndarray
    into: list                # into[v] = [(u, chan, dir)] edges u -> v

    @property
    def n_nodes(self) -> int:
        return len(self.node_ids)

    def node_index(self, node_id: bytes) -> int:
        return self._node_pos[node_id]

    def channel_index(self, scid: int) -> int:
        return self._chan_pos[scid]

    def ends(self, chan: int, direction: int) -> tuple[int, int]:
        """(from node, to node) of a channel in a direction."""
        a, b = int(self.node1[chan]), int(self.node2[chan])
        return (a, b) if direction == 0 else (b, a)


def from_store(path: str) -> Graph:
    msgs = storefile.read_alive(path)
    chans = {}
    for m in msgs["ca"]:                   # later records win
        scid, keys = storefile.ca_fields(m)
        chans[scid] = (keys[0], keys[1])
    scids = sorted(chans)
    node_ids = sorted({k for pair in chans.values() for k in pair})
    node_pos = {k: i for i, k in enumerate(node_ids)}
    chan_pos = {s: i for i, s in enumerate(scids)}
    c = len(scids)
    node1 = np.array([node_pos[chans[s][0]] for s in scids], np.int64)
    node2 = np.array([node_pos[chans[s][1]] for s in scids], np.int64)
    ts = np.zeros((2, c), np.int64)
    enabled = np.zeros((2, c), bool)
    cltv = np.zeros((2, c), np.int64)
    hmin = np.zeros((2, c), np.uint64)
    hmax = np.zeros((2, c), np.uint64)
    base = np.zeros((2, c), np.int64)
    ppm = np.zeros((2, c), np.int64)
    for m in msgs["cu"]:
        scid = int.from_bytes(m[98:106], "big")
        ci = chan_pos.get(scid)
        if ci is None:
            continue
        t = int.from_bytes(m[106:110], "big")
        mflags, cflags = m[110], m[111]
        d = cflags & 1
        if t < ts[d, ci]:                  # the newest update wins
            continue
        ts[d, ci] = t
        enabled[d, ci] = not cflags & 2
        cltv[d, ci] = int.from_bytes(m[112:114], "big")
        hmin[d, ci] = int.from_bytes(m[114:122], "big")
        base[d, ci] = int.from_bytes(m[122:126], "big")
        ppm[d, ci] = int.from_bytes(m[126:130], "big")
        hmax[d, ci] = (int.from_bytes(m[130:138], "big")
                       if mflags & 1 and len(m) >= 138 else 0)
    has = ts > 0
    into = [[] for _ in node_ids]
    for d in (0, 1):
        for ci in np.nonzero(has[d])[0]:
            u = int(node1[ci] if d == 0 else node2[ci])
            v = int(node2[ci] if d == 0 else node1[ci])
            into[v].append((u, int(ci), d))
    g = Graph(node_ids, np.array(scids, np.uint64), node1, node2, has,
              enabled, cltv, hmin, hmax, base, ppm, into)
    g._node_pos, g._chan_pos = node_pos, chan_pos
    return g


def largest_component(g: Graph) -> list[int]:
    """Nodes of the largest component over enabled edges, either way."""
    parent = list(range(g.n_nodes))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for d in (0, 1):
        for ci in np.nonzero(g.has_update[d] & g.enabled[d])[0]:
            a, b = find(int(g.node1[ci])), find(int(g.node2[ci]))
            if a != b:
                parent[a] = b
    roots = [find(i) for i in range(g.n_nodes)]
    best = max(set(roots), key=roots.count)
    return [i for i, r in enumerate(roots) if r == best]
