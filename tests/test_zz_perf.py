"""Perf-observatory wiring tests (doc/perf.md): the live dispatch
paths must feed the attribution model — transfer bytes on verify
flight records, the retrace detector armed by real warmups and fired
by a real forced post-warmup compile, and the getperf RPC surface.

Named test_zz_* to sort LAST: this file imports the jax-backed verify
and routing modules (the pure-model corpus lives in the jax-free
test_attribution.py, early in the alphabet)."""
from __future__ import annotations

import asyncio
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from lightning_tpu import obs
from lightning_tpu.gossip import verify
from lightning_tpu.obs import attribution, flight
from lightning_tpu.utils import events


@pytest.fixture(autouse=True)
def _clean():
    attribution.reset_for_tests()
    flight.reset_for_tests()
    events.reset()
    yield
    attribution.reset_for_tests()
    flight.reset_for_tests()
    events.reset()


def _counter(snap: dict, name: str, **labels) -> float:
    fam = snap["metrics"].get(name, {"samples": []})
    return sum(s["value"] for s in fam["samples"]
               if all(s["labels"].get(k) == v
                      for k, v in labels.items()))


def _synthetic_items(n_rows: int) -> verify.VerifyItems:
    rng = np.random.default_rng(7)
    rows = rng.integers(0, 256, (n_rows, verify.MAX_BLOCKS * 64),
                        dtype=np.uint16).astype(np.uint8)
    nb = np.full(n_rows, 3, np.uint32)
    sigs = np.zeros((n_rows, 64), np.uint8)
    pubs = np.zeros((n_rows, 33), np.uint8)
    pubs[:, 0] = 2
    return verify.VerifyItems(rows, nb, sigs, pubs,
                              np.arange(n_rows, dtype=np.int64))


def _stub_device(pb):
    return np.ones(pb.blocks.shape[0], bool)


# ---------------------------------------------------------------------------
# transfer accounting on the replay path


def test_verify_flight_records_carry_transfer_bytes():
    items = _synthetic_items(64)
    s0 = obs.snapshot()
    ok = verify.verify_items(items, bucket=16, depth=0,
                             device_fn=_stub_device)
    s1 = obs.snapshot()
    assert ok.all()
    recs = flight.recent("verify")
    assert len(recs) == 4  # 64 rows / 16-lane buckets
    for rec in recs:
        # h2d = the bucket's staged operand bytes; d2h = the boolean
        # readback plane (one byte per lane)
        assert rec["h2d_bytes"] > 0
        assert rec["d2h_bytes"] == 16
        assert rec["outcome"] == "ok"
    h2d = _counter(s1, "clntpu_transfer_bytes_total",
                   family="verify", direction="h2d") - \
        _counter(s0, "clntpu_transfer_bytes_total",
                 family="verify", direction="h2d")
    d2h = _counter(s1, "clntpu_transfer_bytes_total",
                   family="verify", direction="d2h") - \
        _counter(s0, "clntpu_transfer_bytes_total",
                 family="verify", direction="d2h")
    assert h2d == sum(r["h2d_bytes"] for r in recs)
    assert d2h == sum(r["d2h_bytes"] for r in recs)
    # the attribution report sees a ring-complete verify family whose
    # transfer tallies match the counters
    rep = attribution.report_local()
    fam = rep["families"]["verify"]
    assert fam["transfer"]["h2d_bytes"] == h2d
    assert fam["transfer"]["d2h_bytes"] == d2h
    assert fam["reconciliation"]["checked"]
    if _counter(s0, "clntpu_replay_prep_seconds_total") == 0:
        # pristine process: counters and ring cover the SAME replay,
        # so the reconciliation contract must hold exactly.  (Earlier
        # test files may have bumped the process-global counters while
        # the autouse fixture reset the ring — then only `checked` is
        # meaningful here; the exact case is pinned by the selfcheck.)
        assert fam["reconciliation"]["ok"], fam["reconciliation"]


def test_host_breaker_bucket_stages_no_transfer():
    from lightning_tpu import resilience

    resilience.reset_for_tests()
    try:
        from lightning_tpu.resilience import breaker as _breaker

        brk = _breaker.get("verify")
        for _ in range(64):
            brk.record_failure()
        assert not _breaker.get("verify").allow()
        items = _synthetic_items(16)
        verify.verify_items(items, bucket=16, depth=0,
                            device_fn=_stub_device)
        recs = flight.recent("verify")
        assert recs and recs[-1]["outcome"] == "host_breaker"
        # no device dispatch happened: nothing crossed the bus
        assert recs[-1]["h2d_bytes"] == 0
        assert recs[-1]["d2h_bytes"] == 0
    finally:
        resilience.reset_for_tests()


# ---------------------------------------------------------------------------
# the retrace detector on the real seams


def test_note_shape_seam_fires_retrace_after_warmup():
    """Every verify-side compile first-sight passes _note_shape; a
    forced post-warmup sighting must fire the counter AND the topic."""
    got = []
    events.subscribe("retrace", got.append)
    with attribution.warmup_scope():
        verify._note_shape("fused", (8, 4))       # the warmup sighting
    s0 = obs.snapshot()
    verify._note_shape("fused", (8, 4))           # seen: silent
    verify._note_shape("fused", (8, 999))         # forced: anomaly
    s1 = obs.snapshot()
    assert _counter(s1, "clntpu_retrace_total", program="fused") - \
        _counter(s0, "clntpu_retrace_total", program="fused") == 1
    assert len(got) == 1 and got[0]["key"] == [8, 999]


@pytest.mark.parametrize("differs", ["shape", "steps"])
def test_forced_post_warmup_route_compile_fires_retrace(tmp_path, differs):
    """The real thing: a route warmup arms the detector, then a solve
    over planes of a DIFFERENT padded shape — or of the warmed shape
    with another step count, as after a graph change that carries the
    largest out-degree across a power of two — pays an actual XLA
    compile: exactly the anomaly the detector exists for."""
    from lightning_tpu.gossip import gossmap as GM
    from lightning_tpu.gossip import store as gstore
    from lightning_tpu.gossip import synth
    from lightning_tpu.routing import device as RD
    from lightning_tpu.routing.planes import RoutePlanes

    path = str(tmp_path / "zzperf.gs")
    synth.make_network_store(path, n_channels=40, n_nodes=12,
                             updates_per_channel=1, sign=False)
    g = GM.from_store(gstore.load_store(path))
    planes = RoutePlanes.build(g)

    # warm a DIFFERENT program: the route program compiles in about a
    # second on CPU, so this is a real-compile test
    steps = RD.edge_order(planes).steps
    if differs == "shape":
        RD.warmup(4, planes.n_pad // 2, 32)
    else:
        RD.warmup(8, planes.n_pad, planes.e_pad, steps=steps + 1)
    assert attribution.retrace_state()["armed"]

    got = []
    events.subscribe("retrace", got.append)
    ids = [bytes(g.node_ids[i]) for i in range(g.n_nodes)]
    queries = [RD.RouteQuery(ids[i], ids[(i + 3) % len(ids)], 1000 + i)
               for i in range(8)]
    s0 = obs.snapshot()
    RD.solve_batch(planes, queries, batch=8)
    s1 = obs.snapshot()
    assert _counter(s1, "clntpu_retrace_total", program="route") - \
        _counter(s0, "clntpu_retrace_total", program="route") == 1
    assert got and got[0]["program"] == "route"
    # the key carries EVERY static operand shape: node pad, edge pad,
    # batch width, sweep budget (an e_pad-only change re-traces too),
    # and the segmented minimum's step count
    assert got[0]["key"] == [planes.n_pad, planes.e_pad, 8,
                             RD.DEFAULT_MAX_HOPS, steps]
    # route transfer accounting rode the same dispatch
    assert _counter(s1, "clntpu_transfer_bytes_total",
                    family="route", direction="h2d") > \
        _counter(s0, "clntpu_transfer_bytes_total",
                 family="route", direction="h2d")
    # a second solve at the now-seen shape stays silent
    RD.solve_batch(planes, queries, batch=8)
    assert len(got) == 1


# ---------------------------------------------------------------------------
# the sign path


def test_micro_sign_batch_stays_host_with_no_transfer():
    from lightning_tpu.crypto import secp256k1 as S
    from lightning_tpu.daemon import hsmd

    n = min(2, S.HOST_VERIFY_MAX)
    hashes = np.zeros((n, 32), np.uint8)
    hashes[:, -1] = 1
    out = hsmd._sign_batch_resilient("htlc", hashes, [5] * n)
    assert out.shape == (n, 64)
    recs = flight.recent("sign")
    assert recs and recs[-1]["outcome"] == "host"
    assert recs[-1]["h2d_bytes"] == 0 and recs[-1]["d2h_bytes"] == 0


# ---------------------------------------------------------------------------
# the RPC surface


class _FakeRpc:
    def __init__(self):
        self.methods = {}

    def register(self, name, fn, deprecated=False):
        self.methods[name] = fn


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, 60))


def test_getperf_rpc_handler():
    from lightning_tpu.daemon.jsonrpc import (RpcError,
                                              attach_admin_commands)
    from lightning_tpu.utils.config import Config
    from lightning_tpu.utils.logring import LogRing

    rpc = _FakeRpc()
    attach_admin_commands(rpc, Config(), LogRing())
    for _ in range(2):
        rec = flight.begin("route", n_real=4, lanes=8, prep_ms=1.0)
        flight.finish(rec, "ok", dispatch_ms=2.0)
    rep = _run(rpc.methods["getperf"]())
    assert "route" in rep["families"]
    assert rep["families"]["route"]["dispatches"] == 2
    assert rep["epsilon"] == attribution.EPSILON
    assert "retraces" in rep and "device_memory" in rep
    # family filter + kernel-rate roofline plumbing
    rep2 = _run(rpc.methods["getperf"](family="route",
                                       kernel_rate=1000))
    assert list(rep2["families"]) == ["route"]
    assert rep2["kernel_rate"] == 1000.0
    with pytest.raises(RpcError):
        _run(rpc.methods["getperf"](family="bogus"))
    with pytest.raises(RpcError):
        _run(rpc.methods["getperf"](kernel_rate="not-a-number"))
    with pytest.raises(RpcError):
        _run(rpc.methods["getperf"](kernel_rate=-1))
    # getmetrics carries the same report as its `perf` section
    snap = _run(rpc.methods["getmetrics"]())
    assert "perf" in snap and "route" in snap["perf"]["families"]
