"""Boot-time crash recovery (daemon/recovery.py; doc/recovery.md):
marker semantics, incident discovery, the host signature oracle, the db
reconciliation sweep, and the hook-replica ahead-by-one fix.
"""
import json
import os

import pytest

import test_ingest as TI
from lightning_tpu.daemon import recovery as R
from lightning_tpu.gossip import store as gstore
from lightning_tpu.gossip import wire
from lightning_tpu.resilience import faultinject as fault
from lightning_tpu.wallet.db import Db, FileReplica, reconcile_file_replica

K1, K2 = TI.K1, TI.K2
SCID = TI.SCID


# -- clean-shutdown marker --------------------------------------------------

def test_marker_lifecycle(tmp_path):
    d = str(tmp_path)
    assert R.read_marker(d) == "first_boot"
    R.mark_running(d)
    assert R.read_marker(d) == "crash"       # still "running" = unclean
    R.mark_clean(d)
    assert R.read_marker(d) == "clean"
    with open(R.marker_path(d), "w") as f:
        f.write("???")                        # only a crash leaves junk
    assert R.read_marker(d) == "crash"


# -- incident discovery -----------------------------------------------------

def test_discover_incidents(tmp_path, monkeypatch):
    monkeypatch.delenv("LIGHTNING_TPU_INCIDENT_DIR", raising=False)
    d = str(tmp_path)
    assert R.discover_incidents(d) == []      # no incidents dir yet

    inc = tmp_path / "incidents"
    for name, trig in (("inc-200-1", "crash"), ("inc-100-1", "breaker"),
                       ("inc-200-2", "deadline")):
        b = inc / name
        b.mkdir(parents=True)
        (b / "manifest.json").write_text(json.dumps(
            {"trigger": {"class": trig}, "captured_at": 1.0}))
    (inc / "not-a-bundle").mkdir()            # ignored
    (inc / "inc-300-1").mkdir()               # manifest missing

    found = R.discover_incidents(d)
    assert [i["id"] for i in found] == [      # (epoch, seq) order
        "inc-100-1", "inc-200-1", "inc-200-2", "inc-300-1"]
    assert [i["trigger"] for i in found] == [
        "breaker", "crash", "deadline", "unreadable"]


# -- host signature oracle --------------------------------------------------

def test_host_sig_checker_valid_and_corrupt():
    chk = R.host_sig_checker()
    ca = TI.make_ca(K1, K2, SCID)
    cu = TI.make_cu(K1, K2, SCID, 0, ts=50)
    na = TI.make_na(K1, ts=50)
    assert chk([ca, cu, na]) == [True, True, True]

    bad_ca = bytearray(ca)
    bad_ca[wire.CA_SIG_OFFSETS[0] + 3] ^= 0xFF
    bad_na = bytearray(na)
    bad_na[wire.NA_SIG_OFFSET + 3] ^= 0xFF
    assert chk([bytes(bad_ca), cu, bytes(bad_na)]) == [
        False, True, False]
    assert chk([b"\x00\x01garbage"]) == [False]


def test_host_sig_checker_cu_without_ca_fails_closed():
    chk = R.host_sig_checker()
    cu = TI.make_cu(K1, K2, SCID, 1, ts=60)
    # a channel_update's key lives in its channel_announcement; with
    # the CA absent from the checked batch it cannot be requalified
    assert chk([cu]) == [False]
    assert chk([TI.make_ca(K1, K2, SCID), cu]) == [True, True]


# -- retransmission-journal structural walk ---------------------------------

def test_retransmit_valid():
    frame = (5).to_bytes(4, "big") + b"hello"
    assert R._retransmit_valid(b"")                        # empty = fine
    assert R._retransmit_valid(bytes([1]) + frame)
    assert R._retransmit_valid(bytes([0]) + frame + frame)
    assert not R._retransmit_valid(bytes([7]) + frame)     # bad sealed
    assert not R._retransmit_valid(bytes([1]) + frame[:-2])  # short body
    assert not R._retransmit_valid(bytes([0]) + b"\x00\x00")  # torn len


# -- db reconciliation sweep ------------------------------------------------

def _insert_channel(db, state: str, retransmit: bytes = b"",
                    inflight: bytes = b"") -> int:
    with db.transaction() as c:
        cur = c.execute(
            "INSERT INTO channels (peer_node_id, hsm_dbid, funder,"
            " channel_id, funding_txid, funding_outidx, funding_sat,"
            " state, to_local_msat, to_remote_msat, feerate_per_kw,"
            " opener_is_local, anchors, reserve_local_msat,"
            " reserve_remote_msat, next_local_commit, next_remote_commit,"
            " delay_on_local, delay_on_remote, their_dust_limit,"
            " their_funding_pub, their_basepoints, their_points,"
            " their_last_secret, retransmit, inflight)"
            " VALUES (x'02', 1, 1, x'aa', x'bb', 0, 100000, ?,"
            " 0, 0, 253, 1, 1, 0, 0, 1, 1, 144, 144, 546,"
            " x'', x'', x'', x'', ?, ?)",
            (state, retransmit, inflight))
        return cur.lastrowid


def test_reconcile_db_sweep(tmp_path):
    db = Db(str(tmp_path / "w.sqlite3"))
    good = bytes([1]) + (2).to_bytes(4, "big") + b"ok"
    with db.transaction() as c:
        c.execute("INSERT INTO payments (payment_hash, amount_msat,"
                  " amount_sent_msat, status, created_at) VALUES"
                  " (x'01', 5, 5, 'pending', 10)")
        c.execute("INSERT INTO payments (payment_hash, amount_msat,"
                  " amount_sent_msat, status, preimage, created_at,"
                  " completed_at) VALUES (x'02', 5, 5, 'complete',"
                  " x'03', 10, 11)")
    keep_live = _insert_channel(db, "CHANNELD_NORMAL", retransmit=good,
                                inflight=b'{"funding_sat": 5}')
    dead = _insert_channel(db, "closed", retransmit=good,
                           inflight=b'{"funding_sat": 5}')
    corrupt = _insert_channel(db, "CHANNELD_NORMAL",
                              retransmit=good[:-1], inflight=b"{torn")

    fixups = R.reconcile_db(db, now=42)
    assert fixups == {"payments_failed": 1, "retransmit_reset": 2,
                      "inflight_reset": 2}

    status, completed_at, failure = db.conn.execute(
        "SELECT status, completed_at, failure FROM payments"
        " WHERE payment_hash=x'01'").fetchone()
    assert status == "failed" and completed_at == 42
    assert "safe to retry" in failure
    assert db.conn.execute("SELECT status FROM payments WHERE"
                           " payment_hash=x'02'").fetchone()[0] == \
        "complete"                            # untouched

    rows = {cid: (r, i) for cid, r, i in db.conn.execute(
        "SELECT id, retransmit, inflight FROM channels")}
    assert rows[keep_live] == (good, b'{"funding_sat": 5}')
    assert rows[dead] == (b"", b"")           # dead state: both reset
    assert rows[corrupt] == (b"", b"")        # structurally invalid

    # idempotent: nothing left to fix on the next boot
    assert R.reconcile_db(db, now=43) == {
        "payments_failed": 0, "retransmit_reset": 0, "inflight_reset": 0}
    db.close()


# -- the hook replica: ahead-by-one window ----------------------------------

def test_file_replica_journal_and_torn_tail(tmp_path):
    rp = str(tmp_path / "rep.jsonl")
    rep = FileReplica(rp)
    rep(1, [("INSERT INTO x VALUES (1)", None)])
    rep(2, [("UPDATE x SET a=2", None)])
    assert [r["v"] for r in rep.records()] == [1, 2]
    assert rep.last_version() == 2

    with open(rp, "ab") as f:                 # crash mid-journal-append
        f.write(b'{"v": 3, "wri')
    assert rep.last_version() == 2            # torn line never acked

    rep.drop_last()
    assert rep.last_version() == 1
    # drop_last rewrote write-then-rename: the torn tail is gone too
    assert open(rp, "rb").read().count(b"\n") == 1
    rep.close()


def test_reconcile_replica_verdicts(tmp_path):
    db = Db(str(tmp_path / "w.sqlite3"))
    rp = str(tmp_path / "rep.jsonl")
    rep = FileReplica(rp)
    assert db.reconcile_replica(rep.last_version()) == "empty"

    db.set_db_write_hook(rep)
    with db.transaction() as c:
        c.execute("INSERT INTO payments (payment_hash, amount_msat,"
                  " amount_sent_msat, status, created_at) VALUES"
                  " (x'01', 1, 1, 'complete', 1)")
    assert db.reconcile_replica(rep.last_version()) == "in_sync"
    assert reconcile_file_replica(db, rep) == "in_sync"

    assert db.reconcile_replica(rep.last_version() + 1) == "ahead_by_one"
    assert db.reconcile_replica(rep.last_version() + 2) == "diverged"
    assert db.reconcile_replica(rep.last_version() - 1) == "behind"
    rep.close()
    db.close()


def test_ahead_by_one_resolved_on_boot(tmp_path):
    """The documented crash window, end to end: the hook streams a
    transaction, the commit dies (injected at the commit seam), and the
    boot reconciliation drops the replica's unacknowledged tail."""
    db = Db(str(tmp_path / "w.sqlite3"))
    rep = FileReplica(str(tmp_path / "rep.jsonl"))
    db.set_db_write_hook(rep)
    with db.transaction() as c:
        c.execute("INSERT INTO payments (payment_hash, amount_msat,"
                  " amount_sent_msat, status, created_at) VALUES"
                  " (x'01', 1, 1, 'complete', 1)")
    v_durable = db._data_version

    with fault.arm("commit:db:raise:1"):
        with pytest.raises(fault.FaultInjected):
            with db.transaction() as c:
                c.execute("INSERT INTO payments (payment_hash,"
                          " amount_msat, amount_sent_msat, status,"
                          " created_at) VALUES (x'02', 2, 2,"
                          " 'complete', 2)")

    # the primary rolled back (version counter included); the replica
    # journalled the dead transaction — ahead by exactly one
    assert db._data_version == v_durable
    assert rep.last_version() == v_durable + 1
    assert db.conn.execute("SELECT COUNT(*) FROM payments").fetchone()[0] == 1

    assert reconcile_file_replica(db, rep) == "dropped_ahead"
    assert rep.last_version() == v_durable
    assert reconcile_file_replica(db, rep) == "in_sync"

    # and the replica keeps working after its reopen
    with db.transaction() as c:
        c.execute("INSERT INTO payments (payment_hash, amount_msat,"
                  " amount_sent_msat, status, created_at) VALUES"
                  " (x'03', 3, 3, 'complete', 3)")
    assert rep.last_version() == db._data_version
    rep.close()
    db.close()


# -- boot_recover -----------------------------------------------------------

def _signed_store(path: str) -> int:
    msgs = [TI.make_ca(K1, K2, SCID), TI.make_cu(K1, K2, SCID, 0, 100),
            TI.make_na(K1, 100)]
    with gstore.StoreWriter(path) as w:
        w.append_many(msgs, [0, 100, 100], sync=True)
    return len(msgs)


def test_boot_recover_states(tmp_path, monkeypatch):
    monkeypatch.delenv("LIGHTNING_TPU_INCIDENT_DIR", raising=False)
    d = str(tmp_path)
    store = os.path.join(d, "gossip_store")
    n = _signed_store(store)

    rep = R.boot_recover(d, store_path=store, verify=False)
    assert rep["state"] == "first_boot" and not rep["skipped"]
    assert rep["store"]["records"] == n
    assert len(rep["_store_idx"]) == n
    # the marker now says "running" — i.e. a re-read classifies as
    # crash until mark_clean runs at orderly shutdown
    assert open(R.marker_path(d)).read().strip() == "running"

    R.mark_clean(d)
    rep = R.boot_recover(d, store_path=store, verify=False)
    assert rep["state"] == "clean"
    assert rep["store"]["crc_bad"] == 0       # no crc pass on clean boots

    # unclean: marker still says running
    rep = R.boot_recover(d, store_path=store, verify=False)
    assert rep["state"] == "crash"
    assert rep["db_fixups"] is None           # no db handed in
    assert rep["store"]["records"] == n


def test_boot_recover_crash_full(tmp_path, monkeypatch):
    """Crash boot with every subsystem handed in, verify replay routed
    through the LIGHTNING_TPU_VERIFY_DEVICE=off host dispatcher (no
    device programs — the path tools/crashmatrix.py children run)."""
    monkeypatch.delenv("LIGHTNING_TPU_INCIDENT_DIR", raising=False)
    monkeypatch.setenv("LIGHTNING_TPU_VERIFY_DEVICE", "off")
    d = str(tmp_path)
    store = os.path.join(d, "gossip_store")
    _signed_store(store)
    # a torn tail AND a phantom pending payment, like a real kill
    with open(store, "ab") as f:
        f.write(b"\x00\x00\x00\x30torn")
    db = Db(os.path.join(d, "w.sqlite3"))
    rep_file = FileReplica(os.path.join(d, "rep.jsonl"))
    with db.transaction() as c:
        c.execute("INSERT INTO payments (payment_hash, amount_msat,"
                  " amount_sent_msat, status, created_at) VALUES"
                  " (x'01', 1, 1, 'pending', 1)")
    b = tmp_path / "incidents" / "inc-1-1"
    b.mkdir(parents=True)
    (b / "manifest.json").write_text(json.dumps(
        {"trigger": {"class": "crash"}, "captured_at": 1.0}))
    R.mark_running(d)

    rep = R.boot_recover(d, store_path=store, db=db, replica=rep_file)
    assert rep["state"] == "crash"
    assert [i["trigger"] for i in rep["incidents"]] == ["crash"]
    assert rep["store"]["truncated_bytes"] > 0
    assert rep["store"]["crc_bad"] == 0
    assert rep["verify"] == {"records": 3, "sigs": 6, "invalid": 0}
    assert rep["db_fixups"]["payments_failed"] == 1
    assert rep["replica"] == "empty"
    assert db.conn.execute("SELECT COUNT(*) FROM payments WHERE"
                           " status='pending'").fetchone()[0] == 0
    assert open(R.marker_path(d)).read().strip() == "running"
    rep_file.close()
    db.close()


def test_boot_recover_disable_knob(tmp_path, monkeypatch):
    monkeypatch.setenv("LIGHTNING_TPU_RECOVERY_DISABLE", "1")
    d = str(tmp_path)
    R.mark_running(d)
    rep = R.boot_recover(d, store_path=os.path.join(d, "gs"))
    assert rep["skipped"] and rep["store"] is None
    assert open(R.marker_path(d)).read().strip() == "running"


# -- crash action grammar ---------------------------------------------------

def test_crash_action_parse_and_armed():
    (spec,) = fault.parse("append:store:crash:1")
    assert spec.action == "crash" and spec.arg == 137.0
    (spec2,) = fault.parse("commit:db:crash:1:9")
    assert spec2.arg == 9.0

    assert not fault.crash_armed("append", "store")
    with fault.arm("append:store:crash:1"):
        # crash_armed matches without consuming the Bresenham schedule
        for _ in range(3):
            assert fault.crash_armed("append", "store")
        assert not fault.crash_armed("commit", "db")
        assert fault.crash_armed("append", "store")
    assert not fault.crash_armed("append", "store")
    with fault.arm("*:*:crash:1"):
        assert fault.crash_armed("commit", "db")


def test_boot_recover_stage_spans(tmp_path, monkeypatch):
    """A crash boot is the span recovery/boot; recovery/store and the
    verify replay's stages (gossip/extract, replay/sort, replay/stream,
    replay/readback) lie inside it, each once, and their parent chains
    end at it (doc/tracing.md)."""
    from lightning_tpu.utils import trace

    monkeypatch.delenv("LIGHTNING_TPU_INCIDENT_DIR", raising=False)
    monkeypatch.setenv("LIGHTNING_TPU_VERIFY_DEVICE", "off")
    d = str(tmp_path)
    store = os.path.join(d, "gossip_store")
    _signed_store(store)
    R.mark_running(d)
    spans: list[dict] = []
    trace.add_tap(spans.append)
    try:
        rep = R.boot_recover(d, store_path=store)
    finally:
        trace.remove_tap(spans.append)
    assert rep["state"] == "crash" and rep["verify"]["sigs"] == 6
    by = {n: [r for r in spans if r["name"] == n]
          for n in ("recovery/boot", "recovery/store", "gossip/extract",
                    "replay/sort", "replay/stream", "replay/readback")}
    assert {n: len(v) for n, v in by.items()} == dict.fromkeys(by, 1)
    boot = by["recovery/boot"][0]
    b0, b1 = boot["start_ns"], boot["start_ns"] + boot["duration_ns"]
    by_id = {r["span_id"]: r for r in spans}
    for name in by:
        if name == "recovery/boot":
            continue
        r = by[name][0]
        assert b0 <= r["start_ns"] \
            and r["start_ns"] + r["duration_ns"] <= b1, name
        up = r
        while up["parent_id"] is not None:
            up = by_id[up["parent_id"]]
        assert up is boot, name
    assert by["recovery/store"][0]["parent"] == "recovery/boot"
    # a clean boot has the same two outer spans and no replay
    R.mark_clean(d)
    spans.clear()
    trace.add_tap(spans.append)
    try:
        R.boot_recover(d, store_path=store)
    finally:
        trace.remove_tap(spans.append)
    assert sorted(r["name"] for r in spans) == ["recovery/boot",
                                                "recovery/store"]
