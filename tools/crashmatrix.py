#!/usr/bin/env python
"""crashmatrix: deterministic kill-matrix harness for crash-consistent
restart (doc/recovery.md).

The daemon's crash-safety story is a chain of disciplines — fsync'd
store appends, sqlite WAL, the clean-shutdown marker, boot-time
reconciliation — and each link is only as good as the last time a
process actually died at its seam.  This harness keeps dying there on
purpose:

for each matrix entry, the parent

1. spawns a REAL child process that boots the daemon's durable
   subsystems (boot_recover → GossipIngest on a real gossip_store →
   wallet Db with the FileReplica db_write hook → incident recorder),
   runs loadgen-style traffic to a warm durable state, then SELF-ARMS
   ``LIGHTNING_TPU_FAULT=<seam>:<family>:crash:1`` and storms until
   the armed seam kills it mid-operation (os._exit — no atexit, no
   flush, exactly what a SIGKILL leaves behind);
2. computes the durable-prefix oracle from the crashed files with an
   INDEPENDENT pure-python record walk (the harness must not trust
   the code under test to define what "durable" meant);
3. optionally flips a byte in the dead store (the bitrot entries:
   payload corruption must be dropped via quarantine, a corrupt
   timestamp must be requalified by the host signature re-check);
4. spawns a second child that runs the real recovery boot
   (daemon/recovery.boot_recover) and reports what it found;
5. asserts: the child died at the armed seam (exit 137), recovery saw
   a crash marker, the prior run's crash bundle was discovered, the
   recovered store matches the oracle byte-for-byte, zero phantom
   pending payments survive, the replica verdict matches the seam
   (ahead-by-one exactly for the commit window), recovery metrics
   were emitted, and the recovered daemon is LIVE (post-recovery
   ingest + db commit succeed).

``--selfcheck`` runs the full matrix; ``--lite`` the 3-seam subset
wired into tools/run_suite.sh.  Children force the CPU backend and
LIGHTNING_TPU_VERIFY_DEVICE=off (the host-oracle dispatcher), so the
whole matrix runs in seconds with zero jax compiles while every
pipeline seam (producer/prep/dispatch/readback supervision included)
stays live.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(TOOLS_DIR)
sys.path.insert(0, REPO_DIR)

KILL_RC = 137          # the crash action's default exit code
RC_NEVER_FIRED = 3     # child ran out of storm budget un-killed

# -- the matrix -------------------------------------------------------------
# Each entry: the armed fault spec, plus what the parent must observe.
#   torn      — the kill lands mid store write; recovery must truncate
#   ahead     — the kill lands between db_write hook and COMMIT;
#               recovery must drop the replica's tail record
#   corrupt   — parent flips a byte in the dead store before recovery
#               ("payload" → quarantined + dropped; "ts" → quarantined
#               but requalified by the host signature re-check)
MATRIX: dict[str, dict] = {
    "append-store": {"fault": "append:store:crash:1", "torn": True},
    "commit-db": {"fault": "commit:db:crash:1", "ahead": True},
    "dispatch-verify": {"fault": "dispatch:verify:crash:1"},
    "bitrot-payload": {"fault": "commit:db:crash:1", "ahead": True,
                       "corrupt": "payload"},
    "bitrot-ts": {"fault": "commit:db:crash:1", "ahead": True,
                  "corrupt": "ts"},
}
LITE = ("append-store", "commit-db", "bitrot-payload")

MSG_CA, MSG_NA, MSG_CU = 256, 257, 258
PENDING_HASH_HEX = "01" * 32


# -- independent durable-prefix oracle (parent side) ------------------------
# Deliberately NOT gossip/store.py: the oracle that defines "what should
# have survived" must not share code with the recovery being tested.

def walk_store(data: bytes):
    """(records, valid_end): records are (rec_off, msg_off, length,
    msg_type, flags); valid_end is the byte length of the longest
    complete-record prefix."""
    recs = []
    off = 1
    size = len(data)
    while off + 12 <= size:
        ln = int.from_bytes(data[off + 2: off + 4], "big")
        if off + 12 + ln > size:
            break
        flags = int.from_bytes(data[off: off + 2], "big")
        mtype = int.from_bytes(data[off + 12: off + 14], "big") if ln >= 2 else 0
        recs.append((off, off + 12, ln, mtype, flags))
        off += 12 + ln
    return recs, off


def expected_store_sha(store_path: str, entry: dict) -> tuple[str, dict]:
    """sha256 the recovered store MUST hash to, computed from the dead
    run's bytes alone, plus the oracle facts the report is checked
    against."""
    with open(store_path, "rb") as f:
        data = f.read()
    recs, valid_end = walk_store(data)
    facts = {"size": len(data), "valid_end": valid_end,
             "torn_bytes": len(data) - valid_end, "records": len(recs)}
    good = bytearray(data[:valid_end])
    if entry.get("corrupt") == "payload":
        # the flipped record fails crc AND the host sig re-check:
        # recovery must flag it FLAG_DELETED in place (byte 0x80 into
        # the flags be16), everything else untouched
        row, (rec_off, _mo, _ln, _mt, _fl) = next(
            (i, r) for i, r in enumerate(recs) if r[3] == MSG_NA)
        good[rec_off] |= 0x80
        facts["dropped_row"] = row
    return hashlib.sha256(bytes(good)).hexdigest(), facts


def corrupt_store(store_path: str, mode: str) -> None:
    with open(store_path, "rb") as f:
        data = bytearray(f.read())
    recs, _ = walk_store(bytes(data))
    if mode == "payload":
        # last payload byte of the warm node_announcement: inside the
        # signed region, so both the crc and the signature break —
        # recovery must DROP it.  (An NA, not the CA: dropping a CA
        # additionally orphans its channel_updates, which is its own
        # scenario — here the oracle wants exactly one dead row.)
        _off, msg_off, ln, _mt, _fl = next(
            r for r in recs if r[3] == MSG_NA)
        data[msg_off + ln - 1] ^= 0xFF
    elif mode == "ts":
        # header timestamp of a node_announcement: the crc covers
        # (timestamp, msg) so it breaks, but the message itself is
        # untouched and self-authenticating — the host re-check must
        # REQUALIFY the row (kept, no flag flip)
        na = next(r for r in recs if r[3] == MSG_NA)
        data[na[0] + 8] ^= 0xFF
    else:
        raise ValueError(mode)
    with open(store_path, "wb") as f:
        f.write(bytes(data))


# -- child plumbing ---------------------------------------------------------

def child_env() -> dict:
    env = dict(os.environ)
    env.pop("LIGHTNING_TPU_FAULT", None)    # children self-arm
    env.update({
        "LIGHTNING_TPU_VERIFY_DEVICE": "off",
        "LIGHTNING_TPU_VERIFY_BUCKET": "8",
        "LIGHTNING_TPU_SIGN_BUCKET": "8",
        "LIGHTNING_TPU_JAX_CACHE_MODE": "ro",
        "LIGHTNING_TPU_MESH_VERIFY": "off",
        "LIGHTNING_TPU_INCIDENT_COOLDOWN_S": "0",
    })
    return env


def run_child(mode: str, data_dir: str, *extra: str,
              timeout: float = 180.0) -> subprocess.CompletedProcess:
    cmd = [sys.executable, os.path.abspath(__file__), mode,
           "--data-dir", data_dir, *extra]
    return subprocess.run(cmd, env=child_env(), capture_output=True,
                          text=True, timeout=timeout, cwd=REPO_DIR)


def _child_setup():
    """Environment discipline shared by both child modes.  Must run
    before any lightning_tpu import that touches jax: the children are
    CPU processes wherever the matrix runs, and a child that took the
    parent's chip would fail or hang the matrix."""
    from lightning_tpu.utils.jaxcfg import force_cpu

    force_cpu(n_devices=1)


# deterministic traffic (the tests/test_ingest.py builders' idiom):
# ref_python signing only — no kernels, no compiles, bit-exact runs
K1, K2, K3 = 11111, 22222, 33333
SCID_BASE = (600000 << 40) | (1 << 16)


def _builders():
    from lightning_tpu.crypto import ref_python as ref
    from lightning_tpu.gossip import wire

    def pub(k):
        return ref.pubkey_serialize(ref.pubkey_create(k))

    def ordered(ka, kb):
        return (ka, kb) if pub(ka) < pub(kb) else (kb, ka)

    def make_ca(ka, kb, scid):
        ka, kb = ordered(ka, kb)
        ca = wire.ChannelAnnouncement(
            short_channel_id=scid,
            node_id_1=pub(ka), node_id_2=pub(kb),
            bitcoin_key_1=pub(ka), bitcoin_key_2=pub(kb))
        m = bytearray(ca.serialize())
        h = ref.sha256d(bytes(m[wire.CA_SIGNED_OFFSET:]))
        for off, k in zip(wire.CA_SIG_OFFSETS, (ka, kb, ka, kb)):
            r, s = ref.ecdsa_sign(h, k)
            m[off:off + 64] = r.to_bytes(32, "big") + s.to_bytes(32, "big")
        return bytes(m)

    def make_cu(ka, kb, scid, direction, ts):
        ka, kb = ordered(ka, kb)
        cu = wire.ChannelUpdate(
            short_channel_id=scid, timestamp=ts, channel_flags=direction,
            htlc_maximum_msat=10 ** 9, fee_base_msat=1000,
            fee_proportional_millionths=10)
        m = bytearray(cu.serialize())
        h = ref.sha256d(bytes(m[wire.CU_SIGNED_OFFSET:]))
        r, s = ref.ecdsa_sign(h, ka if direction == 0 else kb)
        m[wire.CU_SIG_OFFSET:wire.CU_SIG_OFFSET + 64] = (
            r.to_bytes(32, "big") + s.to_bytes(32, "big"))
        return bytes(m)

    def make_na(k, ts):
        na = wire.NodeAnnouncement(
            timestamp=ts, node_id=pub(k),
            alias=b"crashmatrix".ljust(32, b"\0"))
        m = bytearray(na.serialize())
        h = ref.sha256d(bytes(m[wire.NA_SIGNED_OFFSET:]))
        r, s = ref.ecdsa_sign(h, k)
        m[wire.NA_SIG_OFFSET:wire.NA_SIG_OFFSET + 64] = (
            r.to_bytes(32, "big") + s.to_bytes(32, "big"))
        return bytes(m)

    return make_ca, make_cu, make_na


def _open_state(data_dir: str):
    from lightning_tpu.wallet.db import Db, FileReplica

    store = os.path.join(data_dir, "gossip_store")
    db = Db(os.path.join(data_dir, "wallet.db"))
    replica = FileReplica(os.path.join(data_dir, "db_replica.jsonl"))
    return store, db, replica


# -- child mode: run until the armed seam kills us --------------------------

def child_run(data_dir: str, fault: str, storm_max: int) -> int:
    _child_setup()
    import asyncio

    from lightning_tpu.daemon import recovery
    from lightning_tpu.gossip.ingest import GossipIngest
    from lightning_tpu.obs import incident

    os.makedirs(data_dir, exist_ok=True)
    rec = incident.install(incident.IncidentRecorder(
        os.path.join(data_dir, "incidents"), cooldown_s=0.0))
    rec.start()
    store, db, replica = _open_state(data_dir)
    recovery.boot_recover(data_dir, store_path=store, db=db,
                          replica=replica)
    db.set_db_write_hook(replica)
    make_ca, make_cu, make_na = _builders()

    async def main() -> int:
        ing = GossipIngest(store, flush_ms=1.0, bucket=8)
        ing.start()
        # warm durable state: one channel (CA + both-direction CUs +
        # node_announcement), all fsync'd by the ingest write-ahead
        await ing.submit(make_ca(K1, K2, SCID_BASE))
        await ing.submit(make_cu(K1, K2, SCID_BASE, 0, ts=100))
        await ing.submit(make_cu(K1, K2, SCID_BASE, 1, ts=100))
        await ing.submit(make_na(K1, ts=100))
        await ing.drain()
        if ing.stats.accepted != 4:
            print(f"CRASHMATRIX-ERR warm traffic not accepted: "
                  f"{ing.stats}", flush=True)
            return 4
        with db.transaction() as c:
            c.execute(
                "INSERT INTO payments (payment_hash, amount_msat, "
                "amount_sent_msat, status, preimage, created_at, "
                "completed_at) VALUES (x'02', 1000, 1000, 'complete', "
                "x'03', 1000, 1001)")
        # THE phantom: still 'pending' when the kill lands — recovery
        # must turn it retryable-failed, never leave it pending forever
        with db.transaction() as c:
            c.execute(
                "INSERT INTO payments (payment_hash, amount_msat, "
                "amount_sent_msat, status, created_at) VALUES "
                f"(x'{PENDING_HASH_HEX}', 5000, 5000, 'pending', 1002)")
        print("CRASHMATRIX-WARM", flush=True)

        # self-arm: the fault env cache keys on the string, so setting
        # it here arms every seam from the next fire() on
        os.environ["LIGHTNING_TPU_FAULT"] = fault
        for i in range(storm_max):
            scid = SCID_BASE + ((i + 2) << 16)
            await ing.submit(make_ca(K1, K2, scid))       # verify + append
            await ing.submit(make_cu(K1, K2, scid, 0, ts=200 + i))
            await ing.drain()
            with db.transaction() as c:                    # commit seam
                c.execute(
                    "INSERT INTO payments (payment_hash, amount_msat, "
                    "amount_sent_msat, status, preimage, created_at, "
                    "completed_at) VALUES (?, 100, 100, 'complete', "
                    "x'04', ?, ?)", (bytes([5, i]), 2000 + i, 2001 + i))
        return RC_NEVER_FIRED    # storm budget exhausted un-killed

    return asyncio.run(main())


# -- child mode: recover and report -----------------------------------------

def child_recover(data_dir: str) -> int:
    _child_setup()
    import asyncio

    from lightning_tpu.daemon import recovery
    from lightning_tpu.gossip.ingest import GossipIngest
    from lightning_tpu.obs.families import REGISTRY

    store, db, replica = _open_state(data_dir)
    report = recovery.boot_recover(data_dir, store_path=store, db=db,
                                   replica=replica)
    report.pop("_store_idx", None)

    with open(store, "rb") as f:
        store_sha = hashlib.sha256(f.read()).hexdigest()
    pending = db.conn.execute(
        "SELECT COUNT(*) FROM payments WHERE status='pending'"
    ).fetchone()[0]
    failed = db.conn.execute(
        "SELECT COUNT(*) FROM payments WHERE status='failed'"
    ).fetchone()[0]

    # liveness: the recovered daemon must ingest + commit again
    db.set_db_write_hook(replica)
    make_ca, make_cu, make_na = _builders()

    async def liveness() -> int:
        # a brand-new channel_announcement: unconditionally acceptable
        # to a fresh ingest session (an NA/CU would be held pending
        # until its channel is known), so accepted==1 proves the whole
        # post-recovery path — verify flush, store append, fsync
        ing = GossipIngest(store, flush_ms=1.0, bucket=8)
        ing.start()
        await ing.submit(make_ca(K1, K3, SCID_BASE + (999 << 16)))
        await ing.drain()
        await ing.close()
        return ing.stats.accepted

    live_accepted = asyncio.run(liveness())
    with db.transaction() as c:
        c.execute(
            "INSERT INTO payments (payment_hash, amount_msat, "
            "amount_sent_msat, status, preimage, created_at, "
            "completed_at) VALUES (x'06', 7, 7, 'complete', x'07', "
            "3000, 3001)")
    recovery.mark_clean(data_dir)

    snap = REGISTRY.snapshot()["metrics"]
    metrics = {name: fam["samples"] for name, fam in snap.items()
               if name.startswith("clntpu_recovery_")}
    out = {"report": report, "store_sha": store_sha,
           "pending_after": int(pending), "failed_after": int(failed),
           "liveness_accepted": int(live_accepted), "metrics": metrics}
    print("CRASHMATRIX-REPORT " + json.dumps(out), flush=True)
    return 0


# -- parent: the matrix loop ------------------------------------------------

class MatrixFailure(AssertionError):
    pass


def check(cond: bool, what: str, *detail) -> None:
    if not cond:
        raise MatrixFailure(what + (": " + " ".join(
            str(d) for d in detail) if detail else ""))


def run_entry(name: str, *, storm_max: int, keep: bool,
              verbose: bool) -> dict:
    entry = MATRIX[name]
    data_dir = tempfile.mkdtemp(prefix=f"crashmatrix-{name}-")
    t0 = time.monotonic()
    try:
        # 1. run a child until the armed seam kills it
        cp = run_child("child-run", data_dir, "--fault", entry["fault"],
                       "--storm-max", str(storm_max))
        if verbose:
            sys.stderr.write(cp.stderr)
        check(cp.returncode == KILL_RC,
              f"[{name}] child died rc={cp.returncode}, want {KILL_RC}",
              cp.stdout[-2000:], cp.stderr[-2000:])
        check("CRASHMATRIX-WARM" in cp.stdout,
              f"[{name}] kill landed before the warm state was durable")

        # 2. oracle + optional bitrot, from the dead files alone
        store = os.path.join(data_dir, "gossip_store")
        if entry.get("corrupt"):
            corrupt_store(store, entry["corrupt"])
        want_sha, facts = expected_store_sha(store, entry)
        if entry.get("torn"):
            check(facts["torn_bytes"] > 0,
                  f"[{name}] append-seam kill left no torn tail", facts)
        else:
            check(facts["torn_bytes"] == 0,
                  f"[{name}] unexpected torn tail", facts)

        # 3. recover in a fresh child
        cp2 = run_child("child-recover", data_dir)
        if verbose:
            sys.stderr.write(cp2.stderr)
        check(cp2.returncode == 0,
              f"[{name}] recovery child rc={cp2.returncode}",
              cp2.stdout[-2000:], cp2.stderr[-2000:])
        line = next((ln for ln in cp2.stdout.splitlines()
                     if ln.startswith("CRASHMATRIX-REPORT ")), None)
        check(line is not None, f"[{name}] no recovery report emitted")
        rep = json.loads(line.split(" ", 1)[1])
        r = rep["report"]

        # 4. the assertions the matrix exists for
        check(r["state"] == "crash",
              f"[{name}] marker said {r['state']!r}, want 'crash'")
        crash_bundles = [i for i in r["incidents"]
                        if i["trigger"] == "crash"]
        check(len(crash_bundles) == 1,
              f"[{name}] want exactly 1 crash bundle from the dead run",
              r["incidents"])
        check(rep["store_sha"] == want_sha,
              f"[{name}] recovered store != durable-prefix oracle",
              rep["report"]["store"], facts)
        srep = r["store"]
        check(srep["truncated_bytes"] == facts["torn_bytes"],
              f"[{name}] truncation mismatch", srep, facts)
        mode = entry.get("corrupt")
        want_bad = 1 if mode else 0
        want_req = 1 if mode == "ts" else 0
        check(srep["crc_bad"] == want_bad
              and srep["requalified"] == want_req
              and srep["dropped"] == want_bad - want_req,
              f"[{name}] quarantine accounting mismatch", srep)
        check(rep["pending_after"] == 0,
              f"[{name}] phantom pending payment survived recovery")
        check((r["db_fixups"] or {}).get("payments_failed", 0) >= 1,
              f"[{name}] the phantom was not swept to retryable-failed",
              r["db_fixups"])
        want_replica = "dropped_ahead" if entry.get("ahead") else "in_sync"
        check(r["replica"] == want_replica,
              f"[{name}] replica verdict {r['replica']!r}, "
              f"want {want_replica!r}")
        check(r["verify"] is not None and r["verify"]["invalid"] == 0,
              f"[{name}] recovery verify replay", r["verify"])
        check(rep["liveness_accepted"] == 1,
              f"[{name}] post-recovery ingest is not live")
        check(any(s["value"] >= 1 for s in
                  rep["metrics"].get("clntpu_recovery_boots_total", [])
                  if s["labels"].get("state") == "crash"),
              f"[{name}] recovery metrics not emitted", rep["metrics"])
        dt = time.monotonic() - t0
        return {"name": name, "ok": True, "seconds": round(dt, 1),
                "facts": facts, "store": srep, "replica": r["replica"],
                "db_fixups": r["db_fixups"]}
    finally:
        if keep:
            print(f"  [{name}] state kept at {data_dir}")
        else:
            shutil.rmtree(data_dir, ignore_errors=True)


def parent(names, storm_max: int, keep: bool, verbose: bool) -> int:
    results = []
    failed = False
    for name in names:
        try:
            res = run_entry(name, storm_max=storm_max, keep=keep,
                            verbose=verbose)
            print(f"PASS {name}: killed@{MATRIX[name]['fault']} "
                  f"recovered in {res['seconds']}s "
                  f"(store {res['store']['records']} recs, "
                  f"{res['store']['truncated_bytes']}B truncated, "
                  f"replica {res['replica']})", flush=True)
            results.append(res)
        except MatrixFailure as e:
            print(f"FAIL {name}: {e}", flush=True)
            failed = True
        except subprocess.TimeoutExpired:
            print(f"FAIL {name}: child timed out", flush=True)
            failed = True
    print(f"crashmatrix: {len(results)}/{len(list(names))} entries pass")
    return 1 if failed else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="mode")
    for m in ("child-run", "child-recover"):
        p = sub.add_parser(m)
        p.add_argument("--data-dir", required=True)
        p.add_argument("--fault", default="")
        p.add_argument("--storm-max", type=int, default=64)
    ap.add_argument("--selfcheck", action="store_true",
                    help="run the full kill matrix")
    ap.add_argument("--lite", action="store_true",
                    help="the 3-seam subset (tools/run_suite.sh)")
    ap.add_argument("--entry", action="append",
                    help="run specific matrix entries")
    ap.add_argument("--storm-max", type=int, default=64)
    ap.add_argument("--keep", action="store_true",
                    help="keep each entry's data dir for inspection")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="mirror child stderr")
    args = ap.parse_args(argv)

    if args.mode == "child-run":
        return child_run(args.data_dir, args.fault, args.storm_max)
    if args.mode == "child-recover":
        return child_recover(args.data_dir)

    if args.entry:
        names = args.entry
        for n in names:
            if n not in MATRIX:
                ap.error(f"unknown entry {n!r} (have: {list(MATRIX)})")
    elif args.lite:
        names = LITE
    else:
        names = list(MATRIX)    # --selfcheck and the bare default
    return parent(names, args.storm_max, args.keep, args.verbose)


if __name__ == "__main__":
    sys.exit(main())
