"""Crash-consistent boot: the clean-shutdown marker, torn-store
recovery, prior-incident discovery, and the db reconciliation sweep.

The reference earns its `kill -9`-at-any-instant survival from three
disciplines — gossip_store truncate-on-corruption, sqlite WAL, and
startup passes that resolve every in-flight row against what actually
became durable.  This module is that boot phase (doc/recovery.md):

1. read the marker: was the previous run shut down cleanly?
2. on a crash boot, discover the incident bundles the black box
   (obs/incident.py) froze for the dead run and log/meter them —
   forensics travel WITH the restart, not behind it;
3. recover the gossip store (gossip/store.py recover_store): torn tail
   truncated write-then-rename, crc-bad rows quarantined + host
   re-checked, missing store bootstrapped;
4. optionally replay the recovered store through the batched verify
   pipeline (LIGHTNING_TPU_RECOVERY_VERIFY — recovery is the one
   guaranteed-full-occupancy workload);
5. sweep the db: pending payments older than the crash become
   retryable-failed (no pending-forever phantoms in listpays),
   retransmission-journal and splice-inflight blobs are validated
   against channel state, and a hook replica that is "ahead by one"
   (wallet/db.py's documented crash window) drops its tail record.

tools/crashmatrix.py kills a live daemon at every armed seam and
asserts this module brings it back to the durable-prefix oracle.
"""
from __future__ import annotations

import json
import logging
import os
import re
import time

from ..obs import families as _f
from ..utils import events, trace

log = logging.getLogger("lightning_tpu.daemon.recovery")

MARKER_NAME = "run_marker"
# channel states with no live peer protocol: journal blobs there are
# stale by definition (wallet.py restore skips these states too)
DEAD_STATES = ("closingd_complete", "onchain", "closed")
_INC_RE = re.compile(r"^inc-[0-9]+-[0-9]+$")


# -- clean-shutdown marker --------------------------------------------------
# <data-dir>/run_marker: "running" while the daemon is up, "clean" after
# an orderly shutdown.  Written write-then-rename + fsync, so the marker
# itself can never be read torn; a missing marker means first boot.

def marker_path(data_dir: str) -> str:
    return os.path.join(data_dir, MARKER_NAME)


def _write_marker(data_dir: str, state: str) -> None:
    path = marker_path(data_dir)
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf8") as f:
        f.write(state + "\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def mark_running(data_dir: str) -> None:
    _write_marker(data_dir, "running")


def mark_clean(data_dir: str) -> None:
    _write_marker(data_dir, "clean")


def read_marker(data_dir: str) -> str:
    """"first_boot" (no marker), "clean", or "crash" (marker still says
    running — or says anything unrecognizable, which only a crash
    mid-everything could leave)."""
    try:
        with open(marker_path(data_dir), encoding="utf8") as f:
            content = f.read().strip()
    except OSError:
        return "first_boot"
    return "clean" if content == "clean" else "crash"


# -- prior-incident discovery ----------------------------------------------

def discover_incidents(data_dir: str) -> list[dict]:
    """Bundle summaries from the previous run's incident directory
    (newest last).  Reads the on-disk manifests directly — the new
    recorder instance hasn't started yet at this point in boot."""
    inc_dir = os.environ.get("LIGHTNING_TPU_INCIDENT_DIR") or os.path.join(
        data_dir, "incidents")
    try:
        names = sorted(
            (n for n in os.listdir(inc_dir) if _INC_RE.match(n)),
            key=lambda n: (int(n.split("-")[1]), int(n.split("-")[2])))
    except OSError:
        return []
    out = []
    for name in names:
        row = {"id": name, "trigger": None, "captured_at": None}
        try:
            with open(os.path.join(inc_dir, name, "manifest.json"),
                      encoding="utf8") as f:
                man = json.load(f)
            row["trigger"] = (man.get("trigger") or {}).get("class")
            row["captured_at"] = man.get("captured_at")
        except (OSError, ValueError):
            row["trigger"] = "unreadable"
        out.append(row)
    return out


# -- crc-bad host re-check --------------------------------------------------

def host_sig_checker():
    """Returns check_sigs(msgs) -> [bool] for recover_store(): parse +
    verify every signature with the pure-python oracle (crypto/
    ref_python — no jax, no kernels).  A channel_update's key lives in
    its owning channel_announcement, so the checker closes over a
    lazily-built scid→keys map from the messages themselves; a CU whose
    CA is not in the batch cannot be requalified (fails closed)."""
    from ..crypto import ref_python as ref
    from ..gossip import wire

    def _verify_one(sig: bytes, pubkey: bytes, region: bytes) -> bool:
        try:
            r = int.from_bytes(sig[:32], "big")
            s = int.from_bytes(sig[32:], "big")
            return ref.ecdsa_verify(ref.sha256d(region), r, s,
                                    ref.pubkey_parse(pubkey))
        except Exception:
            return False

    def check_sigs(msgs) -> list[bool]:
        parsed = []
        scid_keys: dict[int, tuple[bytes, bytes]] = {}
        for m in msgs:
            try:
                p = wire.parse_gossip(bytes(m))
            except Exception:
                p = None
            parsed.append(p)
            if isinstance(p, wire.ChannelAnnouncement):
                scid_keys[p.short_channel_id] = (p.node_id_1, p.node_id_2)
        out = []
        for m, p in zip(msgs, parsed):
            m = bytes(m)
            if isinstance(p, wire.ChannelAnnouncement):
                region = m[wire.CA_SIGNED_OFFSET:]
                out.append(all(
                    _verify_one(sig, key, region)
                    for sig, key in p.signature_tuples()))
            elif isinstance(p, wire.NodeAnnouncement):
                out.append(_verify_one(
                    p.signature, p.node_id, m[wire.NA_SIGNED_OFFSET:]))
            elif isinstance(p, wire.ChannelUpdate):
                keys = scid_keys.get(p.short_channel_id)
                out.append(keys is not None and _verify_one(
                    p.signature, keys[p.direction],
                    m[wire.CU_SIGNED_OFFSET:]))
            else:
                out.append(False)
        return out

    return check_sigs


# -- db reconciliation sweep ------------------------------------------------

def _retransmit_valid(raw: bytes) -> bool:
    """Structural validity of a retransmission-journal blob (the
    _pack_retransmit format: 1 sealed byte + [u32-be len][msg]...).
    wallet._unpack_retransmit is deliberately tolerant; this walk is
    not — a crash-corrupted blob must be detected, not reinterpreted."""
    if not raw:
        return True
    if raw[0] not in (0, 1):
        return False
    off = 1
    while off < len(raw):
        if off + 4 > len(raw):
            return False
        ln = int.from_bytes(raw[off : off + 4], "big")
        if off + 4 + ln > len(raw):
            return False
        off += 4 + ln
    return True


def reconcile_db(db, *, now: float | None = None) -> dict:
    """The boot sweep over wallet state (one transaction):

    * payments still 'pending' predate this boot by construction (the
      sweep runs before any RPC is served) — each becomes
      status='failed' with a retryable failure note, so listpays never
      shows a pending-forever phantom;
    * channels.retransmit blobs that fail the structural walk, or that
      belong to dead-state channels, reset to empty (a reestablish
      will renegotiate; replaying corrupt bytes would desync the peer);
    * channels.inflight splice blobs that are not valid JSON reset the
      same way.

    Returns {"payments_failed": n, "retransmit_reset": n,
    "inflight_reset": n}."""
    ts = int(now if now is not None else time.time())
    fixups = {"payments_failed": 0, "retransmit_reset": 0,
              "inflight_reset": 0}
    with db.transaction() as c:
        cur = c.execute(
            "UPDATE payments SET status='failed', completed_at=?, "
            "failure=? WHERE status='pending'",
            (ts, "daemon restarted before completion (crash recovery; "
                 "safe to retry)"))
        fixups["payments_failed"] = max(0, cur.rowcount)
        for cid, state, retransmit, inflight in c.execute(
                "SELECT id, state, retransmit, inflight "
                "FROM channels").fetchall():
            retransmit = retransmit or b""
            inflight = inflight or b""
            if retransmit and (state in DEAD_STATES
                               or not _retransmit_valid(retransmit)):
                c.execute("UPDATE channels SET retransmit=x'' WHERE id=?",
                          (cid,))
                fixups["retransmit_reset"] += 1
                log.warning("channel %d: retransmission journal reset "
                            "(state %s, %d bytes)", cid, state,
                            len(retransmit))
            if inflight:
                bad = state in DEAD_STATES
                if not bad:
                    try:
                        json.loads(inflight)
                    except ValueError:
                        bad = True
                if bad:
                    c.execute(
                        "UPDATE channels SET inflight=x'' WHERE id=?",
                        (cid,))
                    fixups["inflight_reset"] += 1
                    log.warning("channel %d: splice-inflight blob reset "
                                "(state %s)", cid, state)
    if fixups["payments_failed"]:
        _f.RECOVERY_DB_FIXUPS.labels("payment_failed").inc(
            fixups["payments_failed"])
    if fixups["retransmit_reset"]:
        _f.RECOVERY_DB_FIXUPS.labels("retransmit_reset").inc(
            fixups["retransmit_reset"])
    if fixups["inflight_reset"]:
        _f.RECOVERY_DB_FIXUPS.labels("inflight_reset").inc(
            fixups["inflight_reset"])
    return fixups


# -- the boot phase ---------------------------------------------------------

def boot_recover(data_dir: str, *, store_path: str | None = None,
                 db=None, replica=None, verify: bool | None = None,
                 now: float | None = None) -> dict:
    """Run the whole recovery phase and leave the marker at "running".

    Called from daemon/__main__.py after the wallet opens and BEFORE
    the gossmap/gossipd are built from the store (they must see the
    recovered file).  Returns a report dict; the "state" key is the
    marker verdict ("first_boot" | "clean" | "crash").

    LIGHTNING_TPU_RECOVERY_DISABLE=1 skips everything except the marker
    write; LIGHTNING_TPU_RECOVERY_VERIFY=0 skips the store verify
    replay on crash boots (`verify=` overrides the knob).

    The whole phase is the span `recovery/boot`; the store recovery
    inside it is `recovery/store`, and the verify replay brings its own
    (`gossip/extract`, `replay/sort`, `replay/stream`,
    `replay/readback`: doc/tracing.md)."""
    with trace.span("recovery/boot"):
        return _boot_recover(data_dir, store_path=store_path, db=db,
                             replica=replica, verify=verify, now=now)


def _boot_recover(data_dir: str, *, store_path: str | None, db, replica,
                  verify: bool | None, now: float | None) -> dict:
    t0 = time.perf_counter()
    state = read_marker(data_dir)
    report: dict = {"state": state, "incidents": [], "store": None,
                    "db_fixups": None, "replica": None,
                    "verify": None, "skipped": False}
    if state == "crash":
        _f.RECOVERY_BOOTS.labels("crash").inc()
    elif state == "clean":
        _f.RECOVERY_BOOTS.labels("clean").inc()
    else:
        _f.RECOVERY_BOOTS.labels("first_boot").inc()

    if os.environ.get("LIGHTNING_TPU_RECOVERY_DISABLE") == "1":
        report["skipped"] = True
        mark_running(data_dir)
        return report

    crashed = state == "crash"
    if crashed:
        log.warning("unclean shutdown detected (marker still said "
                    "running): entering crash recovery")
        incidents = discover_incidents(data_dir)
        report["incidents"] = incidents
        if incidents:
            _f.RECOVERY_INCIDENTS_FOUND.inc(len(incidents))
            newest = incidents[-1]
            log.warning("previous run left %d incident bundle(s); "
                        "newest: %s (trigger %s) — see listincidents",
                        len(incidents), newest["id"], newest["trigger"])

    if store_path is not None:
        from ..gossip import store as gstore

        # crc enforcement + host re-check only on crash boots: a clean
        # shutdown fsynced everything it appended, and the native scan
        # (always run, via load_store inside) still catches torn files
        check_sigs = host_sig_checker() if crashed else None
        with trace.span("recovery/store"):
            idx, srep = gstore.recover_store(
                store_path, check_crc=crashed, check_sigs=check_sigs)
        report["store"] = {
            "bootstrapped": srep.bootstrapped, "records": srep.records,
            "size": srep.size, "truncated_bytes": srep.truncated_bytes,
            "crc_bad": srep.crc_bad, "requalified": srep.requalified,
            "dropped": srep.dropped,
        }
        report["_store_idx"] = idx
        if crashed:
            do_verify = (verify if verify is not None else
                         os.environ.get("LIGHTNING_TPU_RECOVERY_VERIFY",
                                        "1") != "0")
            if do_verify and srep.records:
                # replay the durable store through the batched verify
                # pipeline — full-occupancy by construction (every
                # alive record, one enqueue stream)
                from ..gossip import verify as gverify

                res = gverify.verify_store(idx)
                n_bad = (int((~res.ca_valid).sum())
                         + int((~res.cu_valid).sum())
                         + int((~res.na_valid).sum()))
                report["verify"] = {"records": res.n_records,
                                    "sigs": res.n_sigs,
                                    "invalid": n_bad}
                if n_bad:
                    log.warning("recovery verify replay: %d record(s) "
                                "failed signature re-verification",
                                n_bad)

    if db is not None and crashed:
        report["db_fixups"] = reconcile_db(db, now=now)
    if db is not None and replica is not None:
        from ..wallet.db import reconcile_file_replica

        verdict = reconcile_file_replica(db, replica)
        report["replica"] = verdict
        if verdict == "dropped_ahead":
            _f.RECOVERY_DB_FIXUPS.labels("replica_dropped").inc()

    mark_running(data_dir)
    dt = time.perf_counter() - t0
    _f.RECOVERY_SECONDS.observe(dt)
    events.emit("recovery_complete", {
        "state": state, "seconds": round(dt, 3),
        "incidents": len(report["incidents"]),
        "store": {k: v for k, v in (report["store"] or {}).items()},
        "db_fixups": report["db_fixups"], "replica": report["replica"]})
    if crashed:
        s = report["store"] or {}
        log.warning(
            "crash recovery complete in %.2fs: store %d records "
            "(%d torn bytes truncated, %d crc-bad: %d requalified / "
            "%d dropped), db fixups %s, replica %s",
            dt, s.get("records", 0), s.get("truncated_bytes", 0),
            s.get("crc_bad", 0), s.get("requalified", 0),
            s.get("dropped", 0), report["db_fixups"], report["replica"])
    return report
