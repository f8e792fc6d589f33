"""The benchmark's gossip-store generator.

A copy of what `lightning_tpu/gossip/synth.py` writes (the shapes of
upstream's million-channels store: one `channel_announcement` with four
signatures per channel, two `channel_update`s, one `node_announcement`
per node; `htlc_maximum`, fee base and ppm drawn as synth.py draws
them), rebuilt for speed and independence:

* messages are built as numpy byte matrices, not per-message objects;
* keys are derived and messages signed by OpenSSL in worker processes
  (gen/signer.py), so nothing of the program under test makes the
  inputs and no sign kernel has to compile;
* who is joined to whom is an endpoint law of its own,
  gen/endpoints_<law>.py, named by the configuration's
  `graph.endpoints` (default `uniform`, as synth.py draws them);
* a seeded handful of records is made invalid on purpose (a bit of a
  signature, or of the signed region, flipped after signing) so that
  "a record with a bad signature is reported invalid" can be checked.

`make_store` returns the ground truth the generator knows by
construction: the counts and which record of each kind was corrupted.
The reference (benchmarks/reference/) re-derives the validity bits of a
sample from the file alone.
"""
from __future__ import annotations

import hashlib
import importlib
import json
import os

import numpy as np

from . import signer

VERSION_BYTE = 0x10
MSG_CA, MSG_NA, MSG_CU = 256, 257, 258
CHAIN_HASH = bytes.fromhex(
    "6fe28c0ab6f1b372c1a6a246ae63f74f931e8365e15a089c68d6190000000000")
CA_LEN, CU_LEN, NA_LEN = 432, 138, 142
CA_SIG_OFFSETS = (2, 66, 130, 194)
CA_SIGNED, CU_SIGNED, NA_SIGNED = 258, 66, 66
TS0 = 1_700_000_000
KEEP_STORES = 8


def _be(values: np.ndarray, width: int) -> np.ndarray:
    """(n,) unsigned ints -> (n, width) big-endian bytes."""
    b = np.asarray(values).astype(">u8").view(np.uint8).reshape(-1, 8)
    return b[:, 8 - width:]


def scid_for(i: np.ndarray) -> np.ndarray:
    i = np.asarray(i, np.uint64)
    return ((np.uint64(500000) + i // np.uint64(2016)) << np.uint64(40)) \
        | ((i % np.uint64(2016)) << np.uint64(16))


_CRC_TABLE = None


def crc32c(seeds: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """crc32c (Castagnoli) of every row of a (n, L) byte matrix, seeded
    per row (the store seeds each record's crc with its timestamp)."""
    global _CRC_TABLE
    if _CRC_TABLE is None:
        t = np.arange(256, dtype=np.uint32)
        for _ in range(8):
            t = np.where(t & 1, np.uint32(0x82F63B78) ^ (t >> 1), t >> 1)
        _CRC_TABLE = t.astype(np.uint32)
    crc = ~np.asarray(seeds, np.uint32)
    for col in range(rows.shape[1]):
        crc = _CRC_TABLE[(crc ^ rows[:, col]) & 0xFF] ^ (crc >> 8)
    return ~crc


def _sha256d_rows(rows: np.ndarray, start: int) -> bytes:
    out = bytearray()
    for r in rows:
        out += hashlib.sha256(
            hashlib.sha256(r[start:].tobytes()).digest()).digest()
    return bytes(out)


def _records(msgs: np.ndarray, ts: np.ndarray) -> bytes:
    """be16 flags | be16 len | be32 crc | be32 timestamp | msg."""
    n, ln = msgs.shape
    rec = np.zeros((n, 12 + ln), np.uint8)
    rec[:, 2:4] = _be(np.full(n, ln), 2)
    rec[:, 4:8] = _be(crc32c(ts, msgs), 4)
    rec[:, 8:12] = _be(ts, 4)
    rec[:, 12:] = msgs
    return rec.tobytes()


def endpoint_law(graph: dict) -> str:
    """The law a configuration's `graph` names, `uniform` where none."""
    return graph.get("endpoints", "uniform")


def endpoints(rng: np.random.Generator, graph: dict
              ) -> tuple[np.ndarray, np.ndarray]:
    """The two ends of every channel, by the graph's endpoint law:
    gen/endpoints_<law>.py `endpoints(rng, graph)`, which draws from
    the generator's own stream and reads its parameters in `graph`."""
    law = importlib.import_module(f".endpoints_{endpoint_law(graph)}",
                                  __package__)
    return law.endpoints(rng, graph)


def make_store(path: str, *, graph: dict, seed: int, sign: bool,
               bad_records: int = 0, workers: int | None = None) -> dict:
    """Write the store and return what the generator knows of it.
    `graph` is a configuration's `graph`, whole: `channels`, `nodes`
    and, where it names an endpoint law, the law's own keys."""
    channels, nodes = graph["channels"], graph["nodes"]
    rng = np.random.default_rng(seed)
    seckeys = [int.from_bytes(rng.bytes(32), "big") % (signer.N - 1) + 1
               for _ in range(nodes)]
    if workers is None:
        workers = max(1, min(12, (os.cpu_count() or 2) - 1))
    if nodes < 256:
        workers = 1
    # -- public keys (OpenSSL, in the workers) -----------------------------
    cuts = np.linspace(0, nodes, workers + 1).astype(int)
    parts = signer.run_jobs("derive_pubkeys", [
        (seckeys[a:b],) for a, b in zip(cuts, cuts[1:])])
    pubs = np.frombuffer(b"".join(parts), np.uint8).reshape(nodes, 33)

    # -- endpoints; BOLT 7: node_id_1 is the lexically lesser key ----------
    a, b = endpoints(rng, graph)
    pa, pb = pubs[a], pubs[b]
    diff = pa != pb
    first = diff.argmax(axis=1)
    rows = np.arange(channels)
    swap = pa[rows, first] > pb[rows, first]
    n1 = np.where(swap, b, a)
    n2 = np.where(swap, a, b)
    idx = np.arange(channels)

    # -- channel_announcement ----------------------------------------------
    ca = np.zeros((channels, CA_LEN), np.uint8)
    ca[:, 0:2] = _be(np.full(channels, MSG_CA), 2)
    ca[:, 260:292] = np.frombuffer(CHAIN_HASH, np.uint8)
    ca[:, 292:300] = _be(scid_for(idx), 8)
    ca[:, 300:333] = pubs[n1]
    ca[:, 333:366] = pubs[n2]
    ca[:, 366:399] = pubs[n1]
    ca[:, 399:432] = pubs[n2]

    # -- channel_update: two per channel, direction 0 then 1 ---------------
    n_cu = 2 * channels
    cu_chan = np.repeat(idx, 2)
    cu_dir = np.tile(np.array([0, 1]), channels)
    cu = np.zeros((n_cu, CU_LEN), np.uint8)
    cu[:, 0:2] = _be(np.full(n_cu, MSG_CU), 2)
    cu[:, 66:98] = np.frombuffer(CHAIN_HASH, np.uint8)
    cu[:, 98:106] = _be(scid_for(cu_chan), 8)
    cu[:, 106:110] = _be(TS0 + cu_chan, 4)
    cu[:, 110] = 1                                    # htlc_maximum present
    cu[:, 111] = cu_dir
    cu[:, 112:114] = _be(np.full(n_cu, 6), 2)         # cltv_expiry_delta
    # htlc_minimum_msat 0
    cu[:, 122:126] = _be(rng.integers(0, 5000, n_cu), 4)
    cu[:, 126:130] = _be(rng.integers(0, 10000, n_cu), 4)
    cu[:, 130:138] = _be(rng.integers(1, 1 << 40, n_cu), 8)
    cu_signer = np.where(cu_dir == 0, n1[cu_chan], n2[cu_chan])

    # -- node_announcement --------------------------------------------------
    nidx = np.arange(nodes)
    na = np.zeros((nodes, NA_LEN), np.uint8)
    na[:, 0:2] = _be(np.full(nodes, MSG_NA), 2)
    na[:, 68:72] = _be(TS0 + nidx, 4)
    na[:, 72:105] = pubs
    alias = np.frombuffer(b"".join(
        (b"tpu-node-%06d" % i).ljust(32, b"\x00") for i in range(nodes)),
        np.uint8).reshape(nodes, 32)
    na[:, 108:140] = alias

    bad, bad_ca_sig = {"ca": [], "cu": [], "na": []}, {}
    if sign:
        # every signature is one job: (signer node, digest, where it goes)
        ca_h = np.frombuffer(_sha256d_rows(ca, CA_SIGNED),
                             np.uint8).reshape(channels, 32)
        cu_h = np.frombuffer(_sha256d_rows(cu, CU_SIGNED),
                             np.uint8).reshape(n_cu, 32)
        na_h = np.frombuffer(_sha256d_rows(na, NA_SIGNED),
                             np.uint8).reshape(nodes, 32)
        job_key = np.concatenate([n1, n2, n1, n2, cu_signer, nidx])
        job_hash = np.concatenate([ca_h, ca_h, ca_h, ca_h, cu_h, na_h])
        order = np.argsort(job_key, kind="stable")
        cuts = np.linspace(0, len(order), workers + 1).astype(int)
        jobs = []
        for lo, hi in zip(cuts, cuts[1:]):
            sel = order[lo:hi]
            keys = job_key[sel]
            k0 = int(keys.min()) if len(keys) else 0
            k1 = int(keys.max()) + 1 if len(keys) else 0
            jobs.append((seckeys[k0:k1], (keys - k0).tolist(),
                         job_hash[sel].tobytes()))
        sig_parts = signer.run_jobs("sign_jobs", jobs)
        sigs = np.empty((len(order), 64), np.uint8)
        sigs[order] = np.frombuffer(b"".join(sig_parts),
                                    np.uint8).reshape(-1, 64)
        for j, off in enumerate(CA_SIG_OFFSETS):
            ca[:, off:off + 64] = sigs[j * channels:(j + 1) * channels]
        cu[:, 2:66] = sigs[4 * channels:4 * channels + n_cu]
        na[:, 2:66] = sigs[4 * channels + n_cu:]
        bad, bad_ca_sig = _corrupt(rng, ca, cu, na, bad_records)

    with open(path, "wb") as f:
        f.write(bytes([VERSION_BYTE]))
        f.write(_records(ca, TS0 + idx))
        f.write(_records(cu, TS0 + np.arange(n_cu)))
        f.write(_records(na, TS0 + nidx))
    return {"channels": channels, "nodes": nodes, "channel_updates": n_cu,
            "node_announcements": nodes,
            "records": channels + n_cu + nodes,
            "sigs": 4 * channels + n_cu + nodes if sign else 0,
            "signed": bool(sign), "bad": bad, "bad_ca_sig": bad_ca_sig,
            "bytes": os.path.getsize(path)}


def _corrupt(rng, ca, cu, na, n_bad: int) -> dict:
    """Make n_bad records invalid after signing, spread over the three
    kinds and over both ways a record goes bad: a flipped bit in a
    signature or a flipped bit in the signed region.  A
    channel_announcement's flipped signature is its first, second,
    third, fourth in turn, so 24 bad records put one plant on each of
    the four positions.  Returns the record indices by kind, and for
    those channel_announcements the position flipped (row -> 0..3)."""
    bad = {"ca": [], "cu": [], "na": []}
    ca_sig: dict = {}
    kinds = [("ca", ca), ("cu", cu), ("na", na)]
    for k in range(max(0, n_bad)):
        name, arr = kinds[k % 3]
        while True:
            row = int(rng.integers(0, len(arr)))
            if row not in bad[name]:
                break
        if (k // 3) % 2 == 0:
            off = 2 + int(rng.integers(0, 64))
            if name == "ca":
                which = (k // 6) % 4
                ca_sig[str(row)] = which
                off = CA_SIG_OFFSETS[which] + off - 2
        else:
            # in the signed region, where nothing else reads it: the
            # chain hash, the fees or the alias; never an scid (an
            # update would lose its channel), a length or a key
            off = {"ca": 260, "cu": 122, "na": 108}[name] \
                + int(rng.integers(0, 8))
        arr[row, off] ^= np.uint8(1 << int(rng.integers(0, 8)))
        bad[name].append(row)
    return {k: sorted(v) for k, v in bad.items()}, ca_sig


def sha256_16(path: str) -> str:
    """The first 16 hex digits of a file's sha256: what a run says of
    the inputs it was fed."""
    with open(path, "rb") as f:
        return hashlib.file_digest(f, "sha256").hexdigest()[:16]


def cached_store(cache_dir: str, config: str, graph: dict, seed: int, *,
                 signed: bool, bad_records: int) -> tuple[str, dict]:
    """(path, ground truth) of a configuration's store for a seed,
    generated once per (config, graph, seed, signed, bad_records) under
    cache_dir; the graph's digest is in the name, so a store made
    before a size or a law's parameter was edited is never served.
    Every run of a check brings a new seed, so only the KEEP_STORES
    newest are kept."""
    os.makedirs(cache_dir, exist_ok=True)
    digest = hashlib.sha256(
        json.dumps(graph, sort_keys=True).encode()).hexdigest()[:8]
    stem = os.path.join(cache_dir, "store-%s-%s-%d-%s-%d" % (
        config, digest, seed, "signed" if signed else "plain",
        bad_records))
    if os.path.isfile(stem + ".gs") and os.path.isfile(stem + ".json"):
        with open(stem + ".json", encoding="utf8") as f:
            return stem + ".gs", json.load(f)
    truth = make_store(stem + ".gs.tmp", graph=graph, seed=seed,
                       sign=signed, bad_records=bad_records)
    with open(stem + ".json", "w", encoding="utf8") as f:
        json.dump(truth, f)
    os.replace(stem + ".gs.tmp", stem + ".gs")
    stores = sorted((p for p in os.listdir(cache_dir) if p.endswith(".gs")),
                    key=lambda p: os.path.getmtime(
                        os.path.join(cache_dir, p)))
    for old in stores[:-KEEP_STORES]:
        for ext in (".gs", ".json"):
            try:
                os.unlink(os.path.join(cache_dir, old[:-3] + ext))
            except OSError:
                pass
    return stem + ".gs", truth
