"""Streaming replay-pipeline tests: bucket planner invariants, the
host-prep/device-compute overlap contract (clntpu_replay_* metrics),
the device-resident z handoff, and parity with the host oracle.

Named test_zz_* to sort LAST: the overlap test drives a 25k-row
synthetic replay and the tier-1 runner has a hard wall-clock budget —
heavy tests mid-alphabet displace cheaper tests past the cutoff.

The overlap contract (ISSUE 2 acceptance): host prep wall time must be
≤ 20% VISIBLE on the end-to-end critical path with the double-buffered
pipeline, vs ≥ 90% visible in the serial baseline.  "Visible" is the
clntpu_replay_prep_stall_seconds_total counter — dispatch-thread time
spent waiting on the prepared-bucket queue (== all of prep when
serial).  The device side is a stub dispatcher so the assertion holds
on any backend: it measures the pipeline MACHINERY, which is exactly
what the issue asks to demonstrate ("measurable via obs counters on
any backend").
"""
from __future__ import annotations

import functools
import hashlib
import time

import numpy as np
import pytest

from lightning_tpu import obs
from lightning_tpu.crypto import ref_python as ref
from lightning_tpu.crypto import secp256k1 as S
from lightning_tpu.gossip import verify
from lightning_tpu.obs import attribution


@functools.lru_cache(maxsize=1)
def _signed_batch27():
    from lightning_tpu.gossip import synth

    return synth.make_signed_batch(27)


def _counter(snap: dict, name: str) -> float:
    fam = snap["metrics"].get(name, {"samples": []})
    return sum(s["value"] for s in fam["samples"])


def _hist(snap: dict, name: str) -> tuple[float, float]:
    fam = snap["metrics"].get(name, {"samples": []})
    return (sum(s["count"] for s in fam["samples"]),
            sum(s["sum"] for s in fam["samples"]))


# ---------------------------------------------------------------------------
# bucket planner


def test_plan_buckets_self_contained():
    """Every bucket: ≤ bucket sigs, ≤ bucket rows, rows cover its sigs."""
    rng = np.random.default_rng(3)
    # CA-style fan-out: 4 sigs per row, row-sorted
    roi = np.sort(np.tile(np.arange(1000, dtype=np.int64), 4))
    chunks = verify._plan_buckets(roi, 64)
    covered = 0
    for start, end, r0, r1 in chunks:
        assert end - start <= 64
        assert r1 - r0 <= 64
        assert int(roi[start]) >= r0 and int(roi[end - 1]) < r1
        covered += end - start
    assert covered == len(roi)


def test_plan_buckets_row_straddle_is_safe():
    """A row whose sigs straddle a cut appears in both buckets' row
    ranges (hashed twice, never mis-gathered)."""
    roi = np.sort(np.tile(np.arange(6, dtype=np.int64), 4))  # 24 sigs
    chunks = verify._plan_buckets(roi, 10)
    for start, end, r0, r1 in chunks:
        assert {int(x) for x in roi[start:end]} <= set(range(r0, r1))


def test_plan_buckets_sparse_rows():
    """Signatures referencing far-apart rows force row-span cuts."""
    roi = np.array([0, 1, 900, 901, 902, 1800], dtype=np.int64)
    chunks = verify._plan_buckets(roi, 8)
    assert [c[:2] for c in chunks] == [(0, 2), (2, 5), (5, 6)]
    for start, end, r0, r1 in chunks:
        assert r1 - r0 <= 8


# ---------------------------------------------------------------------------
# overlap contract (stub device, any backend)


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


def _stub_device(sleep_s: float):
    def dispatch(pb):
        if sleep_s:
            time.sleep(sleep_s)
        return np.ones(pb.blocks.shape[0], bool)

    return dispatch


def test_overlap_metrics_25k_row_replay():
    items = _synthetic_items(25_000)
    bucket = 512  # 49 buckets

    # serial baseline (depth 0): prep is inline on the dispatch thread,
    # so ALL of it is visible on the critical path
    s0 = obs.snapshot()
    ok = verify.verify_items(items, bucket=bucket, depth=0,
                             device_fn=_stub_device(0.0))
    s1 = obs.snapshot()
    assert ok.all() and len(ok) == 25_000
    prep = _counter(s1, "clntpu_replay_prep_seconds_total") - \
        _counter(s0, "clntpu_replay_prep_seconds_total")
    stall = _counter(s1, "clntpu_replay_prep_stall_seconds_total") - \
        _counter(s0, "clntpu_replay_prep_stall_seconds_total")
    assert prep > 0
    assert stall >= 0.9 * prep, (stall, prep)

    # overlapped pipeline (double-buffered): device time per bucket is
    # 4× the measured average prep, so prep has ample room to hide
    # behind it.  The assertion is about thread scheduling on a 1-core
    # box, so allow a couple of attempts before calling it a failure —
    # a single preempted producer wakeup must not fail the gate.
    n_chunks = len(verify._plan_buckets(np.arange(25_000), bucket))
    sleep = max(4.0 * prep / n_chunks, 0.005)
    last = None
    for _attempt in range(3):
        s2 = obs.snapshot()
        ok = verify.verify_items(items, bucket=bucket, depth=2,
                                 device_fn=_stub_device(sleep))
        s3 = obs.snapshot()
        assert ok.all()
        prep2 = _counter(s3, "clntpu_replay_prep_seconds_total") - \
            _counter(s2, "clntpu_replay_prep_seconds_total")
        stall2 = _counter(s3, "clntpu_replay_prep_stall_seconds_total") - \
            _counter(s2, "clntpu_replay_prep_stall_seconds_total")
        dispatch2 = _counter(s3,
                             "clntpu_replay_dispatch_seconds_total") - \
            _counter(s2, "clntpu_replay_dispatch_seconds_total")
        assert prep2 > 0
        # non-timing invariants hold on every attempt: one overlap
        # observation per replay, one queue-depth sample per bucket
        cnt_a, sum_a = _hist(s2, "clntpu_replay_overlap_ratio")
        cnt_b, sum_b = _hist(s3, "clntpu_replay_overlap_ratio")
        assert cnt_b == cnt_a + 1
        qcnt_a, _ = _hist(s2, "clntpu_replay_queue_depth")
        qcnt_b, _ = _hist(s3, "clntpu_replay_queue_depth")
        assert qcnt_b - qcnt_a == n_chunks
        # the acceptance numbers: ≤ 20% of host prep visible overlapped
        # (≥ 90% visible serial, above), and invisible relative to the
        # e2e critical path too (the dispatch thread spent its time in
        # device waits, not prep waits)
        last = (stall2, prep2, dispatch2, sum_b - sum_a)
        if (stall2 <= 0.2 * prep2
                and stall2 <= 0.2 * (stall2 + dispatch2)
                and (sum_b - sum_a) >= 0.8):
            break
    else:
        raise AssertionError(f"overlap never reached the 80% hidden "
                             f"contract in 3 runs: {last}")


def test_pipeline_propagates_prep_errors():
    """A producer-thread failure must surface on the caller, not hang."""
    items = _synthetic_items(64)
    # corrupt: pubkey table shorter than the signature count, so a
    # later bucket's prep gather raises on the producer thread
    items.pubkeys = items.pubkeys[:10]

    import pytest

    with pytest.raises(Exception):
        verify.verify_items(items, bucket=8, depth=2,
                            device_fn=_stub_device(0.0))


def test_pipeline_survives_device_errors():
    """A dispatch failure mid-stream must not deadlock the producer —
    and since the resilience layer (doc/resilience.md), it must not
    fail the replay either: the failing bucket bisects, the transient
    error clears on re-dispatch, and the replay completes with the
    failure recorded against the verify breaker."""
    from lightning_tpu.resilience import breaker as RB

    RB.reset_for_tests()
    items = _synthetic_items(64)
    calls = []

    def bad_dispatch(pb):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("device fell over")
        return np.ones(pb.blocks.shape[0], bool)

    s0 = obs.snapshot()
    ok = verify.verify_items(items, bucket=8, depth=2,
                             device_fn=bad_dispatch)
    s1 = obs.snapshot()
    assert ok.all() and len(ok) == 64

    def _brk_failures(snap):
        fam = snap["metrics"].get("clntpu_breaker_failures_total",
                                  {"samples": []})
        return sum(s["value"] for s in fam["samples"]
                   if s["labels"].get("family") == "verify")

    assert _brk_failures(s1) == _brk_failures(s0) + 1
    RB.reset_for_tests()


# ---------------------------------------------------------------------------
# device-resident z handoff (real fused program)


def test_z_handoff_stays_on_device():
    """Zero z bytes cross the host boundary between the hash and verify
    phases: the whole fused dispatch runs under a device→host transfer
    guard, and the staged-bytes counter accounts for every uploaded
    byte — a z readback + re-upload (the pre-round-5 sync point) would
    both trip the guard and inflate the exact byte count."""
    import jax

    # n=27 everywhere in the zz device tests: each distinct batch size
    # costs its own sign/derive-pubkey program shape, and the compile
    # cache is read-only under pytest
    n = 27
    rows, nb, sigs, pubs = _signed_batch27()
    items = verify.VerifyItems(rows, nb, sigs, pubs,
                               np.arange(n, dtype=np.int64))
    real = verify._fused_device_fn(8)

    def guarded(pb):
        with jax.transfer_guard_device_to_host("disallow"):
            return real(pb)

    s0 = obs.snapshot()
    ok = verify.verify_items(items, bucket=8, depth=2, device_fn=guarded)
    s1 = obs.snapshot()
    assert ok.all()
    # a transfer-guard trip would no longer propagate (the resilience
    # layer would bisect + host-recover it) — it would show up here
    def _fails(snap):
        fam = snap["metrics"].get("clntpu_breaker_failures_total",
                                  {"samples": []})
        return sum(s["value"] for s in fam["samples"]
                   if s["labels"].get("family") == "verify")

    assert _fails(s1) == _fails(s0), \
        "device dispatch failed under the transfer guard"

    staged = _counter(s1, "clntpu_verify_device_bytes_total") - \
        _counter(s0, "clntpu_verify_device_bytes_total")
    mb = 4  # 130-byte regions → 3 SHA blocks → quantized width 4
    per_bucket = 8 * (mb * 16 * 4 + 4 + 4 + 64 + 33)
    assert staged == 4 * per_bucket, staged


# ---------------------------------------------------------------------------
# the fused pipeline's verdicts against the host oracle


def _row_hash(row: np.ndarray, n_blocks: int) -> bytes:
    """sha256d of the signed region a sha-padded row holds (its length
    is the 64-bit bit count that closes block n_blocks-1)."""
    bitlen = int.from_bytes(
        row[n_blocks * 64 - 8: n_blocks * 64].tobytes(), "big")
    msg = row[: bitlen // 8].tobytes()
    return hashlib.sha256(hashlib.sha256(msg).digest()).digest()


def _oracle(items: verify.VerifyItems) -> np.ndarray:
    """S._host_verify over hashlib's hashes: exact-int ECDSA that
    shares nothing with the device programs."""
    z = np.stack([
        np.frombuffer(_row_hash(items.rows[r], int(items.n_blocks[r])),
                      np.uint8) for r in items.row_of_item])
    return S._host_verify(z, items.sigs, items.pubkeys)


_SHARED_ROW = 12    # items 12..15 all sign row 12, as a
                    # channel_announcement's four signatures share one


@functools.lru_cache(maxsize=1)
def _shared_row_batch():
    """The shared batch with four signatures over one row by four keys
    (signed on the host: no device program beyond the file's own)."""
    rows, nb, sigs, pubs = _signed_batch27()
    sigs, pubs = sigs.copy(), pubs.copy()
    roi = np.arange(27, dtype=np.int64)
    z = _row_hash(rows[_SHARED_ROW], int(nb[_SHARED_ROW]))
    for k in range(4):
        key = 0x5eed0000 + k
        r, s_ = ref.ecdsa_sign(z, key)
        i = _SHARED_ROW + k
        roi[i] = _SHARED_ROW
        sigs[i] = np.frombuffer(
            r.to_bytes(32, "big") + s_.to_bytes(32, "big"), np.uint8)
        pubs[i] = np.frombuffer(
            ref.pubkey_serialize(ref.pubkey_create(key)), np.uint8)
    return rows, nb, sigs, pubs, roi


def _case_items(case: str) -> tuple[verify.VerifyItems, list[int]]:
    """(items, the signature indices that must fail) for a named case."""
    if case.startswith("shared_row"):
        rows, nb, sigs, pubs, roi = _shared_row_batch()
    else:
        rows, nb, sigs, pubs = _signed_batch27()
        roi = np.arange(27, dtype=np.int64)
    rows, sigs, pubs = rows.copy(), sigs.copy(), pubs.copy()
    bad: list[int] = []
    if case == "bad_sig":
        sigs[5, 10] ^= 0x40
        bad = [5]
    elif case == "bad_msg_byte":
        rows[7, 3] ^= 0x01
        bad = [7]
    elif case == "bad_pubkey_tag":
        pubs[9, 0] = 0x04
        bad = [9]
    elif case.startswith("shared_row_flip"):
        pos = int(case[-1])
        sigs[_SHARED_ROW + pos, 40] ^= 0x01
        bad = [_SHARED_ROW + pos]
    return verify.VerifyItems(rows, nb, sigs, pubs,
                              np.arange(27, dtype=np.int64),
                              row_of_item=roi), bad


@pytest.mark.parametrize("case,depth", [
    ("clean", 2), ("bad_sig", 2), ("bad_msg_byte", 2),
    ("bad_pubkey_tag", 2), ("shared_row_clean", 2),
    ("shared_row_flip0", 2), ("shared_row_flip1", 2),
    ("shared_row_flip2", 2), ("shared_row_flip3", 2), ("bad_sig", 0),
])
def test_fused_matches_host_oracle(case, depth):
    items, bad = _case_items(case)
    ok = verify.verify_items(items, bucket=8, depth=depth)
    assert ok.dtype == np.bool_
    expected = np.ones(27, bool)
    expected[bad] = False
    assert (ok == expected).all()
    assert (ok == _oracle(items)).all()


# ---------------------------------------------------------------------------
# warm-up compiles the programs the replay dispatches, and no other


@pytest.mark.parametrize("dead_name_set", [False, True])
def test_warmup_notes_only_the_fused_shapes(monkeypatch, dead_name_set):
    if dead_name_set:
        monkeypatch.setenv("LIGHTNING_TPU_REPLAY_FUSED", "0")
    bucket = 8
    noted = []
    monkeypatch.setattr(verify, "_note_shape",
                        lambda program, key: noted.append((program, key)))
    monkeypatch.setattr(
        verify, "_jit_fused",
        lambda: lambda blocks, nb, roi, sigs, pubs: np.zeros(bucket, bool))
    monkeypatch.setattr(
        S, "_jit_sign", lambda: lambda z, d, ks: (np.zeros(1, np.uint32),))
    # one device: no mesh to warm
    monkeypatch.setattr(verify, "_mesh_device_fn", lambda *a, **kw: None)
    # warmup() arms the process's retrace detector: put it back after
    monkeypatch.setattr(attribution, "_armed", attribution._armed)

    verify.warmup(bucket)

    assert noted == [("fused", (bucket, 4)),
                     ("fused", (bucket, verify.MAX_BLOCKS)),
                     ("sign", (S.SIGN_BUCKET,))]


# ---------------------------------------------------------------------------
# stage counters move bucket by bucket (doc/replay_pipeline.md)

_STAGE_COUNTERS = ("clntpu_replay_prep_seconds_total",
                   "clntpu_replay_prep_stall_seconds_total",
                   "clntpu_replay_dispatch_seconds_total",
                   "clntpu_replay_sigs_total",
                   "clntpu_verify_device_bytes_total")


def _lanes(snap: dict, kind: str) -> float:
    fam = snap["metrics"].get("clntpu_verify_lanes_total", {"samples": []})
    return sum(s["value"] for s in fam["samples"]
               if s["labels"].get("kind") == kind)


def test_stage_counters_move_between_buckets_two_passes():
    """Two passes over one set of items: inside a pass every stage
    counter, the lane and byte counters and clntpu_replay_sigs_total
    have moved by the time the next bucket is dispatched (a window cut
    mid-pass reads them whole), and each pass's sums are what the
    once-a-pass bookkeeping gave: lanes = buckets x bucket, bytes = the
    buckets' staged bytes, signatures = N, one clntpu_verify_batch_sigs
    observation of N."""
    n, bucket = 1000, 128
    items = _synthetic_items(n)
    n_buckets = len(verify._plan_buckets(np.arange(n, dtype=np.int64),
                                         bucket))
    assert n_buckets == 8

    for depth in (2, 0):                  # streamed, then serial
        seen: list[dict] = []             # the counters at each dispatch
        staged: list[int] = []

        def device(pb):
            seen.append(obs.snapshot())
            staged.append(pb.staged_bytes)
            return np.ones(pb.blocks.shape[0], bool)

        before = obs.snapshot()
        ok = verify.verify_items(items, bucket=bucket, depth=depth,
                                 device_fn=device)
        after = obs.snapshot()
        assert ok.all() and len(seen) == n_buckets

        # at the k-th dispatch the k buckets before it are counted
        for k, snap in enumerate(seen):
            assert _counter(snap, "clntpu_replay_sigs_total") \
                - _counter(before, "clntpu_replay_sigs_total") \
                == k * bucket
            assert _lanes(snap, "verify") - _lanes(before, "verify") \
                == k * bucket
            assert _counter(snap, "clntpu_verify_device_bytes_total") \
                - _counter(before, "clntpu_verify_device_bytes_total") \
                == sum(staged[:k])
        mid = seen[n_buckets // 2]
        for name in ("clntpu_replay_prep_seconds_total",
                     "clntpu_replay_dispatch_seconds_total"):
            assert _counter(before, name) < _counter(mid, name) \
                < _counter(after, name), name
        # the per-replay histograms still observe once, at the end
        assert _hist(mid, "clntpu_verify_batch_sigs") \
            == _hist(before, "clntpu_verify_batch_sigs")

        # the pass's sums, as the parent's end-of-pass increments gave
        assert _counter(after, "clntpu_replay_sigs_total") \
            - _counter(before, "clntpu_replay_sigs_total") == n
        for kind in ("verify", "hash"):
            assert _lanes(after, kind) - _lanes(before, kind) \
                == n_buckets * bucket
        assert _counter(after, "clntpu_verify_device_bytes_total") \
            - _counter(before, "clntpu_verify_device_bytes_total") \
            == sum(staged)
        c0, s0 = _hist(before, "clntpu_verify_batch_sigs")
        c1, s1 = _hist(after, "clntpu_verify_batch_sigs")
        assert (c1 - c0, s1 - s0) == (1, n)
        oc0, _ = _hist(before, "clntpu_replay_overlap_ratio")
        oc1, _ = _hist(after, "clntpu_replay_overlap_ratio")
        assert oc1 - oc0 == 1
        assert _counter(after, "clntpu_replay_readback_seconds_total") \
            > _counter(before, "clntpu_replay_readback_seconds_total")
        if depth == 0:        # serial: all prep is visible as stall
            d_prep = _counter(after, _STAGE_COUNTERS[0]) \
                - _counter(before, _STAGE_COUNTERS[0])
            d_stall = _counter(after, _STAGE_COUNTERS[1]) \
                - _counter(before, _STAGE_COUNTERS[1])
            assert abs(d_prep - d_stall) < 1e-9


def test_replay_sort_and_stream_spans():
    """replay/sort covers the planning before the first bucket;
    replay/stream is the dispatch section, from the producer's start to
    the last dispatch returned, so it holds every verify/dispatch and
    no replay/sort or replay/readback."""
    from lightning_tpu.utils import trace

    records: list[dict] = []
    trace.add_tap(records.append)
    try:
        verify.verify_items(_synthetic_items(1000), bucket=128, depth=2,
                            device_fn=_stub_device(0.001))
    finally:
        trace.remove_tap(records.append)
    one = {n: [r for r in records if r["name"] == n]
           for n in ("replay/sort", "replay/stream", "replay/readback",
                     "verify/dispatch")}
    assert [len(one[n]) for n in ("replay/sort", "replay/stream",
                                  "replay/readback")] == [1, 1, 1]
    stream = one["replay/stream"][0]
    s0, s1 = stream["start_ns"], stream["start_ns"] + stream["duration_ns"]
    assert len(one["verify/dispatch"]) == 8
    for d in one["verify/dispatch"]:
        assert d["parent"] == "replay/stream"
        assert s0 <= d["start_ns"] and d["start_ns"] + d["duration_ns"] <= s1
    sort = one["replay/sort"][0]
    assert sort["start_ns"] + sort["duration_ns"] <= s0
    assert one["replay/readback"][0]["start_ns"] >= s1
    assert stream["attributes"] == {"buckets": 8}


# ---------------------------------------------------------------------------
# lanes per dispatch: a boot replay's bucket comes from the platform,
# the live path's stays DEFAULT_BUCKET


@pytest.mark.parametrize("env,backend,expected", [
    ("8", "cpu", 8),            # the environment wins on any platform
    ("8", "tpu", 8),
    (None, "cpu", 64),
    (None, "tpu", "REPLAY_BUCKET_TPU"),
    (None, "gpu", 64),          # only the platform that was swept
])
def test_replay_bucket_rule(monkeypatch, env, backend, expected):
    """replay_bucket() by environment and platform, and verify_store
    hands exactly that down (an explicit bucket= still wins)."""
    import jax

    from lightning_tpu.gossip import store as gstore

    if env is None:
        monkeypatch.delenv("LIGHTNING_TPU_VERIFY_BUCKET", raising=False)
    else:
        monkeypatch.setenv("LIGHTNING_TPU_VERIFY_BUCKET", env)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expected == "REPLAY_BUCKET_TPU":
        expected = verify.REPLAY_BUCKET_TPU
        assert expected > 64 and expected & (expected - 1) == 0
    assert verify.replay_bucket() == expected

    seen = []

    def items_spy(items, bucket, **kw):
        seen.append(bucket)
        return np.zeros(len(items), bool)

    monkeypatch.setattr(verify, "verify_items", items_spy)
    verify.verify_store(gstore._empty_index())
    verify.verify_store(gstore._empty_index(), bucket=16)
    assert seen == [expected, 16]


@functools.lru_cache(maxsize=1)
def _live_defaults_without_the_knob() -> dict:
    """The live path's defaults as a process without
    LIGHTNING_TPU_VERIFY_BUCKET sees them (this process was started
    with 8, tests/conftest.py), and whether importing the modules
    started a jax backend."""
    import json
    import os
    import subprocess
    import sys

    code = (
        "import inspect, json\n"
        "from lightning_tpu.crypto import secp256k1 as S\n"
        "from lightning_tpu.gossip import verify, ingest, gossipd\n"
        "from jax._src import xla_bridge\n"
        "d = lambda f, a='bucket': inspect.signature(f).parameters[a].default\n"
        "print(json.dumps({\n"
        " 'S.VERIFY_BUCKET': S.VERIFY_BUCKET,\n"
        " 'verify.DEFAULT_BUCKET': verify.DEFAULT_BUCKET,\n"
        " 'verify.warmup': d(verify.warmup),\n"
        " 'verify.verify_items': d(verify.verify_items),\n"
        " 'GossipIngest': d(ingest.GossipIngest.__init__),\n"
        " 'Gossipd': d(gossipd.Gossipd.__init__),\n"
        " 'verify_store': d(verify.verify_store),\n"
        " 'backend_started': xla_bridge.backends_are_initialized()}))\n")
    env = {k: v for k, v in os.environ.items()
           if k != "LIGHTNING_TPU_VERIFY_BUCKET"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, text=True, check=True,
        capture_output=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name,expected", [
    ("S.VERIFY_BUCKET", 64), ("verify.DEFAULT_BUCKET", 64),
    ("verify.warmup", 64), ("verify.verify_items", 64),
    ("GossipIngest", 64),
    ("Gossipd", None),              # None there means DEFAULT_BUCKET
    ("verify_store", None),         # None there means replay_bucket()
    ("backend_started", False),     # the rule is not asked at import
])
def test_live_path_defaults_stay_64(name, expected):
    assert _live_defaults_without_the_knob()[name] == expected


# the shared-row batch's signatures by layout: which of the 27 a replay
# carries, in an order that puts the four signatures over row 12 where
# the bucket of 8 cuts them (or, for `padded`, leaves one dispatch with
# a pad lane).  The rows stay whole; only the signatures are selected.
_LAYOUTS = {
    "padded": (range(10, 17), None),     # 7 signatures: one dispatch
    "cut_1_3": (range(5, 27), 1),        # 7 singles, then row 12
    "cut_2_2": (range(6, 27), 2),
    "cut_3_1": (range(7, 27), 3),
}


@pytest.mark.parametrize("flip", [None, 0, 1, 2, 3])
@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_replay_bucket_cuts_and_pads_match_host_oracle(layout, flip):
    """A replay whose bucket is larger than its store (one padded
    dispatch) and replays whose bucket cuts a channel_announcement's
    four signatures 1/3, 2/2 and 3/1 across two dispatches: with a bad
    signature planted on each of the four positions in turn, the
    verdicts equal the host oracle's bit for bit, the dispatches are
    the planner's and the lane counter moves by bucket x dispatches."""
    bucket = 8
    sel, first_part = _LAYOUTS[layout]
    sel = np.asarray(sel)
    rows, nb, sigs, pubs, roi = _shared_row_batch()
    sigs = sigs.copy()
    if flip is not None:
        sigs[_SHARED_ROW + flip, 40] ^= 0x01
    items = verify.VerifyItems(rows, nb, sigs[sel], pubs[sel],
                               np.arange(len(sel), dtype=np.int64),
                               row_of_item=roi[sel])
    plan = verify._plan_buckets(np.sort(roi[sel]), bucket)
    if first_part is None:
        assert len(plan) == 1 and len(sel) < bucket
    else:
        # the first cut falls inside the run of four over the shared row
        assert plan[0][1] == bucket and plan[1][0] == bucket
        shared = np.nonzero(np.sort(roi[sel]) == _SHARED_ROW)[0]
        assert (shared < bucket).sum() == first_part
        assert (shared >= bucket).sum() == 4 - first_part

    s0 = obs.snapshot()
    ok = verify.verify_items(items, bucket=bucket, depth=2)
    s1 = obs.snapshot()

    expected = np.ones(len(sel), bool)
    if flip is not None:
        expected[list(sel).index(_SHARED_ROW + flip)] = False
    assert (ok == expected).all()
    assert (ok == _oracle(items)).all()
    assert _counter(s1, "clntpu_replay_buckets_total") \
        - _counter(s0, "clntpu_replay_buckets_total") == len(plan)
    assert _lanes(s1, "verify") - _lanes(s0, "verify") == bucket * len(plan)
