"""Tracing span tests (common/trace.c span semantics)."""
from __future__ import annotations

import json
import threading
import time

import pytest

from lightning_tpu.utils import trace


@pytest.fixture(autouse=True)
def _clean():
    trace.set_sink(None)
    trace.reset()
    yield
    trace.set_sink(None)
    trace.reset()


def test_nested_spans_record_parentage():
    with trace.span("outer"):
        with trace.span("inner", n=3):
            time.sleep(0.01)
    recs = trace.records()
    assert [r["name"] for r in recs] == ["inner", "outer"]
    inner, outer = recs
    assert inner["parent"] == "outer"
    assert outer["parent"] is None
    assert inner["attributes"] == {"n": 3}
    assert inner["duration_ns"] >= 10_000_000
    assert outer["duration_ns"] >= inner["duration_ns"]


def test_error_annotated_and_reraised():
    with pytest.raises(ValueError):
        with trace.span("boom"):
            raise ValueError("x")
    assert trace.records()[0]["error"] == "ValueError"


def test_file_sink(tmp_path):
    p = str(tmp_path / "trace.jsonl")
    trace.set_sink(p)
    with trace.span("to-file"):
        pass
    trace.set_sink(None)
    lines = [json.loads(x) for x in open(p)]
    assert lines and lines[0]["name"] == "to-file"
    assert trace.records() == []   # sink bypasses the ring


def test_summarize():
    for _ in range(3):
        with trace.span("phase/a"):
            pass
    with trace.span("phase/b"):
        pass
    s = trace.summarize()
    assert s["phase/a"]["count"] == 3
    assert s["phase/b"]["count"] == 1
    assert s["phase/a"]["total_ms"] >= 0


def test_concurrent_emit_is_lossless():
    """Ring append/prune from flush loops + producer threads + main
    thread must not lose records (the list mutation race ISSUE 5 fixed
    with the module lock)."""
    N_THREADS, PER = 8, 200

    def worker():
        for _ in range(PER):
            with trace.span("race/worker"):
                pass

    threads = [threading.Thread(target=worker)
               for _ in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = [r for r in trace.records() if r["name"] == "race/worker"]
    assert len(recs) == N_THREADS * PER
    assert len({r["span_id"] for r in recs}) == N_THREADS * PER


def test_concurrent_taps_and_sink_swaps(tmp_path):
    """Tap add/remove and set_sink races against emitting threads must
    neither raise nor deadlock — in particular, rotating FILE sinks
    must never close the file out from under a concurrent write (the
    sink runs under the module lock)."""
    stop = threading.Event()
    seen = []
    failed = []

    def emitter():
        try:
            while not stop.is_set():
                with trace.span("race/emit"):
                    pass
        except BaseException as e:   # pragma: no cover - the regression
            failed.append(e)

    th = threading.Thread(target=emitter)
    th.start()
    try:
        for i in range(100):
            trace.add_tap(seen.append)
            trace.set_sink(str(tmp_path / f"sink{i % 2}.jsonl"))
            trace.set_sink(lambda rec: None)
            trace.set_sink(None)
            trace.remove_tap(seen.append)
    finally:
        stop.set()
        th.join()
    assert not failed, failed
    assert all(r["name"] == "race/emit" for r in seen)


def test_set_sink_crash_safe(tmp_path):
    """A failing open() must still close the PREVIOUS file sink, and
    records then fall back to the in-memory ring."""
    p = str(tmp_path / "trace.jsonl")
    trace.set_sink(p)
    f = trace._file
    assert f is not None and not f.closed
    with pytest.raises(OSError):
        trace.set_sink(str(tmp_path / "no-such-dir" / "t.jsonl"))
    assert f.closed
    assert trace._file is None
    with trace.span("after-crash"):
        pass
    assert [r["name"] for r in trace.records()] == ["after-crash"]


def test_corr_carrier_links_across_threads():
    """new_corr() inside the enqueue span stamps it; a worker thread
    opening spans with corr= shares the id (contextvars would not)."""
    with trace.span("enqueue") as sp:
        corr = trace.new_corr()
    out = {}

    def worker():
        with trace.span("dispatch", corr=corr):
            pass
        out["tid"] = threading.get_native_id()

    th = threading.Thread(target=worker)
    th.start()
    th.join()
    by_name = {r["name"]: r for r in trace.records()}
    enq, disp = by_name["enqueue"], by_name["dispatch"]
    assert enq["corr_id"] == disp["corr_id"] == corr.corr_id
    assert enq["span_id"] == corr.span_id
    assert disp["tid"] == out["tid"] != enq["tid"]
    assert disp["parent_id"] is None   # no fake same-thread parentage


def test_instrumented_paths_emit():
    """The hsmd batch signer emits a span."""
    from lightning_tpu.daemon.hsmd import CAP_MASTER, Hsm
    from lightning_tpu.crypto import ref_python as ref

    hsm = Hsm(b"\x11" * 32)
    client = hsm.client(CAP_MASTER, b"", dbid=1)
    point = ref.pubkey_create(5)
    hsm.sign_htlc_batch(client, [b"\xab" * 32], point)
    names = [r["name"] for r in trace.records()]
    assert "hsmd/sign_htlc_batch" in names


def _host_events(trace_dir):
    """Every event on the host planes of the newest xplane under
    trace_dir: [(name, start_ns, duration_ns)]."""
    import glob
    import os

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert paths, "the profile session wrote no xplane"
    return [(ev.name, int(ev.start_ns), int(ev.duration_ns))
            for plane in ProfileData.from_file(paths[-1]).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events]


def test_span_is_a_trace_annotation_inside_a_profile_session(
        tmp_path, monkeypatch):
    """One primitive: inside profile_session() a span is also a
    TraceAnnotation of the same name, once, on the host plane, and the
    session runs without Python's own tracer (no event for the Python
    function the span wraps)."""
    def marker_fn_for_python_tracer():
        return sum(range(10))

    monkeypatch.setenv("LIGHTNING_TPU_PROFILE", str(tmp_path))
    with trace.profile_session():
        assert trace._profile_active
        with trace.span("test/in_session", n=1):
            marker_fn_for_python_tracer()
            time.sleep(0.002)
    assert not trace._profile_active
    with trace.span("test/after_session"):
        pass
    events = _host_events(str(tmp_path))
    mine = [e for e in events if e[0] == "test/in_session"]
    assert len(mine) == 1
    rec = next(r for r in trace.records()
               if r["name"] == "test/in_session")
    # the annotation lies inside the span's own clock readings' reach
    assert 0 < mine[0][2] <= rec["duration_ns"] + 1_000_000
    names = {e[0] for e in events}
    assert "test/after_session" not in names
    assert not any("marker_fn_for_python_tracer" in n for n in names)


def test_span_outside_a_session_never_touches_the_profiler():
    """With no session a span costs one boolean test: a fresh
    interpreter that opens spans has imported neither jax nor its
    profiler, and profile_session() without the knob is a no-op."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from lightning_tpu.utils import trace\n"
        "with trace.profile_session():\n"
        "    with trace.span('a/b'):\n"
        "        pass\n"
        "assert trace.records()[0]['name'] == 'a/b'\n"
        "assert not trace._profile_active\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "assert 'jax.profiler' not in sys.modules\n"
        "assert not hasattr(trace, 'annotation')\n"
        "assert not hasattr(trace, 'device_span')\n")
    import os
    env = {k: v for k, v in os.environ.items()
           if k != "LIGHTNING_TPU_PROFILE"}
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-c", code], check=True, cwd=root,
                   env=env)


def test_span_object_carries_its_duration():
    """`with span(...) as sp` reads the clock its record has (the
    route flush fills the flight record's stage fields from it)."""
    with trace.span("timed") as sp:
        time.sleep(0.005)
    assert sp.duration_ns == trace.records()[0]["duration_ns"] >= 5_000_000
