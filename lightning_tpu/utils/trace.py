"""Tracing spans: lightweight structured profiling of daemon phases.

Functional parity target: common/trace.c (trace_span_start/end/
suspend/resume emitting USDT probes consumed by contrib/cln-tracer) —
re-targeted: spans emit JSON lines (one object per completed span) to a
sink, and — the TPU twist — while a `jax.profiler` session is open
(`profile_session()`) every span is also a TraceAnnotation, so
host-side phases lie beside the device timeline.

Usage:
    from lightning_tpu.utils import trace
    with trace.span("gossip/verify", batch=4096):
        ...
    trace.set_sink(path_or_callable)   # default: in-memory ring

Spans nest via a contextvar; each record carries its parent's name (and
span id) so a flame view can be reconstructed.  Suspend/resume (for
spans crossing an await) are modeled by `span()` measuring wall time
only between enter and exit — matching trace.c's span lifetime
semantics.

Cross-thread correlation (doc/tracing.md): contextvars do not follow
work onto producer threads or flush loops, so causality is carried by
an EXPLICIT ``Carrier`` object instead.  ``new_corr()`` mints one
inside the enqueue span (stamping that span with the correlation id);
the carrier rides the queue item / batch to wherever the work is
dispatched, and every downstream span opened with ``corr=carrier``
shares the id.  The exporter (obs/traceexport.py) turns each
correlation id into a Perfetto flow arrow chain, linking the enqueue
span to its prep/dispatch/readback spans across threads.  Every record
carries ``span_id``/``parent_id``/``tid``/``thread``; spans on a
dispatch path additionally carry ``corr_ids`` (plus ``corr_id``, the
first) and ``dispatch_id``.
"""
from __future__ import annotations

import contextvars
import itertools
import json
import os
import threading
import time
from contextlib import contextmanager

_current = contextvars.ContextVar("trace_span", default=None)

_records: list[dict] = []        # guarded-by: _lock
_MAX_RECORDS = 10_000
_sink = None          # guarded-by: _lock  (None → ring; else callable)
_file = None          # guarded-by: _lock
# taps see EVERY record regardless of the sink (the obs collector feeds
# span-duration histograms from here; a tap must never raise into the
# traced code path)
_taps: list = []                 # guarded-by: _lock
# one lock for ring + taps + sink swaps: flush loops, the replay
# producer thread, and the main thread all emit concurrently, and a
# bare list append/prune pair is a lost-update race under free threading
_lock = threading.RLock()

_span_ids = itertools.count(1)
_corr_ids = itertools.count(1)

# spans on a big coalesced dispatch can carry hundreds of carriers; cap
# what a single record stores so the ring stays bounded (the flow chain
# for capped-out carriers simply starts at the flush span)
CORR_CAP = 32


class Carrier:
    """Explicit correlation context — the cross-thread causality token.

    Mint with ``new_corr()`` at the enqueue point; pass by reference to
    the thread/loop doing the work; open downstream spans with
    ``corr=carrier``.  Deliberately NOT a contextvar: the whole point
    is to survive hops contextvars cannot follow."""

    __slots__ = ("corr_id", "span_id")

    def __init__(self, corr_id: int, span_id: int):
        self.corr_id = corr_id
        self.span_id = span_id

    def __repr__(self):
        return f"Carrier(corr_id={self.corr_id}, span_id={self.span_id})"


class _Span:
    __slots__ = ("name", "span_id", "corr_ids", "duration_ns")

    def __init__(self, name: str, span_id: int):
        self.name = name
        self.span_id = span_id
        self.corr_ids: list[int] = []
        # set when the span closes: a caller that keeps the object
        # (`with span(...) as sp`) reads the same clock its record has
        self.duration_ns = 0


def new_corr() -> Carrier:
    """Mint a correlation carrier at the CURRENT span (the enqueue
    point).  The enclosing span's record gains the correlation id, so
    exported flow arrows start there; with no enclosing span the
    carrier still correlates every downstream span that adopts it."""
    cur = _current.get()
    c = Carrier(next(_corr_ids), cur.span_id if cur is not None else 0)
    if cur is not None and len(cur.corr_ids) < CORR_CAP:
        cur.corr_ids.append(c.corr_id)
    return c


def as_carriers(corr) -> tuple:
    """Normalize a ``corr=`` argument: None, one Carrier, or an
    iterable of Carriers → tuple of Carriers."""
    if corr is None:
        return ()
    if isinstance(corr, Carrier):
        return (corr,)
    return tuple(c for c in corr if isinstance(c, Carrier))


def set_sink(sink) -> None:
    """sink: a path (append JSON lines) or a callable(record) or None
    (in-memory ring, default).  Crash-safe: the previous file sink is
    closed even when opening the new one fails (in which case records
    fall back to the in-memory ring)."""
    global _sink, _file
    with _lock:
        old, _file = _file, None
        _sink = None
        try:
            if isinstance(sink, str):
                f = open(sink, "a")
                _file = f
                _sink = lambda rec: (f.write(json.dumps(rec) + "\n"),
                                     f.flush())
            else:
                _sink = sink
        finally:
            if old is not None:
                try:
                    old.close()
                except OSError:
                    pass


def add_tap(fn) -> None:
    """Register fn(record) to observe every completed span, independent
    of (and in addition to) the configured sink."""
    with _lock:
        if fn not in _taps:
            _taps.append(fn)


def remove_tap(fn) -> None:
    with _lock:
        if fn in _taps:
            _taps.remove(fn)


def records() -> list[dict]:
    with _lock:
        return list(_records)


def reset() -> None:
    with _lock:
        _records.clear()


def _emit(rec: dict) -> None:
    with _lock:
        taps = list(_taps)
    for tap in taps:
        try:
            tap(rec)
        except Exception:
            pass
    # the sink runs UNDER the lock: set_sink closes the old file under
    # the same lock, so a rotation can never close the file out from
    # under a concurrent write (and two threads' JSONL lines can't
    # interleave)
    with _lock:
        if _sink is not None:
            _sink(rec)
            return
        _records.append(rec)
        if len(_records) > _MAX_RECORDS:
            del _records[: _MAX_RECORDS // 2]


@contextmanager
def span(name: str, corr=None, dispatch_id: int | None = None,
         **attributes):
    """Measure one phase; attaches to the enclosing span as parent.

    ``corr`` (a Carrier or iterable of Carriers) stamps the record with
    the correlation ids so the exporter can draw cross-thread flow
    arrows; ``dispatch_id`` ties the span to its flight-recorder
    DispatchRecord (obs/flight.py).  Inside a profile_session() the
    span is also a jax.profiler.TraceAnnotation of the same name."""
    parent = _current.get()
    sp = _Span(name, next(_span_ids))
    for c in as_carriers(corr):
        if len(sp.corr_ids) >= CORR_CAP:
            break
        sp.corr_ids.append(c.corr_id)
    token = _current.set(sp)
    # a deliberately unlocked read: at worst one span around a
    # session's start or stop goes without its annotation
    ann = None
    if _profile_active:
        import jax

        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
    t0 = time.monotonic_ns()
    err = None
    try:
        yield sp
    except BaseException as e:
        err = type(e).__name__
        raise
    finally:
        dur = sp.duration_ns = time.monotonic_ns() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        _current.reset(token)
        rec = {
            "name": name,
            "parent": parent.name if parent is not None else None,
            "span_id": sp.span_id,
            "parent_id": parent.span_id if parent is not None else None,
            "tid": threading.get_native_id(),
            "thread": threading.current_thread().name,
            "start_ns": t0,
            "duration_ns": dur,
        }
        if sp.corr_ids:
            rec["corr_ids"] = list(sp.corr_ids)
            rec["corr_id"] = sp.corr_ids[0]
        if dispatch_id is not None:
            rec["dispatch_id"] = dispatch_id
        if attributes:
            rec["attributes"] = attributes
        if err is not None:
            rec["error"] = err
        _emit(rec)


# -- dispatch profiling (LIGHTNING_TPU_PROFILE, doc/tracing.md) ------------
# One jax.profiler session brackets a whole workload (a replay, a bench
# round); while it is active every span() also opens a TraceAnnotation
# of the same name, so the program's spans lie on the profiler's clock
# beside the XLA device lines.  With no session a span costs one
# boolean test more and the live path never imports jax.profiler.

_profile_active = False          # guarded-by: _lock


@contextmanager
def profile_session():
    """Bracket a workload with jax.profiler start/stop when
    LIGHTNING_TPU_PROFILE=<dir> is set; nested or concurrent sessions
    no-op (the flag flips under the module lock — two replays racing
    here must not both call start_trace, which would raise into the
    second one's verify path).  Python's own tracer stays off: at its
    default level every Python call of the daemon is a host event in
    the trace (some 200 times as many, PERF.md), which disturbs the
    window being read; the TraceAnnotations that span() opens do not
    need it.  stop_trace takes as long either way: its time is the
    device's op events."""
    global _profile_active
    profile_dir = os.environ.get("LIGHTNING_TPU_PROFILE")
    if not profile_dir:
        yield
        return
    with _lock:
        owner = not _profile_active
        if owner:
            _profile_active = True
    if not owner:
        yield
        return
    import jax

    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
    except BaseException:
        with _lock:
            _profile_active = False
        raise
    try:
        yield
    finally:
        with _lock:
            _profile_active = False
        jax.profiler.stop_trace()


def summarize() -> dict:
    """Aggregate by span name: count + total/mean duration (the quick
    operator view `getlog`-style)."""
    agg: dict[str, list[int]] = {}
    for r in records():
        agg.setdefault(r["name"], []).append(r["duration_ns"])
    return {
        name: {
            "count": len(ds),
            "total_ms": sum(ds) / 1e6,
            "mean_ms": sum(ds) / len(ds) / 1e6,
        }
        for name, ds in agg.items()
    }
