"""Reading the program's stage spans as deltas over a window.

Every completed `trace.span(name)` of the program is one observation of
`clntpu_span_duration_seconds{name=...}` (lightning_tpu/obs/collector.py),
so a window's `getmetrics` delta holds, per span name, the seconds spent
and the number of spans that ended inside it.  A program that has no
span of that name (the parent of the PR that added it) reads (0.0, 0),
and the readers built on this return None."""
from __future__ import annotations

FAMILY = "clntpu_span_duration_seconds"


def _side(metrics: dict, span: str) -> tuple[float, int]:
    # (the family's label is called `name`, which lib/counters' keyword
    # filters cannot take: their own first parameter has that name)
    for s in metrics.get(FAMILY, {}).get("samples", []):
        if s["labels"].get("name") == span:
            return float(s.get("sum", 0.0)), int(s.get("count", 0))
    return 0.0, 0


def total(run, name: str) -> tuple[float, int]:
    """(seconds, spans) of the spans called `name` that ended in the
    window."""
    d = run.delta
    if d is None:
        return 0.0, 0
    (s1, c1), (s0, c0) = _side(d.after, name), _side(d.before, name)
    return s1 - s0, c1 - c0


def mean_ms_per(run, name: str, per: str) -> float | None:
    """Milliseconds of `name` spans for each span called `per` (a stage
    per flush, per pass), or None where either was not counted."""
    secs, n = total(run, name)
    _, whole = total(run, per)
    if not n or not whole:
        return None
    return 1e3 * secs / whole
