"""Reading the program's `getmetrics` snapshot as deltas over a window.

The snapshot's shape (lightning_tpu/obs/registry.py `snapshot()`):
`{name: {"kind": ..., "samples": [{"labels": {...}, "value": v}]}}`,
a histogram's sample carrying `sum` and `count` in place of `value`.
"""
from __future__ import annotations


def _samples(metrics: dict, name: str) -> list[dict]:
    return metrics.get(name, {}).get("samples", [])


def _match(sample: dict, labels: dict) -> bool:
    return all(sample["labels"].get(k) == v for k, v in labels.items())


def total(metrics: dict, name: str, field: str = "value",
          **labels) -> float:
    return sum(s.get(field, 0) for s in _samples(metrics, name)
               if _match(s, labels))


def by_label(metrics: dict, name: str, label: str) -> dict:
    out: dict = {}
    for s in _samples(metrics, name):
        key = s["labels"].get(label)
        out[key] = out.get(key, 0) + s.get("value", 0)
    return out


class Delta:
    """after - before, family by family."""

    def __init__(self, before: dict, after: dict):
        self.before, self.after = before, after

    def counter(self, name: str, **labels) -> float:
        return (total(self.after, name, **labels)
                - total(self.before, name, **labels))

    def hist_sum(self, name: str, **labels) -> float:
        return (total(self.after, name, "sum", **labels)
                - total(self.before, name, "sum", **labels))

    def hist_count(self, name: str, **labels) -> float:
        return (total(self.after, name, "count", **labels)
                - total(self.before, name, "count", **labels))

    def by_label(self, name: str, label: str) -> dict:
        a = by_label(self.after, name, label)
        b = by_label(self.before, name, label)
        return {k: v - b.get(k, 0) for k, v in a.items()
                if v - b.get(k, 0)}
