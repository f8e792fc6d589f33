"""spans pass: span/topic/family names and metric label values come
from a FIXED vocabulary — never constructed at the call site.

Ported from tools/lint_spans.py (ISSUE 5 satellite; the shim still
fronts this pass).  Metric cardinality is bounded only because every
label value and span name is a code-bounded constant
(doc/observability.md §vocabulary).  One ``trace.span(f"verify/{scid}")``
or ``.labels(peer_id)`` with an interpolated id turns a bounded family
into an unbounded one: the span histogram grows a bucket set per peer,
the exporter draws a lane per scid, and the registry's cardinality cap
starts silently dropping the labels operators actually query.  The lint
rejects the *construction* itself.
"""
from __future__ import annotations

import ast

from ..core import FileContext, Pass

# call sites whose FIRST argument names a span/topic/family
NAMED_SITES = {"span", "emit", "dispatch", "begin"}
# modules the attr must hang off for NAMED_SITES to apply (so a
# dataclass's own `begin()` or an unrelated `emit` is not flagged)
NAMED_BASES = {"trace", "_trace", "events", "_ev", "_nev", "flight",
               "_flight"}
# journey hop sites: the first argument must ALSO be a member of the
# closed hop vocabulary (obs/journey.py HOPS) — a literal-but-unknown
# hop name would silently fragment the per-hop histograms and the
# tools/journey.py timeline lanes
HOP_SITES = {"hop"}
HOP_BASES = {"journey", "_journey"}


def is_constructed_str(node: ast.AST) -> bool:
    """True if the expression BUILDS a string: f-string, %-format,
    concatenation involving a str literal, str.format()/join()."""
    if isinstance(node, ast.JoinedStr):
        return True
    if isinstance(node, ast.BinOp) and isinstance(
            node.op, (ast.Add, ast.Mod)):
        for side in (node.left, node.right):
            if isinstance(side, ast.Constant) and isinstance(
                    side.value, str):
                return True
            if is_constructed_str(side):
                return True
    if isinstance(node, ast.Call) and isinstance(
            node.func, ast.Attribute) and node.func.attr in (
            "format", "join"):
        return True
    return False


class SpanVocabularyPass(Pass):
    name = "spans"
    description = ("span names, events topics, dispatch families, and "
                   ".labels() values must be fixed-vocabulary constants")
    default_scope = ("lightning_tpu/obs", "lightning_tpu/gossip",
                     "lightning_tpu/routing", "lightning_tpu/resilience",
                     "lightning_tpu/parallel", "lightning_tpu/pay",
                     "lightning_tpu/daemon/hsmd.py")
    node_types = (ast.Call,)

    def visit(self, node: ast.Call, ctx: FileContext) -> None:
        fn = node.func
        if not isinstance(fn, ast.Attribute):
            return
        if fn.attr in HOP_SITES:
            base = fn.value
            if not (isinstance(base, ast.Name)
                    and base.id in HOP_BASES):
                return
            if not node.args:
                return
            first = node.args[0]
            if not (isinstance(first, ast.Constant)
                    and isinstance(first.value, str)):
                self.emit(
                    ctx, node.lineno, "constructed-name",
                    "journey hop name must be a string literal "
                    "(fixed vocabulary, doc/journeys.md)",
                    f"{base.id}.{fn.attr}({ast.unparse(first)})")
                return
            from ...obs.journey import HOP_SET
            if first.value not in HOP_SET:
                self.emit(
                    ctx, node.lineno, "unknown-hop",
                    "hop name is not in obs/journey.py HOPS — add it "
                    "to the vocabulary or fix the typo "
                    "(doc/journeys.md)",
                    f"{base.id}.hop({first.value!r})")
        elif fn.attr in NAMED_SITES:
            base = fn.value
            if not (isinstance(base, ast.Name)
                    and base.id in NAMED_BASES):
                return
            if not node.args:
                return
            first = node.args[0]
            if not (isinstance(first, ast.Constant)
                    and isinstance(first.value, str)):
                self.emit(
                    ctx, node.lineno, "constructed-name",
                    "span/topic/family name must be a string literal "
                    "(fixed vocabulary, doc/tracing.md)",
                    f"{base.id}.{fn.attr}({ast.unparse(first)})")
        elif fn.attr == "labels":
            for arg in node.args:
                if is_constructed_str(arg):
                    self.emit(
                        ctx, node.lineno, "constructed-label",
                        "label value is constructed at the call site — "
                        "unbounded metric cardinality",
                        f"labels({ast.unparse(arg)})")
