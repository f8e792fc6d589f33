"""Test helper, never a benchmark run: drives benchmarks/run.py's
`main` in this process with the harness's look for a chip replaced, and
optionally with the timed path broken underneath (`--fault <name>`).

    python rehearse.py <tree> [--fault NAME] -- <run.py arguments>

`tree` is a directory that holds BENCHMARK.json, benchmarks/ and the
program (tests build one with tiny sizes; see conftest.py).
"""
from __future__ import annotations

import importlib.util
import os
import sys


def fault_answer_altered_crashboot():
    """One validity bit flipped where verify_store produces it."""
    from lightning_tpu.gossip import verify as gverify

    inner = gverify.verify_store

    def broken(*a, **kw):
        res = inner(*a, **kw)
        res.cu_valid[0] = not res.cu_valid[0]
        return res

    gverify.verify_store = broken


def fault_half_left_out_crashboot():
    """The second half of every kind reported valid without a look."""
    from lightning_tpu.gossip import verify as gverify

    inner = gverify.verify_store

    def broken(*a, **kw):
        res = inner(*a, **kw)
        for bits in (res.ca_valid, res.cu_valid, res.na_valid):
            bits[len(bits) // 2:] = True
        return res

    gverify.verify_store = broken


def skip_ca_sig(position: int):
    """A verify path that takes a channel_announcement's signature at
    `position` (0..3) on trust: every record it would have passed for
    that reason reads valid."""
    def fault():
        from lightning_tpu.gossip import verify as gverify
        from reference import storefile as ref_store

        inner = gverify.verify_store

        def broken(idx, *a, **kw):
            res = inner(idx, *a, **kw)
            msgs = ref_store.parse_alive(bytes(idx.buf))["ca"]
            for row in (~res.ca_valid).nonzero()[0]:
                if ref_store.ca_valid(msgs[row], skip=(position,)):
                    res.ca_valid[row] = True
            return res

        gverify.verify_store = broken
    return fault


def fault_answer_altered_rpc():
    """One msat added to the first hop where the route is produced."""
    from lightning_tpu.routing import device

    inner = device.RouteService.getroute

    async def broken(self, *a, **kw):
        hops = await inner(self, *a, **kw)
        hops[0].amount_msat += 1
        return hops

    device.RouteService.getroute = broken


FAULTS = {f.__name__[len("fault_"):]: f for f in (
    fault_answer_altered_crashboot, fault_half_left_out_crashboot,
    fault_answer_altered_rpc)}
FAULTS.update({f"ca_sig_{i}_skipped_crashboot": skip_ca_sig(i)
               for i in range(4)})


def main(argv: list[str]) -> int:
    tree = os.path.abspath(argv[0])
    rest = argv[1:]
    fault = None
    if rest and rest[0] == "--fault":
        fault, rest = rest[1], rest[2:]
    if rest and rest[0] == "--":
        rest = rest[1:]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.join(tree, "benchmarks"))
    sys.path.insert(0, tree)
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(tree, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    run.require_chip = lambda chips: {
        "platform": "stubbed", "kind": "TPU v5 lite", "count": chips}
    if fault:
        FAULTS[fault]()
    return run.main(rest)


if __name__ == "__main__":
    code = main(sys.argv[1:])
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
