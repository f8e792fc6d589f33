"""The controls: the reference put in the program's place with one
guarantee of the configuration broken, judged by the same comparison
that decides `correct`.  Each has to come out as not correct.

    python benchmarks/tests/controls.py --workload <cell> --seeds 1 2 3

runs a cell's control at the cell's own size (host work only: the
control is reference code; nothing of the program is imported), and
prints one line per seed.  test_controls.py keeps them at a size a test
run can hold.

* `crashboot`: a checker that verifies only the first of a
  channel_announcement's four signatures, its bits handed to the
  driver's own `check()`.  Read: what `check()` compares
  (`invalid_gap`, `bits_mismatch`).
* `rpc_closed_loop`: the reference solver with compounding left out
  (every hop priced for the amount the payee receives).  Read: sampled
  queries whose answer the comparison refuses.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import random
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from gen import queries as gen_queries          # noqa: E402
from gen import store as gen_store              # noqa: E402
from reference import graph as ref_graph        # noqa: E402
from reference import storefile as ref_store    # noqa: E402


def crashboot_control(store: str, truth: dict, seed: int,
                      params: dict) -> tuple[int, list]:
    """(records compared, the numbers `drivers/crashboot.check` compares)
    with the control's validity bits in the place of the program's: on
    the records the check samples, a checker that looks at a
    channel_announcement's first signature only; valid elsewhere."""
    import types

    import numpy as np

    from drivers import crashboot
    from lib import counters

    msgs = ref_store.read_alive(store)
    keys = dict(ref_store.ca_fields(m) for m in msgs["ca"])
    rows = crashboot.sample_rows(
        seed, {k: len(v) for k, v in msgs.items()}, truth["bad"],
        params["sample_records"])
    verdict = {"ca": lambda m: ref_store.ca_valid(m, skip=(1, 2, 3)),
               "cu": lambda m: ref_store.cu_valid(m, keys),
               "na": ref_store.na_valid}
    bits = {k: np.ones(len(v), bool) for k, v in msgs.items()}
    for kind, picked in rows.items():
        for row in picked:
            bits[kind][row] = verdict[kind](msgs[kind][row])
    result = types.SimpleNamespace(ca_valid=bits["ca"], cu_valid=bits["cu"],
                                   na_valid=bits["na"])
    report = {"verify": {
        "records": truth["records"], "sigs": truth["sigs"],
        "invalid": sum(int((~b).sum()) for b in bits.values())}}
    run = types.SimpleNamespace(
        seed=seed, workload={"params": params},
        delta=counters.Delta({}, {}), note=lambda **kw: None)
    state = {"truth": truth, "store": store, "reports": [report],
             "results": [result], "errors": [], "alive": False,
             "warm_gap": 0, "compiles": 0}
    compared, checked, _failed = crashboot.check(run, state)
    return checked, compared


def rpc_control(method: str, store: str, seed: int, params: dict,
                sample: int) -> tuple[int, list]:
    """(answers compared, [wrong_answers against its limit]) for the
    control's replies to a seeded sample of the cell's queries."""
    answers = importlib.import_module(f"reference.answers_{method}")
    g = ref_graph.from_store(store)
    qs = gen_queries.pairs(ref_graph.largest_component(g),
                           params["queries"], seed,
                           amount_min_msat=params["amount_min_msat"],
                           amount_max_msat=params["amount_max_msat"])
    rng = random.Random(seed)
    wrong = 0
    picked = rng.sample(range(len(qs)), min(sample, len(qs)))
    for qi in picked:
        try:
            answers.check(g, qs[qi], answers.control_reply(g, qs[qi]))
        except ValueError:
            wrong += 1
    return len(picked), [("wrong_answers", wrong, 0)]


def run_control(workload: dict, config: dict, seed: int) -> tuple[int, list]:
    p, g = workload["params"], config["graph"]
    with tempfile.TemporaryDirectory() as tmp:
        store = os.path.join(tmp, "gossip_store")
        if workload["driver"] == "crashboot":
            truth = gen_store.make_store(
                store, channels=g["channels"], nodes=g["nodes"], seed=seed,
                sign=True, bad_records=p["bad_records"])
            return crashboot_control(store, truth, seed, p)
        gen_store.make_store(store, channels=g["channels"],
                             nodes=g["nodes"], seed=seed, sign=False)
        return rpc_control(p["method"], store, seed, p, p["sample"])


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(BENCH)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf8") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, cfg["file"]), encoding="utf8") as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "workloads", cell["name"] + ".json"),
              encoding="utf8") as f:
        workload = json.load(f)
    ok = True
    for seed in args.seeds:
        n, compared = run_control(workload, config, seed)
        correct = all(abs(v) <= lim for _, v, lim in compared)
        print(json.dumps({
            "control": args.workload, "seed": seed, "looked_at": n,
            "correct": correct, "compared": {
                name: {"value": v, "limit": lim}
                for name, v, lim in compared}}), flush=True)
        ok = ok and not correct
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
