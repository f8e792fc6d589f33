"""The controls: the reference put in the program's place with one
guarantee of the configuration broken, judged by the same comparison
that decides `correct`.  Each has to come out as not correct.

    python benchmarks/tests/controls.py --workload <cell> --seeds 1 2 3

runs a cell's control at the cell's own size (host work only: the
control is reference code; nothing of the program is imported), and
prints one line per seed.  test_controls.py keeps them at a size a test
run can hold.

A traffic shape brings its control in its driver file,
`drivers/<driver>.py` `control(workload, config, seed)`:

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
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)


def driver_module(name: str, bench: str = BENCH):
    """<bench>/drivers/<name>.py, as run.py loads it."""
    spec = importlib.util.spec_from_file_location(
        "bench_drivers_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(bench, "drivers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_control(workload: dict, config: dict, seed: int) -> tuple[int, list]:
    """The control of the cell's traffic shape, which its driver file
    brings: `control(workload, config, seed)`."""
    return driver_module(workload["driver"]).control(workload, config, seed)


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
