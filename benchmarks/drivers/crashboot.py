"""Traffic shape `crashboot`: a node that was killed comes back.

Set-up writes the configuration's signed gossip store (from --seed,
with a seeded handful of records made invalid), marks a data directory
as crashed and drives one crash boot over a miniature store, so that
the verify program is compiled or loaded and has run once.  Then it
starts the worker thread that calls
`lightning_tpu.daemon.recovery.boot_recover` on the full store, pass
after pass, re-marking the directory crashed before each, and lets the
first pass run for `open_after_s`: that lead is set-up too.

The window opens there, in the middle of a pass, and closes
`--seconds` later in the middle of another, so that the host-only work
between two passes (scan, crc check, extraction before the first
dispatch; readback after the last) lies inside it whole, never cut at
an edge.  Store scan, crc check, extraction, bucket preparation,
dispatch and readback are all inside.  At each edge the harness reads
`clntpu_replay_buckets_total` (it moves at every dispatch), then sends
a small program of its own down the same device queue and waits for
it, so that every bucket counted has run when the clock is read.
Buckets become signatures by the ratio the completed passes show
(`clntpu_verify_batch_sigs` over buckets; every pass is the same
store).  The pass in flight at the close is waited for, because its
answers are what `correct` compares.

Quantities: `sigs_per_s`.

Parameters (workloads/<cell>.json `params`): `bad_records`,
`sample_records`, `open_after_s`, `trace_after_s` (from the window's
opening to the traced sub-window), `trace_seconds`.
"""
from __future__ import annotations

import os
import random
import shutil
import tempfile
import threading
import time
import types

from lib import counters
from gen import store as gen_store
from reference import storefile as ref_store

# the warm-up boot's store: a warm-up of the program, whatever the
# configuration's graph is
MINI = {"channels": 96, "nodes": 24}
PASS_WAIT_S = 240.0                      # for the pass in flight
# a cell of this shape at a size a CPU test run can hold
# (tests/conftest.py)
TINY = {
    "graph": {"channels": 96, "nodes": 24},
    "params": {"bad_records": 24, "sample_records": 9,
               "open_after_s": 0.2, "trace_after_s": 0.2,
               "trace_seconds": 0.5},
    "env": {"LIGHTNING_TPU_VERIFY_BUCKET": "8"}, "argv": [],
}


def mark_crashed(data_dir: str) -> None:
    """What a killed daemon leaves: the marker still says running."""
    with open(os.path.join(data_dir, "run_marker"), "w",
              encoding="utf8") as f:
        f.write("running\n")


class Spy:
    """Keeps what `verify_store` returned to `boot_recover`: the
    validity bits of every record, which the report only counts."""

    def __init__(self, gverify):
        self.gverify, self.inner = gverify, gverify.verify_store
        self.results: list = []

    def __call__(self, *a, **kw):
        res = self.inner(*a, **kw)
        self.results.append(res)
        return res

    def install(self):
        self.gverify.verify_store = self
        return self

    def remove(self):
        self.gverify.verify_store = self.inner


def setup(run) -> dict:
    p = run.workload["params"]
    t = time.monotonic()
    store, truth = gen_store.cached_store(
        run.cache_dir, run.cell["config"], run.config["graph"], run.seed,
        signed=True, bad_records=p["bad_records"])
    run.note(inputs={
        "endpoints": gen_store.endpoint_law(run.config["graph"]),
        "store_sha256": gen_store.sha256_16(store)})
    run.phase("store", t)

    t = time.monotonic()
    import jax
    import jax.numpy as jnp

    from lightning_tpu import obs
    from lightning_tpu.daemon import recovery
    from lightning_tpu.gossip import verify as gverify
    run.phase("import_program", t)

    data_dir = os.path.join(run.work_dir, "node")
    os.makedirs(data_dir)
    live = os.path.join(data_dir, "gossip_store")
    shutil.copyfile(store, live)

    # one crash boot over a miniature store: the same entry, the same
    # program shape, everything compiled or loaded before the window
    t = time.monotonic()
    mini_dir = os.path.join(run.work_dir, "mini")
    os.makedirs(mini_dir)
    mini = os.path.join(mini_dir, "gossip_store")
    gen_store.make_store(mini, graph=MINI, seed=run.seed, sign=True,
                         bad_records=3)
    mark_crashed(mini_dir)
    rep = recovery.boot_recover(mini_dir, store_path=mini)
    warm_gap = abs(((rep.get("verify") or {}).get("invalid", -1)) - 3)
    fence = jax.jit(lambda x: x + 1)
    fence(jnp.zeros((8,), jnp.int32)).block_until_ready()
    run.phase("warm_boot", t)

    def sync():
        fence(jnp.zeros((8,), jnp.int32)).block_until_ready()

    state = {"truth": truth, "store": live, "data_dir": data_dir,
             "warm_gap": warm_gap, "obs": obs, "fence": sync,
             "reports": [], "errors": [], "stop": threading.Event(),
             "spy": Spy(gverify).install()}

    def passes():
        try:
            while not state["stop"].is_set():
                mark_crashed(data_dir)
                state["reports"].append(recovery.boot_recover(
                    data_dir, store_path=live))
        except BaseException as e:      # surfaces in check()
            state["errors"].append(repr(e))

    t = time.monotonic()
    state["base"] = _snapshot(state)    # before the first pass
    state["worker"] = threading.Thread(target=passes, name="crashboot",
                                       daemon=True)
    state["worker"].start()
    time.sleep(p["open_after_s"])
    run.phase("pass_lead", t)
    return state


def _snapshot(state) -> dict:
    return state["obs"].snapshot()["metrics"]


def _edge(state) -> tuple[dict, float]:
    """The counters, then the clock once what they count has run.  A
    bucket dispatched between the two has run when the clock is read
    and is not counted: one in some thousands, at both edges alike."""
    snap = _snapshot(state)
    state["fence"]()
    return snap, time.monotonic()


def window(run, state) -> None:
    p = run.workload["params"]
    compiles0 = run.compiles.count()
    before, t_open = _edge(state)
    if run.trace:
        time.sleep(p["trace_after_s"])
        run.traced_window(p["trace_seconds"])
    time.sleep(max(0.0, t_open + run.seconds - time.monotonic()))
    at_close, t_close = _edge(state)
    compiles = run.compiles.count() - compiles0
    state["stop"].set()
    worker = state["worker"]
    worker.join(PASS_WAIT_S)
    after = _snapshot(state)
    state["spy"].remove()
    reports = state["reports"]
    whole = counters.Delta(state["base"], after)
    win = counters.Delta(before, at_close)
    buckets_all = whole.counter("clntpu_replay_buckets_total")
    sigs_all = whole.hist_sum("clntpu_verify_batch_sigs")
    buckets_win = win.counter("clntpu_replay_buckets_total")
    sigs_win = buckets_win * sigs_all / buckets_all if buckets_all else 0.0
    elapsed = t_close - t_open
    run.quantities = {"sigs_per_s": sigs_win / elapsed,
                      "window_elapsed_s": elapsed,
                      "sigs_in_window": sigs_win,
                      "buckets_in_window": buckets_win,
                      "passes_completed": len(reports)}
    # the stage counters move once a pass, so the readers get the whole
    # passes, the one the window opened in with them
    run.delta = whole
    state.update(results=state["spy"].results, compiles=compiles,
                 alive=worker.is_alive(),
                 t_wait=time.monotonic() - t_close)
    run.note(passes=len(reports), buckets_per_pass=(
        buckets_all / len(reports) if reports else None),
        waited_for_pass_s=round(state["t_wait"], 3))


def sample_rows(seed: int, counts: dict, bad: dict,
                sample_records: int) -> dict:
    """The records of each kind that `check` has the reference verify:
    every planted one and a seeded draw of the others."""
    rng = random.Random(seed)
    per_kind = max(1, sample_records // 3)
    return {kind: sorted(set(bad[kind]) | set(rng.sample(
        range(counts[kind]), min(per_kind, counts[kind]))))
        for kind in ("ca", "cu", "na")}


def check(run, state) -> tuple[list, int, int]:
    """Every pass's report against what the generator planted, the
    passes' bits against each other, and the first pass's bits against
    the reference's own verification of a seeded sample of records
    (all the planted ones among them), read from the file."""
    p = run.workload["params"]
    truth, reports, results = state["truth"], state["reports"], \
        state["results"]
    bad = {k: set(v) for k, v in truth["bad"].items()}
    n_bad = sum(len(v) for v in bad.values())
    compared = [("worker_errors", len(state["errors"]) + int(state["alive"]),
                 0),
                ("passes_missing", 0 if reports else 1, 0),
                ("warm_boot_invalid_gap", state["warm_gap"], 0)]
    gap_sigs = gap_invalid = gap_records = 0
    for rep in reports:
        v = rep.get("verify") or {"sigs": 0, "invalid": -1, "records": 0}
        gap_sigs = max(gap_sigs, abs(v["sigs"] - truth["sigs"]))
        gap_invalid = max(gap_invalid, abs(v["invalid"] - n_bad))
        gap_records = max(gap_records, abs(v["records"] - truth["records"]))
    compared += [("sigs_gap", gap_sigs, 0), ("invalid_gap", gap_invalid, 0),
                 ("records_gap", gap_records, 0)]

    import numpy as np

    differ = 0
    for r in results[1:]:
        for a in ("ca_valid", "cu_valid", "na_valid"):
            differ += int((np.asarray(getattr(r, a))
                           != np.asarray(getattr(results[0], a))).sum())
    compared.append(("passes_differ", differ, 0))

    # the reference reads the file and verifies a sample itself
    msgs = ref_store.read_alive(state["store"])
    keys = dict(ref_store.ca_fields(m) for m in msgs["ca"])
    rows = sample_rows(run.seed, {k: len(v) for k, v in msgs.items()},
                       bad, p["sample_records"])
    mismatched = checked = 0
    if results:
        got = {"ca": np.asarray(results[0].ca_valid),
               "cu": np.asarray(results[0].cu_valid),
               "na": np.asarray(results[0].na_valid)}
        for kind in ("ca", "cu", "na"):
            if len(got[kind]) != len(msgs[kind]):
                mismatched += len(msgs[kind])
                continue
            for row in rows[kind]:
                m = msgs[kind][row]
                want = (ref_store.ca_valid(m) if kind == "ca" else
                        ref_store.cu_valid(m, keys) if kind == "cu" else
                        ref_store.na_valid(m))
                checked += 1
                if bool(got[kind][row]) != want \
                        or want != (row not in bad[kind]):
                    mismatched += 1
    compared.append(("bits_mismatch", mismatched, 0))

    d = run.delta
    paths = d.by_label("clntpu_replay_buckets_total", "path")
    compared += [
        ("host_buckets", sum(v for k, v in paths.items() if k != "fused"), 0),
        ("quarantined", d.counter("clntpu_quarantine_total"), 0),
        ("breaker_moves", d.counter("clntpu_breaker_transitions_total"), 0),
        ("compiles_in_window", state["compiles"]
         + d.counter("clntpu_retrace_total"), 0),
    ]
    run.note(checked_records=checked, planted=n_bad, bucket_paths=paths)
    # records found wrong, or the run itself where another number failed
    failed = mismatched or int(any(abs(v) > lim for _, v, lim in compared))
    return compared, max(checked, 1), failed


def control_on(store: str, truth: dict, seed: int,
               params: dict) -> tuple[int, list]:
    """(records compared, the numbers `check` compares) with the
    control's validity bits in the place of the program's: on the
    records the check samples, a checker that looks at a
    channel_announcement's first signature only; valid elsewhere."""
    import numpy as np

    msgs = ref_store.read_alive(store)
    keys = dict(ref_store.ca_fields(m) for m in msgs["ca"])
    rows = sample_rows(seed, {k: len(v) for k, v in msgs.items()},
                       truth["bad"], params["sample_records"])
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
    compared, checked, _failed = check(run, state)
    return checked, compared


def control(workload: dict, config: dict, seed: int) -> tuple[int, list]:
    """This shape's control at a cell's own size, on the cell's own
    store (host work only; tests/controls.py)."""
    p = workload["params"]
    with tempfile.TemporaryDirectory() as tmp:
        store = os.path.join(tmp, "gossip_store")
        truth = gen_store.make_store(
            store, graph=config["graph"], seed=seed, sign=True,
            bad_records=p["bad_records"])
        return control_on(store, truth, seed, p)


def teardown(run, state) -> None:
    shutil.rmtree(run.work_dir, ignore_errors=True)
