"""Traffic shape `rpc_closed_loop`: N callers on the daemon's JSON-RPC
unix socket, each with one request in flight, as `pay`, plugins and
`lightning-cli` call it: a caller asks, waits for the reply, does
something with it for a while (its think time: a payer sends the
payment along the route) and asks again.

Set-up writes the configuration's graph as an unsigned store (from
--seed), boots the daemon **clean** over it in a thread of this
process (`lightning_tpu.daemon.__main__.main()` with the cell's `argv`,
run from inside the node's directory, where the store is
`gossip_store` and the socket has to be `lightning-rpc`; this process
holds the chip), waits until the program's warm-ups have finished
(`obs/attribution.retrace_state()`), starts the load generator
(drivers/rpc_client.py, a jax-free child) and lets it run for
`ramp_seconds`, so that the window opens on a loop in steady state.
The callers start one after another over `start_spread_s`, in an order
drawn from the seed, and never all at once.

The window's edges lie on replies (see `window`), so it lasts
`--seconds` and at most one burst of answers more.  Quantities, all
over every reply that came inside the window:
`answers_per_s` (answers over the window's seconds; a reply that is no
answer, an error other than "no route", counts as failed), `p95_ms`,
`p50_ms`, `mean_ms`, `count`.

`correct`: a seeded sample of the answered requests is solved by the
reference (reference/answers_<method>.py, which also says how the
method is asked and which counters' family serves it) and compared
exactly; the program's counters must show the device path produced the
answers (fallbacks other than `below_occupancy` under 1 % of the
queries solved), no compile, no breaker move, no quarantined row inside
the window.

Parameters (`params`): `method`, `callers`, `think_mean_s` and
`think_spread` (a caller's think times are evenly spaced over
mean * (1 -+ spread), the same set for every caller and seed, in an
order drawn from the seed), `start_spread_s`, `queries`, `pairs` (the
law of who pays whom, gen/pairs_<law>.py; `uniform` where none is
named) with `pairs_params`, `amount_min_msat`, `amount_max_msat`,
`ramp_seconds`, `ready_programs`
(how many programs the retrace detector must know before the warm-ups
count as done; 0 waits for none), `sample`, `trace_seconds`.
"""
from __future__ import annotations

import hashlib
import importlib
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import threading
import time

from gen import queries as gen_queries
from gen import store as gen_store
from lib import counters
from reference import graph as ref_graph

HERE = os.path.dirname(os.path.abspath(__file__))
BENIGN_FALLBACK = "below_occupancy"     # too few queries for a dispatch
# Queries the program solved off the device path for any other reason,
# as a share of all it solved in the window.  Sound runs read 0 on 11
# seeds and 0.08 % on one, in each of its three runs (one query of
# 1,220 whose device route fails the program's own reconstruction and is
# re-solved on the host, as designed); a daemon that answers from the
# host solvers reads 100 % (PR 23's fault, and tests/test_rehearsal.py).
# PERF.md, Findings PR 24 and the first row of Open questions.
HOST_FALLBACK_LIMIT_PCT = 1.0
BOOT_WAIT_S = 1100.0                    # a cold first run compiles
CHILD_WAIT_S = 90.0
# a cell of this shape at a size a CPU test run can hold
# (tests/conftest.py): the host solvers serve it, so the rehearsal proves
# the flow and the comparison, not the device path
TINY = {
    "graph": {"channels": 400, "nodes": 100},
    "params": {"callers": 8, "think_mean_s": 0.05,
               "start_spread_s": 0.2, "queries": 300,
               "ramp_seconds": 0.5, "ready_programs": 0, "sample": 25,
               "trace_seconds": 0.5},
    "env": {}, "argv": ["--cpu", "--gossip-store", "gossip_store",
                        "--rpc-file", "lightning-rpc"],
}


def _answers_module(method: str):
    """reference/answers_<method>.py: what a reply has to say."""
    return importlib.import_module(f"reference.answers_{method}")


class Daemon:
    """The program's daemon in a thread of this process."""

    def __init__(self, argv: list[str], out_path: str):
        self.argv, self.out_path = argv, out_path
        self.rc: list = []
        self.thread = None

    def start(self) -> None:
        from lightning_tpu.daemon.__main__ import main

        sys.argv = ["lightning_tpu.daemon", *self.argv]
        sys.stdout = open(self.out_path, "w", encoding="utf8")

        def body():
            try:
                self.rc.append(main())
            except BaseException as e:
                self.rc.append(repr(e))

        self.thread = threading.Thread(target=body, name="daemon",
                                       daemon=True)
        self.thread.start()

    def alive(self) -> bool:
        return self.thread is not None and self.thread.is_alive()

    def output(self) -> str:
        sys.stdout.flush()
        with open(self.out_path, encoding="utf8") as f:
            return f.read()


def rpc(sock_path: str, method: str, params: dict | None = None,
        timeout: float = 60.0) -> dict:
    import socket

    with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
        s.settimeout(timeout)
        s.connect(sock_path)
        s.sendall(json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                              "params": params or {}}).encode())
        buf = b""
        while not buf.endswith(b"\n\n"):
            chunk = s.recv(1 << 16)
            if not chunk:
                break
            buf += chunk
    return json.loads(buf)


def setup(run) -> dict:
    p = run.workload["params"]
    t = time.monotonic()
    store, _ = gen_store.cached_store(
        run.cache_dir, run.cell["config"], run.config["graph"], run.seed,
        signed=False, bad_records=0)
    run.phase("store", t)

    data_dir = os.path.join(run.work_dir, "node")
    os.makedirs(data_dir)
    shutil.copyfile(store, os.path.join(data_dir, "gossip_store"))
    # relative paths from inside the data directory: a unix socket's
    # path must fit 108 bytes wherever the checkout lies
    os.chdir(data_dir)

    t = time.monotonic()
    from lightning_tpu import obs
    from lightning_tpu.obs import attribution
    run.phase("import_program", t)

    t = time.monotonic()
    daemon = Daemon(run.workload["argv"],
                    os.path.join(data_dir, "daemon.out"))
    daemon.start()

    # meanwhile, on the host: the reference's graph and the queries
    g = ref_graph.from_store("gossip_store")
    nodes = ref_graph.largest_component(g)
    qs = gen_queries.cell_pairs(g, nodes, p, run.seed)
    run.note(inputs={
        "endpoints": gen_store.endpoint_law(run.config["graph"]),
        "pairs": gen_queries.pair_law(p),
        "store_sha256": gen_store.sha256_16("gossip_store"),
        "queries_sha256": hashlib.sha256(
            json.dumps(qs).encode()).hexdigest()[:16]})
    answers = _answers_module(p["method"])
    think, start = gen_queries.pacing(
        p["callers"], run.seed, think_mean_s=p["think_mean_s"],
        think_spread=p["think_spread"], start_spread_s=p["start_spread_s"])
    with open("queries.json", "w", encoding="utf8") as f:
        json.dump({"queries": [{"method": p["method"],
                                "params": answers.request(g, q)}
                               for q in qs],
                   "think_s": think, "start_s": start}, f)

    deadline = time.monotonic() + BOOT_WAIT_S
    booted = False
    while True:
        if not daemon.alive():
            raise RuntimeError(f"daemon ended {daemon.rc}: "
                               f"{daemon.output()[-1500:]}")
        if not booted and os.path.exists("lightning-rpc"):
            booted = True
            run.phase("daemon_boot", t)
        st = attribution.retrace_state()
        if booted and (not p["ready_programs"] or (
                st["armed"] and not st["in_warmup"]
                and st["known_programs"] >= p["ready_programs"])):
            break
        if time.monotonic() > deadline:
            raise RuntimeError(f"warm-ups did not finish: {st}")
        time.sleep(0.5)
    run.phase("daemon_boot_and_warmups", t)

    t = time.monotonic()
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "rpc_client.py"),
         "lightning-rpc", "queries.json", str(p["callers"]),
         "client_out.json"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env={k: v for k, v in os.environ.items()
             if not k.startswith(("JAX_", "XLA_", "TPU_"))})
    state = {"daemon": daemon, "child": child, "graph": g, "queries": qs,
             "obs": obs, "answers": answers}
    if child.stdout.readline().strip() != "ready":
        raise RuntimeError("the load generator did not connect")
    child.stdin.write("go\n")
    child.stdin.flush()
    time.sleep(p["ramp_seconds"])
    run.phase("client_ramp", t)
    return state


def _snapshot(state) -> dict:
    return state["obs"].snapshot()["metrics"]


def window(run, state) -> None:
    p = run.workload["params"]
    compiles0 = run.compiles.count()
    before = _snapshot(state)
    t_open = time.monotonic()
    if run.trace:
        time.sleep(max(0.0, (run.seconds - p["trace_seconds"]) / 2))
        run.traced_window(p["trace_seconds"])
    time.sleep(max(0.0, t_open + run.seconds - time.monotonic()))
    t_close = time.monotonic()
    after = _snapshot(state)
    state["compiles"] = run.compiles.count() - compiles0
    child = state["child"]
    child.stdin.write("stop\n")
    child.stdin.flush()
    try:
        child.wait(CHILD_WAIT_S)
    except subprocess.TimeoutExpired:
        child.kill()
        child.wait()
    with open("client_out.json", encoding="utf8") as f:
        out = json.load(f)
    answers = state["answers"]
    # A batching server answers in bursts (some tens of routes at the
    # end of every dispatch).  A window cut at two arbitrary instants
    # holds a whole number of bursts and its rate moves in steps of one
    # burst (2 % here) as the phase shifts.  So
    # both edges are put on a reply: the window opens with the first
    # reply at or after the nominal opening and closes with the first
    # at or after the nominal close (the callers in flight at `stop`
    # are waited for, so there is one).  All replies in between over
    # all the time in between.
    recv = sorted(s[2] for s in out["samples"])
    t_open = next((t for t in recv if t >= t_open), t_open)
    t_close = next((t for t in recv if t >= t_close), t_close)
    inside = [s for s in out["samples"] if t_open < s[2] <= t_close]
    lat = sorted((s[2] - s[1]) * 1e3 for s in inside)
    replies = {qi: json.loads(r) for qi, r in out["replies"].items()}
    is_answer = {qi: answers.is_answer(r) for qi, r in replies.items()}
    n_answers = sum(1 for s in inside if is_answer.get(str(s[0])))
    elapsed = t_close - t_open

    def pct(q: float) -> float:
        return lat[min(len(lat) - 1, int(q * len(lat)))] if lat else 0.0

    run.quantities = {
        "answers_per_s": n_answers / elapsed, "p95_ms": pct(0.95),
        "p50_ms": pct(0.50),
        "mean_ms": sum(lat) / len(lat) if lat else 0.0,
        "count": len(inside), "window_elapsed_s": elapsed}
    run.delta = counters.Delta(before, after)
    run.samples = inside
    state.update(out=out, inside=inside,
                 not_answers=len(inside) - n_answers)
    run.note(requests_in_window=len(inside), answers=n_answers,
             p50_ms=pct(0.50), p95_ms=pct(0.95), p99_ms=pct(0.99),
             sent_in_all=len(out["samples"]),
             never_answered=out["never_answered"],
             client_errors=out["errors"][:3])


def check(run, state) -> tuple[list, int, int]:
    p = run.workload["params"]
    g, qs, out = state["graph"], state["queries"], state["out"]
    answers = state["answers"]
    fam = answers.FAMILY
    d = run.delta
    done = sorted({s[0] for s in state["inside"]})
    rng = random.Random(run.seed)
    sample = rng.sample(done, min(p["sample"], len(done)))
    wrong, reasons = 0, []
    for qi in sample:
        try:
            answers.check(g, qs[qi], json.loads(out["replies"][str(qi)]))
        except ValueError as e:
            wrong += 1
            reasons.append(f"query {qi}: {e}")
    fallback = d.by_label(f"clntpu_{fam}_fallback_total", "reason")
    benign = fallback.pop(BENIGN_FALLBACK, 0)
    device_ok = d.counter(f"clntpu_{fam}_queries_total", path="device",
                          outcome="ok")
    solved = d.counter(f"clntpu_{fam}_queries_total")
    compared = [
        ("wrong_answers", wrong, 0),
        ("not_answers", state["not_answers"], 0),
        ("never_answered", out["never_answered"] + len(out["errors"]), 0),
        ("no_requests", 0 if done else 1, 0),
        ("host_fallback_pct", 100.0 * sum(fallback.values()) / solved
         if solved else 0.0, HOST_FALLBACK_LIMIT_PCT),
        ("no_device_answers", 0 if device_ok > 0 else 1, 0),
        ("quarantined", d.counter("clntpu_quarantine_total"), 0),
        ("breaker_moves", d.counter("clntpu_breaker_transitions_total"), 0),
        ("compiles_in_window", state["compiles"]
         + d.counter("clntpu_retrace_total"), 0),
    ]
    run.note(checked=len(sample), wrong=reasons[:5], fallback=fallback,
             below_occupancy=benign, device_ok=device_ok)
    attempted = len(state["inside"]) + out["never_answered"]
    failed = state["not_answers"] + out["never_answered"] + wrong
    return compared, max(attempted, 1), failed


def control_on(method: str, store: str, seed: int, params: dict,
               sample: int) -> tuple[int, list]:
    """(answers compared, [wrong_answers against its limit]) for the
    control's replies to a seeded sample of the cell's queries: the
    reference solver with compounding left out (every hop priced for
    the amount the payee receives)."""
    answers = _answers_module(method)
    g = ref_graph.from_store(store)
    qs = gen_queries.cell_pairs(g, ref_graph.largest_component(g), params,
                                seed)
    rng = random.Random(seed)
    wrong = 0
    picked = rng.sample(range(len(qs)), min(sample, len(qs)))
    for qi in picked:
        try:
            answers.check(g, qs[qi], answers.control_reply(g, qs[qi]))
        except ValueError:
            wrong += 1
    return len(picked), [("wrong_answers", wrong, 0)]


def control(workload: dict, config: dict, seed: int) -> tuple[int, list]:
    """This shape's control at a cell's own size, on the cell's own
    store and queries (host work only; tests/controls.py)."""
    p = workload["params"]
    with tempfile.TemporaryDirectory() as tmp:
        store = os.path.join(tmp, "gossip_store")
        gen_store.make_store(store, graph=config["graph"], seed=seed,
                             sign=False)
        return control_on(p["method"], store, seed, p, p["sample"])


def teardown(run, state) -> None:
    child = state.get("child")
    if child is not None and child.poll() is None:
        child.kill()
        child.wait()
    daemon = state["daemon"]
    if daemon.alive():
        try:
            rpc("lightning-rpc", "stop", timeout=20.0)
        except OSError:
            pass
        daemon.thread.join(30.0)
    os.chdir(run.root)
    shutil.rmtree(run.work_dir, ignore_errors=True)
