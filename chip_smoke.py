#!/usr/bin/env python3
"""The quickest proof that the node still starts on the chip.

Drives the main path once, through the entry points a user calls, on
one TPU chip:

  0. a child prints what ``jax.devices()`` reports — anything but a TPU
     ends the script before any work is done;
  1. ``python -m lightning_tpu.gossip.synth`` signs a gossip store of one
     tenth of the repo's mainnet preset on the chip (25,000 channels,
     6,000 nodes, 156,000 ECDSA signatures; a fifth did not fit the
     1,200 s a cold run is given — CHANGES.md PR 23);
  2. the daemon (no ``--cpu``) crash-boots over that store: every
     signature is replayed through the batched verify pipeline, the
     gossmap and route planes are built, RouteService / McfService /
     the gossip verify programs warm up on the device;
  3. concurrent ``getroute`` and ``getroutes`` calls over the unix-socket
     JSON-RPC are checked against the host solvers
     (routing/dijkstra.py, routing/mcf.py) run here in the parent;
  4. ``getmetrics`` must show the device paths did the work: no
     fallback but below-occupancy flushes, every breaker closed, no
     live-path compile;
  5. ``stop`` — exit code 0, no failed warm-up.

Every bucket, batch and flush setting is the daemon's default.

The chip belongs to one process at a time: this parent never imports
jax, and the phases are children run one after another.  One JSON
object per line on stdout; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``
or ``{"ok": false, ...}`` with a non-zero exit code.  Run it twice in
one checkout to see the compile cache work (second run's ``compile``
phases shrink); the cache is ``$JAX_COMPILATION_CACHE_DIR`` or
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import json
import os
import random
import shlex
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))

PLATFORM = "tpu"
SCALE = 0.1                 # of gossip/synth.py's --mainnet preset
SEED = 7
N_GETROUTE = 320            # ≥ 256, in waves that fill ROUTE_BATCH (64)
N_GETROUTES = 8             # one full MCF_BATCH (8) dispatch: ~135 s on a
                            # v5e at this graph; two did not fit a cold run
WAVE = 128                  # concurrent clients: below ROUTE_HIGH_WM (256)
BUDGET_S = 1175             # the whole script, compilation included
FINAL_CLTV = 18             # getroute's `cltv` / getroutes' `final_cltv` default
# a flush that holds too few queries to be worth a dispatch is solved on
# the host by design (HOST_ROUTE_MAX / MCF_HOST_MAX); every other
# fallback reason means the device path failed
BENIGN_FALLBACK = "below_occupancy"

_T0 = time.monotonic()


class SmokeFailed(Exception):
    def __init__(self, phase: str, error: str):
        super().__init__(f"{phase}: {error}")
        self.phase, self.error = phase, error


def _left() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def _emit(**row) -> None:
    print(json.dumps(row), flush=True)


def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + (os.pathsep + env["PYTHONPATH"]
                                if env.get("PYTHONPATH") else "")
    return env


def _run_child(phase: str, argv: list[str]) -> tuple[str, float]:
    """Run one child to its end (it owns the chip meanwhile); returns
    (stdout, seconds).  A child that fails or outlives the budget fails
    the smoke."""
    t0 = time.monotonic()
    try:
        proc = subprocess.run([sys.executable, *argv], env=_child_env(),
                              capture_output=True, text=True,
                              timeout=max(1.0, _left()))
    except subprocess.TimeoutExpired:
        raise SmokeFailed(phase, "timed out")
    if proc.returncode != 0:
        raise SmokeFailed(phase, f"exit code {proc.returncode}: "
                          f"{proc.stderr[-1500:]}")
    return proc.stdout, time.monotonic() - t0


# -- phase 0: which device --------------------------------------------------

_PROBE = ("import json, jax; d = jax.devices(); "
          "print(json.dumps({'platform': d[0].platform, "
          "'kind': d[0].device_kind, 'count': len(d)}))")


def probe_device() -> dict:
    out, dt = _run_child("device", ["-c", _PROBE])
    dev = json.loads(out.strip().splitlines()[-1])
    _emit(phase="device", seconds=round(dt, 1), **dev)
    if dev["platform"] != PLATFORM:
        raise SmokeFailed("device", f"jax reports platform "
                          f"{dev['platform']!r}, not {PLATFORM!r}: "
                          "nothing is run")
    return dev


# -- phase 1: sign the store on the chip ------------------------------------

def synth_store(store: str) -> dict:
    out, dt = _run_child("synth", [
        "-m", "lightning_tpu.gossip.synth", store, "--mainnet",
        "--scale", str(SCALE), "--seed", str(SEED)])
    info = json.loads(out.strip().splitlines()[-1])
    _emit(phase="synth", seconds=round(dt, 1),
          store_bytes=os.path.getsize(store), **info)
    return info


# -- the host oracle (jax-free: dijkstra, mcf, gossmap, store, native) ------

def load_graph(store: str):
    from lightning_tpu.gossip import gossmap, store as gstore

    return gossmap.from_store(gstore.load_store(store))


def pick_queries(g, n_route: int, n_mcf: int):
    """Random reachable pairs from SEED with the host solvers' answers:
    ([(src, dst, amount_msat, oracle_cost)], [(src, dst, amount_msat,
    oracle_result)])."""
    from lightning_tpu.routing import dijkstra as DJ
    from lightning_tpu.routing import mcf as MCF

    rng = random.Random(SEED)
    ids = [bytes(x) for x in g.node_ids]

    def pair():
        a, b = rng.sample(range(len(ids)), 2)
        # 1k – 1M sat
        return ids[a], ids[b], rng.randrange(1_000_000, 1_000_000_001)

    routes, flows = [], []
    while len(routes) < n_route:
        src, dst, amt = pair()
        try:
            hops = DJ.getroute(g, src, dst, amt)
        except DJ.NoRoute:
            continue
        routes.append((src, dst, amt, _route_cost(g, [
            (h.scid, h.direction, h.amount_msat) for h in hops])))
    while len(flows) < n_mcf:
        src, dst, amt = pair()
        try:
            res = MCF.getroutes(g, src, dst, amt)
        except (MCF.McfError, DJ.NoRoute):
            continue
        flows.append((src, dst, amt, res))
    return routes, flows


def _route_cost(g, hops) -> int:
    """dijkstra.py's cost model (fees + risk) over (scid, dir, amount)."""
    from lightning_tpu.routing import dijkstra as DJ

    cost = 0
    for scid, d, amt in hops:
        c = g.channel_index(scid)
        cost += DJ.hop_fee_msat(int(g.fee_base_msat[d, c]),
                                int(g.fee_ppm[d, c]), amt)
        cost += DJ._risk_msat(amt, int(g.cltv_delta[d, c]),
                              DJ.DEFAULT_RISKFACTOR)
    return cost


def _check_path(g, src: bytes, dst: bytes, amount_msat: int,
                final_cltv: int, hops) -> None:
    """A path is valid when it walks enabled channels src → dst in the
    stated directions, delivers amount_msat, and every hop's amount and
    delay are the next hop's plus that channel's exact fee and delta.
    hops: [(next_node_id, scid, direction, amount_msat, delay)]."""
    from lightning_tpu.routing import dijkstra as DJ

    if not hops:
        raise ValueError("empty path")
    at = src
    for node_id, scid, d, amt, _dly in hops:
        c = g.channel_index(scid)
        u = bytes(g.node_ids[int((g.node1 if d == 0 else g.node2)[c])])
        v = bytes(g.node_ids[int((g.node2 if d == 0 else g.node1)[c])])
        if (u, v) != (at, node_id):
            raise ValueError(f"hop over {scid}/{d} does not join the path")
        if not g.enabled[d, c]:
            raise ValueError(f"channel {scid}/{d} is disabled")
        hmax = int(g.htlc_max_msat[d, c])
        if amt < int(g.htlc_min_msat[d, c]) or (hmax and amt > hmax):
            raise ValueError(f"amount outside {scid}/{d}'s htlc window")
        at = node_id
    if at != dst:
        raise ValueError("path does not end at the destination")
    if hops[-1][3] != amount_msat or hops[-1][4] != final_cltv:
        raise ValueError("last hop does not deliver the asked amount")
    for (_, _, _, amt, dly), (_, scid, d, namt, ndly) in zip(hops, hops[1:]):
        c = g.channel_index(scid)
        fee = DJ.hop_fee_msat(int(g.fee_base_msat[d, c]),
                              int(g.fee_ppm[d, c]), namt)
        if amt != namt + fee or dly != ndly + int(g.cltv_delta[d, c]):
            raise ValueError(f"amount/delay do not compound over {scid}")


# -- raw unix-socket JSON-RPC -----------------------------------------------

def _rpc_on(sock: socket.socket, method: str, params: dict) -> dict:
    sock.sendall(json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                             "params": params}).encode())
    buf = b""
    while True:
        chunk = sock.recv(1 << 16)
        if not chunk:
            raise ConnectionError(f"{method}: connection closed")
        buf += chunk
        try:
            return json.loads(buf)
        except ValueError:
            continue


def _connect(path: str, timeout: float) -> socket.socket:
    """Connect, waiting out a full accept backlog (a burst of clients
    can outrun the daemon's accept loop for a moment)."""
    give_up = time.monotonic() + 30.0
    while True:
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.settimeout(timeout)
        try:
            s.connect(path)
            return s
        except (BlockingIOError, ConnectionRefusedError):
            s.close()
            if time.monotonic() > give_up:
                raise
            time.sleep(0.01)


def rpc(path: str, method: str, params: dict | None = None,
        timeout: float = 120.0) -> dict:
    with _connect(path, timeout) as s:
        resp = _rpc_on(s, method, params or {})
    if "error" in resp:
        raise SmokeFailed(method, json.dumps(resp["error"])[:500])
    return resp["result"]


def rpc_wave(path: str, calls: list[tuple[str, dict]],
             timeout: float) -> list[dict]:
    """Send the calls at once: every client is connected first, then
    all send together, so one flush window sees them all."""
    socks = [_connect(path, timeout) for _ in calls]
    try:
        with ThreadPoolExecutor(max_workers=len(calls)) as pool:
            return list(pool.map(lambda sc: _rpc_on(sc[0], *sc[1]),
                                 zip(socks, calls)))
    finally:
        for s in socks:
            s.close()


# -- phase 2: crash-boot the daemon -----------------------------------------

class Daemon:
    def __init__(self, data_dir: str):
        self.dir = data_dir
        self.rpc_path = os.path.join(data_dir, "lightning-rpc")
        self.out_path = os.path.join(data_dir, "daemon.out")
        self.err_path = os.path.join(data_dir, "daemon.err")
        self.proc: subprocess.Popen | None = None
        self.t_start = 0.0

    def start(self) -> None:
        # what a killed daemon leaves behind (daemon/recovery.py): the
        # marker still says "running", so this boot is a crash recovery
        with open(os.path.join(self.dir, "run_marker"), "w") as f:
            f.write("running\n")
        self.t_start = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "lightning_tpu.daemon",
             "--data-dir", self.dir,
             "--gossip-store", os.path.join(self.dir, "gossip_store"),
             "--rpc-file", self.rpc_path],
            env=_child_env(), stdout=open(self.out_path, "w"),
            stderr=open(self.err_path, "w"), start_new_session=True)

    def lines(self) -> list[str]:
        with open(self.out_path) as f:
            return f.read().splitlines()

    def stderr_tail(self, n: int = 1500) -> str:
        with open(self.err_path) as f:
            return f.read()[-n:]

    def wait_line(self, phase: str, prefix: str) -> tuple[str, float]:
        """Block until the daemon has printed a line starting with
        prefix; returns (line, seconds since the daemon started)."""
        while True:
            for ln in self.lines():
                if ln.startswith(prefix):
                    return ln, time.monotonic() - self.t_start
            if self.proc.poll() is not None:
                raise SmokeFailed(phase, f"daemon exited "
                                  f"{self.proc.returncode} before "
                                  f"{prefix!r}: {self.stderr_tail()}")
            if _left() <= 0:
                raise SmokeFailed(phase, f"timed out waiting for {prefix!r}")
            time.sleep(0.2)

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()


def _parse_kv(line: str) -> dict:
    """'backend: platform=tpu kind='TPU v5 lite' count=1' → dict."""
    return dict(tok.split("=", 1) for tok in shlex.split(line)
                if "=" in tok)


def boot(d: Daemon, synth_info: dict) -> dict:
    d.start()
    line, _ = d.wait_line("boot", "backend: ")
    kv = _parse_kv(line)
    device = {"platform": kv["platform"], "kind": kv["kind"],
              "count": int(kv["count"])}
    if device["platform"] != PLATFORM:
        raise SmokeFailed("boot", f"daemon dispatches on {line!r}")
    line, t_replay = d.wait_line("replay", "crash recovery: ")
    words = line.replace(",", " ").split()
    records = int(words[words.index("store") + 1])
    sigs = int(words[words.index("replay") + 1])
    invalid = int(words[words.index("sigs") + 1])
    _emit(phase="replay", seconds_since_start=round(t_replay, 1),
          records=records, sigs=sigs, invalid=invalid)
    if invalid != 0 or sigs != synth_info["sigs"]:
        raise SmokeFailed("replay", f"replayed {sigs} sigs ({invalid} "
                          f"invalid); synth signed {synth_info['sigs']}")
    line, _ = d.wait_line("gossmap", "gossmap: ")
    words = line.replace(",", " ").split()
    if int(words[1]) != synth_info["channels"]:
        raise SmokeFailed("gossmap", line)
    _, t_rpc = d.wait_line("rpc", "rpc ready ")
    _emit(phase="rpc_ready", seconds_since_start=round(t_rpc, 1),
          gossmap=line)
    return device


def wait_warm(d: Daemon) -> dict:
    """Both solvers' and the verify/sign programs' warm-ups have run when
    the retrace detector knows all five programs (fused verify at both
    block widths, sign, route, mcf — each is noted as its warm-up
    starts) and no warm-up scope is open."""
    while True:
        st = rpc(d.rpc_path, "getmetrics")["perf"]["retraces"]
        if st["known_programs"] >= 5 and st["armed"] \
                and not st["in_warmup"]:
            t = time.monotonic() - d.t_start
            _emit(phase="warm", seconds_since_start=round(t, 1), **{
                k: st[k] for k in ("known_programs", "total")})
            return st
        if d.proc.poll() is not None:
            raise SmokeFailed("warm", f"daemon exited "
                              f"{d.proc.returncode}: {d.stderr_tail()}")
        if _left() <= 0:
            raise SmokeFailed("warm", f"timed out in warm-up: {st}")
        time.sleep(1.0)


# -- phase 3: queries against the oracle ------------------------------------

def drive_getroute(d: Daemon, g, routes) -> None:
    from lightning_tpu.gossip.gossmap import scid_parse

    t0 = time.monotonic()
    answers = []
    for i in range(0, len(routes), WAVE):
        answers += rpc_wave(d.rpc_path, [
            ("getroute", {"id": dst.hex(), "amount_msat": amt,
                          "fromid": src.hex()})
            for src, dst, amt, _ in routes[i:i + WAVE]], max(1.0, _left()))
    dt = time.monotonic() - t0
    for (src, dst, amt, want), resp in zip(routes, answers):
        if "error" in resp:
            raise SmokeFailed("getroute", json.dumps(resp["error"])[:500])
        hops = [(bytes.fromhex(h["id"]), scid_parse(h["channel"]),
                 h["direction"], h["amount_msat"], h["delay"])
                for h in resp["result"]["route"]]
        try:
            _check_path(g, src, dst, amt, FINAL_CLTV, hops)
        except (ValueError, KeyError) as e:
            raise SmokeFailed("getroute", f"{src.hex()[:8]}→"
                              f"{dst.hex()[:8]} {amt}: {e}")
        got = _route_cost(g, [(s, dd, a) for _, s, dd, a, _ in hops])
        if got != want:
            raise SmokeFailed("getroute", f"{src.hex()[:8]}→"
                              f"{dst.hex()[:8]} {amt}: cost {got}, "
                              f"host dijkstra {want}")
    _emit(phase="getroute", answers=len(answers), equal_to_host=len(answers),
          wall_seconds=round(dt, 3))


def drive_getroutes(d: Daemon, g, flows) -> None:
    t0 = time.monotonic()
    answers = rpc_wave(d.rpc_path, [
        ("getroutes", {"source": src.hex(), "destination": dst.hex(),
                       "amount_msat": amt})
        for src, dst, amt, _ in flows], max(1.0, _left()))
    dt = time.monotonic() - t0
    for (src, dst, amt, want), resp in zip(flows, answers):
        tag = f"{src.hex()[:8]}→{dst.hex()[:8]} {amt}"
        if "error" in resp:
            raise SmokeFailed("getroutes", json.dumps(resp["error"])[:500])
        got = resp["result"]
        if (got["fee_msat"], got["parts"]) != (want["fee_msat"],
                                               want["parts"]):
            raise SmokeFailed("getroutes", f"{tag}: fee/parts "
                              f"{got['fee_msat']}/{got['parts']}, host mcf "
                              f"{want['fee_msat']}/{want['parts']}")
        if sum(r["amount_msat"] for r in got["routes"]) != amt:
            raise SmokeFailed("getroutes", f"{tag}: parts do not sum")
        for r in got["routes"]:
            hops = [(bytes.fromhex(h["next_node_id"]), h["short_channel_id"],
                     h["direction"], h["amount_msat"], h["delay"])
                    for h in r["path"]]
            try:
                _check_path(g, src, dst, r["amount_msat"],
                            r["final_cltv"], hops)
            except (ValueError, KeyError) as e:
                raise SmokeFailed("getroutes", f"{tag}: {e}")
    _emit(phase="getroutes", answers=len(answers),
          equal_to_host=len(answers), wall_seconds=round(dt, 3))


# -- phase 4: the device did the work ---------------------------------------

def _samples(metrics: dict, name: str) -> list[dict]:
    return metrics.get(name, {}).get("samples", [])


def _by(metrics: dict, name: str, label: str) -> dict:
    return {s["labels"][label]: s["value"] for s in _samples(metrics, name)
            if s["value"]}


def _value(metrics: dict, name: str, **labels) -> float:
    return sum(s["value"] for s in _samples(metrics, name)
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def check_metrics(d: Daemon, synth_info: dict, warm: dict) -> None:
    snap = rpc(d.rpc_path, "getmetrics")
    m = snap["metrics"]
    row = {"phase": "metrics"}
    for fam, sent in (("route", N_GETROUTE), ("mcf", N_GETROUTES)):
        ok_dev = _value(m, f"clntpu_{fam}_queries_total", path="device",
                        outcome="ok")
        fallback = _by(m, f"clntpu_{fam}_fallback_total", "reason")
        row[f"{fam}_device_ok"] = ok_dev
        row[f"{fam}_fallback"] = fallback
        benign = fallback.pop(BENIGN_FALLBACK, 0)
        if fallback:
            raise SmokeFailed("metrics", f"{fam} fell back to the host: "
                              f"{fallback}")
        if ok_dev < 1 or ok_dev + benign != sent:
            raise SmokeFailed("metrics", f"{fam}: {ok_dev} device answers "
                              f"+ {benign} below-occupancy of {sent} sent")
    buckets = _by(m, "clntpu_replay_buckets_total", "path")
    lanes = _value(m, "clntpu_verify_lanes_total", kind="verify")
    sigs = sum(s["sum"] for s in _samples(m, "clntpu_verify_batch_sigs"))
    row.update(verify_buckets=buckets, verify_lanes=lanes, verify_sigs=sigs)
    # every bucket the replay cut ran the fused device program (the lane
    # counter is buckets x bucket width, whatever path a bucket took),
    # and together they carried exactly the signatures synth made
    fused = buckets.get("fused", 0)
    if (set(buckets) != {"fused"} or lanes % fused
            or not 0.9 * lanes <= sigs <= lanes
            or sigs != synth_info["sigs"]):
        raise SmokeFailed("metrics", f"replay buckets {buckets}, "
                          f"{lanes} lanes, {sigs} sigs")
    row["verify_bucket"] = lanes // fused
    row["breakers"] = {f: b["state"] for f, b in
                       snap["resilience"]["breakers"].items()}
    # the gauge covers every family that has a breaker (mcf too):
    # 0 = closed
    row["breaker_gauge"] = {s["labels"]["family"]: s["value"]
                            for s in _samples(m, "clntpu_breaker_state")}
    if (set(row["breakers"].values()) != {"closed"}
            or any(row["breaker_gauge"].values())
            or _value(m, "clntpu_breaker_transitions_total")):
        raise SmokeFailed("metrics", f"breakers {row['breakers']} "
                          f"{row['breaker_gauge']}")
    quarantined = _value(m, "clntpu_quarantine_total")
    retraces = snap["perf"]["retraces"]["total"]
    row.update(quarantined=quarantined, live_compiles=retraces,
               device_memory=snap["perf"].get("device_memory"))
    if quarantined or retraces != warm["total"]:
        raise SmokeFailed("metrics", f"{quarantined} rows quarantined, "
                          f"{retraces} live-path compiles")
    _emit(**row)


# -- the run ----------------------------------------------------------------

def run(workdir: str) -> dict:
    if not os.path.isdir(os.path.join(HERE, "lightning_tpu")):
        raise SmokeFailed("setup", "no lightning_tpu package beside "
                          "chip_smoke.py")
    probe_device()
    store = os.path.join(workdir, "gossip_store")
    synth_info = synth_store(store)
    sys.path.insert(0, HERE)
    g = load_graph(store)
    return boot_and_drive(Daemon(workdir), g, synth_info)


def boot_and_drive(d: Daemon, g, synth_info: dict) -> dict:
    # the host answers are computed while the daemon replays
    pool = ThreadPoolExecutor(max_workers=1)
    oracle = pool.submit(pick_queries, g, N_GETROUTE, N_GETROUTES)
    try:
        device = boot(d, synth_info)
        warm = wait_warm(d)
        routes, flows = oracle.result(max(1.0, _left()))
        drive_getroute(d, g, routes)
        drive_getroutes(d, g, flows)
        check_metrics(d, synth_info, warm)
        rpc(d.rpc_path, "stop")
        try:
            rc = d.proc.wait(timeout=max(1.0, min(120.0, _left())))
        except subprocess.TimeoutExpired:
            raise SmokeFailed("stop", "daemon did not exit after stop")
        err = d.stderr_tail(1 << 20)
        if rc != 0 or "warmup failed" in err:
            raise SmokeFailed("stop", f"exit code {rc}: {err[-1500:]}")
        _emit(phase="stop", exit_code=rc,
              seconds_total=round(time.monotonic() - _T0, 1))
    finally:
        d.kill()
        pool.shutdown(wait=False, cancel_futures=True)
    return device


def main(argv: list[str]) -> int:
    if argv:
        print(__doc__)
        return 2
    # relative paths from a private work directory: a unix socket's
    # path must fit 108 bytes whatever TMPDIR is
    parent = tempfile.mkdtemp(prefix="chip_smoke_")
    os.chdir(parent)
    os.mkdir("d")
    try:
        device = run("d")
    except SmokeFailed as e:
        _emit(ok=False, phase=e.phase, error=e.error)
        return 1
    except Exception as e:   # a bug in the script is a failed smoke too
        _emit(ok=False, phase="script", error=f"{type(e).__name__}: {e}")
        return 1
    finally:
        os.chdir(HERE)
        shutil.rmtree(parent, ignore_errors=True)
    assert "jax" not in sys.modules, "the parent must stay off the chip"
    _emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
