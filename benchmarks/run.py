#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it holds the chip, makes the cell's inputs from --seed,
sets the program up (all of that is `setup_s`), drives it for --seconds,
reads the peak device memory, checks what the timed path produced
against the reference in benchmarks/reference/, and prints one JSON
object as its last line.  Without a TPU it prints no result and exits 3.

Nothing here knows a cell, a configuration or a metric by name.  The
cell's entry in BENCHMARK.json names its configuration
(`configs/<config>.json`); `workloads/<cell>.json` names the driver
(`drivers/<driver>.py`), its parameters, the program's environment and
which of the driver's quantities each end-to-end metric takes; every
per-layer metric is read by `layers/<metric>.py`.  README.md says how a
later PR adds any of these as files of its own.
"""
from __future__ import annotations

import time

T0 = time.monotonic()        # process start, as near as Python allows

import argparse              # noqa: E402
import importlib.util        # noqa: E402
import json                  # noqa: E402
import os                    # noqa: E402
import shutil                # noqa: E402
import sys                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WINDOW_SPAN = "bench/traced_window"


class NoResult(Exception):
    """The run cannot be made: no result line, exit code 3."""


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise NoResult(f"no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise NoResult(f"no {os.path.relpath(path, ROOT)}")
    with open(path, encoding="utf8") as f:
        return json.load(f)


def metrics_of_cell(bench: dict, section: str, cell: str) -> list[dict]:
    """The metrics of `end_to_end` / `per_layer` this cell reports: an
    end-to-end metric that lists no `workloads` is every cell's; a
    per-layer metric always lists its cells (README.md)."""
    if section == "end_to_end":
        return [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    return [m for m in bench["per_layer"] if cell in m["workloads"]]


def require_chip(chips: int) -> dict:
    """What jax reports, or NoResult where it is no TPU or too few.
    The tests under benchmarks/tests/ replace this function; run.py
    itself has no way round it."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoResult(f"jax reports platform {devs[0].platform!r}: the "
                       "benchmark runs on a TPU and never falls back")
    if len(devs) < chips:
        raise NoResult(f"the cell asks for {chips} chips, jax has "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileWatch:
    """Counts what jax compiles or loads from its cache, process-wide
    (jax.monitoring), so that a window can show it built nothing."""

    def __init__(self):
        import jax.monitoring

        self.events: list[str] = []
        self.seconds: dict[str, list] = {}   # every jax event: [s, n]
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **_kw) -> None:
        tot = self.seconds.setdefault(name.rsplit("/", 1)[-1], [0.0, 0])
        tot[0] += secs
        tot[1] += 1
        if name.endswith("backend_compile_duration") \
                or "cache_retrieval" in name:
            self.events.append(name)

    def count(self) -> int:
        return len(self.events)

    def summary(self) -> dict:
        return {k: [round(s, 3), n] for k, (s, n) in self.seconds.items()}


class Run:
    """What a driver and the readers are handed."""

    def __init__(self, args, bench, cell, config, workload, device, peaks):
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.bench, self.cell = bench, cell
        self.config, self.workload = config, workload
        self.device, self.peaks = device, peaks
        self.root, self.here = ROOT, HERE
        self.cache_dir = os.path.join(HERE, ".cache")
        # one directory per cell, emptied at the start of a run
        self.work_dir = os.path.join(self.cache_dir, "run", cell["name"])
        self.trace_dir = os.path.join(self.work_dir, "trace")
        self.compiles = None
        self.notes: list[str] = []     # earlier lines of stdout
        self.phases: dict = {}         # set-up seconds by phase
        # filled by the window
        self.quantities: dict = {}     # the driver's end-to-end numbers
        self.delta = None              # lib.counters.Delta over the window
        self.samples: list = []        # the clients' samples
        self.traced: dict | None = None

    def note(self, **row) -> None:
        self.notes.append(json.dumps(row))

    def phase(self, name: str, t_start: float) -> None:
        self.phases[name] = round(time.monotonic() - t_start, 3)

    def traced_window(self, seconds: float) -> None:
        """Hold a profiler session open for `seconds` (the program's own
        `trace.profile_session()`, switched on by its environment
        variable only for as long as this takes), the harness's span
        around it; then reduce the trace."""
        import jax

        from lightning_tpu.utils import trace as ptrace

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.environ["LIGHTNING_TPU_PROFILE"] = self.trace_dir
        try:
            with ptrace.profile_session():
                # later sessions the program would open see none asked
                os.environ.pop("LIGHTNING_TPU_PROFILE", None)
                with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                    time.sleep(seconds)
        finally:
            os.environ.pop("LIGHTNING_TPU_PROFILE", None)

    def reduce_trace(self) -> None:
        from lib import xplane

        spans = tuple(self.workload.get("trace_spans", ()))
        self.traced = xplane.reduce(self.trace_dir, spans, WINDOW_SPAN)
        shutil.rmtree(self.trace_dir, ignore_errors=True)


def breakdown(traced: dict) -> dict:
    """The ten device operations that took most time (by the XLA op
    names as they are today) and the longest idle gaps by the host span
    around them."""
    top = sorted(traced["ops"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": traced["idle_gaps"][:10]}


def run_cell(args) -> tuple[dict, list[str], list[tuple]]:
    """(result object, earlier stdout lines, numbers compared)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise NoResult(f"BENCHMARK.json has no workload {args.workload!r}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(ROOT, cfg_entry["file"])
    workload = load_json(HERE, "workloads", cell["name"] + ".json")
    if not os.path.isdir(os.path.join(ROOT, "lightning_tpu")):
        raise NoResult("no program beside the benchmark: nothing to measure")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    driver = load_module("drivers", workload["driver"])
    # the cell's environment for the program, before it is imported
    os.environ.update(workload.get("env", {}))
    device = require_chip(cell["chips"])
    peaks = load_json(HERE, "peaks.json")
    if device["kind"] not in peaks:
        raise NoResult(f"peaks.json has no device {device['kind']!r}")
    from lightning_tpu.utils import jaxcfg

    jaxcfg.setup_cache()      # $JAX_COMPILATION_CACHE_DIR or .jax_cache
    run = Run(args, bench, cell, config, workload, device,
              peaks[device["kind"]])
    run.compiles = CompileWatch()
    shutil.rmtree(run.work_dir, ignore_errors=True)
    os.makedirs(run.work_dir)

    state = driver.setup(run)
    setup_s = time.monotonic() - T0
    run.note(setup_jax_events=run.compiles.summary())
    try:
        driver.window(run, state)
        mem = memory_peak_bytes()
        if run.trace:
            run.reduce_trace()
        compared, attempted, failed = driver.check(run, state)
    finally:
        driver.teardown(run, state)

    correct = all(abs(v) <= lim for _, v, lim in compared)
    metrics = {}
    if not run.trace:
        take = workload["end_to_end"]
        for m in metrics_of_cell(bench, "end_to_end", cell["name"]):
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = run.quantities[take[m["name"]]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of_cell(bench, "per_layer", cell["name"]):
            value = load_module("layers", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=mem)
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": dev}
    if run.trace and run.traced is not None:
        dev["busy_s"] = run.traced["busy_s"]
        dev["window_s"] = run.traced["window_s"]
        result["breakdown"] = breakdown(run.traced)
    run.note(setup_s=setup_s, setup_phases=run.phases,
             quantities=run.quantities, device=dev)
    result["compared"] = {n: {"value": v, "limit": lim}
                          for n, v, lim in compared}
    return result, run.notes, compared


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = sys.stdout           # the program's own prints go elsewhere
    try:
        result, notes, compared = run_cell(args)
    except NoResult as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3
    finally:
        sys.stdout = out
    for line in notes:
        print(line, file=out)
    print(json.dumps(result), file=out, flush=True)
    for name, value, limit in compared:
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    code = main(sys.argv[1:])
    sys.stdout.flush()
    # a pass the window abandoned may still hold a thread of the program
    os._exit(code)
