"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
cell's configuration file (``configs[].file``), its traffic mix
(``bench/traffic/<traffic>.json``), the loop that traffic names
(``bench/loops/<loop>.py``, its class ``Loop``; see ``bench.entry``), the
limits of its correctness check (``bench/limits/<cell>.json``) and one
reader per metric (``bench/metrics/<metric>.py``, a ``read(run)``
function that returns a number, or None where it finds nothing to read).

A run: check that JAX sees the chips the cell asks for (else exit 2 with
no result); set-up (inputs from the seed, the solver from the
configuration, every shape of the window run once); the window, which
repeats the cell's unit of work until ``--seconds`` have passed, each
unit ending in ``block_until_ready``; with ``--trace 1`` the window runs
under the profiler and the per-layer metrics are read from its trace;
then the check of a sample of the window's results against the float64
direct sum. The last line of standard output is the result object; the
numbers compared, each with its limit, are the last lines of standard
error and the last key of the result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def named(items: list, name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


@dataclass
class Cell:
    """Everything one cell's run is made from, as read from its files."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, root: str, name: str) -> "Cell":
        spec = load_json(os.path.join(root, "BENCHMARK.json"))
        cell = named(spec["workloads"], name, "workload")
        conf = named(spec["configs"], cell["config"], "config")
        return cls(
            name=name, chips=cell["chips"],
            config=load_json(os.path.join(root, conf["file"])),
            traffic=load_json(os.path.join(
                root, "bench", "traffic", cell["traffic"] + ".json")),
            limits=load_json(os.path.join(root, "bench", "limits",
                                          name + ".json"))["limits"],
            end_to_end=[m for m in spec["end_to_end"] if name in
                        m.get("workloads", [name])],
            per_layer=[m for m in spec["per_layer"] if name in
                       m.get("workloads", [name])])


@dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: str
    unit: str                  # "call" or "step"
    setup_s: float
    window_s: float
    unit_s: list               # host seconds of each unit in the window
    compiles: int              # programs compiled or loaded in the window
    trace: object = None       # bench.trace_reduce.Summary, traced runs


class CompileCounter:
    """Counts the programs JAX compiles, or loads from its persistent
    cache, through ``jax.monitoring``: every new executable passes the
    backend-compile event, cache hit or not."""

    _instance = None

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax

            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._on_duration)
            jax.monitoring.register_event_listener(cls._instance._on_event)
        return cls._instance

    def __init__(self):
        self.compiles = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, a
    fixed path (the path is part of the cache key), unless
    ``JAX_COMPILATION_CACHE_DIR`` names one. Every program is cached,
    however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def chips(count: int) -> list:
    """The devices of the cell, or SystemExit(2) where JAX finds no TPU or
    fewer chips than the cell asks for."""
    import jax

    from .devices import peaks

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < count:
        log(f"needs {count} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform!r} device(s) ({devices[0].device_kind})")
        raise SystemExit(2)
    peaks(devices[0].device_kind)
    return devices[:count]


def load_module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, found by name."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str):
    return load_module("metrics", name).read


def make_entry(config: dict, traffic: dict, seed: int):
    """The loop the traffic names, set to run it from ``seed``."""
    return load_module("loops", traffic["loop"]).Loop(config, traffic, seed)


def read_metrics(defs: list, run: Run) -> dict:
    out = {}
    for m in defs:
        value = load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def window(entry, seconds: float, counter: CompileCounter) -> dict:
    """Repeat ``entry.unit`` for about ``seconds``: the window stops after
    the unit that brings it within half a mean unit of the deadline, so
    that with units of many seconds it ends as near the deadline as whole
    units allow. The window's length is the time from its first unit's
    start to its last unit's end."""
    import jax

    unit_s, failed, i = [], 0, 0
    before = counter.compiles
    with jax.profiler.TraceAnnotation("bench.window"):
        start = time.perf_counter()
        deadline = start + seconds
        while True:
            t = time.perf_counter()
            failed += entry.unit(i)
            end = time.perf_counter()
            unit_s.append(end - t)
            i += 1
            if deadline - end < 0.5 * (end - start) / i:
                break
    return {"window_s": end - start, "unit_s": unit_s, "failed": failed,
            "compiles": counter.compiles - before}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             started: float | None = None, control: str | None = None) -> dict:
    """One run of ``cell``; returns the result object (with the checks as
    its last key). ``control`` (calibration only; ``bench.control``) runs
    the cell with a lower precision in the program's place: the direct sum
    ("direct-bf16", "direct-f32") at the check, or the M2L contraction
    ("m2l-high", "m2l-bf16") throughout."""
    started = time.perf_counter() if started is None else started
    sys.path.insert(0, os.path.join(ROOT, "src"))
    if control and control.startswith("m2l-"):
        from . import control as controls

        with controls.m2l_contraction(control[len("m2l-"):]):
            return run_cell(cell, seed, seconds, trace, started=started)
    import jax

    from . import devices as device_table, trace_reduce

    use_compile_cache()
    counter = CompileCounter.get()
    devs = chips(cell.chips)
    misses0, compiles0 = counter.cache_misses, counter.compiles
    log(f"chip found at {time.perf_counter() - started:.3f} s")

    entry = make_entry(cell.config, cell.traffic, seed)
    entry.setup()
    setup_s = time.perf_counter() - started
    log(f"set-up {setup_s:.3f} s: {counter.compiles - compiles0} programs "
        f"compiled or loaded, {counter.cache_misses - misses0} cache misses")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    window_started = time.perf_counter()
    try:
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        try:
            w = window(entry, seconds, counter)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        summary = trace_reduce.summarize(trace_dir) if trace_dir else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device = device_table.describe(devs)
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    units = sorted(w["unit_s"])
    log(f"window {w['window_s']:.3f} s, {len(units)} {entry.unit_name}s "
        f"(median {1e3 * units[len(units) // 2]:.3f} ms, longest "
        f"{1e3 * units[-1]:.3f} ms), {w['compiles']} compiles, {w['failed']} "
        f"failed; with the trace {time.perf_counter() - window_started:.3f} s")

    run = Run(cell=cell.name, unit=entry.unit_name, setup_s=setup_s,
              window_s=w["window_s"], unit_s=w["unit_s"],
              compiles=w["compiles"], trace=summary)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, run)

    checked = time.perf_counter()
    direct = control[len("direct-"):] if control else None
    readings = entry.readings(direct)
    log(f"check {time.perf_counter() - checked:.3f} s, readings {readings}")
    checks = {name: {"value": readings[name], "limit": limit}
              for name, limit in cell.limits.items()}
    checks["failed"] = {"value": w["failed"], "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": len(w["unit_s"]),
              "failed": w["failed"], "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    started = time.perf_counter()
    args = parse(argv)
    cell = Cell.load(ROOT, args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      started=started)
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0
