"""The per-phase readers (``bench.phases``) and the readers the benchmark
had before them: op names with and without the program's scopes, a
synthetic trace with the program's ``fmm.*`` host spans nested in the
benchmark's ``bench.*`` spans, the trace recorded before the program had
scopes (``b64-n3584-apply.trace.json.gz``), and one second of
``b64-n3584-apply`` recorded on a TPU v5e with the scopes
(``b64-n3584-apply.scoped.trace.json.gz``)."""
import json
import os

import pytest

from bench import harness, phases, trace_reduce
from conftest import ROOT

DATA = os.path.join(ROOT, "bench", "tests", "data")
PHASES = {"topology_ms.solve": "topology", "upward_ms.solve": "upward",
          "downward_ms.solve": "downward", "eval_phase_ms.solve": "evaluation",
          "upward_ms.step": "upward", "downward_ms.step": "downward",
          "eval_phase_ms.step": "evaluation"}
U = phases.UNSCOPED


@pytest.mark.parametrize("name,expected", [
    ("jit(core)/upward/p2m/scatter-add", ("core", "upward")),
    ("jit(core)/vmap(upward)/p2m/scatter-add", ("core", "upward")),
    ("jit(core)/vmap(vmap(topology))/sort/sort", ("core", "topology")),
    ("jit(evaluate)/evaluation/eval_fused/jit(_eval_fused_pallas)/while/"
     "body/closed_call/eval_fused/pallas_call", ("evaluate", "evaluation")),
    ("jit(build)/topology/connect/mul;jit(build)/topology/connect/iota",
     ("build", "topology")),
    ("jit(core)/gather", ("core", U)),
    ("jit(core)/vmap()/gather", ("core", U)),
    ("jit(core)/jit(_m2l_pallas)/while/body/closed_call/pallas_call",
     ("core", U)),
    ("jit(core)/vmap(jit(sort))/sort", ("core", U)),
    ("custom_fusion", (None, U)),
    ("z", (None, U)),
])
def test_program_and_phase_of_an_op_name(name, expected):
    assert phases.program_and_phase(name) == expected


def _ev(pid, tid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": args}


def _synthetic(fmm_spans: bool):
    """Two calls of a batched ``core`` whose ops carry scoped,
    vmap-wrapped, unscoped and no ``tf_op``; with ``fmm_spans`` the
    program's host spans sit inside the benchmark's."""
    meta = [{"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
             "args": {"name": "XLA Modules"}},
            {"ph": "M", "pid": 1, "tid": 3, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": 9, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
             "args": {"name": "python"}}]
    events = [_ev(9, 1, "bench.window", 0, 2000)]
    for t0 in (0, 1000):
        events += [
            _ev(9, 1, "bench.call", t0, 300),
            _ev(9, 1, "bench.block", t0 + 300, 700),
            _ev(1, 2, "jit_core(42)", t0 + 200, 700),
            _ev(1, 3, "sort", t0 + 200, 100, hlo_category="sort",
                tf_op="jit(core)/vmap(topology)/sort/jit(sort)/sort:"),
            _ev(1, 3, "fusion.1", t0 + 300, 250,
                tf_op="jit(core)/vmap(upward)/p2m/scatter-add:"),
            _ev(1, 3, "while.2", t0 + 550, 150, hlo_category="while",
                tf_op="jit(core)/vmap(downward)/m2l/m2l_fused/"
                      "vmap(jit(_m2l_pallas))/while:"),
            _ev(1, 3, "closed_call.3", t0 + 560, 120,
                tf_op="jit(core)/vmap(downward)/m2l/m2l_fused/"
                      "vmap(jit(_m2l_pallas))/while/body/closed_call/"
                      "m2l_fused/pallas_call:"),
            _ev(1, 3, "fusion.4", t0 + 700, 150,
                tf_op="jit(core)/vmap(evaluation)/unsort/scatter:"),
            _ev(1, 3, "custom-call.5", t0 + 850, 30),
            _ev(1, 3, "copy.6", t0 + 880, 20, tf_op="jit(core)/vmap()/copy:"),
        ]
        if fmm_spans:
            events += [_ev(9, 1, "fmm.apply_batched", t0 + 10, 280),
                       _ev(9, 1, "fmm.validate", t0 + 20, 150),
                       _ev(9, 1, "fmm.dispatch", t0 + 180, 100)]
    return meta + events


def _run(unit, summary):
    return harness.Run(cell="c", unit=unit, setup_s=1.0, window_s=2e-3,
                       unit_s=[1e-3, 1e-3], compiles=0, trace=summary)


def _all_readers():
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]


def test_synthetic_phases_per_unit():
    s = trace_reduce.summarize_events(_synthetic(fmm_spans=True))
    assert phases.phase_seconds(s.op_s) == {
        ("core", "topology"): pytest.approx(200e-6),
        ("core", "upward"): pytest.approx(500e-6),
        ("core", "downward"): pytest.approx(300e-6),   # while + kernel
        ("core", "evaluation"): pytest.approx(300e-6),
        ("core", U): pytest.approx(40e-6),
        (None, U): pytest.approx(60e-6)}
    expected = {"topology": 0.1, "upward": 0.25, "downward": 0.15,
                "evaluation": 0.15}
    for name, phase in PHASES.items():
        unit = "step" if name.endswith(".step") else "call"
        assert harness.load_reader(name)(_run(unit, s)) == pytest.approx(
            expected[phase])
        other = "call" if unit == "step" else "step"
        assert harness.load_reader(name)(_run(other, s)) is None
        assert harness.load_reader(name)(_run(unit, None)) is None


def test_program_spans_move_no_existing_reading():
    """The program's ``fmm.*`` spans inside the benchmark's: every reader
    reads what it read without them, and the idle gaps keep the
    benchmark's names."""
    with_spans = trace_reduce.summarize_events(_synthetic(fmm_spans=True))
    without = trace_reduce.summarize_events(_synthetic(fmm_spans=False))
    for name in _all_readers():
        for unit in ("call", "step"):
            read = harness.load_reader(name)
            assert read(_run(unit, with_spans)) == read(_run(unit, without))
    assert with_spans.gap_s == without.gap_s
    assert set(with_spans.gap_s) == {"bench.call", "bench.block"}


#: Every reader on the trace recorded before the program had scopes, in
#: a run of three calls (or steps) of 0.5, 0.49 and 0.51 s over 1.5 s.
BEFORE_SCOPES = {
    "call": {"setup_s": 12.5, "solve_ms": 500.0, "solve_p90_ms": 508.0,
             "idle_share.solve": 0.28012015002689683,
             "sort_ms.solve": 2.349837708, "pallas_ms.solve": 291.874726954,
             "compiles.solve": 0},
    "step": {"setup_s": 12.5, "step_ms": 500.0,
             "idle_share.step": 0.28012015002689683, "compiles.step": 0},
}


@pytest.mark.parametrize("unit", ["call", "step"])
def test_readers_on_the_trace_recorded_before_the_scopes(unit):
    s = trace_reduce.summarize_events(trace_reduce.load(
        os.path.join(DATA, "b64-n3584-apply.trace.json.gz")))
    run = harness.Run(cell="c", unit=unit, setup_s=12.5, window_s=1.5,
                      unit_s=[0.5, 0.49, 0.51], compiles=0, trace=s)
    got = {n: harness.load_reader(n)(run) for n in _all_readers()}
    want = {n: BEFORE_SCOPES[unit].get(n) for n in got}
    assert got == pytest.approx(want)
    # 12 readers before this file: the 7 per-phase ones find no scope
    assert len([n for n in got if n not in PHASES]) == 12


SCOPED = os.path.join(DATA, "b64-n3584-apply.scoped.trace.json.gz")


@pytest.fixture(scope="module")
def scoped():
    return trace_reduce.summarize_events(trace_reduce.load(SCOPED))


def test_recorded_scoped_trace_phases_cover_the_program(scoped):
    """On the chip the scopes hold all but a few percent of ``core``'s
    device time: what has a ``tf_op`` and no scope is under 5 %."""
    seconds = phases.phase_seconds(scoped.op_s)
    core = {p: s for (prog, p), s in seconds.items() if prog == "core"}
    assert set(core) >= {"topology", "upward", "downward", "evaluation"}
    assert core.get(U, 0.0) < 0.05 * scoped.program_s["core"]
    named = sum(s for p, s in core.items() if p != U)
    no_tf_op = seconds.get((None, U), 0.0)
    assert named + core.get(U, 0.0) + no_tf_op == pytest.approx(
        scoped.program_s["core"], rel=0.05)


def test_recorded_scoped_trace_names_each_kernel(scoped):
    kernels = {name.rsplit("/", 2)[-2] for name in scoped.op_s
               if name.endswith("/pallas_call")}
    assert kernels == {"eval_fused", "m2l_fused", "p2l", "leaf_classify"}
    for kernel, phase in [("eval_fused", "evaluation"),
                          ("m2l_fused", "downward"), ("p2l", "downward"),
                          ("leaf_classify", "topology")]:
        assert any(phases.program_and_phase(n) == ("core", phase)
                   and f"/{kernel}/pallas_call" in n for n in scoped.op_s)


def test_recorded_scoped_trace_readers(scoped):
    with open(os.path.join(DATA, "b64-n3584-apply.scoped.json")) as f:
        recorded = json.load(f)
    run = harness.Run(cell="b64-n3584-apply", unit="call", setup_s=1.0,
                      window_s=scoped.window_s, unit_s=recorded["unit_s"],
                      compiles=0, trace=scoped)
    for name in PHASES:
        value = harness.load_reader(name)(run)
        if name.endswith(".step"):
            assert value is None
        else:
            assert value == pytest.approx(recorded["metrics"][name]["value"])
