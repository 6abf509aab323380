"""The reduction from a profiler trace to the per-layer metrics, on a
small trace recorded on a TPU v5e (one second of the b64-n3584-apply cell
under ``bench/run.py --trace 1``) and on synthetic events."""
import os

import pytest

from bench import harness, trace_reduce
from conftest import ROOT

FIXTURE = os.path.join(ROOT, "bench", "tests", "data",
                       "b64-n3584-apply.trace.json.gz")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.summarize_events(trace_reduce.load(FIXTURE))


def test_recorded_trace_window_and_busy_time(summary):
    assert summary.chips == 1
    assert 0 < summary.busy_s <= summary.window_s
    assert 0 <= summary.idle_share < 1
    # exclusive op times add up to the busy time when ops do not overlap
    assert sum(summary.op_s.values()) == pytest.approx(summary.busy_s, rel=0.01)


def test_recorded_trace_kinds_and_programs(summary):
    assert summary.kind_s["pallas"] > 0 and summary.kind_s["sort"] > 0
    assert set(summary.kind_s) <= {"pallas", "sort", "other"}
    assert 0 < summary.program_s["core"] <= summary.window_s
    kernels = {name for name in summary.op_s if "pallas_call" in name}
    assert any("_eval_fused_pallas" in k for k in kernels)
    assert any("_m2l_pallas" in k for k in kernels)


def test_breakdown_lists_the_largest_first(summary):
    b = summary.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for entries in b.values():
        assert len(entries) <= 10
        seconds = [s for _, s in entries]
        assert seconds == sorted(seconds, reverse=True)
    assert all(name.startswith("bench.") or name.startswith("outside")
               for name, _ in b["idle_gaps"])


def _ev(pid, tid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": args}


def _synthetic():
    meta = [{"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name", "args": {"name": "XLA Modules"}},
            {"ph": "M", "pid": 1, "tid": 3, "name": "thread_name", "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": 9, "name": "process_name", "args": {"name": "/host:CPU"}},
            {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name", "args": {"name": "python"}}]
    return meta + [
        _ev(9, 1, "bench.window", 0, 1000),
        _ev(9, 1, "bench.call", 0, 500),
        _ev(9, 1, "bench.refresh_guarded", 500, 500),
        _ev(1, 2, "jit_core(123)", 100, 300),
        _ev(1, 3, "while.1", 100, 300, hlo_category="while"),
        _ev(1, 3, "closed_call.2", 150, 100,
            tf_op="jit(core)/jit(_k)/while/body/closed_call/pallas_call:"),
        _ev(1, 3, "sort", 300, 50, tf_op="jit(core)/sort:", hlo_category="sort"),
        _ev(1, 3, "fusion.9", 700, 100, tf_op="jit(core)/scatter-add:"),
    ]


def test_synthetic_trace_self_times_and_gaps():
    s = trace_reduce.summarize_events(_synthetic())
    assert s.window_s == pytest.approx(1e-3)
    assert s.busy_s == pytest.approx(400e-6)
    assert s.idle_share == pytest.approx(0.6)
    assert s.kind_s["pallas"] == pytest.approx(100e-6)
    assert s.kind_s["sort"] == pytest.approx(50e-6)
    assert s.kind_s["other"] == pytest.approx(250e-6)   # while self + scatter
    assert s.program_s == {"core": pytest.approx(300e-6)}
    # gaps [0,100), [400,700) and [800,1000), each named by the innermost
    # span around its middle
    assert s.gap_s["bench.call"] == pytest.approx(100e-6)
    assert s.gap_s["bench.refresh_guarded"] == pytest.approx(500e-6)


def test_trace_without_window_is_refused():
    events = [e for e in _synthetic() if e.get("name") != "bench.window"]
    with pytest.raises(ValueError):
        trace_reduce.summarize_events(events)


def _run(unit, trace):
    return harness.Run(cell="c", unit=unit, setup_s=1.0, window_s=2.0,
                       unit_s=[0.5] * 4, compiles=0, trace=trace)


@pytest.mark.parametrize("name,unit,expected", [
    ("idle_share.solve", "call", 60.0),
    ("idle_share.step", "step", 60.0),
    ("sort_ms.solve", "call", 1e3 * 50e-6 / 4),
    ("pallas_ms.solve", "call", 1e3 * 100e-6 / 4),
    ("topology_ms.step", "step", None),         # no "build" program ran
    ("evaluation_ms.step", "step", None),
    ("compiles.solve", "call", 0),
    ("compiles.step", "step", 0),
])
def test_per_layer_readers(name, unit, expected):
    s = trace_reduce.summarize_events(_synthetic())
    got = harness.load_reader(name)(_run(unit, s))
    assert got == (pytest.approx(expected) if expected else expected)


@pytest.mark.parametrize("name", ["idle_share.solve", "sort_ms.solve",
                                  "pallas_ms.solve", "topology_ms.step"])
def test_readers_find_nothing_without_a_trace(name):
    unit = "step" if name.endswith(".step") else "call"
    assert harness.load_reader(name)(_run(unit, None)) is None
