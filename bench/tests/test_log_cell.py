"""The logarithmic-kernel cell (``n1m-normal-log-apply``) at a tiny size
on the CPU, Pallas in interpret mode: its loop's check on Re phi reads
``correct: true`` on the program's answer and ``correct: false`` on an
altered one, and the readers of its three per-layer metrics find the
log launches and scope in a trace's op names, and nothing elsewhere."""
import dataclasses

import pytest

from bench import harness, trace_reduce
from conftest import ROOT, last_json_line
from repro.solver import FmmSolver

CELL = "n1m-normal-log-apply"
LOG_METRICS = {"eval_fused_log_ms.solve": "eval_fused_log",
               "p2l_log_ms.solve": "p2l_log",
               "log_planes_ms.solve": "log_planes"}


def tiny_log_cell() -> harness.Cell:
    """The committed cell at N = 1024: its traffic mix, limits and
    metrics, on a configuration of its kernel and distribution."""
    cell = harness.Cell.load(ROOT, CELL)
    fmm = dict(cell.config["fmm"], n=1024, nlevels=2, strong_cap=64,
               weak_cap=160, tile_boxes=8, stage_width=1)
    return dataclasses.replace(
        cell, name="tiny-log", traffic=dict(cell.traffic, problems=3),
        config=dict(cell.config, fmm=fmm))


def _run(monkeypatch, capsys, cell):
    monkeypatch.setattr(harness.Cell, "load",
                        classmethod(lambda c, r, n: cell))
    rc = harness.main(["--workload", cell.name, "--seed", str(2**31 + 11),
                       "--seconds", "2", "--trace", "0"])
    assert rc == 0
    return last_json_line(capsys.readouterr().out)


def test_log_cell_reads_correct(cpu_chips, monkeypatch, capsys):
    cell = tiny_log_cell()
    assert cell.config["fmm"]["kernel"] == "log"
    result = _run(monkeypatch, capsys, cell)
    assert result["correct"] is True, result["checks"]
    assert set(result["checks"]) == {"err_norm", "overflow", "failed"}
    assert result["checks"]["overflow"]["value"] == 0
    assert set(result["metrics"]) == {"setup_s", "solve_ms"}


def test_log_cell_refuses_an_altered_real_part(cpu_chips, monkeypatch,
                                               capsys):
    orig = FmmSolver.apply
    monkeypatch.setattr(FmmSolver, "apply",
                        lambda self, *a: orig(self, *a) * (1 + 1e-2))
    result = _run(monkeypatch, capsys, tiny_log_cell())
    assert result["correct"] is False


def _run_with_ops(op_s: dict, unit: str = "call") -> harness.Run:
    summary = trace_reduce.Summary(window_s=2.0, busy_s=1.9, chips=1,
                                   op_s=op_s)
    return harness.Run(cell=CELL, unit=unit, setup_s=1.0, window_s=2.0,
                       unit_s=[1.0, 1.0], compiles=0, trace=summary)


LOG_OPS = {
    "jit(core)/evaluation/eval_fused_log/jit(_eval_fused_pallas)/while/"
    "body/closed_call/eval_fused_log/pallas_call": 0.300,
    "jit(core)/evaluation/eval_fused_log/pad": 0.010,
    "jit(core)/downward/p2l/p2l_log/jit(_p2l_pallas)/while/body/"
    "closed_call/p2l_log/pallas_call": 0.050,
    "jit(core)/downward/m2l/log_planes/log": 0.004,
    "jit(core)/downward/m2l/log_planes/real;jit(core)/downward/m2l/mul":
        0.002,
    # a fused op counts under its first name alone
    "jit(core)/downward/m2l/mul;jit(core)/downward/m2l/log_planes/imag":
        0.008,
    "jit(core)/downward/m2l/m2l_fused/jit(_m2l_pallas)/while/body/"
    "closed_call/m2l_fused/pallas_call": 0.070,
}
HARMONIC_OPS = {
    "jit(core)/evaluation/eval_fused/jit(_eval_fused_pallas)/while/body/"
    "closed_call/eval_fused/pallas_call": 0.300,
    "jit(core)/downward/p2l/p2l/jit(_p2l_pallas)/while/body/closed_call/"
    "p2l/pallas_call": 0.050,
    "jit(core)/downward/m2l/m2l_fused/jit(_m2l_pallas)/while/body/"
    "closed_call/m2l_fused/pallas_call": 0.070,
    "jit(core)/downward/m2l/log": 0.004,
}


def test_log_readers_read_the_log_launches_and_scope():
    run = _run_with_ops(LOG_OPS)
    got = {m: harness.load_reader(m)(run) for m in LOG_METRICS}
    # ms per call over the window's two calls; a launch's staging ops
    # are not the kernel's, and a fused op whose first name lies outside
    # the scope is not the scope's
    assert got == pytest.approx({"eval_fused_log_ms.solve": 150.0,
                                 "p2l_log_ms.solve": 25.0,
                                 "log_planes_ms.solve": 3.0})


@pytest.mark.parametrize("case", ["harmonic", "untraced", "step"])
def test_log_readers_return_none_without_the_log_names(case):
    run = {"harmonic": _run_with_ops(HARMONIC_OPS),
           "untraced": dataclasses.replace(_run_with_ops(LOG_OPS),
                                           trace=None),
           "step": _run_with_ops(LOG_OPS, unit="step")}[case]
    for m in LOG_METRICS:
        assert harness.load_reader(m)(run) is None, m
