"""The harness end to end on the CPU at a tiny size, Pallas in interpret
mode: the result line's keys, the metrics each kind of cell reports, and
the refusal to run without a TPU."""
import os
import subprocess
import sys

import pytest

from bench import harness
from conftest import ROOT, last_json_line, tiny_cell

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
END_TO_END = {"apply": {"setup_s", "solve_ms", "solve_p90_ms"},
              "apply_batched": {"setup_s", "solve_ms", "solve_p90_ms"},
              "rk2_guarded": {"setup_s", "step_ms"}}


@pytest.mark.parametrize("kind", sorted(END_TO_END))
def test_last_line_has_the_contract_keys(kind, cpu_chips, monkeypatch, capsys):
    cell = tiny_cell(kind)
    monkeypatch.setattr(harness.Cell, "load", classmethod(lambda c, r, n: cell))
    rc = harness.main(["--workload", cell.name, "--seed", str(2**31 + 7),
                       "--seconds", "2", "--trace", "0"])
    out = capsys.readouterr()
    result = last_json_line(out.out)
    assert rc == 0
    assert list(result) == KEYS                   # checks come last
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == END_TO_END[kind]
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["checks"]) == set(cell.limits) | {"failed"}
    assert out.err.strip().splitlines()[-1].startswith("[bench] check ")


def test_same_seed_gives_same_inputs():
    cell = tiny_cell("apply_batched")
    a = harness.make_entry(cell.config, cell.traffic, 12345678901)
    b = harness.make_entry(cell.config, cell.traffic, 12345678901)
    a.setup()
    b.setup()
    for (za, qa), (zb, qb) in zip(a.host, b.host):
        assert (za == zb).all() and (qa == qb).all()


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "n1m-uniform-apply",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs 1 TPU chip" in r.stderr
