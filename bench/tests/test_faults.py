"""The check that decides ``correct`` against faults planted under the
timed path: each run goes through the harness as on the chip (the look
for a TPU skipped), at a tiny size, with one of the program's entry
points broken, and has to come out not correct.

Faults, as each kind of cell can have them:

- ``stale_state``: a time step whose velocity evaluation keeps returning
  the plan of the first positions it saw, so the state it advances from
  is unchanged (time-stepping cell);
- ``half_left_out``: half of the batch (or of the particles) left out,
  its potentials zero;
- ``answer_altered``: the potential scaled by 1 + 1e-2 where it is
  produced (in a batch, one problem's row).

No cell runs across chips, so no exchange between chips can be left out.
"""
import pytest

from bench import harness
from conftest import tiny_cell
from repro.solver import FmmSolver
from repro.solver.guard import GuardedSolver


def _half(phi):
    n = phi.shape[-1]
    return phi.at[..., n // 2:].set(0)


def _altered(phi):
    if phi.ndim == 2:
        return phi.at[0].multiply(1 + 1e-2)
    return phi * (1 + 1e-2)


def _wrap(monkeypatch, cls, name, post):
    orig = getattr(cls, name)
    monkeypatch.setattr(cls, name,
                        lambda self, *a: post(orig(self, *a)))


def _stale_plan(monkeypatch):
    orig = GuardedSolver.refresh_guarded
    first = {}

    def refresh_guarded(self, z, q):
        if "plan" not in first:
            first["plan"] = orig(self, z, q)
        return first["plan"]

    monkeypatch.setattr(GuardedSolver, "refresh_guarded", refresh_guarded)


FAULTS = {
    ("apply", "half_left_out"): lambda m: _wrap(m, FmmSolver, "apply", _half),
    ("apply", "answer_altered"): lambda m: _wrap(m, FmmSolver, "apply", _altered),
    ("apply_batched", "half_left_out"):
        lambda m: _wrap(m, FmmSolver, "apply_batched", lambda p: p.at[p.shape[0] // 2:].set(0)),
    ("apply_batched", "answer_altered"):
        lambda m: _wrap(m, FmmSolver, "apply_batched", _altered),
    ("rk2_guarded", "stale_state"): _stale_plan,
    ("rk2_guarded", "half_left_out"):
        lambda m: _wrap(m, FmmSolver, "apply_plan", _half),
    ("rk2_guarded", "answer_altered"):
        lambda m: _wrap(m, FmmSolver, "apply_plan", _altered),
}


@pytest.mark.parametrize("kind,fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(kind, fault, cpu_chips, monkeypatch):
    cell = tiny_cell(kind)
    cell.traffic["warm"] = 1
    FAULTS[kind, fault](monkeypatch)
    result = harness.run_cell(cell, 2**31 + 11, 1.0, False)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("kind", ["apply", "apply_batched", "rk2_guarded"])
def test_unbroken_run_is_correct(kind, cpu_chips):
    result = harness.run_cell(tiny_cell(kind), 2**31 + 11, 1.0, False)
    assert result["correct"] is True, result["checks"]


@pytest.mark.parametrize("kind", ["apply", "apply_batched"])
def test_caps_that_do_not_hold_are_not_correct(kind, cpu_chips):
    """Caps too small for the inputs: the program drops interactions, and
    its own connectivity count says so."""
    cell = tiny_cell(kind)
    cell.config["fmm"].update(strong_cap=4, weak_cap=8)
    result = harness.run_cell(cell, 2**31 + 11, 1.0, False)
    assert result["checks"]["overflow"]["value"] > 0
    assert result["correct"] is False


@pytest.mark.parametrize("kind", ["apply", "apply_batched", "rk2_guarded"])
@pytest.mark.parametrize("control,correct", [("direct-bf16", False),
                                             ("direct-f32", True)])
def test_direct_sum_control(kind, control, correct, cpu_chips):
    """The direct sum in bfloat16 in the program's place fails the
    committed limits through the harness's own check; the float32 direct
    sum, the configurations' precision, passes them."""
    result = harness.run_cell(tiny_cell(kind), 2**31 + 13, 1.0, False,
                              control=control)
    assert result["correct"] is correct, result["checks"]


@pytest.fixture
def fresh_programs():
    """A control traces the program anew; clear what it traced after."""
    import jax

    FmmSolver.cache_clear()
    jax.clear_caches()
    yield
    FmmSolver.cache_clear()
    jax.clear_caches()


@pytest.mark.parametrize("kind", ["apply", "apply_batched", "rk2_guarded"])
def test_m2l_contraction_in_bf16_is_not_correct(kind, cpu_chips,
                                                fresh_programs):
    """The program with its M2L contraction on bfloat16 operands, the
    chip's default precision, fails the committed limits."""
    result = harness.run_cell(tiny_cell(kind), 2**31 + 13, 1.0, False,
                              control="m2l-bf16")
    assert result["correct"] is False, result["checks"]
