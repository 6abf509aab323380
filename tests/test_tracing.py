"""What the program tells a profiler: the phase scopes and kernel names
in the op names of every compiled entry point (they become the ``tf_op``
of a device trace), and the grid-step counter of the staged kernels
that ``FmmSolver.stats`` reports."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FmmConfig
from repro.data.synthetic import particles
from repro.kernels import common
from repro.solver import FmmSolver

CFG = FmmConfig(n=512, nlevels=2, p=6, theta=0.5, dtype="f32",
                strong_cap=48, weak_cap=128)

#: Scope paths each program's op names must hold, after ``jit(<program>)/``.
TOPOLOGY = ["topology/sort/", "topology/connect/",
            "topology/connect/leaf_classify/"]
EVALUATE = ["upward/p2m/", "upward/m2m/", "downward/m2l/m2l_fused/",
            "downward/l2l/", "downward/p2l/p2l/", "evaluation/eval_fused/",
            "evaluation/unsort/"]
KERNELS = ["eval_fused", "m2l_fused", "p2l", "leaf_classify"]


def _inputs(batch=None):
    z, q = particles("normal", CFG.n, 3)
    z, q = jnp.asarray(z, jnp.complex64), jnp.asarray(q, jnp.complex64)
    if batch:
        return jnp.stack([z] * batch), jnp.stack([q] * batch)
    return z, q


def _entries(solver):
    """(program, jitted entry, arguments, scope paths) of each entry point."""
    z, q = _inputs()
    zb, qb = _inputs(batch=2)
    plan = jax.eval_shape(solver._refresh, z, q)
    return [("core", solver._apply, (z, q), TOPOLOGY + EVALUATE),
            ("core", solver._apply_batched, (zb, qb), TOPOLOGY + EVALUATE),
            ("build", solver._refresh, (z, q), TOPOLOGY),
            ("evaluate", solver._apply_plan, (plan,), EVALUATE)]


def _vmapped(path: str) -> str:
    """The op-name form of ``path`` under ``jax.vmap``: the top-level
    scope reads ``vmap(<scope>)``."""
    head, _, rest = path.partition("/")
    return f"vmap({head})/{rest}"


@pytest.mark.parametrize("entry", range(4),
                         ids=["apply", "apply_batched", "refresh",
                              "apply_plan"])
def test_compiled_op_names_carry_the_phase_and_kernel_scopes(entry):
    solver = FmmSolver(CFG, "pallas")
    program, fn, args, paths = _entries(solver)[entry]
    hlo = fn.lower(*args).compile().as_text()
    assert re.search(rf"^HloModule jit_{program}\b", hlo, re.M)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    batched = fn is solver._apply_batched
    for path in paths:
        want = f"jit({program})/{_vmapped(path) if batched else path}"
        assert any(n.startswith(want) for n in names), want


def test_reference_evaluation_scopes():
    solver = FmmSolver(CFG, "reference")
    names = set(re.findall(r'op_name="([^"]*)"', solver._apply.lower(
        *_inputs()).compile().as_text()))
    for path in ["downward/m2l/", "downward/l2l/", "downward/p2l/",
                 "evaluation/l2p/", "evaluation/m2p/", "evaluation/p2p/",
                 "evaluation/unsort/"]:
        assert any(n.startswith("jit(core)/" + path) for n in names), path


@pytest.mark.parametrize("entry", [0, 1], ids=["apply", "apply_batched"])
def test_tpu_lowering_names_each_kernel_before_pallas_call(entry,
                                                          monkeypatch):
    """Lowered for the chip (Mosaic, not interpret mode), every kernel
    stays a ``pallas_call`` whose op name ends ``<kernel>/pallas_call``,
    and the module keeps the program's name. Lowered with 32-bit
    defaults, as on the chip."""
    monkeypatch.setattr(common, "default_interpret", lambda: False)
    with jax.enable_x64(False):
        solver = FmmSolver(CFG, "pallas")
        program, fn, args, _ = _entries(solver)[entry]
        text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
    assert f"module @jit_{program}" in text
    calls = set(re.findall(r'loc\("([^"]*/pallas_call)"', text))
    assert calls == {f"{k}/pallas_call" for k in KERNELS}


#: The logarithmic kernel's launches, named apart from the harmonic ones.
LOG_KERNELS = ["eval_fused_log", "m2l_fused", "p2l_log", "leaf_classify"]
LOG_PATHS = ["downward/m2l/log_planes/", "downward/p2l/p2l_log/",
             "evaluation/eval_fused_log/"]


def test_log_kernel_names_its_launches_and_log_planes(monkeypatch):
    """Under a log configuration, lowered for the chip, the log variants
    of the fused evaluation and the P2L are ``eval_fused_log`` and
    ``p2l_log`` (scope and ``pallas_call`` alike), the XLA complex log of
    the M2L planes sits under ``log_planes``, and the harmonic
    configuration's names are unchanged."""
    import dataclasses
    monkeypatch.setattr(common, "default_interpret", lambda: False)
    names = {}
    with jax.enable_x64(False):
        for kernel in ("harmonic", "log"):
            cfg = dataclasses.replace(CFG, kernel=kernel)
            program, fn, args, _ = _entries(FmmSolver(cfg, "pallas"))[0]
            text = fn.trace(*args).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
            names[kernel] = set(re.findall(r'loc\("([^"]*)"', text))
    for kernel, launches in (("harmonic", KERNELS), ("log", LOG_KERNELS)):
        calls = {n for n in names[kernel] if n.endswith("/pallas_call")}
        assert calls == {f"{k}/pallas_call" for k in launches}, kernel
    for path in LOG_PATHS:
        assert any(n.startswith("jit(core)/" + path) for n in names["log"])
    assert not any("log_planes" in n or "_log/" in n
                   for n in names["harmonic"] if n.startswith("jit("))


# ---------------------------------------------------------------------------
# grid-step counter
# ---------------------------------------------------------------------------

def _brute_force(lists_seq, dummy, TB, SW):
    """Walk the staged grid step by step: (steps, empty) per region."""
    staged, _, region_steps = common.staged_lists(lists_seq, dummy, TB, SW)
    staged = np.asarray(staged)
    B, rows, _ = staged.shape
    out, start = [], 0
    for k in region_steps:
        steps = empty = 0
        for b in range(B):
            for i in range(rows // TB):
                for s in range(start, start + k):
                    block = staged[b, i * TB:(i + 1) * TB,
                                   s * SW:(s + 1) * SW]
                    steps += 1
                    empty += bool((block == dummy).all())
        out.append((steps, empty))
        start += k
    return out


def _lists(rng, B, nbox, S, fill):
    """(B, nbox, S) lists: row r holds up to ``fill`` valid entries at the
    front, the rest -1, and a few rows are empty."""
    counts = rng.integers(0, fill + 1, size=(B, nbox))
    counts[:, ::5] = 0
    ids = rng.integers(0, nbox, size=(B, nbox, S))
    return jnp.asarray(np.where(np.arange(S) < counts[..., None], ids, -1),
                       jnp.int32)


@pytest.mark.parametrize("TB", [8, 16])
@pytest.mark.parametrize("SW", [1, 2, 4])
@pytest.mark.parametrize("case", ["one", "two_regions", "chunked", "batched"])
def test_grid_step_counter_matches_brute_force(TB, SW, case, monkeypatch):
    rng = np.random.default_rng(TB * 10 + SW)
    B = 3 if case == "batched" else 1
    nbox = 37
    lists_seq = [_lists(rng, B, nbox, 13, 6)]
    if case == "two_regions":
        lists_seq.append(_lists(rng, B, nbox, 7, 2))
    if case == "chunked":
        # a list budget of two tiles: several chunks, the last one padded
        monkeypatch.setattr(common, "SMEM_LIST_BYTES", 2 * 4 * TB * 128)
        assert common.staged_lists(lists_seq, nbox, TB, SW)[1] > 1
    got = [(n, int(e)) for n, e in
           common.staged_grid_steps(lists_seq, nbox, TB, SW)]
    want = _brute_force(lists_seq, nbox, TB, SW)
    assert got == want
    assert all(0 < e < n for n, e in got)


def test_stats_reports_grid_steps_of_the_staged_kernels():
    z, q = _inputs()
    solver = FmmSolver.build(CFG, "pallas")
    stats = solver.stats(z, q)
    assert stats["overflow"] == 0 and set(stats["margins"])
    grid = stats["grid_steps"]
    assert set(grid) == {"m2l", "eval_p2p", "eval_m2p", "p2l"}
    conn = solver.plan(z, q).conn
    TB, SW = CFG.tile_boxes, CFG.stage_width
    p2p, m2p = _brute_force([conn.p2p[None], conn.m2p[None]],
                            conn.p2p.shape[0], TB, SW)
    assert (grid["eval_p2p"]["steps"], grid["eval_p2p"]["empty"]) == p2p
    assert (grid["eval_m2p"]["steps"], grid["eval_m2p"]["empty"]) == m2p
    assert (grid["p2l"]["steps"], grid["p2l"]["empty"]) == _brute_force(
        [conn.p2l[None]], conn.p2l.shape[0], TB, SW)[0]
    weak = jnp.concatenate([conn.weak[l] for l in range(1, CFG.nlevels + 1)])
    assert (grid["m2l"]["steps"], grid["m2l"]["empty"]) == _brute_force(
        [weak[None]], weak.shape[0], TB, SW)[0]
    for c in grid.values():
        assert 0 <= c["empty"] <= c["steps"]
    # the reference backend launches no kernel grid
    assert FmmSolver.build(CFG, "reference").stats(z, q)["grid_steps"] == {}
