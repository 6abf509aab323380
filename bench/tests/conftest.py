"""Tiny cells for the benchmark's CPU tests: the cells' own traffic mixes
and metric definitions at N = 1024 on the Pallas backend, which runs in
interpret mode off a TPU.

Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests`` from the root
of the repository (the repository's own ``testpaths`` is ``tests/``).
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402

#: The committed cell of each kind.
CELLS = {"apply": "n1m-uniform-apply", "apply_batched": "b64-n3584-apply",
         "rk2_guarded": "n64k-vortex-step"}


def tiny_cell(kind: str) -> harness.Cell:
    """The committed cell of ``kind`` at N = 1024 (batch 4): its traffic
    mix, its limits and every metric the committed benchmark defines."""
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.Cell.load(ROOT, CELLS[kind])
    traffic = cell.traffic
    if kind == "apply_batched":
        traffic["batch"] = 4
    config = {"backend": "pallas", "fmm": {
        "n": 1024, "nlevels": 2, "p": 17, "theta": 0.5, "kernel": "harmonic",
        "dtype": "f32", "strong_cap": 48, "weak_cap": 128, "tile_boxes": 8,
        "stage_width": 1}}
    return harness.Cell(name=f"tiny-{kind}", chips=1, config=config,
                        traffic=traffic, limits=cell.limits,
                        end_to_end=spec["end_to_end"],
                        per_layer=spec["per_layer"])


@pytest.fixture
def cpu_chips(monkeypatch):
    """Skip the harness's look for a TPU: the CPU device stands in."""
    import jax

    monkeypatch.setattr(harness, "chips",
                        lambda count: jax.devices()[:count])


def last_json_line(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
