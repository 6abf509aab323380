"""Compile the main path's Pallas kernels for a TPU v5e, without a chip.

The TPU compiler is installed with JAX; it compiles for a described,
unattached chip. Interpret mode (how every other kernel test runs)
accepts block shapes, 64-bit index maps and SMEM footprints that Mosaic
refuses; this file catches those at the paper's scale (N = 2**20, p = 17,
f32) for one problem and for a batch, at the smallest tiling and the
largest tile and stage width the autotuner sweeps. Nothing here runs a kernel or gives a time.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every test worker
imports every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.fmm2d import fmm_config
from repro.core.config import max_leaf_size
from repro.kernels import (eval_fused_pallas_batched, m2l_pallas_batched,
                           p2l_pallas_batched)
from repro.kernels.common import round_up
from repro.kernels.topology.classify import leaf_classify_pallas
from repro.solver.autotune import MAX_STAGED_ROWS, TILE_CANDIDATES

CFG = fmm_config(1 << 20)                     # nlevels 7: 16384 leaves
NBOX = CFG.nboxes
NBOX_M2L = sum(4**l for l in range(1, CFG.nlevels + 1))   # fused levels
N_PAD = round_up(max_leaf_size(CFG), 128)
P = round_up(CFG.p + 1, 128)
S, W = CFG.strong_cap, CFG.weak_cap
# the smallest tiling, and the largest tile and stage width the tuner
# can pick (tile_boxes * stage_width <= MAX_STAGED_ROWS)
TILINGS = [(min(TILE_CANDIDATES), 1),
           (max(TILE_CANDIDATES), MAX_STAGED_ROWS // max(TILE_CANDIDATES)),
           (min(TILE_CANDIDATES), MAX_STAGED_ROWS // min(TILE_CANDIDATES))]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, shapes, one_chip):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    print(compiled.memory_analysis())
    return compiled


def _eval_fused(b, tb, sw, s=S, kernel="harmonic"):
    f32, i32 = jnp.float32, jnp.int32
    tgt, src = (b, NBOX, N_PAD), (b, NBOX + 1, N_PAD)
    shapes = ([((b, NBOX, s), i32)] * 2                    # p2p, m2p lists
              + [(tgt, f32)] * 2 + [(tgt, i32)] + [(tgt, f32)] * 2
              + [((b, NBOX, P), f32)] * 2                  # local coeffs
              + [(src, f32)] * 4 + [(src, i32)]            # source planes
              + [((b, NBOX + 1, P), f32)] * 2              # multipoles
              + [((b, NBOX, s), f32)] * 3)                 # m2p slot planes
    fn = functools.partial(eval_fused_pallas_batched, p=CFG.p, kernel=kernel,
                           tile_boxes=tb, stage_width=sw, interpret=False)
    return fn, shapes


def _m2l_fused(b, tb, sw, w=W, kernel="harmonic"):
    f32 = jnp.float32
    shapes = ([((b, NBOX_M2L, w), jnp.int32)]
              + [((b, NBOX_M2L + 1, P), f32)] * 2
              + [((b, NBOX_M2L, w), f32)] * 4 + [((P, P), f32)])
    fn = functools.partial(m2l_pallas_batched, p=CFG.p, kernel=kernel,
                           tile_boxes=tb, stage_width=sw, interpret=False)
    if kernel == "harmonic":
        return fn, shapes

    def with_log_planes(*args):
        *rest, logr, logi = args
        return fn(*rest, logr=logr, logi=logi)

    return with_log_planes, shapes + [((b, NBOX_M2L, w), f32)] * 2


def _p2l(b, tb, sw, s=S, kernel="harmonic"):
    f32 = jnp.float32
    shapes = ([((b, NBOX, s), jnp.int32)] + [((b, NBOX), f32)] * 3
              + [((b, NBOX + 1, N_PAD), f32)] * 4)
    fn = functools.partial(p2l_pallas_batched, p=CFG.p, P=P, kernel=kernel,
                           tile_boxes=tb, stage_width=sw, interpret=False)
    return fn, shapes


def _leaf_classify(b, tb, sw):
    import dataclasses
    cfg = dataclasses.replace(CFG, tile_boxes=tb, stage_width=sw)

    def one(cand, valid, cr, ci, radii):
        return leaf_classify_pallas(cand, valid, jax.lax.complex(cr, ci),
                                    radii, cfg, interpret=False)

    shapes = ([((b, NBOX, 4 * S), jnp.int32), ((b, NBOX, 4 * S), jnp.bool_)]
              + [((b, NBOX), jnp.float32)] * 3)
    return jax.vmap(one), shapes


KERNELS = {"eval_fused": _eval_fused, "m2l_fused": _m2l_fused,
           "p2l": _p2l, "leaf_classify": _leaf_classify}

#: The logarithmic kernel at the benchmark's clustered configuration
#: (``bench/configs/fmm2d-1m-log.json``): its caps and its tiling.
LOG_STRONG_CAP, LOG_WEAK_CAP = 512, 2024
LOG_TILING = (8, 4)
LOG_KERNELS = {
    "eval_fused": functools.partial(_eval_fused, s=LOG_STRONG_CAP,
                                    kernel="log"),
    "m2l_fused": functools.partial(_m2l_fused, w=LOG_WEAK_CAP, kernel="log"),
    "p2l": functools.partial(_p2l, s=LOG_STRONG_CAP, kernel="log"),
}


@pytest.mark.parametrize("tiling", TILINGS, ids=lambda t: "tb%d-sw%d" % t)
@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e_at_paper_scale(kernel, batch, tiling,
                                                one_chip):
    fn, shapes = KERNELS[kernel](batch, *tiling)
    compiled = _compile(fn, shapes, one_chip)
    mem = compiled.memory_analysis()
    # the whole working set of one launch fits the chip's 16 GB HBM
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9 * 0.9, total


def test_described_chip_is_a_v5e(one_chip):
    dev = next(iter(one_chip.device_set))
    assert dev.platform == "tpu"
    assert "v5" in dev.device_kind.lower(), dev.device_kind


@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("kernel", sorted(LOG_KERNELS))
def test_log_kernel_compiles_for_v5e_at_paper_scale(kernel, batch, one_chip):
    """The logarithmic variants, whose ``atan2`` Mosaic lowers only as the
    kernels' own polynomial (``core.mathf.atan2``), at the clustered
    configuration's caps and tiling."""
    fn, shapes = LOG_KERNELS[kernel](batch, *LOG_TILING)
    compiled = _compile(fn, shapes, one_chip)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9 * 0.9, total
