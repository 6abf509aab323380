"""Checkpointing, data pipeline, compression, straggler/failure
handling — the distributed-runtime substrate."""
import os
import subprocess
import sys
import tempfile

import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_fallback import given, settings, st

from repro.checkpoint import (CheckpointManager, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.data.synthetic import DataConfig, Prefetcher, lm_batch, particles
from repro.launch.runtime import FailureInjector, StragglerMonitor, train_loop
from repro.parallel import dequantize_int8, quantize_int8


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_retention():
    tree = {"a": {"w": jnp.arange(12.0).reshape(3, 4)},
            "step": jnp.int32(7)}
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=2)
        for s in (1, 2, 3):
            cm.save(s, tree)
        cm.wait()
        restored, step = cm.restore_latest()
        assert step == 3
        np.testing.assert_allclose(np.asarray(restored["a"]["w"]),
                                   np.arange(12.0).reshape(3, 4))
        kept = sorted(x for x in os.listdir(d) if x.startswith("step_"))
        assert kept == ["step_00000002", "step_00000003"]


def test_checkpoint_atomicity_no_partial_dirs():
    tree = {"w": jnp.zeros((128, 128))}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 5, tree)
        names = os.listdir(d)
        assert names == ["step_00000005"]
        assert latest_step(d) == 5
        # corrupt detection
        leaf = os.path.join(d, "step_00000005", "w.npy")
        with open(leaf, "wb") as f:
            f.write(b"xx")
        with pytest.raises(IOError):
            restore_checkpoint(d, 5)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_lm_batch_deterministic_and_learnable_structure():
    dc = DataConfig(vocab=512, batch=4, seq=32, seed=1)
    b1 = lm_batch(dc, 10)
    b2 = lm_batch(dc, 10)
    b3 = lm_batch(dc, 11)
    assert (np.asarray(b1["tokens"]) == np.asarray(b2["tokens"])).all()
    assert (np.asarray(b1["tokens"]) != np.asarray(b3["tokens"])).any()
    # labels are next-token shifted
    t = np.asarray(b1["tokens"])
    l = np.asarray(b1["labels"])
    assert (l[:, :-1] == t[:, 1:]).all()


@pytest.mark.parametrize("dist", ["uniform", "normal", "layer"])
def test_particles_in_unit_square(dist):
    z, q = particles(dist, 1000, 0)
    z = np.asarray(z)
    assert (z.real >= 0).all() and (z.real <= 1).all()
    assert (z.imag >= 0).all() and (z.imag <= 1).all()
    assert len(z) == 1000


def test_prefetcher_orders_batches():
    pf = Prefetcher(lambda s: s * s, start_step=3, depth=2)
    got = [pf.get() for _ in range(4)]
    pf.close()
    assert got == [(3, 9), (4, 16), (5, 25), (6, 36)]


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.floats(1e-6, 1e6))
def test_quantize_int8_error_bound(scale):
    x = jnp.asarray(np.random.default_rng(0).normal(size=64) * scale,
                    jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-9 * scale


def test_compressed_allreduce_multidevice_subprocess():
    """Real 8-device shard_map EF all-reduce (runs in a subprocess so the
    forced device count cannot leak into this test session)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.parallel import make_compressed_value_and_grad, init_pod_errors
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
from jax.sharding import NamedSharding, PartitionSpec as PS
# make_mesh axes are Explicit: computations on its arrays (the jit and
# the eager reference alike) run under the mesh as context mesh
with jax.set_mesh(mesh):
    w = jax.device_put(jnp.ones((8, 8)),
                       NamedSharding(mesh, PS(None, "model")))
    batch = jax.device_put(jnp.arange(16.0).reshape(8, 2),
                           NamedSharding(mesh, PS(("pod", "data"), None)))
    loss_fn = lambda p, b: jnp.mean((b @ p["w"][:2, :]) ** 2)
    vg = make_compressed_value_and_grad(loss_fn, mesh)
    errors = jax.device_put(init_pod_errors({"w": w}, 2),
                            {"w": NamedSharding(mesh, PS("pod"))})
    loss, grads, errors = jax.jit(vg)({"w": w}, batch, errors)
    ref_loss, ref_g = jax.value_and_grad(loss_fn)({"w": w}, batch)
rel = np.abs(np.asarray(grads["w"]) - np.asarray(ref_g["w"])).max() / \
    np.abs(np.asarray(ref_g["w"])).max()
assert rel < 0.02, rel
assert abs(float(loss) - float(ref_loss)) < 1e-5
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=300)
    assert "OK" in r.stdout, r.stderr[-2000:]


# ---------------------------------------------------------------------------
# straggler / failure handling
# ---------------------------------------------------------------------------

def test_straggler_monitor_flags_slow_steps():
    m = StragglerMonitor(threshold=2.0, warmup=0)
    for i in range(10):
        m.record(i, 0.1)
    assert m.record(10, 0.5) is True
    assert m.record(11, 0.1) is False
    assert m.slow_steps == [(10, 0.5)]


def test_train_loop_failure_and_resume():
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d)
        fi = FailureInjector(fail_at=(5,))
        step_fn = lambda s, b, i: (s + 1, {"loss": 1.0})
        with pytest.raises(RuntimeError):
            train_loop(step_fn, jnp.zeros(()), lambda s: None, start_step=0,
                       num_steps=10, ckpt_manager=cm, ckpt_every=2,
                       failure=fi, log_every=0)
        restored, step = cm.restore_latest()
        state, summary = train_loop(step_fn, restored, lambda s: None,
                                    start_step=step, num_steps=10,
                                    ckpt_manager=cm, ckpt_every=2,
                                    failure=fi, log_every=0)
        assert int(state) == 10
        assert summary["last_step"] == 9
