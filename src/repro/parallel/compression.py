"""Gradient compression: int8 error-feedback all-reduce.

Intended placement (1000+ node design): *intra-pod* gradient reductions ride
GSPMD's native all-reduces over the fast ICI "data" axis; the *cross-pod*
reduction — the slow DCI hop — is wrapped in a ``shard_map`` over the "pod"
axis only (remaining axes stay auto-sharded), sending int8 + one f32 scale
per tensor (~4x byte reduction) with error feedback so the quantization
noise telescopes instead of accumulating (Seide et al. 2014; 1-bit Adam
lineage).

``ef_allreduce_tree`` is the pure building block; ``cross_pod_reduce``
stitches it into a pjit program via shard_map(auto=...).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


def _shard_map(f, mesh, in_specs, out_specs, manual_axes):
    """shard_map manual over ``manual_axes`` only; the remaining mesh
    axes stay auto-partitioned."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=frozenset(manual_axes), check_vma=False)


def quantize_int8(x):
    """Per-tensor symmetric int8. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def ef_allreduce(g, err, axis_name: str):
    """Error-feedback compressed psum of one tensor over ``axis_name``.

    The quantization scale is agreed up front (pmax of the local amax — one
    f32 scalar per tensor on the wire) so the int8 payloads of all members
    share one codebook and their integer sum dequantizes exactly.
    Returns (mean-reduced tensor f32, new local error).
    """
    y = g.astype(jnp.float32) + err
    amax = jax.lax.pmax(jnp.max(jnp.abs(y)), axis_name)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(y / scale), -127, 127).astype(jnp.int8)
    new_err = y - q.astype(jnp.float32) * scale
    n = jax.lax.psum(1, axis_name)
    summed = jax.lax.psum(q.astype(jnp.int32), axis_name).astype(jnp.float32)
    return summed * scale / n, new_err


def ef_allreduce_tree(grads, errors, axis_name: str):
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(errors)
    out_g, out_e = [], []
    for g, e in zip(flat_g, flat_e):
        rg, re = ef_allreduce(g, e, axis_name)
        out_g.append(rg.astype(g.dtype))
        out_e.append(re)
    return treedef.unflatten(out_g), treedef.unflatten(out_e)


def init_errors(grads):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def make_compressed_value_and_grad(loss_fn, mesh):
    """Cross-pod compressed data parallelism.

    Wraps ``loss_fn(params, batch) -> scalar`` so that the gradient is
    computed *per pod* (shard_map manual over "pod"; "data"/"model" stay
    auto-partitioned inside), then mean-reduced across pods through the
    int8 error-feedback collective instead of a full-precision all-reduce
    — a ~4x cut of the slowest (cross-pod DCI) gradient traffic.

    Error-feedback state is per-pod: leaves carry a leading ``npods`` axis
    sharded over "pod" (init with ``init_pod_errors``).
    """
    def vg(params, batch, errors):
        @functools.partial(
            _shard_map, mesh=mesh,
            in_specs=(PS(), PS("pod"), PS("pod")),
            out_specs=(PS(), PS(), PS("pod")),
            manual_axes=("pod",),
        )
        def inner(p, local_batch, err):
            loss, grads = jax.value_and_grad(loss_fn)(p, local_batch)
            err = jax.tree.map(lambda e: e[0], err)          # drop pod dim
            grads, err = ef_allreduce_tree(grads, err, "pod")
            err = jax.tree.map(lambda e: e[None], err)
            return jax.lax.pmean(loss, "pod"), grads, err

        return inner(params, batch, errors)

    return vg


def init_pod_errors(params, npods: int):
    return jax.tree.map(
        lambda p: jnp.zeros((npods,) + p.shape, jnp.float32), params)
