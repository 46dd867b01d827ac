"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

A stacked-layer model ([L, ...] params driven layer-by-layer) is cut into
S = mesh.shape["pod"] contiguous stages of L/S layers. The batch splits into
microbatches; each tick every stage applies its layers to its current
microbatch and ``ppermute``s the activation to the next stage, so after the
S-1-tick fill the pipeline runs all stages concurrently (bubble fraction
(S-1)/(n_microbatches + S - 1), the GPipe schedule). The batch dim inside a
microbatch additionally shards over ``data``.

This composes with the CDC layers: a stage's layer fn can itself run coded
GEMMs over the `model` axis of a (pod, data, model) mesh — erasure recovery
is intra-stage and never crosses the pipeline axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply"]


def _seq_apply(layer, params, x):
    def body(h, p):
        return layer(p, h), None

    y, _ = jax.lax.scan(body, x, params)
    return y


def pipeline_apply(layer, params, x, *, mesh, n_microbatches: int = 4,
                   axis: str = "pod"):
    """Run ``x`` through L stacked layers, pipelined over ``axis``.

    layer:  fn(layer_params, h) -> h for ONE layer (params without the L dim)
    params: pytree with leading [L, ...] on every leaf
    x:      [B, ...] activations; B % n_microbatches == 0
    Returns [B, ...], numerically the sequential layer-by-layer result.
    """
    L = jax.tree.leaves(params)[0].shape[0]
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return _seq_apply(layer, params, x)  # no pipeline axis: sequential
    S = mesh.shape[axis]
    if L % S:
        raise ValueError(f"n_layers {L} not divisible by {S} stages")
    B = x.shape[0]
    n_mb = n_microbatches
    if B % n_mb:
        raise ValueError(f"batch {B} not divisible by {n_mb} microbatches")
    mb = B // n_mb

    # stage-blocked params [S, L/S, ...] and microbatched input [n_mb, mb, .]
    p_blocked = jax.tree.map(
        lambda a: a.reshape((S, L // S) + a.shape[1:]), params)
    x_mb = x.reshape((n_mb, mb) + x.shape[1:])

    data_ax = "data" if "data" in mesh.axis_names \
        and mb % mesh.shape["data"] == 0 else None
    x_spec = P(*((None, data_ax) + (None,) * (x.ndim - 1)))
    p_spec = jax.tree.map(
        lambda a: P(*((axis,) + (None,) * (a.ndim - 1))), p_blocked)

    def stage_fn(p_stage, x_loc):
        # p_stage leaves: [1, L/S, ...] (this stage's block); x_loc:
        # [n_mb, mb_loc, ...] the full microbatch queue (stage 0 reads it)
        p_stage = jax.tree.map(lambda a: a[0], p_stage)
        stage = jax.lax.axis_index(axis)
        fwd = [(i, (i + 1) % S) for i in range(S)]
        out0 = jnp.zeros(x_loc.shape, x_loc.dtype)
        recv0 = jnp.zeros(x_loc.shape[1:], x_loc.dtype)

        def tick(carry, t):
            out, recv = carry
            inp = jnp.where(stage == 0,
                            x_loc[jnp.clip(t, 0, n_mb - 1)], recv)
            y = _seq_apply(layer, p_stage, inp)
            oidx = jnp.clip(t - (S - 1), 0, n_mb - 1)
            write = (stage == S - 1) & (t >= S - 1)
            out = out.at[oidx].set(jnp.where(write, y, out[oidx]))
            recv = jax.lax.ppermute(y, axis, fwd)
            return (out, recv), None

        (out, _), _ = jax.lax.scan(tick, (out0, recv0),
                                   jnp.arange(n_mb + S - 1))
        # results live on the last stage; zero elsewhere + psum = broadcast
        return jax.lax.psum(jnp.where(stage == S - 1, out, 0), axis)

    fn = jax.shard_map(stage_fn, mesh=mesh, in_specs=(p_spec, x_spec),
                       out_specs=x_spec, check_vma=False)
    y_mb = fn(p_blocked, x_mb)
    return y_mb.reshape((B,) + y_mb.shape[2:])
