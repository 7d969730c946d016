"""Pipeline parallelism: GPipe-style microbatch schedule over a stage axis.

The reference never splits a model — each VM holds a whole AlexNet/ResNet
(`alexnet_resnet.py:18-22`); its only decomposition is range sharding of the
query stream (`mp4_machinelearning.py:516-536`). For models that do not fit
one chip the TPU framework adds the missing axis: the layer stack is cut
into ``p`` stages, one per mesh shard along ``STAGE_AXIS``; microbatches
stream through the stages, activations hop stage→stage over ICI via
``ppermute``, and every device runs the same SPMD program (a
``shard_map``-wrapped ``fori_loop`` over the M + p - 1 schedule slots), so
XLA overlaps the hop with the next microbatch's compute.

The schedule is the classic GPipe fill/steady/drain: at slot ``t`` stage
``s`` processes microbatch ``t - s`` (when in range). Bubble fraction is
(p-1)/(M+p-1) — callers pick M >> p. The whole pipeline is differentiable
(plain JAX ops), so the same function serves inference and training.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


STAGE_AXIS = "stage"


def stack_stage_params(per_stage: list[Any]) -> Any:
    """Stack p structurally-identical per-stage param pytrees along a new
    leading stage dim (leaf [p, ...]) — the layout ``pipeline_apply`` shards
    over the stage axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)


def split_microbatches(x: jnp.ndarray, num: int) -> jnp.ndarray:
    """[N, ...] → [num, N/num, ...]."""
    if x.shape[0] % num:
        raise ValueError(f"batch {x.shape[0]} not divisible by {num}")
    return x.reshape(num, x.shape[0] // num, *x.shape[1:])


def pipeline_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, microbatches: jnp.ndarray,
                   mesh: Mesh, *, axis: str = STAGE_AXIS,
                   data_axis: str | None = None) -> jnp.ndarray:
    """Run microbatches through the p-stage pipeline.

    stage_fn: (one stage's params, activation [mb, ...]) → [mb, ...]
      (activation shape must be stage-invariant, e.g. transformer blocks).
    stage_params: pytree with leaves [p, ...] (see ``stack_stage_params``).
    microbatches: [M, mb, ...] — the global input, replicated.
    Returns [M, mb, ...] — equal to stage_{p-1}(...stage_0(x)), replicated.

    2-D composition: with ``data_axis`` set (a second mesh axis), the
    microbatch dim mb is sharded over it — each data shard runs the same
    GPipe schedule on its slice of every microbatch (PP × DP; stage params
    stay replicated across ``data_axis``, so XLA all-reduces their grads
    over it under AD, the standard DP contract)."""
    p = mesh.shape[axis]
    m = microbatches.shape[0]
    if data_axis is not None:
        dp = mesh.shape[data_axis]
        if microbatches.shape[1] % dp:
            raise ValueError(
                f"microbatch size {microbatches.shape[1]} not divisible by "
                f"data axis {data_axis!r} size {dp}")

    def body(params_sh, x):
        # params_sh leaves arrive [1, ...] (stage-sharded); drop the dim.
        params = jax.tree.map(lambda a: a[0], params_sh)
        s = jax.lax.axis_index(axis)
        perm = [(j, (j + 1) % p) for j in range(p)]
        state0, out0, xv = (
            jax.lax.pcast(a, (axis,), to="varying")
            for a in (jnp.zeros_like(x[0]), jnp.zeros_like(x), x))

        def slot(t, carry):
            state, outputs = carry
            feed = xv[jnp.clip(t, 0, m - 1)]
            inp = jnp.where(s == 0, feed, state)
            act = stage_fn(params, inp)
            state_next = jax.lax.ppermute(act, axis, perm)
            # the last stage's activation at slot t is microbatch t-(p-1)
            oidx = jnp.clip(t - (p - 1), 0, m - 1)
            write = jnp.logical_and(s == p - 1, t >= p - 1)
            outputs = jnp.where(write,
                                jax.lax.dynamic_update_index_in_dim(
                                    outputs, act, oidx, 0),
                                outputs)
            return state_next, outputs

        _, outputs = jax.lax.fori_loop(0, m + p - 1, slot, (state0, out0))
        # only stage p-1 holds real outputs; psum replicates them everywhere
        mask = jnp.where(s == p - 1, 1.0, 0.0).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, axis)

    mb_spec = P(None, data_axis) if data_axis else P()
    return shard_map(body, mesh=mesh,
                     in_specs=(jax.tree.map(lambda _: P(axis), stage_params),
                               mb_spec),
                     out_specs=mb_spec)(stage_params, microbatches)
