"""Expert parallelism: switch-style top-1 MoE dispatch via all_to_all.

The reference's notion of "experts" is its two independent model jobs
fair-sharing the worker pool (`mp4_machinelearning.py:501-539`); within one
model it has no conditional computation. This module adds the real thing
for the TPU framework's sequence models: tokens are routed to the top-1
expert, packed into fixed ``[E, C, d]`` capacity buffers (static shapes —
XLA-friendly; overflow tokens are dropped, the standard switch trade-off),
exchanged over ICI with one ``all_to_all`` so each mesh shard holds only its
``E/p`` experts' tokens, run through the local expert FFNs, and returned by
the mirror ``all_to_all``, with gate-weighted combine back into sequence
order.

Used by `idunno_tpu.models.moe.SwitchFFN`, which also provides the dense
(every-device-holds-every-expert) path for single-device runs and as the
ground truth the EP path is tested against.

The dropless layer of a chip that holds a share of a wider router is
`idunno_tpu.models.moe.routed_experts` (``experts_held`` = (first, count)):
the part of an expert-parallel layer that runs between its two exchanges,
one chip's. A serving stack calls it on one chip without the exchange
(`models/hybrid.py`); no mesh code calls it yet.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


EXPERT_AXIS = "expert"


def switch_dispatch(gate_idx: jnp.ndarray, gate_w: jnp.ndarray,
                    n_experts: int, capacity: int
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-1 dispatch/combine tensors for local tokens.

    gate_idx [n] int, gate_w [n] float → dispatch one-hot [n, E, C] and
    combine (= dispatch · gate weight) [n, E, C]. Tokens beyond an expert's
    capacity get all-zero rows (dropped).
    """
    onehot = jax.nn.one_hot(gate_idx, n_experts, dtype=jnp.float32)  # [n, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0                  # [n, E]
    in_cap = (pos >= 0) & (pos < capacity)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=jnp.float32)                       # [n,E,C]
    dispatch = pos_oh * in_cap[..., None].astype(jnp.float32)
    combine = dispatch * gate_w[:, None, None]
    return dispatch, combine


def expert_parallel_apply(expert_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                          stacked_params: Any, x: jnp.ndarray,
                          gate_idx: jnp.ndarray, gate_w: jnp.ndarray,
                          mesh: Mesh, *, axis: str = EXPERT_AXIS,
                          capacity: int) -> jnp.ndarray:
    """Run the MoE layer with experts sharded over ``axis``.

    x [N, d] and gates [N] are token-sharded over the same axis (N divisible
    by the axis size); stacked_params leaves are [E, ...] with E divisible by
    the axis size. Returns [N, d], token-sharded as the input.
    """
    p = mesh.shape[axis]
    n_experts = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_experts % p:
        raise ValueError(f"{n_experts} experts not divisible by "
                         f"{axis!r} axis size {p}")

    def body(params_sh, x_l, idx_l, w_l):
        # params_sh leaves: [E/p, ...] — this shard's experts.
        dispatch, combine = switch_dispatch(idx_l, w_l, n_experts, capacity)
        buf = jnp.einsum("nec,nd->ecd", dispatch, x_l)        # [E, C, d]
        # group tokens by owning shard: [E/p, p*C, d]
        buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                                 tiled=True)
        out = jax.vmap(expert_fn)(params_sh, buf)             # [E/p, p*C, d]
        out = jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                                 tiled=True)                  # [E, C, d]
        return jnp.einsum("ecd,nec->nd", out, combine)

    pspec = jax.tree.map(lambda _: P(axis), stacked_params)
    return shard_map(body, mesh=mesh,
                     in_specs=(pspec, P(axis), P(axis), P(axis)),
                     out_specs=P(axis))(stacked_params, x, gate_idx, gate_w)
