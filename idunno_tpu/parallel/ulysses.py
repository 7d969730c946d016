"""Ulysses-style sequence parallelism: all-to-all head/sequence re-sharding.

The second canonical long-context strategy next to ring attention
(`idunno_tpu.parallel.ring_attention`): instead of rotating K/V blocks
around the ring, one ``all_to_all`` over ICI re-shards Q/K/V from
sequence-sharded [B, T/p, H, D] to head-sharded [B, T, H/p, D]; each device
then runs ordinary full attention over the complete sequence for its head
group, and a second ``all_to_all`` restores sequence sharding. Communication
is two all-to-alls of activation size (independent of T²), and the attention
itself needs no online-softmax bookkeeping.

Trade-off vs ring attention: Ulysses needs ``num_heads`` divisible by the
axis size and materializes full-T attention per head group (memory
O(T²/heads-group) unless paired with a flash kernel); ring attention has no
head constraint and O((T/p)²) score blocks. Both are exposed through the
same ``attn_fn`` plug on `idunno_tpu.models.transformer.TransformerLM`.

The reference system has no sequence axis at all (image CNNs,
SURVEY.md §5 "long-context") — these modules are the TPU framework's
equivalent of its only scaling axis, query-range sharding
(`mp4_machinelearning.py:516-536`), applied to sequence length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from idunno_tpu.parallel.mesh import DATA_AXIS
from idunno_tpu.parallel.ring_attention import full_attention


def _ulysses_shard(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   axis_name: str, causal: bool, local_attn) -> jnp.ndarray:
    """Per-shard body. q/k/v: [B, T_local, H, D] → same shape."""
    # seq-sharded → head-sharded: split heads into p groups, gather sequence.
    def to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)   # [B, T, H/p, D]
    out = local_attn(qh, kh, vh, causal=causal)
    return to_seq(out)                                    # [B, T/p, H, D]


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      mesh: Mesh, *, seq_axis: str = DATA_AXIS,
                      causal: bool = False,
                      local_attn=full_attention) -> jnp.ndarray:
    """Attention with the sequence dim sharded over ``seq_axis``.

    q/k/v: [B, T, H, D] global, T divisible by the axis size, H divisible by
    the axis size. Returns [B, T, H, D] with the same sharding — a drop-in
    for ``ring_attention`` where the head count allows it.

    ``local_attn`` is the within-shard attention over the full sequence for
    the local head group — ``full_attention`` by default, or the Pallas
    `idunno_tpu.ops.flash_attention.flash_attention` to also avoid the
    O(T²) score materialization on-chip.
    """
    p = mesh.shape[seq_axis]
    if q.shape[2] % p:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the "
            f"{seq_axis!r} axis size ({p}); use ring_attention instead")
    spec = P(None, seq_axis, None, None)
    fn = functools.partial(_ulysses_shard, axis_name=seq_axis, causal=causal,
                           local_attn=local_attn)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)
