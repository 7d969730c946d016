"""Ring attention — sequence/context parallelism over the mesh.

The reference has no sequence dimension anywhere (image CNNs only,
SURVEY.md §5 "long-context"), but a complete TPU framework must scale the
sequence axis the way the reference scales its batch axis. This implements
blockwise ring attention (Liu et al.-style): Q/K/V are sharded along the
sequence across mesh devices; each device computes attention of its local
queries against one K/V block at a time while K/V blocks rotate around the
ring via ``ppermute`` over ICI, accumulating with an online (flash-style)
softmax. Peak memory per device is O(T/p · T/p) instead of O(T²), and the
K/V transfer overlaps compute around the ring.

Pure JAX: `shard_map` + `ppermute` + `fori_loop`, so XLA schedules the
collective/compute overlap — no hand-written RDMA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from idunno_tpu.parallel.mesh import DATA_AXIS


def _ring_attention_shard(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          *, axis_name: str, p: int, causal: bool,
                          scale: float) -> jnp.ndarray:
    """Per-shard body. q/k/v: [B, T_local, H, D]. ``p`` is the concrete
    ring size (= mesh.shape[axis_name])."""
    my = jax.lax.axis_index(axis_name)
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    perm = [(j, (j + 1) % p) for j in range(p)]

    q_pos = my * t_q + jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 0)

    def step(i, carry):
        o, m, l, k_blk, v_blk = carry
        # whose K/V block do we hold after i rotations? (blocks move +1 in
        # ring index per step, so we hold (my - i) mod p's block)
        src = (my - i) % p
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale
        if causal:
            k_pos = src * t_k + jax.lax.broadcasted_iota(
                jnp.int32, (t_q, t_k), 1)
            mask = q_pos >= k_pos
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # guard fully-masked rows: exp(-inf - -inf) -> use safe max
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - m_safe))
        probs = jnp.exp(scores - m_safe[..., None])
        l_new = l * alpha + probs.sum(axis=-1)
        o_new = (o * alpha[..., None]
                 + jnp.einsum("bhqk,bkhd->bhqd", probs, v_blk))
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return o_new, m_new, l_new, k_next, v_next

    o0 = jnp.zeros((b, h, t_q, d), jnp.float32)
    m0 = jnp.full((b, h, t_q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t_q), jnp.float32)
    # mark the replicated initial carry as device-varying so the loop
    # carry type matches its output (shard_map vma typing)
    o0, m0, l0 = (jax.lax.pcast(x, (axis_name,), to="varying")
                  for x in (o0, m0, l0))
    o, m, l, _, _ = jax.lax.fori_loop(
        0, p, step, (o0, m0, l0, k.astype(jnp.float32),
                     v.astype(jnp.float32)))
    l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows -> 0 out
    out = (o / l[..., None]).astype(q.dtype)
    return jnp.einsum("bhqd->bqhd", out)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh: Mesh, *, seq_axis: str = DATA_AXIS,
                   causal: bool = False) -> jnp.ndarray:
    """Multi-head attention with the sequence dim sharded over ``seq_axis``.

    q/k/v: [B, T, H, D] global shape, T divisible by the axis size.
    Returns [B, T, H, D] with the same sharding.
    """
    scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(None, seq_axis, None, None)
    fn = functools.partial(_ring_attention_shard, axis_name=seq_axis,
                           p=mesh.shape[seq_axis], causal=causal, scale=scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def full_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   *, causal: bool = False) -> jnp.ndarray:
    """Single-device reference implementation (for tests and small inputs)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        t_q, t_k = scores.shape[-2], scores.shape[-1]
        mask = (jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 0)
                >= jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 1))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
