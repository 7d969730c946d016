"""The Mamba-2 decode step over the live rows of a slot cache, in place.

One token a row: `S = exp(dt A) S + (dt x) (x) B`, `y = S C` (the single-
token branch of `models/hybrid.py:_ssd`, the same operations in the same
order, float32). The states are the layer scan's carried stack
``[L, B, H, P, N]``; the kernel takes it aliased in to out and visits only
the live rows' blocks of layer ``i``: each is read once, updated, read out
while it is still in the chip's fast memory and written back where it lies.
A dead row's state is neither read nor written.

The grid is ``(B, H / hb)``: step ``(j, h)`` is head block ``h`` of the
``j``-th live slot. The live slots ride in as a scalar-prefetch operand
(``ids``: the live slots first, then the dead ones) with their count; for
``j`` at or past the count the index maps hand back the block of the last
live step, so the pipeline fetches and writes back nothing new, and the
body computes nothing. With no live row at all one block is fetched and
written back unchanged. ``y`` is an output of its own, zero on a dead row.

Layouts: a head's state is ``[P, N]`` (P on sublanes, N on lanes), so the
per-head vectors along P (``x``, ``y``) travel as ``[P, hb]`` columns, and
those along heads (``dt``, ``a``) as ``[1, hb]`` rows; every block's last two
dimensions are its array's own, which is what the chip's compiler tiles.
A block's heads are visited by a loop of trips, `_HEADS_A_TRIP` heads a trip
unrolled: the body is traced at every process start, and 64 heads unrolled
(granite's block) took 2.2 s of a pool's set-up on a TPU v5e host, where a
trip of 8 keeps the unrolled pace to 5% (granite) and 1% (Falcon-H1). A
trip's columns are copied side by side into scratch, the trip index
leading, because the chip indexes a lane only at a static offset.
``interpret`` runs the same kernel on the CPU: True (the pool off the chip)
or `pltpu.InterpretParams()`, which keeps the chip's pipeline and fills
unwritten buffers with NaN (the kernel's tests)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of state one grid step holds; the pipeline keeps two buffers of the
# block in and two out. Falcon-H1 [32, 128, 256] takes 16 heads a block,
# granite-4.0-h-small [128, 64, 128] 64
_BLOCK_BYTES = 2 << 20
# heads a trip of the loop over a block's heads (the most that divide it)
_HEADS_A_TRIP = 8


def head_block(heads: int, per_group: int, head_bytes: int) -> int:
    """Heads a grid step: the most that divide a group's heads (a block
    reads one group's B and C) within `_BLOCK_BYTES`, at least one."""
    if heads % per_group:
        raise ValueError(f"{heads} heads are no whole number of groups of "
                         f"{per_group}")
    return max(k for k in range(1, per_group + 1)
               if per_group % k == 0
               and (k == 1 or k * head_bytes <= _BLOCK_BYTES))


def _kernel(ids_ref, meta_ref, a_ref, dt_ref, x_ref, b_ref, c_ref, s_ref,
            s_out, y_out, dx_s, decay_s, y_s, *, u: int):
    j = pl.program_id(0)
    n = meta_ref[0]
    trips = dx_s.shape[0]

    @pl.when(j < n)
    def _step():
        dt = dt_ref[0, 0]                                        # [1, hb]
        decay = jnp.exp(dt * a_ref[0])                           # [1, hb]
        dx = dt * x_ref[0, 0]                                    # [P, hb]
        b, c = b_ref[0, 0], c_ref[0, 0]                          # [1, N]
        for t in range(trips):
            dx_s[t] = lax.slice_in_dim(dx, t * u, (t + 1) * u, axis=1)
            decay_s[t] = lax.slice_in_dim(decay, t * u, (t + 1) * u, axis=1)

        def trip(t, carry):
            dxt, decayt = dx_s[t], decay_s[t]                 # [P, u], [1, u]
            # `lax` primitives (which broadcast a size-1 axis as the
            # operators do) trace faster than `jnp` operators, to the same
            # kernel
            for k in range(u):
                state = lax.add(
                    lax.mul(s_ref[0, 0, t * u + k],
                            lax.slice_in_dim(decayt, k, k + 1, axis=1)),
                    lax.mul(lax.slice_in_dim(dxt, k, k + 1, axis=1),
                            b))                                  # [P, N]
                s_out[0, 0, t * u + k] = state
                y_s[t, :, k:k + 1] = lax.expand_dims(
                    lax.reduce(lax.mul(state, c), 0.0, lax.add, (1,)), (1,))
            return carry

        lax.fori_loop(0, trips, trip, 0)
        for t in range(trips):
            y_out[0, 0, :, t * u:(t + 1) * u] = y_s[t]

    @pl.when(j >= n)
    def _dead():
        y_out[...] = jnp.zeros_like(y_out)

    @pl.when(n == 0)
    def _none_live():
        # every step maps to one block: hand it back as it came
        s_out[...] = s_ref[...]


def ssd_decode(states, layer, live, dt, x, bm, cm, a, *, interpret=False):
    """One decode step of the selective scan for the live rows of layer
    ``layer`` of the stacked ``states`` [L, B, H, P, N] float32, updated
    where they lie.

    ``live`` bool [B]; ``dt`` [B, H] (the step sizes after softplus),
    ``x`` [B, H, P], ``bm``/``cm`` [B, G, N], ``a`` [H] (negative): float32.
    The heads of group g are ``g * H/G ..``. Returns ``(states, y)``, ``y``
    [B, H, P] float32, zero on a dead row."""
    nl, rows, heads, p, n = states.shape
    groups = bm.shape[1]
    if states.dtype != jnp.float32:
        raise ValueError(f"states must be float32, not {states.dtype}")
    if (dt.shape != (rows, heads) or x.shape != (rows, heads, p)
            or bm.shape != (rows, groups, n) or cm.shape != bm.shape
            or a.shape != (heads,)):
        raise ValueError(
            f"inputs do not tile states {states.shape}: dt {dt.shape}, "
            f"x {x.shape}, B {bm.shape}, C {cm.shape}, a {a.shape}")
    if not groups or heads % groups:
        raise ValueError(f"{heads} heads do not split into {groups} groups")
    per_group = heads // groups
    hb = head_block(heads, per_group, p * n * 4)
    nh, blocks_a_group = heads // hb, per_group // hb
    f32 = jnp.float32

    def cols(v):                                       # [B, nh, *, hb]
        return jnp.swapaxes(v.astype(f32).reshape(rows, nh, hb, -1), 2, 3)

    dt4 = cols(dt[..., None])                               # [B, nh, 1, hb]
    x4 = cols(x)                                            # [B, nh, P, hb]
    a3 = a.astype(f32).reshape(nh, 1, hb)
    b4, c4 = (v.astype(f32)[:, :, None] for v in (bm, cm))  # [B, G, 1, N]
    ids = jnp.argsort(~live, stable=True).astype(jnp.int32)
    meta = jnp.stack([jnp.sum(live, dtype=jnp.int32),
                      jnp.asarray(layer, jnp.int32)])

    def pick(j, h, ids_ref, meta_ref):
        # a dead step stays on the last live step's block
        count = meta_ref[0]
        on = j < count
        slot = ids_ref[jnp.where(on, j, jnp.maximum(count - 1, 0))]
        return slot, jnp.where(on, h, nh - 1)

    def state_map(j, h, ids_ref, meta_ref):
        slot, hh = pick(j, h, ids_ref, meta_ref)
        return meta_ref[1], slot, hh, 0, 0

    def row_map(j, h, ids_ref, meta_ref):
        slot, hh = pick(j, h, ids_ref, meta_ref)
        return slot, hh, 0, 0

    def group_map(j, h, ids_ref, meta_ref):
        slot, hh = pick(j, h, ids_ref, meta_ref)
        return slot, hh // blocks_a_group, 0, 0

    def a_map(j, h, ids_ref, meta_ref):
        return pick(j, h, ids_ref, meta_ref)[1], 0, 0

    def y_map(j, h, ids_ref, meta_ref):
        # every slot's own block: a dead one is written zeros
        return ids_ref[j], h, 0, 0

    u = max(k for k in range(1, _HEADS_A_TRIP + 1) if hb % k == 0)
    block = hb * p * n * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows, nh),
        # a trip's columns of dt x and decay, and of y
        scratch_shapes=[pltpu.VMEM((hb // u, p, u), f32),
                        pltpu.VMEM((hb // u, 1, u), f32),
                        pltpu.VMEM((hb // u, p, u), f32)],
        in_specs=[pl.BlockSpec((1, 1, hb), a_map),
                  pl.BlockSpec((1, 1, 1, hb), row_map),
                  pl.BlockSpec((1, 1, p, hb), row_map),
                  pl.BlockSpec((1, 1, 1, n), group_map),
                  pl.BlockSpec((1, 1, 1, n), group_map),
                  pl.BlockSpec((1, 1, hb, p, n), state_map)],
        out_specs=[pl.BlockSpec((1, 1, hb, p, n), state_map),
                   pl.BlockSpec((1, 1, p, hb), y_map)])
    states, y4 = pl.pallas_call(
        functools.partial(_kernel, u=u),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(states.shape, f32),
                   jax.ShapeDtypeStruct((rows, nh, p, hb), f32)],
        # operand 7, counting the two scalar-prefetch ones: the states
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            # a dead step must follow the live ones: sequential
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * block + (8 << 20)),
        name="ssd_decode",
        interpret=interpret,
    )(ids, meta, a3, dt4, x4, b4, c4, states)
    return states, jnp.swapaxes(y4, 2, 3).reshape(rows, heads, p)
