"""Pallas TPU kernel: fused uint8 → normalized bfloat16 preprocessing.

The preprocess step (`idunno_tpu.ops.preprocess.preprocess_batch`) is pure
HBM bandwidth: read uint8 pixels once, write normalized bf16 once. This
kernel performs the cast + scale + per-channel mean/std in a single VMEM
pass over a [rows, W*C] view of the cropped image batch, with the channel
index recovered as ``lane % 3`` via a 2-D broadcasted iota (TPU needs ≥2-D
iota). The XLA path (`preprocess_batch`) produces identical values; the
engine (``InferenceEngine._use_pallas``) selects this kernel on TPU (or when
``EngineConfig.preprocess == "pallas"``) and the XLA path elsewhere.

Run on CPU with ``interpret=True`` (tests); compiled on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from idunno_tpu.ops.preprocess import IMAGENET_MEAN, IMAGENET_STD, center_crop

_ROWS_PER_BLOCK = 256


def _norm_kernel(x_ref, mean_ref, inv_std_ref, o_ref):
    # Mosaic has no direct u8->f32 cast; hop through int32.
    x = x_ref[:].astype(jnp.int32).astype(jnp.float32) * (1.0 / 255.0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, dimension=1)
    c = lanes % 3
    mean = jnp.where(c == 0, mean_ref[0, 0],
                     jnp.where(c == 1, mean_ref[0, 1], mean_ref[0, 2]))
    inv_std = jnp.where(c == 0, inv_std_ref[0, 0],
                        jnp.where(c == 1, inv_std_ref[0, 1],
                                  inv_std_ref[0, 2]))
    o_ref[:] = ((x - mean) * inv_std).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("crop", "interpret"))
def preprocess_batch_pallas(images_u8: jnp.ndarray, *, crop: int = 224,
                            interpret: bool = False) -> jnp.ndarray:
    """uint8 NHWC (canonical 256²) → normalized bf16 [B, crop, crop, 3]."""
    x = center_crop(images_u8, crop)            # XLA slice, fused upstream
    b, h, w, ch = x.shape
    rows = b * h
    flat = x.reshape(rows, w * ch)
    mean = jnp.asarray([IMAGENET_MEAN], dtype=jnp.float32)          # [1, 3]
    inv_std = 1.0 / jnp.asarray([IMAGENET_STD], dtype=jnp.float32)  # [1, 3]

    # carry the input's varying mesh axes on the out aval so the kernel can
    # run inside shard_map with check_vma on
    out_shape = jax.ShapeDtypeStruct((rows, w * ch), jnp.bfloat16,
                                     vma=jax.typeof(flat).vma)

    block_rows = min(_ROWS_PER_BLOCK, rows)
    grid = (pl.cdiv(rows, block_rows),)
    out = pl.pallas_call(
        _norm_kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, w * ch), lambda i: (i, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, w * ch), lambda i: (i, 0)),
        interpret=interpret,
    )(flat, mean, inv_std)
    return out.reshape(b, h, w, ch)
