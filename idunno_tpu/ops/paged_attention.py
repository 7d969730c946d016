"""Block-native paged decode attention (ISSUE 7 tentpole).

Queries attend over K/V blocks addressed *through the block table* —
no contiguous prefix is ever materialized (vLLM's PagedAttention,
PAPERS.md). Two interchangeable backends behind the same signature:

- ``kernel="pallas"``: a Pallas kernel whose grid walks the request's
  block chain; the block table rides in as a *scalar-prefetch* operand
  (`pltpu.PrefetchScalarGridSpec`) so the K/V BlockSpec index_map can
  address physical block ``tables[b, j]`` directly — the DMA engine
  does the "gather", one whole page (every kv-head, contiguous in the
  pool) at a time, overlapped with compute.
  Online softmax (running max/denominator) is structurally the same as
  `ops/flash_attention.py:_flash_kernel`, including the (rows, 128)
  broadcast-scratch trick for m/l and the `_out_struct` vma convention.
  ``interpret=True`` runs the same kernel on CPU for tier-1 tests.
- ``kernel="xla"``: stock-XLA fallback (gather + masked softmax) —
  the earn-it-or-swap baseline.

Both backends are int8-native (ISSUE 16): quantized pools hand their
per-token `k_scale`/`v_scale` leaves (`engine/kv_blocks.py:KV_LEAF_KEYS`,
``[N, bs, KVH]`` f32) through the same signature, and each backend
dequantizes its own tiles — the pallas kernel multiplies the scale
column into the block tile right after the int8→f32 cast, so no
dequantized copy of the pool ever materializes in HBM.

Both return *normalized* per-(query, kv-head, group) outputs plus the
log-sum-exp of their softmax, so the caller can merge with the
slot-local attention via `merge_attention` — exact because the merged
result is (o_a·Z_a + o_b·Z_b)/(Z_a+Z_b) with Z=exp(lse). A row with an
empty chain yields lse≈-1e30, whose merge weight underflows to exactly
0.0 in f32: zero-hit rows reproduce the dense result bit-for-bit.

Selection rule (CLAUDE.md conventions): ``resolve_paged_kernel`` maps
"auto" to the measured winner. Until the decode-shaped FLASH_SWEEP
section is captured on the real chip, "auto" stays on "xla"
(earn-it-or-swap: the kernel must beat the gather+flash baseline in
`FLASH_SWEEP.json` before it becomes the default).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from idunno_tpu.ops.flash_attention import _NEG_INF, _out_struct

# "auto" resolves here until an on-chip measurement blesses the kernel
# (it compiles and agrees with this path on the chip since PR 22; its
# speed against the gathered path is not measured).
AUTO_KERNEL = "xla"


def resolve_paged_kernel(kind: str, *, int8: bool = False) -> str:
    """Earn-it-or-swap selection: "auto" → measured winner ("xla" until
    the decode sweep says otherwise). Since ISSUE 16 the pallas kernel
    dequantizes int8 pages in-kernel, so ``int8`` no longer forces the
    xla path or refuses "pallas" — the kwarg stays for callers that
    still pass it, and "auto" resolves identically either way."""
    if kind not in ("auto", "pallas", "xla"):
        raise ValueError(f"paged_kernel must be auto|pallas|xla, got {kind!r}")
    del int8  # both backends are int8-native now
    if kind == "auto":
        return AUTO_KERNEL
    return kind


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedContext:
    """Everything the decode step needs to attend over paged KV.

    Traced children: per-layer (or stacked ``[L, ...]``) page stores,
    the per-row block table ``tables [S, C]`` (int32, dead entries 0)
    and block-aligned paged lengths ``lengths [S]``. Static aux:
    ``start`` (absolute cache position where the paged region begins —
    the static-prefix length), ``kernel`` and ``interpret``.
    """

    k_pages: Any
    v_pages: Any
    tables: Any
    lengths: Any
    k_scale_pages: Any = None
    v_scale_pages: Any = None
    start: int = 0
    kernel: str = "xla"
    interpret: bool = False

    def tree_flatten(self):
        children = (self.k_pages, self.v_pages, self.tables, self.lengths,
                    self.k_scale_pages, self.v_scale_pages)
        aux = (self.start, self.kernel, self.interpret)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        kp, vp, tables, lengths, ks, vs = children
        start, kernel, interpret = aux
        return cls(k_pages=kp, v_pages=vp, tables=tables, lengths=lengths,
                   k_scale_pages=ks, v_scale_pages=vs, start=start,
                   kernel=kernel, interpret=interpret)

    def layer(self, kp, vp, ks=None, vs=None) -> "PagedContext":
        """Per-layer slice for the scanned decode body."""
        return dataclasses.replace(
            self, k_pages=kp, v_pages=vp,
            k_scale_pages=ks, v_scale_pages=vs)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

# query rows one program holds across all its kv-heads: bounds the q/out
# blocks and the acc/m/l scratch to a few MiB of VMEM at head dim 128
_MAX_ROWS = 1024


def _paged_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref,
                  *refs, scale: float, block_size: int,
                  quantized: bool):
    """Grid (B, R, C), C innermost sequential: one program per (batch
    row, query-row tile, chain position). The K/V BlockSpec index_map
    already resolved ``tables[b, j]`` and the block carries the page's
    WHOLE kv-head extent (Mosaic only takes a block whose last two dims
    are the array's own or (8, 128)-aligned, so a single head cannot be
    cut out of ``[bs, KVH, D]`` by the BlockSpec) — this body decides
    liveness and runs one online-softmax step per kv-head over the block.

    ``quantized=True`` threads two extra per-token scale tiles
    (``ks_ref``/``vs_ref``, one f32 scale per (token, kv-head)) into
    ``refs`` right before the outputs; dequant is the elementwise
    multiply into the int8→f32 cast below — the block never exists
    dequantized outside VMEM.

    No causal/position masking: the paged region wholly precedes the
    queries and ``lengths`` are block-aligned, so a live block is live
    in full. m/l live as (rows, 128) broadcast scratch per head
    (min-tile rule, same trick as `_flash_kernel`)."""
    if quantized:
        ks_ref, vs_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    kvh = q_ref.shape[1]
    b = pl.program_id(0)
    j = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * block_size < lengths_ref[b])
    def _step():
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)          # [rows, d]
            k = k_ref[0, :, h, :].astype(jnp.float32)    # [bs, d]
            v = v_ref[0, :, h, :].astype(jnp.float32)    # [bs, d]
            if quantized:
                k = k * ks_ref[0, :, h:h + 1].astype(jnp.float32)
                v = v * vs_ref[0, :, h:h + 1].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [rows, bs]
            m_prev = m_ref[h].max(axis=-1, keepdims=True)     # [rows, 1]
            l_prev = l_ref[h].max(axis=-1, keepdims=True)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == nc - 1)
    def _finalize():
        m = m_ref[...].max(axis=-1, keepdims=True)
        l = l_ref[...].max(axis=-1, keepdims=True)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (m + jnp.log(l_safe)).astype(lse_ref.dtype)


def _paged_pallas(q5, k_pages, v_pages, tables, lengths, *,
                  k_scale_pages=None, v_scale_pages=None,
                  scale: float, interpret: bool):
    """q5 [B,T,KVH,G,D] against pages [N,bs,KVH,D] via the block table.

    Rows = T*G query vectors per (batch, kv-head), padded to a multiple
    of 8 for the f32 min tile and cut into row tiles so one program
    holds at most `_MAX_ROWS` query rows across its kv-heads (decode is
    one tile; a long prefill suffix re-walks the chain once per tile
    instead of outgrowing VMEM). The table is flattened and handed to
    the grid as a scalar-prefetch operand so the K/V index_map can read
    it. Each program DMAs one whole page (``[bs, KVH, D]``, contiguous in
    the pool) and walks its kv-heads in the body. Quantized pools add
    two ``[N, bs, KVH]`` scale-page operands that ride the SAME
    index_map as their pages, so the dequant multiply happens in VMEM
    per block.
    """
    b, t, kvh, g, d = q5.shape
    n, bs, _, _ = k_pages.shape
    c = tables.shape[1]
    r = t * g
    tr = min(((r + 7) // 8) * 8, max(8, _MAX_ROWS // kvh // 8 * 8))
    rp = ((r + tr - 1) // tr) * tr
    qz = jnp.transpose(q5, (0, 2, 1, 3, 4)).reshape(b, kvh, r, d)
    if rp != r:
        qz = jnp.pad(qz, ((0, 0), (0, 0), (0, rp - r), (0, 0)))
    quantized = k_scale_pages is not None

    def row_map(bi, ri, ji, tbl, lens):
        return (bi, 0, ri, 0)

    page_spec = pl.BlockSpec((1, bs, kvh, d),
                             lambda bi, ri, ji, tbl, lens:
                             (tbl[bi * c + ji], 0, 0, 0))
    in_specs = [pl.BlockSpec((1, kvh, tr, d), row_map), page_spec,
                page_spec]
    operands = [qz, k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec((1, bs, kvh),
                                  lambda bi, ri, ji, tbl, lens:
                                  (tbl[bi * c + ji], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale_pages, v_scale_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, rp // tr, c),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, kvh, tr, d), row_map),
            pl.BlockSpec((1, kvh, tr, 1), row_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((kvh, tr, d), jnp.float32),
            pltpu.VMEM((kvh, tr, 128), jnp.float32),
            pltpu.VMEM((kvh, tr, 128), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, block_size=bs,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[
            _out_struct((b, kvh, rp, d), jnp.float32, q5),
            _out_struct((b, kvh, rp, 1), jnp.float32, q5),
        ],
        interpret=interpret,
    )(tables.reshape(-1), lengths, *operands)
    out = out[:, :, :r].reshape(b, kvh, t, g, d)
    lse = lse[:, :, :r, 0].reshape(b, kvh, t, g)
    return (jnp.transpose(out, (0, 2, 1, 3, 4)),
            jnp.transpose(lse, (0, 2, 1, 3)))


# ---------------------------------------------------------------------------
# Stock-XLA fallback (gather + masked softmax)
# ---------------------------------------------------------------------------

def _paged_xla(q5, k_pages, v_pages, tables, lengths, *,
               k_scale_pages=None, v_scale_pages=None, scale: float):
    b, t, kvh, g, d = q5.shape
    n, bs, _, _ = k_pages.shape
    c = tables.shape[1]
    k = k_pages[tables].astype(jnp.float32)   # [B,C,bs,KVH,D]
    v = v_pages[tables].astype(jnp.float32)
    if k_scale_pages is not None:
        k = k * k_scale_pages[tables].astype(jnp.float32)[..., None]
        v = v * v_scale_pages[tables].astype(jnp.float32)[..., None]
    k = jnp.transpose(k, (0, 3, 1, 2, 4)).reshape(b, kvh, c * bs, d)
    v = jnp.transpose(v, (0, 3, 1, 2, 4)).reshape(b, kvh, c * bs, d)
    q = jnp.transpose(q5, (0, 2, 1, 3, 4)).astype(jnp.float32)  # [B,KVH,T,G,D]
    s = jnp.einsum("bhtgd,bhsd->bhtgs", q, k) * scale
    live = (jnp.arange(c * bs)[None, :] < lengths[:, None])  # [B, C*bs]
    s = jnp.where(live[:, None, None, None, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhtgs,bhsd->bhtgd", p / l_safe, v)
    lse = (m + jnp.log(l_safe))[..., 0]
    # a fully-masked row degenerates to a uniform softmax over garbage;
    # the merge weight already underflows to 0 there, but pin the same
    # (zeros, _NEG_INF) contract the pallas kernel produces
    dead = lengths == 0
    o = jnp.where(dead[:, None, None, None, None], 0.0, o)
    lse = jnp.where(dead[:, None, None, None], _NEG_INF, lse)
    return (jnp.transpose(o, (0, 2, 1, 3, 4)),
            jnp.transpose(lse, (0, 2, 1, 3)))


# ---------------------------------------------------------------------------
# Public surface
# ---------------------------------------------------------------------------

def paged_attention_grouped(q5, k_pages, v_pages, tables, lengths, *,
                            k_scale_pages=None, v_scale_pages=None,
                            kernel: str = "xla", interpret: bool = False):
    """Grouped-query paged attention.

    q5 ``[B, T, KVH, G, D]`` (the transformer's head-grouping order:
    ``q.reshape(b, t, kv_heads, heads // kv_heads, d)``); pages
    ``[N, bs, KVH, D]``; tables ``[B, C]`` int32 (dead entries 0);
    lengths ``[B]`` int32 block-multiples. Returns normalized outputs
    ``[B, T, KVH, G, D]`` f32 and lse ``[B, T, KVH, G]`` f32 —
    lse≈-1e30 on empty chains (merge weight underflows to exactly 0).
    """
    d = q5.shape[-1]
    scale = 1.0 / (d ** 0.5)
    c = tables.shape[1]
    if c == 0:
        b, t, kvh, g, _ = q5.shape
        return (jnp.zeros((b, t, kvh, g, d), jnp.float32),
                jnp.full((b, t, kvh, g), _NEG_INF, jnp.float32))
    if kernel == "pallas":
        return _paged_pallas(q5, k_pages, v_pages, tables, lengths,
                             k_scale_pages=k_scale_pages,
                             v_scale_pages=v_scale_pages,
                             scale=scale, interpret=interpret)
    return _paged_xla(q5, k_pages, v_pages, tables, lengths,
                      k_scale_pages=k_scale_pages,
                      v_scale_pages=v_scale_pages, scale=scale)


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"))
def paged_attention(q, k_pages, v_pages, tables, lengths, *,
                    k_scale_pages=None, v_scale_pages=None,
                    kernel: str = "xla", interpret: bool = False):
    """Flat-head convenience wrapper: q ``[B, T, H, D]`` → out
    ``[B, T, H, D]`` f32 + lse ``[B, T, H]``. H must be a multiple of
    the page store's KVH (standard GQA grouping)."""
    b, t, h, d = q.shape
    kvh = k_pages.shape[2]
    if h % kvh:
        raise ValueError(f"heads {h} not a multiple of kv_heads {kvh}")
    q5 = q.reshape(b, t, kvh, h // kvh, d)
    o5, lse5 = paged_attention_grouped(
        q5, k_pages, v_pages, tables, lengths,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        kernel=kernel, interpret=interpret)
    return o5.reshape(b, t, h, d), lse5.reshape(b, t, h)


def merge_attention(o_a, lse_a, o_b, lse_b):
    """Merge two normalized attention partials over disjoint key sets.

    Exact: with Z=exp(lse) the softmax over the union is
    (o_a·Z_a + o_b·Z_b)/(Z_a+Z_b). lse inputs broadcast against o with
    a trailing feature axis. An lse of ≈-1e30 contributes weight
    exactly 0.0 (f32 underflow), so an empty partial is a no-op."""
    m = jnp.maximum(lse_a, lse_b)
    wa = jnp.exp(lse_a - m)[..., None]
    wb = jnp.exp(lse_b - m)[..., None]
    return (o_a * wa + o_b * wb) / (wa + wb)
