"""Transformer with pluggable attention — the long-context model family.

The reference serves only image CNNs (`alexnet_resnet.py`), but the
framework's job inventory must cover sequence models at TPU scale: this
module provides a causal/bidirectional transformer whose attention
implementation is injectable — ``full_attention`` on one device, or
``ring_attention`` with the sequence dimension sharded over the mesh
(`idunno_tpu.parallel.ring_attention`) for contexts that do not fit one
chip. Rotary position embeddings keep positions global and length-agnostic,
and they are applied on the (sequence-sharded) global view under jit, so
each shard rotates with its true global positions.
"""
from __future__ import annotations

from collections.abc import Callable
from functools import partial

import flax.linen as nn
import jax.numpy as jnp
import jax

from idunno_tpu.ops.paged_attention import (merge_attention,
                                            paged_attention_grouped)
from idunno_tpu.parallel.ring_attention import full_attention

AttnFn = Callable[..., jnp.ndarray]     # (q, k, v, *, causal) -> out
# (dim, dtype, param_dtype, name) -> flax module replacing the dense MLP
FfnFactory = Callable[..., nn.Module]


def make_attn_fn(kind: str = "auto", *, mesh=None, axis: str = "data",
                 **kw) -> AttnFn:
    """One knob for the attention kernel family:

      full    — reference XLA attention (single device)
      flash   — Pallas blockwise kernel, training-capable (custom VJP);
                pass ``interpret=True`` off-TPU
      ring    — blockwise ring attention, sequence sharded over ``mesh``
      ulysses — all-to-all head re-sharding over ``mesh``
      auto    — flash on TPU, full elsewhere

    ring/ulysses require ``mesh`` (the sequence axis is ``axis``)."""
    from functools import partial as _p

    if mesh is not None and kind not in ("ring", "ulysses"):
        # a mesh means sequence parallelism, which only ring/ulysses do —
        # silently dropping it would serve single-device attention
        raise ValueError(f"attn kind {kind!r} ignores mesh; "
                         "use kind='ring' or 'ulysses'")
    auto = kind == "auto"
    if auto:
        import jax as _jax
        kind = "flash" if _jax.devices()[0].platform == "tpu" else "full"
    if kind == "full":
        # auto may resolve here holding flash-only kwargs — drop them (the
        # graceful-degradation path); an EXPLICIT 'full' with kwargs is a
        # caller error and must not be silently ignored
        if kw and not auto:
            raise TypeError(f"full attention takes no kwargs, got {kw}")
        return full_attention
    if kind == "flash":
        from idunno_tpu.ops.flash_attention import flash_attention
        return _p(flash_attention, **kw) if kw else flash_attention
    if kind in ("ring", "ulysses"):
        if mesh is None:
            raise ValueError(f"attn kind {kind!r} needs a mesh")
        if kind == "ring":
            from idunno_tpu.parallel.ring_attention import ring_attention
            return _p(ring_attention, mesh=mesh, seq_axis=axis, **kw)
        from idunno_tpu.parallel.ulysses import ulysses_attention
        return _p(ulysses_attention, mesh=mesh, seq_axis=axis, **kw)
    raise ValueError(f"unknown attention kind {kind!r}; "
                     "want auto|full|flash|ring|ulysses")


def rope(x: jnp.ndarray, *, base: float = 10000.0,
         positions: jnp.ndarray | None = None) -> jnp.ndarray:
    """Rotary embedding over [B, T, H, D]; ``positions`` overrides the
    default global positions 0..T-1 — shape [T] (shared across the batch;
    decode steps pass their absolute position so cached keys and the new
    query rotate consistently) or [B, T] (per-row positions, the
    continuous-batching decode where every row sits at its own depth)."""
    b, t, h, d = x.shape
    half = d // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., half]
    if angles.ndim == 2:                         # [T, half] → [1, T, 1, half]
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]         # [1|B, T, 1, half]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


# the least tile of the decode step's context ladder: under it a shorter
# read saves less than one more trip of the loop costs, and a cache no
# longer than this has the one rung
_MIN_TILE = 128


def context_rungs(max_decode_len: int, tiles: int = 8) -> tuple[int, ...]:
    """The context lengths the per-row decode step can read, ascending:
    whole tiles of one ``tiles``-th (an eighth) of ``max_decode_len`` (of
    `_MIN_TILE` at least), the last ``max_decode_len`` itself. A step
    reads the first rung that holds every row it is handed
    (`MultiHeadAttention._decode_step`), a tile at a time; a cache of
    one rung is read whole, with no loop."""
    tile = max(-(-max_decode_len // tiles), _MIN_TILE)
    return (*range(tile, max_decode_len, tile), max_decode_len)


class MultiHeadAttention(nn.Module):
    """Pluggable-kernel attention; ``decode=True`` switches to single-token
    autoregressive serving with a KV cache in the flax "cache" collection
    (zero-init via `init`, threaded through `apply(..., mutable=["cache"])`
    by `idunno_tpu.engine.generate`).

    ``num_kv_heads`` < num_heads is grouped-query attention (GQA): groups
    of query heads share one K/V head, shrinking the decode KV cache —
    the dominant HBM tenant of long-context serving — by the group factor
    while the MXU compute shape is unchanged. num_kv_heads == num_heads
    (default) is exact MHA; num_kv_heads == 1 is MQA."""

    dim: int
    num_heads: int
    num_kv_heads: int | None = None
    causal: bool = True
    attn_fn: AttnFn = full_attention
    use_rope: bool = True
    decode: bool = False
    max_decode_len: int = 0
    decode_per_row: bool = False
    # "native" stores K/V at the compute dtype; "int8" stores symmetric
    # per-(row, position, head) int8 with float32 scales — ~4x (vs f32) /
    # ~2x (vs bf16) less KV-cache HBM, the long-context serving lever
    # alongside GQA. Lossy: greedy streams can drift from the native-cache
    # model's (opt-in; the exactness oracles run on "native").
    kv_cache_dtype: str = "native"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @property
    def _kv_heads(self) -> int:
        kv = (self.num_heads if self.num_kv_heads is None
              else self.num_kv_heads)
        if kv < 1:
            raise ValueError(f"num_kv_heads {kv} must be >= 1 "
                             "(1 = MQA; None = MHA)")
        if self.num_heads % kv:
            raise ValueError(f"num_heads {self.num_heads} must be a "
                             f"multiple of num_kv_heads {kv}")
        return kv

    @nn.compact
    def __call__(self, x, paged=None, layer=None):
        b, t, _ = x.shape
        head_dim = self.dim // self.num_heads
        kv_heads = self._kv_heads
        dense = partial(nn.DenseGeneral, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        q = dense(features=(self.num_heads, head_dim), name="q")(x)
        k = dense(features=(kv_heads, head_dim), name="k")(x)
        v = dense(features=(kv_heads, head_dim), name="v")(x)
        if self.decode:
            return self._decode_step(q, k, v, paged=paged, layer=layer)
        if paged is not None or layer is not None:
            raise ValueError("paged KV attention and a depth-stacked cache "
                             "are decode-mode features")
        if self.use_rope:
            q, k = rope(q), rope(k)
        if kv_heads != self.num_heads:
            # the training/prefill forward repeats K/V up to the query
            # heads so every attn_fn (full/flash/ring/ulysses) runs
            # unchanged — the GQA saving is the CACHE, which only the
            # decode path holds
            rep = self.num_heads // kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        out = self.attn_fn(q, k, v, causal=self.causal)
        return nn.DenseGeneral(features=self.dim, axis=(-2, -1),
                               dtype=self.dtype,
                               param_dtype=self.param_dtype,
                               name="out")(out)

    def _decode_step(self, q, k, v, paged=None, layer=None):
        """Autoregressive serving against the KV cache — three shapes:

        scalar cursor, t=1: one token in, one out (``engine.generate``);
        scalar cursor, t>1: CHUNKED prefill — the whole prompt in one apply,
          K/V written at cursor..cursor+t-1, causal within the chunk;
        per-row cursors (``decode_per_row``): continuous batching — every
          batch row sits at its own depth, cursors are int32 [B] and OWNED
          BY THE CALLER (read, never advanced here; the serving loop
          advances only its live rows — `engine.serve_lm.DecodeServer`);
          t>1 is the per-row chunk: row r writes K/V at cursors[r]..
          cursors[r]+t-1, causal within the chunk (no program path feeds
          this shape; the model-level tests do — ROADMAP D16).

        Uses its own cached softmax-attention kernel — any correct causal
        ``attn_fn`` (full/ring/flash) is numerically equivalent, so the
        training-time kernel choice does not matter here; non-causal models
        cannot be decoded autoregressively and are rejected.

        ``paged`` (an `ops.paged_attention.PagedContext`) splits the key
        space: cache positions [paged.start, paged.start + lengths[r])
        of row r are EXCLUDED from the slot-local mask and served from
        the block pool THROUGH the block table instead (no contiguous
        gather); the two normalized partials merge exactly via their
        log-sum-exps (`merge_attention`). A row's own chunk positions
        always sit beyond its paged region, so the local partial is
        never empty; zero-length chains contribute weight exactly 0.

        ``layer`` (a traced index; `scanned_apply`) says the K/V (and
        scale) variables are the DEPTH-STACKED leaves ``[L, B, T, kv, d]``
        the layer scan carries: this layer writes its new tokens' rows at
        ``(layer, row, position)`` of the stacked leaf, in place, and
        attends over its slice of that leaf read where it lies — the
        slice is never a value the scan hands back. The arithmetic is the
        same either way: write, then attend over the cache that holds the
        new token.

        The per-row step reads the LIVE context, not ``max_decode_len``:
        with ``need = max(cursors) + t`` it reads the first rung of
        `context_rungs` that holds ``need`` positions, for every row, a
        tile at a time with a running softmax (the sum's order differs
        from one softmax over the whole axis by float rounding; a masked
        position weighs exactly 0 either way). The cursors it is handed
        set the bound, so the caller hands a dead row 0
        (`engine.serve_lm._build_decode`). The scalar-cursor shapes read
        the whole axis at once, as ever: a prefill fills it, and
        `engine.generate`'s step is the oracle the pool's streams are held
        to."""
        if self.max_decode_len <= 0:
            raise ValueError("decode=True needs max_decode_len > 0")
        if not self.causal:
            raise ValueError("decode=True requires causal=True "
                             "(autoregressive serving of a bidirectional "
                             "model would silently change its semantics)")
        if self.kv_cache_dtype not in ("native", "int8"):
            raise ValueError(f"kv_cache_dtype {self.kv_cache_dtype!r}: "
                             "want native|int8")
        quant = self.kv_cache_dtype == "int8"
        b, t, h, d = q.shape
        kv_heads = k.shape[2]          # < h under GQA: the cache saving
        ck = self.variable("cache", "cached_k", jnp.zeros,
                           (b, self.max_decode_len, kv_heads, d),
                           jnp.int8 if quant else k.dtype)
        cv = self.variable("cache", "cached_v", jnp.zeros,
                           (b, self.max_decode_len, kv_heads, d),
                           jnp.int8 if quant else v.dtype)
        ks = vs = None
        if quant:
            ks = self.variable("cache", "k_scale", jnp.zeros,
                               (b, self.max_decode_len, kv_heads),
                               jnp.float32)
            vs = self.variable("cache", "v_scale", jnp.zeros,
                               (b, self.max_decode_len, kv_heads),
                               jnp.float32)

        def q8(x):
            """Symmetric int8 over the head dim: [.., kv_heads, d] →
            (int8 values, float32 scale [.., kv_heads])."""
            xf = x.astype(jnp.float32)
            s = jnp.maximum(jnp.abs(xf).max(axis=-1) / 127.0, 1e-8)
            vals = jnp.clip(jnp.round(xf / s[..., None]), -127, 127)
            return vals.astype(jnp.int8), s

        def at(*idx):
            """Index of this layer's rows in a cache leaf."""
            return idx if layer is None else (layer, *idx)

        if self.decode_per_row:
            cur = self.variable("cache", "cursors",
                                lambda: jnp.zeros((b,), jnp.int32))
            i = cur.value                                  # [B]
            # per-row positions [B, t]: row r covers i[r]..i[r]+t-1
            pos_bt = i[:, None] + jnp.arange(t)[None, :]
            # overflow guard: keep the cache intact and poison the scores
            # to NaN so misuse is loud, not silent
            overflow = i + t > self.max_decode_len         # [B]
            if self.use_rope:
                p = pos_bt.astype(jnp.float32)
                q, k = rope(q, positions=p), rope(k, positions=p)
            slot = jnp.clip(pos_bt, 0, self.max_decode_len - 1)  # [B, t]
            sel = at(jnp.arange(b)[:, None], slot)

            def put(var, vals):
                # overflow gating happens on the VALUES before the scatter
                # (an overflowing row re-writes its old cache entries — a
                # no-op), never as a post-scatter jnp.where over the whole
                # cache: that select would keep the pre-scatter cache live,
                # forcing XLA to COPY the full buffer every layer every
                # decode step instead of scattering in place
                ovr = overflow.reshape((b,) + (1,) * (vals.ndim - 1))
                return var.value.at[sel].set(
                    jnp.where(ovr, var.value[sel], vals))
            nxt = i          # read, never advanced: the caller owns them

            def mask_of(lo, n):
                # [B, 1, t, n]: row r's chunk position j attends slots
                # ≤ i[r]+j, here those of [lo, lo + n)
                ax = lo + jnp.arange(n)[None, None, :]
                live = ax <= pos_bt[:, :, None]
                if paged is not None:
                    # the paged interval is served through the block table
                    # — exclude it here so the merge never double-counts
                    live &= ~((ax >= paged.start) & (
                        ax < paged.start + paged.lengths[:, None, None]))
                return live[:, None, :, :]
            poison = overflow[:, None, None, None, None]
            rungs = context_rungs(self.max_decode_len)
        else:
            cur = self.variable("cache", "cursor",
                                lambda: jnp.zeros((), jnp.int32))
            i = cur.value
            pos = (i + jnp.arange(t)).astype(jnp.float32)  # [T]
            overflow = i + t > self.max_decode_len
            if self.use_rope:
                q, k = rope(q, positions=pos), rope(k, positions=pos)

            def put(var, vals):
                # same value-gating as the per-row branch: on overflow the
                # update writes back the OLD slice (dynamic_slice/-update
                # clamp the start identically, so the round-trip is a
                # no-op) instead of post-selecting over the whole cache,
                # which would block the in-place update and copy the
                # full buffer
                start = at(0, i) + (0,) * (vals.ndim - 2)
                if layer is not None:
                    vals = vals[None]
                old = jax.lax.dynamic_slice(var.value, start, vals.shape)
                return jax.lax.dynamic_update_slice(
                    var.value, jnp.where(overflow, old, vals), start)
            nxt = i + t
            # [q, T]: chunk position j attends cache slots ≤ i + j
            ax = jnp.arange(self.max_decode_len)[None, :]
            live = ax <= (i + jnp.arange(t))[:, None]
            if paged is not None:
                # batch-1 in the scalar-cursor shape: one chain length
                live &= ~((ax >= paged.start)
                          & (ax < paged.start + paged.lengths[0]))
            mask = live[None, None, :, :]
            poison = overflow
            rungs = (self.max_decode_len,)

            def mask_of(lo, n):
                return mask
        if quant:
            (k_st, k_sc), (v_st, v_sc) = q8(k), q8(v)
            written = [(ck, k_st), (cv, v_st), (ks, k_sc), (vs, v_sc)]
        else:
            written = [(ck, k), (cv, v)]
        new = [put(var, vals) for var, vals in written]
        if not self.is_initializing():      # init must return a CLEAN cache
            for (var, _), leaf in zip(written, new):
                var.value = leaf
            cur.value = nxt

        group = h // kv_heads

        def span(lo, n):
            """Positions [lo, lo + n) of this layer's rows: the grouped
            queries, their masked float32 scores against the keys, and the
            values."""
            if n < self.max_decode_len:
                if layer is None:
                    part = [jax.lax.dynamic_slice_in_dim(leaf, lo, n, axis=1)
                            for leaf in new]
                else:
                    part = [jax.lax.squeeze(jax.lax.dynamic_slice(
                        leaf, (layer, 0, lo) + (0,) * (leaf.ndim - 3),
                        (1, b, n) + leaf.shape[3:]), (0,)) for leaf in new]
            elif layer is not None:
                # the layer's [B, T, ...] slice of the carried leaf, read
                # where it lies: no copy of it is a value of the scan
                part = [jax.lax.dynamic_index_in_dim(leaf, layer, 0,
                                                     keepdims=False)
                        for leaf in new]
            else:
                part = new
            new_k, new_v, *scales = part
            # grouped attention against the (possibly narrower) cache: query
            # heads reshape to [.., kv_heads, group, d] so the einsum reads
            # the small cache straight from HBM — no repeat materialization.
            # group == 1 is exact MHA (identical contraction).
            if quant:
                new_k = new_k.astype(jnp.float32) * scales[0][..., None]
                new_v = new_v.astype(jnp.float32) * scales[1][..., None]
            q5 = q.reshape(b, t, kv_heads, group, d)
            # f32 casts on the operands: they FUSE into the dot reads (HBM
            # traffic stays at the cache's stored width), and XLA:CPU's
            # emulated-bf16 dots make a native-dtype einsum measurably slower
            # in the test/dev loop — measured 2026-07-31, 103→116 ms/step
            scores = jnp.einsum("bqhgd,bthd->bhgqt", q5.astype(jnp.float32),
                                new_k.astype(jnp.float32)) / (d ** 0.5)
            mask = mask_of(lo, n)[:, :, None]    # broadcast over the group
            scores = jnp.where(poison, jnp.nan, scores)
            scores = jnp.where(mask, scores, -jnp.inf)
            return q5, scores, new_v

        lse_l = None
        if len(rungs) == 1:
            q5, scores, new_v = span(0, rungs[0])
            if paged is None:
                weights = jax.nn.softmax(scores, axis=-1)
                o_l = jnp.einsum("bhgqt,bthd->bqhgd", weights,
                                 new_v.astype(jnp.float32))
            else:
                # explicit softmax so the local partial exposes its lse for
                # the exact merge with the paged partial; the query's own
                # chunk positions are always live locally, so m_l is finite
                # (NaN poison still propagates — overflow stays loud)
                m_l = jnp.max(scores, axis=-1, keepdims=True)
                p_l = jnp.exp(scores - jax.lax.stop_gradient(m_l))
                l_l = jnp.sum(p_l, axis=-1, keepdims=True)
                # normalize BEFORE the value einsum — the exact op order of
                # jax.nn.softmax + einsum above, so a row whose paged chain
                # is empty reproduces the dense branch bit-for-bit
                o_l = jnp.einsum("bhgqt,bthd->bqhgd", p_l / l_l,
                                 new_v.astype(jnp.float32))
                lse_l = jnp.transpose((m_l + jnp.log(l_l))[..., 0],
                                      (0, 3, 1, 2))           # [b, t, kvh, g]
        else:
            # the context ladder, a tile at a time with a running softmax:
            # as many tiles as the first rung that holds the deepest row's
            # new tokens (an overflowing row takes them all and is
            # poisoned). A masked position weighs exactly 0, so the tiles
            # left unread change nothing. The loop reads the carried
            # leaves where they lie; a `lax.switch` over the rungs had the
            # compiler copy a whole leaf into every branch (PERF.md §6)
            tile, top = rungs[0], self.max_decode_len

            def one_tile(j, carry):
                m, l, acc = carry
                # the last rung may be no whole tile: its slice starts
                # early, and leaves what the tile before it covered
                lo = jnp.minimum(j * tile, top - tile)
                _, scores, new_v = span(lo, tile)
                fresh = lo + jnp.arange(tile) >= j * tile
                scores = jnp.where(fresh, scores, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
                # a row with nothing live so far has m_new -inf
                base = jnp.where(m_new == -jnp.inf, 0.0, m_new)
                p = jnp.exp(scores - base[..., None])
                keep = jnp.exp(m - base)
                return (m_new, l * keep + jnp.sum(p, axis=-1),
                        acc * keep[..., None]
                        + jnp.einsum("bhgqt,bthd->bhgqd", p,
                                     new_v.astype(jnp.float32)))

            stat = (b, kv_heads, group, t)
            m_l, l_l, acc = jax.lax.fori_loop(
                0, jnp.sum(jnp.max(i) + t > jnp.asarray(rungs[:-1])) + 1,
                one_tile, (jnp.full(stat, -jnp.inf, jnp.float32),
                           jnp.zeros(stat, jnp.float32),
                           jnp.zeros(stat + (d,), jnp.float32)))
            o_l = jnp.transpose(acc / l_l[..., None], (0, 3, 1, 2, 4))
            if paged is not None:
                q5 = q.reshape(b, t, kv_heads, group, d)
                lse_l = jnp.transpose(m_l + jnp.log(l_l), (0, 3, 1, 2))
        if paged is None:
            out = o_l.astype(self.dtype)
        else:
            o_p, lse_p = paged_attention_grouped(
                q5.astype(jnp.float32), paged.k_pages, paged.v_pages,
                paged.tables, paged.lengths,
                k_scale_pages=paged.k_scale_pages,
                v_scale_pages=paged.v_scale_pages,
                kernel=paged.kernel, interpret=paged.interpret)
            out = merge_attention(o_l, lse_l, o_p, lse_p).astype(self.dtype)
        out = out.reshape(b, t, h, d)
        return nn.DenseGeneral(features=self.dim, axis=(-2, -1),
                               dtype=self.dtype,
                               param_dtype=self.param_dtype,
                               name="out")(out)


class Block(nn.Module):
    """Pre-LN block with pluggable attention AND pluggable FFN — MoE and
    other conditional-compute families swap the MLP via ``ffn_factory``
    instead of duplicating the residual wiring."""

    dim: int
    num_heads: int
    num_kv_heads: int | None = None
    mlp_ratio: int = 4
    causal: bool = True
    attn_fn: AttnFn = full_attention
    ffn_factory: FfnFactory | None = None
    use_rope: bool = True
    decode: bool = False
    max_decode_len: int = 0
    decode_per_row: bool = False
    kv_cache_dtype: str = "native"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, paged=None, layer=None):
        ln = partial(nn.LayerNorm, dtype=self.dtype,
                     param_dtype=self.param_dtype)
        x = x + MultiHeadAttention(
            self.dim, self.num_heads, num_kv_heads=self.num_kv_heads,
            causal=self.causal,
            attn_fn=self.attn_fn, use_rope=self.use_rope,
            decode=self.decode, max_decode_len=self.max_decode_len,
            decode_per_row=self.decode_per_row,
            kv_cache_dtype=self.kv_cache_dtype,
            dtype=self.dtype,
            param_dtype=self.param_dtype, name="attn")(
                ln(name="ln1")(x), paged=paged, layer=layer)
        h_in = ln(name="ln2")(x)
        if self.ffn_factory is not None:
            return x + self.ffn_factory(
                dim=self.dim, dtype=self.dtype,
                param_dtype=self.param_dtype, name="ffn")(h_in)
        dense = partial(nn.Dense, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        h = dense(self.dim * self.mlp_ratio, name="mlp_up")(h_in)
        return x + dense(self.dim, name="mlp_down")(nn.gelu(h))


class TransformerLM(nn.Module):
    """Minimal causal LM for long-context serving/training demos.

    ``ffn_factory`` swaps the dense MLP for another FFN (e.g. a switch-MoE
    layer) on every ``ffn_every``-th block (counting from the last block
    backwards, the Switch-Transformer interleaving); the remaining blocks
    keep the dense MLP.
    """

    vocab: int = 1024
    dim: int = 128
    depth: int = 2
    num_heads: int = 4
    num_kv_heads: int | None = None   # < num_heads = GQA; None = MHA
    causal: bool = True
    attn_fn: AttnFn = full_attention
    ffn_factory: FfnFactory | None = None
    ffn_every: int = 1
    decode: bool = False
    max_decode_len: int = 0
    decode_per_row: bool = False
    # "int8": quantized KV cache in decode mode (see MultiHeadAttention)
    kv_cache_dtype: str = "native"
    remat: bool = False
    # scan_layers=True marks the SCANNED decode twin: params/cache leaves
    # carry a leading depth axis and the layer loop is one `lax.scan`
    # (`scanned_apply`). The flax module itself must never run in this
    # mode — `decode_apply` is the only entry point; the unscanned module
    # stays the canonical layout for init/checkpointing/training.
    scan_layers: bool = False
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def decode_context_rungs(self, max_len: int,
                             rows: int) -> tuple[int, ...] | None:
        """What `DecodeServer` asks of any model: the context lengths its
        per-row decode step can read of a cache of ``rows`` slots x
        ``max_len`` tokens (the step reads the first that holds the
        deepest cursor it is handed, so the pool hands a dead row 0 and
        counts `decode_context_*`), or None where the step reads what it
        reads whatever the cursors. Every block's attention here is
        `MultiHeadAttention._decode_step`: always its ladder, whatever
        the rows."""
        return context_rungs(max_len)

    @nn.compact
    def __call__(self, tokens):
        if self.scan_layers:
            raise ValueError(
                "scan_layers=True models hold depth-stacked params/cache "
                "and cannot run through the flax per-layer loop; call "
                "decode_apply (models.transformer) instead of .apply")
        if self.ffn_every < 1:
            raise ValueError(f"ffn_every={self.ffn_every}: must be >= 1")
        # remat: recompute each block's activations in the backward pass
        # instead of storing them — activation memory drops from O(depth·T·d)
        # to O(T·d) at ~1/3 extra FLOPs, the standard long-context trade
        block_cls = nn.remat(Block) if self.remat else Block
        x = nn.Embed(self.vocab, self.dim, dtype=self.dtype,
                     param_dtype=self.param_dtype, name="embed")(tokens)
        for i in range(self.depth):
            use_ffn = (self.ffn_factory is not None
                       and (self.depth - 1 - i) % self.ffn_every == 0)
            x = block_cls(self.dim, self.num_heads,
                          num_kv_heads=self.num_kv_heads,
                          causal=self.causal,
                          attn_fn=self.attn_fn,
                          ffn_factory=self.ffn_factory if use_ffn else None,
                          decode=self.decode,
                          max_decode_len=self.max_decode_len,
                          decode_per_row=self.decode_per_row,
                          kv_cache_dtype=self.kv_cache_dtype,
                          dtype=self.dtype,
                          param_dtype=self.param_dtype, name=f"block{i}")(x)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype,
                         name="ln_f")(x)
        logits = nn.Dense(self.vocab, dtype=self.dtype,
                          param_dtype=self.param_dtype, name="head")(x)
        return logits.astype(jnp.float32)


# -- scanned decode: the layer loop as ONE lax.scan -------------------------
#
# The per-layer Python loop above emits `depth` separate fusion groups per
# decode step; at serving dims each group is a handful of small ops, so the
# step time is dominated by dispatch overhead rather than the HBM-bound
# weight stream (TRACE_LM_DECODE.json: 1.98 ms measured vs ~1.03 ms bound).
# Stacking every block's params/cache on a leading depth axis and scanning
# `Block.apply` collapses the loop to one fused scan body. The scan body IS
# `Block.apply` on one layer's slice — same module, same math, same order —
# but XLA's scan-body fusion may move float rounding by ~1 ULP vs the
# unrolled loop, so exactness is enforced STRUCTURALLY instead: serving and
# `engine.generate` run the IDENTICAL scanned step, and every oracle test
# (tests/test_serve_lm.py) pins the streams against each other.


def scan_compatible(model: TransformerLM) -> bool:
    """Whether a model's blocks are homogeneous enough to scan: every
    block must run the same program on its own param/cache slice, which a
    per-block ``ffn_factory`` (MoE interleaving) breaks — those models
    keep the per-layer loop."""
    return model.ffn_factory is None


def stack_block_params(params, depth: int):
    """Per-block params → the scanned layout: ``block0..block{L-1}``
    subtrees are stacked leaf-wise onto a leading depth axis under
    ``"blocks"``; embed/ln_f/head pass through. Works on quantized trees
    too (QTensor is a pytree — q and scale stack independently, and
    `ops.quantize.dequantize_tree`'s per-leaf broadcast is rank-agnostic,
    so quantize-then-stack preserves the dequantized numerics)."""
    blocks = [params[f"block{i}"] for i in range(depth)]
    return {
        "embed": params["embed"],
        "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
        "ln_f": params["ln_f"],
        "head": params["head"],
    }


def scanned_apply(model: TransformerLM, params, cache, tokens, paged=None):
    """One decode/prefill step of a ``scan_layers=True`` model: embed →
    `lax.scan` of `Block.apply` over the depth-stacked params → final norm
    → logits. Returns ``(float32 logits, new cache)`` — the same contract
    as ``model.apply(..., mutable=["cache"])`` unpacked, with the cache's
    leading axis the layer index.

    The K/V leaves (K, V and, under int8, their scales) ride through the
    scan as CARRY, whole and depth-stacked: a layer writes only its new
    tokens' rows into them, in place, and reads its own slice where it
    lies (`MultiHeadAttention._decode_step`, ``layer``). As the scan's
    ``xs``/``ys`` they were sliced out and written back whole, every
    layer of every step — more device time than the weight stream at 28
    slots x 4096 (PERF.md §6, PR 30). The cursor leaves are a few
    integers a layer and stay ``xs``/``ys``.

    ``paged`` carries depth-stacked page stores (``[L, N, bs, ...]``,
    `engine.kv_blocks.KVBlockPool.kv_pages`); the scan slices each
    layer's page array alongside its params slice, so the block pool is
    read in place — never gathered."""
    blk = Block(model.dim, model.num_heads,
                num_kv_heads=model.num_kv_heads,
                causal=model.causal,
                attn_fn=model.attn_fn,
                ffn_factory=None,
                decode=model.decode,
                max_decode_len=model.max_decode_len,
                decode_per_row=model.decode_per_row,
                kv_cache_dtype=model.kv_cache_dtype,
                dtype=model.dtype,
                param_dtype=model.param_dtype)
    x = nn.Embed(model.vocab, model.dim, dtype=model.dtype,
                 param_dtype=model.param_dtype).apply(
        {"params": params["embed"]}, tokens)
    cursors = {k: v for k, v in cache["attn"].items()
               if k in ("cursor", "cursors")}
    kv = {k: v for k, v in cache["attn"].items() if k not in cursors}
    pages = None if paged is None else (
        paged.k_pages, paged.v_pages, paged.k_scale_pages,
        paged.v_scale_pages)

    def body(carry, layer):
        h, kv = carry
        i_l, p_l, cur_l, pages_l = layer
        h, mut = blk.apply(
            {"params": p_l, "cache": {"attn": {**kv, **cur_l}}}, h,
            paged=None if paged is None else paged.layer(*pages_l),
            layer=i_l, mutable=["cache"])
        new = mut["cache"]["attn"]
        return ((h, {k: new[k] for k in kv}),
                {k: new[k] for k in cur_l})

    (x, kv), cursors = jax.lax.scan(
        body, (x, kv),
        (jnp.arange(model.depth), params["blocks"], cursors, pages))
    x = nn.LayerNorm(dtype=model.dtype, param_dtype=model.param_dtype
                     ).apply({"params": params["ln_f"]}, x)
    logits = nn.Dense(model.vocab, dtype=model.dtype,
                      param_dtype=model.param_dtype).apply(
        {"params": params["head"]}, x)
    return logits.astype(jnp.float32), {"attn": {**kv, **cursors}}


def decode_apply(model: TransformerLM, params, cache, tokens, paged=None):
    """THE decode-step entry point: dispatches on ``model.scan_layers``
    so callers (`engine.serve_lm`, `engine.generate`) are layout-blind.
    Returns ``(float32 logits, new cache)``. A model that is no
    `TransformerLM` (`models/hybrid.py`: layers of several kinds, caches
    of several shapes) brings its own step as ``model.decode_apply``."""
    own = getattr(model, "decode_apply", None)
    if own is not None:
        return own(params, cache, tokens, paged=paged)
    if getattr(model, "scan_layers", False):
        return scanned_apply(model, params, cache, tokens, paged=paged)
    if paged is not None:
        raise ValueError(
            "paged KV attention requires the scanned decode layout "
            "(scan_layers=True): page stores are depth-stacked")
    logits, mut = model.apply({"params": params, "cache": cache}, tokens,
                              mutable=["cache"])
    return logits, mut["cache"]
