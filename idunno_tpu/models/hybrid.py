"""A hybrid LM stack described by data: layers of several kinds in any order.

`TransformerLM` is one block repeated, one cache shape a layer. This module
serves stacks whose layers differ in kind (`mixers`, one name a layer) and
whose caches differ with them. Six kinds of mixer:

  ``minicpm4``        block-sparse softmax attention (InfLLM-v2): grouped
                      query heads without RoPE over `cached_k`/`cached_v`,
                      plus `comp_k`, the keys mean-pooled over
                      `kernel_size` tokens every `kernel_stride` (the
                      indexer's cache). A query whose context is longer
                      than `dense_len` scores the pooled keys, takes a
                      block's score from the pooled keys that overlap it and
                      attends block 0, the blocks over its last
                      `window_size` tokens and the `topk` best others.
  ``lightning-attn``  decayed linear attention: a float32 `state`
                      [heads, d, d] a row, `S_t = exp(-s) S_{t-1} + k_t^T
                      v_t`, `o_t = q_t S_t / sqrt(d)`; no token axis.
  ``mamba2``          a state-space layer (Mamba-2): one in-projection
                      split into gate / convolution input / step sizes, a
                      depthwise causal convolution whose last `ssm_conv - 1`
                      inputs are the row's `conv` window, the selective
                      scan `S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`,
                      `y_t = S_t C_t + D x_t` over a float32 `state`
                      [heads, P, N] a row (in chunks of `ssm_chunk` tokens
                      in prefill, one update a decode step), a gated
                      RMSNorm, the out-projection; no token axis.
  ``attention``       grouped-query softmax attention over
                      `cached_k`/`cached_v`, no selection, scale
                      `attn_scale`; no positional embedding, or
                      (`attn_rope`) rotary positions at `rope_theta` over
                      the whole head or its first `rotary_dims` channels;
                      the keys times `key_mult`; optionally (Qwen3-Next's
                      gated attention) RMSNorm of each head's q and k
                      (`attn_qk_norm`) and a sigmoid gate an output
                      element, the second half of each head's q-projection
                      (`attn_gate`). The per-row decode step reads the
                      LIVE context: the first rung of `context_rungs` that
                      holds the deepest cursor it is handed, a tile at a
                      time over the carried K/V (`_attend_live`).
  ``attention+mamba2`` both of the last two in ONE layer, side by side on
                      one normed input (Falcon-H1): `h += ssm_out_mult *
                      Mamba2(ssm_in_mult * u) + attn_out_mult *
                      Attention(attn_in_mult * u)`, `u = RMSNorm(h)`. The
                      layer's cache holds `cached_k`/`cached_v` (a token
                      axis) AND `state` and `conv` (none). A name with a
                      `+` is the kinds it joins: `has("mamba2")` and
                      `has("attention")` hold of such a stack.
  ``gated-deltanet``  the gated delta rule (Qwen3-Next's linear
                      attention): in-projections grouped by key head, a
                      depthwise causal convolution over q, k and v whose
                      last `ssm_conv - 1` inputs are the row's `conv`
                      window, l2-normed q and k (a value head reads its key
                      head), `S_t = exp(g_t) S_{t-1}` then `S_t += k_t
                      (beta_t (v_t - S_t^T k_t))^T`, `o_t = S_t^T q_t` over
                      a float32 `state` [value heads, dk, dv] a row (in
                      its chunked form over chunks of `_DELTA_CHUNK`
                      tokens in prefill, one update a decode step), RMSNorm of
                      each head's output times silu(z), the
                      out-projection; no token axis.

and two kinds of feed-forward, one a stack (`ffn`): ``dense``, a gated SiLU
MLP (its gate and its output times `mlp_mults`), and ``moe``, routed experts
of which this chip holds a share (`models.moe.routed_experts`: dropless
top-k over the whole router, the held experts' terms alone) plus a shared
expert every token takes, times sigmoid(x wsg) where `shared_gate`
(Qwen3-Next). A step multiplies every token by every held expert only where
its tokens are few (`_GROUP_FROM`) and each held expert expects a pick;
otherwise tokens are grouped by expert.

The first two mixers sit in MiniCPM's pre-norm block (RMSNorm, bias-free
projections, q/k RMSNorm, sigmoid output gate); every layer is `h += a *
Mixer(RMSNorm(h)); h += a * FFN(RMSNorm(h))` with `a = scale_depth /
sqrt(published_depth)`, the embedding times `scale_emb` and the logits over
`logit_div`. Consecutive layers of one kind are ONE `lax.scan` over their
stacked parameters and cache, so a stack of 1 + 6 + 2 + 3 layers is four
scans in one program, not twelve dispatches.

`HybridLM` is a frozen dataclass, not a flax module: it is the jit-static
description, `init_cache` and `decode_apply` are its two entry points, and
`engine.generate.init_cache` / `models.transformer.decode_apply` hand over
to them, so `engine/generate.py` and `DecodeServer` stay layout-blind.
`decode_context_rungs` tells the pool whether the stack's decode step
bounds its read by the cursors (the `attention` kinds, without a
block-sparse layer): the pool then hands a dead row's cursor as 0. The
cache follows the scanned layout's rules (leaves named by kind, depth
leading, the slot axis second): `cached_k`/`cached_v` [L, B, T, kvh, d],
`comp_k` [L, B, T/stride, kvh, d] float32, `state` [L, B, H, d, d] or
[L, B, H, P, N] float32, `conv` [L, B, ssm_conv - 1, width], one
`cursor`/`cursors` leaf and, in the scalar-cursor (prefill) shape, one
`valid` leaf: positions at or past it are padding and enter neither the
state, the window nor the pooled keys. A stack with `last_logits` computes
a prefill's logits at the last real position alone ([B, 1, vocab]: a
2048-token chunk's float32 logits over 261120 rows would be 2.1 GB). A
per-row (decode) cache of a ``moe`` stack or of one with a ``mamba2`` or
``gated-deltanet`` layer also carries `live` [B] (the rows that hold a
request: the dispatch sets it; a dead row is routed nowhere, and its state
is neither read nor written: `ops/ssd_decode.py`, `_delta_live`) and the
counters: a run of a ``moe`` stack `expert_load` [L, held] and
`expert_steps` [L, 3] (int32: picks on each held expert; held experts
touched, steps and tokens, summed over the steps that had a live row), a
run with a ``mamba2`` or ``gated-deltanet`` layer `state_rows` [L, 2]
(int32: the rows whose state a step moved, and the rows the slots hold,
summed over the steps). They have no slot axis and are read when
`DecodeServer.stats()` is.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from idunno_tpu.models.moe import routed_experts
from idunno_tpu.models.transformer import context_rungs, rope
from idunno_tpu.ops.ssd_decode import ssd_decode

SPARSE, LINEAR = "minicpm4", "lightning-attn"
MAMBA, ATTENTION = "mamba2", "attention"
PARALLEL = ATTENTION + "+" + MAMBA     # both mixers of one layer, summed
DELTA = "gated-deltanet"
DENSE, MOE = "dense", "moe"
_HI = jax.lax.Precision.HIGHEST
_NO_LIMIT = np.iinfo(np.int32).max
_LIN_BLOCK = 256        # tokens a lightning sub-block (intra-chunk product)
_L2_EPS = 1e-6          # gated-deltanet: the l2 norm of its queries and keys
_DELTA_CHUNK = 64       # and the tokens a chunk of its prefill's chunked form
_TRI_BLOCK = 16         # rows a diagonal block of that chunk's inverse
_KEY_TILE = 1024        # keys a tile of the chunked sparse prefill
_QUERY_TILE = 512       # queries a tile of the plain attention's prefill
# below this many tokens an expert layer multiplies every token by every
# held expert, where each held expert expects a pick (tokens x experts a
# token >= the router's width: granite-4.0-h-small's 48 decode rows x 10 >=
# 72); from it on, or where most held experts would be read for no pick
# (Qwen3-Next's 16 rows x 10 of 512 touch some 69 of 256 held), tokens are
# grouped by expert (`routed_experts`, `reads_every_held_expert`)
_GROUP_FROM = 256
# a trip of the decode step's context ladder (`_attend_live`) stages one
# tile of K and one of V, every slot's, before the products read them (the
# chip's compiler fuses no dynamic slice into a product); up to this many
# bytes a tile both stay in the chip's fast memory, so the cache is read
# from HBM once. At granite-4.0-h-small's 60 MB (48 slots x 608 tokens x 8
# heads) only one did, and the step cost 0.5 ms MORE than a read of the
# whole axis, which that stack's one-layer run needs no slice for
# (PERF.md section 6, PR 37)
_STAGED_TILE_BYTES = 32 << 20
# the cache leaves of a run that ride through its scan as carry, whole and
# depth-stacked, and are written where they lie
_CARRIED = {ATTENTION: ("cached_k", "cached_v"), MAMBA: ("state",),
            PARALLEL: ("cached_k", "cached_v", "state"), DELTA: ("state",)}
# the parameters of a run that its scan does not slice a layer: the routed
# experts, which the grouped product reads from the whole stack
_WHOLE = ("w1", "w2")


class UnsupportedStack(ValueError):
    """A serving feature that a stack with recurrent or block-sparse layers
    cannot use yet (a radix hit restores keys and values only), or a shape
    of such a stack that this module does not run."""


@dataclass(frozen=True)
class HybridLM:
    vocab: int
    dim: int
    mlp_dim: int
    mixers: tuple            # SPARSE / LINEAR / MAMBA / ATTENTION /
    #                          PARALLEL / DELTA
    layer_ids: tuple         # each layer's PUBLISHED index (its slopes)
    published_depth: int
    num_heads: int           # sparse and attention layers: query heads
    num_kv_heads: int        # sparse and attention layers: KV heads
    head_dim: int
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    logit_div: float = 1.0   # hidden_size / dim_model_base
    eps: float = 1e-6
    rope_theta: float = 10000.0
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    attn_scale: float | None = None    # attention: None = 1 / sqrt(d)
    attn_rope: bool = False  # attention: rotary positions at `rope_theta`
    key_mult: float = 1.0    # attention: the keys' multiplier
    # attention (Qwen3-Next's gated attention): RMSNorm of each head's
    # queries and keys (`qn`, `kn`), rotary positions over the first
    # `rotary_dims` channels of a head alone (0: all of them), and a
    # sigmoid gate an output element, the second half of each head's
    # query projection (`wq` [dim, heads, 2 head_dim])
    attn_qk_norm: bool = False
    rotary_dims: int = 0
    attn_gate: bool = False
    # a PARALLEL layer's branch multipliers, on the normed input and on the
    # out-projection's output of each mixer
    attn_in_mult: float = 1.0
    attn_out_mult: float = 1.0
    ssm_in_mult: float = 1.0
    ssm_out_mult: float = 1.0
    # mamba2: multipliers of the in-projection's output, a segment each
    # (gate, x, B, C, step sizes); () = none
    ssm_mults: tuple = ()
    mlp_mults: tuple = (1.0, 1.0)      # dense MLP: on the gate, on the output
    ssm_heads: int = 0       # mamba2: heads H, each of `ssm_head_dim` (P)
    ssm_head_dim: int = 0
    ssm_state: int = 0       # N
    ssm_groups: int = 1      # B and C are shared by the heads of a group,
    #                          and the gated norm is over a group's channels
    ssm_conv: int = 4        # taps of the depthwise convolution
    ssm_chunk: int = 256     # tokens a chunk of the prefill scan
    # gated-deltanet: key heads and value heads (value head j reads key
    # head j // (value heads / key heads)) and their widths; its
    # convolution has `ssm_conv` taps
    delta_key_heads: int = 0
    delta_value_heads: int = 0
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    ffn: str = DENSE         # DENSE (width `mlp_dim`) or MOE
    experts: int = 0         # moe: the router's width (all the experts)
    experts_per_token: int = 0
    experts_held: tuple = (0, 0)       # (first, count) held by this chip
    shared_dim: int = 0      # the shared expert's width; `mlp_dim` is a
    #                          routed expert's
    shared_gate: bool = False          # the shared expert times
    #                                    sigmoid(x wsg)
    last_logits: bool = False          # a prefill's logits: the last real
    #                                    position's alone, [B, 1, vocab]
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    decode: bool = False
    decode_per_row: bool = False
    max_decode_len: int = 0
    # the Pallas kernels of `ops/` under the interpreter (off the chip:
    # `DecodeServer` sets it as it does for the paged kernel)
    interpret: bool = False
    # what `DecodeServer` asks of any model
    causal: bool = True
    scan_layers: bool = True
    kv_cache_dtype: str = "native"
    ffn_factory: Any = None

    def __post_init__(self):
        if len(self.mixers) != len(self.layer_ids):
            raise ValueError("one published index a layer")
        bad = set(self.mixers) - {SPARSE, LINEAR, MAMBA, ATTENTION, PARALLEL,
                                  DELTA}
        if bad:
            raise ValueError(f"unknown mixer kinds {sorted(bad)}")
        if self.ffn not in (DENSE, MOE):
            raise ValueError(f"unknown feed-forward kind {self.ffn!r}")
        if self.has(SPARSE) and (self.block_size % self.kernel_stride
                                 or self.kernel_size % self.kernel_stride):
            raise ValueError("block_size and kernel_size must be multiples "
                             "of kernel_stride")
        if ((self.has(SPARSE) or self.has(ATTENTION))
                and self.num_heads % self.num_kv_heads):
            raise ValueError("query heads must be a multiple of KV heads")
        if self.has(MAMBA) and self.ssm_heads % self.ssm_groups:
            raise ValueError("state-space heads must be a multiple of "
                             "their groups")
        if self.has(DELTA) and not (
                0 < self.delta_key_heads
                and self.delta_value_heads % self.delta_key_heads == 0
                and self.delta_value_heads and self.delta_key_dim
                and self.delta_value_dim):
            raise ValueError("gated-deltanet value heads must be a "
                             "multiple of its key heads, and widths given")
        if self.rotary_dims % 2 or self.rotary_dims > self.head_dim:
            raise ValueError("rotary_dims is an even part of head_dim")
        if len(self.ssm_mults) not in (0, 5) or len(self.mlp_mults) != 2:
            raise ValueError("ssm_mults is one multiplier a segment (gate, "
                             "x, B, C, step sizes), mlp_mults two")
        if self.has(PARALLEL) and self.ffn != DENSE:
            raise ValueError("a layer of both mixers takes the dense "
                             "feed-forward")
        if self.ffn == MOE:
            first, held = self.experts_held
            if not (0 <= first and 0 < held
                    and first + held <= self.experts):
                raise ValueError(
                    f"experts_held {self.experts_held} is no share of "
                    f"{self.experts} experts")
            if not 0 < self.experts_per_token <= self.experts:
                raise ValueError("experts_per_token must be 1..experts")

    def has(self, kind: str) -> bool:
        """Whether the stack has a layer of ``kind``, alone or as one of
        the mixers a layer joins."""
        return any(kind in (m, *m.split("+")) for m in self.mixers)

    @property
    def depth(self) -> int:
        return len(self.mixers)

    # a slot holds state that keys and values cannot rebuild: a radix
    # prefix hit is not restorable (`DecodeServer` asks)
    recurrent = True

    def runs(self) -> list[tuple[str, tuple]]:
        """[(kind, published indices)] of consecutive layers of one kind."""
        out: list[list] = []
        for kind, lid in zip(self.mixers, self.layer_ids):
            if out and out[-1][0] == kind:
                out[-1][1].append(lid)
            else:
                out.append([kind, [lid]])
        return [(k, tuple(ids)) for k, ids in out]

    def slopes(self, layer_id: int) -> np.ndarray:
        """Lightning Attention's decay rates of one layer, a head each."""
        h = self.lightning_heads
        base = 2.0 ** (-8.0 * (np.arange(h) + 1) / h)
        return (base * (1.0 - layer_id / (self.published_depth - 1) + 1e-5)
                ).astype(np.float32)

    def sel_width(self) -> int:
        """Blocks a sparse decode step gathers at most: the selection
        (init + window + topk) or a whole dense context, whichever is
        more."""
        window = -(-self.window_size // self.block_size) + 1
        return max(self.init_blocks + window + self.topk,
                   -(-self.dense_len // self.block_size))

    def attended_tokens(self, contexts) -> np.ndarray:
        """Tokens a sparse layer's query attends when its context holds
        ``contexts`` tokens (itself included), from the geometry alone."""
        n = np.asarray(contexts, np.int64)
        bs = self.block_size
        t = n - 1
        w0 = np.maximum(t - self.window_size + 1, 0) // bs
        init = np.minimum(self.init_blocks, w0)
        picked = np.minimum(self.topk, w0 - init)
        sparse = (init + picked) * bs + (n - w0 * bs)
        return np.where(n > self.dense_len, sparse, n)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.published_depth ** 0.5

    @property
    def ssm_conv_width(self) -> int:
        """Channels of a mamba2 layer's convolution: x, B and C."""
        return (self.ssm_heads * self.ssm_head_dim
                + 2 * self.ssm_groups * self.ssm_state)

    @property
    def delta_conv_width(self) -> int:
        """Channels of a gated-deltanet layer's convolution: q, k and v."""
        return (2 * self.delta_key_heads * self.delta_key_dim
                + self.delta_value_heads * self.delta_value_dim)

    def state_bytes(self, batch: int) -> int:
        """Bytes of recurrent state ``batch`` rows hold: every leaf without
        a token axis (the float32 states, the convolution windows)."""
        per_row = 0
        item = jnp.dtype(self.dtype).itemsize
        for kind in self.mixers:
            if kind == LINEAR:
                per_row += 4 * self.lightning_heads * self.lightning_head_dim ** 2
            elif kind in (MAMBA, PARALLEL):
                per_row += (4 * self.ssm_heads * self.ssm_head_dim
                            * self.ssm_state
                            + (self.ssm_conv - 1) * self.ssm_conv_width
                            * item)
            elif kind == DELTA:
                per_row += (4 * self.delta_value_heads * self.delta_key_dim
                            * self.delta_value_dim
                            + (self.ssm_conv - 1) * self.delta_conv_width
                            * item)
        return batch * per_row

    def init_cache(self, batch: int) -> dict:
        """Zeroed cache for ``batch`` rows of ``max_decode_len`` tokens."""
        if self.max_decode_len <= 0:
            raise ValueError("decode=True needs max_decode_len > 0")
        tm = self.max_decode_len
        if self.has(SPARSE) and tm % self.block_size:
            raise ValueError(
                f"cache length {tm} must be a multiple of the selection's "
                f"block_size {self.block_size}")
        nk = max(1, (tm - self.kernel_size) // self.kernel_stride + 1)
        counted = self.ffn == MOE and self.decode_per_row
        cache: dict = {}
        if self.decode_per_row:
            cache["cursors"] = jnp.zeros((batch,), jnp.int32)
            if counted or self.has(MAMBA) or self.has(DELTA):
                cache["live"] = jnp.ones((batch,), jnp.bool_)
        else:
            cache["cursor"] = jnp.zeros((), jnp.int32)
            cache["valid"] = jnp.full((), _NO_LIMIT, jnp.int32)
        kv = (batch, tm, self.num_kv_heads, self.head_dim)
        for r, (kind, ids) in enumerate(self.runs()):
            n = len(ids)
            run = {}
            if kind in (SPARSE, ATTENTION, PARALLEL):
                run = {k: jnp.zeros((n,) + kv, self.dtype)
                       for k in ("cached_k", "cached_v")}
                if kind == SPARSE:
                    run["comp_k"] = jnp.zeros((n, batch, nk) + kv[2:],
                                              jnp.float32)
            if kind in (MAMBA, PARALLEL):
                run.update(state=jnp.zeros(
                    (n, batch, self.ssm_heads, self.ssm_head_dim,
                     self.ssm_state), jnp.float32),
                           conv=jnp.zeros(
                    (n, batch, self.ssm_conv - 1, self.ssm_conv_width),
                    self.dtype))
                if self.decode_per_row:
                    run["state_rows"] = jnp.zeros((n, 2), jnp.int32)
            if kind == LINEAR:
                d = self.lightning_head_dim
                run = {"state": jnp.zeros(
                    (n, batch, self.lightning_heads, d, d), jnp.float32)}
            if kind == DELTA:
                run = {"state": jnp.zeros(
                    (n, batch, self.delta_value_heads, self.delta_key_dim,
                     self.delta_value_dim), jnp.float32),
                       "conv": jnp.zeros(
                    (n, batch, self.ssm_conv - 1, self.delta_conv_width),
                    self.dtype)}
                if self.decode_per_row:
                    run["state_rows"] = jnp.zeros((n, 2), jnp.int32)
            if counted:
                run["expert_load"] = jnp.zeros((n, self.experts_held[1]),
                                               jnp.int32)
                run["expert_steps"] = jnp.zeros((n, 3), jnp.int32)
            cache[f"run{r}"] = run
        return cache

    def decode_apply(self, params, cache, tokens, paged=None):
        return hybrid_apply(self, params, cache, tokens, paged=paged)

    def decode_context_rungs(self, max_len: int,
                             rows: int) -> tuple[int, ...] | None:
        """What `DecodeServer` asks of any model: the context lengths its
        per-row decode step can read of a cache of ``rows`` slots x
        ``max_len`` tokens (`models.transformer.context_rungs`; the step
        reads the first that holds the deepest cursor it is handed, so the
        pool hands a dead row 0 and counts `decode_context_*`), or None
        where the step reads what it reads whatever the cursors (it is
        handed them as they are). Here: a stack whose token-axis caches
        are all plain attention's (`_attend_live`); a block-sparse layer
        reads its own selection. Eight tiles, or as many more as keep a
        staged tile within `_STAGED_TILE_BYTES`."""
        if not self.has(ATTENTION) or self.has(SPARSE):
            return None
        per_token = (rows * self.num_kv_heads * self.head_dim
                     * jnp.dtype(self.dtype).itemsize)
        tiles = 8
        while True:
            rungs = context_rungs(max_len, tiles)
            if (rungs[0] * per_token <= _STAGED_TILE_BYTES
                    or len(rungs) < tiles):     # the least tile: no finer
                return rungs
            tiles *= 2


# -- pieces ----------------------------------------------------------------

def _rms(x, scale, eps, dtype):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def _proj(x, w):
    return jnp.einsum("btd,dhk->bthk", x, w)


def block_scores(p, model: HybridLM, nblocks: int):
    """[..., NK] pooled-key scores -> [..., nblocks]: a block's score is
    the largest among the pooled keys that overlap it (absent: 0)."""
    r = model.block_size // model.kernel_stride
    back = model.kernel_size // model.kernel_stride - 1
    need = back + r * nblocks
    pad = [(0, 0)] * (p.ndim - 1) + [(back, max(0, need - back - p.shape[-1]))]
    pp = jnp.pad(p, pad)[..., :need]
    out = pp[..., 0::r][..., :nblocks]
    for o in range(1, r + back):
        out = jnp.maximum(out, pp[..., o::r][..., :nblocks])
    return out


def _pooled_scores(model: HybridLM, q, comp_k, pos):
    """Block scores of the queries ``q`` [B, T, K, G, d] at positions
    ``pos`` [B, T] against the pooled keys ``comp_k`` [B, NK, K, d]:
    softmax over the pooled keys that end at or before the query, summed
    over the group's heads, max over a block's pooled keys. Float32 at
    full precision: the scores decide a discrete choice."""
    d = q.shape[-1]
    nk = comp_k.shape[1]
    ends = (model.kernel_stride * jnp.arange(nk) + model.kernel_size - 1)
    ok = ends[None, None, :] <= pos[:, :, None]                  # [B, T, NK]
    ls = jnp.einsum("btkgd,bjkd->bkgtj", q.astype(jnp.float32), comp_k,
                    precision=_HI) / (d ** 0.5)
    ok = ok[:, None, None]
    ls = jnp.where(ok, ls, -jnp.inf)
    m = jnp.max(ls, axis=-1, keepdims=True)
    e = jnp.where(ok, jnp.exp(ls - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    den = jnp.sum(e, axis=-1, keepdims=True)
    return jnp.sum(e / jnp.where(den > 0, den, 1.0), axis=2)     # [B,K,T,NK]


def _select(model: HybridLM, score, pos):
    """[B, K, T, NB] block scores, [B, T] positions -> bool [B, K, T, NB]:
    the blocks each query attends (`selected_blocks` of the reference)."""
    bs = model.block_size
    nb = score.shape[-1]
    b = jnp.arange(nb)
    t = pos[:, None, :, None]
    mine = t // bs
    w0 = jnp.maximum(t - model.window_size + 1, 0) // bs
    forced = (b < model.init_blocks) | (b >= w0)
    others = ~forced
    k = min(model.topk, nb)
    _vals, best = jax.lax.top_k(jnp.where(others, score, -jnp.inf), k)
    picked = jnp.any(best[..., None] == b, axis=-2) & others
    sparse = (forced | picked) & (b <= mine)
    return jnp.where(t + 1 > model.dense_len, sparse, b <= mine)


def _pool_update(model: HybridLM, comp_k, k_cache, p0, t, valid):
    """Write into ``comp_k`` [B, NK, K, d] the pooled keys whose span ends
    inside [p0, p0 + t) and before ``valid``, from the keys just cached
    (``k_cache`` [B, Tm, K, d]); ``p0`` is [B]."""
    ks, st = model.kernel_size, model.kernel_stride
    nk = comp_k.shape[1]
    n = -(-t // st)
    jlo = jnp.maximum(-((ks - 1 - p0) // st), 0)                 # ceil
    j = jlo[:, None] + jnp.arange(n)[None, :]                    # [B, n]
    end = st * j + ks - 1
    done = (end < p0[:, None] + t) & (end < valid) & (j < nk)
    idx = jnp.clip(st * j[:, :, None] + jnp.arange(ks), 0,
                   k_cache.shape[1] - 1)                         # [B, n, ks]
    rows = jnp.arange(comp_k.shape[0])
    span = k_cache[rows[:, None, None], idx].astype(jnp.float32)
    pooled = jnp.mean(span, axis=2)                              # [B,n,K,d]
    return comp_k.at[rows[:, None], jnp.where(done, j, nk)].set(
        pooled, mode="drop")


def _sparse_decode(model: HybridLM, q, kc, vc, comp_k, pos):
    """One query a row (``q`` [B, 1, K, G, d], ``pos`` [B, 1]): select
    blocks, gather them, attend. Reads the selected blocks only."""
    b, _t, kvh, g, d = q.shape
    bs = model.block_size
    nb = kc.shape[1] // bs
    score = block_scores(_pooled_scores(model, q, comp_k, pos), model, nb)
    sel = _select(model, score, pos)[:, :, 0]                    # [B, K, NB]
    width = min(model.sel_width(), nb)
    if width < nb:
        # the selected blocks first, in order: a stable sort of "not chosen"
        idx = jnp.argsort(~sel, axis=-1, stable=True)[..., :width]
        live = jnp.take_along_axis(sel, idx, axis=-1)
    else:
        idx = jnp.broadcast_to(jnp.arange(nb), sel.shape)
        live = sel
    rows = jnp.arange(b)[:, None, None]

    def gather(cache):
        blocks = cache.reshape(b, nb, bs, kvh, d)[rows, idx]  # [B,K,W,bs,K,d]
        return jnp.stack([blocks[:, h, :, :, h] for h in range(kvh)], 1)

    kb, vb = gather(kc), gather(vc)                          # [B,K,W,bs,d]
    tok = idx[..., None] * bs + jnp.arange(bs)               # [B,K,W,bs]
    mask = live[..., None] & (tok <= pos[:, :, None, None])
    s = jnp.einsum("bkgd,bkwsd->bkgws", q[:, 0].astype(jnp.float32),
                   kb.astype(jnp.float32)) / (d ** 0.5)
    s = jnp.where(mask[:, :, None], s, -jnp.inf)
    w = jax.nn.softmax(s.reshape(b, kvh, g, -1), axis=-1)
    out = jnp.einsum("bkgn,bknd->bkgd", w,
                     vb.reshape(b, kvh, -1, d).astype(jnp.float32))
    return out[:, None]                                      # [B,1,K,G,d]


def _sparse_chunk(model: HybridLM, q, kc, vc, comp_k, pos):
    """A chunk of queries that share their positions (``q`` [B, T, K, G,
    d], ``pos`` [B, T], every row at the same positions): block selection a
    query, then attention over the cache in tiles of keys with a running
    softmax. Only the tiles that hold a key at or before the chunk's last
    position are visited."""
    b, t, kvh, g, d = q.shape
    bs = model.block_size
    tm = kc.shape[1]
    nb = tm // bs
    sel = _select(model, block_scores(
        _pooled_scores(model, q, comp_k, pos), model, nb), pos)  # [B,K,T,NB]
    per = max(m for m in range(1, _KEY_TILE // bs + 1) if nb % m == 0)
    tile = per * bs
    qf = q.astype(jnp.float32)
    last = pos[0, -1]

    def body(i, carry):
        m, l, acc = carry
        k_t = jax.lax.dynamic_slice_in_dim(kc, i * tile, tile, axis=1)
        v_t = jax.lax.dynamic_slice_in_dim(vc, i * tile, tile, axis=1)
        sel_t = jax.lax.dynamic_slice_in_dim(sel, i * per, per, axis=3)
        tok = i * tile + jnp.arange(tile)
        mask = (jnp.repeat(sel_t, bs, axis=-1)
                & (tok[None, None, None, :] <= pos[:, None, :, None]))
        s = jnp.einsum("btkgd,bskd->bkgts", qf,
                       k_t.astype(jnp.float32)) / (d ** 0.5)
        s = jnp.where(mask[:, :, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe[..., None])
        scale = jnp.exp(m - safe)            # 0 while nothing was seen
        l = l * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "bkgts,bskd->bkgtd", p, v_t.astype(jnp.float32))
        return m_new, l, acc

    init = (jnp.full((b, kvh, g, t), -jnp.inf, jnp.float32),
            jnp.zeros((b, kvh, g, t), jnp.float32),
            jnp.zeros((b, kvh, g, t, d), jnp.float32))
    _m, l, acc = jax.lax.fori_loop(0, last // tile + 1, body, init)
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4))               # [B,T,K,G,d]


def _linear_block(q, k, v, m, state, slope):
    """One sub-block of the decayed recurrence: ``q``/``k``/``v`` [B, C, H,
    d] float32, ``m`` [B, C] (1 = a real token), ``state`` [B, H, d, d].
    Padding neither decays the state nor adds to it."""
    c = jnp.cumsum(m, axis=1)                                    # [B, C]
    s = slope[None, :, None]                                     # [1, H, 1]
    dist = c[:, :, None] - c[:, None, :]                         # [B, C, C]
    tri = (jnp.arange(q.shape[1])[:, None] >= jnp.arange(q.shape[1])[None])
    keep = tri[None] & (m[:, None, :] > 0)
    decay = jnp.where(keep[:, None], jnp.exp(
        -slope[None, :, None, None] * jnp.maximum(dist, 0)[:, None]), 0.0)
    a = jnp.einsum("bihd,bjhd->bhij", q, k, precision=_HI) * decay
    intra = jnp.einsum("bhij,bjhd->bihd", a, v, precision=_HI)
    qd = q * jnp.exp(-s * c[:, None, :]).transpose(0, 2, 1)[..., None]
    inter = jnp.einsum("bihd,bhde->bihe", qd, state, precision=_HI)
    total = c[:, -1]                                             # [B]
    kd = k * (m[:, None, :] * jnp.exp(
        -s * (total[:, None, None] - c[:, None, :]))
              ).transpose(0, 2, 1)[..., None]
    state = (state * jnp.exp(-s * total[:, None, None])[..., None]
             + jnp.einsum("bjhd,bjhe->bhde", kd, v, precision=_HI))
    return intra + inter, state


def _cut(x, size: int):
    """[B, T, ...] -> [T / size, B, size, ...], the tail zero-padded."""
    b, t = x.shape[:2]
    n = -(-t // size)
    x = jnp.pad(x, [(0, 0), (0, n * size - t)] + [(0, 0)] * (x.ndim - 2))
    return jnp.moveaxis(x.reshape((b, n, size) + x.shape[2:]), 1, 0)


def _linear_mix(q, k, v, m, state, slope):
    """The recurrence over [B, T, H, d] in sub-blocks of `_LIN_BLOCK`."""
    b, t, h, d = q.shape
    if t <= _LIN_BLOCK:
        return _linear_block(q, k, v, m, state, slope)

    def body(state, xs):
        o, state = _linear_block(*xs, state, slope)
        return state, o

    state, o = jax.lax.scan(
        body, state, tuple(_cut(x, _LIN_BLOCK) for x in (q, k, v, m)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, -1, h, d)
    return o[:, :t], state


# -- the selective scan (mamba2) ---------------------------------------------

def _ssd_block(xs, dt, a, bm, cm, state):
    """One chunk of `S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`, `y_t =
    S_t C_t`, in its chunked (quadratic inside, recurrent across) form.
    ``xs`` [B, L, G, E, P], ``dt`` [B, L, G, E] (0 at a padded position:
    it neither decays the state nor adds to it), ``a`` [G, E] (negative),
    ``bm``/``cm`` [B, L, G, N], ``state`` [B, G, E, P, N]; float32."""
    cum = jnp.cumsum(dt * a, axis=1)                             # [B,L,G,E]
    n = xs.shape[1]
    tri = (jnp.arange(n)[:, None] >= jnp.arange(n)[None, :])[None, :, :,
                                                              None, None]
    seg = cum[:, :, None] - cum[:, None, :]                  # [B,i,j,G,E]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    scores = jnp.einsum("bign,bjgn->bijg", cm, bm, precision=_HI)
    w = scores[..., None] * decay * dt[:, None]
    y = jnp.einsum("bijge,bjgep->bigep", w, xs, precision=_HI)
    y = y + jnp.einsum("bign,bgepn->bigep", cm, state, precision=_HI
                       ) * jnp.exp(cum)[..., None]
    last = cum[:, -1]                                            # [B,G,E]
    into = (jnp.exp(last[:, None] - cum) * dt)[..., None] * xs
    state = (state * jnp.exp(last)[..., None, None]
             + jnp.einsum("bjgep,bjgn->bgepn", into, bm, precision=_HI))
    return y, state


def _ssd(xs, dt, a, bm, cm, state, chunk: int):
    """The scan over [B, T, ...]: one update for a single token, else
    chunks of ``chunk`` tokens (the tail padded with dt = 0)."""
    b, t = xs.shape[:2]
    if t == 1:
        # elementwise, so that the state is read and written once
        state = (state * jnp.exp(dt[:, 0] * a)[..., None, None]
                 + (dt[:, 0, ..., None] * xs[:, 0])[..., None]
                 * bm[:, 0, :, None, None, :])
        y = jnp.sum(state * cm[:, 0, :, None, None, :], axis=-1)
        return y[:, None], state
    if t <= chunk:
        return _ssd_block(xs, dt, a, bm, cm, state)

    def body(state, part):
        y, state = _ssd_block(*part[:2], a, *part[2:], state)
        return state, y

    state, y = jax.lax.scan(
        body, state, tuple(_cut(x, chunk) for x in (xs, dt, bm, cm)))
    y = jnp.moveaxis(y, 0, 1).reshape((b, -1) + y.shape[3:])
    return y[:, :t], state


def _gated_norm(model: HybridLM, y, scale):
    """RMSNorm of the gated scan output ``y`` [B, T, d_inner], float32: over
    all of it, or, with several `ssm_groups`, over each group's channels
    (a group's heads are consecutive)."""
    g = model.ssm_groups
    if g == 1:
        return _rms(y, scale, model.eps, model.dtype)
    grouped = y.shape[:-1] + (g, y.shape[-1] // g)
    return _rms(y.reshape(grouped), scale.reshape(grouped[-2:]), model.eps,
                model.dtype).reshape(y.shape)


def _ssd_live(states, i, live, xs, dt, a, bm, cm, interpret: bool):
    """`_ssd`'s single-token step for the live rows of layer ``i`` of the
    run's stacked ``states`` [L, B, H, P, N], in place (`ops/ssd_decode`):
    (y [B, 1, G, E, P], states)."""
    b, _t, g, e, pd = xs.shape
    h = g * e
    states, y = ssd_decode(
        states, i, live, dt.reshape(b, h), xs.reshape(b, h, pd), bm[:, 0],
        cm[:, 0], a.reshape(h), interpret=interpret)
    return y.reshape(b, 1, g, e, pd), states


def _mamba_mix(model: HybridLM, p, c, held, i, hn, mask, live=None):
    """The state-space mixer of layer ``i`` over the normed ``hn``; the
    run's stacked float32 states (``held``) ride through the scan as
    carry (updated where they lie; as the scan's ``xs``/``ys`` every
    layer's state was copied out and back a step: 5.5 ms of a 28 ms step at
    granite-4.0-h-small's widths). ``mask`` [B, T]: the real tokens (a
    prefix of a row, or none of it). What it leaves out enters neither the
    window nor the state. A per-row decode step handed ``live`` [B] moves
    the live rows' states alone (`_ssd_live`); every other shape (prefill,
    a chunk, `engine.generate`'s step, the oracle the pool's streams are
    held to) runs `_ssd` over the layer's whole slice. Where ``c`` holds
    `state_rows` the layer adds the rows whose state it moved and those
    the slots hold."""
    b, t, _ = hn.shape
    h, pd, n, g = (model.ssm_heads, model.ssm_head_dim, model.ssm_state,
                   model.ssm_groups)
    di, width, taps = h * pd, model.ssm_conv_width, model.ssm_conv
    proj = hn @ p["w_in"]
    if model.ssm_mults:
        # one multiplier a segment of the in-projection's output
        proj = proj * jnp.asarray(np.repeat(
            np.asarray(model.ssm_mults, np.float32),
            (di, di, g * n, g * n, h)), proj.dtype)
    z, xc, dt = jnp.split(proj, (di, di + width), axis=-1)
    seq = jnp.concatenate([c["conv"], xc.astype(c["conv"].dtype)], axis=1)
    conv = p["conv_b"].astype(jnp.float32) + sum(
        seq[:, j:j + t].astype(jnp.float32)
        * p["conv_w"][j].astype(jnp.float32) for j in range(taps))
    conv = jax.nn.silu(conv)
    # the window the next token sees: the last inputs before the padding
    kept = jnp.sum(mask, axis=1)                                 # [B]
    window = jax.vmap(lambda s, k: jax.lax.dynamic_slice_in_dim(
        s, k, taps - 1, axis=0))(seq, kept)
    xs = conv[..., :di].reshape(b, t, g, h // g, pd)
    bm = conv[..., di:di + g * n].reshape(b, t, g, n)
    cm = conv[..., di + g * n:].reshape(b, t, g, n)
    dt = jnp.where(mask[..., None], jax.nn.softplus(
        dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)), 0.0)
    a = -jnp.exp(p["A_log"].astype(jnp.float32)).reshape(g, h // g)
    states = held["state"]
    new = {"conv": window}
    if live is not None and t == 1:
        y, states = _ssd_live(states, i, live, xs,
                              dt.reshape(b, t, g, h // g), a, bm, cm,
                              model.interpret)
        moved = jnp.sum(live, dtype=jnp.int32)
    else:
        state = jax.lax.dynamic_index_in_dim(
            states, i, 0, keepdims=False).reshape(b, g, h // g, pd, n)
        y, state = _ssd(xs, dt.reshape(b, t, g, h // g), a, bm, cm, state,
                        model.ssm_chunk)
        states = jax.lax.dynamic_update_index_in_dim(
            states, state.reshape(states.shape[1:]), i, 0)
        moved = jnp.int32(b)
    if "state_rows" in c:
        new["state_rows"] = c["state_rows"] + jnp.stack([moved,
                                                         jnp.int32(b)])
    y = y + p["D"].astype(jnp.float32).reshape(g, h // g)[..., None] * xs
    y = y.reshape(b, t, di) * jax.nn.silu(z.astype(jnp.float32))
    out = _gated_norm(model, y, p["norm"]) @ p["w_out"]
    return out, {"state": states}, new


def _mamba_layer(model: HybridLM, p, c, held, i, x, mask, live):
    """Layer ``i`` of a state-space run: the mixer over its own norm."""
    hn = _rms(x, p["ln1"], model.eps, model.dtype)
    return _mamba_mix(model, p, c, held, i, hn, mask, live)


# -- the delta rule (gated-deltanet) ------------------------------------------

def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + _L2_EPS)


def _delta_step(state, q, k, v, g, beta):
    """One token of the gated delta rule: ``state`` [..., H, dk, dv], ``q``
    and ``k`` [..., H, dk], ``v`` [..., H, dv], ``g`` and ``beta`` [..., H],
    float32. The state decays, is read along k, takes back the correction
    `beta (v - S^T k)` along k, and is read out along q: (state, o [...,
    H, dv])."""
    state = state * jnp.exp(g)[..., None, None]
    err = v - jnp.sum(state * k[..., :, None], axis=-2)
    state = state + k[..., :, None] * (beta[..., None] * err)[..., None, :]
    return state, jnp.sum(state * q[..., :, None], axis=-2)


def _unit_lower_inverse(a):
    """`(I - a)^-1` of every strictly lower ``a`` [..., C, C] (zero on and
    above the diagonal), by blocks of `_TRI_BLOCK` rows (the whole C where
    the block does not divide it): the diagonal blocks by forward
    substitution, a row a trip over all of them at once, then block row i
    from those above it, `T_ij = T_ii sum_{j<=m<i} a_im T_mj`. The matrices
    ride on the LAST axis, so a row of 16 is a row of whole vectors, not 16
    of a vector's 128 lanes, and the products between blocks are float32
    sums of elementwise products (as batched 16 x 16 matrix products the
    inverse took a TPU v5e 25 times as long)."""
    size = a.shape[-1]
    bs = min(_TRI_BLOCK, size)
    bs = bs if size % bs == 0 else size
    m = int(np.prod(a.shape[:-2]))
    x = jnp.moveaxis(a.reshape((m, size, size)), 0, -1)       # [C, C, M]
    diag = jnp.concatenate([x[o:o + bs, o:o + bs]
                            for o in range(0, size, bs)], -1)  # [b, b, nb M]

    def substitute(i, d):
        # row i of the block's inverse from the rows above it: `d` is zero
        # on and above the diagonal, so the sum reaches the finished rows
        r = jax.lax.dynamic_index_in_dim(d, i, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            d, r + jnp.sum(r[:, None] * d, axis=0), i, 0)

    def times(p, q):                       # [i, k, M] by [k, j, M]
        return jnp.sum(p[:, :, None] * q[None], axis=1)

    diag = (jax.lax.fori_loop(1, bs, substitute, diag)
            + jnp.eye(bs, dtype=a.dtype)[..., None])
    inv = diag[..., :m]
    for o in range(bs, size, bs):
        tii = diag[..., o // bs * m:(o // bs + 1) * m]
        row = times(tii, times(x[o:o + bs, :o], inv))
        inv = jnp.concatenate([
            jnp.pad(inv, ((0, 0), (0, bs), (0, 0))),
            jnp.concatenate([row, tii], 1)], 0)
    return jnp.moveaxis(inv, -1, 0).reshape(a.shape)


def _delta_chunks(q, k, v, g, beta, state, size: int):
    """The delta rule over [B, T, H, ...] in its chunked form, chunks of
    ``size`` tokens (the tail padded with g = 0 and beta = 0: it neither
    decays the state nor adds to it). Inside a chunk, with `c` the
    cumulative g and `D_ij = exp(c_i - c_j)` (i >= j): `T = (I + tril(diag(
    beta) K K^T * D, -1))^-1`, by blocks (`_unit_lower_inverse`: forward
    substitution inside the 16 x 16 diagonal blocks of every chunk at once,
    products between them); `W = T (beta K exp(c))`, `U = T (beta V)`. Then one
    scan over the chunks: `V' = U - W S`, `O = exp(c) Q S + tril(Q K^T *
    D) V'`, `S = exp(c_last) S + (exp(c_last - c) K)^T V'`. Float32, the
    products at full precision: (o [B, T, H, dv], state)."""
    b, t, h, _dk = q.shape
    q, k, v, g, beta = (_cut(x, size) for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=2)                                # [N, B, C, H]
    ch = jnp.moveaxis(cum, -1, 2)                              # [N, B, H, C]
    tri = jnp.arange(size)[:, None] >= jnp.arange(size)[None, :]
    below = jnp.arange(size)[:, None] > jnp.arange(size)[None, :]
    decay = jnp.where(tri, jnp.exp(jnp.where(
        tri, ch[..., :, None] - ch[..., None, :], 0.0)), 0.0)  # [N,B,H,C,C]
    kk = jnp.einsum("nbihd,nbjhd->nbhij", k, k, precision=_HI)
    inv = _unit_lower_inverse(jnp.where(
        below, -jnp.moveaxis(beta, -1, 2)[..., None] * kk * decay, 0.0))
    w = jnp.einsum("nbhij,nbjhd->nbihd", inv,
                   k * (beta * jnp.exp(cum))[..., None], precision=_HI)
    u = jnp.einsum("nbhij,nbjhe->nbihe", inv, v * beta[..., None],
                   precision=_HI)
    qk = jnp.einsum("nbihd,nbjhd->nbhij", q, k, precision=_HI) * decay
    last = cum[:, :, -1]                                       # [N, B, H]
    kd = k * jnp.exp(last[:, :, None] - cum)[..., None]

    def chunk(state, xs):
        w, u, qk, qc, cum, kd, last = xs
        fresh = u - jnp.einsum("bihd,bhde->bihe", w, state, precision=_HI)
        o = (jnp.einsum("bihd,bhde->bihe", qc * jnp.exp(cum)[..., None],
                        state, precision=_HI)
             + jnp.einsum("bhij,bjhe->bihe", qk, fresh, precision=_HI))
        state = (state * jnp.exp(last)[..., None, None]
                 + jnp.einsum("bjhd,bjhe->bhde", kd, fresh, precision=_HI))
        return state, o

    state, o = jax.lax.scan(chunk, state, (w, u, qk, q, cum, kd, last))
    o = jnp.moveaxis(o, 0, 1).reshape((b, -1) + o.shape[3:])
    return o[:, :t], state


def _delta_live(states, i, live, q, k, v, g, beta):
    """`_delta_step` for the live rows of layer ``i`` of the run's stacked
    ``states`` [L, B, H, dk, dv], one row at a time where it lies: a dead
    row's state is neither read nor written. ``q`` .. ``beta`` [B, H, ...]
    as `_delta_step`'s. (states, o [B, H, dv]), o zero on a dead row."""
    ids = jnp.argsort(~live, stable=True)
    shape = (1, 1) + states.shape[2:]

    def row(j, carry):
        states, out = carry
        s = ids[j]
        at = (i, s, 0, 0, 0)
        state, o = _delta_step(
            jax.lax.dynamic_slice(states, at, shape)[0, 0],
            *(jax.lax.dynamic_index_in_dim(x, s, 0, keepdims=False)
              for x in (q, k, v, g, beta)))
        return (jax.lax.dynamic_update_slice(states, state[None, None], at),
                jax.lax.dynamic_update_index_in_dim(out, o, s, 0))

    return jax.lax.fori_loop(
        0, jnp.sum(live, dtype=jnp.int32), row,
        (states, jnp.zeros(v.shape, jnp.float32)))


def _delta_mix(model: HybridLM, p, c, held, i, hn, mask, live=None):
    """The gated-deltanet mixer of layer ``i`` over the normed ``hn``
    (Qwen3-Next's `linear_attention`). `[q k v z] = hn W_qkvz` and `[b a] =
    hn W_ba`, each grouped by key head as published (a key head's `[q dk |
    k dk | v r dv | z r dv]` and `[b r | a r]`, r value heads a key head);
    a causal depthwise convolution over `[q | k | v]` (`ssm_conv` taps, no
    bias) whose last inputs are the row's `conv` window, SiLU; `q / sqrt(dk)`
    and `k` l2-normed, a value head reading its key head; `beta =
    sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)`; the delta rule
    over a float32 `state` [H, dk, dv] a row (`_delta_chunks` over a
    prefill, `_delta_step` a decode step); RMSNorm of each head's output
    times `norm`, times silu(z); the out-projection. The run's stacked
    states ride through its scan as carry, as `_mamba_mix`'s. What ``mask``
    leaves out enters neither the window nor the state; a per-row decode
    step handed ``live`` moves the live rows' states alone (`_delta_live`),
    and where ``c`` holds `state_rows` the layer adds the rows whose state
    it moved and those the slots hold, as `_mamba_mix` does."""
    b, t, _ = hn.shape
    kh, vh = model.delta_key_heads, model.delta_value_heads
    dk, dv, taps = model.delta_key_dim, model.delta_value_dim, model.ssm_conv
    r = vh // kh
    f32 = jnp.float32
    q, k, v, z = jnp.split(_proj(hn, p["w_qkvz"]),
                           (dk, 2 * dk, 2 * dk + r * dv), axis=-1)
    ba = _proj(hn, p["w_ba"]).astype(f32)                     # [B,T,KH,2r]
    xc = jnp.concatenate([x.reshape(b, t, -1) for x in (q, k, v)], axis=-1)
    seq = jnp.concatenate([c["conv"], xc.astype(c["conv"].dtype)], axis=1)
    conv = jax.nn.silu(sum(
        seq[:, j:j + t].astype(f32) * p["conv_w"][j].astype(f32)
        for j in range(taps)))
    # the window the next token sees: the last inputs before the padding
    kept = jnp.sum(mask, axis=1)
    window = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(
        s, n, taps - 1, axis=0))(seq, kept)
    q, k, v = jnp.split(conv, (kh * dk, 2 * kh * dk), axis=-1)
    q = jnp.repeat(_l2norm(q.reshape(b, t, kh, dk)) * dk ** -0.5, r, axis=2)
    k = jnp.repeat(_l2norm(k.reshape(b, t, kh, dk)), r, axis=2)
    v = v.reshape(b, t, vh, dv)
    keep = mask[..., None]
    beta = jnp.where(keep, jax.nn.sigmoid(ba[..., :r].reshape(b, t, vh)),
                     0.0)
    g = jnp.where(keep, -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        ba[..., r:].reshape(b, t, vh) + p["dt_bias"].astype(f32)), 0.0)
    states = held["state"]
    new = {"conv": window}
    if live is not None and t == 1:
        states, o = _delta_live(states, i, live, q[:, 0], k[:, 0], v[:, 0],
                                g[:, 0], beta[:, 0])
        o = o[:, None]
        moved = jnp.sum(live, dtype=jnp.int32)
    else:
        state = jax.lax.dynamic_index_in_dim(states, i, 0, keepdims=False)
        if t == 1:
            state, o = _delta_step(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                   beta[:, 0])
            o = o[:, None]
        else:
            o, state = _delta_chunks(q, k, v, g, beta, state,
                                     _DELTA_CHUNK)
        states = jax.lax.dynamic_update_index_in_dim(states, state, i, 0)
        moved = jnp.int32(b)
    if "state_rows" in c:
        new["state_rows"] = c["state_rows"] + jnp.stack([moved,
                                                         jnp.int32(b)])
    o = _rms(o, p["norm"], model.eps, f32) * jax.nn.silu(
        z.reshape(b, t, vh, dv).astype(f32))
    out = jnp.einsum("bthk,hkd->btd", o.astype(model.dtype), p["w_out"])
    return out, {"state": states}, new


def _delta_layer(model: HybridLM, p, c, held, i, x, mask, live):
    """Layer ``i`` of a gated-deltanet run: the mixer over its own norm."""
    hn = _rms(x, p["ln1"], model.eps, model.dtype)
    return _delta_mix(model, p, c, held, i, hn, mask, live)


# -- plain attention ---------------------------------------------------------

def _attend(q, kc, vc, pos, scale):
    """``q`` [B, T, K, G, d] at positions ``pos`` [B, T] over the cache
    [B, S, K, d]: causal softmax attention, float32 scores."""
    s = jnp.einsum("btkgd,bskd->bkgts", q, kc,
                   preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(kc.shape[1])[None, None, :] <= pos[:, :, None]
    w = jax.nn.softmax(jnp.where(seen[:, None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bkgts,bskd->btkgd", w.astype(vc.dtype), vc)


def _attend_live(q, kc, vc, i, pos, scale, rungs):
    """`_attend` for rows that sit each at its own depth (``q`` [B, T, K,
    G, d], ``pos`` [B, T]), over layer ``i`` of the run's carried stacks
    ``kc``/``vc`` [L, B, S, K, d] read where they lie: the first of
    ``rungs`` (`context_rungs`) that holds the deepest row's new tokens, a
    tile at a time with a running softmax, as
    `MultiHeadAttention._decode_step` reads its cache. A masked position
    weighs exactly 0, so the tiles left unread change nothing, and the
    sum's order differs from `_attend`'s one softmax by float rounding.
    The cursors it is handed set the bound: the caller hands a dead row 0
    (`engine.serve_lm._build_decode`). A loop, not a `lax.switch` over
    the rungs: the compiler copied the whole leaf into every branch
    (PERF.md section 6, PR 32)."""
    b, t, kvh, g, d = q.shape
    tile, top = rungs[0], rungs[-1]

    def one_tile(j, carry):
        m, l, acc = carry
        # the last rung may be no whole tile: its slice starts early, and
        # leaves what the tile before it covered
        lo = jnp.minimum(j * tile, top - tile)
        k_t, v_t = (jax.lax.squeeze(jax.lax.dynamic_slice(
            leaf, (i, 0, lo, 0, 0), (1, b, tile, kvh, d)), (0,))
            for leaf in (kc, vc))
        s = jnp.einsum("btkgd,bskd->bkgts", q, k_t,
                       preferred_element_type=jnp.float32) * scale
        at = lo + jnp.arange(tile)
        seen = (at[None, None, :] <= pos[:, :, None]) & (at >= j * tile)
        s = jnp.where(seen[:, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a row with nothing live so far has m_new -inf
        base = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.exp(s - base[..., None])
        keep = jnp.exp(m - base)
        return (m_new, l * keep + jnp.sum(p, axis=-1),
                acc * keep[..., None]
                + jnp.einsum("bkgts,bskd->bkgtd", p.astype(v_t.dtype), v_t,
                             preferred_element_type=jnp.float32))

    stat = (b, kvh, g, t)
    _m, l, acc = jax.lax.fori_loop(
        0, jnp.sum(jnp.max(pos[:, 0]) + t > jnp.asarray(rungs[:-1])) + 1,
        one_tile, (jnp.full(stat, -jnp.inf, jnp.float32),
                   jnp.zeros(stat, jnp.float32),
                   jnp.zeros(stat + (d,), jnp.float32)))
    return jnp.transpose(acc / l[..., None], (0, 3, 1, 2, 4)).astype(vc.dtype)


def _attention_mix(model: HybridLM, p, kv, i, hn, pos):
    """Attention of layer ``i`` over the normed ``hn`` (with the stack's
    q/k norm, partial rotary positions and output gate where it sets
    them); the run's
    stacked K/V ``kv`` ride through the scan as carry: the new tokens' rows
    are written where they lie, and the layer's slice is read from there
    (as `scanned_apply` does). The per-row (decode) shape reads the live
    context (`_attend_live`) where the cache has more than one rung; the
    scalar-cursor shapes (prefill, a chunk, `engine.generate`'s step, the
    oracle the pool's streams are held to) read the whole axis: a prefill
    cache is as long as its bucket."""
    b, t, _ = hn.shape
    kvh, d = model.num_kv_heads, model.head_dim

    def turned(x):
        if not model.attn_rope:
            return x
        rd = model.rotary_dims
        if rd and rd < d:
            # rotary positions over the head's first `rotary_dims` channels
            return jnp.concatenate([rope(
                x[..., :rd], base=model.rope_theta,
                positions=pos.astype(jnp.float32)), x[..., rd:]], axis=-1)
        return rope(x, base=model.rope_theta,
                    positions=pos.astype(jnp.float32))

    def normed(x, scale):
        if not model.attn_qk_norm:
            return x
        return _rms(x, p[scale], model.eps, model.dtype)

    q = _proj(hn, p["wq"])
    if model.attn_gate:
        q, gate = q[..., :d], q[..., d:]
    q = turned(normed(q, "qn")).reshape(
        b, t, kvh, model.num_heads // kvh, d)
    p0 = pos[:, 0]
    k = _proj(hn, p["wk"])
    if model.key_mult != 1.0:
        k = k * model.key_mult
    k = turned(normed(k, "kn"))
    kv = {"cached_k": _write_kv(kv["cached_k"], k, p0,
                                model.decode_per_row, layer=i),
          "cached_v": _write_kv(kv["cached_v"], _proj(hn, p["wv"]), p0,
                                model.decode_per_row, layer=i)}
    scale = d ** -0.5 if model.attn_scale is None else model.attn_scale
    rungs = (model.decode_context_rungs(model.max_decode_len, b)
             if model.decode_per_row else None)
    if rungs is not None and len(rungs) > 1:
        o = _attend_live(q, kv["cached_k"], kv["cached_v"], i, pos, scale,
                         rungs)
    else:
        kc, vc = (jax.lax.dynamic_index_in_dim(kv[k], i, 0, keepdims=False)
                  for k in ("cached_k", "cached_v"))
        if t > _QUERY_TILE:
            # a long chunk a tile of queries at a time: the float32 scores
            # of 2048 queries over 4096 keys would be a gigabyte
            o = jax.lax.map(lambda qp: _attend(qp[0], kc, vc, qp[1], scale),
                            (_cut(q, _QUERY_TILE), _cut(pos, _QUERY_TILE)))
            o = jnp.moveaxis(o, 0, 1).reshape((b, -1) + q.shape[2:])[:, :t]
        else:
            o = _attend(q, kc, vc, pos, scale)
    o = o.reshape(b, t, model.num_heads, d)
    if model.attn_gate:
        o = (o * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)
    out = jnp.einsum("bthk,hkd->btd", o, p["wo"])
    return out, kv


def _attention_layer(model: HybridLM, p, kv, i, x, pos):
    """Layer ``i`` of a plain attention run: the mixer over its own norm."""
    hn = _rms(x, p["ln1"], model.eps, model.dtype)
    return (*_attention_mix(model, p, kv, i, hn, pos), {})


def _parallel_layer(model: HybridLM, p, c, held, i, x, pos, mask, live):
    """Layer ``i`` of a run whose layers hold BOTH mixers: each reads the
    one normed input times its `*_in_mult`, and their out-projections,
    times `*_out_mult`, are summed. ``held`` carries the run's K/V and
    its states alike."""
    hn = _rms(x, p["ln1"], model.eps, model.dtype)
    ssm, state, new = _mamba_mix(model, p, c, held, i,
                                 hn * model.ssm_in_mult, mask, live)
    att, kv = _attention_mix(model, p, held, i, hn * model.attn_in_mult,
                             pos)
    mix = ssm * model.ssm_out_mult + att * model.attn_out_mult
    return mix, {**kv, **state}, new


# -- the stack -------------------------------------------------------------

def reads_every_held_expert(model: HybridLM, tokens: int) -> bool:
    """Whether an expert layer over ``tokens`` tokens takes
    `routed_experts`' all-experts branch, which reads every held expert's
    weights: few tokens (`_GROUP_FROM`), and enough of them that each held
    expert expects a pick. Otherwise tokens are grouped by expert and the
    touched experts alone are read."""
    return (tokens < _GROUP_FROM
            and tokens * model.experts_per_token >= model.experts)


def _ffn(model: HybridLM, p, experts, i, c, x, mask):
    """The layer's feed-forward over the normed ``x``, and what it counted
    (the leaves of ``c`` it updates: an expert stack's, in a decode
    cache). ``experts``: the run's routed experts, stacked, of which this
    is layer ``i`` (`_WHOLE`)."""
    hn = _rms(x, p["ln2"], model.eps, model.dtype)
    if model.ffn == DENSE:
        gate_mult, out_mult = model.mlp_mults
        gate = hn @ p["wg"]
        if gate_mult != 1.0:
            gate = gate * gate_mult
        out = (jax.nn.silu(gate) * (hn @ p["wu"])) @ p["wd"]
        return (out if out_mult == 1.0 else out * out_mult), {}
    b, t, d = hn.shape
    y, load = routed_experts(
        hn.reshape(b * t, d), p["router"], experts["w1"], experts["w2"],
        top_k=model.experts_per_token, experts_held=model.experts_held,
        mask=mask.reshape(-1), dense=reads_every_held_expert(model, b * t),
        layer=i)
    up = hn @ p["ws1"]
    shared = (jax.nn.silu(up[..., :model.shared_dim])
              * up[..., model.shared_dim:]) @ p["ws2"]
    if model.shared_gate:
        shared = (shared * jax.nn.sigmoid(
            (hn @ p["wsg"]).astype(jnp.float32))).astype(shared.dtype)
    counted = {}
    if "expert_load" in c:
        any_live = jnp.any(mask).astype(jnp.int32)
        counted = {
            "expert_load": c["expert_load"] + load,
            "expert_steps": c["expert_steps"] + jnp.stack(
                [jnp.sum(load > 0, dtype=jnp.int32), any_live,
                 jnp.sum(mask, dtype=jnp.int32)])}
    return y.reshape(b, t, d) + shared, counted


def _write_kv(cache_leaf, new, p0, per_row: bool, layer=None):
    """Cache ``new`` [B, T, K, d] at each row's positions p0.. ; with
    ``layer`` the leaf is a run's stack [L, B, Tm, K, d] and the rows of
    that layer are written where they lie."""
    new = new.astype(cache_leaf.dtype)
    at = () if layer is None else (layer,)
    if per_row:
        rows = jnp.arange(new.shape[0])[:, None]
        slot = jnp.clip(p0[:, None] + jnp.arange(new.shape[1])[None, :], 0,
                        cache_leaf.shape[-3] - 1)
        return cache_leaf.at[at + (rows, slot)].set(new)
    return jax.lax.dynamic_update_slice(
        cache_leaf, new[(None,) * len(at)], at + (0, p0[0], 0, 0))


def _qkv_gate(model: HybridLM, p, x):
    """Both mixers' way in: normed q and k, v, the float32 output gate."""
    hn = _rms(x, p["ln1"], model.eps, model.dtype)
    q = _rms(_proj(hn, p["wq"]), p["qn"], model.eps, model.dtype)
    k = _rms(_proj(hn, p["wk"]), p["kn"], model.eps, model.dtype)
    gate = jax.nn.sigmoid(_proj(hn, p["wz"]).astype(jnp.float32))
    return q, k, _proj(hn, p["wv"]), gate


def _sparse_layer(model: HybridLM, p, c, x, pos, valid):
    b, t, _ = x.shape
    kvh, g = model.num_kv_heads, model.num_heads // model.num_kv_heads
    q, k, v, gate = _qkv_gate(model, p, x)
    p0 = pos[:, 0]
    kc = _write_kv(c["cached_k"], k, p0, model.decode_per_row)
    vc = _write_kv(c["cached_v"], v, p0, model.decode_per_row)
    comp_k = _pool_update(model, c["comp_k"], kc, p0, t, valid)
    q5 = q.reshape(b, t, kvh, g, model.head_dim)
    if t == 1:
        o = _sparse_decode(model, q5, kc, vc, comp_k, pos)
    elif model.decode_per_row:
        raise UnsupportedStack(
            "a block-sparse layer takes one token a row a step, or a chunk "
            "of one row")
    else:
        o = _sparse_chunk(model, q5, kc, vc, comp_k, pos)
    o = (o.reshape(b, t, model.num_heads, model.head_dim) * gate
         ).astype(model.dtype)
    out = jnp.einsum("bthk,hkd->btd", o, p["wo"])
    return out, {"cached_k": kc, "cached_v": vc, "comp_k": comp_k}


def _linear_layer(model: HybridLM, p, c, slope, x, pos, valid):
    d = model.lightning_head_dim
    q, k, v, gate = _qkv_gate(model, p, x)
    posf = pos.astype(jnp.float32)
    q = rope(q, base=model.rope_theta, positions=posf).astype(jnp.float32)
    k = rope(k, base=model.rope_theta, positions=posf).astype(jnp.float32)
    m = (pos < valid).astype(jnp.float32)
    o, state = _linear_mix(q, k, v.astype(jnp.float32), m, c["state"], slope)
    o = _rms(o / (d ** 0.5), p["on"], model.eps, jnp.float32) * gate
    out = jnp.einsum("bthk,hkd->btd", o.astype(model.dtype), p["wo"])
    return out, {"state": state}


def hybrid_apply(model: HybridLM, params, cache, tokens, paged=None):
    """One decode or prefill step: (float32 logits [B, T, vocab], new
    cache), the contract of `models.transformer.decode_apply`."""
    if paged is not None:
        raise UnsupportedStack("a hybrid stack has no paged attention path")
    if not model.decode:
        raise ValueError("HybridLM runs in decode mode only "
                         "(engine.generate.decode_model)")
    b, t = tokens.shape
    if model.decode_per_row:
        p0 = cache["cursors"]
        valid = jnp.int32(_NO_LIMIT)
    else:
        p0 = jnp.broadcast_to(cache["cursor"], (b,))
        valid = cache["valid"]
    pos = p0[:, None] + jnp.arange(t)[None, :]
    # the real tokens: before `valid` in a prefill, a live row's in decode
    live = cache.get("live") if model.decode_per_row else None
    mask = (pos < valid if live is None
            else jnp.broadcast_to(live[:, None], pos.shape))
    a = model.residual_scale
    x = (params["embed"][tokens] * model.scale_emb).astype(model.dtype)
    new_cache = dict(cache)
    for r, (kind, ids) in enumerate(model.runs()):
        # a plain attention run's K/V and a state-space run's states (a
        # run of both mixers: both) are the scan's carry (written in
        # place); every other leaf is sliced a layer and written back
        c_r, p_r = cache[f"run{r}"], params["runs"][r]
        held = {k: c_r[k] for k in _CARRIED.get(kind, ())}
        rest = {k: v for k, v in c_r.items() if k not in held}
        experts = {k: p_r[k] for k in _WHOLE if k in p_r}

        def body(carry, layer, kind=kind):
            h, held = carry
            i, p_l, c_l, slope = layer
            if kind == SPARSE:
                mix, new = _sparse_layer(model, p_l, c_l, h, pos, valid)
            elif kind == LINEAR:
                mix, new = _linear_layer(model, p_l, c_l, slope, h, pos,
                                         valid)
            elif kind == MAMBA:
                mix, held, new = _mamba_layer(model, p_l, c_l, held, i, h,
                                              mask, live)
            elif kind == PARALLEL:
                mix, held, new = _parallel_layer(model, p_l, c_l, held, i,
                                                 h, pos, mask, live)
            elif kind == DELTA:
                mix, held, new = _delta_layer(model, p_l, c_l, held, i, h,
                                              mask, live)
            else:
                mix, held, new = _attention_layer(model, p_l, held, i, h,
                                                  pos)
            h = h + (a * mix).astype(model.dtype)
            out, counted = _ffn(model, p_l, experts, i, c_l, h, mask)
            h = h + (a * out).astype(model.dtype)
            return (h, held), {**new, **counted}

        slopes = (jnp.asarray(np.stack([model.slopes(i) for i in ids]))
                  if kind == LINEAR else None)
        (x, held), rest = jax.lax.scan(
            body, (x, held),
            (jnp.arange(len(ids)),
             {k: v for k, v in p_r.items() if k not in experts}, rest,
             slopes))
        new_cache[f"run{r}"] = {**held, **rest}
    if not model.decode_per_row:
        new_cache["cursor"] = cache["cursor"] + t
    if model.last_logits and not model.decode_per_row:
        # a prefill: the head over the last real position alone (the
        # chunk's last where the prompt goes on)
        x = jax.lax.dynamic_slice_in_dim(
            x, jnp.clip(valid - 1 - p0[0], 0, t - 1), 1, axis=1)
    hn = _rms(x, params["norm_f"], model.eps, jnp.float32) / model.logit_div
    if "head" in params:
        logits = hn.astype(model.dtype) @ params["head"]
    else:       # tied: the embedding is the head, the logits float32
        logits = jnp.einsum("btd,vd->btv", hn.astype(model.dtype),
                            params["embed"],
                            preferred_element_type=jnp.float32)
    return logits.astype(jnp.float32), new_cache
