"""Continuous batching for LM serving (JetStream/vLLM-style, TPU-first).

`engine.generate` serves one fixed batch start-to-finish: every sequence
waits for the slowest, and a new prompt waits for the whole batch. Real
serving is a STREAM of requests with ragged arrival and length; the standard
fix is continuous batching — a fixed pool of decode slots where finished
sequences retire immediately and queued prompts are admitted into the freed
rows while the other rows keep decoding.

TPU-first structure (everything static-shape, three compiled programs):

  prefill  — the whole prompt in ONE chunked-decode apply (`transformer.
             MultiHeadAttention._decode_step`, scalar-cursor t>1 branch):
             prompt K/V written into a length-P cache, logits out, first
             generated token picked at the row's true length.
  insert   — the prefilled cache rows + prompt tokens spliced into slot r
             of the live [S, L] decode state (pure gather/scatter).
  decode   — ONE token for ALL S slots per dispatch via the per-row-cursor
             cache (`decode_per_row=True`): each row attends its own depth;
             retired rows idle harmlessly (their writes are idempotent and
             gated out). ``decode_steps>1`` fuses N tokens into one
             dispatch with a `lax.fori_loop` (fewer host round-trips; the
             trade is admission only happens at dispatch boundaries).

The reference serves nothing autoregressive at all; this is the
beyond-parity serving tier over the same engine/model machinery
(`alexnet_resnet.py:12-92` is its entire model layer).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from idunno_tpu.engine.generate import decode_model, init_cache
from idunno_tpu.engine.kv_blocks import (KV_LEAF_KEYS, SLOT_LEAF_KEYS,
                                         concat_kv_prefix)
from idunno_tpu.models.hybrid import (SPARSE, UnsupportedStack,
                                      reads_every_held_expert)
from idunno_tpu.models.transformer import (TransformerLM, decode_apply,
                                           scan_compatible,
                                           stack_block_params)
from idunno_tpu.parallel.sharding import (sampling_collective_bytes,
                                          tp_collective_bytes)
from idunno_tpu.ops.paged_attention import (PagedContext,
                                            resolve_paged_kernel)
from idunno_tpu.ops.quantize import dequantize_tree, quantize_tree
from idunno_tpu.ops.sampling import fused_decode_tail, masked_sample_logits

# slot default shared with the serving control plane (`serve/control.py`,
# `serve/lm_manager.py`). 16 is the measured knee of the BENCH_SUITE=
# lm_slots scaling curve (RESULTS.md decode section / BENCH_LAST_GOOD_
# lm_slots.json): throughput still rises toward 64 slots (~1.6x) but
# sub-linearly, while KV-cache HBM and time-to-first-token grow linearly
# — 16 is the balanced serving default; operators chasing batch
# throughput pass slots=64 explicitly (tests pin their own sizes).
DEFAULT_SLOTS = 16

# the id lane (`utils/spans.py`) of a pool's own timeline: how many loop
# iterations run between two requests depends on thread timing, and must
# neither shift the ids of a request's trace nor crowd its spans out
LOOP_LANE = "loop"
NO_SPAN = nullcontext()


@contextmanager
def loop_span(spans, name: str, trace: str, parent: str | None, **attrs):
    """One span of a pool's own timeline (trace ``t:<node>:loop:<pool>``:
    `loop.iter` and its children, `lm.step` and its children), also
    entered as a profiler annotation of the same name, so a device profile
    taken meanwhile shows the host's phases above the device's operations
    on the profiler's own clock. Called only with a store wired."""
    with jax.profiler.TraceAnnotation(name):
        sp = spans.start(name, trace=trace, parent=parent, attrs=attrs,
                         lane=LOOP_LANE)
        try:
            yield sp
        finally:
            spans.finish(sp)


@dataclass
class Request:
    """One generation request: ``tokens`` is the raw prompt (host ints);
    ``temperature`` 0 = greedy, > 0 = per-row softmax sampling seeded by
    ``seed`` (defaults to the request id, so every request draws an
    independent, reproducible stream)."""

    id: int
    tokens: list[int]
    max_new: int
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # token-level stop sequences over the GENERATED region; like eos_id,
    # a matched stop sequence is KEPT in the output and the row retires
    # at its final token (host-side detection at dispatch boundaries, so
    # up to decode_steps-1 overshoot tokens are computed then discarded)
    stop: list[list[int]] | None = None
    seed: int | None = None
    # stamps on the pool's clock (`DecodeServer.clock`), copied onto the
    # `Completion`: entry into the serving loop's (or, driven bare, the
    # server's) submit, entry into the server's queue, slot admission, the
    # end of the first step that showed tokens of this request to the host
    # with how many it showed, the end of the last step that showed more
    t_submit: float = 0.0
    t_queued: float = 0.0
    t_admit: float = 0.0
    t_first: float | None = None
    n_first: int = 0
    t_last: float | None = None
    dispatch0: int = 0         # the pool's dispatch count at admission
    t_decode0: float | None = None   # traced only: its first dispatch
    # admitted before the server's FIRST decode dispatch: this request's
    # service time funds the one-time XLA compiles (prefill bucket +
    # decode program), not steady-state work — flagged so downstream
    # demand signals (fair share, autoscaler) can exclude it
    cold: bool = False
    # (trace_id, parent_span_id) from the submitting hop (utils/spans.py);
    # None = untraced. _admit re-points the parent at its prefill span so
    # the `lm.decode` span chains under the prefill in the waterfall.
    trace: tuple | None = None


@dataclass
class Completion:
    id: int
    tokens: list[int]          # prompt + generated, true ragged length
    prompt_len: int
    # SERVICE time: slot admission (prefill start) → retirement. Excludes
    # queue wait here and at any upstream manager, so it measures the
    # pool's per-request processing capacity — the load-independent signal
    # the heterogeneous fair share needs (a backlogged pool must not look
    # slower than an idle one; reference normalizes processing time,
    # `mp4_machinelearning.py:656-674`).
    service_s: float = 0.0
    # client-cancelled mid-stream: ``tokens`` holds whatever was generated
    # before the cancel landed (possibly just the prompt + first token)
    cancelled: bool = False
    # per-GENERATED-token logprobs under the raw model distribution
    # (aligned with tokens[prompt_len:]); None unless the pool was built
    # with track_logprobs=True
    logprobs: list[float] | None = None
    # gateway rejection that completed the request without decoding
    # ("expired": its deadline_ms passed while queued — tokens hold the
    # prompt only); None for every request that reached a slot
    rejected: str | None = None
    # service_s includes the pool's one-time compile window (the request
    # was admitted before the first-ever decode dispatch). Fair-share and
    # autoscaler demand signals skip these samples: a one-time compile is
    # capacity planning, not per-request cost (VERDICT item 4). A
    # `warmup()`-ed pool never produces one.
    cold_start: bool = False
    # the pool's own stamps (one clock, `DecodeServer.clock`): submit, slot
    # admission, the end of the step that first showed the host tokens of
    # this request (``n_first`` of them: the prefill's one; the row joins
    # the next step's dispatch), the end of the step that showed its last.
    # A step ends once the rows' cursors are read back, the moment a
    # streaming client could see the tokens. None where the request never
    # got that far.
    t_submit: float | None = None
    t_admit: float | None = None
    t_first: float | None = None
    n_first: int = 0
    t_last: float | None = None

    def ttft_s(self) -> float | None:
        """Submit to the first visible token(s)."""
        if self.t_first is None or self.t_submit is None:
            return None
        return self.t_first - self.t_submit

    def tpot_s(self) -> float | None:
        """Seconds a token after the first stamp; None where no token
        came after it."""
        later = len(self.tokens) - self.prompt_len - self.n_first
        if self.t_first is None or self.t_last is None or later <= 0:
            return None
        return (self.t_last - self.t_first) / later


# `run`'s arguments that a TPU dispatch donates (`_build_decode`): tokens,
# cache, cursors, remaining, keys, logprobs, counts
_DECODE_DONATED = (1, 2, 3, 4, 8, 9, 12)


def _set_leaf(cache: Any, key: str, value) -> Any:
    """Overwrite every leaf named ``key`` with ``value``, broadcast to the
    leaf's shape and cast to its type; a cache with no such leaf comes
    back as it is."""
    def f(path, leaf):
        if path and getattr(path[-1], "key", None) == key:
            return jnp.broadcast_to(jnp.asarray(value, leaf.dtype),
                                    leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(f, cache)


def _set_cursors(cache: Any, cursors: jnp.ndarray) -> Any:
    """Overwrite every per-layer ``cursors`` leaf with the server's single
    source of truth (the layers never disagree; per-row cursors are
    caller-owned — `MultiHeadAttention._decode_step`). Broadcast covers
    both layouts: per-block [S] leaves and the scanned cache's [L, S]
    stacked leaf."""
    return _set_leaf(cache, "cursors", cursors)


def _set_valid(cache: Any, n) -> Any:
    """Tell a batch-1 prefill cache how many of its positions are real: the
    scalar ``valid`` leaf of a stack with recurrent layers
    (`models/hybrid.py`), which keeps a bucket's padding out of the state
    it carries. A cache with no such leaf comes back as it is."""
    return _set_leaf(cache, "valid", n)


@partial(jax.jit, static_argnames=("model", "prompt_len"))
def _prefill(model: TransformerLM, params: Any, prompt: jnp.ndarray,
             true_len: jnp.ndarray, prompt_len: int):
    """[1, P] prompt → (length-P cache rows, first generated token).
    Pad positions ≥ true_len leave garbage K/V in the cache tail; the
    insert sets the slot cursor to true_len so they are masked until
    overwritten by real generated tokens."""
    dec = decode_model(model, prompt_len)
    cache = _set_valid(init_cache(model, 1, prompt_len), true_len)
    params = dequantize_tree(params)     # no-op for full-precision trees
    logits, cache = decode_apply(dec, params, cache,
                                 prompt.astype(jnp.int32))
    if getattr(model, "last_logits", False):
        # such a stack computed the last real position's row alone
        # (`models/hybrid.py`, from the cache's `valid`)
        return cache, logits[0, 0]
    last = jax.lax.dynamic_index_in_dim(logits[0], true_len - 1, axis=0,
                                        keepdims=False)     # [vocab]
    return cache, last


def _set_scalar_cursor(cache: Any, value) -> Any:
    """Overwrite the scalar ``cursor`` leaves of a batch-1 decode cache
    (the chunked-prefill twin of `_set_cursors`; broadcast covers the
    scanned cache's [L] stacked cursor leaf)."""
    return _set_leaf(cache, "cursor", value)


@partial(jax.jit, static_argnames=("model", "prefix_len", "prompt_len"))
def _prefill_suffix(model: TransformerLM, params: Any, prefix_cache: Any,
                    suffix: jnp.ndarray, true_len: jnp.ndarray,
                    prefix_len: int, prompt_len: int):
    """[1, P] suffix after a length-``prefix_len`` CACHED prefix →
    (length-(prefix_len+P) cache rows, first generated token's logits).

    The cached prefix is spliced into the head of a fresh cache and the
    chunk applies from cursor ``prefix_len`` — positions/RoPE and the
    causal mask then match a from-scratch prefill of prefix+suffix
    exactly (the scalar-cursor t>1 branch, `models/transformer.py`
    chunked prefill). Two callers: the pool-level static ``prefix=``
    cache (paid once at pool build) and, generalized per request, the
    radix prefix cache (`serve/prefix_cache.py`) whose block-chain
    gathers arrive here as ``prefix_cache`` with ``prefix_len`` =
    static prefix + block-aligned hit. Hits are block multiples, so the
    static ``prefix_len`` values stay a bounded compile set."""
    total = prefix_len + prompt_len
    dec = decode_model(model, total)
    cache = _splice_prefix(init_cache(model, 1, total), prefix_cache)
    cache = _set_scalar_cursor(cache, prefix_len)
    params = dequantize_tree(params)
    logits, cache = decode_apply(dec, params, cache,
                                 suffix.astype(jnp.int32))
    last = jax.lax.dynamic_index_in_dim(logits[0], true_len - 1, axis=0,
                                        keepdims=False)     # [vocab]
    return cache, last


def _splice_prefix(cache: Any, prefix_cache: Any) -> Any:
    """Write a cached prefix's K/V leaves into the head of a (longer)
    fresh cache — the splice `_prefill_suffix` does inline, shared with
    the paged/chunked prefill twins."""
    src = {jax.tree_util.keystr(p): leaf for p, leaf
           in jax.tree_util.tree_flatten_with_path(prefix_cache)[0]}

    def put(path, dst):
        if getattr(path[-1], "key", None) not in (
                "cached_k", "cached_v", "k_scale", "v_scale"):
            return dst
        kv = src[jax.tree_util.keystr(path)]
        return jax.lax.dynamic_update_slice(dst, kv, (0,) * dst.ndim)

    return jax.tree_util.tree_map_with_path(put, cache)


def _make_paged_ctx(pages: dict, tables: jnp.ndarray, lengths: jnp.ndarray,
                    start: int, kernel: str, interpret: bool
                    ) -> PagedContext:
    """PagedContext from a `KVBlockPool.kv_pages()` dict (int8 pools
    carry scale pages; BOTH backends dequantize them — the pallas
    kernel in-VMEM per block tile, the xla fallback after the gather)."""
    return PagedContext(
        pages["cached_k"], pages["cached_v"], tables, lengths,
        k_scale_pages=pages.get("k_scale"),
        v_scale_pages=pages.get("v_scale"),
        start=start, kernel=kernel, interpret=interpret)


@partial(jax.jit, static_argnames=("model", "prefix_len", "prompt_len",
                                  "start", "kernel", "interpret"))
def _prefill_suffix_paged(model: TransformerLM, params: Any,
                          prefix_cache: Any, suffix: jnp.ndarray,
                          true_len: jnp.ndarray, prefix_len: int,
                          prompt_len: int, tables: jnp.ndarray,
                          plen: jnp.ndarray, pages: dict, *, start: int,
                          kernel: str, interpret: bool):
    """The gather-free twin of `_prefill_suffix`: the radix-hit region
    [start, prefix_len) is NOT spliced into the fresh cache — it stays
    zero (and the paged mask exclusion keeps it invisible) while the
    suffix attends those positions THROUGH the block table
    (`ops.paged_attention`). Only the pool-level static prefix
    [0, start), if any, is spliced contiguously. ``prefix_len`` is still
    static (block-aligned hits keep the compile set bounded, exactly as
    in `_prefill_suffix`); the written suffix then lands at the same
    absolute positions as the gathered path, so the radix insert from
    this row cache stays block-exact."""
    total = prefix_len + prompt_len
    dec = decode_model(model, total)
    cache = init_cache(model, 1, total)
    if prefix_cache is not None:
        cache = _splice_prefix(cache, prefix_cache)
    cache = _set_scalar_cursor(cache, prefix_len)
    params = dequantize_tree(params)
    ctx = _make_paged_ctx(pages, tables, plen, start, kernel, interpret)
    logits, cache = decode_apply(dec, params, cache,
                                 suffix.astype(jnp.int32), paged=ctx)
    last = jax.lax.dynamic_index_in_dim(logits[0], true_len - 1, axis=0,
                                        keepdims=False)     # [vocab]
    return cache, last


@partial(jax.jit, static_argnames=("model", "total"))
def _chunk_init(model: TransformerLM, prefix_cache: Any, total: int):
    """Fresh batch-1 length-``total`` cache with an optional contiguous
    prefix spliced in — the starting state of a chunked prefill
    (`DecodeServer._advance_prefill`). The cursor is set per chunk."""
    cache = init_cache(model, 1, total)
    if prefix_cache is not None:
        cache = _splice_prefix(cache, prefix_cache)
    return cache


@partial(jax.jit, static_argnames=("model", "total", "start", "kernel",
                                   "interpret"))
def _prefill_chunk(model: TransformerLM, params: Any, cache: Any,
                   tok: jnp.ndarray, cursor: jnp.ndarray, total: int,
                   tables: jnp.ndarray | None, plen: jnp.ndarray | None,
                   pages: dict | None, *, start: int = 0,
                   kernel: str = "xla", interpret: bool = False):
    """ONE chunk of a chunked prefill: ``tok`` [1, n] applies from
    ``cursor`` (traced — every chunk of every admission reuses the same
    compile per (total, n)). The scalar-cursor t>1 branch writes K/V at
    cursor..cursor+n-1 and masks per position, so chunk boundaries are
    invisible: N chunks produce the identical cache and logits as one
    length-``Σn`` apply (`tests/test_serve_lm.py` pins this). ``tables``
    None = no paged radix hit for this admission."""
    dec = decode_model(model, total)
    cache = _set_scalar_cursor(cache, cursor)
    params = dequantize_tree(params)
    ctx = None
    if tables is not None:
        ctx = _make_paged_ctx(pages, tables, plen, start, kernel,
                              interpret)
    logits, cache = decode_apply(dec, params, cache,
                                 tok.astype(jnp.int32), paged=ctx)
    return cache, logits


def _next_token(logits: jnp.ndarray, temp: jnp.ndarray,
                key: jnp.ndarray, top_p: jnp.ndarray,
                top_k: jnp.ndarray) -> jnp.ndarray:
    """Greedy (temp == 0) or temperature + top-k/nucleus-sampled next
    token; shared by the prefill pick and the batched decode step
    (vmapped there, so every array is one row's). Samples from the
    MASKED-SCALED form (`ops.sampling.masked_sample_logits`) — the same
    construction `generate` and the fused tail use, so the first token
    of a stream is picked by the identical math as every later one."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temp, 1e-6)
    sampled = jax.random.categorical(
        key, masked_sample_logits(scaled, top_p, top_k),
        axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0.0, sampled, greedy)


@jax.jit
def _pick_first(logits: jnp.ndarray, temp: jnp.ndarray,
                key: jnp.ndarray, top_p: jnp.ndarray,
                top_k: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """First generated token from the prefill logits; returns (token,
    advanced key) so the decode stream continues from a fresh subkey."""
    sub, nxt_key = jax.random.split(key)
    return _next_token(logits, temp, sub, top_p, top_k), nxt_key


def _splice_rows(cache: Any, row_cache: Any, slot: jnp.ndarray,
                 stacked: bool) -> Any:
    """Write a batch-1 prefill cache's K/V rows into row ``slot`` of a
    pool cache. The two trees' structures differ only at the cursor leaves
    (scalar "cursor" in the prefill cache vs caller-owned [S] "cursors"
    in the pool) — K/V (and, for int8 caches, their scale) leaves match
    by path, and so do a hybrid stack's pooled keys and recurrent state
    (`kv_blocks.SLOT_LEAF_KEYS`; a state leaf has no token axis and lands
    whole, so nothing of the slot's last tenant is left in it); everything
    else untouched. ``stacked`` (static — the layout
    is not inferable from rank: a per-block cached_k and a stacked
    k_scale are both 4-D) selects the scanned layout, where every leaf
    carries a leading depth axis and the slot axis is SECOND."""
    src = {jax.tree_util.keystr(p): leaf for p, leaf
           in jax.tree_util.tree_flatten_with_path(row_cache)[0]}

    def splice(path, dst):
        if getattr(path[-1], "key", None) not in SLOT_LEAF_KEYS:
            return dst
        kv = src[jax.tree_util.keystr(path)]          # [(L,) 1, P, h, d]
        if stacked:
            dst_rows = jax.lax.dynamic_update_slice(
                dst[:, slot], kv[:, 0], (0,) * (kv.ndim - 1))
            return dst.at[:, slot].set(dst_rows)
        dst_row = jax.lax.dynamic_update_slice(
            dst[slot], kv[0], (0,) * kv[0].ndim)
        return dst.at[slot].set(dst_row)

    return jax.tree_util.tree_map_with_path(splice, cache)


@partial(jax.jit, static_argnames=("prompt_len", "stacked"),
         donate_argnums=(0, 1))
def _insert(tokens: jnp.ndarray, cache: Any, row_cache: Any,
            prompt: jnp.ndarray, first_tok: jnp.ndarray,
            true_len: jnp.ndarray, slot: jnp.ndarray,
            prompt_len: int, stacked: bool = False
            ) -> tuple[jnp.ndarray, Any]:
    """Splice a prefilled request into decode slot ``slot``: tokens[:P] =
    prompt, tokens[true_len] = first generated token, cache rows [:P] from
    the prefill. Cursors are NOT touched here — the server tracks them."""
    row = tokens[slot]
    row = jax.lax.dynamic_update_slice(row, prompt[0].astype(jnp.int32),
                                       (0,))
    row = row.at[true_len].set(first_tok)
    tokens = tokens.at[slot].set(row)
    return tokens, _splice_rows(cache, row_cache, slot, stacked)


@jax.jit
def _set_rows(arrays: tuple, at: tuple, values: tuple) -> tuple:
    """``a.at[i].set(v)`` for every array of a pool's per-slot state, as
    ONE program. An admission sets a dozen of them; done eagerly each is a
    handful of tiny programs, and the runtime lets the host have only so
    many programs in flight (32): behind a decode dispatch in flight the
    admission's enqueues then stall until that dispatch has finished."""
    return tuple(a.at[i].set(v) for a, i, v in zip(arrays, at, values))


# the slot cache's counters (`models/hybrid.py`): leaves without a slot axis
# that a dispatch adds to
_COUNTER_KEYS = ("expert_load", "expert_steps", "state_rows")


def _counter_keys(cache: Any) -> tuple[str, ...]:
    """The counters a slot cache holds: an expert stack's, a state-space
    stack's, or none."""
    runs = [v for k, v in cache.items() if k.startswith("run")]
    return tuple(k for k in _COUNTER_KEYS if any(k in r for r in runs))


@jax.jit
def _counters(cache: Any) -> dict:
    """Copies of the slot cache's counters, a list over the runs of layers
    that hold each: what `DecodeServer.stats()` reads while the cache they
    came from is already donated to the next dispatch."""
    runs = [v for k, v in cache.items() if k.startswith("run")]
    return {key: [jnp.copy(r[key]) for r in runs if key in r]
            for key in _counter_keys(cache)}


class DecodeServer:
    """Continuous-batching decode pool over a dense `TransformerLM`.

    ``slots`` concurrent sequences, each ≤ ``max_len`` total tokens;
    prompts are padded to the static ``prompt_len`` bucket (true lengths
    tracked exactly). Greedy requests match `generate(temperature=0)`
    token-for-token (the tests' exactness oracle); sampled requests draw
    per-request seeded streams.

    Usage::

        srv = DecodeServer(model, params, slots=4, prompt_len=16,
                           max_len=64)
        srv.submit([1, 2, 3], max_new=10)
        while srv.step():          # admit + one decode dispatch per call
            for done in srv.poll():
                ...
    """

    def __init__(self, model: TransformerLM, params: Any, *, slots: int,
                 prompt_len: int, max_len: int, decode_steps: int = 1,
                 quantize: str = "none", eos_id: int | None = None,
                 mesh=None, n_model: int = 1,
                 prompt_buckets: tuple[int, ...] | None = None,
                 track_logprobs: bool = False,
                 penalties: bool = False,
                 prefix: list[int] | None = None,
                 kv_block_size: int = 0,
                 kv_cache_blocks: int = 0,
                 paged_kernel: str | None = None,
                 prefill_chunk: int = 0) -> None:
        if not model.causal:
            raise ValueError("continuous batching needs a causal LM")
        if prompt_len > max_len:
            raise ValueError(f"prompt_len {prompt_len} > max_len {max_len}")
        # static-shape buckets: each admission prefills at the SMALLEST
        # bucket covering its true length (one compile per bucket) instead
        # of padding every prompt to prompt_len — short prompts stop paying
        # the long bucket's prefill FLOPs
        self.prompt_buckets = tuple(sorted(set(prompt_buckets or ())))
        if self.prompt_buckets:
            if self.prompt_buckets[-1] != prompt_len:
                raise ValueError(
                    f"largest prompt bucket {self.prompt_buckets[-1]} must "
                    f"equal prompt_len {prompt_len}")
            if self.prompt_buckets[0] < 1:
                raise ValueError("prompt buckets must be >= 1")
        else:
            self.prompt_buckets = (prompt_len,)
        if decode_steps < 1:
            raise ValueError(f"decode_steps {decode_steps} must be >= 1")
        # cross-request radix prefix cache (engine/kv_blocks.py +
        # serve/prefix_cache.py): kv_block_size > 0 enables it; hits are
        # block-aligned so the `_prefill_suffix` static prefix lengths
        # stay a bounded set (block multiples) instead of one compile
        # per distinct hit length
        self.kv_block_size = int(kv_block_size)
        if self.kv_block_size < 0:
            raise ValueError(
                f"kv_block_size {kv_block_size} must be >= 0 (0 = off)")
        if kv_cache_blocks and not self.kv_block_size:
            raise ValueError("kv_cache_blocks needs kv_block_size > 0")
        # block-native paged attention (ops/paged_attention.py): radix
        # hits attend THROUGH the block table instead of being gathered
        # back into the slot cache. None = legacy gathered path (the
        # earn-it-or-swap default until `paged_suite` blesses the kernel
        # on real hardware).
        if paged_kernel is not None and not self.kv_block_size:
            raise ValueError("paged_kernel needs kv_block_size > 0")
        # int8 pools resolve like any other since ISSUE 16 (the pallas
        # kernel dequantizes block tiles in-VMEM) — no forcing to xla
        self.paged_kernel = (None if paged_kernel is None else
                             resolve_paged_kernel(paged_kernel))
        self._paged = paged_kernel is not None
        # chunked prefill: long suffixes apply prefill_chunk tokens at a
        # time, one chunk per step() call, so resident rows keep decoding
        # between chunks. 0 = off (one-shot prefill). Independent of the
        # paged path — the gathered path chunks too.
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk {prefill_chunk} must be >= 0 (0 = off)")
        self._pending: dict | None = None   # in-flight chunked admission
        self._block_pool = self._radix = None
        self._held: dict[int, list] = {}   # live request id → pinned chain
        # optional per-node span recorder (utils/spans.py), set by the
        # serving layer after construction; None = tracing off, zero cost
        self.spans = None
        # the pool's one clock: every stamp on `Request`/`Completion`.
        # Whoever wires `spans` points it at the store's clock, so stamps
        # and spans share a timeline (and fake-clock tests stay exact)
        self.clock = time.monotonic
        # (trace_id, parent_span_id) the step's own spans go under: the
        # serving loop points it at its `loop.iter` span each iteration;
        # driven bare, the steps root a trace of their own
        self.step_ctx: tuple | None = None
        self._step_span = None            # the running step's `lm.step`
        # optional cluster prefix cache (serve/cluster_prefix.py), set by
        # the serving layer after construction like `spans` — the engine
        # layer stays free of store/transport dependencies. None = local-
        # only radix caching, zero cost on the admission path.
        self.cluster_prefix = None
        # cheap argument validation BEFORE any device allocation or
        # weight quantization: a bad prefix must fail in microseconds
        self.prefix = list(prefix) if prefix else None
        self._prefix_cache = None
        if self.prefix:
            for t in self.prefix:
                if not 0 <= t < model.vocab:
                    raise ValueError(f"prefix token {t} outside vocab "
                                     f"[0, {model.vocab})")
            if len(self.prefix) + max(self.prompt_buckets) > max_len:
                raise ValueError(
                    f"prefix of {len(self.prefix)} + prompt bucket "
                    f"{max(self.prompt_buckets)} exceeds max_len {max_len}")
        if quantize == "int8":
            # decode re-reads every weight per step — int8 residency halves
            # that HBM traffic; dequant happens inside the jitted programs
            params = quantize_tree(params)
        elif quantize != "none":
            raise ValueError(f"quantize={quantize!r}: want none|int8")
        self.quantize = quantize
        # compile-time flag: when off, the decode programs carry zero
        # logprob bookkeeping (the hot path is unchanged); when on, every
        # generated token's logprob under the RAW model distribution
        # (untempered, unfiltered — sampler-independent semantics) is
        # recorded and returned on the Completion
        self.track_logprobs = bool(track_logprobs)
        # compile-time flag for presence/frequency penalties (a [S, vocab]
        # generated-token count buffer + a scatter-add per step; zero cost
        # when off)
        self.penalties = bool(penalties)
        # scanned decode hot loop: every scan-compatible model (dense
        # blocks — `models.transformer.scan_compatible`) is converted to
        # the stacked layout here, INSIDE the server, so callers keep
        # handing over canonical per-block params (checkpoints, the
        # manager's rebuild-from-store path) while the compiled step runs
        # the layer loop as one lax.scan. Quantization above ran first:
        # stacking QTensors stacks q/scale independently and preserves
        # the dequantized numerics. MoE pools keep the per-layer loop.
        if scan_compatible(model) and not getattr(model, "scan_layers",
                                                  False):
            model = dataclasses.replace(model, scan_layers=True)
            params = stack_block_params(params, model.depth)
        self._scan = bool(getattr(model, "scan_layers", False))
        if self._paged and not self._scan:
            # decode_apply threads PagedContext through the ONE lax.scan
            # body; the unscanned per-layer loop never grew the plumbing
            # (MoE pools keep the gathered path)
            raise ValueError("paged_kernel requires the scanned decode "
                             "layout (dense scan-compatible blocks)")
        # a stack with recurrent or block-sparse layers (`models/hybrid.py`)
        # keeps state a slot that a chain of K/V blocks cannot rebuild: the
        # radix cache never serves it a hit (`prefix_skipped_recurrent`
        # counts the admissions that would have looked), and what rests on
        # restoring or re-deriving K/V alone is refused here, by name
        self._recurrent = bool(getattr(model, "recurrent", False))
        if self._recurrent:
            refused = [what for what, asked in (
                ("n_model > 1 or mesh= (no sharding specs)",
                 n_model != 1 or mesh is not None),
                ("paged_kernel=", paged_kernel is not None),
                ("prefix= (a shared prefix restores K/V only)",
                 bool(prefix)),
                ("quantize=", quantize != "none")) if asked]
            if refused:
                raise UnsupportedStack(
                    "a stack with recurrent or block-sparse layers does "
                    "not support " + "; ".join(refused))
        # CPU tier runs the real kernel under the Pallas interpreter so
        # tier-1 tests exercise the exact kernel the TPU compiles
        self._paged_interpret = jax.devices()[0].platform != "tpu"
        self._pl_static = len(self.prefix) if self.prefix else 0
        self.model = model
        self.params = params
        self.slots = slots
        self.prompt_len = prompt_len
        self.max_len = max_len
        self.decode_steps = decode_steps
        # generating eos_id retires the row immediately (the eos token is
        # kept in the output, truncating the sequence below max_new) — the
        # freed slot admits the next queued prompt at the following step
        self.eos_id = eos_id

        self._dec = self._per_row_decode(model, max_len)
        if self._recurrent:
            # a hybrid stack's decode step runs `ops/ssd_decode.py`: under
            # the interpreter off the chip, as the paged kernel above
            self._dec = dataclasses.replace(
                self._dec, interpret=self._paged_interpret)
        self._prefill_model = model

        # mesh sharding: the pool's slot dimension spreads over the mesh's
        # data axis (every per-row decode op is elementwise over slots, so
        # the step runs SPMD with zero cross-row collectives). n_model > 1
        # — or a mesh whose "model" axis has extent > 1 — additionally
        # activates tensor parallelism: the stacked scanned params take
        # the Megatron column/row split over the model axis
        # (`parallel/sharding.py:lm_tp_specs`), so GSPMD inserts the two
        # per-block psums INSIDE the one `lax.scan`, and the KV caches
        # shard their head dim while the slot axis stays on
        # `P(None, "data")`. One pool then scales co-resident sequences
        # across the data axis AND a too-big-for-one-chip model across
        # the model axis.
        n_model = int(n_model)
        if n_model < 1:
            raise ValueError(f"n_model {n_model} must be >= 1")
        if mesh is None and n_model > 1:
            # pure-TP mesh over n_model devices; pass an explicit mesh
            # for combined data x model
            from idunno_tpu.parallel.mesh import make_mesh
            mesh = make_mesh(1, n_model)
        if mesh is not None:
            from idunno_tpu.parallel.mesh import MODEL_AXIS
            mesh_model = int(mesh.shape.get(MODEL_AXIS, 1))
            if n_model == 1:
                n_model = mesh_model        # mesh is authoritative
            elif n_model != mesh_model:
                raise ValueError(
                    f"n_model={n_model} conflicts with the mesh's model "
                    f"axis extent {mesh_model}")
        self.n_model = n_model
        self._kv_shard = False
        if n_model > 1:
            if not self._scan:
                # TP specs target the stacked layout; MoE/unscanned pools
                # keep the per-layer loop and stay data-parallel only
                raise ValueError(
                    "n_model > 1 requires the scanned decode layout "
                    "(dense scan-compatible blocks)")
            from idunno_tpu.parallel.mesh import check_head_divisibility
            check_head_divisibility(model.num_heads, n_model)
            kvh = getattr(model, "num_kv_heads", None) or model.num_heads
            # GQA divide-or-replicate: non-dividing KV heads replicate
            # k/v params and the KV cache while Q still shards
            self._kv_shard = kvh % n_model == 0
        self.mesh = mesh
        rows = None
        stacked_rows = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from idunno_tpu.parallel.mesh import DATA_AXIS
            from idunno_tpu.parallel.sharding import (
                batch_sharding, lm_tp_specs, replicate, replicated_sharding)
            n_data = mesh.shape[DATA_AXIS]
            if slots % n_data:
                raise ValueError(f"slots={slots} must divide over the "
                                 f"mesh data axis ({n_data})")
            rows = batch_sharding(mesh)
            # scanned caches lead with DEPTH ([L, slots, ...]): the slot
            # split moves one dim right, depth stays whole on every chip
            stacked_rows = NamedSharding(mesh, PartitionSpec(None, DATA_AXIS))
            if self.n_model > 1:
                specs = lm_tp_specs(self.params, n_model=self.n_model,
                                    kv_shard=self._kv_shard)
                self.params = jax.tree.map(
                    lambda leaf, sp: jax.device_put(
                        leaf, NamedSharding(mesh, sp)),
                    self.params, specs)
            else:
                self.params = replicate(mesh, self.params)

        def zeros(shape, dtype, stacked=False):
            # allocate UNDER the sharding: materializing the full cache on
            # one device first would need the whole pool to fit one chip's
            # HBM, defeating the point of sharding the slot dimension
            if rows is None:
                return jnp.zeros(shape, dtype)
            if stacked:
                sh = (stacked_rows if len(shape) >= 2
                      else replicated_sharding(mesh))
            else:
                sh = rows
            return jax.jit(lambda: jnp.zeros(shape, dtype),
                           out_shardings=sh)()

        # device state
        self._tokens = zeros((slots, max_len), jnp.int32)
        cache_shapes = jax.eval_shape(
            lambda: init_cache(self._dec_for_init(), slots, max_len))
        if self.n_model > 1:
            # TP cache layout: slot axis stays on the data axis, KV head
            # dim shards over "model" when the heads divide
            from jax.sharding import NamedSharding
            from idunno_tpu.parallel.sharding import lm_cache_specs
            cache_spec = lm_cache_specs(cache_shapes, n_model=self.n_model,
                                        kv_shard=self._kv_shard)
            self._cache = jax.tree.map(
                lambda s, sp: jax.jit(
                    lambda: jnp.zeros(s.shape, s.dtype),
                    out_shardings=NamedSharding(mesh, sp))(),
                cache_shapes, cache_spec)
        else:
            self._cache = jax.tree.map(
                lambda s: zeros(s.shape, s.dtype, stacked=self._scan),
                cache_shapes)
        # the keys and values the slot cache holds (a gauge of `stats()`)
        self._kv_cache_bytes = sum(
            leaf.size * leaf.dtype.itemsize for path, leaf
            in jax.tree_util.tree_flatten_with_path(cache_shapes)[0]
            if getattr(path[-1], "key", None) in KV_LEAF_KEYS)
        self._cursors = zeros((slots,), jnp.int32)
        self._remaining = zeros((slots,), jnp.int32)
        # paged decode state: per-slot block table + paged-region length
        # (tokens resident in blocks, always a block multiple). Width =
        # the longest possible radix hit — capped one block short of the
        # largest bucket by `_admit`'s hit cap. Retired rows leave stale
        # entries behind: finite garbage whose outputs are gated by
        # remaining == 0, never read as live state.
        self._tables = self._plens = None
        if self._paged:
            self._max_chain = max(
                1, (prompt_len - 1) // self.kv_block_size)
            self._tables = zeros((slots, self._max_chain), jnp.int32)
            self._plens = zeros((slots,), jnp.int32)
        # host cache of (remaining, cursors), fetched as ONE stacked D2H
        # transfer and reused until a device-side mutation invalidates it:
        # step() consults these arrays several times per dispatch, and
        # every separate np.asarray is a host<->device round trip
        self._rc_cache: np.ndarray | None = None
        self._temps = zeros((slots,), jnp.float32)
        self._top_ps = zeros((slots,), jnp.float32) + 1.0
        self._top_ks = zeros((slots,), jnp.int32)        # 0 = no k-filter
        self._keys = zeros((slots, 2), jnp.uint32)       # per-row rng
        # width-0 when tracking is off: the decode programs keep one
        # signature and the buffer costs nothing (no in-body updates).
        # The empty buffer is allocated UNSHARDED — XLA refuses a named
        # sharding on a zero-size dimension, and it carries no data
        self._logprobs = (zeros((slots, max_len), jnp.float32)
                          if self.track_logprobs
                          else jnp.zeros((slots, 0), jnp.float32))
        self._pres = zeros((slots,), jnp.float32)
        self._freq = zeros((slots,), jnp.float32)
        self._counts = (zeros((slots, model.vocab), jnp.int32)
                        if self.penalties
                        else jnp.zeros((slots, 0), jnp.int32))
        # host state
        self._queue: deque[Request] = deque()
        self._live: dict[int, Request] = {}       # slot → request
        self._done: list[Completion] = []
        # (completion, request) of the rows the running step retired,
        # until its end stamps them; traced admissions awaiting their
        # first dispatch (the next step's), whose start `lm.decode` takes
        self._retired: list[tuple[Completion, Request]] = []
        self._new_traced: list[Request] = []
        # while the running step's decode dispatch is in flight: the
        # cursors the host held before it (an admission queued behind it
        # puts its slot's new cursor there: the slot took no part in the
        # dispatch); None when the step enqueued none
        self._in_flight: np.ndarray | None = None
        self._next_id = 0
        self._cancelled: set[int] = set()     # ids cancelled while live
        self._stats = {"dispatches": 0, "admitted": 0,
                       # admissions whose slot splice (a chunked one's
                       # last chunk with it) was enqueued behind a decode
                       # dispatch in flight; the rest met an empty pool
                       "admissions_overlapped": 0, "completed": 0,
                       "tokens_generated": 0, "cancelled": 0,
                       # padded suffix tokens actually computed by
                       # admission prefills — the work the prefix cache
                       # exists to shrink (bench comparison counter)
                       "prefill_tokens": 0,
                       # paged/chunked win counters (gauges via lm_stats)
                       "prefill_chunks": 0, "kv_gather_bytes_saved": 0,
                       # DistServe handoff counters (ISSUE 18): exports
                       # shipped from this pool / KVC1 bytes encoded or
                       # adopted / ships that fell back to decode-side
                       # prefill (gauges via lm_stats)
                       "kv_handoff_requests": 0, "kv_handoff_bytes": 0,
                       "kv_handoff_fallbacks": 0}
        # prefix-cache counters (zero-cost when the cache is off)
        self._pc_lookups = self._pc_hits = self._pc_tokens_saved = 0
        # hybrid stacks only: each live slot's cursor as the host last saw
        # it, from which a dispatch's contexts are counted without a read
        # of the device; tokens its sparse layers' queries attended and had
        # in context, summed over live rows and decode steps (a stack with
        # block-sparse layers alone: the model says which kinds it has);
        # admissions that skipped the radix lookup
        self._seen_cursor: dict[int, int] = {}
        self._sparse = self._recurrent and model.has(SPARSE)
        if self._recurrent:
            self._stats["prefix_skipped_recurrent"] = 0
        if self._sparse:
            self._stats.update(sparse_tokens_attended=0,
                               sparse_tokens_in_context=0)
        # the slot cache's counters (an expert stack's `expert_load`,
        # `expert_steps`; a state-space or Gated DeltaNet stack's
        # `state_rows`: leaves without a slot axis) as the last dispatch
        # left them: a copy on the device, because the cache itself is
        # donated to the next dispatch while another thread may be asking
        # `stats()`
        self._counted = self._recurrent and bool(_counter_keys(self._cache))
        self._counts_copy = (_counters(self._cache) if self._counted
                             else None)
        # the context lengths the decode step can read, as the model says
        # (`decode_context_rungs`: `MultiHeadAttention._decode_step`, a
        # hybrid stack's `_attend_live`), None for a model whose step reads
        # what it reads whatever the cursors; how far along the token axis
        # the dispatches' steps read the slot cache, and how far it
        # reaches: a token a slot a step (`_count_context`)
        rungs = model.decode_context_rungs(max_len, slots)
        self._ladder = None if rungs is None else np.asarray(rungs)
        if self._ladder is not None:
            self._stats.update(decode_context_read=0, decode_context_held=0)
        # flips True at the first decode dispatch and NEVER resets (the
        # warmup() stats reset must not re-mark a warmed pool cold):
        # requests admitted while False carry Request.cold → their
        # completions are cold_start-tagged
        self._dispatched_ever = False

        self._decode = self._build_decode(decode_steps)

        # shared-prefix cache (system prompt): the prefix is prefilled
        # ONCE here; every admission then prefills only its suffix from a
        # spliced copy (`_prefill_suffix`). Completions INCLUDE the
        # prefix (prompt_len covers prefix + suffix, so
        # tokens[prompt_len:] is still exactly the generated region).
        if self.prefix:
            pf = jnp.asarray([self.prefix], jnp.int32)
            pl = len(self.prefix)
            self._prefix_cache, _ = _prefill(
                self._prefill_model, self.params, pf, jnp.int32(pl), pl)

        # paged KV block pool + radix tree over PER-REQUEST prompt
        # prefixes (the static prefix above is shared by construction
        # and sits in front of every chain). Deferred imports: the serve
        # package pulls this module back in via lm_pool.
        if self.kv_block_size:
            from idunno_tpu.engine.kv_blocks import KVBlockPool
            from idunno_tpu.serve.prefix_cache import RadixPrefixCache
            nblocks = int(kv_cache_blocks) or slots * (
                (prompt_len + self.kv_block_size - 1) // self.kv_block_size)
            self._block_pool = KVBlockPool(
                model, nblocks, self.kv_block_size,
                mesh=self.mesh if self.n_model > 1 else None)
            self._radix = RadixPrefixCache(self._block_pool)

    @staticmethod
    def _per_row_decode(model: TransformerLM,
                        max_len: int = 0) -> TransformerLM:
        """The per-row-cursor decode twin of ``model`` (max_len 0 = leave
        for `init_cache` to set)."""
        return dataclasses.replace(model, decode=True, decode_per_row=True,
                                   max_decode_len=max_len)

    def _dec_for_init(self) -> TransformerLM:
        return self._per_row_decode(self.model)

    def _build_decode(self, n_steps: int):
        """The decode dispatch (`jit_run`): ``n_steps`` tokens for every
        slot, a `fori_loop` of (model step, fused sampling tail) over the
        donated decode state.

        The model step reads the slot cache as far as the deepest cursor
        it is handed (`MultiHeadAttention._decode_step`'s context ladder),
        so the cache is handed ``where(remaining > 0, cursors, 0)``: a dead
        slot keeps the cursor of the request that left it and must not set
        the bound. That is safe because a slot's rows have one reader, the
        step itself, and only while the slot is live: a dead row writes
        the K/V it computes (and nobody samples) at position 0 of its own
        slot instead of at its stale cursor, the next `_insert` overwrites
        the slot's rows from position 0 before the slot is live again, and
        the block pool is written from prefill caches, never from a slot.
        Tokens and the sampling tail keep the true cursors. The same holds
        of a hybrid stack whose attention takes the ladder
        (`models/hybrid.py:_attend_live`): `_splice_rows` lands EVERY leaf
        of a slot whole (K/V, scan state, window) before the slot is live
        again, so what a dead row at cursor 0 writes into its own K/V and
        state is overwritten the same way. A model that answers
        `decode_context_rungs` with None (a block-sparse or linear stack)
        is handed the cursors as they are."""
        dec = self._dec
        track = self.track_logprobs     # static: traced once
        pen = self.penalties            # static: traced once
        paged = self._paged             # static: traced once
        # a model without a ladder reads what it reads, whatever the
        # cursors: it is handed them as they are
        bound_by_live = self._ladder is not None

        def run(params, tokens, cache, cursors, remaining, temps,
                top_ps, top_ks, keys, logprobs, pres, freq, counts,
                tables=None, plens=None, pages=None):
            params = dequantize_tree(params)   # int8 stays HBM-resident
            # paged pool: every step attends the radix-hit region through
            # the block table (ops/paged_attention.py) — the pool's pages
            # ride in as read-only args (NOT donated: blocks are shared
            # across rows and with the radix tree)
            ctx = (_make_paged_ctx(pages, tables, plens, self._pl_static,
                                   self.paged_kernel,
                                   self._paged_interpret)
                   if paged else None)

            def body(_, carry):
                (tokens, cache, cursors, remaining, keys, logprobs,
                 counts) = carry
                # dead rows do not set how far the step reads (docstring)
                cache = _set_cursors(
                    cache, jnp.where(remaining > 0, cursors, 0)
                    if bound_by_live else cursors)
                # an expert stack routes a dead row nowhere and counts
                # the live ones, a state-space or Gated DeltaNet layer moves
                # the live rows' states alone (`models/hybrid.py`); no other
                # cache has the leaf
                cache = _set_leaf(cache, "live", remaining > 0)
                tok = jnp.take_along_axis(tokens, cursors[:, None], axis=1)
                # decode_apply: the scanned step (one lax.scan over the
                # stacked layers, the cache its carry) on scan-compatible
                # pools, the flax per-layer loop otherwise
                logits, cache = decode_apply(dec, params, cache, tok,
                                             paged=ctx)
                # the whole post-model tail — penalties, sampling pick,
                # token/logprob scatter, cursor/remaining/EOS/count
                # bookkeeping — is ONE fused helper (`ops.sampling.
                # fused_decode_tail`), traced into this same jitted body
                (tokens, cursors, remaining, keys, logprobs,
                 counts) = fused_decode_tail(
                    logits[:, 0], tokens, cursors, remaining, temps,
                    top_ps, top_ks, keys, logprobs, pres, freq, counts,
                    max_len=self.max_len, eos_id=self.eos_id,
                    track=track, pen=pen)
                return (tokens, cache, cursors, remaining, keys, logprobs,
                        counts)

            return jax.lax.fori_loop(
                0, n_steps, body,
                (tokens, cache, cursors, remaining, keys, logprobs,
                 counts))

        # donate the decode state: the slot cache is by far the largest
        # buffer, and with the donation the dispatch updates it where it
        # lies — the layer scan carries the stacked K/V and writes only the
        # new tokens' rows (`models/transformer.py:scanned_apply`), and this
        # `fori_loop` hands the same buffers from step to step, so no whole
        # leaf is copied in, between or after the steps
        # (`tests/test_chip_compile.py` holds the compiled program to
        # that). (CPU doesn't implement donation and would warn.)
        # temps/top_ps/top_ks are read-only and not donated.
        if jax.devices()[0].platform == "tpu":
            return jax.jit(run, donate_argnums=_DECODE_DONATED)
        return jax.jit(run)

    # -- client surface ---------------------------------------------------

    def validate(self, tokens: list[int], max_new: int,
                 temperature: float = 0.0, top_p: float = 1.0,
                 top_k: int = 0, presence_penalty: float = 0.0,
                 frequency_penalty: float = 0.0,
                 stop: list[list[int]] | None = None) -> None:
        """Raise ValueError if the request can't fit this server's static
        buckets; shared by every submission front-end (the RPC serving
        loop validates on the caller's thread with this)."""
        if not tokens:
            raise ValueError("empty prompt")
        for t in tokens:
            # out-of-range ids would be silently clamped by the embedding
            # gather on TPU, producing a plausible-looking but meaningless
            # completion — fail on the caller's thread instead
            if not 0 <= t < self.model.vocab:
                raise ValueError(f"prompt token {t} outside vocab "
                                 f"[0, {self.model.vocab})")
        if len(tokens) > self.prompt_len:
            raise ValueError(f"prompt of {len(tokens)} tokens exceeds the "
                             f"prompt_len bucket {self.prompt_len}")
        pl = len(self.prefix) if self.prefix else 0
        if pl + len(tokens) + max_new > self.max_len:
            raise ValueError(
                (f"{pl} prefix + " if pl else "")
                + f"{len(tokens)} prompt + {max_new} new"
                + f" > max_len {self.max_len}")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if temperature < 0.0:
            raise ValueError(f"temperature {temperature} must be >= 0")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p {top_p} must be in (0, 1]")
        if top_k < 0 or top_k != int(top_k):
            raise ValueError(f"top_k {top_k} must be a non-negative int")
        if (presence_penalty or frequency_penalty) and not self.penalties:
            raise ValueError(
                "this pool was built without penalties=True; "
                "presence/frequency penalties need the count buffer")
        for seq in stop or ():
            if not seq:
                raise ValueError("empty stop sequence")
            for t in seq:
                if not 0 <= t < self.model.vocab:
                    raise ValueError(f"stop token {t} outside vocab "
                                     f"[0, {self.model.vocab})")

    def submit(self, tokens: list[int], max_new: int, *,
               temperature: float = 0.0, top_p: float = 1.0,
               top_k: int = 0, presence_penalty: float = 0.0,
               frequency_penalty: float = 0.0,
               stop: list[list[int]] | None = None,
               seed: int | None = None,
               trace: tuple | None = None,
               t_submit: float | None = None) -> int:
        """Queue a prompt; returns the request id. ``temperature`` 0 =
        greedy; > 0 samples with a per-request stream seeded by ``seed``
        (default: the request id); ``top_p`` < 1 restricts sampling to
        the nucleus and ``top_k`` > 0 to the k most probable tokens
        (k-filter first, then nucleus), exactly as in `engine.generate`.
        ``trace`` is an optional (trace_id, parent_span_id) context —
        prefill/decode spans are recorded under it when `self.spans` is
        wired (utils/spans.py). ``t_submit`` is the serving loop's own
        stamp of the request's arrival (on `self.clock`); left out, the
        request arrives now."""
        self.validate(tokens, max_new, temperature, top_p, top_k,
                      presence_penalty, frequency_penalty, stop)
        rid = self._next_id
        self._next_id += 1
        now = self.clock()
        self._queue.append(Request(id=rid, tokens=list(tokens),
                                   max_new=max_new,
                                   temperature=temperature, top_p=top_p,
                                   top_k=int(top_k),
                                   presence_penalty=float(presence_penalty),
                                   frequency_penalty=float(frequency_penalty),
                                   stop=([list(q) for q in stop]
                                         if stop else None),
                                   seed=seed,
                                   t_submit=(now if t_submit is None
                                             else t_submit),
                                   t_queued=now,
                                   trace=(tuple(trace) if trace else None)))
        return rid

    def poll(self) -> list[Completion]:
        """Completions finished since the last poll (ownership transfers)."""
        out, self._done = self._done, []
        return out

    def cancel(self, rid: int) -> str:
        """Best-effort cancel: a queued request is dropped before admission
        ("queued"); a live request's row stops decoding at the next
        retirement pass and completes with ``cancelled=True`` and whatever
        tokens it had ("live"); anything else — already completed or never
        seen — is "unknown". Idempotent: cancelling twice is "unknown" the
        second time."""
        if self._pending is not None and self._pending["req"].id == rid:
            # mid-chunked-prefill: drop the pending admission whole — it
            # was never live, so the completion mirrors the queued shape
            p, self._pending = self._pending, None
            if p["hit_chain"]:          # the temporary hit pins
                self._radix.release(p["hit_chain"])
            if p["span"] is not None:
                self.spans.finish(p["span"], cancelled=True,
                                  chunks=p["chunks"])
            full = (self.prefix or []) + list(p["per_req"])
            self._done.append(Completion(
                id=rid, tokens=full, prompt_len=len(full),
                cancelled=True,
                logprobs=[] if self.track_logprobs else None,
                t_submit=p["req"].t_submit, t_admit=p["req"].t_admit))
            self._stats["cancelled"] += 1
            return "queued"
        for i, req in enumerate(self._queue):
            if req.id == rid:
                del self._queue[i]
                # same shape as admitted completions on a prefix pool:
                # tokens include the shared prefix, prompt_len covers it
                full = (self.prefix or []) + list(req.tokens)
                # logprobs=[] (not None) on tracking pools so the
                # completion shape matches LMServingLoop.cancel
                self._done.append(Completion(
                    id=rid, tokens=full, prompt_len=len(full),
                    cancelled=True,
                    logprobs=[] if self.track_logprobs else None,
                    t_submit=req.t_submit))
                self._stats["cancelled"] += 1
                return "queued"
        for slot, req in self._live.items():
            if req.id == rid:
                # a row whose budget is already exhausted (it finished
                # during the last dispatch and merely awaits retirement)
                # is COMPLETE, not cancellable — labelling it cancelled
                # would mislabel a full stream as a truncated partial
                if int(self._remaining_cursors()[0][slot]) == 0:
                    return "unknown"
                # zeroing the row's budget makes the next
                # `_retire_finished` pass retire it through the normal
                # path; the freed slot admits the next queued prompt
                self._remaining = self._remaining.at[slot].set(0)
                self._rc_invalidate()
                self._cancelled.add(rid)
                self._stats["cancelled"] += 1
                return "live"
        return "unknown"

    def snapshot(self) -> list[dict]:
        """Progress of every LIVE row — id, tokens so far (prompt +
        generated), prompt length — for streaming partial results to
        polling clients. One D2H read; queued requests are not included
        (they have no progress)."""
        if not self._live:
            return []
        cursors = self._remaining_cursors()[1]
        tokens = np.asarray(self._tokens)
        return [{"id": req.id,
                 "tokens": [int(t) for t in tokens[slot][:cursors[slot] + 1]],
                 "prompt_len": len(req.tokens)}
                for slot, req in sorted(self._live.items())]

    def pending(self) -> int:
        return (len(self._queue) + len(self._live)
                + (1 if self._pending is not None else 0))

    def stats(self) -> dict:
        """Serving counters: decode dispatches (``decode_steps`` tokens per
        live row each), requests admitted/completed, generated-token total,
        current occupancy, and the pool's serving configuration (what an
        operator reading `lm_stats` needs to know the pool is actually
        running — GQA width, cache dtype, weight quantization)."""
        m = self.model
        config = {
            "vocab": m.vocab, "dim": m.dim, "depth": m.depth,
            "heads": m.num_heads,
            "kv_heads": m.num_kv_heads or m.num_heads,
            "kv_cache_dtype": m.kv_cache_dtype,
            "quantize": self.quantize,
            "track_logprobs": self.track_logprobs,
            "penalties": self.penalties,
            "prefix_len": len(self.prefix) if self.prefix else 0,
            "decode_steps": self.decode_steps,
            "prompt_len": self.prompt_len, "max_len": self.max_len,
            "kv_block_size": self.kv_block_size,
            "paged_kernel": self.paged_kernel,
            "prefill_chunk": self.prefill_chunk,
            "kv_cache_blocks": (self._block_pool.num_blocks
                                if self._block_pool is not None else 0),
            "scan_layers": self._scan,
            # tensor parallelism: model-axis extent + estimated psum
            # payload per decode step (2 row-parallel reductions per
            # block over a [slots, 1, dim] activation; 0 when TP is off)
            "n_model": self.n_model,
            "tp_collective_bytes": tp_collective_bytes(
                self.model, self.slots, self.n_model),
            # vocab-sharded sampling tail (ISSUE 16): per-row scalar
            # merge payload instead of an all-gathered [S, vocab]; 0
            # when TP is off or the vocab degraded to replicated
            "sampling_collective_bytes": sampling_collective_bytes(
                self.model, self.slots, self.n_model),
        }
        out = dict(self._stats, live=len(self._live),
                   queued=len(self._queue), slots=self.slots,
                   kv_cache_bytes=self._kv_cache_bytes, config=config)
        if self._recurrent:
            out["recurrent_state_bytes"] = self.model.state_bytes(self.slots)
        if self._counts_copy is not None:
            out.update(self._counter_stats())
        if self._radix is not None:
            out["prefix_cache"] = self.prefix_cache_stats()
        return out

    def _counter_stats(self) -> dict:
        """The slot cache's counters over the decode steps so far, read
        from the device now. An expert stack's: the token-picks that fell
        on the experts held here and those offered (live tokens x experts a
        token, a layer); the picks on the busiest held expert of each
        layer, summed, and the mean over a layer's held experts, summed;
        the held experts that took a pick, summed over steps and layers,
        how many there were to take one, and the held experts whose weights
        the steps' branch read: every held expert where a decode step's
        rows take the all-experts branch (`hybrid.reads_every_held_expert`,
        fixed by the slot count), those touched where they are grouped. A
        stack with Mamba-2 or Gated DeltaNet layers: the rows whose state a
        step moved and the rows the slots hold, such a layer a step."""
        c = {k: np.concatenate([np.asarray(x, np.int64) for x in v])
             for k, v in self._counts_copy.items()}      # [layers, ...]
        out = {}
        if "expert_load" in c:
            load, steps = c["expert_load"], c["expert_steps"]
            out.update(
                expert_tokens_routed=int(load.sum()),
                expert_tokens_offered=int(
                    steps[:, 2].sum() * self.model.experts_per_token),
                expert_load_max=int(load.max(axis=1).sum()),
                expert_load_mean=float(load.mean(axis=1).sum()),
                experts_touched=int(steps[:, 0].sum()),
                experts_touchable=int(steps[:, 1].sum() * load.shape[1]))
            out["experts_read"] = out[
                "experts_touchable" if reads_every_held_expert(
                    self._dec, self.slots) else "experts_touched"]
        if "state_rows" in c:
            moved, held = c["state_rows"].sum(axis=0)
            out.update(state_rows_moved=int(moved),
                       state_rows_held=int(held))
        return out

    def prefix_cache_stats(self) -> dict:
        """Radix prefix-cache gauges (only meaningful on kv_block_size
        pools): hit rate over admissions, prompt tokens whose prefill
        was skipped, block-pool occupancy, tree churn counters, plus
        the cluster prefix-cache counters (zeros when the cluster tier
        is off, so dashboards see a stable gauge set)."""
        cp = self.cluster_prefix
        out = {
            "prefix_hit_rate": (self._pc_hits / self._pc_lookups
                                if self._pc_lookups else 0.0),
            "lookups": self._pc_lookups,
            "hits": self._pc_hits,
            "cached_tokens_saved": self._pc_tokens_saved,
            "kv_blocks_free": self._block_pool.num_free,
            "kv_blocks_used": self._block_pool.num_used,
            "evictions": self._radix.evictions,
            "insert_skips": self._radix.insert_skips,
            "inserted_blocks": self._radix.inserted_blocks,
            "evict_nodes_walked": self._radix.evict_nodes_walked,
            "blocks_written": self._block_pool.blocks_written,
            "blocks_gathered": self._block_pool.blocks_gathered,
            "nodes": self._radix.num_nodes(),
            "prefix_remote_hits": 0,
            "prefix_published_chains": 0,
            "prefix_warm_blocks": 0,
            "prefix_fetch_bytes": 0,
        }
        if cp is not None:
            out.update(cp.stats())
        return out

    # -- cluster prefix cache (serve/cluster_prefix.py) -------------------

    def _cluster_fetch(self, per_req: list, local: int, want: int) -> int:
        """Probe the ring for a chain longer than the ``local`` radix
        depth, fetch the missing depths [local, found) and graft them.
        Returns new blocks grafted (0 = miss/failure — the admission
        proceeds on its local hit)."""
        cp = self.cluster_prefix
        bs = self.kv_block_size
        depth = cp.probe(per_req[:want * bs], start_depth=local)
        if depth <= local:
            return 0
        fetched = cp.fetch(per_req, local, depth)
        if not fetched:
            return 0
        wrote = self._radix.graft(per_req, fetched, local)
        if wrote:
            cp.remote_hits += 1
        return wrote

    def prefix_probe(self, tokens: list[int]) -> dict:
        """`prefix_probe` verb: local radix depth vs the deepest
        published depth for this prompt. Pure read (the lookup only
        touches LRU stamps)."""
        cp = self._require_cluster()
        local = len(self._radix.lookup(list(tokens)))
        remote = cp.probe(list(tokens))
        return {"local_blocks": local, "remote_blocks": remote,
                "namespace": cp.namespace,
                "block_size": self.kv_block_size}

    def prefix_warm(self, tokens: list[int] | None = None,
                    tenant: str | None = None) -> dict:
        """`prefix_fetch` verb: pull published chains into the radix
        tree WITHOUT an admission — the warm-at-spawn primitive. With
        ``tenant`` (and no tokens) the per-tenant SDFS warm index names
        the prefixes to pull. Fetched blocks count as ``warm_blocks``;
        grafting is naturally idempotent (already-present chunks are
        reused), so a replayed warm converges."""
        cp = self._require_cluster()
        targets = []
        if tokens is not None:
            targets.append([int(t) for t in tokens])
        elif tenant is not None:
            targets = [e.get("tokens", []) for e in
                       cp.tenant_entries(str(tenant))]
        else:
            raise ValueError("prefix_fetch needs tokens or tenant")
        fetched_blocks = 0
        for toks in targets:
            want = len(toks) // self.kv_block_size
            if want < 1:
                continue
            local = len(self._radix.lookup(toks))
            if local >= want:
                continue
            depth = cp.probe(toks[:want * self.kv_block_size],
                             start_depth=local)
            if depth <= local:
                continue
            blobs = cp.fetch(toks, local, depth)
            if blobs:
                fetched_blocks += self._radix.graft(toks, blobs, local)
        cp.warm_blocks += fetched_blocks
        return {"fetched_blocks": fetched_blocks,
                "targets": len(targets), "bytes": cp.fetch_bytes}

    def prefix_publish(self, tokens: list[int] | None = None,
                       tenant: str | None = None) -> dict:
        """`prefix_publish` verb: push cached chains to the ring. With
        ``tokens``, the longest local chain for that prompt; without,
        every root-to-leaf path in the radix tree (min-hits policy
        bypassed — an explicit publish is an operator decision)."""
        cp = self._require_cluster()
        chains = []
        if tokens is not None:
            chain = self._radix.lookup([int(t) for t in tokens])
            if chain:
                chains.append(chain)
        else:
            stack = [[nd] for nd in
                     self._radix._root.children.values()]
            while stack:
                path = stack.pop()
                kids = path[-1].children
                if not kids:
                    chains.append(path)
                    continue
                for nd in kids.values():
                    stack.append(path + [nd])
        published = blocks = 0
        for chain in chains:
            toks = [t for nd in chain for t in nd.chunk]
            out = cp.publish(
                toks, len(chain),
                (lambda ch: lambda j: self._block_pool.read_block(
                    ch[j].block))(chain),
                tenant=tenant, force=True)
            published += out["published"]
            blocks += out["blocks"]
        return {"published_blocks": published, "chains": len(chains),
                "blocks": blocks}

    def _refuse_recurrent(self, what: str) -> None:
        if self._recurrent:
            raise UnsupportedStack(
                f"{what}: a chain of K/V blocks cannot rebuild a slot of a "
                "stack with recurrent or block-sparse layers")

    def _require_cluster(self):
        self._refuse_recurrent("cluster prefix cache")
        if self.cluster_prefix is None or self._radix is None:
            raise ValueError("pool has no cluster prefix cache "
                             "(serve with cluster_prefix= and "
                             "kv_block_size > 0)")
        return self.cluster_prefix

    # -- kv handoff (DistServe prefill→decode ship, ISSUE 18) -------------
    #
    # A prefill-role replica fills the block-aligned head of a long
    # prompt, encodes the populated blocks as KVC1 blobs, and the decode
    # replica grafts them into its own radix tree — point-to-point over
    # the transport, no SDFS round-trip. The handoff state machine
    # (prefilling → shipping → adopted, with fallback) lives in
    # `serve/lm_manager.py`; these three verbs are its pool-local legs
    # and are gated only on the radix tier (kv_block_size > 0), NOT the
    # cluster prefix cache — handoff is transport-direct by design.

    def _require_handoff(self) -> None:
        self._refuse_recurrent("kv handoff")
        if self._radix is None:
            raise ValueError("pool has no KV block tier "
                             "(serve with kv_block_size > 0)")

    def handoff_probe(self, tokens: list[int]) -> dict:
        """`kv_handoff` probe leg: the local radix depth for ``tokens``
        plus the pool's block geometry, so a prefill replica ships only
        the block suffix this replica doesn't already hold (delta-only
        ship — prefix-cache hits compose). Pure read (the lookup only
        touches LRU stamps)."""
        self._require_handoff()
        toks = [int(t) for t in tokens]
        bs = self.kv_block_size
        return {"depth": len(self._radix.lookup(toks)),
                "want": max(0, (len(toks) - 1) // bs),
                "block_size": bs}

    def _prefill_head(self, head: list[int], hit_chain: list) -> list:
        """Prefill the missing block-aligned suffix of ``head`` (the
        handoff export's fill leg) and insert the chain — `_admit`'s
        non-chunked prefill branches with the block head in place of the
        full prompt, so paged/gathered/prefix pools all fill through
        their own machinery. Returns the ACQUIRED chain for ``head``
        (caller releases)."""
        pl = len(self.prefix) if self.prefix else 0
        bs = self.kv_block_size
        hit = len(hit_chain) * bs
        head_true = len(head)
        while True:
            rest = head_true - hit
            bucket = next(
                (b for b in self.prompt_buckets
                 if b >= rest and pl + hit + b <= self.max_len), None)
            if bucket is not None:
                break
            if hit <= 0:
                raise ValueError(
                    f"no prompt bucket fits a {head_true}-token "
                    "handoff head")
            hit -= bs
        hit_chain = hit_chain[:hit // bs]
        if hit_chain:
            self._radix.acquire(hit_chain)
        try:
            suffix = np.zeros((1, bucket), np.int32)
            suffix[0, :head_true - hit] = head[hit:]
            self._stats["prefill_tokens"] += bucket
            if self._paged and hit:
                tab = np.asarray([[nd.block for nd in hit_chain]],
                                 np.int32)
                row_cache, _ = _prefill_suffix_paged(
                    self._prefill_model, self.params, self._prefix_cache,
                    jnp.asarray(suffix), jnp.int32(head_true - hit),
                    pl + hit, bucket, jnp.asarray(tab),
                    jnp.asarray([hit], np.int32),
                    self._block_pool.kv_pages(), start=pl,
                    kernel=self.paged_kernel,
                    interpret=self._paged_interpret)
            elif hit:
                gathered = self._block_pool.gather(
                    [nd.block for nd in hit_chain])
                pre = (concat_kv_prefix(
                    self._prefix_cache, gathered,
                    token_axis=2 if self._scan else 1)
                    if self.prefix else gathered)
                row_cache, _ = _prefill_suffix(
                    self._prefill_model, self.params, pre,
                    jnp.asarray(suffix), jnp.int32(head_true - hit),
                    pl + hit, bucket)
            elif self.prefix:
                row_cache, _ = _prefill_suffix(
                    self._prefill_model, self.params, self._prefix_cache,
                    jnp.asarray(suffix), jnp.int32(head_true), pl, bucket)
            else:
                row_cache, _ = _prefill(
                    self._prefill_model, self.params, jnp.asarray(suffix),
                    jnp.int32(head_true), bucket)
            return self._radix.insert(head, row_cache, pl)
        finally:
            if hit_chain:
                self._radix.release(hit_chain)

    def handoff_export(self, tokens: list[int], from_depth: int = 0,
                       trace: tuple | None = None) -> dict:
        """`kv_handoff` export leg (prefill replica): ensure the radix
        tree holds the full usable block chain for ``tokens`` —
        prefilling the missing block-aligned region if needed — then
        encode depths [``from_depth``, want) as KVC1 blobs. ``want``
        always leaves ≥ 1 suffix token for the decode side's own
        admission prefill (the same cap `_admit` applies), so the first
        generated token's logits are computed there, token-exactly."""
        self._require_handoff()
        toks = [int(t) for t in tokens]
        bs = self.kv_block_size
        want = max(0, (len(toks) - 1) // bs)
        from_depth = max(0, int(from_depth))
        if want <= from_depth:
            return {"blobs": [], "depth": from_depth, "blocks": 0,
                    "bytes": 0, "block_size": bs}
        from idunno_tpu.store.kv_chain import encode_block
        t0 = (self.spans.clock()
              if self.spans is not None and trace else None)
        head = toks[:want * bs]
        chain = self._radix.lookup(head)
        if len(chain) < want:
            chain = self._prefill_head(head, chain)
        else:
            self._radix.acquire(chain)
        try:
            if len(chain) < want:
                raise ValueError(
                    f"handoff export covered {len(chain)} of {want} "
                    "blocks (block pool exhausted; ship refused)")
            blobs, nbytes = [], 0
            for j in range(from_depth, want):
                chunk = head[j * bs:(j + 1) * bs]
                blob = encode_block(
                    {"tokens": chunk, "depth": j, "block_size": bs},
                    self._block_pool.read_block(chain[j].block))
                blobs.append(blob)
                nbytes += len(blob)
        finally:
            self._radix.release(chain)
        self._stats["kv_handoff_requests"] += 1
        self._stats["kv_handoff_bytes"] += nbytes
        if t0 is not None:
            self.spans.record(
                "lm.handoff_export", trace=trace[0], parent=trace[1],
                t_start=t0, attrs={"blocks": want - from_depth,
                                   "from_depth": from_depth,
                                   "bytes": nbytes})
        return {"blobs": blobs, "depth": from_depth,
                "blocks": want - from_depth, "bytes": nbytes,
                "block_size": bs}

    def handoff_adopt(self, tokens: list[int], blobs: list[bytes],
                      start_depth: int = 0,
                      trace: tuple | None = None) -> dict:
        """`kv_handoff` adopt leg (decode replica): decode each KVC1
        blob against the expected token chunk — ``expect_tokens=`` makes
        a stale/wrong-content blob a typed refusal, never a graft — and
        splice the verified blocks via `RadixPrefixCache.graft`, which
        REUSES chunks already held. A duplicated/replayed adopt therefore
        converges on the same block-pool state, and the next admission's
        radix lookup turns the shipped range into a prefix hit: zero
        re-prefill for shipped blocks, structurally."""
        self._require_handoff()
        toks = [int(t) for t in tokens]
        bs = self.kv_block_size
        start_depth = max(0, int(start_depth))
        t0 = (self.spans.clock()
              if self.spans is not None and trace else None)
        from idunno_tpu.store.kv_chain import decode_block
        fetched, nbytes = [], 0
        for i, blob in enumerate(blobs):
            j = start_depth + i
            chunk = toks[j * bs:(j + 1) * bs]
            if len(chunk) < bs:
                raise ValueError(
                    f"handoff blob at depth {j} extends past the "
                    "prompt's full blocks")
            _, arrays = decode_block(blob, expect_tokens=chunk)
            fetched.append((chunk, arrays))
            nbytes += len(blob)
        wrote = self._radix.graft(toks, fetched, start_depth)
        self._stats["kv_handoff_bytes"] += nbytes
        depth = len(self._radix.lookup(toks))
        if t0 is not None:
            self.spans.record(
                "lm.handoff_adopt", trace=trace[0], parent=trace[1],
                t_start=t0, attrs={"blocks": len(fetched), "wrote": wrote,
                                   "start_depth": start_depth,
                                   "bytes": nbytes, "depth": depth})
        return {"adopted": len(fetched), "wrote": wrote,
                "depth": depth, "bytes": nbytes}

    def handoff_fallback(self) -> dict:
        """Count a ship that degraded to decode-side prefill (the
        manager's fallback transition); the request itself is unharmed —
        it forwards through the normal path and re-prefills there."""
        self._require_handoff()
        self._stats["kv_handoff_fallbacks"] += 1
        return {"fallbacks": self._stats["kv_handoff_fallbacks"]}

    # -- serving loop -----------------------------------------------------

    def _remaining_cursors(self) -> tuple[np.ndarray, np.ndarray]:
        """Host view of (remaining, cursors) — one stacked D2H transfer,
        cached until `_rc_invalidate` (every device-side mutation site:
        dispatch, admission, cancel, stop-truncation). A fetch inside a
        traced step is an `lm.readback` span: the wait for the chip and
        the copy."""
        if self._rc_cache is None:
            with (self._span("lm.readback") if self._step_span is not None
                  else NO_SPAN):
                self._rc_cache = np.asarray(
                    jnp.stack([self._remaining, self._cursors]))
        return self._rc_cache[0], self._rc_cache[1]

    def _rc_invalidate(self) -> None:
        self._rc_cache = None

    def _retire_finished(self) -> None:
        if not self._live:
            return
        remaining, cursors = self._remaining_cursors()
        finished = [s for s, r in enumerate(remaining)
                    if r == 0 and s in self._live]
        if not finished:
            return
        with self._span("lm.retire", rows=len(finished)):
            for slot in finished:
                req = self._live.pop(slot)
                total = int(cursors[slot]) + 1
                row = np.asarray(self._tokens[slot])[:total]
                was_cancelled = req.id in self._cancelled
                self._cancelled.discard(req.id)
                lps = None
                if self.track_logprobs:
                    lp_row = np.asarray(self._logprobs[slot])[:total]
                    lps = [float(x) for x in lp_row[len(req.tokens):]]
                done = Completion(
                    id=req.id, tokens=[int(t) for t in row],
                    prompt_len=len(req.tokens),
                    service_s=self.clock() - req.t_admit,
                    cancelled=was_cancelled, logprobs=lps,
                    cold_start=req.cold, t_submit=req.t_submit,
                    t_admit=req.t_admit, t_first=req.t_first,
                    n_first=req.n_first, t_last=req.t_last)
                self._done.append(done)
                self._retired.append((done, req))  # `_stamp` ends the step
                if not was_cancelled:
                    self._stats["completed"] += 1
                self._stats["tokens_generated"] += total - len(req.tokens)
                if self._radix is not None:   # unpin the request's chain
                    chain = self._held.pop(req.id, None)
                    if chain:
                        self._radix.release(chain)

    def _child_span(self, parent, name: str, t_start, **attrs) -> None:
        """A child of a traced admission's `lm.prefill`, from ``t_start``
        to now; nothing for an untraced one (``parent`` None)."""
        if parent is not None:
            self.spans.record(name, trace=parent.trace_id,
                              parent=parent.span_id, t_start=t_start,
                              attrs=attrs)

    def _gather_hit(self, hit_chain: list, span) -> Any:
        """The radix hit's blocks as one contiguous prefix cache (the
        gathered path's copy; paged pools read through the table)."""
        t0 = span and self.spans.clock()
        gathered = self._block_pool.gather([nd.block for nd in hit_chain])
        self._child_span(span, "kv.gather", t0, blocks=len(hit_chain))
        return gathered

    def _admit(self) -> None:
        if self._pending is not None:
            # a chunked prefill is in flight: its slot is reserved and
            # admissions stay FIFO behind it (`step` advances it by one
            # chunk per call, decode dispatches landing in between)
            return
        # a row the dispatch in flight may finish still holds its slot:
        # the host has not read that dispatch back
        free = [s for s in range(self.slots) if s not in self._live]
        while free and self._queue:
            slot = free.pop(0)
            req = self._queue.popleft()
            req.t_admit = self.clock()
            req.cold = not self._dispatched_ever
            per_req = list(req.tokens)      # pre-prefix request tokens
            suffix_true = len(per_req)
            # `lm.prefill` is the admission's HOST time, from here (the
            # radix lookup) to the slot splice in `_finish_admission`:
            # what the host spends enqueueing the work. The device's time
            # for the same work is the prefill programs' in a profile.
            sp = None
            if self.spans is not None and req.trace:
                self.spans.record(
                    "lm.slot_wait", trace=req.trace[0], parent=req.trace[1],
                    t_start=req.t_queued, t_end=req.t_admit,
                    attrs={"id": req.id})
                sp = self.spans.start(
                    "lm.prefill", trace=req.trace[0], parent=req.trace[1],
                    attrs={"id": req.id, "prompt_len": suffix_true})
            pl = len(self.prefix) if self.prefix else 0
            # radix prefix cache: longest block-aligned cached chain for
            # this prompt. The hit is capped one block short of the full
            # prompt so the suffix apply always has ≥ 1 real token (the
            # first-token logits come from it), and shrunk block-by-
            # block until prefix+hit+bucket fits max_len (hit 0 always
            # fits — the plain path's own guarantee).
            hit, hit_chain = 0, []
            if self._recurrent:
                if self._radix is not None:     # never a hit: see __init__
                    self._stats["prefix_skipped_recurrent"] += 1
            elif self._radix is not None:
                self._pc_lookups += 1
                t_lookup = sp and self.spans.clock()
                hit_chain = self._radix.lookup(per_req)
                bs = self.kv_block_size
                want = (suffix_true - 1) // bs   # usable depth in blocks
                # cluster prefix cache: a local miss (or shorter local
                # hit) probes the ring for a longer published chain and
                # grafts ONLY the missing block suffix into the radix
                # tree; the re-lookup below then extends the hit so the
                # prefill covers just the remainder. Degrades to the
                # local hit on any store/transport failure.
                if (self.cluster_prefix is not None
                        and len(hit_chain) < want):
                    if self._cluster_fetch(per_req, len(hit_chain), want):
                        hit_chain = self._radix.lookup(per_req)
                self._child_span(sp, "kv.lookup", t_lookup,
                                 blocks_hit=len(hit_chain))
                hit = min(len(hit_chain) * bs,
                          ((suffix_true - 1) // bs) * bs)
            while True:
                rest = suffix_true - hit
                suffix_bucket = next(
                    (b for b in self.prompt_buckets
                     if b >= rest and pl + hit + b <= self.max_len), None)
                if suffix_bucket is not None:
                    break
                if hit <= 0:   # unreachable: validate()/__init__ checks
                    raise RuntimeError(
                        f"no prompt bucket fits {suffix_true} tokens")
                hit -= self.kv_block_size
            if hit:
                hit_chain = hit_chain[:hit // self.kv_block_size]
                # pin before gather: eviction (from a concurrent-looking
                # insert later this admission) must not free these
                self._radix.acquire(hit_chain)
                self._pc_hits += 1
                self._pc_tokens_saved += hit
            elif hit_chain:
                hit_chain = []
            if sp is not None:
                sp.attrs.update(prefix_hit=hit, bucket=suffix_bucket)
                if self._step_span is not None:   # the step that ran it
                    sp.attrs["step"] = self._step_span.span_id
            suffix = np.zeros((1, suffix_bucket), np.int32)
            suffix[0, :suffix_true - hit] = per_req[hit:]
            self._stats["prefill_tokens"] += suffix_bucket
            # paged pools never gather the hit back: the batch-1 table
            # (exact chain width — the compile set is already keyed on
            # hit via prefix_len) lets the suffix attend the hit region
            # through the blocks
            tab_np = plen_np = None
            if self._paged and hit:
                nb = hit // self.kv_block_size
                tab_np = np.asarray(
                    [[nd.block for nd in hit_chain[:nb]]], np.int32)
                plen_np = np.asarray([hit], np.int32)
            if self.prefill_chunk and suffix_bucket > self.prefill_chunk:
                # chunked prefill: park the admission as `_pending` and
                # apply `prefill_chunk` tokens per step() call, decode
                # dispatches of resident rows landing between chunks.
                # The scalar-cursor apply writes K/V per position and
                # masks per query, so N chunks build the identical row
                # cache and last-token logits as the one-shot apply.
                total = pl + hit + suffix_bucket
                if hit and tab_np is None:
                    gathered = self._gather_hit(hit_chain, sp)
                    pre = (concat_kv_prefix(
                        self._prefix_cache, gathered,
                        token_axis=2 if self._scan else 1)
                        if self.prefix else gathered)
                else:   # paged hit (hit region stays zero) or no hit
                    pre = self._prefix_cache if self.prefix else None
                if sp is not None:
                    sp.attrs["chunked"] = True
                self._pending = {
                    "req": req, "slot": slot,
                    "cache": _set_valid(
                        _chunk_init(self._prefill_model, pre, total),
                        pl + suffix_true),
                    "suffix": suffix, "true": suffix_true - hit,
                    "suffix_true": suffix_true, "cursor0": pl + hit,
                    "bucket": suffix_bucket, "off": 0, "hit": hit,
                    "hit_chain": hit_chain, "per_req": per_req, "pl": pl,
                    "last": None, "total": total, "tables": tab_np,
                    "plen": plen_np, "span": sp, "chunks": 0}
                self._advance_prefill()   # first chunk lands this step
                return
            if hit and tab_np is not None:
                row_cache, last_logits = _prefill_suffix_paged(
                    self._prefill_model, self.params, self._prefix_cache,
                    jnp.asarray(suffix), jnp.int32(suffix_true - hit),
                    pl + hit, suffix_bucket, jnp.asarray(tab_np),
                    jnp.asarray(plen_np), self._block_pool.kv_pages(),
                    start=pl, kernel=self.paged_kernel,
                    interpret=self._paged_interpret)
            elif hit:
                gathered = self._gather_hit(hit_chain, sp)
                # stacked caches carry the token axis at 2 (depth, batch,
                # token, ...) instead of the per-block layout's 1
                pre = (concat_kv_prefix(self._prefix_cache, gathered,
                                        token_axis=2 if self._scan else 1)
                       if self.prefix else gathered)
                row_cache, last_logits = _prefill_suffix(
                    self._prefill_model, self.params, pre,
                    jnp.asarray(suffix), jnp.int32(suffix_true - hit),
                    pl + hit, suffix_bucket)
            elif self.prefix:
                row_cache, last_logits = _prefill_suffix(
                    self._prefill_model, self.params, self._prefix_cache,
                    jnp.asarray(suffix), jnp.int32(suffix_true), pl,
                    suffix_bucket)
            else:
                row_cache, last_logits = _prefill(
                    self._prefill_model, self.params, jnp.asarray(suffix),
                    jnp.int32(suffix_true), suffix_bucket)
            self._finish_admission(
                req, slot, row_cache, last_logits, hit=hit,
                hit_chain=hit_chain, per_req=per_req, pl=pl,
                suffix_true=suffix_true, suffix_bucket=suffix_bucket,
                suffix=suffix, span=sp)
            # max_new == 1: the prefill's token was the only one; the step's
            # one `_retire_finished` pass, at its end, retires the row
            # before any decode dispatch has it

    def _advance_prefill(self) -> None:
        """Apply ONE chunk of the pending chunked admission. Called once
        per `step`, after the step's decode dispatch is enqueued and
        before `_admit`, so every chunk of a long prompt has a decode
        dispatch of the resident rows between it and the next — the
        fairness property `tests/test_serve_lm.py` asserts — and queues
        behind that dispatch on the chip."""
        p = self._pending
        n = min(self.prefill_chunk, p["bucket"] - p["off"])
        tok = jnp.asarray(p["suffix"][:, p["off"]:p["off"] + n])
        cursor = jnp.int32(p["cursor0"] + p["off"])
        if p["tables"] is not None:
            cache, logits = _prefill_chunk(
                self._prefill_model, self.params, p["cache"], tok,
                cursor, p["total"], jnp.asarray(p["tables"]),
                jnp.asarray(p["plen"]), self._block_pool.kv_pages(),
                start=p["pl"], kernel=self.paged_kernel,
                interpret=self._paged_interpret)
        else:
            cache, logits = _prefill_chunk(
                self._prefill_model, self.params, p["cache"], tok,
                cursor, p["total"], None, None, None)
        p["cache"] = cache
        # the first-token logits live at true-1 (suffix coordinates) —
        # capture them from whichever chunk covers that position
        t = p["true"]
        if p["off"] <= t - 1 < p["off"] + n:
            # (one row where the stack computed that position's alone)
            p["last"] = logits[0, min(t - 1 - p["off"],
                                      logits.shape[1] - 1)]
        p["chunks"] += 1
        self._stats["prefill_chunks"] += 1
        if p["span"] is not None:
            self.spans.record(
                "lm.prefill_chunk", trace=p["span"].trace_id,
                parent=p["span"].span_id,
                attrs={"id": p["req"].id, "chunk": p["chunks"] - 1,
                       "of": -(-p["bucket"] // self.prefill_chunk),
                       "tokens": int(n)})
        p["off"] += n
        if p["off"] >= p["bucket"]:
            self._pending = None
            self._finish_admission(
                p["req"], p["slot"], p["cache"], p["last"], hit=p["hit"],
                hit_chain=p["hit_chain"], per_req=p["per_req"],
                pl=p["pl"], suffix_true=p["suffix_true"],
                suffix_bucket=p["bucket"], suffix=p["suffix"],
                span=p["span"], chunks=p["chunks"])

    def _finish_admission(self, req, slot: int, row_cache, last_logits, *,
                          hit: int, hit_chain: list, per_req: list,
                          pl: int, suffix_true: int, suffix_bucket: int,
                          suffix: np.ndarray, span=None,
                          chunks: int = 0) -> None:
        """Everything after the row cache exists: radix insert + pinning,
        paged table install, slot splice, per-slot sampler state, spans.
        Shared verbatim by the one-shot (`_admit`) and chunked
        (`_advance_prefill`) prefill paths so they cannot drift."""
        if self._radix is not None and not self._recurrent:
            # seed/extend the tree from this prefill's row cache and
            # pin the request's full chain for its lifetime (insert
            # returns it acquired); the temporary hit pins drop. On the
            # paged path the hit region of `row_cache` is ZERO — insert
            # walks the existing (hit) nodes without writing them, so
            # zeros never reach the blocks, and the returned chain keeps
            # the table's blocks pinned in `_held`.
            rx, bp = self._radix, self._block_pool
            t_insert = span and self.spans.clock()
            before = (bp.blocks_written, rx.evictions,
                      rx.evict_nodes_walked)
            chain = rx.insert(per_req, row_cache, pl)
            self._child_span(
                span, "kv.insert", t_insert,
                blocks_written=bp.blocks_written - before[0],
                evicted=rx.evictions - before[1],
                nodes_walked=rx.evict_nodes_walked - before[2])
            if hit_chain:
                self._radix.release(hit_chain)
            if chain:
                self._held[req.id] = chain
            cp = self.cluster_prefix
            if (cp is not None and chain
                    and hit // self.kv_block_size >= cp.publish_min_hits):
                # publish the request's full chain: a local hit of at
                # least `publish_min_hits` blocks proved the prompt head
                # is shared (0 = publish every inserted chain). Content-
                # addressed names make a replayed publish converge, and
                # every failure degrades to a skip (cp.errors).
                cp.publish(per_req, len(chain),
                           lambda j: self._block_pool.read_block(
                               chain[j].block))
        # (attribute, index, value): the slot's entries of the pool's
        # per-slot arrays, all set by one program at the end (`_set_rows`)
        sets = []
        if self._paged:
            nb = hit // self.kv_block_size
            tab = np.zeros((self._max_chain,), np.int32)
            if nb:
                tab[:nb] = [nd.block for nd in hit_chain[:nb]]
                # the gathered path would have copied these blocks into
                # the contiguous prefix at admission — the win the gauge
                # counts
                self._stats["kv_gather_bytes_saved"] += (
                    nb * self._block_pool.bytes_per_block)
            sets += [("_tables", slot, tab), ("_plens", slot, hit)]
        if hit or self.prefix:
            # downstream state (tokens row, cursors, prompt_len,
            # stop/logprob regions) sees the FULL prompt
            full = np.zeros((1, pl + hit + suffix_bucket), np.int32)
            if self.prefix:
                full[0, :pl] = self.prefix
                req = dataclasses.replace(
                    req, tokens=self.prefix + per_req)
            full[0, pl:pl + suffix_true] = per_req
            prompt, true_len = full, pl + suffix_true
            bucket = pl + hit + suffix_bucket
        else:
            prompt, true_len, bucket = suffix, suffix_true, suffix_bucket
        temp = jnp.float32(req.temperature)
        topp = jnp.float32(req.top_p)
        topk = jnp.int32(req.top_k)
        seed = req.id if req.seed is None else req.seed
        first, key = _pick_first(last_logits, temp,
                                 jax.random.PRNGKey(seed), topp, topk)
        t_splice = span and self._recurrent and self.spans.clock()
        self._tokens, self._cache = _insert(
            self._tokens, self._cache, row_cache, jnp.asarray(prompt),
            first, jnp.int32(true_len), jnp.int32(slot), bucket,
            stacked=self._scan)
        if self._in_flight is not None:
            # the slot sat out the dispatch in flight: over it the cursor
            # did not move (`_count_context` differences the two)
            self._in_flight[slot] = true_len
        if self._recurrent:
            # the row's state, window and pooled keys went into the slot
            # with its K/V, whole: nothing of the slot's last tenant is left
            self._seen_cursor[slot] = true_len
            self._child_span(span, "state.splice", t_splice,
                             state_bytes=self.model.state_bytes(1))
        rem = req.max_new - 1
        if self.eos_id is not None and int(first) == self.eos_id:
            rem = 0                   # the prompt's very next token
        sets += [("_cursors", slot, true_len), ("_remaining", slot, rem),
                 ("_temps", slot, temp), ("_top_ps", slot, topp),
                 ("_top_ks", slot, topk), ("_keys", slot, key)]
        if self.track_logprobs:   # the prefill-picked token's logprob
            lp0 = jax.nn.log_softmax(
                last_logits.astype(jnp.float32))[first]
            sets.append(("_logprobs", (slot, true_len), lp0))
        if self.penalties:   # fresh row; the first token counts.
            # validate() guarantees zero penalties off-flag, so the
            # buffers are only ever touched when the kernel reads them
            sets += [("_pres", slot, req.presence_penalty),
                     ("_freq", slot, req.frequency_penalty),
                     ("_counts", slot, jax.nn.one_hot(
                         first, self.model.vocab, dtype=jnp.int32))]
        names, at, values = zip(*sets)
        for name, new in zip(names, _set_rows(
                tuple(getattr(self, n) for n in names), at, values)):
            setattr(self, name, new)
        self._rc_invalidate()
        # the step's own dispatch is counted already: the row's first is
        # the next step's
        req.dispatch0 = self._stats["dispatches"]
        if span is not None:
            # the span opened at admission closes here; a chunked one has
            # the per-chunk records as children besides
            self.spans.finish(span, **({"chunks": chunks} if chunks else {}))
            # `lm.decode` chains under the prefill
            req = dataclasses.replace(
                req, trace=(req.trace[0], span.span_id))
            self._new_traced.append(req)
        self._live[slot] = req
        self._stats["admitted"] += 1
        if self._in_flight is not None:
            self._stats["admissions_overlapped"] += 1

    def _apply_stops(self) -> None:
        """Host-side stop-sequence pass (after a dispatch, before
        retirement): for each live row that asked for stop sequences,
        scan its GENERATED tokens for the earliest-ending match and
        truncate the row there — cursor moved back to the match's last
        token, remaining zeroed, so the normal retire pass completes it
        (a truncated row is retired before any further scan). Tokens
        decoded past the stop inside the same dispatch are discarded.
        The stop sequence itself is KEPT in the output, like eos_id.

        Each pass scans only the tokens a single dispatch can have added
        (plus a max-seq-1 overlap), so the per-dispatch host cost is
        O(new tokens), statelessly: any match wholly inside the
        previously-scanned region was caught by an earlier pass."""
        stops = {slot: req.stop for slot, req in self._live.items()
                 if req.stop}
        if not stops:
            return
        bound = self.decode_steps
        cursors = self._remaining_cursors()[1]
        for slot, seqs in stops.items():
            gen_start = len(self._live[slot].tokens)
            end = int(cursors[slot]) + 1
            overlap = max(len(q) for q in seqs) - 1
            # bound + 1, not bound: the first post-admission dispatch has
            # bound+1 unscanned tokens (the admission-picked token plus
            # `bound` decode tokens) — without the +1 a length-1 stop
            # equal to the FIRST generated token is never seen
            lo = max(gen_start, end - bound - 1 - overlap)
            row = np.asarray(self._tokens[slot])[:end].tolist()
            best = None                      # earliest END of any match
            for seq in seqs:
                n = len(seq)
                for at in range(lo, end - n + 1):
                    if row[at:at + n] == list(seq):
                        best = at + n if best is None else min(best,
                                                               at + n)
                        break                # earliest for THIS seq found
            if best is None:
                continue
            self._cursors = self._cursors.at[slot].set(best - 1)
            self._remaining = self._remaining.at[slot].set(0)
            self._rc_invalidate()

    def step(self) -> int:
        """One turn of the pool, the decode dispatch first: enqueue one
        dispatch (``decode_steps`` tokens for every row live now) and do
        not wait for it; admit queued prompts into the slots that were
        free before it (one chunk of a pending chunked admission first),
        the admission's host work running while the chip runs the
        dispatch and its programs queueing behind it; then wait for the
        chip ONCE, retire what finished and stamp. A row admitted here has
        its prefill's token at the step's end and joins the NEXT step's
        dispatch; with no row live there is no dispatch to hide behind and
        the step admits, waits and stamps. The served tokens do not depend
        on the order: rows are independent in every program.
        Returns live rows + still-queued requests — 0 means drained (a
        max_new=1 admission can retire instantly, leaving 0 live rows with
        the queue non-empty, so live alone would end a client loop early)."""
        with self._span("lm.step") as st:
            self._step_span = st
            try:
                return self._step(st)
            finally:
                self._step_span = self._in_flight = None

    def _span(self, name: str, **attrs):
        """A span of the pool's own timeline, under the running `lm.step`
        (`lm.step` itself: under the serving loop's iteration); nothing
        and no profiler annotation where no store is wired."""
        if self.spans is None:
            return NO_SPAN
        trace, parent = self.step_ctx or (
            f"t:{self.spans.node}:loop:bare", None)
        if self._step_span is not None:
            parent = self._step_span.span_id
        return loop_span(self.spans, name, trace, parent, **attrs)

    def _retire_synced(self, after: str) -> None:
        """`_retire_finished`; where the step's dispatch is in flight
        (`_in_flight`), after its counters and `_apply_stops`. With the
        cursors' host copy stale, the read-back returns only once the chip
        has finished everything enqueued before it. A step calls it twice: at its start
        (``after`` "cancel": it waits only if a cancel made the copy stale,
        and nothing is in flight then) and at its end ("dispatch": the
        step's one wait for the chip, covering the dispatch and every
        admission program queued behind it, or an empty pool's admission
        alone). Each wait is an `lm.step.sync` span; the counters are an
        `lm.count` inside it (the wait an `lm.readback` in that, where a
        counter fetches first)."""
        blocks = self._rc_cache is None and bool(self._live)
        with self._span("lm.step.sync", after=after) if blocks else NO_SPAN:
            if self._in_flight is not None:
                if self._sparse or self._ladder is not None:
                    with self._span("lm.count"):
                        if self._sparse:
                            self._count_attended()
                        if self._ladder is not None:
                            self._count_context(self._in_flight)
                self._apply_stops()
            self._retire_finished()

    def _count_attended(self) -> None:
        """After a dispatch on a hybrid stack: what its sparse layers'
        queries attended and what they had in context, a token a live row
        a decode step, from the cursors the dispatch left and those the
        host last saw (the model's geometry says what a context of n
        tokens attends)."""
        cursors = self._remaining_cursors()[1]
        for slot in self._live:
            now, was = int(cursors[slot]), self._seen_cursor[slot]
            if now > was:
                ctx = np.arange(was + 1, now + 1)
                self._stats["sparse_tokens_in_context"] += int(ctx.sum())
                self._stats["sparse_tokens_attended"] += int(
                    self.model.attended_tokens(ctx).sum())
                self._seen_cursor[slot] = now

    def _count_context(self, was: np.ndarray) -> None:
        """After a dispatch: how far along the slot cache's token axis
        each of its steps read (the rung that holds the deepest row live
        at that step, as the program picks it: `context_rungs`) against
        the whole axis, a token a slot a step. From the cursors the host
        held before the dispatch and those it left: a row that advanced k
        tokens was live in the first k steps."""
        steps = np.arange(self.decode_steps)[:, None]
        live = (self._remaining_cursors()[1] - was)[None, :] > steps
        need = np.where(live, was[None, :] + steps, 0).max(axis=1) + 1
        rung = self._ladder[np.searchsorted(self._ladder, need)]
        self._stats["decode_context_read"] += int(rung.sum()) * self.slots
        self._stats["decode_context_held"] += (
            self.decode_steps * self.max_len * self.slots)

    def _step(self, st) -> int:
        admitted0 = self._stats["admitted"]
        self._retire_synced("cancel")
        # rows retired so far get no token from this step's dispatch
        early = len(self._retired)
        rows = len(self._live)
        if self._live:
            pg = ((self._tables, self._plens,
                   self._block_pool.kv_pages()) if self._paged else ())
            # the host's copy: no read
            self._in_flight = self._remaining_cursors()[1].copy()
            with self._span("lm.decode_step", rows=rows) as sp:
                for req in self._new_traced:    # admitted a step ago
                    req.t_decode0 = sp.t_start
                self._new_traced.clear()
                (self._tokens, self._cache, self._cursors,
                 self._remaining, self._keys, self._logprobs,
                 self._counts) = self._decode(
                    self.params, self._tokens, self._cache,
                    self._cursors, self._remaining, self._temps,
                    self._top_ps, self._top_ks, self._keys,
                    self._logprobs, self._pres, self._freq,
                    self._counts, *pg)
            self._stats["dispatches"] += 1
            self._dispatched_ever = True
            if self._counted:             # see __init__
                self._counts_copy = _counters(self._cache)
            self._rc_invalidate()         # the dispatch advances the rows
        # the chip is busy with the dispatch: the admission's host work
        # costs it nothing, and its programs (prefill, block writes, the
        # slot splice and per-slot sets, which take the dispatch's outputs
        # as they are handed on) run behind it in the order enqueued
        with self._span("lm.step.admit"):
            if self._pending is not None:
                # one chunk of the pending long admission a step: resident
                # rows advance between chunks
                self._advance_prefill()
            self._admit()
        # the step's one wait; max_new == 1 admissions retire here
        self._retire_synced("dispatch")
        if st is not None:
            st.attrs.update(
                rows=rows, queued=len(self._queue),
                admitted=self._stats["admitted"] - admitted0,
                retired=len(self._retired))
        self._stamp(early)
        return (len(self._live) + len(self._queue)
                + (1 if self._pending is not None else 0))

    def _stamp(self, early: int) -> None:
        """The end of a step: the cursors are on the host, a streaming
        client could see every token they cover. One clock read stamps the
        first sight of each live request (a row admitted in the step shows
        its prefill's one token) and the last of each request the step
        retired; of those, the first ``early`` retired before the dispatch
        (cancelled) and keep the last stamp they had. No device read: the
        cursors are the copy the last `_retire_finished` fetched. An
        `lm.stamp` span."""
        with self._span("lm.stamp",
                        rows=len(self._live) + len(self._retired)):
            now = self.clock()
            if self._live:
                remaining = self._remaining_cursors()[0]
                for slot, req in self._live.items():
                    req.t_last = now
                    if req.t_first is None:
                        req.t_first = now
                        req.n_first = req.max_new - int(remaining[slot])
            for i, (done, req) in enumerate(self._retired):
                generated = len(done.tokens) - done.prompt_len
                if req.t_first is None:   # admitted and retired in one step
                    req.t_first = req.t_last = now
                    req.n_first = generated
                elif i >= early:
                    req.t_last = now
                done.t_first, done.n_first = req.t_first, req.n_first
                done.t_last = req.t_last
                if req.t_decode0 is not None:   # traced, and dispatched
                    self.spans.record(
                        "lm.decode", trace=req.trace[0], parent=req.trace[1],
                        t_start=req.t_decode0, t_end=req.t_last,
                        attrs={"id": req.id, "tokens": generated,
                               "steps": self._stats["dispatches"]
                               - req.dispatch0,
                               "t_first": req.t_first,
                               "n_first": req.n_first})
            self._retired.clear()

    def run_until_drained(self, max_steps: int = 10_000) -> list[Completion]:
        """Drive `step` until queue and slots are empty; returns every
        completion (including earlier un-polled ones)."""
        for _ in range(max_steps):
            if self.step() == 0:
                break
        else:
            raise RuntimeError(f"not drained after {max_steps} steps")
        self._retire_finished()
        return self.poll()

    def warmup(self) -> float:
        """Pay the pool's one-time compiles (prefill at the smallest
        bucket, insert, the decode dispatch) on a throwaway request BEFORE
        serving traffic; returns the wall seconds spent. Afterwards the
        host-visible accounting is reset so the warm-up is invisible:
        request ids restart at 0 (seed streams default to the id — a
        warmed pool draws the same streams as a cold one), stats and
        prefix-cache counters re-zero. The first REAL request's
        `Completion.service_s` then measures steady-state work, which is
        what the fair-share scheduler's service signal needs (a one-time
        compile is capacity planning, not per-request cost). Call only on
        an idle pool (no queued or live requests). On radix pools the
        warm chain stays cached unpinned — token-exact if ever hit, LRU-
        evicted otherwise."""
        if self._queue or self._live:
            raise RuntimeError("warmup() needs an idle pool")
        toks = [t % self.model.vocab for t in (1, 2, 3)][:self.prompt_len]
        pl = len(self.prefix) if self.prefix else 0
        max_new = max(1, min(self.decode_steps + 1,
                             self.max_len - pl - len(toks)))
        t0 = time.perf_counter()
        self.submit(toks, max_new=max_new)
        self.run_until_drained()
        warm_s = time.perf_counter() - t0
        self._next_id = 0
        for k in self._stats:
            self._stats[k] = 0
        self._pc_lookups = self._pc_hits = self._pc_tokens_saved = 0
        if self._radix is not None:
            self._radix.evict_nodes_walked = 0
            self._block_pool.blocks_written = 0
            self._block_pool.blocks_gathered = 0
        if self.cluster_prefix is not None:
            self.cluster_prefix.reset_counters()
        return warm_s
