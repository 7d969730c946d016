"""Paged KV-cache block pool (vLLM PagedAttention-style, PAPERS.md).

The decode-cache leaves (`cached_k`/`cached_v`, plus `k_scale`/`v_scale`
on int8 caches — `models/transformer.py`) are contiguous per sequence:
token position t lives at index t of the cache's token axis. That layout
is what the serving tier's static-shape programs want, but it makes KV
reuse all-or-nothing — the single pool-level `prefix=` cache in
`engine/serve_lm.py` is paid once at pool build and shared by every
request, and nothing else is ever reused.

This module adds the missing granularity: a pool of fixed-size TOKEN
BLOCKS over the same leaves. Each block holds `block_size` consecutive
token positions of every K/V leaf; a prompt's KV is then a CHAIN of
blocks that other requests with the same token prefix (at the same
absolute positions) can splice into their own prefill via the existing
`_prefill_suffix` path. Ownership/eviction policy lives one level up in
`serve/prefix_cache.py` (the radix tree); this pool only does storage:

  alloc/free     — free-list, O(1), no compaction (blocks are uniform)
  incref/decref  — per-block reference counts: a block is pinned while
                   any admitted request's chain holds it, so the tree
                   can only evict refcount-0 chains
  write_blocks   — write blocks' worth of a prefill row cache's K/V
                   into the stores IN PLACE: the store is donated to
                   one compiled program per (store shape, row length)
                   that updates the live buffer, so a write moves a
                   block's bytes and never a copy of the pool (the
                   block ids and token offsets are traced, so block
                   churn never recompiles). A store handle therefore
                   does NOT survive a write: fetch `kv_pages()` afresh
                   for every dispatch, never cache it
  gather         — assemble a chain back into a batch-1, length-n·bs
                   cache tree whose leaf paths match `init_cache`'s, so
                   `_prefill_suffix` can splice it verbatim

Correctness note: the transformer is causal, so a token's K/V depends
only on the tokens at and before its position — KV written by ONE
request is bit-identical to what any other request with the same token
prefix (and the same pool-level static prefix ahead of it) would
compute at those positions. That is the whole reason cross-request
sharing can keep greedy decode token-exact (`tests/test_prefix_cache.py`
pins this against `engine/generate.py`).

The block stores are allocated unsharded by default (replicated under a
mesh): blocks are batch-1 slivers the admission path gathers/scatters on
the host-facing side of the pool; the big [slots, max_len] decode cache
in `DecodeServer` keeps its mesh sharding unchanged. Under tensor
parallelism (``mesh=`` with a "model" axis of extent > 1) the stores
shard their KV-head dim over the model axis — matching the decode
cache's head split, so the paged kernel's page reads stay chip-local —
while the block axis stays whole on every chip (the host-side free-list
addresses any block from anywhere).

The reference has no KV reuse at any granularity — every query
recomputes from scratch (`mp4_machinelearning.py:541-616`).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from idunno_tpu.engine.generate import init_cache

# cache leaves that carry per-token K/V state (int8 caches add scales);
# must stay in lockstep with `serve_lm._prefill_suffix`'s splice filter
KV_LEAF_KEYS = ("cached_k", "cached_v", "k_scale", "v_scale")
# leaves a slot holds BESIDE its K/V on a stack with block-sparse or
# recurrent layers (`models/hybrid.py`): the sparse layers' pooled keys
# (a token axis of its own, 1 / kernel_stride of K's), the linear and
# state-space layers' state and the latter's convolution window (no token
# axis). A layer that runs an attention and a state-space mixer side by
# side holds leaves of BOTH tuples under one layer index. They are spliced
# into a slot whole at admission and are never cut into blocks: the pool's
# filter above leaves them out, so a chain of blocks cannot rebuild such a
# slot
STATE_LEAF_KEYS = ("comp_k", "state", "conv")
SLOT_LEAF_KEYS = KV_LEAF_KEYS + STATE_LEAF_KEYS


def _is_kv(path) -> bool:
    return bool(path) and getattr(path[-1], "key", None) in KV_LEAF_KEYS


# blocks one `_write_block` dispatch writes: a prompt's new blocks go out
# in groups of this many per leaf instead of one dispatch each. A constant
# of the program, not of the row: the compile count stays one per (store
# shape, row length), and the unrolled program stays small to compile
_WRITE_GROUP = 16


@partial(jax.jit, static_argnames=("stacked",), donate_argnums=(0,))
def _write_block(store: jnp.ndarray, row_leaf: jnp.ndarray,
                 plan: jnp.ndarray, stacked: bool = False) -> jnp.ndarray:
    """store[bid] = row_leaf[0, off:off+block_size] for every (bid, off)
    column of ``plan`` (int32 [2, n]; a short group repeats its last
    column, which rewrites the same bytes). The store is DONATED and
    each block lands as a dynamic-update-slice into the live buffer: the
    output aliases the input and no copy of the store is made. That is
    why the columns are unrolled: around a scatter, and around a loop
    wherever it lays the block axis out minor (scale leaves, head size
    64), the chip's compiler re-lays the whole store out, a copy in and
    one out (`tests/test_chip_compile.py` holds the shapes). The plan is
    traced: one compile per (store shape, row length), not per block or
    offset. ``stacked`` is a STATIC flag, not rank-inferred: a
    scanned-cache k_scale leaf [L, 1, T, kvh] has the same rank as an
    unscanned cached_k [1, T, h, d], so only the caller knows the
    layout."""
    # Stacked stores are [L, N, bs, ...] (depth LEADS, block second) so
    # the paged decode path can hand `store[l]` — a ready-made
    # [N, bs, ...] page array — to the per-layer scan body with no
    # moveaxis/copy (ops/paged_attention.py); row_leaf is [L, 1, T, ...]
    # and block slivers keep the depth axis.
    axis = 1 if stacked else 0            # block axis; the row's token axis
    bs = store.shape[axis + 1]
    row = row_leaf[:, 0] if stacked else row_leaf[0]
    for i in range(plan.shape[1]):
        chunk = jax.lax.dynamic_slice_in_dim(row, plan[1, i], bs, axis=axis)
        start = [0] * store.ndim
        start[axis] = plan[0, i]
        store = jax.lax.dynamic_update_slice(
            store, jnp.expand_dims(chunk.astype(store.dtype), axis), start)
    return store


@partial(jax.jit, static_argnames=("n", "stacked"))
def _gather_blocks(store: jnp.ndarray, bids: jnp.ndarray,
                   n: int, stacked: bool = False) -> jnp.ndarray:
    """[n blocks] → one contiguous leaf: [1, n·block_size, ...] per-block,
    [L, 1, n·block_size, ...] stacked (depth leads, batch-1 second)."""
    if stacked:
        picked = store[:, bids]                    # [L, n, bs, ...]
        return picked.reshape(
            (store.shape[0], 1, n * store.shape[2]) + store.shape[3:])
    return store[bids].reshape((1, n * store.shape[1]) + store.shape[2:])


def concat_kv_prefix(front: Any, back: Any, token_axis: int = 1) -> Any:
    """Concatenate two batch-1 cache trees along the token axis at the
    K/V leaves (static pool prefix + gathered radix chain → one combined
    prefix for `_prefill_suffix`). Non-K/V leaves (cursors) are taken
    from ``front`` — the consumer overwrites them anyway. Leaves match
    by keystr path, not container identity, so a flax-mutated cache and
    an `init_cache` template compose regardless of dict flavor.
    ``token_axis`` is 1 for the per-block layout, 2 for depth-stacked
    scanned caches ([L, 1, T, ...])."""
    src = {jax.tree_util.keystr(p): leaf for p, leaf
           in jax.tree_util.tree_flatten_with_path(back)[0] if _is_kv(p)}

    def f(path, x):
        if _is_kv(path):
            return jnp.concatenate(
                [x, src[jax.tree_util.keystr(path)]], axis=token_axis)
        return x
    return jax.tree_util.tree_map_with_path(f, front)


class KVBlockPool:
    """Fixed-size token-block storage over a model's decode-cache K/V
    leaves, with free-list allocation and per-block refcounts. Policy-
    free: see `serve/prefix_cache.py` for the radix tree that decides
    what the blocks mean and when they are evicted."""

    def __init__(self, model, num_blocks: int, block_size: int,
                 mesh=None) -> None:
        if num_blocks < 1:
            raise ValueError(f"num_blocks {num_blocks} must be >= 1")
        if block_size < 1:
            raise ValueError(f"block_size {block_size} must be >= 1")
        self.model = model
        self.num_blocks = num_blocks
        self.block_size = block_size
        # TP page sharding: shard the KV-head dim of every store over the
        # mesh's "model" axis when the heads divide (mirrors the decode
        # cache's split — `parallel/sharding.py:lm_cache_specs`); a
        # non-dividing head count replicates, same as no mesh at all
        self._head_shard = None
        if mesh is not None:
            from idunno_tpu.parallel.mesh import MODEL_AXIS
            n_model = int(mesh.shape.get(MODEL_AXIS, 1))
            kvh = getattr(model, "num_kv_heads", None) or model.num_heads
            if n_model > 1 and kvh % n_model == 0:
                self._head_shard = (mesh, n_model)
        # scanned models carry depth-stacked caches ([L, 1, bs, ...]);
        # the stores lead with the depth axis ([L, N, bs, ...]) so one
        # write/gather moves every layer's sliver at once AND store[l]
        # is directly the per-layer page array the paged kernel reads
        self._stacked = bool(getattr(model, "scan_layers", False))
        # batch-1 length-block_size template names the K/V leaves and
        # their per-token shapes; the stores add a leading block axis
        shapes = jax.eval_shape(lambda: init_cache(model, 1, block_size))
        self._stores: dict[str, jnp.ndarray] = {}
        # leaf NAME ("cached_k", …) → store keystr, for kv_pages(); a
        # stacked pool has exactly one cache leaf per name, unscanned
        # pools have one per layer (name collisions → kv_pages refuses)
        self._leaf_names: dict[str, str | None] = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            if _is_kv(path):
                if self._stacked:
                    # depth LEADS: [L, N, bs, ...] — store[l] is the
                    # per-layer page array the paged kernel consumes
                    shape = ((leaf.shape[0], num_blocks, block_size)
                             + leaf.shape[3:])
                else:
                    shape = (num_blocks, block_size) + leaf.shape[2:]
                key = jax.tree_util.keystr(path)
                name = path[-1].key
                self._stores[key] = self._alloc_store(shape, leaf.dtype,
                                                      name)
                self._leaf_names[name] = (
                    None if name in self._leaf_names else key)
        if not self._stores:
            raise ValueError("model's decode cache has no K/V leaves")
        self._free: list[int] = list(range(num_blocks - 1, -1, -1))
        self._refs: dict[int, int] = {}       # allocated block → refcount
        # eval_shape templates for gather output trees, keyed by length
        self._tree_templates: dict[int, Any] = {}
        # blocks moved, counted where they move (`prefix_cache_stats`)
        self.blocks_written = 0
        self.blocks_gathered = 0

    def _alloc_store(self, shape: tuple, dtype, name: str) -> jnp.ndarray:
        """Zeroed store, head-sharded over the model axis under TP. The
        KV-head dim is second-to-last on cached_k/v ([.., kvh, d]) and
        last on the scale leaves ([.., kvh])."""
        if self._head_shard is None:
            return jnp.zeros(shape, dtype)
        from jax.sharding import NamedSharding, PartitionSpec
        from idunno_tpu.parallel.mesh import MODEL_AXIS
        mesh, _ = self._head_shard
        head_dim = len(shape) - (2 if name in ("cached_k", "cached_v")
                                 else 1)
        axes = [None] * len(shape)
        axes[head_dim] = MODEL_AXIS
        sh = NamedSharding(mesh, PartitionSpec(*axes))
        return jax.jit(lambda: jnp.zeros(shape, dtype),
                       out_shardings=sh)()

    # -- allocation -------------------------------------------------------

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_blocks - len(self._free)

    def alloc(self) -> int | None:
        """One free block (refcount 0) or None when the pool is full —
        the caller decides whether to evict or skip."""
        if not self._free:
            return None
        bid = self._free.pop()
        self._refs[bid] = 0
        return bid

    def free(self, bid: int) -> None:
        refs = self._refs.get(bid)
        if refs is None:
            raise ValueError(f"block {bid} is not allocated")
        if refs:
            # refused free must leave the block tracked (still allocated)
            raise ValueError(f"block {bid} freed with refcount {refs}")
        del self._refs[bid]
        self._free.append(bid)

    def incref(self, bid: int) -> None:
        self._refs[bid] += 1

    def decref(self, bid: int) -> None:
        if self._refs[bid] < 1:
            raise ValueError(f"block {bid} decref below zero")
        self._refs[bid] -= 1

    def refcount(self, bid: int) -> int:
        return self._refs[bid]

    # -- data movement ----------------------------------------------------

    def write_blocks(self, bids: list[int], row_cache: Any,
                     offsets: list[int]) -> None:
        """Copy token positions [offset, offset+block_size) of a batch-1
        prefill cache's K/V leaves into block ``bid``, for every pair of
        ``bids`` and ``offsets``, `_WRITE_GROUP` blocks to a dispatch
        per leaf. An offset is an ABSOLUTE cache position — with a
        pool-level static prefix ahead of the request tokens, the caller
        passes prefix_len + i.

        The write is IN PLACE: each store is donated to `_write_block`
        and rebound from its result, so the pool is the only owner of a
        live store and any handle taken before the write is dead after
        it (see `kv_pages`).

        Every window must lie inside the row cache: `dynamic_slice`
        clamps out-of-range starts SILENTLY, which would duplicate the
        tail block's tokens into the next block and poison every later
        prefix hit — so out-of-range offsets raise here instead, before
        any store is touched. Likewise an unallocated block id raises:
        the in-place update would clamp it onto a neighbour's block."""
        src = {jax.tree_util.keystr(p): leaf for p, leaf
               in jax.tree_util.tree_flatten_with_path(row_cache)[0]
               if _is_kv(p)}
        tok_axis = 2 if self._stacked else 1
        row_len = next(iter(src.values())).shape[tok_axis]
        for offset in offsets:
            if offset < 0 or offset + self.block_size > row_len:
                raise ValueError(
                    f"write_block offset {offset} + block_size "
                    f"{self.block_size} outside row cache of {row_len} "
                    f"tokens (offset is an ABSOLUTE cache position — did "
                    f"the caller forget/double-count the static prefix "
                    f"length?)")
        for bid in bids:
            if bid not in self._refs:
                raise ValueError(f"block {bid} is not allocated")
        for g in range(0, len(bids), _WRITE_GROUP):
            cols = list(zip(bids[g:g + _WRITE_GROUP],
                            offsets[g:g + _WRITE_GROUP]))
            cols += cols[-1:] * (_WRITE_GROUP - len(cols))
            self._write(src, jnp.asarray(np.asarray(cols, np.int32).T))
        self.blocks_written += len(bids)

    def _write(self, rows: dict[str, Any], plan: jnp.ndarray) -> None:
        """The one place a store changes: donated, rebound from the result."""
        for key, store in self._stores.items():
            self._stores[key] = _write_block(store, rows[key], plan,
                                             stacked=self._stacked)

    def write_block(self, bid: int, row_cache: Any, offset: int) -> None:
        """`write_blocks` for one block."""
        self.write_blocks([bid], row_cache, [offset])

    def read_block(self, bid: int) -> dict[str, Any]:
        """One block's raw per-leaf content as HOST numpy arrays, keyed
        by leaf keystr — the payload half of a cluster prefix-cache
        publish (`serve/cluster_prefix.py`). Stacked pools return
        ``[L, bs, ...]`` slivers, unscanned ``[bs, ...]``. Under TP the
        read gathers the head-sharded store — logical shapes (and
        bytes) are identical across ``n_model``, so published blobs are
        content-equal regardless of the publisher's mesh."""
        if bid not in self._refs:
            raise ValueError(f"block {bid} is not allocated")
        out = {}
        for key, store in self._stores.items():
            sliver = store[:, bid] if self._stacked else store[bid]
            out[key] = np.asarray(jax.device_get(sliver))
        return out

    def write_raw_block(self, bid: int, arrays: dict[str, Any]) -> None:
        """Inverse of `read_block`: install fetched raw slivers into
        block ``bid``. Every store leaf must be present with its exact
        per-block shape — a partial or mis-shaped payload raises before
        any store is touched (a half-written block would poison every
        later prefix hit on its chain). In place, through the same
        donated writer as `write_blocks`."""
        if bid not in self._refs:
            raise ValueError(f"block {bid} is not allocated")
        staged = {}
        for key, store in self._stores.items():
            arr = arrays.get(key)
            want = store.shape[:1] + store.shape[2:] if self._stacked \
                else store.shape[1:]
            if arr is None:
                raise ValueError(f"write_raw_block missing leaf {key!r}")
            if tuple(arr.shape) != tuple(want):
                raise ValueError(
                    f"write_raw_block leaf {key!r} shape {arr.shape} != "
                    f"store block shape {want}")
            # a sliver is a batch-1 row of exactly one block: the same
            # donated in-place writer as `write_blocks`, offset 0
            staged[key] = jnp.expand_dims(jnp.asarray(arr, store.dtype),
                                          1 if self._stacked else 0)
        self._write(staged, jnp.asarray([[bid], [0]], jnp.int32))
        self.blocks_written += 1

    def kv_pages(self) -> dict[str, jnp.ndarray]:
        """Raw page stores by leaf name ({"cached_k", "cached_v"} plus
        {"k_scale", "v_scale"} on int8 pools), each ``[L, N, bs, ...]``
        — the arrays the paged decode path (`ops/paged_attention.py`)
        reads THROUGH the block table instead of gathering. These are
        the LIVE stores, and a block write donates them: fetch them per
        dispatch and hand them straight to the program (a dispatch
        already enqueued keeps its buffer until it has run); a dict held
        across a write holds deleted arrays. Stacked (scanned) pools
        only: an unscanned multi-layer pool has one store per layer
        under the same leaf name, which has no single per-name page
        array to hand out."""
        if not self._stacked:
            raise ValueError(
                "kv_pages() requires a depth-stacked (scanned) pool; "
                "unscanned pools keep the gather path")
        out = {}
        for name, key in self._leaf_names.items():
            if key is None:
                raise ValueError(
                    f"ambiguous page store for leaf {name!r} "
                    f"(per-layer leaves collide)")
            out[name] = self._stores[key]
        return out

    @property
    def bytes_per_block(self) -> int:
        """Bytes one block occupies across every K/V leaf store — the
        unit of the `kv_gather_bytes_saved` gauge."""
        return sum(int(s.size // self.num_blocks) * s.dtype.itemsize
                   for s in self._stores.values())

    def gather(self, blocks: list[int]) -> Any:
        """Chain → a batch-1, length-``len(blocks)·block_size`` cache
        tree (leaf paths identical to `init_cache`'s, non-K/V leaves
        zeroed) ready for `_prefill_suffix`'s prefix splice."""
        n = len(blocks)
        if n < 1:
            raise ValueError("empty block chain")
        total = n * self.block_size
        template = self._tree_templates.get(total)
        if template is None:
            template = jax.eval_shape(
                lambda: init_cache(self.model, 1, total))
            self._tree_templates[total] = template
        self.blocks_gathered += n
        bids = jnp.asarray(blocks, jnp.int32)
        parts = {key: _gather_blocks(store, bids, n,
                                     stacked=self._stacked)
                 for key, store in self._stores.items()}

        def fill(path, leaf):
            if _is_kv(path):
                return parts[jax.tree_util.keystr(path)]
            return jnp.zeros(leaf.shape, leaf.dtype)
        return jax.tree_util.tree_map_with_path(fill, template)
