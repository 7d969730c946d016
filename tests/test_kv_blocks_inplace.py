"""Block writes update the KV block pool IN PLACE (`engine/kv_blocks.py`).

`_write_block` donates the store: the compiled program aliases its output
to it and a block lands as a dynamic-update-slice into the live buffer,
never as a copy of the pool. What that changes for callers — a store
handle does not survive a write — and what it must not change — the bytes
of every block, the stores' sharding, the paged decode path that reads the
stores the writes mutate — is pinned here over stacked / unstacked pools,
native / int8 (scale leaves) caches and `write_block` / `write_raw_block`.
The alias is read from the compiled program, not from a timing, so it
holds on the CPU; `tests/test_chip_compile.py` asks the chip's compiler the
same at the benchmark's widths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idunno_tpu.engine.generate import generate
from idunno_tpu.engine.kv_blocks import (
    _WRITE_GROUP, KVBlockPool, _is_kv, _write_block)
from idunno_tpu.engine.serve_lm import DecodeServer, _prefill
from idunno_tpu.models.transformer import TransformerLM, stack_block_params
from idunno_tpu.serve.prefix_cache import RadixPrefixCache

VOCAB, BS, BLOCKS = 61, 2, 8
LAYOUTS = ["stacked", "unstacked"]
DTYPES = ["native", "int8"]
WRITERS = ["write_block", "write_raw_block"]


def build(layout: str, dtype: str):
    """(model, params) in the pool's layout: a scanned model carries
    depth-stacked caches, an int8 cache adds the scale leaves."""
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                          num_kv_heads=2, kv_cache_dtype=dtype)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    if layout == "stacked":
        model = dataclasses.replace(model, scan_layers=True)
        params = stack_block_params(params, model.depth)
    return model, params


def row_cache_for(model, params, tokens):
    cache, _ = _prefill(model, params, jnp.asarray([tokens], jnp.int32),
                        jnp.int32(len(tokens)), len(tokens))
    return cache


def expected(model, params, prompt, max_new) -> list[int]:
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   prompt_len=len(prompt), max_new=max_new)
    return [int(t) for t in np.asarray(out[0])]


def kv_leaves(tree) -> dict:
    return {jax.tree_util.keystr(p): leaf for p, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0] if _is_kv(p)}


def sliver(leaf, j: int, stacked: bool) -> np.ndarray:
    """Block ``j`` of a batch-1 row leaf, as `read_block` shapes it."""
    leaf = np.asarray(leaf)
    return (leaf[:, 0, j * BS:(j + 1) * BS] if stacked
            else leaf[0, j * BS:(j + 1) * BS])


def write(pool, writer: str, bid: int, cache, j: int) -> None:
    if writer == "write_block":
        pool.write_block(bid, cache, j * BS)
    else:
        pool.write_raw_block(bid, {
            key: sliver(leaf, j, pool._stacked)
            for key, leaf in kv_leaves(cache).items()})


def snapshot(pool) -> dict:
    return {key: np.asarray(s).copy() for key, s in pool._stores.items()}


@pytest.fixture(scope="module", params=[(la, dt) for la in LAYOUTS
                                        for dt in DTYPES],
                ids=lambda p: "-".join(p))
def filled(request):
    """A pool with every block allocated and written once (nothing in it is
    zero by accident), the row cache it was written from, and its kind."""
    layout, dtype = request.param
    model, params = build(layout, dtype)
    tokens = list(np.random.default_rng(3).integers(1, VOCAB, BLOCKS * BS))
    cache = row_cache_for(model, params, [int(t) for t in tokens])
    pool = KVBlockPool(model, num_blocks=BLOCKS, block_size=BS)
    bids = [pool.alloc() for _ in range(BLOCKS)]
    pool.write_blocks(bids, cache, [j * BS for j in range(BLOCKS)])
    return pool, cache, dtype


# -- 1. the compiled program aliases its output to the store ----------------

@pytest.mark.parametrize("writer", WRITERS)
def test_compiled_write_aliases_the_store(filled, writer):
    pool, cache, dtype = filled
    src = kv_leaves(cache)
    assert len(pool._stores) == (4 if dtype == "int8" else 2) * (
        1 if pool._stacked else 2)
    for key, store in pool._stores.items():
        if writer == "write_block":
            row, n = src[key], _WRITE_GROUP
        else:       # a raw sliver is a row of exactly one block
            row = jnp.expand_dims(
                jnp.asarray(sliver(src[key], 0, pool._stacked)),
                1 if pool._stacked else 0)
            n = 1
        compiled = _write_block.lower(
            store, row, jnp.zeros((2, n), jnp.int32),
            stacked=pool._stacked).compile()
        assert "input_output_alias={ {}: (0, {}" in compiled.as_text(), key
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == store.nbytes, \
            f"{key}: output does not alias the donated store"


# -- 2. scattered writes: what was written reads back, the rest is untouched

@pytest.mark.parametrize("writer", WRITERS)
def test_scattered_writes_leave_other_blocks_untouched(filled, writer):
    pool, cache, _ = filled
    fresh = row_cache_for(*build("stacked" if pool._stacked else "unstacked",
                                 filled[2]),
                          [int(t) for t in np.random.default_rng(11)
                           .integers(1, VOCAB, BLOCKS * BS)])
    before = snapshot(pool)
    targets = {5: 1, 0: 6, 7: 3, 2: 2}         # block id -> source block
    for bid, j in targets.items():
        write(pool, writer, bid, fresh, j)
    src = kv_leaves(fresh)
    for bid, j in targets.items():
        got = pool.read_block(bid)
        for key, arr in got.items():
            np.testing.assert_array_equal(
                arr, sliver(src[key], j, pool._stacked),
                err_msg=f"read_block({bid}) at {key}")
    order = list(targets)
    for key, leaf in kv_leaves(pool.gather(order)).items():
        want = np.concatenate(
            [sliver(src[key], targets[b], pool._stacked) for b in order],
            axis=1 if pool._stacked else 0)
        np.testing.assert_array_equal(
            np.asarray(leaf)[:, 0] if pool._stacked else np.asarray(leaf)[0],
            want, err_msg=f"gather at {key}")
    after = snapshot(pool)
    others = [b for b in range(BLOCKS) if b not in targets]
    for key in before:
        a, b = ((after[key][:, others], before[key][:, others])
                if pool._stacked else (after[key][others],
                                       before[key][others]))
        assert a.tobytes() == b.tobytes(), \
            f"{key}: a block no write named changed"


@pytest.mark.parametrize("layout", LAYOUTS)
def test_insert_writes_a_long_chain_in_groups(layout):
    """A radix insert hands all its new blocks to `write_blocks` at once;
    more than `_WRITE_GROUP` of them go out in several dispatches, the
    last one short, and every block holds its own tokens' KV."""
    model, params = build(layout, "native")
    n = 2 * _WRITE_GROUP + 3
    tokens = [int(t) for t in
              np.random.default_rng(5).integers(1, VOCAB, n * BS + 1)]
    cache = row_cache_for(model, params, tokens)
    pool = KVBlockPool(model, num_blocks=n + 2, block_size=BS)
    tree = RadixPrefixCache(pool)
    chain = tree.insert(tokens, cache, 0)
    assert len(chain) == n and pool.blocks_written == n
    assert tree.inserted_blocks == n and tree.num_nodes() == n
    got = kv_leaves(pool.gather([nd.block for nd in chain]))
    tok = 2 if pool._stacked else 1
    for key, leaf in kv_leaves(cache).items():
        np.testing.assert_array_equal(
            np.asarray(got[key]),
            np.asarray(jax.lax.slice_in_dim(leaf, 0, n * BS, axis=tok)),
            err_msg=f"chain content at {key}")
    # a second prompt sharing 3 blocks writes only what is new
    other = tokens[:3 * BS] + [VOCAB - 1] * (2 * BS)
    chain2 = tree.insert(other, row_cache_for(model, params, other), 0)
    assert [nd.block for nd in chain2[:3]] == [nd.block for nd in chain[:3]]
    assert pool.blocks_written == n + 2


def test_refused_write_leaves_tree_and_pool_as_they_were():
    """`insert` writes its new blocks after the walk that hung their
    nodes in: a write the pool refuses (an offset past the row) takes the
    nodes out again, gives the blocks back and drops the chain's pins."""
    model, params = build("stacked", "native")
    tokens = [5, 11, 17, 23, 2, 44]
    cache = row_cache_for(model, params, tokens)
    pool = KVBlockPool(model, num_blocks=4, block_size=BS)
    tree = RadixPrefixCache(pool)
    with pytest.raises(ValueError, match="ABSOLUTE"):
        tree.insert(tokens, cache, 3)           # 3 + 3 blocks > 6 tokens
    assert tree.num_nodes() == 0 and tree.inserted_blocks == 0
    assert pool.num_free == 4 and pool.blocks_written == 0
    assert tree.lookup(tokens) == []
    with pytest.raises(ValueError, match="not allocated"):
        pool.write_block(2, cache, 0)           # never clamped onto block 3


# -- 3. a store handle does not survive a write; kv_pages() is live ---------

@pytest.mark.parametrize("writer", WRITERS)
def test_store_handle_does_not_survive_a_write(filled, writer):
    pool, cache, _ = filled
    held = dict(pool._stores)
    pages = pool.kv_pages() if pool._stacked else {}
    write(pool, writer, 4, cache, 4)
    for key, old in held.items():
        assert pool._stores[key] is not old
        assert old.is_deleted(), f"{key}: the store was not donated"
        assert not pool._stores[key].is_deleted()
    assert all(p.is_deleted() for p in pages.values())
    if pool._stacked:
        live = pool.kv_pages()
        assert set(live) == {k.split("'")[-2] for k in held}
        for page in live.values():
            assert page.shape[1:3] == (BLOCKS, BS)
            np.asarray(page)                  # readable: a live buffer
    else:
        with pytest.raises(ValueError, match="stacked"):
            pool.kv_pages()


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_paged_decode_after_writes_matches_gathered(kernel, dtype):
    """The paged step reads the stores the writes mutate: a second
    admission writes its blocks BETWEEN the first request's decode
    dispatches, and both streams must match the gathered path's (and
    `generate`'s) token for token."""
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                          kv_cache_dtype=dtype)
    params = model.init(jax.random.PRNGKey(2),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    first, second = [5, 11, 17, 23, 2, 44], [5, 11, 17, 23, 9, 30, 8]
    streams = {}
    for k in (None, kernel):
        srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=32,
                           kv_block_size=BS, kv_cache_blocks=16,
                           paged_kernel=k)
        # seed the tree, so that both requests below decode THROUGH blocks
        srv.submit(first[:4], max_new=2)
        srv.run_until_drained()
        a = srv.submit(first, max_new=10)
        srv.step()
        srv.step()
        written = srv._block_pool.blocks_written
        b = srv.submit(second, max_new=6)
        done = {c.id: c.tokens for c in srv.run_until_drained()}
        assert srv._block_pool.blocks_written > written
        streams[k] = (done[a], done[b])
    assert streams[kernel] == streams[None]
    assert streams[kernel] == (expected(model, params, first, 10),
                               expected(model, params, second, 6))


# -- 4. under a model axis the store keeps its sharding ---------------------

@pytest.mark.parametrize("writer", WRITERS)
def test_tp_store_sharding_unchanged_by_write(writer):
    from idunno_tpu.parallel.mesh import MODEL_AXIS

    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       kv_block_size=BS, kv_cache_blocks=16,
                       paged_kernel="xla", n_model=2)
    pool = srv._block_pool
    before = {key: s.sharding for key, s in pool._stores.items()}
    assert all(MODEL_AXIS in tuple(sh.spec) for sh in before.values())
    if writer == "write_block":
        rid = srv.submit([5, 11, 17, 23, 2, 44], max_new=4)
        done = {c.id: c for c in srv.run_until_drained()}
        assert done[rid].tokens == expected(
            model, params, [5, 11, 17, 23, 2, 44], 4)
    else:
        bid = pool.alloc()
        pool.write_raw_block(bid, {
            key: np.full(s.shape[:1] + s.shape[2:], 3, s.dtype)
            for key, s in pool._stores.items()})
        assert all((a == 3).all() for a in pool.read_block(bid).values())
    assert pool.blocks_written > 0
    for key, store in pool._stores.items():
        assert store.sharding == before[key], \
            f"{key}: a write changed the store's sharding"
        assert len(store.sharding.device_set) == 2
