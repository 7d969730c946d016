"""Paged KV block pool + cross-request radix prefix cache
(`engine/kv_blocks.py`, `serve/prefix_cache.py`).

Exactness oracle: a radix hit splices KV another request computed — greedy
decode through a `kv_block_size` pool must stay token-for-token identical
to `engine.generate.generate` at EVERY hit depth (empty, partial-block,
multi-block, full-prompt), for MHA, GQA/MQA, penalties pools, int8
caches and a pool-level static prefix. The reference has no
counterpart: every query recomputes from scratch
(`mp4_machinelearning.py:541-616`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idunno_tpu.engine.generate import generate
from idunno_tpu.engine.kv_blocks import (
    KVBlockPool, _is_kv, concat_kv_prefix)
from idunno_tpu.engine.serve_lm import DecodeServer, _prefill
from idunno_tpu.models.transformer import TransformerLM
from idunno_tpu.serve.prefix_cache import RadixPrefixCache

VOCAB = 61
BS = 2          # kv_block_size under test: small → multi-block chains


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def expected(model, params, prompt, max_new, **kw):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   prompt_len=len(prompt), max_new=max_new, **kw)
    return [int(t) for t in np.asarray(out[0])]


def kv_leaves(tree) -> dict:
    return {jax.tree_util.keystr(p): leaf for p, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0] if _is_kv(p)}


def row_cache_for(model, params, tokens):
    cache, _ = _prefill(model, params,
                        jnp.asarray([tokens], jnp.int32),
                        jnp.int32(len(tokens)), len(tokens))
    return cache


# -- KVBlockPool unit -------------------------------------------------------

def test_pool_alloc_free_refcount(lm):
    model, _ = lm
    pool = KVBlockPool(model, num_blocks=3, block_size=BS)
    bids = [pool.alloc() for _ in range(3)]
    assert sorted(bids) == [0, 1, 2] and pool.num_free == 0
    assert pool.alloc() is None, "exhausted pool must return None, not raise"
    pool.incref(bids[0])
    with pytest.raises(ValueError, match="refcount"):
        pool.free(bids[0])                      # pinned block can't be freed
    pool.decref(bids[0])
    with pytest.raises(ValueError, match="below zero"):
        pool.decref(bids[0])
    pool.free(bids[0])
    with pytest.raises(ValueError, match="not allocated"):
        pool.free(bids[0])                      # double free
    assert pool.num_free == 1 and pool.num_used == 2


def test_pool_validation(lm):
    model, _ = lm
    with pytest.raises(ValueError):
        KVBlockPool(model, num_blocks=0, block_size=BS)
    with pytest.raises(ValueError):
        KVBlockPool(model, num_blocks=2, block_size=0)


def test_write_gather_roundtrip(lm):
    """Blocks written from a real prefill cache must gather back into a
    tree whose K/V leaves equal the contiguous source slice — this is
    the storage half of the token-exactness argument."""
    model, params = lm
    cache = row_cache_for(model, params, [5, 11, 17, 23, 2, 44])
    pool = KVBlockPool(model, num_blocks=4, block_size=BS)
    bids = [pool.alloc() for _ in range(3)]
    for j, bid in enumerate(bids):
        pool.write_block(bid, cache, j * BS)
    got = kv_leaves(pool.gather(bids))
    src = kv_leaves(cache)
    assert set(got) == set(src)
    for key, leaf in got.items():
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(src[key][:, :3 * BS]),
            err_msg=f"gather mismatch at {key}")
    # gathering a permuted chain reorders the token axis accordingly
    perm = kv_leaves(pool.gather([bids[1], bids[0]]))
    for key, leaf in perm.items():
        np.testing.assert_array_equal(
            np.asarray(leaf[:, :BS]), np.asarray(src[key][:, BS:2 * BS]))


def test_concat_kv_prefix_matches_contiguous(lm):
    """static-prefix cache ++ gathered chain ≈ one contiguous prefill
    of the concatenated tokens (K/V leaves only; cursors come from
    ``front`` and are overwritten by the consumer). allclose, not
    array_equal: the length-2 and length-6 prefills are DIFFERENT
    compiled programs whose accumulations may round differently — the
    serving tier splices the same arrays a previous prefill produced,
    which is why the hit-depth tests below are token-EXACT."""
    model, params = lm
    front_tokens, back_tokens = [7, 3], [9, 1, 4, 6]
    whole = row_cache_for(model, params, front_tokens + back_tokens)
    front = row_cache_for(model, params, front_tokens)
    pool = KVBlockPool(model, num_blocks=2, block_size=BS)
    bids = [pool.alloc(), pool.alloc()]
    for j, bid in enumerate(bids):
        # absolute offsets: the chain sits AFTER the static prefix
        pool.write_block(bid, whole, len(front_tokens) + j * BS)
    combined = kv_leaves(concat_kv_prefix(front, pool.gather(bids)))
    ref = kv_leaves(whole)
    for key, leaf in combined.items():
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(ref[key]),
                                   rtol=1e-4, atol=1e-6,
                                   err_msg=f"concat mismatch at {key}")
        # the spliced back half is the very same stored data — exact
        np.testing.assert_array_equal(
            np.asarray(leaf[:, len(front_tokens):]),
            np.asarray(ref[key][:, len(front_tokens):]))


# -- RadixPrefixCache semantics --------------------------------------------

def test_radix_insert_lookup_sharing(lm):
    model, params = lm
    pool = KVBlockPool(model, num_blocks=8, block_size=BS)
    tree = RadixPrefixCache(pool)
    assert tree.lookup([1, 2, 3, 4]) == []

    a = [1, 2, 3, 4, 9]                  # 2 full blocks + 1 partial token
    chain = tree.insert(a, row_cache_for(model, params, a), 0)
    assert len(chain) == 2, "partial tail block must not be inserted"
    assert all(pool.refcount(nd.block) == 1 for nd in chain), \
        "insert must return the chain acquired"
    tree.release(chain)

    b = [1, 2, 7, 8]                     # shares only the first block
    chain_b = tree.insert(b, row_cache_for(model, params, b), 0)
    assert chain_b[0] is chain[0], "shared head chunk must reuse the node"
    assert chain_b[1] is not chain[1]
    assert tree.num_nodes() == 3 and tree.inserted_blocks == 3
    tree.release(chain_b)

    hit = tree.lookup([1, 2, 3, 4, 5, 6])
    assert [nd.chunk for nd in hit] == [(1, 2), (3, 4)]


def test_radix_lru_eviction_leaves_only(lm):
    """Eviction frees the LRU refcount-0 LEAF; inner nodes survive while
    a child pins their position in some chain."""
    model, params = lm
    pool = KVBlockPool(model, num_blocks=3, block_size=BS)
    tree = RadixPrefixCache(pool)
    a = [1, 2, 3, 4]                     # chain: (1,2) -> (3,4)
    tree.release(tree.insert(a, row_cache_for(model, params, a), 0))
    b = [1, 2, 5, 6]                     # adds leaf (5,6) under (1,2)
    tree.release(tree.insert(b, row_cache_for(model, params, b), 0))
    tree.lookup(a)                       # a's leaf is now most recent

    c = [9, 8, 7, 6]                     # needs 2 blocks, pool has 0 free
    chain_c = tree.insert(c, row_cache_for(model, params, c), 0)
    assert len(chain_c) == 2 and tree.evictions == 2
    # LRU leaf (5,6) went first, then (3,4); inner (1,2) still cached
    assert tree.lookup(b) == [] or tree.lookup(b)[0].chunk == (1, 2)
    assert [nd.chunk for nd in tree.lookup(a)] == [(1, 2)], \
        "inner node with no children left should still serve a 1-block hit"
    tree.release(chain_c)


def test_radix_pinned_chains_never_evicted(lm):
    model, params = lm
    pool = KVBlockPool(model, num_blocks=2, block_size=BS)
    tree = RadixPrefixCache(pool)
    a = [1, 2, 3, 4]
    held = tree.insert(a, row_cache_for(model, params, a), 0)  # acquired
    b = [5, 6, 7, 8]
    chain_b = tree.insert(b, row_cache_for(model, params, b), 0)
    assert chain_b == [] and tree.insert_skips == 1 and tree.evictions == 0, \
        "a fully-pinned pool must skip the insert, never evict a held chain"
    assert [nd.chunk for nd in tree.lookup(a)] == [(1, 2), (3, 4)]
    tree.release(held)
    # released chain becomes evictable: the same insert now succeeds
    chain_b = tree.insert(b, row_cache_for(model, params, b), 0)
    assert len(chain_b) == 2 and tree.evictions == 2
    tree.release(chain_b)


# -- serving-tier exactness across hit depths -------------------------------

def hit_depth_prompts(rng):
    """(prompt, expected_hit_tokens) pairs driven in order through one
    pool: empty tree, partial-block overlap (block-aligned down to 2),
    multi-block, and an identical resubmit (full-prompt, capped one
    block short so ≥ 1 suffix token feeds the prefill)."""
    base = [int(t) for t in rng.integers(0, VOCAB, size=8)]
    return [
        (base, 0),                                    # cold tree
        (base[:3] + [base[3] ^ 1] + base[4:], 2),     # diverges in block 2
        (base[:6] + [59, 58], 6),                     # 3 shared blocks
        (base, 6),                                    # full prompt, capped
    ]


@pytest.mark.parametrize("kind", ["mha", "gqa", "mqa", "penalties"])
def test_hit_depths_token_exact(lm, kind):
    if kind in ("gqa", "mqa"):
        model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                              num_kv_heads=2 if kind == "gqa" else 1)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    else:
        model, params = lm
    gen_kw = ({"presence_penalty": 0.5, "frequency_penalty": 0.3}
              if kind == "penalties" else {})
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       penalties=kind == "penalties", kv_block_size=BS,
                       kv_cache_blocks=16)
    saved = 0
    for prompt, hit in hit_depth_prompts(np.random.default_rng(3)):
        rid = srv.submit(prompt, max_new=6, **gen_kw)
        done = {c.id: c for c in srv.run_until_drained()}
        assert done[rid].tokens == expected(model, params, prompt, 6,
                                            **gen_kw), \
            f"{kind}: diverged at expected hit depth {hit}"
        saved += hit
        assert srv.prefix_cache_stats()["cached_tokens_saved"] == saved, \
            f"{kind}: wrong hit depth for {prompt}"
    pc = srv.prefix_cache_stats()
    assert pc["lookups"] == 4 and pc["hits"] == 3
    assert pc["prefix_hit_rate"] == pytest.approx(0.75)


def test_hit_depths_with_static_prefix_and_int8(lm):
    """Radix chains sit at absolute positions AFTER the pool-level static
    prefix; int8 caches add k_scale/v_scale leaves to every block."""
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                          kv_cache_dtype="int8")
    params = model.init(jax.random.PRNGKey(2),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    pre = [20, 21, 22]
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=32,
                       prefix=pre, kv_block_size=BS, kv_cache_blocks=16)
    for prompt, _ in hit_depth_prompts(np.random.default_rng(5)):
        rid = srv.submit(prompt, max_new=5)
        done = {c.id: c for c in srv.run_until_drained()}
        assert done[rid].tokens == expected(model, params, pre + prompt, 5)
    assert srv.prefix_cache_stats()["hits"] == 3


def test_prompt_bucket_shrinks_after_hit(lm):
    """A radix hit must move the suffix into a SMALLER prompt bucket —
    the prefill-FLOPs reduction the cache exists for — visible in the
    ``prefill_tokens`` counter."""
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       prompt_buckets=(2, 4, 8), kv_block_size=BS,
                       kv_cache_blocks=16)
    p = [4, 9, 14, 19, 24, 29, 34, 39]
    srv.submit(p, max_new=2)
    srv.run_until_drained()
    cold = srv.stats()["prefill_tokens"]
    assert cold == 8
    rid = srv.submit(p, max_new=2)             # full-prompt hit (capped 6)
    done = {c.id: c for c in srv.run_until_drained()}
    assert done[rid].tokens == expected(model, params, p, 2)
    assert srv.stats()["prefill_tokens"] - cold == 2, \
        "6-token hit should drop the 8-bucket prefill to the 2-bucket"


# -- block-native paged decode path ----------------------------------------

@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("kind", ["mha", "gqa"])
def test_hit_depths_paged_token_exact(lm, kind, kernel):
    """The tentpole exactness claim: with ``paged_kernel`` set, radix
    hits are consumed IN PLACE through the block table (no contiguous
    gather) and every hit depth stays token-exact vs `generate` — the
    zero hit region of the row cache is mask-excluded, the table chain
    covers it."""
    if kind == "gqa":
        model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                              num_kv_heads=2)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    else:
        model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       kv_block_size=BS, kv_cache_blocks=16,
                       paged_kernel=kernel)
    saved_blocks = 0
    for prompt, hit in hit_depth_prompts(np.random.default_rng(3)):
        rid = srv.submit(prompt, max_new=6)
        done = {c.id: c for c in srv.run_until_drained()}
        assert done[rid].tokens == expected(model, params, prompt, 6), \
            f"{kind}/{kernel}: diverged at expected hit depth {hit}"
        saved_blocks += hit // BS
    st = srv.stats()
    assert st["kv_gather_bytes_saved"] == \
        saved_blocks * srv._block_pool.bytes_per_block
    assert st["config"]["paged_kernel"] == kernel
    assert srv.prefix_cache_stats()["hits"] == 3


def test_paged_seeded_sampling_matches_gathered(lm):
    """Paged and gathered hit consumption must produce IDENTICAL sampled
    streams under a pinned seed — same logits bit-for-bit, same
    categorical draws — or managed-recovery replays would fork."""
    model, params = lm
    streams = {}
    for kernel in (None, "xla", "pallas"):
        srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                           kv_block_size=BS, kv_cache_blocks=16,
                           paged_kernel=kernel)
        out = []
        for prompt, _ in hit_depth_prompts(np.random.default_rng(7)):
            rid = srv.submit(prompt, max_new=6, temperature=0.8,
                             top_p=0.9, seed=42)
            out.append({c.id: c for c in srv.run_until_drained()}[rid].tokens)
        streams[kernel] = out
        assert srv.prefix_cache_stats()["hits"] == 3
    assert streams["xla"] == streams[None], "paged xla forked the stream"
    assert streams["pallas"] == streams[None], "paged pallas forked the stream"


@pytest.mark.parametrize("kernel,resolved", [("auto", "xla"),
                                             ("pallas", "pallas")])
def test_paged_int8_static_prefix_token_exact(lm, kernel, resolved):
    """Quantized pools run BOTH backends (ISSUE 16): "auto" keeps the
    earn-it-or-swap default (no int8 forcing anymore — it resolves the
    same as an f32 pool), and an explicit "pallas" dequantizes the
    block tiles in-kernel and stays token-exact at every hit depth."""
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                          kv_cache_dtype="int8")
    params = model.init(jax.random.PRNGKey(2),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    pre = [20, 21, 22]
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=32,
                       prefix=pre, kv_block_size=BS, kv_cache_blocks=16,
                       paged_kernel=kernel)
    assert srv.paged_kernel == resolved
    for prompt, _ in hit_depth_prompts(np.random.default_rng(5)):
        rid = srv.submit(prompt, max_new=5)
        done = {c.id: c for c in srv.run_until_drained()}
        assert done[rid].tokens == expected(model, params, pre + prompt, 5)
    assert srv.prefix_cache_stats()["hits"] == 3
    assert srv.stats()["kv_gather_bytes_saved"] > 0


def test_paged_requires_blocks_and_scan(lm):
    model, params = lm
    with pytest.raises(ValueError, match="kv_block_size"):
        DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                     paged_kernel="xla")


def test_write_block_rejects_out_of_range_offset(lm):
    """Regression for the absolute-position footgun: `write_block`
    offsets are ABSOLUTE cache positions. A caller that forgets the
    static prefix (or double-counts it) walks past the row cache — the
    pool must refuse instead of silently storing zeros."""
    model, params = lm
    cache = row_cache_for(model, params, [5, 11, 17, 23])
    pool = KVBlockPool(model, num_blocks=2, block_size=BS)
    bid = pool.alloc()
    with pytest.raises(ValueError, match="ABSOLUTE"):
        pool.write_block(bid, cache, 4)        # 4 + BS > 4-token cache
    with pytest.raises(ValueError, match="ABSOLUTE"):
        pool.write_block(bid, cache, -1)
    # the prefix-ahead layout that motivated the check: a 3-token static
    # prefix shifts the request tokens to positions [3, 7) — block 0 of
    # the request lives at absolute offset 3, NOT 0
    pre_cache = row_cache_for(model, params, [20, 21, 22, 5, 11, 17, 23])
    pool.write_block(bid, pre_cache, 3)
    got = kv_leaves(pool.gather([bid]))
    src = kv_leaves(pre_cache)
    for key, leaf in got.items():
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(src[key][:, 3:3 + BS]),
            err_msg=f"prefix-ahead write landed wrong at {key}")


# -- eviction under slot churn (satellite: cache pressure never corrupts) --

def test_eviction_under_churn_token_exact(lm):
    """A pool far too small for the workload: every admission evicts or
    skips, long-lived co-resident rows pin their chains the whole time,
    and every stream must stay exact with nonzero eviction traffic."""
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       kv_block_size=BS, kv_cache_blocks=4)
    rng = np.random.default_rng(17)
    reqs = {}
    long_prompt = [int(t) for t in rng.integers(0, VOCAB, size=7)]
    reqs[srv.submit(long_prompt, max_new=14)] = (long_prompt, 14)
    for _ in range(8):                          # churn the second slot
        p = [int(t) for t in rng.integers(0, VOCAB, size=6)]
        reqs[srv.submit(p, max_new=2)] = (p, 2)
    done = {c.id: c for c in srv.run_until_drained()}
    assert set(done) == set(reqs)
    for rid, (p, mn) in reqs.items():
        assert done[rid].tokens == expected(model, params, p, mn), \
            f"stream {rid} corrupted under eviction pressure"
    pc = srv.prefix_cache_stats()
    assert pc["evictions"] > 0, "4-block pool must have evicted"
    assert pc["kv_blocks_used"] + pc["kv_blocks_free"] == 4
    # every request retired → every chain released → nothing stays pinned
    assert all(srv._block_pool.refcount(b) == 0
               for b in list(srv._block_pool._refs))


def test_admission_survives_unallocatable_pool(lm):
    """Two live rows can pin the entire pool; later admissions must
    serve exactly (cache-off path) with ``insert_skips`` counted —
    never blocked, never corrupted."""
    model, params = lm
    srv = DecodeServer(model, params, slots=3, prompt_len=8, max_len=24,
                       kv_block_size=BS, kv_cache_blocks=2)
    a, b, c = ([1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12])
    ra = srv.submit(a, max_new=12)              # pins 2 blocks for a while
    rb = srv.submit(b, max_new=12)              # pool now unallocatable
    rc = srv.submit(c, max_new=3)
    done = {x.id: x for x in srv.run_until_drained()}
    assert done[ra].tokens == expected(model, params, a, 12)
    assert done[rb].tokens == expected(model, params, b, 12)
    assert done[rc].tokens == expected(model, params, c, 3)
    assert srv.prefix_cache_stats()["insert_skips"] >= 1


# -- recovery / rebuild -----------------------------------------------------

def test_rebuild_cold_miss_token_exact(lm):
    """`lm_manager` node-death recovery rebuilds a pool from its
    journaled spec (kv_block_size/kv_cache_blocks ride the spec —
    `serve/control.py`): the new pool starts with an EMPTY tree, so
    resubmitted requests cold-miss and recompute rather than replaying
    another node's blocks. Cited from `serve/lm_manager.py:_recover_pool`."""
    model, params = lm
    spec = dict(slots=2, prompt_len=8, max_len=24, kv_block_size=BS,
                kv_cache_blocks=8)
    prompt = [3, 1, 4, 1, 5, 9]
    first = DecodeServer(model, params, **spec)
    for _ in range(2):                          # seed + hit on the old node
        first.submit(prompt, max_new=4)
        first.run_until_drained()
    assert first.prefix_cache_stats()["hits"] == 1
    assert first.stats()["config"]["kv_block_size"] == BS, \
        "spec must carry the cache config or recovery rebuilds cache-off"

    rebuilt = DecodeServer(model, params, **spec)   # recovery path
    rid = rebuilt.submit(prompt, max_new=4)
    done = {c.id: c for c in rebuilt.run_until_drained()}
    pc = rebuilt.prefix_cache_stats()
    assert pc["hits"] == 0 and pc["lookups"] == 1, "rebuild must cold-miss"
    assert done[rid].tokens == expected(model, params, prompt, 4)


# -- stats plumbing ---------------------------------------------------------

def test_stats_surface(lm):
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       kv_block_size=BS, kv_cache_blocks=8)
    assert "prefix_cache" not in DecodeServer(
        model, params, slots=2, prompt_len=8, max_len=24).stats(), \
        "cache-off pools must not grow a prefix_cache stats section"
    srv.submit([1, 2, 3, 4], max_new=2)
    srv.run_until_drained()
    s = srv.stats()
    pc = s["prefix_cache"]
    for k in ("prefix_hit_rate", "lookups", "hits", "cached_tokens_saved",
              "kv_blocks_free", "kv_blocks_used", "evictions",
              "insert_skips", "inserted_blocks", "nodes"):
        assert k in pc, f"missing gauge {k}"
    assert s["config"]["kv_block_size"] == BS
    assert s["config"]["kv_cache_blocks"] == 8


def test_metrics_lm_gauges_roundtrip():
    """`lm_stats` pushes the gauges into the C8 tracker; they must ride
    the failover wire format (`serve/metrics.py`)."""
    from idunno_tpu.serve.metrics import MetricsTracker
    m = MetricsTracker()
    assert m.lm_gauges("pool") is None
    g = {"prefix_hit_rate": 0.5, "cached_tokens_saved": 12,
         "kv_blocks_free": 3, "kv_blocks_used": 5}
    m.record_lm_gauges("pool", g)
    assert m.lm_gauges("pool") == g
    m2 = MetricsTracker()
    m2.load_wire(m.to_wire())
    assert m2.lm_gauges("pool") == g


# -- tensor parallelism over the paged pool (ISSUE 9) -----------------------

def test_paged_tp_hit_depths_token_exact(lm, eight_devices):
    """TP composes with the paged block pool: the block stores shard
    their KV-head dim over the model axis (block axis stays whole, so
    the host-side free-list is unchanged) and every radix hit depth
    stays token-exact vs `generate` under n_model=2 — greedy AND a
    pinned-seed sampled stream."""
    from idunno_tpu.parallel.mesh import MODEL_AXIS

    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       kv_block_size=BS, kv_cache_blocks=16,
                       paged_kernel="xla", n_model=2)
    assert srv.n_model == 2
    # the stores actually carry the model axis on the KV head dim
    k_store = next(s for key, s in srv._block_pool._stores.items()
                   if "cached_k" in key)
    assert MODEL_AXIS in tuple(k_store.sharding.spec)
    ref = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       kv_block_size=BS, kv_cache_blocks=16,
                       paged_kernel="xla")
    for prompt, hit in hit_depth_prompts(np.random.default_rng(3)):
        rid = srv.submit(prompt, max_new=6)
        done = {c.id: c for c in srv.run_until_drained()}
        assert done[rid].tokens == expected(model, params, prompt, 6), \
            f"TP paged diverged at expected hit depth {hit}"
        sid = srv.submit(prompt, max_new=6, temperature=0.8, top_p=0.9,
                         seed=42)
        sampled = {c.id: c for c in srv.run_until_drained()}[sid].tokens
        fid = ref.submit(prompt, max_new=6, temperature=0.8, top_p=0.9,
                         seed=42)
        ref.submit(prompt, max_new=6)             # keep hit depths aligned
        ref_sampled = {c.id: c for c in ref.run_until_drained()}[fid].tokens
        assert sampled == ref_sampled, \
            f"TP paged sampled stream forked at hit depth {hit}"
    assert srv.prefix_cache_stats()["hits"] >= 3

# -- cluster-wide prefix cache over the SDFS ring (ISSUE 17) ----------------

from idunno_tpu.serve.cluster_prefix import ClusterPrefixCache  # noqa: E402
from idunno_tpu.store.kv_chain import (  # noqa: E402
    MAGIC, chain_names, decode_block, encode_block)
from idunno_tpu.store.sdfs import StoreError  # noqa: E402


class FakeRing:
    """In-memory stand-in for `FileStoreService`'s client surface with
    the two semantics the subsystem leans on: monotone versions that
    bump PAST a tombstone on republish, and typed StoreError misses."""

    def __init__(self):
        self.blobs: dict[str, tuple[bytes, int]] = {}
        self.tombs: dict[str, int] = {}

    def put_bytes(self, name, blob):
        v = max(self.blobs.get(name, (b"", 0))[1],
                self.tombs.get(name, 0)) + 1
        self.blobs[name] = (bytes(blob), v)
        return v

    def get_bytes(self, name, version=None):
        if name not in self.blobs:
            raise StoreError(f"{name}: not found")
        return self.blobs[name]

    def stat(self, name):
        if name not in self.blobs:
            raise StoreError(f"{name}: not found")
        return self.blobs[name][1], ("n0",)

    def delete(self, name):
        if name in self.blobs:
            self.tombs[name] = self.blobs.pop(name)[1]


def cluster_pair(model, params, ring, ns="ns-test", **kw):
    """Publisher + cold consumer sharing one ring and namespace — the
    two-replica shape every cluster test reduces to. The cluster cache
    is attached the way `serve/control.py` attaches it post-warmup."""
    spec = dict(slots=2, prompt_len=8, max_len=24, kv_block_size=BS,
                kv_cache_blocks=16)
    spec.update(kw)
    out = []
    for _ in range(2):
        srv = DecodeServer(model, params, **spec)
        srv.cluster_prefix = ClusterPrefixCache(ring, ns, BS,
                                                publish_min_hits=0)
        out.append(srv)
    return out


def test_kv_chain_codec_roundtrip():
    arrays = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
              "b": np.asarray([[1, -2]], np.int8),
              "c": np.asarray(jnp.ones((2, 2), jnp.bfloat16))}
    meta = {"tokens": [5, 7], "depth": 0, "namespace": "ns",
            "block_size": 2}
    blob = encode_block(meta, arrays)
    assert blob[:4] == MAGIC
    got_meta, got = decode_block(blob, expect_tokens=[5, 7])
    assert got_meta["depth"] == 0
    for k, arr in arrays.items():
        np.testing.assert_array_equal(got[k], np.asarray(arr))
        assert got[k].dtype == np.asarray(arr).dtype, k
    # the correctness guard: embedded tokens must match the expected
    # chunk, and a non-KVC1 payload is refused outright
    with pytest.raises(ValueError, match="token mismatch"):
        decode_block(blob, expect_tokens=[5, 8])
    with pytest.raises(ValueError, match="magic"):
        decode_block(b"XXXX" + blob[4:])
    # bit-stable encoding: identical content → identical bytes
    assert encode_block(meta, arrays) == blob


def test_chain_names_prefix_and_namespace_properties():
    names = chain_names("ns", [1, 2, 3, 4], 2)
    assert len(names) == 2
    # depth-j name commits to chunks 0..j: extending the prompt keeps
    # the shallower names (the dedupe property), the partial tail token
    # contributes nothing
    assert chain_names("ns", [1, 2, 3, 4, 9], 2) == names
    assert chain_names("ns", [1, 2, 3, 4, 5, 6], 2)[:2] == names
    # different namespace or different head → fully disjoint names
    assert not set(chain_names("other", [1, 2, 3, 4], 2)) & set(names)
    assert chain_names("ns", [9, 2, 3, 4], 2)[1] != names[1]


def test_graft_contract(lm):
    """`RadixPrefixCache.graft`: inserts fetched blocks contiguously at
    start_depth, reuses chunks already present (idempotent replays),
    and refuses both a missing walk chunk and a chunk/prompt mismatch
    (the double-prefill guards)."""
    model, params = lm
    cache = row_cache_for(model, params, [1, 2, 3, 4])
    src = KVBlockPool(model, num_blocks=2, block_size=BS)
    bids = [src.alloc(), src.alloc()]
    for j, bid in enumerate(bids):
        src.write_block(bid, cache, j * BS)
    fetched = [([1, 2], src.read_block(bids[0])),
               ([3, 4], src.read_block(bids[1]))]
    pool = KVBlockPool(model, num_blocks=4, block_size=BS)
    tree = RadixPrefixCache(pool)
    assert tree.graft([1, 2, 3, 4], fetched, 0) == 2
    hit = tree.lookup([1, 2, 3, 4])
    assert [nd.chunk for nd in hit] == [(1, 2), (3, 4)]
    # the grafted KV is byte-identical to the source pool's blocks
    got = kv_leaves(pool.gather([nd.block for nd in hit]))
    src_leaves = kv_leaves(cache)
    for key, leaf in got.items():
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(src_leaves[key][:, :2 * BS]))
    # graft leaves the chain UNPINNED (refcount 0): the admission path
    # re-runs lookup and acquires it itself
    assert all(pool.refcount(nd.block) == 0 for nd in hit)
    assert tree.graft([1, 2, 3, 4], fetched, 0) == 0, \
        "re-graft of present chunks must reuse, not duplicate"
    with pytest.raises(ValueError, match="missing"):
        tree.graft([9, 9, 3, 4], fetched[1:], 1)
    with pytest.raises(ValueError, match="does not match"):
        tree.graft([1, 2, 9, 9], fetched[1:], 1)


@pytest.mark.parametrize("kernel", [None, "xla", "pallas"])
@pytest.mark.parametrize("kind", ["mha", "gqa"])
def test_cluster_remote_hit_token_exact(lm, kind, kernel):
    """The tentpole exactness matrix: a cold consumer replica extends
    its (empty or shorter) local hit with the publisher's ring chain at
    EVERY hit depth, staying token-exact vs `generate` — for MHA and
    GQA pools, gathered and both paged kernels."""
    if kind == "gqa":
        model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                              num_kv_heads=2)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    else:
        model, params = lm
    ring = FakeRing()
    kw = {"paged_kernel": kernel} if kernel else {}
    pub, sub = cluster_pair(model, params, ring, **kw)
    prompts = hit_depth_prompts(np.random.default_rng(3))
    for prompt, _ in prompts:        # publisher inserts + publishes
        rid = pub.submit(prompt, max_new=6)
        done = {c.id: c for c in pub.run_until_drained()}
        assert done[rid].tokens == expected(model, params, prompt, 6)
    assert pub.cluster_prefix.published_blocks >= 4
    # consumer drives the same depths: prompt 0 is local-NONE (whole
    # chain from the ring), prompt 1 is local-SHORTER (2 local blocks,
    # ring extends to 3), prompts 2-3 are full local hits
    for i, (prompt, hit) in enumerate(prompts):
        rid = sub.submit(prompt, max_new=6)
        done = {c.id: c for c in sub.run_until_drained()}
        assert done[rid].tokens == expected(model, params, prompt, 6), \
            f"{kind}/{kernel}: remote hit diverged at matrix row {i} " \
            f"(expected local hit depth {hit})"
    st = sub.prefix_cache_stats()
    assert st["prefix_remote_hits"] == 2, \
        "rows 0 (local-none) and 1 (local-shorter) must remote-hit"
    assert st["prefix_fetch_bytes"] > 0
    assert st["hits"] >= 3


def test_cluster_tp_remote_hit_token_exact(lm, eight_devices):
    """The matrix's n_model=2 column: the consumer's block stores shard
    KV heads over the model axis, and grafted ring blocks must land
    sharded AND token-exact at every depth."""
    model, params = lm
    ring = FakeRing()
    pub, sub = cluster_pair(model, params, ring, paged_kernel="xla",
                            n_model=2)
    assert sub.n_model == 2
    prompts = hit_depth_prompts(np.random.default_rng(3))
    for prompt, _ in prompts:
        rid = pub.submit(prompt, max_new=6)
        done = {c.id: c for c in pub.run_until_drained()}
        assert done[rid].tokens == expected(model, params, prompt, 6)
    for i, (prompt, hit) in enumerate(prompts):
        rid = sub.submit(prompt, max_new=6)
        done = {c.id: c for c in sub.run_until_drained()}
        assert done[rid].tokens == expected(model, params, prompt, 6), \
            f"TP remote hit diverged at matrix row {i} (local {hit})"
    assert sub.prefix_cache_stats()["prefix_remote_hits"] == 2


def test_cluster_int8_static_prefix_remote_hit(lm):
    """int8 caches add per-block k_scale/v_scale leaves to every blob,
    and a pool-level static prefix shifts chains to absolute positions
    AFTER it — both must survive the encode/ship/graft trip."""
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                          kv_cache_dtype="int8")
    params = model.init(jax.random.PRNGKey(2),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    ring = FakeRing()
    pub, sub = cluster_pair(model, params, ring, prefix=[20, 21, 22],
                            max_len=32)
    prompts = hit_depth_prompts(np.random.default_rng(5))
    for prompt, _ in prompts:
        rid = pub.submit(prompt, max_new=5)
        done = {c.id: c for c in pub.run_until_drained()}
        assert done[rid].tokens == expected(model, params,
                                            [20, 21, 22] + prompt, 5)
    for i, (prompt, _) in enumerate(prompts):
        rid = sub.submit(prompt, max_new=5)
        done = {c.id: c for c in sub.run_until_drained()}
        assert done[rid].tokens == expected(model, params,
                                            [20, 21, 22] + prompt, 5), \
            f"int8+prefix remote hit diverged at matrix row {i}"
    assert sub.prefix_cache_stats()["prefix_remote_hits"] == 2


def test_cluster_remote_hit_prefills_only_suffix(lm):
    """The acceptance claim, structurally: a remote hit moves the
    consumer's prefill into a SMALLER prompt bucket — only the suffix
    is recomputed (visible in `prefill_tokens`, same oracle as
    `test_prompt_bucket_shrinks_after_hit`)."""
    model, params = lm
    ring = FakeRing()
    pub, sub = cluster_pair(model, params, ring,
                            prompt_buckets=(2, 4, 8))
    p = [4, 9, 14, 19, 24, 29, 34, 39]
    pub.submit(p, max_new=2)
    pub.run_until_drained()
    rid = sub.submit(p, max_new=2)
    done = {c.id: c for c in sub.run_until_drained()}
    assert done[rid].tokens == expected(model, params, p, 2)
    assert sub.stats()["prefill_tokens"] == 2, \
        "remote 6-token hit must drop the cold 8-bucket to the 2-bucket"
    assert sub.prefix_cache_stats()["prefix_remote_hits"] == 1


def test_cluster_warm_then_first_request_suffix_only(lm):
    """Warm-at-spawn: `prefix_warm(tenant=...)` pulls the tenant's
    published set off the warm index into a FRESH replica, whose very
    first request then prefills only the suffix."""
    model, params = lm
    ring = FakeRing()
    pub, sub = cluster_pair(model, params, ring,
                            prompt_buckets=(2, 4, 8))
    p = [4, 9, 14, 19, 24, 29, 34, 39]
    pub.cluster_prefix.note(p, "acme")     # serve/lm_pool.py notes at submit
    pub.submit(p, max_new=2)
    pub.run_until_drained()
    out = sub.prefix_warm(tenant="acme")
    assert out["fetched_blocks"] == 4, \
        "warm must pull the tenant's whole published chain"
    st = sub.prefix_cache_stats()
    assert st["prefix_warm_blocks"] == 4
    assert st["prefix_remote_hits"] == 0, "warm is not an admission hit"
    rid = sub.submit(p, max_new=2)
    done = {c.id: c for c in sub.run_until_drained()}
    assert done[rid].tokens == expected(model, params, p, 2)
    assert sub.stats()["prefill_tokens"] == 2, \
        "warmed replica's FIRST request must prefill only the suffix"
    # probe surfaces both views
    probe = sub.prefix_probe(p)
    assert probe["remote_blocks"] == 4 and probe["local_blocks"] >= 3


def test_cluster_evict_tombstone_and_force_republish(lm):
    """Eviction is an SDFS tombstone; a FORCED republish (the explicit
    `prefix_publish` verb) bumps versions past it even though the
    publisher's own memo cannot see another pool's eviction, and a
    fresh consumer remote-hits the republished chain token-exactly."""
    model, params = lm
    ring = FakeRing()
    pub, sub = cluster_pair(model, params, ring)
    p = [4, 9, 14, 19, 24, 29, 34, 39]
    pub.submit(p, max_new=2)
    pub.run_until_drained()
    names = pub.cluster_prefix.names(p)
    v0 = {n: ring.stat(n)[0] for n in names}
    # another pool evicts the chain cluster-wide
    evictor = ClusterPrefixCache(ring, "ns-test", BS)
    assert evictor.evict(p) == 4
    for n in names:
        with pytest.raises(StoreError):
            ring.stat(n)
    fresh = ClusterPrefixCache(ring, "ns-test", BS)
    assert fresh.probe(p) == 0, "tombstoned chain must probe as a miss"
    # the publisher still holds the chain locally: the explicit verb
    # republishes (force bypasses only the MEMO, not the ring stat)
    out = pub.prefix_publish(tokens=p)
    assert out["published_blocks"] == 4
    for n in names:
        assert ring.stat(n)[0] > v0[n], "republish must outrank tombstone"
    rid = sub.submit(p, max_new=2)
    done = {c.id: c for c in sub.run_until_drained()}
    assert done[rid].tokens == expected(model, params, p, 2)
    assert sub.prefix_cache_stats()["prefix_remote_hits"] == 1


def test_cluster_miss_degrades_never_fails(lm):
    """Failure policy: a ring that errors on every call must degrade
    every admission to its local hit — exact tokens, errors counted,
    serving never raises."""

    class BrokenRing:
        def put_bytes(self, *a):
            raise OSError("ring down")
        get_bytes = stat = delete = put_bytes

    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       kv_block_size=BS, kv_cache_blocks=16)
    srv.cluster_prefix = ClusterPrefixCache(BrokenRing(), "ns", BS,
                                            publish_min_hits=0)
    for prompt, _ in hit_depth_prompts(np.random.default_rng(3)):
        rid = srv.submit(prompt, max_new=6)
        done = {c.id: c for c in srv.run_until_drained()}
        assert done[rid].tokens == expected(model, params, prompt, 6)
    st = srv.prefix_cache_stats()
    assert st["prefix_remote_hits"] == 0
    assert srv.cluster_prefix.errors > 0
    assert st["hits"] >= 3, "local radix hits must be untouched"


# -- DistServe KV-block handoff, prefill → decode (ISSUE 18) ----------------


def handoff_pair(model, params, **kw):
    """Prefill replica + decode replica, transport-direct (no ring): the
    two-pool shape `serve/lm_manager.py:_handoff_ship` drives via the
    `kv_handoff` verb."""
    spec = dict(slots=2, prompt_len=8, max_len=24, kv_block_size=BS,
                kv_cache_blocks=16)
    spec.update(kw)
    return DecodeServer(model, params, **spec), \
        DecodeServer(model, params, **spec)


def ship(pre, dec, prompt):
    """One probe→export→adopt round trip, the manager's ship leg."""
    d0 = dec.handoff_probe(prompt)["depth"]
    exp = pre.handoff_export(prompt, from_depth=d0)
    return dec.handoff_adopt(prompt, exp["blobs"], start_depth=d0), exp


@pytest.mark.parametrize("kernel", [None, "xla", "pallas"])
@pytest.mark.parametrize("kind", ["mha", "gqa"])
def test_handoff_token_exact_matrix(lm, kind, kernel):
    """The ISSUE 18 exactness matrix: a prompt prefilled on one replica
    and shipped block-by-block to another must decode token-for-token
    like `generate` — at every local hit depth (cold, partial-block,
    multi-block, full resubmit), for MHA and GQA pools, gathered and
    both paged kernels. The full-resubmit row doubles as the delta-ship
    proof: the probe reports the chain present, so the export ships
    ZERO blobs and no bytes move."""
    if kind == "gqa":
        model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                              num_kv_heads=2)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    else:
        model, params = lm
    kw = {"paged_kernel": kernel} if kernel else {}
    pre, dec = handoff_pair(model, params, **kw)
    prompts = hit_depth_prompts(np.random.default_rng(3))
    shipped_bytes = 0
    for i, (prompt, _) in enumerate(prompts):
        adopt, exp = ship(pre, dec, prompt)
        shipped_bytes += exp["bytes"]
        if i < 2:   # cold chain / divergent tail: blocks move
            assert exp["blocks"] > 0 and adopt["wrote"] > 0, i
        else:       # rows 2-3 share their whole usable head with row 0:
            # the probe sees it held and the export ships NOTHING
            assert exp["blocks"] == 0 and exp["bytes"] == 0, \
                "delta-only ship: a held chain must ship nothing"
        assert adopt["depth"] >= (len(prompt) - 1) // BS
        rid = dec.submit(prompt, max_new=6)
        done = {c.id: c for c in dec.run_until_drained()}
        assert done[rid].tokens == expected(model, params, prompt, 6), \
            f"{kind}/{kernel}: handed-off request diverged at row {i}"
    assert dec.stats()["kv_handoff_bytes"] == shipped_bytes
    # the gauge counts SHIPS: the two zero-delta exports are free
    assert pre.stats()["kv_handoff_requests"] == 2
    assert pre.stats()["tokens_generated"] == 0, \
        "the prefill replica must never decode a shipped request"


def test_handoff_zero_reprefill_for_shipped_blocks(lm):
    """The acceptance claim, structurally: after the adopt, the decode
    replica's admission prefills ONLY the sub-block suffix — the same
    bucket-drop oracle as the cluster cache — and a replayed adopt
    converges (writes nothing new) instead of doubling blocks."""
    model, params = lm
    pre, dec = handoff_pair(model, params, prompt_buckets=(2, 4, 8))
    p = [4, 9, 14, 19, 24, 29, 34, 39]
    adopt, exp = ship(pre, dec, p)
    assert adopt["wrote"] == 3 and adopt["depth"] == 3
    # replay (duplicated ship after a mid-handoff death): same state
    adopt2 = dec.handoff_adopt(p, exp["blobs"], start_depth=exp["depth"])
    assert adopt2["wrote"] == 0 and adopt2["depth"] == 3
    rid = dec.submit(p, max_new=2)
    done = {c.id: c for c in dec.run_until_drained()}
    assert done[rid].tokens == expected(model, params, p, 2)
    assert dec.stats()["prefill_tokens"] == 2, \
        "shipped 6-token head must drop the cold 8-bucket to the 2-bucket"
    # the prefill side paid exactly one full-head fill for the ship
    assert pre.stats()["kv_handoff_requests"] == 1
    assert pre.stats()["prefill_tokens"] > 0


def test_handoff_int8_static_prefix_token_exact(lm):
    """int8 block scales and a pool-level static prefix ride the same
    KVC1 encode/graft trip the cluster cache proved — handoff must
    compose with both."""
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                          kv_cache_dtype="int8")
    params = model.init(jax.random.PRNGKey(2),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    pre, dec = handoff_pair(model, params, prefix=[20, 21, 22],
                            max_len=32)
    for i, (prompt, _) in enumerate(
            hit_depth_prompts(np.random.default_rng(5))):
        ship(pre, dec, prompt)
        rid = dec.submit(prompt, max_new=5)
        done = {c.id: c for c in dec.run_until_drained()}
        assert done[rid].tokens == expected(
            model, params, [20, 21, 22] + prompt, 5), \
            f"int8+prefix handoff diverged at row {i}"


def test_handoff_tp_token_exact(lm, eight_devices):
    """The matrix's n_model=2 column: exported blobs come off a
    model-sharded block pool and graft into another — exact at every
    depth."""
    model, params = lm
    pre, dec = handoff_pair(model, params, paged_kernel="xla", n_model=2)
    assert dec.n_model == 2
    for i, (prompt, _) in enumerate(
            hit_depth_prompts(np.random.default_rng(3))):
        ship(pre, dec, prompt)
        rid = dec.submit(prompt, max_new=6)
        done = {c.id: c for c in dec.run_until_drained()}
        assert done[rid].tokens == expected(model, params, prompt, 6), \
            f"TP handoff diverged at matrix row {i}"


def test_handoff_validation_and_fallback_counter(lm):
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24)
    with pytest.raises(ValueError, match="KV block tier"):
        srv.handoff_probe([1, 2, 3])
    pre, dec = handoff_pair(model, params)
    p = [4, 9, 14, 19, 24, 29, 34, 39]
    exp = pre.handoff_export(p)
    # a blob claiming a depth past the prompt's full blocks is refused
    with pytest.raises(ValueError, match="full blocks"):
        dec.handoff_adopt(p, exp["blobs"], start_depth=4)
    # wrong-prompt adoption: the KVC1 token guard refuses the graft
    with pytest.raises(ValueError, match="token mismatch"):
        dec.handoff_adopt([9] * 8, exp["blobs"], start_depth=0)
    assert dec.handoff_fallback()["fallbacks"] == 1
    assert dec.stats()["kv_handoff_fallbacks"] == 1
