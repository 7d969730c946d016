"""The hybrid stack (`models/hybrid.py`: block-sparse attention with its
pooled-key cache beside per-slot recurrent state) held to the benchmark's
plain reference (`benchmark/families/minicpm_sala/reference.py`) on seeded
weights, float32, CPU: the full forward, prefill then decode through
`DecodeServer`'s slot cache, and what the pool refuses on such a stack."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import manifest
from idunno_tpu.engine.generate import decode_model, init_cache
from idunno_tpu.engine.serve_lm import DecodeServer, _prefill
from idunno_tpu.models.hybrid import UnsupportedStack
from idunno_tpu.models.transformer import decode_apply

# tiny widths and a tiny sparse geometry, so that 40-odd tokens already pass
# `dense_len`, select blocks, complete pooled-key spans and cross a chunk
CFG = dict(
    family="minicpm_sala", hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16, vocab_size=512,
    num_hidden_layers=6,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
                 "minicpm4", "lightning-attn"],
    layer_ids=[9, 10, 11, 16, 17, 18], published={"num_hidden_layers": 32},
    mup_denominator=32, scale_emb=12, scale_depth=1.4, dim_model_base=16,
    rms_norm_eps=1e-6, rope_theta=10000,
    sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8, topk=2,
                       init_blocks=1, window_size=16, dense_len=32),
    as_run={"dtype": "float32"})
TOL = 2e-4       # float32 on the CPU, logits of order 1


@pytest.fixture(scope="module")
def fam():
    return manifest.Manifest().family(CFG)


@pytest.fixture(scope="module")
def weights(fam):
    """Seeded weights with every norm scale moved off 1, so that a path
    that dropped one would show."""
    w = fam.weights.make_weights(CFG, 7)
    rng = np.random.default_rng(0)
    for k in list(w):
        if k.split("_")[-1] in ("ln1", "ln2", "qn", "kn", "on", "f"):
            w[k] = w[k] * (1 + 0.1 * jnp.asarray(
                rng.standard_normal(w[k].shape), w[k].dtype))
    return w


@pytest.fixture(scope="module")
def built(fam, weights):
    model, params, kw = fam.program.build(CFG, weights)
    assert kw == {}
    return model, params


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


def _chunks(model, params, toks, total, chunk, valid):
    """Prefill ``toks`` padded to ``total`` in chunks: (logits, cache)."""
    dec = decode_model(model, total)
    cache = init_cache(model, 1, total)
    cache["valid"] = jnp.int32(valid)
    pad = np.zeros((1, total), np.int32)
    pad[0, :len(toks)] = toks
    step = jax.jit(lambda p, c, t: decode_apply(dec, p, c, t))
    out = []
    for o in range(0, total, chunk):
        lg, cache = step(params, cache, jnp.asarray(pad[:, o:o + chunk]))
        out.append(np.asarray(lg)[0])
    return np.concatenate(out)[:len(toks)], cache


@pytest.mark.parametrize("chunk", [256, 64, 40])
def test_full_forward_matches_the_reference(fam, weights, built, chunk):
    """One apply, and chunks that cut pooled-key spans, blocks and the
    lightning sub-blocks anywhere: the same logits at every position."""
    model, params = built
    toks = _tokens(200)
    ref = fam.reference.logits_at(weights, CFG, toks, list(range(200)))
    total = 256 if chunk != 40 else 240
    got, cache = _chunks(model, params, toks, total, chunk, valid=200)
    assert np.abs(got - ref).max() < TOL
    assert int(cache["cursor"]) == total


def test_padding_enters_neither_state_nor_pooled_keys(built):
    """A prompt of 77 tokens (a multiple of nothing) padded to its bucket
    leaves the state of exactly 77 tokens, and no pooled key whose span is
    not whole at 77."""
    model, params = built
    toks = _tokens(77, seed=3)
    _lg, exact = _chunks(model, params, toks + [0] * 3, 80, 80, valid=77)
    _lg, padded = _chunks(model, params, toks + _tokens(51, seed=4), 128, 32,
                          valid=77)
    _lg, unmasked = _chunks(model, params, toks + _tokens(51, seed=4), 128,
                            32, valid=128)
    st, ks = 2, 4
    whole = (77 - ks) // st + 1          # spans [2j, 2j + 4) inside 77 tokens
    for r, (kind, _ids) in enumerate(model.runs()):
        a, b, c = (x[f"run{r}"] for x in (exact, padded, unmasked))
        if kind == "lightning-attn":
            # the state's entries are of order 10
            assert np.abs(np.asarray(a["state"] - b["state"])).max() < 1e-4
            assert np.abs(np.asarray(a["state"] - c["state"])).max() > 1e-1
        else:
            pa, pb = np.asarray(a["comp_k"]), np.asarray(b["comp_k"])
            assert np.abs(pa[:, :, :whole] - pb[:, :, :whole]).max() < 1e-5
            assert not pb[:, :, whole:].any()
            assert np.asarray(c["comp_k"])[:, :, whole:].any()


def _gaps(fam, weights, done):
    """max over served tokens of (reference's best logit - its logit of the
    served token): the benchmark's own check, on one completion."""
    toks, pl = done.tokens, done.prompt_len
    where = list(range(pl - 1, len(toks) - 1))
    ref = fam.reference.logits_at(weights, CFG, toks, where)
    served = np.asarray(toks[pl:])
    return float((ref.max(-1) - ref[np.arange(len(where)), served]).max())


def _server(built, **kw):
    model, params = built
    args = dict(slots=2, prompt_len=96, max_len=160, decode_steps=2,
                prompt_buckets=(24, 48, 96), kv_block_size=8,
                kv_cache_blocks=16, prefill_chunk=32)
    args.update(kw)
    return DecodeServer(model, params, **args)


def test_prefill_then_decode_through_the_slot_cache(fam, weights, built):
    """Three prompts over two slots: chunked (three chunks) and one-shot
    admissions, two rows of different length in one dispatch, contexts that
    start under `dense_len` and pass it while decoding, pooled-key spans
    completed by decode steps, and a slot reused after its first tenant
    retired. Every served token is the reference's best at its position."""
    srv = _server(built)
    prompts = [_tokens(70, 11), _tokens(21, 12), _tokens(45, 13)]
    ids = [srv.submit(p, max_new=n) for p, n in zip(prompts, (30, 24, 40))]
    done = {c.id: c for c in srv.run_until_drained()}
    assert sorted(done) == ids
    for rid, p in zip(ids, prompts):
        assert done[rid].tokens[:len(p)] == p
        assert _gaps(fam, weights, done[rid]) < TOL
    st = srv.stats()
    assert st["prefill_chunks"] == 3 + 2            # buckets 96 and 48
    assert st["recurrent_state_bytes"] == 4 * 3 * 2 * 4 * 16 * 16
    # contexts past dense_len (32) attend the selection, not everything
    assert 0 < st["sparse_tokens_attended"] < st["sparse_tokens_in_context"]
    # 33 tokens: block 0, the one other block, the window's 17; 100
    # tokens: block 0, the two best others, 20 in the window's blocks
    one = srv.model.attended_tokens([20, 32, 33, 100])
    assert one.tolist() == [20, 32, 8 + 8 + 17, 8 + 2 * 8 + 20]


def test_sparse_counters_add_up_request_by_request(built):
    """Rows admitted while others decode join the next step's dispatch;
    over the schedule the sparse layers' counters are what each request's
    own decode steps come to: contexts prompt + 1 .. prompt + max_new - 1
    (the prefill's token is no decode step), whatever rows shared them."""
    srv = _server(built)
    plan = {0: [(70, 9)], 2: [(21, 24)], 3: [(45, 1)], 5: [(40, 14)],
            6: [(50, 6)]}
    attended = in_context = n = 0
    for i in range(60):
        for size, max_new in plan.get(i, ()):
            srv.submit(_tokens(size, 40 + n), max_new=max_new)
            n += 1
            ctx = np.arange(size + 1, size + max_new)
            in_context += int(ctx.sum())
            attended += int(srv.model.attended_tokens(ctx).sum())
        srv.step()
    assert srv.pending() == 0 and len(srv.poll()) == n == 5
    st = srv.stats()
    assert (st["sparse_tokens_attended"], st["sparse_tokens_in_context"]) \
        == (attended, in_context)
    assert 0 < attended < in_context
    assert 0 < st["admissions_overlapped"] < st["admitted"] == 5


def test_the_same_prompt_twice_is_served_the_same(fam, weights, built):
    """No radix hit on a stack with recurrent layers: the second admission
    of a prompt prefills it whole again, is counted as skipped, and gives
    the same tokens (a hit that restored K/V alone would not)."""
    srv = _server(built, slots=1)
    p = _tokens(64, 21)
    a = srv.submit(p, max_new=12)
    first = srv.run_until_drained()[0]
    b = srv.submit(p, max_new=12)
    second = srv.run_until_drained()[0]
    assert (first.id, second.id) == (a, b)
    assert first.tokens == second.tokens
    assert _gaps(fam, weights, second) < TOL
    st = srv.stats()
    assert st["prefix_skipped_recurrent"] == 2
    pc = st["prefix_cache"]
    assert pc["cached_tokens_saved"] == 0 and pc["lookups"] == 0
    assert pc["kv_blocks_used"] == 0 and pc["blocks_written"] == 0


def test_one_shot_prefill_masks_its_padding(fam, weights, built):
    """`_prefill` (a bucket no longer than a chunk) tells the stack the
    prompt's true length: the logits at its last token are the
    reference's."""
    model, params = built
    toks = _tokens(37, 31)
    pad = np.zeros((1, 48), np.int32)
    pad[0, :37] = toks
    _cache, last = _prefill(model, params, jnp.asarray(pad), jnp.int32(37),
                            48)
    ref = fam.reference.logits_at(weights, CFG, toks, [36])[0]
    assert np.abs(np.asarray(last) - ref).max() < TOL


@pytest.mark.parametrize("kw, what", [
    (dict(n_model=2), "n_model"),
    (dict(paged_kernel="xla"), "paged_kernel"),
    (dict(prefix=[1, 2, 3]), "prefix="),
    (dict(quantize="int8"), "quantize="),
])
def test_what_rests_on_kv_alone_is_refused_by_name(built, kw, what):
    with pytest.raises(UnsupportedStack, match=what):
        _server(built, **kw)


def test_handoff_and_cluster_prefix_are_refused(built):
    srv = _server(built)
    for call in (lambda: srv.handoff_probe([1, 2, 3]),
                 lambda: srv.handoff_export([1] * 16),
                 lambda: srv.prefix_warm([1] * 16),
                 lambda: srv.prefix_publish([1] * 16)):
        with pytest.raises(UnsupportedStack):
            call()


def test_the_dense_pool_counts_nothing_new():
    """A `TransformerLM` pool's stats keep their keys: the hybrid stack's
    counters are its own."""
    from idunno_tpu.models.transformer import TransformerLM
    model = TransformerLM(vocab=64, dim=32, depth=2, num_heads=2)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=16,
                       kv_block_size=4)
    srv.submit([1, 2, 3, 4, 5], max_new=3)
    srv.run_until_drained()
    st = srv.stats()
    assert not {"recurrent_state_bytes", "sparse_tokens_attended",
                "prefix_skipped_recurrent"} & set(st)
    assert st["prefix_cache"]["lookups"] == 1


def test_a_block_sparse_stack_is_handed_the_cursors_as_they_are(built):
    """No layer of this stack takes the context ladder (ISSUE 37: a
    block-sparse layer reads its own selection, a linear one no token
    axis): the model answers the pool's question with None, the pool
    counts no `decode_context_*` and hands the dispatch every cursor as it
    is, a dead row's too."""
    model, _params = built
    assert model.decode_context_rungs(512, 2) is None
    srv = _server(built)
    assert srv._ladder is None
    srv.submit(_tokens(20, 41), max_new=6)
    srv.run_until_drained()
    assert not {"decode_context_read", "decode_context_held"} & set(
        srv.stats())
