"""The hybrid stack's state-space kind, plain attention kind and routed
experts (`models/hybrid.py`: `mamba2`, `attention`, the `moe` feed-forward
over `models.moe.routed_experts`) held to the benchmark's plain reference
(`benchmark/families/granite_moe_hybrid/reference.py`) on seeded weights,
float32, CPU: the full forward, prefill then decode through `DecodeServer`'s
slot cache, the shares of a wider router adding up, and what the pool
refuses on such a stack."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ladder_cases
from benchmark import manifest
from idunno_tpu.engine.generate import decode_model, init_cache
from idunno_tpu.engine.serve_lm import DecodeServer, _prefill
from idunno_tpu.models import hybrid
from idunno_tpu.models.hybrid import UnsupportedStack
from idunno_tpu.models.moe import routed_experts
from idunno_tpu.models.transformer import decode_apply

# tiny widths; a scan chunk of 16 tokens, so that 100 tokens cross several;
# 4 of the router's 8 experts held
CFG = dict(
    family="granite_moe_hybrid", hidden_size=64, intermediate_size=24,
    shared_intermediate_size=48, num_attention_heads=4,
    num_key_value_heads=2, vocab_size=512, num_hidden_layers=6,
    layer_types=["mamba", "mamba", "attention", "mamba", "attention",
                 "mamba"],
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1,
    mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=16,
    mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
    position_embedding_type="nope", tie_word_embeddings=True,
    num_local_experts=4, num_experts_per_tok=2,
    published={"num_local_experts": 8, "num_hidden_layers": 40},
    attention_multiplier=0.0625, embedding_multiplier=12, logits_scaling=4,
    residual_multiplier=0.22, rms_norm_eps=1e-5,
    as_run={"dtype": "float32"})
TOL = 1e-4       # float32 on the CPU, logits of order 0.2


@pytest.fixture(scope="module")
def fam():
    return manifest.Manifest().family(CFG)


@pytest.fixture(scope="module")
def weights(fam):
    """Seeded weights with every norm scale and D moved off 1, so that a
    path that dropped one would show."""
    w = fam.weights.make_weights(CFG, 7)
    rng = np.random.default_rng(0)
    for k in list(w):
        if k.split("_")[-1] in ("ln1", "ln2", "norm", "D"):
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


@pytest.mark.parametrize("total, chunk", [(320, 320), (320, 64), (300, 20),
                                          (1024, 1024)])
def test_full_forward_matches_the_reference(fam, weights, built, total,
                                            chunk):
    """One apply (tokens grouped by expert: 320 of them; 1024 through the
    attention's tiles of queries) and chunks that cut the scan's chunks of
    16 anywhere (every token by every held expert): the same logits at
    every position."""
    model, params = built
    toks = _tokens(300)
    ref = fam.reference.logits_at(weights, CFG, toks, list(range(300)))
    got, cache = _chunks(model, params, toks, total, chunk, valid=300)
    assert np.abs(got - ref).max() < TOL
    assert int(cache["cursor"]) == total


def test_padding_enters_neither_state_nor_window(built):
    """A prompt of 77 tokens padded to its bucket leaves the state and the
    convolution window of exactly 77 tokens, whatever follows them."""
    model, params = built
    toks = _tokens(77, seed=3)
    _lg, exact = _chunks(model, params, toks, 77, 77, valid=77)
    _lg, padded = _chunks(model, params, toks + _tokens(51, seed=4), 128, 32,
                          valid=77)
    _lg, unmasked = _chunks(model, params, toks + _tokens(51, seed=4), 128,
                            32, valid=128)
    seen = 0
    for r, (kind, _ids) in enumerate(model.runs()):
        if kind != hybrid.MAMBA:
            continue
        a, b, c = (x[f"run{r}"] for x in (exact, padded, unmasked))
        for leaf, off in (("state", 1e-3), ("conv", 1e-2)):
            assert np.abs(np.asarray(a[leaf] - b[leaf])).max() < 1e-5
            assert np.abs(np.asarray(a[leaf] - c[leaf])).max() > off
        seen += 1
    assert seen == 3


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
    admissions, two rows of different length in one dispatch, and a slot
    reused after its first tenant retired, which starts from the new
    request's state and window, not the old one's. Every served token is
    the reference's best at its position; the experts' counters add up."""
    srv = _server(built)
    prompts = [_tokens(70, 11), _tokens(21, 12), _tokens(45, 13)]
    new = (30, 24, 40)
    ids = [srv.submit(p, max_new=n) for p, n in zip(prompts, new)]
    done = {c.id: c for c in srv.run_until_drained()}
    assert sorted(done) == ids
    for rid, p in zip(ids, prompts):
        assert done[rid].tokens[:len(p)] == p
        assert _gaps(fam, weights, done[rid]) < TOL
    st = srv.stats()
    assert st["prefill_chunks"] == 3 + 2            # buckets 96 and 48
    # 4 mamba layers: float32 states [8, 16, 16] and windows [3, 160]
    assert st["recurrent_state_bytes"] == 2 * 4 * (4 * 8 * 16 * 16
                                                   + 4 * 3 * 160)
    assert st["prefix_skipped_recurrent"] == 3
    # no block-sparse kind: nothing of its bookkeeping
    assert not {"sparse_tokens_attended", "sparse_tokens_in_context"} & set(st)
    # every token after a request's first came from a decode step of a live
    # row: 6 layers, 2 picks a token, about half of them on the 4 held
    decoded = sum(new) - len(new)
    assert st["expert_tokens_offered"] == decoded * 6 * 2
    assert 0.3 < st["expert_tokens_routed"] / st["expert_tokens_offered"] < 0.7
    assert st["expert_load_mean"] * 4 == pytest.approx(
        st["expert_tokens_routed"])
    assert st["expert_load_max"] >= st["expert_load_mean"]
    assert 0 < st["experts_touched"] <= st["experts_touchable"]
    assert st["experts_touchable"] % (6 * 4) == 0


def test_a_reused_slot_starts_from_zero_state(built):
    """The same prompt through a fresh pool and through a slot another
    request just left: the same tokens."""
    p = _tokens(40, 21)
    fresh = _server(built, slots=1)
    fresh.submit(p, max_new=16)
    want = fresh.run_until_drained()[0].tokens
    srv = _server(built, slots=1)
    srv.submit(_tokens(90, 22), max_new=20)
    srv.run_until_drained()
    srv.submit(p, max_new=16)
    assert srv.run_until_drained()[0].tokens == want


def test_one_shot_prefill_masks_its_padding(fam, weights, built):
    model, params = built
    toks = _tokens(37, 31)
    pad = np.zeros((1, 48), np.int32)
    pad[0, :37] = toks
    _cache, last = _prefill(model, params, jnp.asarray(pad), jnp.int32(37),
                            48)
    ref = fam.reference.logits_at(weights, CFG, toks, [36])[0]
    assert np.abs(np.asarray(last) - ref).max() < TOL


# -- the routed layer and its shares -----------------------------------------

def _layer_inputs(seed=5, tokens=300):
    rng = np.random.default_rng(seed)
    d, e, f = 64, 8, 24
    f32 = jnp.float32
    return (jnp.asarray(rng.standard_normal((tokens, d)), f32),
            jnp.asarray(rng.standard_normal((d, e)) * 0.25, f32),
            jnp.asarray(rng.standard_normal((e, d, 2 * f)) * d ** -0.5, f32),
            jnp.asarray(rng.standard_normal((e, f, d)) * f ** -0.5, f32))


def _reference_routed(fam, x, router, w1, w2, top_k=2):
    """The family's reference layer over ALL the experts (uncut)."""
    at = {"router": router, "w1": w1, "w2": w2}.__getitem__
    with jax.default_matmul_precision("highest"):
        return np.asarray(fam.reference._routed(x, x, at, top_k, 0, None))


@pytest.mark.parametrize("dense", [False, True])
def test_the_shares_add_up(fam, dense):
    """8 experts split 4 + 4 over two chips: the two shares' routed parts
    (each routes over all 8 and computes its own 4) add up to the uncut
    reference's routed layer; the shared expert, which every chip computes
    alike, is counted once and is none of this sum."""
    x, router, w1, w2 = _layer_inputs()
    parts, loads = zip(*(routed_experts(
        x, router, w1[a:a + 4], w2[a:a + 4], top_k=2, experts_held=(a, 4),
        dense=dense) for a in (0, 4)))
    whole = _reference_routed(fam, x, router, w1, w2)
    assert np.abs(np.asarray(parts[0] + parts[1]) - whole).max() < 1e-5
    assert np.abs(np.asarray(parts[0])).max() > 0.1     # both shares count
    assert np.abs(np.asarray(parts[1])).max() > 0.1
    assert int(sum(ld.sum() for ld in loads)) == 300 * 2


def test_the_stack_layer_is_one_share_plus_the_shared_expert(fam, weights,
                                                             built):
    """`hybrid._ffn` of the program's stack (4 of 8 held) against the
    reference given the same share: the routed part of the held experts
    and the shared expert once."""
    model, params = built
    run = params["runs"][0]
    p = jax.tree.map(lambda a: a[1], run)              # the run's layer 1
    x = jnp.asarray(np.random.default_rng(8).standard_normal((1, 300, 64)),
                    jnp.float32)
    got, counted = hybrid._ffn(model, p, {k: run[k] for k in ("w1", "w2")},
                               1, {}, x, jnp.ones((1, 300), bool))
    assert counted == {}
    u = fam.reference._rms(x[0], p["ln2"], 1e-5)
    with jax.default_matmul_precision("highest"):
        want = (fam.reference._routed(u, u, p.__getitem__, 2, 0, None)
                + fam.reference._gated(u, p["ws1"], p["ws2"], None))
    assert np.abs(np.asarray(got[0] - want)).max() < 1e-5


@pytest.mark.parametrize("dense", [False, True])
def test_no_token_is_dropped_under_a_skewed_router(fam, dense):
    """A router that sends every token to expert 1 first: that expert takes
    all 300 tokens (no capacity), and the layer is still the reference's."""
    x, router, w1, w2 = _layer_inputs(seed=6)
    # every token's logit for expert 1 is about 190, the others' about +-6
    x = x + 3.0
    router = router.at[:, 1].set(1.0)
    got, load = routed_experts(x, router, w1[:4], w2[:4], top_k=2,
                               experts_held=(0, 4), dense=dense)
    assert int(load[1]) == 300
    at = {"router": router, "w1": w1[:4], "w2": w2[:4]}.__getitem__
    with jax.default_matmul_precision("highest"):
        want = np.asarray(fam.reference._routed(x, x, at, 2, 0, None))
    assert np.abs(np.asarray(got) - want).max() < 1e-4


def test_a_masked_token_is_routed_nowhere():
    x, router, w1, w2 = _layer_inputs(seed=7, tokens=40)
    mask = jnp.arange(40) < 25
    got, load = routed_experts(x, router, w1[:4], w2[:4], top_k=2,
                               experts_held=(0, 4), mask=mask)
    full, _ = routed_experts(x, router, w1[:4], w2[:4], top_k=2,
                             experts_held=(0, 4))
    assert np.abs(np.asarray(got[:25] - full[:25])).max() < 1e-6
    assert not np.asarray(got[25:]).any()
    assert int(load.sum()) <= 25 * 2


# -- what the stack refuses, and what it is not asked -------------------------

@pytest.mark.parametrize("case", ladder_cases.CASES,
                         ids=lambda c: c.__name__)
def test_the_pool_reads_the_live_context(built, case):
    """`ladder_cases`' cases (ISSUE 37) over two plain attention layers
    without positions among four state-space layers, routed experts in
    every one: the pool bounds the attention's read by the live rows'
    cursors and serves `generate`'s streams."""
    case(built, ladder_cases.hybrid_pool)


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


def test_a_stack_is_checked_for_the_kinds_it_has():
    """No sparse geometry is asked of a stack without a sparse layer, no
    share of experts of a dense one; a stack is refused for what its own
    kinds need."""
    base = dict(vocab=64, dim=32, mlp_dim=16, layer_ids=(0,),
                published_depth=1, num_heads=4, num_kv_heads=2, head_dim=8)
    # block_size 60 is no multiple of kernel_stride 16: a sparse stack's
    # fault, nobody else's
    hybrid.HybridLM(mixers=(hybrid.ATTENTION,), block_size=60, **base)
    hybrid.HybridLM(mixers=(hybrid.MAMBA,), block_size=60, ssm_heads=4,
                    ssm_head_dim=8, ssm_state=8, **base)
    with pytest.raises(ValueError, match="kernel_stride"):
        hybrid.HybridLM(mixers=(hybrid.SPARSE,), block_size=60, **base)
    with pytest.raises(ValueError, match="groups"):
        hybrid.HybridLM(mixers=(hybrid.MAMBA,), ssm_heads=4, ssm_groups=3,
                        **base)
    with pytest.raises(ValueError, match="share"):
        hybrid.HybridLM(mixers=(hybrid.ATTENTION,), ffn=hybrid.MOE,
                        experts=8, experts_per_token=2,
                        experts_held=(6, 4), **base)
    with pytest.raises(ValueError, match="feed-forward"):
        hybrid.HybridLM(mixers=(hybrid.ATTENTION,), ffn="switch", **base)
    # a cache no longer has to be whole selection blocks
    dec = hybrid.HybridLM(mixers=(hybrid.ATTENTION,), decode=True,
                          max_decode_len=50, **base)
    assert dec.init_cache(2)["run0"]["cached_k"].shape == (1, 2, 50, 2, 8)
    assert dec.state_bytes(3) == 0
