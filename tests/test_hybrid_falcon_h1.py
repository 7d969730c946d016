"""The hybrid stack's layer of BOTH mixers (`models/hybrid.py`:
`attention+mamba2`, RoPE and a key multiplier on the attention body, the
branch, segment and MLP multipliers, the gated norm a group, the prefill's
last-position logits) held to the benchmark's plain reference
(`benchmark/families/falcon_h1/reference.py`) on seeded weights, float32,
CPU: the layer alone and a stack of three, chunks cut anywhere, prefill then
decode through `DecodeServer`'s slot cache, and what the pool refuses on
such a stack. Logits are compared, never tokens."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ladder_cases
import live_state_cases
from benchmark import manifest
from idunno_tpu.engine.generate import decode_model, init_cache
from idunno_tpu.engine.serve_lm import DecodeServer, _prefill
from idunno_tpu.models import hybrid
from idunno_tpu.models.hybrid import UnsupportedStack
from idunno_tpu.models.transformer import context_rungs, decode_apply

# tiny widths with the published multipliers; a scan chunk of 16 tokens, so
# that 100 tokens cross several; two groups of two state-space heads
CFG = dict(
    family="falcon_h1", hidden_size=64, intermediate_size=160,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=512, num_hidden_layers=3, mamba_n_heads=4, mamba_d_head=16,
    mamba_d_ssm=64, mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4,
    mamba_expand=2, mamba_chunk_size=16, mamba_conv_bias=True,
    mamba_proj_bias=False, attention_bias=False, projectors_bias=False,
    mlp_bias=False, mamba_rms_norm=True, mamba_norm_before_gate=False,
    mamba_use_mlp=True, attn_layer_indices=None, rope_scaling=None,
    hidden_act="silu", tie_word_embeddings=False,
    attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
    embedding_multiplier=5.656854249492381,
    key_multiplier=0.011048543456039804, lm_head_multiplier=0.0078125,
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    rope_theta=100000000000,
    ssm_in_multiplier=0.25,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    ssm_out_multiplier=0.08838834764831845, rms_norm_eps=1e-5,
    as_run={"dtype": "float32"})
# float32 on the CPU against float32 `highest`, logits of standard deviation
# 1: the two differ by the order of their sums (2e-5 measured); a dropped
# multiplier, a wrong position or a stale state reads 1e-2 to 1
TOL = 1e-4


@pytest.fixture(scope="module")
def fam():
    return manifest.Manifest().family(CFG)


def _weights(fam, cfg, seed=7):
    """Seeded weights with every norm scale and D moved off 1, and the gate
    of the second state-space group three times the first's, so that a
    path that dropped a scale, or normed over both groups at once, would
    show."""
    w = fam.weights.make_weights(cfg, seed)
    rng = np.random.default_rng(0)
    for k in ("ln1", "ln2", "norm", "D", "norm_f"):
        w[k] = w[k] * (1 + 0.1 * jnp.asarray(
            rng.standard_normal(w[k].shape), w[k].dtype))
    inner = cfg["mamba_d_ssm"]
    w["w_in"] = w["w_in"].at[:, :, inner // 2:inner].multiply(3.0)
    return w


@pytest.fixture(scope="module")
def weights(fam):
    return _weights(fam, CFG)


@pytest.fixture(scope="module")
def built(fam, weights):
    model, params, kw = fam.program.build(CFG, weights)
    assert kw == {}
    assert model.mixers == (hybrid.PARALLEL,) * 3 and model.last_logits
    return model, params


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


def _chunks(model, params, toks, total, chunk, valid):
    """Prefill ``toks`` padded to ``total`` in chunks, every position's
    logits: (logits, cache)."""
    model = dataclasses.replace(model, last_logits=False)
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
def test_a_stack_of_three_matches_the_reference(fam, weights, built, total,
                                                chunk):
    """One apply (1024: attention a tile of queries at a time) and chunks
    that cut the scan's chunks of 16 anywhere: the same logits at every
    position, so every chunk's keys and queries were turned by their own
    positions."""
    model, params = built
    toks = _tokens(300)
    ref = fam.reference.logits_at(weights, CFG, toks, list(range(300)))
    assert 0.7 < ref.std() < 1.4            # the scale the tolerance is on
    got, cache = _chunks(model, params, toks, total, chunk, valid=300)
    assert np.abs(got - ref).max() < TOL
    assert int(cache["cursor"]) == total


def test_the_layer_alone_matches_the_reference(fam):
    cfg = dict(CFG, num_hidden_layers=1)
    w = _weights(fam, cfg, seed=9)
    model, params, _kw = fam.program.build(cfg, w)
    toks = _tokens(90, seed=2)
    ref = fam.reference.logits_at(w, cfg, toks, list(range(90)))
    got, _cache = _chunks(model, params, toks, 96, 24, valid=90)
    assert np.abs(got - ref).max() < TOL


def _scaled(cfg, key):
    """``cfg`` with one multiplier (or one entry of a list of them) times
    1.7."""
    name, _, at = key.partition(".")
    out = dict(cfg)
    if at:
        out[name] = list(cfg[name])
        out[name][int(at)] *= 1.7
    else:
        out[name] = cfg[name] * 1.7
    return out


@pytest.mark.parametrize("key", [
    "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
    "attention_in_multiplier", "attention_out_multiplier",
    "ssm_in_multiplier", "ssm_out_multiplier", "mlp_multipliers.0",
    "mlp_multipliers.1", "ssm_multipliers.0", "ssm_multipliers.1",
    "ssm_multipliers.2", "ssm_multipliers.3", "ssm_multipliers.4"])
def test_no_multiplier_is_silently_one(fam, weights, built, key):
    """The same weights under one multiplier changed: the program's logits
    move (by far more than the tolerance), and move as the reference's."""
    toks = _tokens(80, seed=5)
    cfg = _scaled(CFG, key)
    model, params, _kw = fam.program.build(cfg, weights)
    base, _c = _chunks(built[0], built[1], toks, 80, 80, valid=80)
    got, _c = _chunks(model, params, toks, 80, 80, valid=80)
    assert np.abs(got - base).max() > 100 * TOL
    ref = fam.reference.logits_at(weights, cfg, toks, list(range(80)))
    assert np.abs(got - ref).max() < 2 * TOL   # logits up to 1.7 times wider


def test_a_lower_precision_fails_the_tolerance(fam, weights):
    """The control: the reference with its matrix products' inputs in
    float8 is a hundred tolerances off."""
    toks = _tokens(120, seed=6)
    where = list(range(120))
    ref = fam.reference.logits_at(weights, CFG, toks, where)
    low = fam.reference.logits_at(weights, CFG, toks, where, quant="fp8")
    assert np.abs(low - ref).max() > 100 * TOL


def test_the_gated_norm_by_group(built):
    """Two groups: each half of the channels by its own mean square (by
    hand in float64); one group: today's norm over all of them, to the
    bit."""
    model, _p = built
    rng = np.random.default_rng(3)
    y = rng.standard_normal((2, 5, 64)) * np.repeat([1.0, 4.0], 32)
    scale = 1 + 0.1 * rng.standard_normal(64)
    yj, sj = jnp.asarray(y, jnp.float32), jnp.asarray(scale, jnp.float32)
    want = np.concatenate([
        h / np.sqrt((h ** 2).mean(-1, keepdims=True) + 1e-5)
        for h in (y[..., :32], y[..., 32:])], -1) * scale
    got = np.asarray(hybrid._gated_norm(model, yj, sj))
    assert np.abs(got - want).max() < 1e-5
    one = dataclasses.replace(model, ssm_groups=1)
    whole = np.asarray(hybrid._gated_norm(one, yj, sj))
    assert np.array_equal(whole, np.asarray(
        hybrid._rms(yj, sj, model.eps, model.dtype)))
    assert np.abs(whole - want).max() > 0.3     # the groups' sizes differ


def test_padding_enters_neither_state_window_nor_the_real_keys(built):
    """A prompt of 77 tokens padded to its bucket leaves the state and the
    convolution window of exactly 77 tokens, and keys and values of its 77
    positions that no later token changed, whatever follows them."""
    model, params = built
    toks = _tokens(77, seed=3)
    _lg, exact = _chunks(model, params, toks, 77, 77, valid=77)
    _lg, padded = _chunks(model, params, toks + _tokens(51, seed=4), 128, 32,
                          valid=77)
    _lg, unmasked = _chunks(model, params, toks + _tokens(51, seed=4), 128,
                            32, valid=128)
    a, b, c = (x["run0"] for x in (exact, padded, unmasked))
    assert sorted(a) == ["cached_k", "cached_v", "conv", "state"]
    for leaf, off in (("state", 1e-3), ("conv", 1e-2)):
        assert a[leaf].shape[0] == 3                 # one a layer
        assert np.abs(np.asarray(a[leaf] - b[leaf])).max() < 1e-5
        assert np.abs(np.asarray(a[leaf] - c[leaf])).max() > off
    for leaf in ("cached_k", "cached_v"):
        assert np.abs(np.asarray(a[leaf] - b[leaf][:, :, :77])).max() < 1e-5


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
    """Three prompts over two slots: chunked (three chunks, the last real
    position in the last or in an earlier one) and one-shot admissions, two
    rows of different length in one dispatch, and a slot reused after its
    first tenant retired. Every served token is the reference's best at its
    position (a gap under the tolerance); the gauges count both caches."""
    srv = _server(built)
    prompts = [_tokens(70, 11), _tokens(21, 12), _tokens(45, 13)]
    new = (30, 24, 40)
    ids = [srv.submit(p, max_new=n) for p, n in zip(prompts, new)]
    done = {c.id: c for c in srv.run_until_drained()}
    assert sorted(done) == ids
    for rid, p in zip(ids, prompts):
        assert done[rid].tokens[:len(p)] == p
        assert _gaps(fam, weights, done[rid]) < TOL
        # seeded weights do not decode into one repeated token
        assert len(set(done[rid].tokens[len(p):])) > len(p) // 4
    st = srv.stats()
    assert st["prefill_chunks"] == 3 + 2            # buckets 96 and 48
    # 3 layers a slot: float32 states [4, 16, 16], windows [3, 128], and K
    # and V of 160 tokens x 2 heads x 16
    assert st["recurrent_state_bytes"] == 2 * 3 * (4 * 4 * 16 * 16
                                                   + 4 * 3 * 128)
    assert st["kv_cache_bytes"] == 2 * 3 * 2 * 160 * 2 * 16 * 4
    assert st["prefix_skipped_recurrent"] == 3
    assert not {"sparse_tokens_attended", "expert_tokens_routed"} & set(st)


def test_a_reused_slot_starts_from_zero_state(built):
    """The same prompt through a fresh pool and through a slot another,
    longer request just left: the same tokens."""
    p = _tokens(40, 21)
    fresh = _server(built, slots=1)
    fresh.submit(p, max_new=16)
    want = fresh.run_until_drained()[0].tokens
    srv = _server(built, slots=1)
    srv.submit(_tokens(90, 22), max_new=20)
    srv.run_until_drained()
    srv.submit(p, max_new=16)
    assert srv.run_until_drained()[0].tokens == want


@pytest.mark.parametrize("true_len", [37, 48, 1])
def test_one_shot_prefill_hands_back_the_last_real_row(fam, weights, built,
                                                       true_len):
    """`_prefill` over a padded bucket: the head ran over one position, the
    last real one."""
    model, params = built
    toks = _tokens(true_len, 31)
    pad = np.zeros((1, 48), np.int32)
    pad[0, :true_len] = toks
    _cache, last = _prefill(model, params, jnp.asarray(pad),
                            jnp.int32(true_len), 48)
    assert last.shape == (512,)
    ref = fam.reference.logits_at(weights, CFG, toks, [true_len - 1])[0]
    assert np.abs(np.asarray(last) - ref).max() < TOL
    dec = decode_model(model, 48)
    cache = init_cache(model, 1, 48)
    cache["valid"] = jnp.int32(true_len)
    lg, _c = decode_apply(dec, params, cache, jnp.asarray(pad))
    assert lg.shape == (1, 1, 512)


# -- the decode step reads the live context (ISSUE 37) -------------------------

@pytest.mark.parametrize("case", ladder_cases.CASES,
                         ids=lambda c: c.__name__)
def test_the_pool_reads_the_live_context(built, case):
    """`ladder_cases`' cases over three layers of both mixers with RoPE
    and the key multiplier: the pool bounds the read by the live rows'
    cursors and serves `generate`'s streams."""
    case(built, ladder_cases.hybrid_pool)


@pytest.mark.parametrize("top, t", [(512, 1), (576, 1), (576, 3)],
                         ids=["whole-tiles", "last-rung-no-whole-tile",
                              "chunk-of-3"])
def test_the_ladder_is_attend_over_the_whole_axis(top, t):
    """`_attend_live` over layer 1 of a carried stack against `_attend` over
    that layer's slice: rows on every rung (576 = four tiles of 128 and a
    last rung of 64, read as the tile [448, 576) less what the tile before
    it covered) agree to float32 rounding. The tiles past the rung that
    holds the deepest row are not read: NaNs there reach no output."""
    rng = np.random.default_rng(top + t)
    rungs = context_rungs(top)
    assert rungs[0] == 128 and (top - rungs[-2]) in (64, 128)
    b, kvh, g, d = 5, 2, 3, 16
    kc, vc = (jnp.asarray(rng.standard_normal((2, b, top, kvh, d)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((b, t, kvh, g, d)), jnp.float32)
    for depths in ([0, 127, 128, 300, top - t], [0, 5, 100, 130, 255]):
        pos = jnp.asarray(depths)[:, None] + jnp.arange(t)[None, :]
        want = hybrid._attend(q, kc[1], vc[1], pos, 0.25)
        got = hybrid._attend_live(q, kc, vc, 1, pos, 0.25, rungs)
        assert got.shape == want.shape and got.dtype == want.dtype
        assert np.abs(np.asarray(got - want)).max() < 1e-5
    read = rungs[int(np.searchsorted(rungs, 255 + t))]
    assert read < top
    holed = [x.at[:, :, read:].set(jnp.nan) for x in (kc, vc)]
    alone = hybrid._attend_live(q, *holed, 1, pos, 0.25, rungs)
    assert np.array_equal(np.asarray(alone), np.asarray(got))


def test_a_cache_of_one_rung_takes_the_whole_axis_path(built, monkeypatch):
    """Per-row rows over a cache no longer than the least tile (every toy
    pool): no loop; from two rungs on, the loop, and the same logits."""
    model, params = built
    calls = []
    live = hybrid._attend_live
    monkeypatch.setattr(hybrid, "_attend_live",
                        lambda *a: calls.append(a[-1]) or live(*a))
    logits = {}
    for max_len in (128, 160):
        dec = dataclasses.replace(model, decode=True, decode_per_row=True,
                                  max_decode_len=max_len, interpret=True)
        cache = dec.init_cache(2)
        cache["cursors"] = jnp.asarray([3, 90], jnp.int32)
        logits[max_len], _c = decode_apply(dec, params, cache,
                                           jnp.asarray([[5], [9]]))
    assert calls == [(128, 160)]          # one trace: the scan's one body
    assert np.abs(np.asarray(logits[128] - logits[160])).max() < TOL
    assert model.decode_context_rungs(512, 2) == (128, 256, 384, 512)


def test_the_ladder_has_more_tiles_where_an_eighth_is_too_much_to_stage(built):
    """A row's K (or V) token is 2 heads x 16 x 4 bytes here. Eight tiles
    while every slot's tile of K is within `_STAGED_TILE_BYTES`, then
    twice as many, and so on down to the least tile."""
    model, _params = built
    assert hybrid._STAGED_TILE_BYTES == 512 * 512 * 128
    assert model.decode_context_rungs(4096, 512)[0] == 512
    assert model.decode_context_rungs(4096, 513)[0] == 256
    assert model.decode_context_rungs(4096, 1024)[:2] == (256, 512)
    assert model.decode_context_rungs(4096, 1 << 20)[0] == 128
    assert model.decode_context_rungs(4096, 1 << 20)[-1] == 4096


# -- what the stack refuses, and how it is described --------------------------

@pytest.mark.parametrize("kw, what", [
    (dict(n_model=2), "n_model"),
    (dict(paged_kernel="xla"), "paged_kernel"),
    (dict(prefix=[1, 2, 3]), "prefix="),
    (dict(quantize="int8"), "quantize="),
])
def test_what_rests_on_kv_alone_is_refused_by_name(built, kw, what):
    with pytest.raises(UnsupportedStack, match=what):
        _server(built, **kw)


def test_handoff_cluster_prefix_and_paged_steps_are_refused(built):
    srv = _server(built)
    for call in (lambda: srv.handoff_probe([1, 2, 3]),
                 lambda: srv.handoff_export([1] * 16),
                 lambda: srv.prefix_warm([1] * 16),
                 lambda: srv.prefix_publish([1] * 16)):
        with pytest.raises(UnsupportedStack):
            call()
    model, params = built
    dec = decode_model(model, 16)
    with pytest.raises(UnsupportedStack, match="paged"):
        hybrid.hybrid_apply(dec, params, init_cache(model, 1, 16),
                            jnp.zeros((1, 4), jnp.int32), paged=object())


def test_a_layer_of_both_mixers_is_both_kinds():
    base = dict(vocab=64, dim=32, mlp_dim=16, layer_ids=(0, 1),
                published_depth=1, num_heads=4, num_kv_heads=2, head_dim=8,
                ssm_heads=4, ssm_head_dim=8, ssm_state=8)
    m = hybrid.HybridLM(mixers=(hybrid.PARALLEL,) * 2, **base)
    assert m.has(hybrid.PARALLEL) and m.has(hybrid.MAMBA)
    assert m.has(hybrid.ATTENTION) and not m.has(hybrid.SPARSE)
    assert m.runs() == [(hybrid.PARALLEL, (0, 1))]
    plain = hybrid.HybridLM(mixers=(hybrid.MAMBA, hybrid.ATTENTION), **base)
    assert not plain.has(hybrid.PARALLEL)
    # the carry of such a run: K/V and the states, whole and depth-stacked
    assert hybrid._CARRIED[hybrid.PARALLEL] == ("cached_k", "cached_v",
                                                "state")
    dec = dataclasses.replace(m, decode=True, max_decode_len=50)
    run = dec.init_cache(3)["run0"]
    assert run["cached_k"].shape == (2, 3, 50, 2, 8)
    assert run["state"].shape == (2, 3, 4, 8, 8)
    assert run["conv"].shape == (2, 3, 3, 4 * 8 + 2 * 8)
    assert dec.state_bytes(3) == 3 * 2 * (4 * 4 * 8 * 8 + 3 * 48 * 4)
    # refused for what its own kinds need
    with pytest.raises(ValueError, match="KV heads"):
        hybrid.HybridLM(mixers=(hybrid.PARALLEL,) * 2,
                        **dict(base, num_kv_heads=3))
    with pytest.raises(ValueError, match="groups"):
        hybrid.HybridLM(mixers=(hybrid.PARALLEL,) * 2, ssm_groups=3, **base)
    with pytest.raises(ValueError, match="dense"):
        hybrid.HybridLM(mixers=(hybrid.PARALLEL,) * 2, ffn=hybrid.MOE,
                        experts=8, experts_per_token=2, experts_held=(0, 4),
                        **base)
    with pytest.raises(ValueError, match="segment"):
        hybrid.HybridLM(mixers=(hybrid.PARALLEL,) * 2, ssm_mults=(1.0, 2.0),
                        **base)
    with pytest.raises(ValueError, match="unknown mixer"):
        hybrid.HybridLM(mixers=("mamba2+attention",) * 2, **base)


# -- the decode step moves the live rows' scan states alone ------------------

@pytest.mark.parametrize("case", live_state_cases.CASES,
                         ids=lambda c: c.__name__)
def test_the_pool_moves_live_rows_states(built, case):
    """`live_state_cases`' cases over Falcon-H1's three layers of both
    mixers: dead rows in a dispatch, slots reused after they went dead, and
    the counters' arithmetic."""
    case(built, ladder_cases.hybrid_pool)
