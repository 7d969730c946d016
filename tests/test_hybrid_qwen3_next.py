"""The hybrid stack's Gated DeltaNet kind, its gated attention (q/k norm,
partial rotary positions, output gate) and its routed experts behind a gated
shared expert (`models/hybrid.py`: `gated-deltanet`, the `attention` kind's
options, the `moe` feed-forward's `shared_gate`) held to the benchmark's
plain reference (`benchmark/families/qwen3_next/reference.py`) on seeded
weights, float32, CPU: the full forward with chunks cut anywhere, prefill
then decode through `DecodeServer`'s slot cache, the chunked delta rule
against the reference's token-at-a-time recurrence, the shares of a wider
router adding up, the two expert branches and which one a step takes.
Logits are compared, never tokens."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ladder_cases
import live_state_cases
from benchmark import manifest
from idunno_tpu.engine.generate import decode_model, init_cache
from idunno_tpu.engine.serve_lm import DecodeServer
from idunno_tpu.models import hybrid
from idunno_tpu.models.moe import routed_experts
from idunno_tpu.models.transformer import decode_apply

# tiny widths: key heads 16 wide, value heads 8 (a transposed state would
# not fit), two value heads a key head; rotary positions over 4 of a
# head's 16 channels; 4 of the router's 8 experts held
CFG = dict(
    family="qwen3_next", hidden_size=64, moe_intermediate_size=24,
    shared_expert_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25,
    rope_theta=10000000, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, vocab_size=512, num_hidden_layers=3,
    layer_types=["linear_attention", "linear_attention", "full_attention"],
    num_experts=4, num_experts_per_tok=2,
    published={"num_experts": 8, "num_hidden_layers": 48},
    decoder_sparse_step=1, mlp_only_layers=[], norm_topk_prob=True,
    hidden_act="silu", rope_scaling=None, use_sliding_window=False,
    tie_word_embeddings=False, rms_norm_eps=1e-6,
    as_run={"dtype": "float32"})
# float32 on the CPU against float32 `highest`, logits of standard deviation
# about 1: the two differ by the order of their sums (the chunked delta rule
# against the reference's token at a time: 1e-6 measured on the rule alone);
# a wrong position, key head, gate or stale state reads 1e-2 to 1
TOL = 1e-4


@pytest.fixture(scope="module")
def fam():
    return manifest.Manifest().family(CFG)


@pytest.fixture(scope="module")
def weights(fam):
    """Seeded weights with every norm scale moved off its seed, so that a
    path that dropped one would show."""
    w = fam.weights.make_weights(CFG, 7)
    rng = np.random.default_rng(0)
    for k in list(w):
        if k.split("_", 1)[-1] in ("ln1", "ln2", "norm", "qn", "kn") or (
                k == "norm_f"):
            w[k] = w[k] * (1 + 0.1 * jnp.asarray(
                rng.standard_normal(w[k].shape), w[k].dtype))
    return w


@pytest.fixture(scope="module")
def built(fam, weights):
    model, params, kw = fam.program.build(CFG, weights)
    assert kw == {}
    assert model.mixers == (hybrid.DELTA, hybrid.DELTA, hybrid.ATTENTION)
    return model, params


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


def _chunks(model, params, toks, total, chunk, valid):
    """Prefill ``toks`` padded to ``total`` in chunks, every position's
    logits (the stack's `last_logits` off): (logits, cache)."""
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


@pytest.mark.parametrize("total, chunk", [(320, 320), (320, 96),
                                          (640, 640)])
def test_full_forward_matches_the_reference(fam, weights, built, total,
                                            chunk):
    """One apply (tokens grouped by expert: 320 of them; 640 through the
    attention's tiles of queries and ten delta chunks of 64) and chunks
    that cut the delta rule's chunks of 64 anywhere (96 tokens, every token
    by every held expert, the tail padded): the same logits at every
    position."""
    model, params = built
    toks = _tokens(300)
    ref = fam.reference.logits_at(weights, CFG, toks, list(range(300)))
    got, cache = _chunks(model, params, toks, total, chunk, valid=300)
    assert np.abs(got - ref).max() < TOL
    assert np.abs(ref).std() > 0.3
    assert int(cache["cursor"]) == total


def test_padding_enters_neither_state_nor_window(built):
    """A prompt of 77 tokens padded to its bucket leaves the delta-rule
    state and the convolution window of exactly 77 tokens, whatever follows
    them."""
    model, params = built
    toks = _tokens(77, seed=3)
    _lg, exact = _chunks(model, params, toks, 77, 77, valid=77)
    _lg, padded = _chunks(model, params, toks + _tokens(51, seed=4), 128, 32,
                          valid=77)
    _lg, unmasked = _chunks(model, params, toks + _tokens(51, seed=4), 128,
                            32, valid=128)
    seen = 0
    for r, (kind, ids) in enumerate(model.runs()):
        if kind != hybrid.DELTA:
            continue
        a, b, c = (x[f"run{r}"] for x in (exact, padded, unmasked))
        # one apply brackets the 77 tokens as delta chunks of 64 + 13, the
        # calls of 32 as 32 + 32 + 13: float32 sums in another order, on
        # states of order 1 (1.3e-5 measured)
        for leaf, off in (("state", 1e-3), ("conv", 1e-2)):
            assert np.abs(np.asarray(a[leaf] - b[leaf])).max() < 1e-4
            assert np.abs(np.asarray(a[leaf] - c[leaf])).max() > off
        seen += len(ids)
    assert seen == 2


def _gaps(fam, weights, done):
    """max over served tokens of (reference's best logit - its logit of the
    served token): the benchmark's own check, on one completion."""
    toks, pl = done.tokens, done.prompt_len
    where = list(range(pl - 1, len(toks) - 1))
    ref = fam.reference.logits_at(weights, CFG, toks, where)
    served = np.asarray(toks[pl:])
    return float((ref.max(-1) - ref[np.arange(len(where)), served]).max())


def test_prefill_then_decode_through_the_slot_cache(fam, weights, built):
    """Three prompts over two slots: chunked (three chunks) and one-shot
    admissions, two rows of different length in one dispatch, and a slot
    reused after its first tenant retired, which starts from the new
    request's state and window. Every served token is the reference's best
    at its position (the prefill's last-position logits among them); the
    counters add up."""
    model, params = built
    srv = DecodeServer(model, params, slots=2, prompt_len=96, max_len=160,
                       decode_steps=2, prompt_buckets=(24, 48, 96),
                       kv_block_size=8, kv_cache_blocks=16, prefill_chunk=32)
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
    # 2 delta layers: float32 states [4, 16, 8] and windows [3, 2*32 + 32]
    assert st["recurrent_state_bytes"] == 2 * 2 * (4 * 4 * 16 * 8
                                                   + 4 * 3 * 96)
    assert st["prefix_skipped_recurrent"] == 3
    decoded = sum(new) - len(new)
    assert st["state_rows_moved"] == 2 * decoded
    assert st["state_rows_moved"] < st["state_rows_held"]
    assert st["expert_tokens_offered"] == decoded * 3 * 2
    # two slots x 2 picks < 8 experts: the decode step groups tokens by
    # expert and reads the held experts a token picked, no more
    assert 0 < st["experts_read"] == st["experts_touched"]
    assert st["experts_touched"] < st["experts_touchable"]


# -- the delta rule: chunked form against the token-at-a-time reference ------

def _hard_keys(rng, shape):
    """Keys that share one direction (cosines near 1 between any two): with
    no decay and beta near 1 the chunk's triangle is close to all ones
    below the diagonal, its inverse's rows alternate in sign, and each row
    of the substitution cancels nearly all of what it sums."""
    x = rng.standard_normal(shape[-1]) + 0.05 * rng.standard_normal(shape)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


@pytest.mark.parametrize("t, real, size, hard", [
    (200, 163, 64, False), (130, 97, 16, False), (50, 50, 64, False),
    (200, 163, 64, True)],
    ids=["200-163-64", "130-97-16", "50-50-64", "200-163-64-hard"])
def test_the_chunked_delta_rule_is_the_recurrence(fam, t, real, size, hard):
    """`hybrid._delta_chunks` over ``t`` tokens in chunks of ``size`` (the
    last ``t - real`` padding: g = 0 and beta = 0, as the mixer sets them),
    from a state that is not zero: the reference's token at a time
    (`reference._delta_rule`) at every real position, and the state after
    the last real token. The reference starts from zeros, so a leading
    token writes the start (beta 1, a unit key: S0 = k0 v0^T), and dk
    trailing ones read the state out (q a unit vector, beta 0, g 0: row i
    of S). ``hard``: no decay, beta near 1, keys of one direction."""
    rng = np.random.default_rng(t + real)
    h, dk, dv = 3, 16, 8
    f32 = jnp.float32

    def unit(shape):
        x = jnp.asarray(rng.standard_normal(shape), f32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k = unit((t, h, dk)) * dk ** -0.5, unit((t, h, dk))
    v = jnp.asarray(rng.standard_normal((t, h, dv)), f32)
    real_ = (jnp.arange(t) < real)[:, None]
    g = jnp.where(real_, -jnp.asarray(rng.uniform(0, 3, (t, h)), f32), 0.0)
    beta = jnp.where(real_, jax.nn.sigmoid(jnp.asarray(
        rng.standard_normal((t, h)), f32)), 0.0)
    if hard:
        k = jnp.asarray(_hard_keys(rng, (t, h, dk)), f32)
        g = jnp.zeros((t, h), f32)
        beta = jnp.where(real_, jnp.full((t, h), 0.999, f32), 0.0)
    k0, v0 = unit((1, h, dk)), jnp.asarray(rng.standard_normal((1, h, dv)),
                                           f32)
    start = k0[0][..., :, None] * v0[0][..., None, :]
    o, state = jax.jit(hybrid._delta_chunks, static_argnums=(6,))(
        q[None], k[None], v[None], g[None], beta[None], start[None], size)
    eye = jnp.broadcast_to(jnp.eye(dk, dtype=f32)[:, None], (dk, h, dk))
    read = (jnp.zeros((dk, h)), jnp.zeros((dk, h)))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(fam.reference._delta_rule)(*(jnp.concatenate(x) for x in (
            (jnp.zeros((1, h, dk), f32), q[:real], eye),
            (k0, k[:real], eye), (v0, v[:real], jnp.zeros((dk, h, dv))),
            (jnp.zeros((1, h), f32), g[:real], read[0]),
            (jnp.ones((1, h), f32), beta[:real], read[1]))))
    assert o.shape == (1, t, h, dv)
    assert np.abs(np.asarray(o[0, :real] - ref[1:real + 1])).max() < 1e-5
    assert np.abs(np.asarray(state[0] - jnp.moveaxis(ref[real + 1:], 0, 1))
                  ).max() < 1e-5
    assert np.abs(np.asarray(ref)).max() > 0.1


def _triangle(size, hard):
    """`-tril(diag(beta) K K^T * D, -1)` of 6 chunks x 4 heads of ``size``
    tokens, keys of 128, float64, as `_delta_chunks` forms it: decaying
    keys of random directions, or the hard case (`_hard_keys`, g = 0, beta
    0.999)."""
    n, h, dk = 6, 4, 128
    rng = np.random.default_rng(size)
    if hard:
        k = _hard_keys(rng, (n, h, size, dk))
        beta, g = np.full((n, h, size), 0.999), np.zeros((n, h, size))
    else:
        k = rng.standard_normal((n, h, size, dk))
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        beta = 1 / (1 + np.exp(-rng.standard_normal((n, h, size))))
        g = -rng.uniform(0, 3, (n, h, size))
    c = np.cumsum(g, axis=-1)
    below = np.tril(np.ones((size, size), bool), -1)
    d = np.exp(np.where(below, c[..., :, None] - c[..., None, :], 0.0))
    kk = np.einsum("nhid,nhjd->nhij", k, k)
    return np.where(below, -beta[..., None] * kk * d, 0.0)


@pytest.mark.parametrize("hard", [False, True], ids=["decaying", "hard"])
@pytest.mark.parametrize("size", [16, 32, 64])
def test_the_blocked_inverse_is_float64s(size, hard):
    """`hybrid._unit_lower_inverse` (diagonal blocks by substitution, the
    rest by products; one block at 16) in float32 against float64
    `numpy.linalg.inv` of `I - a`, to 1e-5 on an inverse of entries up to
    1; the hard case's entries reach 1 with alternating signs."""
    a = _triangle(size, hard)
    ref = np.linalg.inv(np.eye(size) - a)
    got = np.asarray(jax.jit(hybrid._unit_lower_inverse)(
        jnp.asarray(a, jnp.float32)))
    assert got.shape == a.shape
    assert np.abs(got - ref).max() < 1e-5
    assert np.abs(np.tril(ref, -1)).max() > (0.5 if hard else 0.05)


def _loop_carries(jaxpr):
    """The shapes each loop of ``jaxpr`` (its inner jaxprs too) carries from
    one trip to the next: a `while`'s, and a `scan`'s, which is what a
    `fori_loop` with static bounds becomes."""
    for eqn in jaxpr.eqns:
        p = eqn.params
        if eqn.primitive.name == "while":
            yield [tuple(v.aval.shape) for v in
                   p["body_jaxpr"].jaxpr.invars[p["body_nconsts"]:]]
        elif eqn.primitive.name == "scan":
            at = p["num_consts"]
            yield [tuple(v.aval.shape) for v in
                   p["jaxpr"].jaxpr.invars[at:at + p["num_carry"]]]
        for sub in jax.core.jaxprs_in_params(p):
            yield from _loop_carries(sub)


def test_no_loop_carries_a_whole_chunk_inverse():
    """`hybrid._delta_chunks` at the Qwen3-Next cell's geometry (a 4096-token
    chunk, 32 value heads, keys and values of 128, chunks of 64) loops
    over no [.., 64, 64] array: a loop that carried every chunk's inverse
    would pass over all of it (33.5 MB) once a row. The substitution's
    loop carries the diagonal 16 x 16 blocks, 8.4 MB; the scan over the
    chunks carries the state."""
    f32 = jnp.float32
    t, h, d = 4096, 32, 128
    spec = [jax.ShapeDtypeStruct(s, f32) for s in (
        (1, t, h, d), (1, t, h, d), (1, t, h, d), (1, t, h), (1, t, h),
        (1, h, d, d))]
    closed = jax.make_jaxpr(hybrid._delta_chunks, static_argnums=(6,))(
        *spec, hybrid._DELTA_CHUNK)
    carries = [[s for s in c if s] for c in _loop_carries(closed.jaxpr)]
    assert sorted(carries) == [[(1, h, d, d)], [(16, 16, 64 * h * 4)]]
    assert not [s for c in carries for s in c if s[-2:] == (64, 64)]


# -- gated attention ---------------------------------------------------------

def test_partial_rotary_gated_attention_is_the_references(fam, weights,
                                                          built):
    """`hybrid._attention_mix` over the gated-attention layer's weights, a
    prefill of 256 positions: the reference's gated attention (q/k norm,
    rotary positions over 4 of 16 channels, the sigmoid gate). Rotating
    every channel, or none, is not."""
    model, params = built
    r = next(i for i, (kind, _ids) in enumerate(model.runs())
             if kind == hybrid.ATTENTION)
    p = jax.tree.map(lambda a: a[0], params["runs"][r])
    dec = dataclasses.replace(model, decode=True, max_decode_len=256)
    kv = {k: v for k, v in dec.init_cache(1)[f"run{r}"].items()
          if k in ("cached_k", "cached_v")}
    u = jnp.asarray(np.random.default_rng(4).standard_normal((1, 256, 64)),
                    jnp.float32)
    pos = jnp.arange(256)[None]
    got, _kv = jax.jit(hybrid._attention_mix, static_argnums=(0,))(
        dec, p, kv, 0, u, pos)

    @jax.jit
    def want(p, u):
        at = {k: v.astype(jnp.float32) for k, v in p.items()}.__getitem__
        with jax.default_matmul_precision("highest"):
            return [fam.reference._gated_attention(u[0], at, 1e7, rot, 1e-6,
                                                   None) for rot in (4, 16, 0)]

    gaps = [np.abs(np.asarray(got[0] - w)).max() for w in want(p, u)]
    assert gaps[0] < 1e-5 < 1e-2 < min(gaps[1:])


# -- the routed layer, its shares and its two branches -----------------------

@pytest.fixture(scope="module")
def uncut(fam):
    """The toy stack's weights with all 8 of the router's experts held."""
    return fam.weights.make_weights(dict(CFG, num_experts=8), 7)


@pytest.mark.parametrize("tokens", [300, 40], ids=["grouped", "dense"])
def test_the_shares_add_up_with_the_gated_shared_expert_once(
        built, fam, uncut, tokens):
    """8 experts split 4 + 4 over two chips, each layer holding its share
    and computing the gated shared expert alike: the two shares' layers
    less one shared expert are the uncut reference's layer (every expert
    and the shared one once), whichever branch the step takes (300 tokens
    grouped, 40 every token by every held expert)."""
    model, params = built
    p = dict(jax.tree.map(lambda a: a[0], params["runs"][0]),
             router=uncut["r0_router"][0])
    x = jnp.asarray(np.random.default_rng(8).standard_normal(
        (1, tokens, 64)), jnp.float32)
    mask = jnp.ones((1, tokens), bool)
    outs = []
    for first in (0, 4):
        share = dataclasses.replace(model, experts_held=(first, 4))
        experts = {k: uncut[f"r0_{k}"][:, first:first + 4]
                   for k in ("w1", "w2")}
        y, _c = jax.jit(hybrid._ffn, static_argnums=(0,))(
            share, p, experts, 0, {}, x, mask)
        outs.append(np.asarray(y[0]))
    at = dict(p, **{k: uncut[f"r0_{k}"][0] for k in ("w1", "w2")}
              ).__getitem__
    u = fam.reference._rms(x[0], p["ln2"], 1e-6)
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(jax.nn.sigmoid(u @ p["wsg"]) * fam.reference
                            ._gated(u, p["ws1"], p["ws2"], None))
        routed = np.asarray(fam.reference._routed(u, u, at, 2, 0, None))
    assert np.abs(outs[0] + outs[1] - shared - (routed + shared)
                  ).max() < 1e-5
    assert np.abs(shared).max() > 0.05      # the shared expert counts
    assert np.abs(outs[0] - outs[1]).max() > 0.05


@pytest.mark.parametrize("rows, experts, dense", [
    (16, 512, False),     # Qwen3-Next's decode: 16 x 10 of 512
    (48, 72, True),       # granite-4.0-h-small's: 48 x 10 of 72
    (300, 72, False)])    # a prefill chunk: grouped from 256 tokens
def test_the_branch_a_step_takes_and_what_it_reads(rows, experts, dense):
    """Every token by every held expert only where each held expert
    expects a pick (rows x 10 >= the router's width) and rows are under
    256; the two branches give the same layer, and the step's counters
    are the same on both."""
    held, top = 8, 10
    rng = np.random.default_rng(rows)
    d, f = 32, 8
    f32 = jnp.float32
    model = hybrid.HybridLM(
        vocab=64, dim=d, mlp_dim=f, mixers=(hybrid.ATTENTION,),
        layer_ids=(0,), published_depth=1, num_heads=2, num_kv_heads=1,
        head_dim=16, ffn=hybrid.MOE, experts=experts, experts_per_token=top,
        experts_held=(0, held), shared_dim=f, shared_gate=True)
    p = {"ln2": jnp.ones((d,), f32),
         "router": jnp.asarray(rng.standard_normal((d, experts)), f32),
         "ws1": jnp.asarray(rng.standard_normal((d, 2 * f)), f32) * 0.2,
         "ws2": jnp.asarray(rng.standard_normal((f, d)), f32) * 0.3,
         "wsg": jnp.asarray(rng.standard_normal((d, 1)), f32) * 0.2}
    w = {"w1": jnp.asarray(rng.standard_normal((1, held, d, 2 * f)), f32),
         "w2": jnp.asarray(rng.standard_normal((1, held, f, d)), f32)}
    x = jnp.asarray(rng.standard_normal((rows, 1, d)), f32)
    mask = jnp.ones((rows, 1), bool)
    c = {"expert_load": jnp.zeros((held,), jnp.int32),
         "expert_steps": jnp.zeros((3,), jnp.int32)}
    _y, counted = jax.jit(hybrid._ffn, static_argnums=(0,))(
        model, p, w, 0, c, x, mask)
    assert hybrid.reads_every_held_expert(model, rows) is dense
    assert np.asarray(counted["expert_steps"]).tolist() == [np.sum(
        np.asarray(counted["expert_load"]) > 0), 1, rows]
    y = [jax.jit(routed_experts, static_argnames=(
        "top_k", "experts_held", "dense"))(
            x.reshape(rows, d), p["router"], w["w1"], w["w2"], top_k=top,
            experts_held=(0, held), layer=0, dense=br)[0]
         for br in (False, True)]
    assert np.abs(np.asarray(y[0] - y[1])).max() < 1e-4
    assert np.abs(np.asarray(y[0])).max() > 0.1


# -- the pool's shared cases over the toy stack ------------------------------

@pytest.mark.parametrize("case", ladder_cases.CASES,
                         ids=lambda c: c.__name__)
def test_the_pool_reads_the_live_context(built, case):
    """`ladder_cases`' cases over a gated-attention layer among three Gated
    DeltaNet layers, routed experts in every one: the pool bounds the
    attention's read by the live rows' cursors and serves `generate`'s
    streams."""
    case(built, ladder_cases.hybrid_pool)


@pytest.mark.parametrize("case", live_state_cases.CASES,
                         ids=lambda c: c.__name__)
def test_the_pool_moves_live_rows_states(built, case):
    """`live_state_cases`' cases over the three Gated DeltaNet layers: dead
    rows in a dispatch, slots reused after they went dead, the dead rows'
    delta-rule states untouched, and `state_rows` counted a live row a
    layer a step."""
    case(built, ladder_cases.hybrid_pool)
