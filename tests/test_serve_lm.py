"""Continuous-batching LM serving (`engine.serve_lm.DecodeServer`).

Exactness oracle: greedy continuous batching must produce token-for-token
the same output as a standalone `engine.generate.generate` call per request
— admission order, slot reuse, and co-residency with other sequences must
not change any sequence's tokens (each row attends only its own cache rows).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ladder_cases
from idunno_tpu.engine.generate import generate
from idunno_tpu.engine.serve_lm import DecodeServer
from idunno_tpu.models.transformer import TransformerLM

VOCAB = 61


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def expected(model, params, prompt: list[int], max_new: int) -> list[int]:
    out = generate(model, params,
                   jnp.asarray([prompt], jnp.int32),
                   prompt_len=len(prompt), max_new=max_new)
    return [int(t) for t in np.asarray(out[0])]


def test_continuous_batching_matches_generate(lm):
    model, params = lm
    rng = np.random.default_rng(7)
    reqs = [([int(t) for t in rng.integers(0, VOCAB, size=n)], m)
            for n, m in [(3, 9), (8, 4), (5, 12), (8, 1), (2, 7)]]

    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24)
    ids = {}
    for prompt, max_new in reqs[:3]:          # 3 requests into 2 slots
        ids[srv.submit(prompt, max_new)] = (prompt, max_new)
    for _ in range(3):                        # mid-flight...
        srv.step()
    for prompt, max_new in reqs[3:]:          # ...new arrivals are admitted
        ids[srv.submit(prompt, max_new)] = (prompt, max_new)
    done = srv.run_until_drained()

    assert {c.id for c in done} == set(ids)
    for c in done:
        prompt, max_new = ids[c.id]
        assert c.prompt_len == len(prompt)
        assert c.tokens == expected(model, params, prompt, max_new), \
            f"request {c.id} diverged from standalone generate"


def test_short_requests_complete_while_long_one_runs(lm):
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=40)
    long_id = srv.submit([1, 2, 3], max_new=30)
    short_ids = [srv.submit([4 + i], max_new=2) for i in range(3)]
    finished_order = []
    for _ in range(200):
        live = srv.step()
        finished_order.extend(c.id for c in srv.poll())
        if live == 0 and srv.pending() == 0:
            break
    assert finished_order[-1] == long_id, \
        "short requests should retire before the long one finishes"
    assert set(finished_order) == {long_id, *short_ids}


def test_request_filling_max_len_exactly_is_served(lm):
    """prompt + max_new == max_len is admitted, one token more is refused,
    and under four tokens a dispatch (the budget ends mid-dispatch, the
    last write lands on the row's last position) the stream is exact."""
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=20,
                       decode_steps=4)
    prompt = [5, 11, 17]
    with pytest.raises(ValueError, match="> max_len 20"):
        srv.submit(prompt, max_new=18)
    rid = srv.submit(prompt, max_new=17)
    other = srv.submit([9], max_new=6)               # a co-resident row
    done = {c.id: c for c in srv.run_until_drained()}
    assert len(done[rid].tokens) == 20
    assert done[rid].tokens == expected(model, params, prompt, 17)
    assert done[other].tokens == expected(model, params, [9], 6)


def test_fused_decode_steps_match(lm):
    model, params = lm
    prompt = [5, 11, 17]
    one = DecodeServer(model, params, slots=2, prompt_len=4, max_len=20)
    fused = DecodeServer(model, params, slots=2, prompt_len=4, max_len=20,
                         decode_steps=4)
    one.submit(prompt, max_new=10)
    fused.submit(prompt, max_new=10)
    a = one.run_until_drained()[0]
    b = fused.run_until_drained()[0]
    assert a.tokens == b.tokens == expected(model, params, prompt, 10)


def test_docstring_loop_serves_all_instant_requests(lm):
    """`while srv.step():` must not exit while requests are still queued —
    a max_new=1 admission retires instantly, leaving 0 live rows with a
    non-empty queue (step() counts both)."""
    model, params = lm
    srv = DecodeServer(model, params, slots=1, prompt_len=4, max_len=8)
    ids = [srv.submit([3, 1], max_new=1), srv.submit([2, 7], max_new=1)]
    done = []
    while srv.step():
        done.extend(srv.poll())
    done.extend(srv.poll())
    assert {c.id for c in done} == set(ids)
    for c in done:
        prompt = [3, 1] if c.id == ids[0] else [2, 7]
        assert c.tokens == expected(model, params, prompt, 1)


def test_eos_retires_rows_early(lm):
    """Generating ``eos_id`` stops that row immediately (eos kept in the
    output): the completion is the exact PREFIX of the non-eos greedy
    rollout through the first eos, and the freed slot serves queued work."""
    model, params = lm
    prompt = [9, 21, 3]
    full = expected(model, params, prompt, 12)      # greedy, no eos
    eos = full[len(prompt) + 5]                     # token at mid-rollout
    cut = full[:full.index(eos, len(prompt)) + 1]   # prefix THROUGH 1st eos

    srv = DecodeServer(model, params, slots=1, prompt_len=4, max_len=24,
                       eos_id=eos)
    first = srv.submit(prompt, max_new=12)
    second = srv.submit([2, 5], max_new=3)          # queued behind slot 0
    done = {c.id: c for c in srv.run_until_drained()}
    assert done[first].tokens == cut, "eos did not truncate the rollout"
    assert len(done[first].tokens) < len(full)
    assert second in done                           # freed slot was reused

    # an eos that never occurs → full-length generation
    srv2 = DecodeServer(model, params, slots=1, prompt_len=4, max_len=24,
                        eos_id=VOCAB + 5)
    srv2.submit(prompt, max_new=12)
    assert srv2.run_until_drained()[0].tokens == full


def test_pool_shards_over_mesh(lm, eight_devices):
    """The pool's slot dimension shards over the mesh data axis (SPMD
    decode, zero cross-row collectives): outputs must be token-for-token
    identical to the unsharded pool / standalone generate."""
    from idunno_tpu.parallel.mesh import local_mesh

    model, params = lm
    mesh = local_mesh()
    n = mesh.shape["data"]
    srv = DecodeServer(model, params, slots=n, prompt_len=8, max_len=24,
                       mesh=mesh)
    rng = np.random.default_rng(5)
    reqs = [([int(t) for t in rng.integers(0, VOCAB, size=k)], m)
            for k, m in [(3, 9), (8, 4), (5, 12), (2, 7), (6, 6),
                         (1, 10), (4, 5), (7, 8), (3, 3), (2, 11)]]
    ids = {srv.submit(p, m): (p, m) for p, m in reqs[:n]}
    for _ in range(2):
        srv.step()
    for p, m in reqs[n:]:                  # admitted into freed slots
        ids[srv.submit(p, m)] = (p, m)
    done = srv.run_until_drained()
    assert {c.id for c in done} == set(ids)
    for c in done:
        p, m = ids[c.id]
        assert c.tokens == expected(model, params, p, m), c.id

    with pytest.raises(ValueError, match="divide"):
        DecodeServer(model, params, slots=n + 1, prompt_len=4, max_len=8,
                     mesh=mesh)


def test_per_request_sampling(lm):
    """temperature > 0 rows sample from a per-request seeded stream:
    reproducible across pools, independent of co-resident rows, and a
    greedy request co-resident with sampled ones stays EXACTLY greedy."""
    model, params = lm
    prompt = [5, 11, 17]

    def serve(order):
        srv = DecodeServer(model, params, slots=2, prompt_len=4,
                           max_len=24)
        ids = {}
        for kind in order:
            if kind == "greedy":
                ids[srv.submit(prompt, max_new=10)] = kind
            else:
                ids[srv.submit(prompt, max_new=10, temperature=1.0,
                               seed=kind)] = kind
        return {ids[c.id]: c.tokens for c in srv.run_until_drained()}

    a = serve(["greedy", 7, 8])
    b = serve([7, "greedy", 8])           # different slots/admission order
    assert a["greedy"] == expected(model, params, prompt, 10)
    assert b["greedy"] == a["greedy"]     # co-residency can't perturb it
    assert a[7] == b[7] and a[8] == b[8]  # seeded streams reproduce
    assert a[7] != a[8]                   # different seeds diverge
    assert a[7] != a["greedy"]            # sampling actually sampled
    assert all(0 <= t < VOCAB for t in a[7][3:])


def test_sampling_fast_path_boundary(lm):
    """The decode step skips the whole sampling branch when no LIVE row
    samples (the all-greedy fast path). This test crosses that boundary
    mid-serving in both directions: a short sampled row retires while a
    long greedy row keeps decoding (branch flips sampled→greedy), then a
    NEW sampled request admits into the freed slot (greedy→sampled).
    Greedy output must equal `generate` exactly across both flips, and
    the late sampled stream must reproduce the same tokens it gets on a
    fresh pool — its key chain depends only on its own admission seed."""
    model, params = lm
    prompt = [5, 11, 17]
    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=40)
    gid = srv.submit(prompt, max_new=30)                  # long greedy
    sid = srv.submit(prompt, max_new=4, temperature=1.0,  # short sampled
                     seed=3)
    done = {}
    for _ in range(10):        # sampled row retires; steps run all-greedy
        srv.step()
        done.update({c.id: c.tokens for c in srv.poll()})
        if sid in done:
            break
    assert sid in done and gid not in done
    lid = srv.submit(prompt, max_new=6, temperature=1.0,  # late sampled
                     seed=9)
    done.update({c.id: c.tokens for c in srv.run_until_drained()})
    assert done[gid] == expected(model, params, prompt, 30)

    fresh = DecodeServer(model, params, slots=2, prompt_len=4, max_len=40)
    fid = fresh.submit(prompt, max_new=6, temperature=1.0, seed=9)
    fresh_tokens = {c.id: c.tokens for c in fresh.run_until_drained()}
    assert done[lid] == fresh_tokens[fid]


def test_prompt_buckets_exact_across_slot_reuse(lm):
    """Multi-bucket prefill: each admission uses the smallest bucket
    covering its prompt; outputs stay exact when a long-prompt request
    reuses a slot that previously held a short one and vice versa (stale
    cache/tokens beyond the bucket must never leak)."""
    model, params = lm
    srv = DecodeServer(model, params, slots=1, prompt_len=8, max_len=24,
                       prompt_buckets=(2, 4, 8))
    rng = np.random.default_rng(11)
    lens = [2, 7, 1, 8, 3, 5]              # hits all three buckets
    ids = {}
    for n in lens:
        p = [int(t) for t in rng.integers(0, VOCAB, size=n)]
        ids[srv.submit(p, max_new=6)] = p
    for c in srv.run_until_drained():
        assert c.tokens == expected(model, params, ids[c.id], 6), \
            f"bucketed prefill diverged for prompt len {len(ids[c.id])}"

    with pytest.raises(ValueError, match="largest prompt bucket"):
        DecodeServer(model, params, slots=1, prompt_len=8, max_len=24,
                     prompt_buckets=(2, 4))


def test_submit_validation(lm):
    model, params = lm
    srv = DecodeServer(model, params, slots=1, prompt_len=4, max_len=8)
    with pytest.raises(ValueError, match="empty"):
        srv.submit([], max_new=1)
    with pytest.raises(ValueError, match="bucket"):
        srv.submit([1, 2, 3, 4, 5], max_new=1)
    with pytest.raises(ValueError, match="max_len"):
        srv.submit([1, 2, 3], max_new=6)
    with pytest.raises(ValueError, match="max_new"):
        srv.submit([1], max_new=0)
    with pytest.raises(ValueError, match="temperature"):
        srv.submit([1], max_new=1, temperature=-0.5)


def test_service_time_excludes_queue_wait(lm):
    """The fair-share signal must be load-independent (round-3 VERDICT
    weak #4): a completion's ``service_s`` covers slot admission →
    retirement only, so requests that sat in a backlog queue report the
    same per-request cost as requests served from an idle pool."""
    import time as _time

    model, params = lm
    srv = DecodeServer(model, params, slots=1, prompt_len=4, max_len=24)
    srv.submit([1, 2], max_new=6)              # warm-up: pays the compiles
    warm = srv.run_until_drained()[0]
    assert warm.service_s > 0

    # 3 identical requests into ONE slot: a deliberate backlog — requests
    # 2 and 3 queue behind request 1
    t0 = _time.monotonic()
    for _ in range(3):
        srv.submit([1, 2, 3], max_new=8)
    done = srv.run_until_drained()
    wall = _time.monotonic() - t0
    assert len(done) == 3
    for c in done:
        assert c.service_s > 0
    # the load-immune discriminator: with ONE slot the three service
    # intervals are disjoint sub-intervals of the wall clock, so correct
    # service accounting sums to <= wall (+ scheduling slack), while
    # sojourn accounting sums to ~2x wall (1/3 + 2/3 + 3/3). A
    # per-request ratio bound flakes under xdist box load (measured:
    # 0.62x-wall bound tripped on a loaded 4-worker run); the sum cannot.
    svc = sorted(c.service_s for c in done)
    assert sum(svc) < 1.5 * wall, (svc, wall)
    # identical work → same-order measured service (loose: box jitter)
    assert svc[-1] < 5.0 * svc[0], svc


def test_logprobs_tracking(lm):
    """track_logprobs=True: every completion carries per-generated-token
    logprobs under the raw model distribution — cross-checked against a
    teacher-forced full forward over the completed sequence. Greedy and
    sampled rows both covered; flag off → logprobs is None."""
    model, params = lm
    prompt = [5, 11, 17]

    def teacher_forced_lps(tokens):
        logits = model.apply({"params": params},
                             jnp.asarray([tokens], jnp.int32))
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)[0]
        return [float(lp[i - 1, tokens[i]])
                for i in range(len(prompt), len(tokens))]

    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=24,
                       track_logprobs=True)
    rid_g = srv.submit(prompt, max_new=8)
    rid_s = srv.submit(prompt, max_new=8, temperature=1.2, top_k=5,
                       seed=3)
    done = {c.id: c for c in srv.run_until_drained()}
    g, smp = done[rid_g], done[rid_s]
    assert g.tokens == expected(model, params, prompt, 8)
    for c in (g, smp):
        assert c.logprobs is not None and len(c.logprobs) == 8
        want = teacher_forced_lps(c.tokens)
        np.testing.assert_allclose(c.logprobs, want, atol=2e-3,
                                   err_msg=f"request {c.id}")

    # flag off (the default): no logprob bookkeeping, field stays None
    off = DecodeServer(model, params, slots=1, prompt_len=4, max_len=24)
    off.submit(prompt, max_new=4)
    assert off.run_until_drained()[0].logprobs is None

    # the ADVERTISED delivery path: the serving-loop wrapper must carry
    # logprobs through its completion re-wrap (the field was silently
    # dropped there once)
    import time as _time

    from idunno_tpu.serve.lm_pool import LMServingLoop

    loop = LMServingLoop(DecodeServer(model, params, slots=1,
                                      prompt_len=4, max_len=24,
                                      track_logprobs=True), name="lp")
    try:
        loop.submit(prompt, max_new=8)
        got, deadline = None, _time.time() + 60.0
        while got is None and _time.time() < deadline:
            for c in loop.poll():
                got = c
            _time.sleep(0.02)
        assert got is not None and got.tokens == g.tokens
        np.testing.assert_allclose(got.logprobs, g.logprobs, atol=1e-6)
    finally:
        loop.stop()


def test_kitchen_sink_pool(lm):
    """Every pool feature composed on ONE pool — shared prefix, penalty
    buffer, logprob tracking — serving co-residents that each exercise a
    different request surface (greedy+stop, penalized greedy, top-k
    sampled, plain greedy). Each stream must still match its `generate`
    oracle exactly where an oracle exists; feature state must not leak
    between rows or across slot reuse."""
    model, params = lm
    prefix = [7, 2, 19]
    sfx = [3, 1, 4]

    def gen(max_new, **kw):
        out = generate(model, params, jnp.asarray([prefix + sfx],
                                                  jnp.int32),
                       prompt_len=len(prefix) + len(sfx),
                       max_new=max_new, **kw)
        return [int(t) for t in np.asarray(out[0])]

    plain = gen(12)
    g = plain[len(prefix) + len(sfx):]
    stop2 = [g[4], g[5]]
    # the tiny fixture model's greedy stream can repeat tokens, so the
    # pair drawn at positions 4-5 may first occur earlier — the oracle
    # retirement point is the EARLIEST match, computed rather than assumed
    m = next(i for i in range(len(g) - 1) if g[i:i + 2] == stop2)

    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=40,
                       prefix=prefix, penalties=True, track_logprobs=True)
    r_stop = srv.submit(sfx, max_new=12, stop=[stop2])
    r_pen = srv.submit(sfx, max_new=12, frequency_penalty=1e9)
    r_topk = srv.submit(sfx, max_new=12, temperature=1.2, top_k=4,
                        seed=5)
    r_plain = srv.submit(sfx, max_new=12)
    done = {c.id: c for c in srv.run_until_drained()}

    assert done[r_stop].tokens == plain[:len(prefix) + len(sfx) + m + 2]
    assert done[r_pen].tokens == gen(12, frequency_penalty=1e9)
    gen_pen = done[r_pen].tokens[len(prefix) + len(sfx):]
    assert len(set(gen_pen)) == len(gen_pen)     # no repeats
    assert done[r_plain].tokens == plain         # untouched by neighbors
    for rid in (r_pen, r_topk, r_plain):
        c = done[rid]
        assert c.prompt_len == len(prefix) + len(sfx)
        assert len(c.logprobs) == len(c.tokens) - c.prompt_len
        assert all(lp <= 1e-6 for lp in c.logprobs)   # valid logprobs

    # slot reuse: 4 requests through 2 slots already reused both slots;
    # run a second wave to confirm no stale penalty/stop/logprob state
    r2 = srv.submit(sfx, max_new=12)
    done2 = {c.id: c for c in srv.run_until_drained()}
    assert done2[r2].tokens == plain


def test_prefix_cache(lm):
    """Shared-prefix pools (system prompt): the prefix is prefilled once
    at pool build; every admission prefills only its suffix from the
    spliced cache. Completions must be token-exact vs `generate` over
    the FULL prefix+suffix prompt — plain and int8-KV pools — with prompt_len covering prefix+suffix (so the generated
    region and logprob alignment are unchanged)."""
    import dataclasses as dc

    model, params = lm
    prefix = [7, 2, 19, 4, 30]
    suffixes = [[3, 1, 4], [9], [21, 8]]

    def want(suffix, m=model, max_new=10):
        return expected(m, params, prefix + suffix, max_new)

    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=32,
                       prefix=prefix, track_logprobs=True)
    assert srv.stats()["config"]["prefix_len"] == len(prefix)
    ids = {srv.submit(sfx, max_new=10): sfx for sfx in suffixes}
    done = {c.id: c for c in srv.run_until_drained()}
    for rid, sfx in ids.items():
        c = done[rid]
        assert c.tokens == want(sfx), f"suffix {sfx} diverged"
        assert c.prompt_len == len(prefix) + len(sfx)
        assert len(c.logprobs) == 10          # generated region only

    # int8 KV cache: prefix splice carries the scale leaves too
    m8 = dc.replace(model, kv_cache_dtype="int8")
    srv8 = DecodeServer(m8, params, slots=1, prompt_len=4, max_len=32,
                        prefix=prefix)
    srv8.submit([3, 1, 4], max_new=8)
    assert srv8.run_until_drained()[0].tokens == want([3, 1, 4], m=m8,
                                                      max_new=8)

    # budget: prefix counts against max_len
    with pytest.raises(ValueError, match="prefix"):
        srv.submit([1, 2], max_new=30)        # 5 + 2 + 30 > 32
    with pytest.raises(ValueError, match="max_len"):
        DecodeServer(model, params, slots=1, prompt_len=8, max_len=10,
                     prefix=prefix)           # 5 + bucket 8 > 10
    with pytest.raises(ValueError, match="vocab"):
        DecodeServer(model, params, slots=1, prompt_len=4, max_len=32,
                     prefix=[VOCAB + 1])


def test_stop_sequences(lm):
    """Token-level stop sequences: the completion is the exact greedy
    rollout truncated at (and including) the earliest stop match in the
    GENERATED region; multi-sequence picks the earliest end; prompt-side
    occurrences don't count; works under fused dispatches (tokens decoded
    past the stop are discarded); unmatched stop = full length."""
    model, params = lm
    prompt = [9, 21, 3]
    full = expected(model, params, prompt, 12)
    gen = full[len(prompt):]

    # a 2-token stop that genuinely occurs mid-stream
    stop2 = [gen[4], gen[5]]
    want = full[:len(prompt) + 6]          # kept through the match

    def serve(stop, decode_steps=1, max_new=12):
        srv = DecodeServer(model, params, slots=2, prompt_len=4,
                           max_len=48, decode_steps=decode_steps)
        rid = srv.submit(prompt, max_new=max_new, stop=stop)
        other = srv.submit(prompt, max_new=max_new)    # no-stop co-resident
        done = {c.id: c for c in srv.run_until_drained()}
        return done[rid].tokens, done[other].tokens

    got, other = serve([stop2])
    assert got == want, (got, want)
    assert other == full                   # co-resident unaffected

    # earliest-end wins across sequences (a later 1-token match loses)
    got2, _ = serve([[gen[8]], stop2])
    assert got2 == want

    # prompt occurrences don't count: a stop matching a PROMPT token that
    # never appears in the generated region must not truncate anything
    # (falls back to any unused token if the whole prompt reappears)
    loner = next((t for t in prompt if t not in gen),
                 next(t for t in range(VOCAB) if t not in gen))
    got3, _ = serve([[loner]])
    assert got3 == full

    # four tokens a dispatch: same truncated stream
    got4, other4 = serve([stop2], decode_steps=4)
    assert got4 == want and other4 == full

    # a length-1 stop equal to the FIRST generated token (the
    # admission-picked one): the first post-admission dispatch has
    # bound+1 unscanned tokens, so the scan window must reach back to
    # gen_start (ADVICE r4 high: off-by-one hid this exact case)
    got5, other5 = serve([[gen[0]]])
    assert got5 == full[:len(prompt) + 1], (got5, gen[0])
    assert other5 == full
    # same case under four tokens a dispatch (a bigger bound)
    got6, _ = serve([[gen[0]]], decode_steps=4)
    assert got6 == full[:len(prompt) + 1]

    with pytest.raises(ValueError, match="empty stop"):
        serve([[]])
    with pytest.raises(ValueError, match="stop token"):
        serve([[VOCAB + 7]])


def test_presence_frequency_penalties(lm):
    """Penalties on a penalties=True pool: a penalized greedy stream is
    token-exact vs `generate` with the same penalties (the count
    bookkeeping agrees across tiers), a huge frequency penalty forbids
    any repeat, co-resident unpenalized rows are untouched, sampled
    penalized streams are seed-reproducible, and the flag's guard
    rejects what it must."""
    model, params = lm
    prompt = [3, 1, 4]

    def gen(max_new=12, **kw):
        out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                       prompt_len=3, max_new=max_new, **kw)
        return [int(t) for t in np.asarray(out[0])]

    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=24,
                       penalties=True)
    r_pen = srv.submit(prompt, max_new=12, frequency_penalty=1e9)
    r_plain = srv.submit(prompt, max_new=12)
    done = {c.id: c for c in srv.run_until_drained()}
    assert done[r_pen].tokens == gen(frequency_penalty=1e9)
    g = done[r_pen].tokens[3:]
    assert len(set(g)) == len(g), "huge frequency penalty must forbid repeats"
    assert done[r_plain].tokens == expected(model, params, prompt, 12)

    # presence penalty: also cross-tier exact (different formula branch)
    srv2 = DecodeServer(model, params, slots=1, prompt_len=4, max_len=24,
                        penalties=True)
    srv2.submit(prompt, max_new=10, presence_penalty=2.5)
    assert srv2.run_until_drained()[0].tokens == gen(
        max_new=10, presence_penalty=2.5)

    def sampled(seed):
        s3 = DecodeServer(model, params, slots=1, prompt_len=4,
                          max_len=24, penalties=True)
        rid = s3.submit(prompt, max_new=10, temperature=1.1,
                        frequency_penalty=0.7, seed=seed)
        return {c.id: c for c in s3.run_until_drained()}[rid].tokens

    assert sampled(11) == sampled(11)

    # guard: a penalized request needs the flag
    off = DecodeServer(model, params, slots=1, prompt_len=4, max_len=24)
    with pytest.raises(ValueError, match="penalties"):
        off.submit(prompt, max_new=4, presence_penalty=0.5)


def test_pool_top_k_sampling(lm):
    """top_k in the pool: reproducible per seed, differs from unfiltered
    sampling on the same seed, top_k=1 is exactly the greedy stream, and
    a greedy co-resident is unaffected."""
    model, params = lm
    prompt = [5, 11, 17]

    def serve(top_k):
        srv = DecodeServer(model, params, slots=2, prompt_len=4,
                           max_len=24)
        rid = srv.submit(prompt, max_new=10, temperature=1.5,
                         top_k=top_k, seed=42)
        g = srv.submit(prompt, max_new=10)
        done = {c.id: c for c in srv.run_until_drained()}
        return done[rid].tokens, done[g].tokens

    a1, g1 = serve(3)
    a2, g2 = serve(3)
    b1, _ = serve(0)
    one, _ = serve(1)
    assert a1 == a2                     # seeded top-k stream reproducible
    assert g1 == g2 == expected(model, params, prompt, 10)
    assert a1 != b1                     # the k-filter changed the stream
    # k=1 leaves only the argmax token: identical to the greedy stream
    assert one == g1
    with pytest.raises(ValueError, match="top_k"):
        serve(-1)


def test_pool_top_p_sampling(lm):
    """top_p in the pool: reproducible per seed, differs from top_p=1 on
    the same seed (the nucleus genuinely filters), greedy unaffected."""
    model, params = lm
    prompt = [5, 11, 17]

    def serve(top_p):
        srv = DecodeServer(model, params, slots=2, prompt_len=4,
                           max_len=24)
        rid = srv.submit(prompt, max_new=10, temperature=1.5,
                         top_p=top_p, seed=42)
        g = srv.submit(prompt, max_new=10)
        done = {c.id: c for c in srv.run_until_drained()}
        return done[rid].tokens, done[g].tokens

    a1, g1 = serve(0.3)
    a2, g2 = serve(0.3)
    b1, _ = serve(1.0)
    assert a1 == a2                     # seeded nucleus stream reproducible
    assert g1 == g2 == expected(model, params, prompt, 10)
    assert a1 != b1                     # the filter changed the stream
    with pytest.raises(ValueError, match="top_p"):
        serve(0.0)
    with pytest.raises(ValueError, match="top_p"):
        serve(1.5)


def test_int8_kv_cache_pool_matches_its_own_generate(lm):
    """kv_cache_dtype="int8": the cache stores int8 values + per-(row,
    position, head) scales at a quarter of the float32 footprint. The
    pool and one-shot generate share the quantized math, so the pool
    stays token-exact vs generate ON THE SAME MODEL; drift vs the
    native-cache model is bounded (lossy by design, opt-in)."""
    import dataclasses

    import jax.numpy as jnp

    from idunno_tpu.engine.generate import init_cache

    model, params = lm
    m8 = dataclasses.replace(model, kv_cache_dtype="int8")

    cache = init_cache(m8, 2, 16)
    leaf = cache["block0"]["attn"]["cached_k"]
    assert leaf.dtype == jnp.int8
    assert cache["block0"]["attn"]["k_scale"].shape == (2, 16, 4)

    prompt = [5, 11, 17]
    want8 = expected(m8, params, prompt, 10)       # int8-cache generate
    srv = DecodeServer(m8, params, slots=2, prompt_len=4, max_len=24)
    a = srv.submit(prompt, max_new=10)
    b = srv.submit([2, 7], max_new=6)
    done = {c.id: c for c in srv.run_until_drained()}
    assert done[a].tokens == want8                 # pool == its generate
    assert done[b].tokens == expected(m8, params, [2, 7], 6)

    # bounded drift vs the native cache (tiny model: logit error well
    # under 2% of the logit range)
    import numpy as np

    from idunno_tpu.engine.generate import stepwise_logits
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 10), 0, VOCAB)
    l8 = np.asarray(stepwise_logits(m8, params, toks))
    lf = np.asarray(model.apply({"params": params}, toks))
    assert np.abs(l8 - lf).max() < 0.02 * (lf.max() - lf.min() + 1e-9) + 0.05


def test_stats_reports_serving_config(lm):
    """`lm_stats` must tell an operator what the pool is actually running
    (GQA width, cache dtype, weight quantization)."""
    import dataclasses

    model, params = lm
    m = dataclasses.replace(model, num_kv_heads=2, kv_cache_dtype="int8")
    srv = DecodeServer(m, params, slots=2, prompt_len=4, max_len=16,
                       quantize="int8")
    cfg = srv.stats()["config"]
    assert cfg["kv_heads"] == 2 and cfg["heads"] == 4
    assert cfg["kv_cache_dtype"] == "int8"
    assert cfg["quantize"] == "int8"

    plain = DecodeServer(model, params, slots=1, prompt_len=4, max_len=20)
    assert plain.stats()["config"]["quantize"] == "none"


def test_handoff_lands_mid_serve_all_streams_exact(lm):
    """DistServe composing with live traffic (ISSUE 18): a long prompt
    prefilled on a SEPARATE replica ships its block chain into a decode
    server whose slots are mid-flight on other work — the graft happens
    between steps, the long admits through the radix hit, and every
    stream (prior rows, the handed-off long, later arrivals) stays
    token-exact vs `generate`."""
    model, params = lm
    rng = np.random.default_rng(11)
    kw = dict(slots=2, prompt_len=8, max_len=24, kv_block_size=2,
              kv_cache_blocks=16)
    pre = DecodeServer(model, params, **kw)
    dec = DecodeServer(model, params, **kw)
    ids = {}
    for n, m in [(3, 6), (5, 4)]:
        p = [int(t) for t in rng.integers(0, VOCAB, size=n)]
        ids[dec.submit(p, max_new=m)] = (p, m)
    for _ in range(2):
        dec.step()                            # rows decoding mid-flight
    long_p = [int(t) for t in rng.integers(0, VOCAB, size=8)]
    d0 = dec.handoff_probe(long_p)["depth"]
    exp = pre.handoff_export(long_p, from_depth=d0)
    adopt = dec.handoff_adopt(long_p, exp["blobs"], start_depth=d0)
    assert adopt["depth"] == 3 and exp["bytes"] > 0
    ids[dec.submit(long_p, max_new=6)] = (long_p, 6)
    p_late = [int(t) for t in rng.integers(0, VOCAB, size=4)]
    ids[dec.submit(p_late, max_new=5)] = (p_late, 5)
    done = {c.id: c for c in dec.run_until_drained()}
    assert set(done) == set(ids)
    for rid, (p, m) in ids.items():
        assert done[rid].tokens == expected(model, params, p, m), \
            f"request {rid} diverged after a mid-serve handoff graft"
    # gauge surface: the ship is visible on both endpoints' lm_stats
    assert pre.stats()["kv_handoff_requests"] == 1
    assert dec.stats()["kv_handoff_bytes"] == exp["bytes"]
    assert dec.stats()["kv_handoff_fallbacks"] == 0


def test_cancel_queued_request(lm):
    """A cancel that lands while the request is still queued drops it
    before admission: its completion carries only the prompt and the
    cancelled flag; the already-live request is untouched (exact)."""
    model, params = lm
    srv = DecodeServer(model, params, slots=1, prompt_len=4, max_len=24)
    live_id = srv.submit([1, 2], max_new=6)
    srv.step()                                # admit into the only slot
    queued_id = srv.submit([3, 4, 5], max_new=6)
    assert srv.cancel(queued_id) == "queued"
    done = {c.id: c for c in srv.run_until_drained()}
    assert done[queued_id].cancelled
    assert done[queued_id].tokens == [3, 4, 5]          # prompt only
    assert done[queued_id].prompt_len == 3
    assert not done[live_id].cancelled
    assert done[live_id].tokens == expected(model, params, [1, 2], 6)
    assert srv.stats()["cancelled"] == 1
    assert srv.stats()["completed"] == 1      # cancelled is not completed
    assert done[queued_id].logprobs is None   # non-tracking pool

    # on a track_logprobs pool the queued-cancel completion carries
    # logprobs=[] — same shape LMServingLoop.cancel produces (ADVICE r4
    # low: the two tiers disagreed)
    srv_lp = DecodeServer(model, params, slots=1, prompt_len=4, max_len=24,
                          track_logprobs=True)
    live2 = srv_lp.submit([1, 2], max_new=6)
    srv_lp.step()
    queued2 = srv_lp.submit([3, 4], max_new=6)
    assert srv_lp.cancel(queued2) == "queued"
    done2 = {c.id: c for c in srv_lp.run_until_drained()}
    assert done2[queued2].cancelled and done2[queued2].logprobs == []
    assert len(done2[live2].logprobs) == 6    # live row tracked normally


def test_cancel_live_returns_partial_and_frees_slot(lm):
    """Cancelling a live row retires it with the tokens generated so far
    (a strict prefix of what it would have produced), frees the slot for
    the next queued prompt, and never perturbs co-resident rows."""
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=44)
    long_id = srv.submit([1, 2, 3], max_new=40)
    other_id = srv.submit([7, 8], max_new=10)
    for _ in range(4):
        srv.step()
    assert srv.cancel(long_id) == "live"
    follow_id = srv.submit([5], max_new=3)    # admitted into the freed slot
    done = {c.id: c for c in srv.run_until_drained()}

    full = expected(model, params, [1, 2, 3], 40)
    got = done[long_id]
    assert got.cancelled
    assert len(got.tokens) < len(full)
    assert got.tokens == full[:len(got.tokens)], \
        "partial tokens must be a prefix of the uncancelled stream"
    assert len(got.tokens) > 3                # prompt + at least one token
    assert not done[other_id].cancelled
    assert done[other_id].tokens == expected(model, params, [7, 8], 10)
    assert done[follow_id].tokens == expected(model, params, [5], 3)
    # idempotence / unknown ids
    assert srv.cancel(long_id) == "unknown"
    assert srv.cancel(999) == "unknown"


def test_snapshot_streams_prefixes(lm):
    """`snapshot` exposes every live row's progress as an exact prefix of
    its final stream — the streaming surface behind lm_partial."""
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=30)
    a = srv.submit([1, 2], max_new=20)
    b = srv.submit([9, 3, 4], max_new=20)
    assert srv.snapshot() == []               # nothing admitted yet
    for _ in range(3):
        srv.step()
    snap = {r["id"]: r for r in srv.snapshot()}
    assert set(snap) == {a, b}
    for rid, prompt in ((a, [1, 2]), (b, [9, 3, 4])):
        row = snap[rid]
        assert row["prompt_len"] == len(prompt)
        full = expected(model, params, prompt, 20)
        assert len(row["tokens"]) > len(prompt)         # progress visible
        assert row["tokens"] == full[:len(row["tokens"])]
    srv.run_until_drained()
    assert srv.snapshot() == []               # drained pool has no live rows


@pytest.mark.parametrize("kv_heads", [None, 2, 1])
def test_prefix_cache_pool_stays_exact_under_staggered_admission(kv_heads):
    """kv_block_size>0 turns on the cross-request radix prefix cache
    (`serve/prefix_cache.py`): the ORIGINAL exactness oracle must keep
    holding under staggered admission and slot reuse while requests
    share prompt heads at every hit depth (cold, partial-block,
    multi-block, full-prompt resubmit), for MHA and GQA/MQA pools.
    The full cache-semantics matrix lives in `tests/test_prefix_cache.py`."""
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                          num_kv_heads=kv_heads)
    params = model.init(jax.random.PRNGKey(4),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(13)
    base = [int(t) for t in rng.integers(0, VOCAB, size=8)]
    reqs = [(base, 6),                                  # cold
            (base[:2] + [59, 58, 57], 5),               # 1-block hit
            (base[:6] + [55], 4),                       # 3-block hit
            (base, 6),                                  # full-prompt hit
            ([53, 52, 51], 7)]                          # miss, short

    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       kv_block_size=2, kv_cache_blocks=12)
    ids = {}
    for prompt, max_new in reqs[:3]:
        ids[srv.submit(prompt, max_new)] = (prompt, max_new)
    for _ in range(3):                        # mid-flight...
        srv.step()
    for prompt, max_new in reqs[3:]:          # ...new arrivals are admitted
        ids[srv.submit(prompt, max_new)] = (prompt, max_new)
    done = srv.run_until_drained()

    assert {c.id for c in done} == set(ids)
    for c in done:
        prompt, max_new = ids[c.id]
        assert c.tokens == expected(model, params, prompt, max_new), \
            f"request {c.id} diverged with the prefix cache on"
    pc = srv.prefix_cache_stats()
    assert pc["lookups"] == 5 and pc["hits"] >= 2
    assert pc["cached_tokens_saved"] > 0


def test_pool_scans_layers_and_reports_it(lm):
    """A scan-compatible model is converted to the scanned twin at pool
    construction (stacked params, `lax.scan` layer loop) and says so in
    the stats config — the serving default IS the scanned hot loop."""
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=16)
    assert srv.model.scan_layers
    assert srv.stats()["config"]["scan_layers"] is True
    # the stacked layout is real: one "blocks" subtree with a leading
    # depth axis, not per-block subtrees
    assert "blocks" in srv.params and "block0" not in srv.params


def test_moe_pool_stays_unscanned_and_exact(lm):
    """A per-block ffn_factory (MoE interleave) breaks block homogeneity:
    the pool must keep the per-layer loop — and keep the exactness
    oracle — rather than scan heterogeneous blocks."""
    from idunno_tpu.models.moe import MoETransformerLM
    model = MoETransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                             n_experts=2)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=16)
    assert not srv.model.scan_layers
    assert srv.stats()["config"]["scan_layers"] is False
    prompt = [5, 11, 17]
    rid = srv.submit(prompt, max_new=8)
    done = {c.id: c for c in srv.run_until_drained()}
    assert done[rid].tokens == expected(model, params, prompt, 8)


def test_warmup_pays_compiles_then_resets_the_pool(lm):
    """`warmup()` runs a throwaway request through prefill+decode so the
    one-time compile cost never lands in
    a real request's service time or the fair-share signal — then resets
    ids and counters so the pool looks untouched. Streams after warm-up
    must match the `generate` oracle exactly (the warm-up must not leak
    state into real rows)."""
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=4, max_len=20)
    warm_s = srv.warmup()
    assert warm_s > 0.0
    assert srv.stats()["completed"] == 0               # counters reset
    prompt = [5, 11, 17]
    rid = srv.submit(prompt, max_new=10)
    assert rid == 0                                    # ids restart at 0
    done = {c.id: c for c in srv.run_until_drained()}
    assert done[rid].tokens == expected(model, params, prompt, 10)
    st = srv.stats()
    assert st["completed"] == 1 and st["admitted"] == 1
    srv.submit([1], max_new=2)                         # pool no longer idle
    with pytest.raises(RuntimeError, match="idle"):
        srv.warmup()

# -- chunked prefill --------------------------------------------------------

@pytest.mark.parametrize("pool_kw", [
    {},                                                    # plain pool
    {"kv_block_size": 2, "kv_cache_blocks": 16},           # gathered radix
    {"kv_block_size": 2, "kv_cache_blocks": 16,            # paged radix
     "paged_kernel": "pallas"},
])
def test_chunked_prefill_token_exact(lm, pool_kw):
    """Splitting a long prompt's prefill into fixed-size chunks must be
    INVISIBLE in the streams: scalar cursors + per-position K/V writes +
    per-query masks make the chunk boundaries pure scheduling. Same
    oracle as one-shot admission, across radix hit reuse too."""
    model, params = lm
    rng = np.random.default_rng(23)
    prompts = [[int(t) for t in rng.integers(0, VOCAB, size=n)]
               for n in (8, 7, 8, 3)]
    prompts.append(list(prompts[0]))          # radix hit on kv pools
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       prefill_chunk=3, **pool_kw)
    ids = {srv.submit(p, max_new=6): p for p in prompts}
    done = {c.id: c for c in srv.run_until_drained()}
    for rid, p in ids.items():
        assert done[rid].tokens == expected(model, params, p, 6), \
            f"chunked admission diverged for {p} under {pool_kw}"
    st = srv.stats()
    # 8-bucket prompts chunk (ceil(8/3)=3 each); the 3-token prompt pads
    # to the single 8 bucket here too, so every admission chunks
    assert st["prefill_chunks"] == 3 * len(prompts)
    assert st["config"]["prefill_chunk"] == 3


def test_chunked_prefill_interleaves_decode(lm):
    """Fairness: while a long prompt's prefill is pending, resident rows
    must keep decoding BETWEEN chunks — the head-of-line blocking cure
    chunked prefill exists for (Sarathi-style stall-free batching)."""
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=40,
                       prompt_buckets=(2, 8), prefill_chunk=2)
    a = srv.submit([1], max_new=24)           # 2-bucket: admits one-shot
    srv.step()
    snap0 = {r["id"]: len(r["tokens"]) for r in srv.snapshot()}
    b = srv.submit([5, 6, 7, 8, 9, 10, 11], max_new=4)  # 8-bucket: 4 chunks
    progress = []
    while True:                               # b's admission in flight
        srv.step()
        if srv._pending is None:
            break
        live = {r["id"]: len(r["tokens"]) for r in srv.snapshot()}
        progress.append(live.get(a, 0))
    assert len(progress) >= 2, "8-bucket/chunk-2 prefill should take 4 steps"
    assert progress[-1] > snap0[a], \
        "resident row did not advance while the chunked prefill was pending"
    assert all(y > x for x, y in zip(progress, progress[1:])), \
        "every chunk step must also run a decode dispatch for resident rows"
    done = {c.id: c for c in srv.run_until_drained()}
    assert done[a].tokens == expected(model, params, [1], 24)
    assert done[b].tokens == expected(
        model, params, [5, 6, 7, 8, 9, 10, 11], 4)


def test_cancel_mid_chunk(lm):
    """A cancel landing between chunks drops the pending admission:
    queued-shape completion (prompt only, cancelled), the slot it was
    bound for admits the next prompt, stats count one cancel."""
    model, params = lm
    srv = DecodeServer(model, params, slots=1, prompt_len=8, max_len=24,
                       prefill_chunk=2)
    victim = [3, 1, 4, 1, 5, 9, 2, 6]
    vid = srv.submit(victim, max_new=6)
    srv.step()                                # first chunk only (of 4)
    assert srv.pending() == 1
    assert srv.cancel(vid) == "queued"
    assert srv.pending() == 0
    follow = srv.submit([7, 8], max_new=3)
    done = {c.id: c for c in srv.run_until_drained()}
    assert done[vid].cancelled and done[vid].tokens == victim
    assert done[follow].tokens == expected(model, params, [7, 8], 3)
    st = srv.stats()
    assert st["cancelled"] == 1 and st["completed"] == 1
    assert st["admitted"] == 1, "cancelled pending admission never admitted"


def test_short_prompts_skip_chunking(lm):
    """Prompts at or under the chunk size admit one-shot — no pending
    state, no prefill_chunks counted."""
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       prompt_buckets=(2, 4, 8), prefill_chunk=4)
    rid = srv.submit([5, 9], max_new=4)       # 2-bucket ≤ chunk 4
    srv.step()
    assert srv._pending is None and srv.stats()["prefill_chunks"] == 0
    done = {c.id: c for c in srv.run_until_drained()}
    assert done[rid].tokens == expected(model, params, [5, 9], 4)


# -- tensor-parallel decode (ISSUE 9) ---------------------------------------

@pytest.mark.parametrize("n_model", [2, 4])
def test_tp_decode_token_exact(lm, eight_devices, n_model):
    """The Megatron split over the model axis changes WHERE the math runs,
    not what it computes: a TP pool must match the standalone generate
    oracle token-for-token — greedy rows and seeded sampled rows alike."""
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24,
                       n_model=n_model)
    assert srv.n_model == n_model
    rng = np.random.default_rng(13)
    reqs = [([int(t) for t in rng.integers(0, VOCAB, size=k)], m)
            for k, m in [(3, 9), (8, 4), (5, 12), (2, 7)]]
    ids = {srv.submit(p, m): (p, m, None) for p, m in reqs}
    sp = [4, 17, 2]
    sid = srv.submit(sp, max_new=8, temperature=0.8, top_p=0.9, seed=21)
    done = {c.id: c for c in srv.run_until_drained()}
    for rid, (p, m, _) in ids.items():
        assert done[rid].tokens == expected(model, params, p, m), rid
    # the sampled stream must reproduce the n_model=1 pool's stream
    ref = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24)
    ref_id = ref.submit(sp, max_new=8, temperature=0.8, top_p=0.9, seed=21)
    ref_done = {c.id: c for c in ref.run_until_drained()}
    assert done[sid].tokens == ref_done[ref_id].tokens, \
        "seeded sampling diverged under TP"


def test_tp_decode_2d_mesh_with_gqa(lm, eight_devices):
    """4x2 (data, model) mesh: slots shard over data, heads over model,
    and GQA KV heads that don't divide n_model replicate (divide-or-
    replicate) — all still token-exact vs generate."""
    from idunno_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(4, 2, devices=eight_devices)
    for kvh in (2, 1):                    # divides / replicates (MQA)
        gqa = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                            num_kv_heads=kvh)
        gparams = gqa.init(jax.random.PRNGKey(3),
                           jnp.zeros((1, 8), jnp.int32))["params"]
        srv = DecodeServer(gqa, gparams, slots=4, prompt_len=8,
                           max_len=24, mesh=mesh)
        assert srv.n_model == 2           # derived from the mesh
        rids = {srv.submit([1 + kvh, 5, 9], max_new=6),
                srv.submit([7, 2], max_new=8)}
        done = {c.id: c for c in srv.run_until_drained()}
        assert set(done) == rids
        for c in done.values():
            p = [1 + kvh, 5, 9] if len(c.tokens) == 9 else [7, 2]
            assert c.tokens == expected(gqa, gparams, p,
                                        len(c.tokens) - len(p)), kvh


@pytest.fixture(scope="module")
def lm64():
    """Vocab 64 DIVIDES n_model 2 and 4, so the unembed genuinely
    column-shards (the module-level VOCAB=61 degrades to replicated)."""
    model = TransformerLM(vocab=64, dim=32, depth=2, num_heads=4)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.mark.parametrize("n_model", [2, 4])
def test_tp_sharded_tail_five_modes_token_exact(lm64, eight_devices,
                                                n_model):
    """ISSUE 16: with the unembed column-sharded, the fused tail resolves
    every pick from per-shard partial stats (`ops/sampling.py:
    sample_keep_mask` bit-bisection — no [S, vocab] all-gather, no sort).
    Five serving modes must stay token-exact vs the replicated n_model=1
    pool, and the deterministic rows vs the `generate` oracle: greedy,
    seeded-sampled, filtered (top_k+top_p), penalized, and per-token-
    logprob rows."""
    model, params = lm64

    def serve(nm):
        srv = DecodeServer(model, params, slots=3, prompt_len=8,
                           max_len=32, n_model=nm,
                           penalties=True, track_logprobs=True)
        rows = {
            "greedy": srv.submit([5, 11, 17], max_new=8),
            "sampled": srv.submit([4, 17, 2], max_new=8,
                                  temperature=0.8, seed=21),
            "filtered": srv.submit([9, 1], max_new=8, temperature=0.9,
                                   top_k=7, top_p=0.85, seed=5),
            "penalized": srv.submit([3, 7, 31, 8], max_new=8,
                                    presence_penalty=0.6,
                                    frequency_penalty=0.4),
            "logprobs": srv.submit([2, 40, 13], max_new=6),
        }
        done = {c.id: c for c in srv.run_until_drained()}
        return {k: done[rid] for k, rid in rows.items()}

    got, ref = serve(n_model), serve(1)
    for mode in got:
        assert got[mode].tokens == ref[mode].tokens, \
            f"{mode} row diverged at n_model={n_model}"
    # deterministic rows also match the standalone generate oracle
    assert got["greedy"].tokens == expected(model, params, [5, 11, 17], 8)
    pen = generate(model, params, jnp.asarray([[3, 7, 31, 8]], jnp.int32),
                   prompt_len=4, max_new=8,
                   presence_penalty=0.6, frequency_penalty=0.4)
    assert got["penalized"].tokens == [int(t) for t in np.asarray(pen[0])]
    # logprobs ride the sharded tail's one-hot pick — same values as the
    # replicated pool within float reduction-order noise
    for mode in got:
        np.testing.assert_allclose(got[mode].logprobs, ref[mode].logprobs,
                                   atol=1e-5, err_msg=mode)


def test_tp_rejects_bad_shapes(lm, eight_devices):
    """n_model must divide Q heads (typed MeshShapeError), conflict with
    an explicit mesh raises, and the unscanned layout refuses TP."""
    from idunno_tpu.parallel.mesh import MeshShapeError, make_mesh

    model, params = lm
    with pytest.raises(MeshShapeError):   # 4 heads over 3 shards
        DecodeServer(model, params, slots=2, prompt_len=4, max_len=8,
                     n_model=3)
    mesh = make_mesh(4, 2, devices=eight_devices)
    with pytest.raises(ValueError, match="conflicts"):
        DecodeServer(model, params, slots=4, prompt_len=4, max_len=8,
                     mesh=mesh, n_model=4)
    moe_like = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4,
                             ffn_factory=lambda: None)
    with pytest.raises(ValueError, match="scanned"):
        DecodeServer(moe_like, params, slots=2, prompt_len=4, max_len=8,
                     n_model=2)


# -- the context ladder: a dispatch reads the deepest live row's rung ---------

_LADDER_POOLS = {
    "native": {},
    "int8": {"kv_cache_dtype": "int8"},
    "gqa": {"num_kv_heads": 2},
    "radix-gathered": {"pool": {"kv_block_size": 4, "kv_cache_blocks": 160}},
    "radix-paged": {"pool": {"kv_block_size": 4, "kv_cache_blocks": 160,
                             "paged_kernel": "xla"}},
}


def _ladder_pool(lm, kind, **kw):
    import dataclasses

    model, params = lm
    spec = dict(_LADDER_POOLS[kind])
    pool_kw = spec.pop("pool", {})
    model = dataclasses.replace(model, **spec)
    if "num_kv_heads" in spec:                    # narrower K/V kernels
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    kw = dict(dict(slots=3, prompt_len=320, max_len=512, decode_steps=4,
                   prompt_buckets=(8, 320)), **pool_kw, **kw)
    return model, params, DecodeServer(model, params, **kw)


@pytest.mark.parametrize("kind", list(_LADDER_POOLS))
def test_rows_on_every_rung_serve_generates_streams(lm, kind):
    """max_len 512 has four rungs of 128. Rows that start at depths 3, 130
    and 300 and grow across rung borders (one of them from 126 to 140)
    draw, token for token, what `generate` draws with its one read of the
    whole axis; the int8 pool, whose streams may drift from the
    native-cache model's, is held to its own one-rung self."""
    from idunno_tpu.models import transformer

    rng = np.random.default_rng(5)
    reqs = [([int(t) for t in rng.integers(0, VOCAB, size=n)], m)
            for n, m in [(3, 9), (126, 14), (300, 90), (130, 6), (250, 12)]]
    if "radix" in kind:           # a shared head, so that later rows hit
        head = reqs[1][0][:64]
        reqs = [(head + p[64:] if len(p) > 64 else p, m) for p, m in reqs]

    def serve():
        model, params, srv = _ladder_pool(lm, kind)
        ids = {srv.submit(p, m): (p, m) for p, m in reqs}
        return model, params, srv, ids, srv.run_until_drained()
    model, params, srv, ids, done = serve()
    stats = srv.stats()
    assert 0 < stats["decode_context_read"] < stats["decode_context_held"]
    if kind == "int8":
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(transformer, "context_rungs", lambda n: (n,))
            want = {c.id: c.tokens for c in serve()[-1]}
    else:
        want = {rid: expected(model, params, p, m)
                for rid, (p, m) in ids.items()}
    assert {c.id: c.tokens for c in done} == want


@pytest.mark.parametrize("case", ladder_cases.CASES[1:],
                         ids=lambda c: c.__name__)
def test_the_bound_follows_the_live_rows(lm, case):
    """`ladder_cases`' pool cases over the native pool (the first is
    `test_rows_on_every_rung_serve_generates_streams`, which also runs
    the int8 and radix pools): a row that ends mid-dispatch stops holding
    the bound, a slot a deep request left serves a fresh pool's stream, a
    row at the end of the cache reads all of it. A stack whose model
    answers `decode_context_rungs` with None has no such counters
    (`tests/test_hybrid_lm.py`)."""
    case(lm, lambda built, **kw: _ladder_pool(built, "native", **kw)[2])


# -- the decode dispatch goes first (ISSUE 35) ---------------------------------

_ORDER_POOLS = {
    "plain": {},
    "radix-gathered": {"kv_block_size": 2, "kv_cache_blocks": 64},
    "radix-paged": {"kv_block_size": 2, "kv_cache_blocks": 64,
                    "paged_kernel": "xla"},
}


@pytest.mark.parametrize("kind", list(_ORDER_POOLS))
def test_a_mixed_schedule_serves_generates_tokens_with_stamps_in_order(
        lm, kind):
    """Arrivals into an empty pool and into a busy one, a chunked
    admission beside the dispatches, a one-token request, a cancel of a
    live row and of a pending chunked admission: every stream is
    `generate`'s, every first stamp shows the prefill's one token, and
    `admissions_overlapped` counts the admissions of the steps that
    dispatched."""
    import itertools

    model, params = lm
    srv = DecodeServer(model, params, slots=3, prompt_len=8, max_len=40,
                       prompt_buckets=(4, 8), prefill_chunk=4,
                       **_ORDER_POOLS[kind])
    ticks = itertools.count(1)
    srv.clock = lambda: float(next(ticks))
    rng = np.random.default_rng(35)
    reqs, done, behind = {}, {}, []

    def submit(n, max_new):
        prompt = [int(t) for t in rng.integers(0, VOCAB, size=n)]
        rid = srv.submit(prompt, max_new)
        reqs[rid] = (prompt, max_new)
        return rid

    def step():
        before = srv.stats()
        left = srv.step()
        after = srv.stats()
        if after["dispatches"] > before["dispatches"]:
            behind.append(after["admitted"] - before["admitted"])
        done.update((c.id, c) for c in srv.poll())
        return left

    a = submit(3, 30)
    step()                                    # an empty pool: no dispatch
    assert srv.stats()["dispatches"] == 0 and len(srv._live) == 1
    b = submit(2, 30)                         # one-shot, behind a's dispatch
    c = submit(8, 6)                          # two chunks
    step()
    assert srv._pending is not None and len(srv._live) == 2
    step()                                    # the second chunk: c is in
    assert srv._pending is None and len(srv._live) == 3
    one = submit(4, 1)                        # no slot is free yet
    assert srv.cancel(b) == "live"
    step()                  # b leaves at the step's start, `one` at its end
    assert done[b].cancelled and one in done and len(srv._live) == 2
    e = submit(8, 5)
    step()                                    # e's first chunk
    assert srv._pending is not None and srv.cancel(e) == "queued"
    f = submit(5, 7)
    while step():
        pass
    g = submit(3, 2)                          # an empty pool again
    while step():
        pass

    assert set(done) == set(reqs)
    for rid, (prompt, max_new) in reqs.items():
        d, want = done[rid], expected(model, params, prompt, max_new)
        if rid == e:                          # it never had a slot
            assert d.cancelled and d.tokens == prompt and d.t_first is None
            continue
        if rid == b:
            assert len(prompt) < len(d.tokens) < len(want)
        else:
            assert not d.cancelled and len(d.tokens) == len(want)
        assert d.tokens == want[:len(d.tokens)], rid
        assert d.t_submit < d.t_admit < d.t_first <= d.t_last, rid
        assert d.n_first == 1, rid
    assert done[one].t_first == done[one].t_last
    st = srv.stats()
    assert st["admitted"] == len(reqs) - 1
    # a and g met an empty pool; b, c, one and f queued behind a dispatch
    assert st["admissions_overlapped"] == sum(behind) == 4
    assert done[a].cold_start and not done[g].cold_start
    assert f in done


def test_context_and_decode_spans_count_the_dispatches_a_row_took_part_in(
        lm):
    """Rows admitted mid-flight join the NEXT step's dispatch: what
    `decode_context_read` adds up, and the `steps` of each `lm.decode`
    span, equal a reckoning from the rows live BEFORE each step (four
    steps a dispatch, rungs of 128, three slots)."""
    from idunno_tpu.utils.spans import SpanStore

    model, params, srv = _ladder_pool(lm, "native")
    srv.spans = store = SpanStore("n0")
    rng = np.random.default_rng(11)
    plan = {0: [(300, 7)], 1: [(3, 10)], 2: [(130, 3), (4, 1)],
            4: [(250, 6), (5, 9)], 5: [(126, 12)]}
    max_new, read, held, dispatches = {}, 0, 0, 0
    for i in range(30):
        for n, m in plan.get(i, ()):
            rid = srv.submit([int(t) for t in rng.integers(0, VOCAB, size=n)],
                             m, trace=(f"t:{len(max_new)}", "root"))
            max_new[rid] = m
        rows = [(len(r["tokens"]) - 1,
                 max_new[r["id"]] - (len(r["tokens"]) - r["prompt_len"]))
                for r in srv.snapshot()]
        if rows:
            assert all(left > 0 for _cur, left in rows)
            dispatches += 1
            held += 4 * 512 * 3
            for j in range(4):
                need = max((cur + j for cur, left in rows if left > j),
                           default=0) + 1
                read += -(-need // 128) * 128 * 3
        srv.step()
    assert srv.pending() == 0 and len(srv.poll()) == len(max_new) == 7
    st = srv.stats()
    assert st["dispatches"] == dispatches
    assert (st["decode_context_read"], st["decode_context_held"]) \
        == (read, held)
    steps = {s["attrs"]["id"]: s["attrs"] for s in store.dump()
             if s["name"] == "lm.decode"}
    # the prefill gives a row its first token, each dispatch four more; a
    # row whose one token was the prefill's never met a dispatch
    assert {rid: a["steps"] for rid, a in steps.items()} \
        == {rid: -(-(m - 1) // 4) for rid, m in max_new.items() if m > 1}
    assert all(a["n_first"] == 1 and a["tokens"] == max_new[rid]
               for rid, a in steps.items())


@pytest.mark.parametrize("pool_kw", [
    {},
    {"track_logprobs": True, "penalties": True, "kv_block_size": 2,
     "kv_cache_blocks": 32, "paged_kernel": "xla"},
], ids=["plain", "logprobs-penalties-paged"])
def test_an_admission_sets_its_slots_state_with_one_program(
        lm, monkeypatch, pool_kw):
    """Every per-slot array an admission touches goes through one
    `_set_rows` call (done eagerly they were dozens of tiny programs, and
    behind a dispatch in flight the host ran into the runtime's limit on
    programs in flight), and the slot reads what the eager sets left."""
    from idunno_tpu.engine import serve_lm

    model, params = lm
    calls = []
    inner = serve_lm._set_rows
    monkeypatch.setattr(serve_lm, "_set_rows",
                        lambda *a: calls.append(len(a[0])) or inner(*a))
    srv = DecodeServer(model, params, slots=3, prompt_len=8, max_len=24,
                       **pool_kw)
    srv.submit([9, 8, 7], max_new=12)
    srv.step()
    srv.submit([1, 2, 3, 4, 5], max_new=6, temperature=0.7, top_p=0.9,
               top_k=5, seed=3,
               **({"presence_penalty": 0.5, "frequency_penalty": 0.25}
                  if pool_kw else {}))
    srv.step()                                # behind the first's dispatch
    assert calls == [12 if pool_kw else 6] * 2
    (slot,) = [s for s, r in srv._live.items() if r.id == 1]
    first = int(np.asarray(srv._tokens)[slot, 5])
    assert (int(srv._cursors[slot]), int(srv._remaining[slot])) == (5, 5)
    assert (float(srv._temps[slot]), float(srv._top_ps[slot]),
            int(srv._top_ks[slot])) == (np.float32(0.7), np.float32(0.9), 5)
    if pool_kw:
        counts = np.asarray(srv._counts)[slot]
        assert counts.sum() == 1 and counts[first] == 1
        assert (float(srv._pres[slot]), float(srv._freq[slot])) \
            == (0.5, 0.25)
        assert np.asarray(srv._logprobs)[slot, 5] < 0.0
        assert int(srv._plens[slot]) == 0
    done = {c.id: c for c in srv.run_until_drained()}
    assert done[0].tokens == expected(model, params, [9, 8, 7], 12)
    assert len(done[1].tokens) == 11 and done[1].tokens[5] == first
