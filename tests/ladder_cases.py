"""What a pool whose decode step takes the context ladder is held to
(`MultiHeadAttention._decode_step`, `models/hybrid.py:_attend_live`,
`DecodeServer._build_decode`), at ``max_len`` 512: four rungs of 128. One
body a case, ``case(built, pool)`` with ``built`` = (model, params) and
``pool(built, **kw)`` the `DecodeServer` over it; run by `test_serve_lm.py`
for `TransformerLM` and by `test_hybrid_falcon_h1.py` and
`test_hybrid_granite.py` over their own toy stacks (ISSUE 37), so that each
family counts each case."""
import numpy as np

import jax.numpy as jnp

from idunno_tpu.engine.generate import generate
from idunno_tpu.engine.serve_lm import DecodeServer

def hybrid_pool(built, **kw):
    model, params = built
    kw = dict(dict(slots=3, prompt_len=320, max_len=512, decode_steps=4,
                   prompt_buckets=(8, 320), kv_block_size=8,
                   kv_cache_blocks=16), **kw)
    return DecodeServer(model, params, **kw)


def expected(built, prompt: list[int], max_new: int) -> list[int]:
    """`engine.generate`'s greedy stream: scalar-cursor steps, each ONE
    softmax over the whole axis."""
    model, params = built
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   prompt_len=len(prompt), max_new=max_new)
    return [int(t) for t in np.asarray(out[0])]


def _prompt(built, rng, n):
    return [int(t) for t in rng.integers(0, built[0].vocab, size=n)]


def rows_on_every_rung_serve_generates_streams(built, pool):
    """Rows that start at depths 3, 130 and 300 and grow across rung
    borders (126 to 140, 300 to 390), five over three slots: token for
    token what `generate` draws, with fewer positions read than held."""
    rng = np.random.default_rng(5)
    reqs = [(_prompt(built, rng, n), m)
            for n, m in [(3, 9), (126, 14), (300, 90), (130, 6), (250, 12)]]
    srv = pool(built)
    ids = {srv.submit(p, m): (p, m) for p, m in reqs}
    done = srv.run_until_drained()
    stats = srv.stats()
    assert 0 < stats["decode_context_read"] < stats["decode_context_held"]
    assert {c.id: c.tokens for c in done} == {
        rid: expected(built, p, m) for rid, (p, m) in ids.items()}


def a_row_that_ends_mid_dispatch_stops_holding_the_bound(built, pool):
    """Four steps a dispatch; a row at depth 300 with two tokens left and a
    row at depth 5: the first two steps read the third rung (384), the
    last two the first (128): the dead row's stale cursor no longer
    counts. Then the shallow row alone: every step the first rung."""
    rng = np.random.default_rng(3)
    srv = pool(built, slots=2)
    srv.submit(_prompt(built, rng, 300), max_new=3)   # one at admission
    srv.submit([7, 8, 9, 10, 11], max_new=12)
    srv.step()                         # an empty pool: admissions alone
    assert srv.stats()["dispatches"] == 0
    srv.step()
    s = srv.stats()
    assert s["dispatches"] == 1
    assert s["decode_context_held"] == 4 * 512 * 2
    assert s["decode_context_read"] == (2 * 384 + 2 * 128) * 2
    srv.step()
    s = srv.stats()
    assert s["decode_context_read"] == (2 * 384 + 6 * 128) * 2
    done = srv.run_until_drained()
    assert sorted(len(c.tokens) for c in done) == [17, 303]


def a_slot_reused_after_a_deep_request_serves_a_fresh_pools_stream(built,
                                                                   pool):
    """A dead slot is handed cursor 0, so the step writes its discarded K/V
    at position 0 of that slot and steps its state from there, beside a
    live row, for three dispatches. The next request admitted there draws
    what a fresh pool draws: the splice landed every leaf of the slot."""
    rng = np.random.default_rng(9)
    deep, other, late = (_prompt(built, rng, n) for n in (310, 5, 6))
    srv = pool(built, slots=2)
    rid_deep = srv.submit(deep, max_new=10)
    rid_other = srv.submit(other, max_new=60)
    done = {}
    while rid_deep not in done:                   # the deep row retires...
        srv.step()
        done.update((c.id, c) for c in srv.poll())
    for _ in range(3):                            # ...its slot idles, dead,
        srv.step()                                # beside a live row
    rid_late = srv.submit(late, max_new=20)       # and is taken again
    done.update((c.id, c) for c in srv.run_until_drained())
    for rid, (p, m) in {rid_deep: (deep, 10), rid_other: (other, 60),
                        rid_late: (late, 20)}.items():
        assert done[rid].tokens == expected(built, p, m)
    fresh = pool(built, slots=2)
    fresh.submit(late, max_new=20)
    assert fresh.run_until_drained()[0].tokens == done[rid_late].tokens


def a_row_at_the_end_of_the_cache_reads_all_of_it(built, pool):
    """A pool of short rows reads the first rung of four; one row at
    ``max_len - decode_steps`` puts every step on the last: 1.0."""
    srv = pool(built)
    for n in (3, 5, 8):
        srv.submit(list(range(1, n + 1)), max_new=9)
    srv.run_until_drained()
    s = srv.stats()
    assert s["decode_context_read"] / s["decode_context_held"] == 0.25
    srv = pool(built, prompt_len=507, prompt_buckets=(8, 507))
    srv.submit(_prompt(built, np.random.default_rng(1), 507), max_new=5)
    srv.run_until_drained()
    s = srv.stats()
    assert s["dispatches"] == 1
    assert s["decode_context_read"] == s["decode_context_held"] > 0


CASES = [rows_on_every_rung_serve_generates_streams,
         a_row_that_ends_mid_dispatch_stops_holding_the_bound,
         a_slot_reused_after_a_deep_request_serves_a_fresh_pools_stream,
         a_row_at_the_end_of_the_cache_reads_all_of_it]
