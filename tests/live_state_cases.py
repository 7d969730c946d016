"""What a pool over a stack with Mamba-2 or Gated DeltaNet layers is held to
now that its decode step moves the live rows' states alone
(`ops/ssd_decode.py`, `models/hybrid.py:_ssd_live` and `_delta_live`): the
slot cache carries `live`, a dispatch whose rows end in its middle and a
slot reused after it went dead serve `engine.generate`'s streams token for
token, and `state_rows_moved` / `state_rows_held` count a slot a layer a
step, as the step moved them (the cache's `state_rows` counters). One body
a case, ``case(built, pool)`` as in `ladder_cases`; run by
`test_hybrid_falcon_h1.py`, `test_hybrid_granite.py` and
`test_hybrid_qwen3_next.py` over their own toy stacks."""
import dataclasses

import jax.numpy as jnp
import numpy as np

from idunno_tpu.models import hybrid
from ladder_cases import _prompt, expected


def state_layers(model) -> int:
    """The stack's Mamba-2 layers, alone or beside attention, and its Gated
    DeltaNet layers."""
    return sum(hybrid.MAMBA in m.split("+") or m == hybrid.DELTA
               for m in model.mixers)


def rows_that_end_mid_dispatch_and_reused_slots_serve_generates_streams(
        built, pool):
    """Five requests over three slots, four steps a dispatch: outputs of
    3, 6, 11, 5 and 8 tokens end at the second, second, third, first and
    fourth step of a dispatch, so the rows beside them step on with dead
    rows in the batch, and the last two requests take slots that were dead
    for a dispatch or more."""
    model, _params = built
    rng = np.random.default_rng(7)
    reqs = [(_prompt(built, rng, n), m)
            for n, m in [(12, 3), (40, 6), (7, 11), (25, 5), (9, 8)]]
    srv = pool(built)
    assert "live" in srv._cache
    ids = {srv.submit(p, m): (p, m) for p, m in reqs}
    done = srv.run_until_drained()
    assert {c.id: c.tokens for c in done} == {
        rid: expected(built, p, m) for rid, (p, m) in ids.items()}
    st = srv.stats()
    assert st["state_rows_moved"] == state_layers(model) * sum(
        m - 1 for _p, m in reqs)
    assert 0 < st["state_rows_moved"] < st["state_rows_held"]


def the_state_counters_count_a_live_row_a_layer_a_step(built, pool):
    """Two slots, four steps a dispatch, L Mamba-2 layers. A row with 3
    tokens to serve (1 from its prefill) and one with 12. Dispatch 1: the
    first row is live in steps 1-2, the second in 1-4: (2 + 4) L moved of
    4 x 2 L held. Dispatch 2: the second row alone, 4 L of 8 L. Dispatch 3:
    its last 3 tokens (12 - 1 - 8), 3 L of 8 L. In all 13 L of 24 L."""
    layers = state_layers(built[0])
    rng = np.random.default_rng(3)
    srv = pool(built, slots=2)
    srv.submit(_prompt(built, rng, 20), max_new=3)
    srv.submit(_prompt(built, rng, 5), max_new=12)
    srv.step()                         # an empty pool: admissions alone
    s = srv.stats()
    assert (s["dispatches"], s["state_rows_moved"],
            s["state_rows_held"]) == (0, 0, 0)
    srv.step()
    s = srv.stats()
    assert s["state_rows_moved"] == (2 + 4) * layers
    assert s["state_rows_held"] == 4 * 2 * layers
    srv.step()
    s = srv.stats()
    assert s["state_rows_moved"] == (2 + 4 + 4) * layers
    assert s["state_rows_held"] == 2 * 4 * 2 * layers
    done = srv.run_until_drained()
    assert sorted(len(c.tokens) - c.prompt_len for c in done) == [3, 12]
    s = srv.stats()
    assert s["dispatches"] == 3
    assert s["state_rows_moved"] == 13 * layers
    assert s["state_rows_held"] == 24 * layers


def the_counters_read_what_the_step_moved(built, pool):
    """One per-row decode step over three slots, the second dead: each
    Mamba-2 (Gated DeltaNet) layer counts 2 rows moved of 3 held where the
    step takes the live rows alone, and 3 of 3 where it is handed no
    `live` leaf and moves every slot's state. The dead row's states are
    the cache's as they came, the live rows' are not."""
    model, params = built
    dec = dataclasses.replace(model, decode=True, decode_per_row=True,
                              max_decode_len=64, interpret=True)
    cache = dec.init_cache(3)
    runs = [k for k in cache if k.startswith("run")]
    for r in runs:
        if "state_rows" in cache[r]:
            cache[r]["state"] = jnp.full(cache[r]["state"].shape, 0.5,
                                         jnp.float32)
    cache["live"] = jnp.asarray([True, False, True])
    tok = jnp.asarray([[5], [9], [7]])
    for drop, want in ((False, [2, 3]), (True, [3, 3])):
        c = {k: v for k, v in cache.items() if not (drop and k == "live")}
        _logits, new = dec.decode_apply(params, c, tok)
        rows = [np.asarray(new[r]["state_rows"]) for r in runs
                if "state_rows" in new[r]]
        assert sum(len(r) for r in rows) == state_layers(model)
        assert all(r.tolist() == [want] * len(r) for r in rows)
        for r in runs:
            if "state_rows" in new[r]:
                same = np.all(np.asarray(new[r]["state"]) == 0.5,
                              axis=(0, 2, 3, 4))
                assert same.tolist() == [False, not drop, False]


CASES = [rows_that_end_mid_dispatch_and_reused_slots_serve_generates_streams,
         the_state_counters_count_a_live_row_a_layer_a_step,
         the_counters_read_what_the_step_moved]
