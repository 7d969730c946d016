"""The decode pool times itself (ISSUE 25): token stamps on `Completion`,
the pool loop's own span timeline, spans and counters in the radix cache
and block pool. Every time asserted here comes from a fake clock."""
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from idunno_tpu.engine.serve_lm import DecodeServer
from idunno_tpu.models.transformer import TransformerLM
from idunno_tpu.serve.lm_pool import LMServingLoop
from idunno_tpu.utils.spans import SpanStore

VOCAB = 61


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


class Manual:
    """A clock that stands still until the test moves it."""

    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float = 1.0) -> float:
        self.t += dt
        return self.t


class Ticking:
    """A clock that moves one second at every reading and remembers them
    all, so the last reading of a call is told from its first."""

    def __init__(self):
        self.seen = [0.0]

    def __call__(self) -> float:
        self.seen.append(self.seen[-1] + 1.0)
        return self.seen[-1]


def _pool(lm, **kw):
    model, params = lm
    args = dict(slots=2, prompt_len=8, max_len=32, decode_steps=4)
    args.update(kw)
    return DecodeServer(model, params, **args)


def _wait(pred, what, timeout=60.0):
    end = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < end, f"timed out waiting for {what}"
        time.sleep(0.002)


def _serve(loop, prompt, max_new, **kw):
    rid = loop.submit(prompt, max_new, **kw)
    got = []
    _wait(lambda: got.extend(c for c in loop.poll() if c.id == rid) or got,
          f"request {rid}")
    return got[0]


# -- A: stamps ---------------------------------------------------------------

def test_stamps_are_the_end_of_the_admitting_and_of_the_retiring_step(lm):
    srv = _pool(lm)
    clk = srv.clock = Ticking()
    stamp = srv._stamp

    def stamp_reads_nothing(early):
        # the stamps come from the copy the last retirement fetched
        assert srv._rc_cache is not None or not srv._live
        stamp(early)
    srv._stamp = stamp_reads_nothing

    rid = srv.submit([1, 2, 3], max_new=9)
    t_submit = clk.seen[-1]
    srv.step()                      # an empty pool: the prefill's token
    end_of_first = clk.seen[-1]
    srv.step()                      # its first dispatch of 4
    assert srv.poll() == []
    srv.step()                      # the other 4: retired
    end_of_third = clk.seen[-1]
    (c,) = srv.poll()
    assert c.id == rid and len(c.tokens) - c.prompt_len == 9
    assert (c.t_first, c.n_first, c.t_last) == (end_of_first, 1,
                                                end_of_third)
    assert c.t_submit == t_submit < c.t_admit < c.t_first
    assert c.ttft_s() == end_of_first - t_submit
    assert c.tpot_s() == (end_of_third - end_of_first) / 8

    # one token: first and last sight in one step, nothing to divide by
    srv.submit([4, 5], max_new=1)
    srv.step()
    (one,) = srv.poll()
    assert one.t_first == one.t_last == clk.seen[-1] and one.n_first == 1
    assert one.tpot_s() is None and one.ttft_s() > 0


def test_a_cancelled_row_keeps_the_stamps_it_had(lm):
    srv = _pool(lm)
    clk = srv.clock = Ticking()
    rid = srv.submit([1, 2, 3], max_new=20)
    srv.step()
    first = clk.seen[-1]
    srv.step()
    srv.step()
    last = clk.seen[-1]
    assert srv.cancel(rid) == "live"
    srv.step()                      # retires it before any dispatch
    (c,) = srv.poll()
    assert c.cancelled and len(c.tokens) - c.prompt_len == 9
    assert (c.t_first, c.n_first, c.t_last) == (first, 1, last)
    assert srv.stats()["dispatches"] == 2
    # cancelled before a slot took it: the submit stamp alone
    srv.submit([1, 2], max_new=4)
    srv.submit([3, 4], max_new=4)
    queued = srv.submit([5, 6], max_new=4)
    assert srv.cancel(queued) == "queued"
    (q,) = srv.poll()
    assert q.t_submit is not None and q.t_first is None
    assert q.ttft_s() is None and q.tpot_s() is None


@pytest.mark.parametrize("what, prompt, max_new", [
    ("one-shot", [4, 5], 6),
    ("chunked", [1, 2, 3, 4, 5, 6, 7, 8], 6),
    ("one-token", [4, 5], 1),
])
def test_a_step_enqueues_its_dispatch_first_and_waits_for_the_chip_once(
        lm, what, prompt, max_new):
    """A busy pool and an arrival: the step enqueues the dispatch, then
    the admission's programs behind it, then reads the device back, once."""
    srv = _pool(lm, prompt_buckets=(4, 8), prefill_chunk=4)
    srv.submit([1, 2, 3], max_new=24)
    srv.step()
    srv.step()
    events = []

    def noting(name, inner, when=lambda: True):
        def call(*a, **kw):
            if when():
                events.append(name)
            return inner(*a, **kw)
        return call
    srv._decode = noting("dispatch", srv._decode)
    srv._advance_prefill = noting("chunk", srv._advance_prefill)
    srv._finish_admission = noting("splice", srv._finish_admission)
    srv._remaining_cursors = noting("read", srv._remaining_cursors,
                                    lambda: srv._rc_cache is None)
    rid = srv.submit(prompt, max_new)
    before = srv.stats()
    srv.step()
    after = srv.stats()
    assert after["dispatches"] == before["dispatches"] + 1
    if what == "chunked":
        assert events == ["dispatch", "chunk", "read"]
        assert srv._pending is not None and after["admitted"] == 1
        srv.step()                  # the last chunk and the splice
        assert events[3:] == ["dispatch", "chunk", "splice", "read"]
        after = srv.stats()
    else:
        assert events == ["dispatch", "splice", "read"]
    assert (after["admitted"], after["admissions_overlapped"]) == (2, 1)
    if what == "one-token":         # retired by the step's one read
        (c,) = srv.poll()
        assert c.id == rid and (c.n_first, c.t_first) == (1, c.t_last)
    else:
        assert srv.poll() == [] and len(srv._live) == 2
        new = next(r for r in srv._live.values() if r.id == rid)
        assert new.n_first == 1 and new.dispatch0 == after["dispatches"]


def test_stamps_equal_the_benchmarks_step_clock(lm, monkeypatch):
    """`benchmark.timing.StepClock` stamps the same moments from outside
    (it wraps `step` and reads private state); the two agree request by
    request."""
    from benchmark import timing

    srv = _pool(lm)
    clk = srv.clock = Manual()
    monkeypatch.setattr(timing, "clock", clk)
    sc = timing.StepClock(srv)
    plan = {0: [(3, 9), (5, 1)], 1: [(8, 14)], 2: [(2, 3), (4, 6)],
            5: [(6, 5)]}
    done, want = {}, 0
    for i in range(40):
        for n, max_new in plan.get(i, ()):
            srv.submit(list(range(1, n + 1)), max_new)
            want += 1
        clk.advance(0.125)
        srv.step()
        done.update((c.id, c) for c in srv.poll())
    sc.remove()
    assert len(done) == want == 6
    times = timing.request_times(sc.steps)
    for rid, c in done.items():
        t = times[rid]
        assert (c.t_first, c.n_first, c.t_last) == (
            t["t_first"], t["n_first"], t["t_last"]), rid
        assert len(c.tokens) - c.prompt_len == t["n"]
        assert c.t_admit == t["t_admit"]


def test_lm_poll_carries_durations_not_times(lm):
    from idunno_tpu.serve.control import ControlService

    class T:
        def serve(self, *_a, **_k):
            pass
    node = type("NodeStub", (), {})()
    node.host, node.transport = "n0", T()
    ctl = ControlService(node)
    loop = LMServingLoop(_pool(lm), name="p")
    ctl._lm_loops["p"] = loop
    try:
        one = loop.submit([1, 2, 3], 1)
        many = loop.submit([4, 5, 6], 6)
        got = {}
        _wait(lambda: got.update(
            (c["id"], c) for c in ctl._dispatch(
                "lm_poll", {"name": "p"})["completions"])
            or len(got) == 2, "both completions")
    finally:
        loop.stop()
    assert got[one]["tpot_s"] is None and got[one]["ttft_s"] >= 0.0
    assert got[many]["tpot_s"] > 0.0 and got[many]["ttft_s"] > 0.0
    assert got[many]["service_s"] > 0.0
    assert not any(k.startswith("t_") or k == "n_first"
                   for c in got.values() for k in c)


# -- B: the loop's own timeline ----------------------------------------------

def _loop_spans(store, loop):
    return store.dump(trace_id=loop.loop_trace)


def test_loop_iterations_tile_and_children_lie_inside_parents(lm):
    srv = _pool(lm)
    reads = []
    inner = srv._remaining_cursors

    def counted():
        if srv._rc_cache is None:
            reads.append(1)
        return inner()
    srv._remaining_cursors = counted
    store = SpanStore("n0", clock=Ticking(), capacity=1 << 16)
    loop = LMServingLoop(srv, name="n0-tile", spans=store)
    assert loop.loop_trace == "t:n0:loop:tile"
    try:
        for n in (9, 1, 6):
            _serve(loop, [1, 2, 3], n)

        def turned():
            loop._wake.set()        # an idle turn need not last 0.5 s
            return sum(s["name"] == "loop.iter"
                       for s in _loop_spans(store, loop)) >= 50
        _wait(turned, "50 iterations")
    finally:
        loop.stop()
    spans = _loop_spans(store, loop)
    iters = [s for s in spans if s["name"] == "loop.iter"]
    assert len(iters) >= 50
    for a, b in zip(iters, iters[1:]):
        assert b["t_start"] == a["t_end"], "the next starts where one ended"
    assert sum(s["t_end"] - s["t_start"] for s in iters) \
        == iters[-1]["t_end"] - iters[0]["t_start"]
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        assert s["span_id"].startswith("n0:loop."), "ids of their own lane"
        if s["parent"] is not None:
            p = by_id[s["parent"]]
            assert p["t_start"] <= s["t_start"] <= s["t_end"] <= p["t_end"], \
                (s["name"], p["name"])
    parents = {"loop.drain": "loop.iter", "lm.step": "loop.iter",
               "loop.publish": "loop.iter", "loop.idle_wait": "loop.iter",
               "lm.decode_step": "lm.step", "lm.step.sync": "lm.step",
               "lm.readback": "lm.step", "lm.count": "lm.step",
               "lm.retire": "lm.step", "lm.stamp": "lm.step",
               "lm.step.admit": "lm.step"}
    assert {s["name"] for s in spans} == set(parents) | {"loop.iter"}
    for s in spans:
        if s["name"] != "loop.iter":
            assert by_id[s["parent"]]["name"] == parents[s["name"]]
    # a sync span wherever the host waited for the chip, and only there
    syncs = [s for s in spans if s["name"] == "lm.step.sync"]
    steps = [s for s in spans if s["name"] == "lm.step"]
    dispatches = [s for s in spans if s["name"] == "lm.decode_step"]
    assert len(syncs) == len(reads) > 0
    assert len(dispatches) == srv.stats()["dispatches"] < len(steps)
    assert sum(s["attrs"]["admitted"] for s in steps) == 3
    assert sum(s["attrs"]["retired"] for s in steps) == 3
    assert sum(s["attrs"]["taken"] for s in spans
               if s["name"] == "loop.drain") == 3


def _request_lane(n_idle, lm):
    store = SpanStore("n0", clock=Manual())
    loop = LMServingLoop(_pool(lm, kv_block_size=2, kv_cache_blocks=16),
                         name="seeded", spans=store)
    try:
        for i, n in enumerate((9, 1, 6)):
            root = store.start("client.lm_submit")
            _serve(loop, [1 + i, 2, 3, 4, 5], n, trace=root.ctx)
            store.finish(root)
            seen = len(_loop_spans(store, loop))

            def idled():
                loop._wake.set()
                return sum(s["name"] == "loop.idle_wait" for s in
                           _loop_spans(store, loop)[seen:]) >= n_idle
            _wait(idled, f"{n_idle} idle turns")
            # the loop sleeps again before the next submit races it
            _wait(lambda: not loop._wake.is_set(), "the loop to wait")
    finally:
        loop.stop()
    return [(s["trace_id"], s["span_id"], s["parent"], s["name"])
            for s in store.dump() if s["trace_id"] != loop.loop_trace]


def test_request_span_ids_do_not_depend_on_idle_iterations(lm):
    few, many = _request_lane(1, lm), _request_lane(20, lm)
    assert few == many
    names = {n for _t, _s, _p, n in few}
    assert {"lm.admit", "lm.queue_wait", "lm.slot_wait", "lm.prefill",
            "kv.lookup", "kv.insert", "lm.decode", "lm.finish"} <= names
    assert not any(sid.startswith("n0:loop.") for _t, sid, _p, _n in few)


def test_slot_wait_covers_the_steps_spent_in_the_queue(lm):
    srv = _pool(lm, slots=1)
    store = SpanStore("n0", clock=Manual(0.0))
    srv.spans, srv.clock = store, store.clock
    a = srv.submit([1, 2, 3], 9, trace=("t:a", "root"))
    b = srv.submit([4, 5, 6], 5, trace=("t:b", "root"))
    # A: admitted at 1, dispatched at 2 and 3, where it retires once the
    # dispatch is read back: the slot is free for B from 4 on
    for _ in range(5):
        store.clock.advance(1.0)
        srv.step()
    assert {c.id for c in srv.poll()} == {a, b}
    waits = {s["attrs"]["id"]: (s["t_start"], s["t_end"], s["parent"])
             for s in store.dump() if s["name"] == "lm.slot_wait"}
    assert waits == {a: (0.0, 1.0, "root"), b: (0.0, 4.0, "root")}
    decodes = {s["attrs"]["id"]: s for s in store.dump()
               if s["name"] == "lm.decode"}
    assert decodes[a]["attrs"] == {"id": a, "tokens": 9, "steps": 2,
                                   "t_first": 1.0, "n_first": 1}
    assert (decodes[a]["t_start"], decodes[a]["t_end"]) == (2.0, 3.0)
    assert decodes[b]["attrs"]["steps"] == 1
    assert (decodes[b]["t_start"], decodes[b]["t_end"]) == (5.0, 5.0)
    prefills = {s["attrs"]["id"]: s for s in store.dump()
                if s["name"] == "lm.prefill"}
    assert decodes[b]["parent"] == prefills[b]["span_id"]
    # driven bare, the steps root the pool's timeline themselves
    bare = store.dump(trace_id="t:n0:loop:bare")
    steps = [s for s in bare if s["name"] == "lm.step"]
    assert len(steps) == 5 and all(s["parent"] is None for s in steps)
    assert prefills[b]["attrs"]["step"] == steps[3]["span_id"]


def test_no_store_no_span_and_no_profiler_annotation(lm, monkeypatch):
    entered = []

    class Counting:
        def __init__(self, name, **_kw):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *_exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    loop = LMServingLoop(_pool(lm), name="dark")
    try:
        c = _serve(loop, [1, 2, 3], 6, trace=("t:x", "root"))
    finally:
        loop.stop()
    assert c.t_first is not None, "the stamps need no store"
    assert entered == [] and loop.loop_trace is None
    assert loop.server.step_ctx is None
    store = SpanStore("n0")
    lit = LMServingLoop(_pool(lm), name="lit", spans=store)
    try:
        _serve(lit, [1, 2, 3], 6)
    finally:
        lit.stop()
    assert {"loop.iter", "lm.step", "lm.decode_step", "lm.step.sync"} \
        <= set(entered)
    assert set(entered) == {s["name"] for s in _loop_spans(store, lit)}


def test_spans_module_needs_no_jax():
    code = ("import sys, idunno_tpu.utils.spans as s; "
            "s.SpanStore('n').record('x', lane='loop'); "
            "assert 'jax' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_lanes_have_their_own_ids_and_ring():
    store = SpanStore("n0", clock=Manual(), capacity=4)
    req = store.record("lm.admit")
    for _ in range(10):
        store.record("loop.iter", trace="t:n0:loop:p", lane="loop")
    assert store.record("lm.finish", trace=req.trace_id).span_id == "n0:3"
    assert [s["span_id"] for s in store.dump(trace_id="t:n0:loop:p")] \
        == [f"n0:loop.{i}" for i in (7, 8, 9, 10)]
    assert [s["name"] for s in store.dump()][:2] == ["lm.admit", "lm.finish"]
    assert store.depth() == 6 and store.recorded_total() == 12
    assert "lane" not in store.dump()[0]


# -- C: counters where the work happens --------------------------------------

def test_kv_insert_spans_and_counters_agree_under_eviction(lm):
    srv = _pool(lm, slots=1, kv_block_size=2, kv_cache_blocks=8)
    store = SpanStore("n0", clock=Manual())
    srv.spans, srv.clock = store, store.clock
    rx = srv._radix
    sizes, evict = [], rx._evict_one

    def sized():
        sizes.append(rx.num_nodes())
        return evict()
    rx._evict_one = sized
    prompts = [[10 * i + j for j in range(1, 9)] for i in range(1, 4)]
    prompts.append(prompts[2][:4] + [50, 51, 52, 53])   # shares two blocks
    for i, p in enumerate(prompts):
        srv.submit(p, 2, trace=(f"t:{i}", "root"))
        srv.run_until_drained()
    pc = srv.prefix_cache_stats()
    # the walk visits the whole tree for every block it frees
    assert pc["evictions"] == len(sizes) == 6
    assert pc["evict_nodes_walked"] == sum(sizes) == 6 * 8
    assert pc["blocks_written"] == pc["inserted_blocks"] == 14
    assert pc["blocks_gathered"] == 2
    inserts = [s["attrs"] for s in store.dump() if s["name"] == "kv.insert"]
    assert [a["blocks_written"] for a in inserts] == [4, 4, 4, 2]
    assert [a["evicted"] for a in inserts] == [0, 0, 4, 2]
    assert [a["nodes_walked"] for a in inserts] == [0, 0, 32, 16]
    (gather,) = [s for s in store.dump() if s["name"] == "kv.gather"]
    assert gather["attrs"] == {"blocks": 2}
    lookups = [s["attrs"]["blocks_hit"] for s in store.dump()
               if s["name"] == "kv.lookup"]
    assert lookups == [0, 0, 0, 2]
    prefills = {s["span_id"] for s in store.dump()
                if s["name"] == "lm.prefill"}
    assert all(s["parent"] in prefills for s in store.dump()
               if s["name"].startswith("kv."))
    srv.warmup()
    pc = srv.prefix_cache_stats()
    assert pc["evict_nodes_walked"] == pc["blocks_gathered"] == 0
