"""QoS admission gateway (`serve/gateway.py` + `serve/admission.py`).

Policy units run against an injected fake clock — quotas, EDF, weighted
fair queueing, backpressure and expiry are all deterministic, no
wall-clock sleeps (fast lane). The integration test drives a REAL
`DecodeServer` through `LMServingLoop` at overload and holds the serving
tier's standing oracle: every ADMITTED request's token stream is exact
vs standalone `engine.generate`, while batch traffic takes the sheds.
"""
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idunno_tpu.serve.admission import (
    AdmissionShed, BackpressureConfig, shed_reason)
from idunno_tpu.serve.gateway import AdmissionGateway, TokenBucket


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class TickingClock:
    """Every reading is 2 ms after the last, whichever thread makes it:
    a deadline of 1 ms has passed by any reading after the one that set
    it."""

    def __init__(self):
        self._n = itertools.count()        # next() is atomic in CPython

    def __call__(self) -> float:
        return 0.002 * next(self._n)


def gw(spec=None, clock=None) -> AdmissionGateway:
    return AdmissionGateway(spec, clock=clock or FakeClock())


# -- token bucket ---------------------------------------------------------

def test_token_bucket_refill():
    b = TokenBucket(rate=1.0, burst=2.0, now=0.0)
    assert b.try_take(0.0) and b.try_take(0.0)
    assert not b.try_take(0.0), "burst exhausted"
    assert not b.try_take(0.5), "half a token is not a token"
    assert b.try_take(1.5), "1 token refilled after 1s at rate 1"
    assert not b.try_take(1.5)


def test_token_bucket_unlimited_and_zero_rate():
    assert all(TokenBucket(None, 1.0, 0.0).try_take(t) for t in range(5))
    b = TokenBucket(0.0, 3.0, 0.0)   # rate 0: the burst is the whole budget
    assert [b.try_take(1e9) for _ in range(4)] == [True, True, True, False]


# -- admission policy -----------------------------------------------------

def test_quota_shed_and_counters():
    g = gw({"tenants": {"t": {"rate": 0, "burst": 2}}})
    g.admit(0, "a", tenant="t")
    g.admit(1, "b", tenant="t")
    with pytest.raises(AdmissionShed) as ei:
        g.admit(2, "c", tenant="t")
    assert ei.value.reason == "quota"
    g.admit(3, "d", tenant="other")   # default quota is unlimited
    s = g.stats()
    assert s["classes"]["interactive"]["shed"]["quota"] == 1
    assert s["tenants"]["t"] == dict(
        admitted=2, dispatched=0, shed=1, expired=0, queued=2,
        rate=0.0, burst=2.0, weight=1.0)
    assert s["recent_sheds"][-1]["reason"] == "quota"


def test_queue_full_shed():
    g = gw({"max_queue": 2})
    g.admit(0, "a")
    g.admit(1, "b", priority="batch")
    with pytest.raises(AdmissionShed) as ei:
        g.admit(2, "c")
    assert ei.value.reason == "queue_full"
    assert g.queued() == 2


def test_backpressure_thresholds():
    bp = BackpressureConfig()    # slacks 2.0 / 4.0, kv floor 1/8
    g4 = {"slots": 4, "live": 4}
    assert bp.pressure_reason("batch", dict(g4, waiting=7)) is None
    assert "slack" in bp.pressure_reason("batch", dict(g4, waiting=8))
    assert bp.pressure_reason("interactive", dict(g4, waiting=15)) is None
    assert "slack" in bp.pressure_reason("interactive", dict(g4, waiting=16))
    # KV floor binds batch only, and only on paged pools (total > 0)
    kv = {"slots": 4, "live": 0, "waiting": 0,
          "kv_blocks_total": 16, "kv_blocks_free": 1}
    assert "KV blocks" in bp.pressure_reason("batch", kv)
    assert bp.pressure_reason("interactive", kv) is None
    assert bp.pressure_reason("batch", dict(kv, kv_blocks_free=2)) is None
    assert bp.pressure_reason("batch", dict(kv, kv_blocks_total=0)) is None


def test_backpressure_counts_gateway_queue():
    """The gateway's own queue depth is part of the backlog: admissions
    the loop has not yet taken must push toward the shed threshold."""
    g = gw()   # batch slack 2.0: sheds at backlog >= slots * 3
    gauges = {"slots": 1, "live": 1, "waiting": 1}
    g.admit(0, "a", priority="batch", pool_gauges=gauges)   # backlog 2
    with pytest.raises(AdmissionShed) as ei:                # backlog 3
        g.admit(1, "b", priority="batch", pool_gauges=gauges)
    assert ei.value.reason == "backpressure"


def test_readmit_bypasses_quota_queue_and_pressure():
    g = gw({"max_queue": 1, "tenants": {"t": {"rate": 0, "burst": 1}}})
    g.admit(0, "a", tenant="t")
    with pytest.raises(AdmissionShed):
        g.admit(1, "b", tenant="t")
    g.admit(2, "c", tenant="t", readmit=True,
            pool_gauges={"slots": 1, "live": 99, "waiting": 99})
    assert g.queued() == 2


def test_bad_inputs():
    with pytest.raises(ValueError, match="priority"):
        gw().admit(0, "a", priority="urgent")
    with pytest.raises(ValueError, match="deadline_ms"):
        gw().admit(0, "a", deadline_ms=0)
    with pytest.raises(ValueError, match="unknown gateway spec"):
        AdmissionGateway.validate_spec({"quotas": {}})
    with pytest.raises(ValueError, match="burst"):
        AdmissionGateway.validate_spec({"default": {"burst": 0.5}})
    with pytest.raises(ValueError, match="max_queue"):
        AdmissionGateway.validate_spec({"max_queue": 0})
    assert AdmissionGateway.validate_spec(True) == {}
    assert AdmissionGateway.validate_spec(None) == {}


# -- dispatch order -------------------------------------------------------

def test_interactive_dispatches_before_batch_regardless_of_deadline():
    g = gw()
    g.admit(0, "b", priority="batch", deadline_ms=50.0)
    g.admit(1, "i", priority="interactive")
    ready, expired = g.take(1)
    assert [e.rid for e in ready] == [1] and not expired


def test_edf_within_class():
    g = gw()
    g.admit(0, "late", deadline_ms=5000.0)
    g.admit(1, "none")                      # undeadlined sorts last
    g.admit(2, "soon", deadline_ms=1000.0)
    ready, _ = g.take(3)
    assert [e.rid for e in ready] == [2, 0, 1]


def test_wfq_weights_interleave():
    """Start-time fair tags: a weight-2 tenant pays 0.5 virtual time per
    request, weight-1 pays 1.0 — dispatch interleaves ~2:1 even though
    every heavy request arrived before any light one."""
    roomy = {"slots": 64, "live": 0, "waiting": 0}
    g = gw({"tenants": {"heavy": {"weight": 2.0},
                        "light": {"weight": 1.0}}})
    for i in range(6):
        g.admit(i, f"h{i}", tenant="heavy", pool_gauges=roomy)
    for i in range(6, 9):
        g.admit(i, f"l{i}", tenant="light", pool_gauges=roomy)
    order = [e.tenant for e in g.take(9)[0]]
    assert order == ["heavy", "heavy", "light"] * 3


def test_wfq_vt_advance_no_starvation():
    """A light tenant arriving AFTER the class virtual time advanced must
    not owe the past: its start tag is max(vt, its last finish tag)."""
    roomy = {"slots": 64, "live": 0, "waiting": 0}
    g = gw({"tenants": {"heavy": {"weight": 4.0}}})
    for i in range(8):
        g.admit(i, "h", tenant="heavy", pool_gauges=roomy)
    assert len(g.take(8)[0]) == 8           # vt advances to 2.0
    g.admit(8, "h", tenant="heavy", pool_gauges=roomy)
    g.admit(9, "l", tenant="light",         # fresh tenant, ft = vt + 1.0
            pool_gauges=roomy)
    order = [e.rid for e in g.take(2)[0]]
    assert order == [8, 9], "late-arriving tenant dispatches this round"


def test_expiry_returned_regardless_of_budget():
    clk = FakeClock()
    g = gw(clock=clk)
    g.admit(0, "dies", deadline_ms=100.0)
    g.admit(1, "lives")
    clk.advance(0.2)
    ready, expired = g.take(0)              # zero budget still expires
    assert not ready and [e.rid for e in expired] == [0]
    ready, expired = g.take(4)
    assert [e.rid for e in ready] == [1] and not expired
    s = g.stats()["classes"]["interactive"]
    assert s["expired"] == 1
    assert s["reject_rate"] == pytest.approx(0.5)   # 1 of 2 submitted


def test_cancel_and_drain():
    g = gw()
    g.admit(0, "a")
    g.admit(1, "b")
    e = g.cancel(0)
    assert e is not None and e.rid == 0
    assert g.cancel(0) is None, "cancel is idempotent"
    assert [e.rid for e in g.drain()] == [1]
    assert g.queued() == 0 and g.take(4) == ([], [])


def test_queue_wait_percentiles():
    clk = FakeClock()
    g = gw(clock=clk)
    for i in range(4):
        g.admit(i, "x")
    clk.advance(2.0)
    assert len(g.take(4)[0]) == 4
    w = g.stats()["classes"]["interactive"]["queue_wait_s"]
    assert w["n"] == 4 and w["p50"] == pytest.approx(2.0)
    assert w["p99"] == pytest.approx(2.0)


def test_shed_reason_roundtrip():
    """The typed reason must survive the RPC error-string transport the
    manager journal reads it back from (`serve/lm_manager.py`)."""
    e = AdmissionShed("backpressure", "backlog 9 >= 8")
    assert shed_reason(str(e)) == "backpressure"
    assert shed_reason(f"node n3: {e}") == "backpressure"
    assert shed_reason("slot allocation failed") is None
    assert shed_reason(None) is None


# -- integration: real pool at overload -----------------------------------

VOCAB = 61


@pytest.fixture(scope="module")
def lm():
    from idunno_tpu.models.transformer import TransformerLM
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def test_gateway_pool_overload(lm):
    """2 slots, a 10-request interactive burst (>= 2x what the pool can
    hold), then batch arrivals and a 1 ms-deadline straggler. Batch must
    shed on backpressure, the straggler must expire without decoding, and
    every admitted interactive stream must match standalone generate —
    admission control must never perturb decode."""
    from idunno_tpu.engine.generate import generate
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.serve.lm_pool import LMServingLoop

    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=24)
    loop = LMServingLoop(srv, gateway=AdmissionGateway({
        # batch sheds once backlog >= 2 * 1.5 = 3; interactive absorbs
        # the whole burst (threshold 2 * 21 = 42)
        "batch_wait_slack": 0.5, "interactive_wait_slack": 20.0,
        "max_queue": 64}, clock=TickingClock()))
    try:
        rng = np.random.default_rng(3)
        want = {}
        for i in range(10):
            prompt = [int(t) for t in rng.integers(0, VOCAB, size=3 + i % 4)]
            rid = loop.submit(prompt, 6 + i % 5, tenant="ivy")
            want[rid] = (prompt, 6 + i % 5)

        # >= 10 requests outstanding (first retirement is many decode
        # steps away), far past batch's threshold of 3
        sheds = 0
        for _ in range(3):
            with pytest.raises(AdmissionShed) as ei:
                loop.submit([1, 2, 3], 4, tenant="bulk", priority="batch")
            assert ei.value.reason == "backpressure"
            sheds += 1

        # its deadline sorts it ahead of the whole burst, so whether the
        # loop's next drain has room for it must not matter: on the
        # gateway's clock the 1 ms have passed by that drain's own reading
        dead_prompt = [7, 8, 9]
        dead_rid = loop.submit(dead_prompt, 5, deadline_ms=1.0)

        done = {}
        deadline = time.monotonic() + 120.0
        while len(done) < len(want) + 1 and time.monotonic() < deadline:
            for c in loop.poll():
                done[c.id] = c
            time.sleep(0.01)
        assert len(done) == len(want) + 1, f"drained {sorted(done)}"

        exp = done.pop(dead_rid)
        assert exp.rejected == "expired"
        assert exp.tokens == dead_prompt, "expired request never decoded"

        for rid, (prompt, max_new) in want.items():
            ref = generate(model, params, jnp.asarray([prompt], jnp.int32),
                           prompt_len=len(prompt), max_new=max_new)
            assert done[rid].rejected is None
            assert done[rid].tokens == [int(t) for t in np.asarray(ref[0])], \
                f"request {rid} diverged from standalone generate"

        s = loop.stats()["gateway"]
        assert s["classes"]["batch"]["shed"]["backpressure"] == sheds
        assert s["classes"]["interactive"]["shed"] == {
            "quota": 0, "queue_full": 0, "backpressure": 0}
        assert s["classes"]["interactive"]["expired"] == 1
        assert s["tenants"]["ivy"]["dispatched"] == len(want)
        assert len(loop.gateway.recent_sheds()) == sheds
        assert loop.errors() == []
    finally:
        loop.stop()


def test_traced_expiry_waterfall_is_fake_clock_exact(lm):
    """Tracing rides the same injected clock as the gateway: a traced
    request that expires in-queue leaves a waterfall whose offsets are
    exact fake-clock arithmetic — admission at 0 ms, expiry at precisely
    the 600 ms we advanced, nothing timed by the wall clock."""
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.serve.lm_pool import LMServingLoop
    from idunno_tpu.utils.spans import SpanStore
    from tools.trace_export import waterfall

    model, params = lm
    clk = FakeClock(200.0)
    spans = SpanStore("q0", clock=clk)
    srv = DecodeServer(model, params, slots=1, prompt_len=8, max_len=256)
    loop = LMServingLoop(srv, gateway=AdmissionGateway(
        {"batch_wait_slack": 50.0}, clock=clk), spans=spans)
    try:
        # two fillers occupy the slot and the server queue: the dispatch
        # budget (2*slots - pending) pins at 0, so the traced batch
        # request waits in the gateway until its deadline passes
        loop.submit([1, 2, 3], 200)
        loop.submit([4, 5, 6], 200)
        root = spans.start("client.lm_submit")
        rid = loop.submit([7, 8, 9], 5, priority="batch",
                          deadline_ms=500.0, trace=root.ctx)
        clk.advance(0.6)                 # past the deadline — fake time
        done = {}
        deadline = time.monotonic() + 60.0
        while rid not in done and time.monotonic() < deadline:
            for c in loop.poll():
                done[c.id] = c
            time.sleep(0.005)
        assert done[rid].rejected == "expired"
        spans.finish(root)

        raw = spans.dump(trace_id=root.trace_id)
        by_name = {s["name"]: s for s in raw}
        assert set(by_name) == {"client.lm_submit", "lm.admit", "lm.expire"}
        assert by_name["lm.admit"]["parent"] == root.span_id
        assert by_name["lm.expire"]["parent"] \
            == by_name["lm.admit"]["span_id"]
        wf = waterfall(root.trace_id, raw)
        rows = {r["name"]: r for r in wf["rows"]}
        assert rows["lm.admit"]["offset_ms"] == 0.0
        assert rows["lm.admit"]["ms"] == 0.0
        assert rows["lm.expire"]["offset_ms"] == 600.0
        assert rows["lm.expire"]["ms"] == 0.0
        assert rows["client.lm_submit"]["ms"] == 600.0
        assert wf["duration_ms"] == 600.0
        assert rows["lm.expire"]["attrs"]["reason"] == "expired"
    finally:
        loop.stop()


def test_handoff_waterfall_is_fake_clock_exact(lm):
    """ISSUE 18: the DistServe handoff hops span under the client context
    on the same injected clock — export on the prefill replica at the
    +100 ms we advanced, adopt on the decode replica at +350 ms, every
    waterfall offset exact fake-clock arithmetic and every span attr
    equal to the verb's own return values."""
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.utils.spans import SpanStore
    from tools.trace_export import waterfall

    model, params = lm
    clk = FakeClock(300.0)
    spans_p = SpanStore("pf0", clock=clk)
    spans_d = SpanStore("dc0", clock=clk)
    kw = dict(slots=2, prompt_len=8, max_len=24,
              kv_block_size=2, kv_cache_blocks=16)
    pre = DecodeServer(model, params, **kw)
    dec = DecodeServer(model, params, **kw)
    pre.spans, dec.spans = spans_p, spans_d

    prompt = [7, 3, 9, 4, 11, 2, 6, 5]
    root = spans_p.start("client.kv_handoff")
    clk.advance(0.1)
    exp = pre.handoff_export(prompt, from_depth=0, trace=root.ctx)
    clk.advance(0.25)
    got = dec.handoff_adopt(prompt, exp["blobs"], 0, trace=root.ctx)
    clk.advance(0.05)
    spans_p.finish(root)

    raw = (spans_p.dump(trace_id=root.trace_id)
           + spans_d.dump(trace_id=root.trace_id))
    by_name = {s["name"]: s for s in raw}
    assert set(by_name) == {"client.kv_handoff", "lm.handoff_export",
                            "lm.handoff_adopt"}
    ship = by_name["lm.handoff_export"]
    graft = by_name["lm.handoff_adopt"]
    assert ship["parent"] == root.span_id and ship["node"] == "pf0"
    assert graft["parent"] == root.span_id and graft["node"] == "dc0"
    # attrs mirror the verbs' own return values, field for field
    assert exp["blocks"] == 3 and exp["bytes"] > 0
    assert ship["attrs"] == {"blocks": exp["blocks"], "from_depth": 0,
                             "bytes": exp["bytes"]}
    assert graft["attrs"] == {"blocks": got["adopted"],
                              "wrote": got["wrote"], "start_depth": 0,
                              "bytes": got["bytes"],
                              "depth": got["depth"]}
    assert got["depth"] == exp["blocks"], "whole shipped chain grafted"

    wf = waterfall(root.trace_id, raw)
    rows = {r["name"]: r for r in wf["rows"]}
    assert rows["lm.handoff_export"]["offset_ms"] == 100.0
    assert rows["lm.handoff_export"]["ms"] == 0.0
    assert rows["lm.handoff_adopt"]["offset_ms"] == 350.0
    assert rows["lm.handoff_adopt"]["ms"] == 0.0
    assert rows["client.kv_handoff"]["ms"] == 400.0
    assert wf["duration_ms"] == 400.0
    assert wf["nodes"] == ["dc0", "pf0"]
