"""Profiler trace utilities."""
import os

import jax
import jax.numpy as jnp

from idunno_tpu.utils.tracing import trace


def test_trace_writes_profile(tmp_path):
    log_dir = str(tmp_path / "prof")
    with trace(log_dir):
        with jax.profiler.TraceAnnotation("matmul-region"):
            x = jnp.ones((128, 128))
            jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
    found = []
    for root, _, files in os.walk(log_dir):
        found.extend(f for f in files if f.endswith((".pb", ".xplane.pb",
                                                     ".json.gz", ".trace")))
    assert found, f"no trace artifacts under {log_dir}"


def test_profile_control_verb(tmp_path):
    """The `profile` RPC captures a trace of whatever the node runs during
    the window, into a caller-chosen (or node-local default) directory."""
    import threading

    import jax.numpy as jnp
    import pytest

    from idunno_tpu.serve.control import ControlService

    class T:
        def serve(self, *_a, **_k):
            pass
    node = type("NodeStub", (), {})()
    node.host, node.transport = "n0", T()
    ctl = ControlService(node)

    # keep the device busy during the window so the trace has content
    stop = threading.Event()

    def busy():
        x = jnp.ones((64, 64))
        while not stop.is_set():
            (x @ x).block_until_ready()
    t = threading.Thread(target=busy, daemon=True)
    t.start()
    try:
        log_dir = str(tmp_path / "prof")
        out = ctl._dispatch("profile", {"seconds": 0.5, "log_dir": log_dir})
        assert out == {"log_dir": log_dir, "seconds": 0.5}
        found = any(fn for _, _, files in __import__("os").walk(log_dir)
                    for fn in files)
        assert found, f"no trace artifacts under {log_dir}"
        with pytest.raises(ValueError, match="seconds"):
            ctl._dispatch("profile", {"seconds": 0})
    finally:
        stop.set()
        t.join(timeout=5)


# == distributed request tracing (utils/spans.py, ISSUE 6) ================
#
# Spans ride verb payloads next to the epoch stamp; per-node ring buffers
# record every hop; the `trace` control verb collects a request's spans
# cluster-wide. The chaos-backed tests below certify the two properties
# logs cannot give: one trace across a transport RETRY (the dedup hop is
# visible) and across a FAILOVER ADOPTION (the journal carries the ctx to
# the new owner).

import json as _json
import logging as _logging
import time as _time

import pytest

from idunno_tpu.utils.spans import (
    SpanStore, current, push_ctx, stamp_trace, trace_from_payload)


class _Clock:
    """Recording fake clock: every value it ever returned is in `seen`,
    so a test can prove a span's timestamps came from THIS clock."""

    def __init__(self, t: float):
        self.t = t
        self.seen = {t}

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t = round(self.t + dt, 6)
        self.seen.add(self.t)


def test_span_store_ids_deterministic_and_ring_bounded():
    clk = _Clock(10.0)
    s = SpanStore("nX", clock=clk, capacity=4)
    root = s.start("a")
    assert (root.trace_id, root.span_id) == ("t:nX:1", "nX:2")
    assert s.depth() == 0, "open spans are not in the buffer yet"
    clk.advance(0.5)
    s.finish(root, ok=True)
    assert s.dump() == [{
        "trace_id": "t:nX:1", "span_id": "nX:2", "parent": None,
        "name": "a", "node": "nX", "t_start": 10.0, "t_end": 10.5,
        "attrs": {"ok": True}}]
    for i in range(6):
        s.record("spin", trace=root.trace_id, parent=root.span_id)
    assert s.depth() == 4, "ring bounded at capacity"
    assert s.recorded_total() == 7, "lifetime count survives eviction"
    assert s.dump(trace_id="t:other") == []
    assert len(s.dump(limit=2)) == 2
    # a second store never collides: the node name prefixes every id
    assert SpanStore("nY", clock=clk).start("b").span_id.startswith("nY:")


def test_stamp_roundtrip_and_thread_local_ctx():
    p = {"verb": "x"}
    assert trace_from_payload(p) is None, "unstamped payload -> no ctx"
    assert stamp_trace(p, None) is p and "trace" not in p
    stamp_trace(p, ("t:n0:1", "n0:2"))
    assert trace_from_payload(p) == ("t:n0:1", "n0:2")
    assert trace_from_payload({"trace": [None, "x"]}) is None
    assert current() is None
    with push_ctx("t:n0:1", "n0:2"):
        assert current() == ("t:n0:1", "n0:2")
    assert current() is None
    s = SpanStore("n0")
    with s.span("scoped") as sp:
        assert current() == sp.ctx
    assert current() is None and s.depth() == 1


def test_json_log_formatter_tags_node_epoch_and_trace():
    """Satellite: the opt-in JSON-lines formatter cross-links log records
    to the active span via the spans thread-local."""
    from idunno_tpu.utils.logging import JsonLineFormatter

    fmt = JsonLineFormatter("n7", epoch_fn=lambda: 3)
    logger = _logging.getLogger("idunno_tpu.test.jsonl")
    rec = logger.makeRecord("idunno.n7.lm_pool", _logging.WARNING,
                            __file__, 1, "queue %d deep", (9,), None)
    with push_ctx("t:n7:1", "n7:2"):
        line = fmt.format(rec)
    d = _json.loads(line)
    assert d["node"] == "n7" and d["component"] == "lm_pool"
    assert d["level"] == "WARNING" and d["msg"] == "queue 9 deep"
    assert d["epoch"] == 3
    assert d["trace_id"] == "t:n7:1" and d["span_id"] == "n7:2"
    # outside any span: no trace keys, and a crashing epoch_fn is dropped
    bad = JsonLineFormatter("n7", epoch_fn=lambda: 1 / 0)
    d2 = _json.loads(bad.format(rec))
    assert "trace_id" not in d2 and "epoch" not in d2


def test_trace_export_and_metrics_scrape_selftests():
    """The CLI selftests double as unit tests: Perfetto round-trip is
    exact, Prometheus exposition is well-formed (fast lane, no network)."""
    from tools.metrics_scrape import selftest as scrape_selftest
    from tools.trace_export import selftest as export_selftest

    out = export_selftest()
    assert out["selftest"] == "ok" and out["spans"] == 4
    out = scrape_selftest()
    assert out["selftest"] == "ok" and out["series"] >= 10


def test_retry_counters_and_exhaustion():
    """Satellite: comm/retry.py attempts/exhaustion are counted, not just
    logged (PR-5 left them log-only)."""
    from idunno_tpu.comm.retry import (
        TransportError, call_with_retry, reset_retry_counters,
        retry_counters)

    reset_retry_counters()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransportError("connection refused", reason="refused")
        return "ok"

    assert call_with_retry(flaky, attempts=5, base_s=0.0, cap_s=0.0,
                           sleep=lambda s: None) == "ok"
    with pytest.raises(TransportError):
        call_with_retry(lambda: (_ for _ in ()).throw(
            TransportError("boom", reason="refused")),
            attempts=2, base_s=0.0, cap_s=0.0, sleep=lambda s: None)
    c = retry_counters()
    assert c["retry_attempts"] == 3, c
    assert c["retry_exhausted"] == 1, c
    reset_retry_counters()
    assert retry_counters() == {"retry_attempts": 0, "retry_exhausted": 0,
                                "hedged_rpcs": 0, "hedge_wins": 0}


def test_call_hedged_win_loss_merge_and_error_paths():
    """ISSUE 20: the tail-hedged read primitive. A slow primary loses to
    the hedged backup (hedge_wins counts), a fast primary never hedges,
    the loser's late success still reaches on_late, and an all-fail call
    raises the last error."""
    import threading
    import time

    from idunno_tpu.comm.retry import (
        TransportError, call_hedged, reset_retry_counters, retry_counters)

    # slow primary, fast backup: backup wins, loser merges via on_late
    reset_retry_counters()
    late, got_late = [], threading.Event()

    def slow():
        time.sleep(0.08)
        return "primary"

    out = call_hedged([slow, lambda: "backup"], delay_s=0.01,
                      on_late=lambda r: (late.append(r), got_late.set()))
    assert out == "backup"
    c = retry_counters()
    assert c["hedged_rpcs"] == 1 and c["hedge_wins"] == 1, c
    assert got_late.wait(2.0) and late == ["primary"]

    # fast primary: the hedge never fires, no counters move
    reset_retry_counters()
    assert call_hedged([lambda: "fast", slow], delay_s=0.5) == "fast"
    c = retry_counters()
    assert c["hedged_rpcs"] == 0 and c["hedge_wins"] == 0, c

    # primary errors BEFORE the delay expires: the error surfaces and the
    # backup never fires — hedging defends against slowness; fast
    # failures belong to the retry layer (call_with_retry wraps it)
    reset_retry_counters()

    def boom():
        raise TransportError("boom", reason="timeout")

    with pytest.raises(TransportError):
        call_hedged([boom, lambda: "backup"], delay_s=0.5)
    assert retry_counters()["hedged_rpcs"] == 0

    # slow-failing primary: the hedge fires, the backup's success wins
    def slow_boom():
        time.sleep(0.08)
        raise TransportError("late boom", reason="timeout")

    reset_retry_counters()
    assert call_hedged([slow_boom, lambda: "backup"],
                       delay_s=0.01) == "backup"
    c = retry_counters()
    assert c["hedged_rpcs"] == 1 and c["hedge_wins"] == 1, c

    # every thunk fails: the last error surfaces
    with pytest.raises(TransportError):
        call_hedged([boom, boom], delay_s=0.0)

    # degenerate single-thunk call: plain passthrough
    reset_retry_counters()
    assert call_hedged([lambda: 7], delay_s=0.0) == 7
    assert retry_counters()["hedged_rpcs"] == 0


# -- chaos-backed: retry dedup and failover adoption ----------------------

def test_retry_keeps_one_trace_with_duplicate_span_visible(tmp_path):
    """A lost submit ACK forces a transport retry: the SAME stamped trace
    rides both attempts, so the master's window shows two `cnn.schedule`
    spans in one trace — the second marked duplicate by the idempotency
    dedup — while the query books exactly once."""
    from idunno_tpu.chaos import ChaosCluster

    c = ChaosCluster(515, str(tmp_path))
    c.net.lose_next_reply("n2", "n0")
    q = c.services["n2"].submit_query("retry-model", 100, 119)
    subs = [s for s in c.spans["n2"].dump() if s["name"] == "cnn.submit"]
    assert len(subs) == 1 and subs[0]["attrs"]["qnum"] == q
    tid = subs[0]["trace_id"]
    scheds = [s for s in c.spans["n0"].dump(trace_id=tid)
              if s["name"] == "cnn.schedule"]
    assert len(scheds) == 2, "one trace, two attempt spans"
    assert [bool(s["attrs"].get("duplicate")) for s in scheds] \
        == [False, True], "retry hop is duplicate-marked"
    assert scheds[0]["attrs"]["qnum"] == q
    # exactly one booking behind the two spans
    booked = [k for k in c.services["n0"].scheduler.book._by_query
              if k[0] == "retry-model"]
    assert booked == [("retry-model", q)]


def test_trace_survives_failover_adoption(tmp_path):
    """The journaled trace ctx rides standby replication: after the
    coordinator AND the pool's scope owner are isolated, n1 — cluster
    standby and the scope's rendezvous successor — adopts both (epoch
    bump + scoped journal replay), still resolves the old request's
    trace id, records the adoption as a span, and books fresh traced
    submits under ITS node name."""
    from idunno_tpu.chaos import ChaosCluster

    c = ChaosCluster(616, str(tmp_path))
    c.pump_work()
    # register both hand-rolled submits like op_lm would: the chaos
    # delivery-vs-attempted invariant runs at the end of this test
    c.lm_attempted.append({"serial": 0, "prompt": [5, 6, 7],
                           "seed": 5, "max_new": 4})
    c.lm_attempted.append({"serial": 1, "prompt": [8, 8, 8],
                           "seed": 8, "max_new": 4})
    root = c.spans["n3"].start("client.lm_submit")
    out = c._client_control(
        "n3", {"verb": "lm_submit", "name": c.LM_POOL,
               "prompt": [5, 6, 7], "max_new": 4, "seed": 5,
               "trace": [root.trace_id, root.span_id]}, idem="n3:tr1")
    rid = int(out["id"])
    c.spans["n3"].finish(root, rid=rid)
    assert c.managers["n4"].trace_of(c.LM_POOL, rid) == root.trace_id
    c.pump_membership(waves=3)          # ownership claim gossips out
    c.pump_work()                       # journal reaches the standby
    # a second submit lands AFTER the snapshot replication above: its
    # synchronous write-ahead makes pool A's WAL strictly newer than the
    # replicated snapshot, so adoption must REPLAY the pool journal
    # segment (counter asserted below), not just load the snapshot
    c.lm_attempted.append({"serial": 2, "prompt": [9, 9, 9],
                           "seed": 9, "max_new": 4})
    c._client_control("n3", {"verb": "lm_submit", "name": c.LM_POOL,
                             "prompt": [9, 9, 9], "max_new": 4,
                             "seed": 9}, idem="n3:tr3")
    c.op_isolate("n0")                  # deposes the cluster master...
    c.op_isolate("n4")                  # ...and pool A's scope owner
    # push past BOTH suspicion timeouts: the standby's monitor notices
    # n0 fast, peer failure detection of n4 takes a few more waves
    for _ in range(18):
        c.pump_membership(waves=1)
        c.pump_work()
        c.record_fences()
    assert c.members["n1"].is_acting_master
    assert c.members["n1"].epoch.view() == (1, "n1")
    # the adoption itself is a span on the new owner, naming the epoch
    adopts = [s for s in c.spans["n1"].dump()
              if s["name"] == "failover.adopt"]
    assert adopts and adopts[-1]["attrs"]["epoch"] == 1
    assert adopts[-1]["t_end"] is not None
    # the pre-failover request's trace crossed the adoption intact
    assert c.managers["n1"].trace_of(c.LM_POOL, rid) == root.trace_id
    # and a fresh traced submit books on the NEW owner under the client's
    # trace — the waterfall names n1, not the deposed n0
    root2 = c.spans["n3"].start("client.lm_submit")
    out2 = c._client_control(
        "n3", {"verb": "lm_submit", "name": c.LM_POOL,
               "prompt": [8, 8, 8], "max_new": 4, "seed": 8,
               "trace": [root2.trace_id, root2.span_id]}, idem="n3:tr2")
    c.spans["n3"].finish(root2, rid=int(out2["id"]))
    booked = [s for s in c.spans["n1"].dump(trace_id=root2.trace_id)
              if s["name"] == "lm.submit"]
    assert booked and booked[0]["node"] == "n1"
    # ISSUE 14: the per-pool adoption/replay counters land on the new
    # owner's metrics plane and ride the same Prometheus exposition
    text = c.services["n1"].metrics.prometheus_text("n1")
    assert 'idunno_events_total{node="n1",name="pool_scope_adopted"}' \
        in text
    assert 'idunno_events_total{node="n1",name="pool_wal_replayed"}' \
        in text
    c.converge()
    c.check_invariants()


# -- acceptance: cluster-wide collection via the `trace` verb -------------

def test_two_node_cluster_collects_lm_trace(tmp_path):
    """A traced lm_submit from node n1 into n0's decode pool, collected
    back through the `trace` control verb: one trace spanning both nodes
    with admission, queue-wait, prefill and decode-step spans correctly
    parent-linked, every timestamp from the injected fake clocks."""
    import jax
    import jax.numpy as jnp

    from idunno_tpu.comm.inproc import InProcNetwork
    from idunno_tpu.comm.message import Message
    from idunno_tpu.config import ClusterConfig
    from idunno_tpu.engine.generate import save_lm
    from idunno_tpu.models.transformer import TransformerLM
    from idunno_tpu.serve.node import Node
    from idunno_tpu.utils.types import MessageType
    from tests.conftest import TimedFakeEngine

    def _call(node, payload):
        out = node.control._handle("control", Message(
            MessageType.INFERENCE, "client", payload))
        assert out.type is MessageType.ACK, out.payload
        return out.payload

    net = InProcNetwork()
    cfg = ClusterConfig(hosts=("n0", "n1"), coordinator="n0",
                        standby_coordinator="n1", introducer="n0",
                        replication_factor=2, ping_interval_s=0.1,
                        failure_timeout_s=1.0, metadata_interval_s=0.2)
    nodes = {h: Node(h, cfg, net.transport(h), str(tmp_path / h),
                     engine=TimedFakeEngine(0.01)) for h in cfg.hosts}
    for n in nodes.values():
        n.start()
    try:
        deadline = _time.time() + 5.0
        while _time.time() < deadline and not all(
                len(n.membership.members.alive_hosts()) == 2
                for n in nodes.values()):
            _time.sleep(0.02)
        # fake clocks injected AFTER start: every span timestamp the test
        # produces must be a value these clocks returned (5e8 is far from
        # any time.monotonic() reading)
        clk = _Clock(5e8)
        for n in nodes.values():
            n.spans.clock = clk

        model = TransformerLM(vocab=32, dim=32, depth=1, num_heads=4)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        save_lm(nodes["n0"].store, "tlm", model, params)
        _call(nodes["n0"], {"verb": "lm_serve", "name": "tlm", "slots": 2,
                            "prompt_len": 4, "max_len": 16,
                            # block pool on: the prefix-cache gauge set
                            # (incl. the ISSUE 17 cluster counters) joins
                            # the scrape below
                            "kv_block_size": 2})

        root = nodes["n1"].spans.start("client.lm_submit",
                                       attrs={"pool": "tlm"})
        out = nodes["n1"].transport.call(
            "n0", "control",
            Message(MessageType.INFERENCE, "n1",
                    {"verb": "lm_submit", "name": "tlm",
                     "prompt": [1, 2, 3, 4], "max_new": 6,
                     "trace": [root.trace_id, root.span_id]}))
        assert out.type is MessageType.ACK, out.payload
        rid = int(out.payload["id"])
        nodes["n1"].spans.finish(root, rid=rid)

        done = {}
        deadline = _time.time() + 60.0
        while rid not in done and _time.time() < deadline:
            clk.advance(0.25)
            for comp in _call(nodes["n0"], {"verb": "lm_poll",
                                            "name": "tlm"})["completions"]:
                done[comp["id"]] = comp
            _time.sleep(0.01)
        assert rid in done and len(done[rid]["tokens"]) == 10

        got = _call(nodes["n0"], {"verb": "trace", "name": "tlm",
                                  "id": rid})
        assert got["trace_id"] == root.trace_id
        assert sorted(got["nodes"]) == ["n0", "n1"], \
            "trace collected from both nodes"
        spans = got["spans"]
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        for want in ("client.lm_submit", "lm.submit", "lm.admit",
                     "lm.queue_wait", "lm.slot_wait", "lm.prefill",
                     "kv.lookup", "kv.insert", "lm.decode", "lm.finish"):
            assert want in by_name, f"missing {want}: {sorted(by_name)}"
        sub = by_name["lm.submit"][0]
        admit = by_name["lm.admit"][0]
        prefill = by_name["lm.prefill"][0]
        # parent chain: client root -> submit verb -> admit -> {queue-wait,
        # prefill -> decode steps, finish}
        assert sub["parent"] == root.span_id and sub["node"] == "n0"
        assert admit["parent"] == sub["span_id"]
        assert by_name["lm.queue_wait"][0]["parent"] == admit["span_id"]
        assert by_name["lm.slot_wait"][0]["parent"] == admit["span_id"]
        assert prefill["parent"] == admit["span_id"]
        for kv in ("kv.lookup", "kv.insert"):
            assert by_name[kv][0]["parent"] == prefill["span_id"]
        # one decode span a request (not one a row a dispatch): 6 tokens
        # are the prefill's one and 5 dispatches' (decode_steps 1)
        (decode,) = by_name["lm.decode"]
        assert decode["parent"] == prefill["span_id"]
        assert decode["attrs"]["steps"] == 5
        assert decode["attrs"]["tokens"] == 6
        # the first stamp shows the prefill's token alone: the row joins
        # the next step's dispatch
        assert decode["attrs"]["n_first"] == 1
        finish = by_name["lm.finish"][0]
        assert finish["parent"] == admit["span_id"]
        # the stamps ride the finish span, on the same injected clock
        for k in ("t_submit", "t_admit", "t_first", "t_last"):
            assert finish["attrs"][k] in clk.seen, k
        assert finish["attrs"]["t_first"] == decode["attrs"]["t_first"]
        # the pool's own timeline is one trace, whatever requests ran
        loop_tid = "t:n0:loop:tlm"
        ltl = _call(nodes["n0"], {"verb": "trace",
                                  "trace_id": loop_tid})["spans"]
        lnames = {s["name"] for s in ltl}
        assert {"loop.iter", "loop.drain", "lm.step", "lm.decode_step",
                "lm.step.sync", "loop.publish"} <= lnames, lnames
        assert prefill["attrs"]["step"] in {
            s["span_id"] for s in ltl if s["name"] == "lm.step"}
        assert not any(s["trace_id"] == loop_tid for s in spans)
        # fake-clock exactness: every timestamp is a value the injected
        # clock actually produced, and every closed span is well-ordered
        for s in spans:
            assert s["t_start"] in clk.seen, s
            if s["t_end"] is not None:
                assert s["t_end"] in clk.seen and s["t_end"] >= s["t_start"]

        # the shell waterfall renders the same collection
        from idunno_tpu.cli.shell import format_waterfall
        text = format_waterfall(got["trace_id"], spans)
        assert "lm.prefill" in text and "n1" in text and "n0" in text

        # spans_dump is the node-local window the verb fanned out to
        local = _call(nodes["n1"], {"verb": "spans_dump",
                                    "trace_id": root.trace_id})
        assert [s["name"] for s in local["spans"]] == ["client.lm_submit"]

        # metrics_export: local text, and forwarded to the peer via host=
        # (lm_stats records the pool's TP gauges on the metrics plane, so
        # the Prometheus text names n_model/tp_collective_bytes even for a
        # plain n_model=1 pool)
        _call(nodes["n0"], {"verb": "lm_stats", "name": "tlm"})
        text = _call(nodes["n0"], {"verb": "metrics_export"})["text"]
        assert 'node="n0"' in text and "span_buffer_depth" in text
        assert 'name="n_model"' in text
        assert 'name="tp_collective_bytes"' in text
        # ISSUE 16: the vocab-sharded sampling tail's merge-payload gauge
        # rides beside it (0 for an n_model=1 pool, but always named)
        assert 'name="sampling_collective_bytes"' in text
        # PR-5 durability-gap counter joins the scrape (ISSUE 14): acked
        # work whose write-ahead was skipped because the standby was down
        assert 'idunno_gauge{node="n0",name="wal_skips"}' in text
        # ISSUE 15: the delta-WAL byte gauge and the ownership-routing
        # counters join the scrape unconditionally (zero-valued until
        # the first redirect / scope handoff)
        assert 'idunno_gauge{node="n0",name="pool_wal_bytes"}' in text
        assert 'name="scope_owner_redirects"' in text
        assert 'name="scope_owner_moves"' in text
        # ISSUE 17: the cluster prefix-cache gauges ride the lm_stats
        # gauge plane (zero-valued while the cluster tier is off, but
        # always named on a kv_block_size pool)...
        for g in ("prefix_remote_hits", "prefix_published_chains",
                  "prefix_warm_blocks", "prefix_fetch_bytes"):
            assert f'name="{g}"' in text, g
        # ...and the shipped-WAL compaction counter scrapes
        # unconditionally beside the ISSUE 15 byte gauge
        assert 'idunno_gauge{node="n0",name="pool_wal_truncated"}' in text
        # ISSUE 18: the DistServe handoff gauges ride the same lm_stats
        # plane (zero-valued until the first ship, but always named on a
        # kv_block_size pool), and the fallback + predictive-spawn
        # counters scrape unconditionally
        for g in ("kv_handoff_requests", "kv_handoff_bytes",
                  "kv_handoff_fallbacks"):
            assert f'name="{g}"' in text, g
        assert 'idunno_events_total{node="n0",name="kv_handoff_fallbacks"}' \
            in text
        assert 'idunno_events_total{node="n0",name="predictive_spawns"}' \
            in text
        # ISSUE 20: the differential-health gauges and the gray-failure
        # counters scrape unconditionally — the ledger exists on every
        # node (zero-scored until a transport observation lands), and
        # the hedge counters ride retry_counters() beside the retry ones
        assert 'idunno_gauge{node="n0",name="node_health_score"}' in text
        assert 'idunno_gauge{node="n0",name="quarantined_nodes"}' in text
        for c in ("hedged_rpcs", "hedge_wins", "early_redispatches",
                  "quarantine_reroutes"):
            assert f'idunno_events_total{{node="n0",name="{c}"}}' in text, c
        remote = _call(nodes["n0"], {"verb": "metrics_export",
                                     "host": "n1"})["text"]
        assert 'node="n1"' in remote

        # ISSUE 18: the kv_handoff verb's op="ship" orchestration on the
        # REAL control plane (chaos.py mirrors this handler node-locally,
        # so this is where the production probe→export→adopt RPC chain
        # actually executes): serve a decode-side pool on n1 off the same
        # stored model, ship tlm's block chain into it point-to-point,
        # and collect the handoff trace across both nodes.
        _call(nodes["n1"], {"verb": "lm_serve", "name": "tlm2",
                            "model": "tlm", "slots": 2, "prompt_len": 4,
                            "max_len": 16, "kv_block_size": 2})
        hroot = nodes["n0"].spans.start("client.kv_handoff")
        shipped = _call(nodes["n0"], {
            "verb": "kv_handoff", "op": "ship", "name": "tlm",
            "target_host": "n1", "target_name": "tlm2",
            "tokens": [1, 2, 3, 4],
            "trace": [hroot.trace_id, hroot.span_id]})
        nodes["n0"].spans.finish(hroot)
        assert shipped["shipped"] == 1 and shipped["bytes"] > 0
        # a replayed ship converges: the probe sees the chain held, the
        # empty delta short-circuits before any adopt RPC
        again = _call(nodes["n0"], {
            "verb": "kv_handoff", "op": "ship", "name": "tlm",
            "target_host": "n1", "target_name": "tlm2",
            "tokens": [1, 2, 3, 4]})
        assert again["already"] is True and again["bytes"] == 0
        hgot = _call(nodes["n0"], {"verb": "trace",
                                   "trace_id": hroot.trace_id})
        hby = {s["name"]: s for s in hgot["spans"]}
        hship = hby["lm.handoff"]
        assert hship["parent"] == hroot.span_id and hship["node"] == "n0"
        assert hby["lm.handoff_export"]["parent"] == hship["span_id"]
        hadopt = hby["lm.handoff_adopt"]
        assert hadopt["parent"] == hship["span_id"]
        assert hadopt["node"] == "n1"
        assert hadopt["attrs"]["blocks"] == shipped["shipped"]
        # the gauges land on each endpoint's own stats plane: the export
        # counts the ship on the prefill pool (the zero-delta replay is
        # free), the adopt counts the bytes on the decode pool
        pre_stats = _call(nodes["n0"], {"verb": "lm_stats",
                                        "name": "tlm"})["stats"]
        dec_stats = _call(nodes["n1"], {"verb": "lm_stats",
                                        "name": "tlm2"})["stats"]
        assert pre_stats["kv_handoff_requests"] == 1
        assert dec_stats["kv_handoff_bytes"] == shipped["bytes"]
    finally:
        for n in nodes.values():
            n.stop()
