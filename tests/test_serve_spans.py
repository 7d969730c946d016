"""The host's phases inside `DecodeServer.step` (ISSUE 38): the read-back,
the counters, retirement, the stamps and the admission are spans of the
pool's own timeline (lane `loop`, under `lm.step`) and profiler
annotations; with no store wired they are nothing, and the served tokens
are the same either way."""
import jax
import jax.numpy as jnp
import pytest

from benchmark import harness, manifest
from idunno_tpu.engine.serve_lm import DecodeServer
from idunno_tpu.models.transformer import TransformerLM
from idunno_tpu.utils.spans import SpanStore

VOCAB = 61
PHASES = ("lm.readback", "lm.count", "lm.retire", "lm.stamp",
          "lm.step.admit")
# (prompt, max_new, temperature): rows that finish in different steps,
# one sampled
REQUESTS = [([1, 2, 3], 9, 0.0), ([4, 5, 6, 7], 3, 0.0),
            ([8, 9], 6, 0.8), ([10, 11, 12], 5, 0.0)]


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab=VOCAB, dim=32, depth=2, num_heads=4)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _serve(lm, store=None):
    model, params = lm
    srv = DecodeServer(model, params, slots=2, prompt_len=8, max_len=32,
                       decode_steps=4)
    if store is not None:
        srv.spans, srv.clock = store, store.clock
    for toks, n, temp in REQUESTS:
        srv.submit(toks, n, temperature=temp)
    done = srv.run_until_drained()
    return srv, {c.id: c.tokens for c in done}


def test_a_traced_step_holds_its_phases_inside_lm_step(lm):
    store = SpanStore("n0", capacity=1 << 16)
    _serve(lm, store)
    spans = store.dump(trace_id="t:n0:loop:bare")
    by_id = {s["span_id"]: s for s in spans}
    kids: dict = {}
    for s in spans:
        if s["name"] != "lm.step":
            step = by_id[s["parent"]]
            assert step["name"] == "lm.step", s["name"]
            assert step["t_start"] <= s["t_start"] <= s["t_end"] \
                <= step["t_end"], s["name"]
            assert s["span_id"].startswith("n0:loop."), "the loop's lane"
            kids.setdefault(s["parent"], set()).add(s["name"])
    # a step that dispatched, then read the dispatch back and retired a row
    full = [names for names in kids.values()
            if {"lm.decode_step", "lm.readback", "lm.retire", "lm.stamp",
                "lm.step.admit"} <= names]
    assert full
    assert {s["name"] for s in spans} == {"lm.step", "lm.decode_step",
                                          "lm.step.sync", *PHASES}
    retired = [s for s in spans if s["name"] == "lm.retire"]
    assert sum(s["attrs"]["rows"] for s in retired) == len(REQUESTS)
    assert all(s["attrs"]["rows"] >= 1 for s in spans
               if s["name"] == "lm.stamp")


def test_no_store_enters_no_annotation_and_records_nothing(lm, monkeypatch):
    entered = []

    class Counting:
        def __init__(self, name, **_kw):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *_exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    srv, done = _serve(lm)
    assert len(done) == len(REQUESTS) and srv.spans is None
    assert entered == []
    store = SpanStore("n0", capacity=1 << 16)
    _serve(lm, store)
    assert set(PHASES) <= set(entered)
    assert set(entered) == {s["name"] for s in store.dump()
                            if s["span_id"].startswith("n0:loop.")}


def test_traced_and_untraced_pools_serve_the_same_tokens(lm):
    _srv, plain = _serve(lm)
    _srv, traced = _serve(lm, SpanStore("n0", capacity=1 << 16))
    assert traced == plain
    assert [len(plain[i]) for i in sorted(plain)] == [
        len(p) + n for p, n, _t in REQUESTS]


def _span(name, sid, parent, t0, t1, **attrs):
    return {"trace_id": "t", "span_id": sid, "parent": parent, "name": name,
            "node": "bench", "t_start": t0, "t_end": t1, "attrs": attrs}


def _iteration(i, t0, with_phases):
    """One loop iteration of 200 ms whose step dispatched and waited
    60 ms for the chip; ``with_phases`` adds this PR's children."""
    it, st = f"l.{i}0", f"l.{i}1"
    out = [_span("loop.iter", it, None, t0, t0 + 0.2),
           _span("lm.step", st, it, t0 + 0.01, t0 + 0.19, rows=2),
           _span("lm.decode_step", st + "a", st, t0 + 0.02, t0 + 0.03,
                 rows=2),
           _span("lm.step.sync", st + "b", st, t0 + 0.1, t0 + 0.16,
                 after="dispatch")]
    if with_phases:
        out += [_span("lm.step.admit", st + "c", st, t0 + 0.03, t0 + 0.09),
                _span("lm.count", st + "d", st, t0 + 0.1, t0 + 0.14),
                _span("lm.readback", st + "e", st, t0 + 0.1, t0 + 0.13),
                _span("lm.retire", st + "f", st, t0 + 0.14, t0 + 0.16,
                      rows=1),
                _span("lm.stamp", st + "g", st, t0 + 0.16, t0 + 0.17,
                      rows=2)]
    return out


@pytest.mark.parametrize("metric", ["step_host_ms", "step_host_ms.batch"])
def test_step_host_ms_reads_the_same_with_the_new_children(metric):
    fn, kw = manifest.Manifest().reader(metric)
    read = {}
    for with_phases in (False, True):
        spans = []
        for i, t0 in enumerate((110.0, 111.0, 112.5)):
            spans += _iteration(i, t0, with_phases)
        run = harness.RunData(cfg={}, device={}, w0=100.0, w1=150.0,
                              spans=spans)
        read[with_phases] = fn(run, **kw)
    assert read[False] == pytest.approx(140.0)
    assert read[True] == read[False]
