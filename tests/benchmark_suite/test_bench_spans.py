"""The readers of the program's own spans and counters
(`benchmark/readers/spans.py`): each reader's value on a hand-made run, None
where the program records nothing of the kind (the parent commit), and the
rehearsal taking them up as new files and entries alone."""
import argparse

import pytest

from benchmark import harness, manifest

W0, W1 = 100.0, 150.0


def _span(name, sid, parent, t0, t1, **attrs):
    return {"trace_id": "t", "span_id": sid, "parent": parent, "name": name,
            "node": "bench", "t_start": t0, "t_end": t1, "attrs": attrs}


def _run(spans=(), stats0=None, stats1=None):
    return harness.RunData(cfg={}, device={}, w0=W0, w1=W1,
                           spans=list(spans), stats0=stats0 or {},
                           stats1=stats1 or {})


def _reader(name):
    fn, kw = manifest.Manifest().reader(name)
    return lambda run: fn(run, **kw)


def _loop(i, t0, sync_ms, dispatch=True, dur=0.2):
    """One loop iteration of ``dur`` seconds whose step waited ``sync_ms``
    for the chip, in two spans."""
    it, st = f"b:loop.{10 * i}", f"b:loop.{10 * i + 1}"
    out = [_span("loop.iter", it, None, t0, t0 + dur, live=1, done=0),
           _span("lm.step", st, it, t0 + 0.01, t0 + dur - 0.01, rows=1),
           _span("lm.step.sync", st + "a", st, t0 + 0.02,
                 t0 + 0.02 + sync_ms / 2e3, after="admit"),
           _span("lm.step.sync", st + "b", st, t0 + 0.1,
                 t0 + 0.1 + sync_ms / 2e3, after="dispatch")]
    if dispatch:
        out.append(_span("lm.decode_step", st + "c", st, t0 + 0.09,
                         t0 + 0.1, rows=1))
    return out


def test_each_reader_reads_its_spans():
    spans = []
    for i, (sub, first) in enumerate([(101.0, 101.3), (102.0, 102.1),
                                      (103.0, 103.9), (104.0, 104.2)]):
        spans.append(_span("lm.finish", f"b:{i}", "a", first + 1, first + 1,
                           rid=i, t_submit=sub, t_first=first, n_first=5))
    # submitted outside the window, or never saw a token: not counted
    spans.append(_span("lm.finish", "b:8", "a", 101.0, 101.0, rid=8,
                       t_submit=99.0, t_first=100.5))
    spans.append(_span("lm.cancel", "b:9", "a", 120.0, 120.0, rid=9,
                       t_submit=119.0, t_first=None))
    for i, ms in enumerate((1.0, 3.0, 8.0)):
        spans.append(_span("lm.slot_wait", f"s:{i}", "a", 110.0,
                           110.0 + ms / 1e3))
        spans.append(_span("lm.prefill", f"p:{i}", "a", 111.0,
                           111.0 + 10 * ms / 1e3, prompt_len=8,
                           prefix_hit=0))
        spans.append(_span("kv.insert", f"k:{i}", f"p:{i}", 111.0,
                           111.0 + 5 * ms / 1e3, evicted=2))
    spans += _loop(1, 120.0, sync_ms=150.0)             # host 50 ms
    spans += _loop(2, 121.0, sync_ms=190.0)             # host 10 ms
    spans += _loop(3, 122.0, sync_ms=0.0, dispatch=False)   # idle turn
    spans += _loop(4, 149.9, sync_ms=100.0)             # ends past the window
    run = _run(spans,
               {"prefix_cache": {"evictions": 10, "evict_nodes_walked": 9000}},
               {"prefix_cache": {"evictions": 30,
                                 "evict_nodes_walked": 27000}})
    assert _reader("first_token_p90_ms")(run) == pytest.approx(900.0)
    assert _reader("slot_wait_p50_ms")(run) == pytest.approx(3.0)
    assert _reader("admission_host_p50_ms")(run) == pytest.approx(30.0)
    assert _reader("kv_insert_p50_ms")(run) == pytest.approx(15.0)
    assert _reader("evict_walk_per_block")(run) == pytest.approx(900.0)
    assert _reader("step_host_ms")(run) == pytest.approx(30.0)
    assert _reader("step_host_ms.batch")(run) == pytest.approx(30.0)


NEW = ["first_token_p90_ms", "slot_wait_p50_ms", "admission_host_p50_ms",
       "kv_insert_p50_ms", "evict_walk_per_block", "step_host_ms",
       "step_host_ms.batch"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_span_or_counter_reads_as_none(name):
    # what the parent commit records: a decode span a row a dispatch under
    # the prefill, a finish without stamps, no eviction walk counter
    parent = [_span("lm.prefill", "b:1", "a", 110.0, 110.1, prompt_len=8,
                    prefix_hit=0),
              _span("lm.decode_step", "b:2", "b:1", 110.1, 110.2, batch=1),
              _span("lm.finish", "b:3", "a", 111.0, 111.0, rid=0, tokens=9)]
    stats = {"prefix_cache": {"evictions": 5}}
    read = _reader(name)
    assert read(_run()) is None
    if name != "admission_host_p50_ms":      # `lm.prefill` is as it was
        assert read(_run(parent, stats, stats)) is None
    # nothing evicted in the window: no walk to divide
    same = {"prefix_cache": {"evictions": 5, "evict_nodes_walked": 50}}
    if name == "evict_walk_per_block":
        assert read(_run([], same, same)) is None


def test_the_new_metrics_are_entries_and_files_alone():
    man = manifest.Manifest()
    by_name = {m["name"]: m for m in man.data["per_layer"]}
    assert [m["name"] for m in man.data["per_layer"]][-len(NEW):] == NEW
    for name in NEW:
        assert "workloads" not in by_name[name]
    got = {m["name"] for m in man.per_layer("starcoder2-7b.completion")}
    assert set(NEW) - {"step_host_ms.batch"} <= got
    assert {m["name"] for m in man.per_layer("starcoder2-3b.batch")} \
        & set(NEW) == {"step_host_ms.batch"}


def test_rehearsal_reports_the_programs_own_readings():
    args = argparse.Namespace(workload="starcoder2-7b.completion", seed=11,
                              seconds=3.0, trace=1, rehearse=True,
                              control="", root=None)
    result, summary = harness.run(args, harness.clock())
    got = result["metrics"]
    for name in ("first_token_p90_ms", "step_host_ms",
                 "admission_host_p50_ms", "slot_wait_p50_ms",
                 "kv_insert_p50_ms"):
        assert got["rehearse." + name]["value"] >= 0.0, name
    # what was reported before is reported still
    for name in ("queue_wait_p50_ms", "admission_p50_ms", "batch_occupancy",
                 "prefix_hit_share"):
        assert "rehearse." + name in got
    # the program's first-token stamp and the benchmark's own are the end
    # of the same step (request by request: tests/test_pool_timing.py),
    # one from the submit and one from the due time before it
    late = summary["gen_lateness_max_ms"]
    assert summary["all"]["ttft_p90_ms"] - late - 2.0 \
        <= got["rehearse.first_token_p90_ms"]["value"] \
        <= summary["all"]["ttft_p90_ms"] + 2.0
