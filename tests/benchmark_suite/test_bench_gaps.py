"""The time between two decode dispatches (ISSUE 38): the device's idle
time put down to the innermost program annotation (`benchmark/phases.py`)
on synthetic and recorded traces, the readers of `benchmark/readers/
gaps.py` on hand-made runs, and the four metrics as entries and files
alone (by membership, never by position)."""
import copy
import gzip
import json
import os
import threading
import types

import pytest

from benchmark import harness, manifest, phases, trace

MAN = manifest.Manifest()
BENCH = os.path.dirname(os.path.abspath(manifest.__file__))
RECORDED = os.path.join(BENCH, "data", "small_trace_phases.json.gz")
NEW = {"dispatch_gap_ms": "tpot_p90_ms",
       "dispatch_gap_ms.batch": "out_tok_s",
       "dispatch_occupancy": "tpot_p90_ms",
       "dispatch_occupancy.batch": "out_tok_s"}
LOOP, OTHER = "/host:CPU#3", "/host:CPU#4"


def _reader(name):
    fn, kw = MAN.reader(name)
    return lambda run: fn(run, **kw)


# -- attribution --------------------------------------------------------------

def test_innermost_pieces_nest_and_cut_a_child_to_its_parent():
    events = [("loop.iter", 0.0, 10.0), ("lm.step", 1.0, 8.0),
              ("lm.decode_step", 1.0, 2.0), ("lm.step.sync", 4.0, 4.0),
              ("lm.readback", 4.0, 1.0), ("lm.retire", 7.0, 3.0)]
    assert phases.innermost(events) == [
        ("loop.iter", 0.0, 1.0), ("lm.decode_step", 1.0, 3.0),
        ("lm.step", 3.0, 4.0), ("lm.readback", 4.0, 5.0),
        ("lm.step.sync", 5.0, 7.0), ("lm.retire", 7.0, 8.0),
        ("lm.step", 8.0, 9.0), ("loop.iter", 9.0, 10.0)]
    assert phases.innermost([]) == []


def _trace(ops, marks=(0.0, 10.0)):
    return {"devices": [{"name": "/device:TPU:0",
                         "lines": {"XLA Ops": [(f"%fusion.{i} = f()", s, d)
                                               for i, (s, d) in
                                               enumerate(ops)],
                                   "XLA Modules": [
                                       ("jit_run(1)", 0.0, 10.0)]}}],
            "host": [("bench.mark", t, 0.0) for t in marks]}


def test_idle_goes_to_the_innermost_annotation_then_the_request_lane():
    # busy [0, 1), [1.000001, 2), [5, 6), [9, 10): idle 1 us between two
    # ops, then [2, 5) and [6, 9)
    tr = _trace([(0.0, 1.0), (1.000001, 0.999999), (5.0, 1.0),
                 (9.0, 1.0)])
    program = [("loop.iter", 1.5, 4.0, LOOP), ("lm.step", 1.6, 3.0, LOOP),
               ("lm.readback", 2.0, 1.0, LOOP),
               ("lm.stamp", 3.5, 0.5, LOOP),
               ("loop.publish", 4.8, 0.5, LOOP),
               # another thread's annotation names nothing
               ("lm.readback", 6.0, 3.0, OTHER)]
    lane = [("lm.prefill", 6.5, 7.5)]
    got = phases.idle_phases(tr, program, 0.0, 10.0, lane)
    assert got == {"lm.readback": pytest.approx(1.0),
                   "lm.step": pytest.approx(0.5 + 0.6),
                   "lm.stamp": pytest.approx(0.5),
                   "loop.iter": pytest.approx(0.2),
                   "loop.publish": pytest.approx(0.2),
                   "lm.prefill": pytest.approx(1.0),
                   "unattributed": pytest.approx(2.0),
                   "device.between_ops": pytest.approx(1e-6)}
    total_idle = 10.0 - trace.busy_seconds(tr)
    assert sum(got.values()) == pytest.approx(total_idle)
    # the longest stretch, [2, 5), is named by the piece over its middle
    pieces = phases.innermost(phases.loop_events(program))
    assert phases.longest_gap(tr, pieces, 0.0, 10.0) == [
        pytest.approx(3000.0), "lm.stamp"]
    assert phases.longest_gap(tr, [], 0.0, 10.0)[1] == "unattributed"


def test_program_annotations_are_read_from_a_profile(tmp_path):
    """A profile taken here (the CPU's host plane): the loop thread's
    annotations under their names, another thread's apart, `bench.*` and
    other names left out."""
    import jax

    both = threading.Barrier(2, timeout=30)    # two live threads at once

    def loop():
        with jax.profiler.TraceAnnotation("loop.iter"):
            with jax.profiler.TraceAnnotation("lm.step"):
                with jax.profiler.TraceAnnotation("lm.readback"):
                    both.wait()

    def other():
        with jax.profiler.TraceAnnotation("kv.lookup"):
            both.wait()

    jax.profiler.start_trace(str(tmp_path))
    try:
        threads = [threading.Thread(target=fn) for fn in (loop, other)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        with jax.profiler.TraceAnnotation("bench.mark"):
            pass
        with jax.profiler.TraceAnnotation("not.program"):
            pass
    finally:
        jax.profiler.stop_trace()
    program = phases.load_program(trace.find_xplane(str(tmp_path)))
    names = [e[0] for e in program]
    assert sorted(names) == ["kv.lookup", "lm.readback", "lm.step",
                             "loop.iter"]
    threads = {e[0]: e[3] for e in program}
    assert threads["lm.step"] == threads["loop.iter"] \
        == threads["lm.readback"] != threads["kv.lookup"]
    assert [e[0] for e in phases.loop_events(program)] == [
        "loop.iter", "lm.step", "lm.readback"]
    assert all(a[1] <= b[1] for a, b in zip(program, program[1:]))


def test_device_lag_is_the_most_a_dispatch_precedes_its_enqueue():
    tr = _trace([(0.0, 1.0)])
    tr["devices"][0]["lines"]["XLA Modules"] = [
        ("jit_run(1)", 1.0, 0.5), ("jit_run(1)", 2.0, 0.5),
        ("jit_run(1)", 3.0, 0.5), ("jit__prefill(2)", 4.0, 0.5)]
    program = [("lm.decode_step", 1.0009, 1e-4, LOOP),   # 0.9 ms early
               ("lm.decode_step", 1.9998, 1e-4, LOOP),   # after: no bound
               ("lm.decode_step", 3.0004, 1e-4, LOOP),
               ("lm.decode_step", 4.0020, 1e-4, LOOP),   # no dispatch
               ("lm.step", 0.5, 4.0, LOOP)]
    assert phases.device_lag(tr, program) == pytest.approx(9e-4)
    assert phases.device_lag(tr, program[1:2]) == 0.0
    assert phases.device_lag(tr, []) == 0.0


def test_clock_skew_is_the_farthest_span_from_its_annotation():
    program = [("lm.step", 1.0, 0.5, LOOP), ("lm.step", 2.0, 0.5, LOOP),
               ("lm.step", 3.0, 0.5, LOOP)]
    spans = [{"name": "lm.step", "t_start": t, "t_end": t + 0.5}
             for t in (10.9999, 12.0002, 13.0)]
    # a span that ends past the window has no annotation yet: left out
    spans.append({"name": "lm.step", "t_start": 13.8, "t_end": 14.5})
    got = phases.clock_skew_ms(program, spans, -10.0, 0.0, 4.0)
    assert got == pytest.approx(0.2)
    assert phases.clock_skew_ms([], spans, -10.0, 0.0, 4.0) is None


# -- the recorded piece of a chip trace ---------------------------------------

@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace with the program's annotations")
    with gzip.open(RECORDED, "rt") as f:
        meta = json.load(f)["meta"]
    return trace.load(RECORDED), meta


def test_the_recorded_trace_names_nine_tenths_of_its_idle(recorded):
    tr, meta = recorded
    a, b = meta["w0"], meta["w1"]
    cut = trace.clip(tr, a, b)
    lag = phases.device_lag(cut, tr["program"])
    # the chip's two clocks: every dispatch of the piece starts 0.76-0.88
    # ms before the annotation that enqueued it
    assert lag * 1e3 == pytest.approx(meta["device_lag_ms"], rel=1e-9)
    assert 0.5 < lag * 1e3 < 1.5
    program = phases.clip([(n, s - lag, d, th)
                           for n, s, d, th in tr["program"]], a, b)
    got = phases.idle_phases(cut, program, a, b)
    idle = (b - a) - trace.busy_seconds(cut)
    assert sum(got.values()) == pytest.approx(idle, rel=1e-9)
    named = idle - got.get("device.between_ops", 0.0)
    loose = sum(got.get(k, 0.0) for k in ("unattributed", "lm.step",
                                          "loop.iter"))
    assert loose < 0.1 * named
    assert {k: pytest.approx(v, rel=1e-9) for k, v in got.items()} \
        == meta["phases"]


def test_phases_leave_what_fill_trace_gives_as_it_was():
    """`fill_trace` with and without `fill_phases` after it, over the
    recorded trace of PR 24 (with a last `bench.mark` at its end): the
    same busy time, programs, operations and idle classes; the phases add
    `idle_phases` alone."""
    tr = trace.load(os.path.join(BENCH, "data", "small_trace.json.gz"))
    end = max(s + d for _n, s, d in tr["devices"][0]["lines"]["XLA Ops"])
    tr["host"].append(("bench.mark", end, 0.0))
    marks = [tr["host"][0][1] - 5.0, 0.0, end - 5.0]
    stub = types.SimpleNamespace(**vars(trace))
    stub.find_xplane = lambda _d: "recorded"
    stub.load_xplane = lambda _p: copy.deepcopy(tr)
    steps = [(s - 5.0, s - 5.0 + 0.004, 28, {}, []) for s in
             (0.06, 0.1, 0.2)]
    runs = []
    for with_phases in (False, True):
        run = harness.RunData(cfg={}, device={}, steps=list(steps))
        harness.fill_trace(run, stub, "dir", marks)
        if with_phases:
            phases.fill_phases(run, copy.deepcopy(tr), [], marks)
        runs.append(run)
    plain, both = runs
    assert both.busy_s == plain.busy_s > 0
    assert both.modules == plain.modules and both.ops == plain.ops
    assert both.breakdown["idle_gaps"] == plain.breakdown["idle_gaps"]
    assert both.breakdown["device_ops"] == plain.breakdown["device_ops"]
    assert set(both.breakdown) - set(plain.breakdown) == {
        "idle_phases", "longest_idle", "device_lag_ms",
        "phase_clock_skew_ms"}
    assert sum(v for _k, v in both.breakdown["idle_phases"]) == \
        pytest.approx(plain.trace_window_s - plain.busy_s)


# -- readers ------------------------------------------------------------------

def _run(**kw):
    base = dict(cfg={"serving": {"slots": 4}}, device={}, w0=100.0,
                w1=150.0)
    base.update(kw)
    return harness.RunData(**base)


@pytest.mark.parametrize("name", ["dispatch_gap_ms",
                                  "dispatch_gap_ms.batch"])
def test_dispatch_gap_is_the_idle_less_waits_over_the_dispatches(name):
    run = _run(trace_window_s=6.0, busy_s=5.7,
               modules={"jit_run": [5.0, 100], "jit__prefill": [0.7, 9]},
               breakdown={"idle_gaps": [["step.other", 0.25],
                                        ["loop.idle_wait", 0.02],
                                        ["device.between_ops", 0.03]]})
    assert _reader(name)(run) == pytest.approx(2.5)
    # nothing traced, or no dispatch in the trace: nothing to read
    assert _reader(name)(_run()) is None
    assert _reader(name)(_run(trace_window_s=6.0, busy_s=1.0,
                              modules={"jit__prefill": [1.0, 3]})) is None


@pytest.mark.parametrize("name", ["dispatch_occupancy",
                                  "dispatch_occupancy.batch"])
def test_dispatch_occupancy_is_the_rows_each_dispatch_carried(name):
    def span(t_end, rows):
        return {"name": "lm.decode_step", "t_start": t_end - 0.01,
                "t_end": t_end, "attrs": {"rows": rows}}
    spans = [span(110.0, 4), span(120.0, 3), span(130.0, 1),
             span(99.0, 4), span(150.0, 4),         # outside the window
             {"name": "lm.step", "t_start": 111.0, "t_end": 112.0,
              "attrs": {"rows": 4}}]
    assert _reader(name)(_run(spans=spans)) == pytest.approx(8 / 3 / 4)
    assert _reader(name)(_run()) is None
    # a program whose dispatch span has no `rows`
    bare = [dict(spans[0], attrs={"batch": 4})]
    assert _reader(name)(_run(spans=bare)) is None


# -- entries and files --------------------------------------------------------

@pytest.mark.parametrize("name", list(NEW))
def test_the_new_metrics_are_entries_and_files(name):
    by_name = {m["name"]: m for m in MAN.data["per_layer"]}
    m = by_name[name]
    assert m["moves"] == NEW[name] and "workloads" not in m
    fn, _kw = MAN.reader(name)
    assert fn.__module__.endswith("gaps")
    for cell in MAN.data["workloads"]:
        e2e = {e["name"] for e in MAN.end_to_end(cell["name"])}
        got = {p["name"] for p in MAN.per_layer(cell["name"])}
        assert (name in got) == (NEW[name] in e2e), cell["name"]
