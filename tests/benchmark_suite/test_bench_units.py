"""The benchmark's own arithmetic, none of it needing a chip: the traffic
generator, the end-to-end metrics, the trace reduction, the operation and
byte counts, the peaks table, the manifest."""
import gzip
import json
import math
import os

import pytest

from benchmark import manifest, peaks, timing, trace, traffic
from benchmark.families.starcoder2 import counts

BENCH = os.path.dirname(os.path.abspath(manifest.__file__))
MIXES = ["batch", "completion", "repo-prefix"]


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _gen(mix, seed, seconds=20):
    cfg = _cfg("starcoder2-3b")
    return traffic.generate(_mix(mix), cfg["serving"], cfg["vocab_size"],
                            seed, seconds)


# -- traffic ----------------------------------------------------------------

@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_bytes(mix):
    big = 2**31 + 12345
    assert (traffic.stream_bytes(_gen(mix, big))
            == traffic.stream_bytes(_gen(mix, big)))
    assert (traffic.stream_bytes(_gen(mix, big))
            != traffic.stream_bytes(_gen(mix, big + 1)))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work_in_another_order(mix):
    a, b = _gen(mix, 1), _gen(mix, 2)
    assert len(a.requests) == len(b.requests)
    prompts = lambda tr: sorted(len(r.tokens) - r.shared_len
                                for r in tr.requests)
    assert prompts(a) == prompts(b)
    if not a.clients:         # a closed loop cuts its first requests short
        outs = lambda tr: sorted(r.max_new for r in tr.requests)
        assert outs(a) == outs(b)
    if a.requests[0].due_s is not None:
        def gaps(tr):          # the first arrival comes half a gap in
            due = [r.due_s for r in tr.requests]
            return sorted(round(g, 9) for g in [2 * due[0]] + [
                y - x for x, y in zip(due, due[1:])])
        assert gaps(a) == gaps(b)
        same_order = ([r.due_s for r in a.requests]
                      == [r.due_s for r in b.requests])
        # a mix may replay one schedule under every seed ("order": "fixed");
        # the token ids still come from the seed
        assert same_order == (_mix(mix).get("order") == "fixed")
    assert [r.tokens for r in a.requests] != [r.tokens for r in b.requests]


def test_open_loop_rate_and_window_count():
    tr = _gen("completion", 5, seconds=40)
    mix = _mix("completion")
    due = [r.due_s for r in tr.requests]
    assert due == sorted(due)
    n_window = sum(r.phase == "window" for r in tr.requests)
    assert abs(n_window - mix["rate_rps"] * 40) <= 0.1 * mix["rate_rps"] * 40


def test_shared_prefixes_repeat_and_setup_admits_each_once():
    tr = _gen("repo-prefix", 9, seconds=300)
    groups = _mix("repo-prefix")["sharing"]["groups"]
    assert len(tr.setup_requests) == groups
    heads = {}
    for r in tr.setup_requests + tr.requests:
        assert r.shared_len % 16 == 0 and r.shared_len >= 1536
        head = tuple(r.tokens[:r.shared_len])
        assert heads.setdefault(r.group, head) == head
    # the coldest repository still comes back evenly through the stream
    cold = [i for i, r in enumerate(tr.requests) if r.group == groups - 1]
    assert len(cold) >= 2
    gaps = [b - a for a, b in zip(cold, cold[1:])]
    assert max(gaps) <= 2 * (len(tr.requests) / len(cold)) + 2


def test_closed_loop_has_a_client_per_slot():
    tr = _gen("batch", 3)
    assert tr.clients == _cfg("starcoder2-3b")["serving"]["slots"]
    assert all(r.due_s is None for r in tr.requests)


def test_burst_warp_keeps_the_mean_rate():
    import numpy as np
    t = np.linspace(0.05, 99.95, 1000)
    w = traffic._burst_warp(t, {"period_s": 10, "on_s": 1.5, "factor": 4})
    assert np.all(np.diff(w) >= 0) and abs(w[-1] - t[-1]) < 10
    inside = np.mod(w, 10) < 1.5
    assert 0.5 < inside.mean() < 0.7      # 4 x 1.5 / 10 of the arrivals


def test_a_request_that_cannot_fit_is_refused_before_the_run():
    cfg = _cfg("starcoder2-3b")
    mix = dict(_mix("completion"),
               prompt={"dist": "fixed", "value": 5000})
    with pytest.raises(ValueError, match="do not fit"):
        traffic.generate(mix, cfg["serving"], cfg["vocab_size"], 1, 5)


# -- end-to-end arithmetic ----------------------------------------------------

def _synthetic(stall_at=None, stall_s=0.0, n=200, step_s=0.1, k=4):
    """A window of steps ``step_s`` apart in which every step emits ``k``
    tokens for one request; a request is due every step and is served by
    the step that ends after it. A stall delays every later step."""
    steps, records, t = [], [], 0.0
    for i in range(n):
        t0 = t
        t += step_s + (stall_s if i == stall_at else 0.0)
        steps.append((t0, t, 1, {i: k}, [100]))
        steps.append((t, t, 1, {i: 2 * k}, [100]))
        records.append({"due": i * step_s, "t_first": t, "n_first": k,
                        "t_last": t + step_s, "n": 2 * k, "complete": True})
    return records, steps


def test_a_stall_moves_ttft_and_throughput():
    calm = timing.end_to_end(*_synthetic(), 0.0, 20.0)
    rec, steps = _synthetic(stall_at=50, stall_s=3.0)
    stalled = timing.end_to_end(rec, steps, 0.0, 20.0)
    # every request after the stall waits 3 s longer: the tail sees it
    assert stalled["ttft_p90_ms"] > calm["ttft_p90_ms"] + 2500
    # tokens stamped after the window's end are not its tokens
    assert stalled["out_tok_s"] < 0.9 * calm["out_tok_s"]
    assert calm["out_tok_s"] == pytest.approx(200 * 8 / 20.0, rel=0.01)


def test_a_failed_request_counts_as_the_worst():
    rec, steps = _synthetic(n=20)
    for r in rec[:3]:                       # 15%: past the 90th percentile
        r["t_first"] = None
    out = timing.end_to_end(rec, steps, 0.0, 2.0)
    assert math.isinf(out["ttft_p90_ms"])
    assert math.isfinite(out["ttft_p50_ms"])


def test_percentile_is_a_request_s_own_reading():
    xs = [float(i) for i in range(1, 101)]
    assert timing.percentile(xs, 90) == 90.0
    assert timing.percentile(xs, 50) == 50.0
    assert math.isnan(timing.percentile([], 90))


def test_request_times_and_tokens_in():
    steps = [(0.0, 1.0, 1, {7: 5}, [9]), (1.0, 2.0, 1, {7: 9}, [13]),
             (2.0, 3.0, 1, {7: 12}, [16])]
    t = timing.request_times(steps)[7]
    assert (t["t_admit"], t["t_first"], t["n_first"]) == (0.0, 1.0, 5)
    assert (t["t_last"], t["n"]) == (3.0, 12)
    assert timing.tokens_in(steps, 1.5, 3.5) == 7
    assert timing.tokens_in(steps, 0.0, 3.5) == 12


# -- counts -------------------------------------------------------------------

@pytest.mark.parametrize("name,per_layer,kv", [
    ("starcoder2-3b", 95_944_704, 30_720),
    ("starcoder2-7b", 217_055_232, 2_048 * 16),
])
def test_counts_match_hand_counts(name, per_layer, kv):
    cfg = _cfg(name)
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    kvw = cfg["num_key_value_heads"] * cfg["head_dim"]
    assert counts.params_per_layer(cfg, biases=False) == per_layer
    assert per_layer == 2 * h * h + 2 * h * kvw + 2 * h * f
    assert counts.kv_bytes_per_token(cfg) == kv
    assert counts.weight_bytes(cfg) == 2 * counts.params_total(cfg)


def test_decode_and_prefill_work_from_shapes():
    cfg = _cfg("starcoder2-3b")
    f1, b1 = counts.decode_step_work(cfg, [1000] * 8)
    f2, b2 = counts.decode_step_work(cfg, [2000] * 8)
    assert f1 > 2 * counts.matmul_params(cfg) * 8       # + attention
    assert b2 - b1 == counts.kv_bytes_per_token(cfg) * 8000
    assert b1 > counts.matmul_params(cfg) * 2           # weights once
    fp, _ = counts.prefill_work(cfg, 512)
    fh, _ = counts.prefill_work(cfg, 128, cached_tokens=384)
    assert fh < fp / 3                                  # a hit saves the work


# -- peaks --------------------------------------------------------------------

def test_peaks_table_is_keyed_by_exact_device_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["flops_bf16"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (
        197e12, 819e9, 16e9)
    for unknown in ("TPU v5", "tpu v5 lite", "cpu", "TPU v5e"):
        with pytest.raises(KeyError, match="no published peaks"):
            peaks.peaks_for(unknown)


def test_roofline_share_names_its_bound():
    share, bound = peaks.roofline_share(197e12, 1.0, 2.0, "TPU v5 lite")
    assert (share, bound) == (50.0, "compute")
    share, bound = peaks.roofline_share(1.0, 819e9, 4.0, "TPU v5 lite")
    assert (share, bound) == (25.0, "memory")
    assert peaks.mfu(197e12, 2.0, "TPU v5 lite") == 50.0


# -- trace reduction ----------------------------------------------------------

def _toy_trace():
    ops = [("%fusion.1 = f32[] fusion()", 0.0, 1.0),
           ("%copy.4 = bf16[] copy()", 1.0, 0.5),
           ("%while.2 = () while()", 0.0, 1.5),        # covers its leaves
           ("%fusion.1 = f32[] fusion()", 3.0, 1.0),
           ("%copy.9 = bf16[] copy()", 5.0, 1.0)]
    mods = [("jit_run(123)", 0.0, 1.6), ("jit_run(123)", 3.0, 1.0),
            ("jit__write_block(9)", 5.0, 1.0)]
    return {"devices": [{"name": "/device:TPU:0",
                         "lines": {"XLA Ops": ops, "XLA Modules": mods}}],
            "host": [("bench.mark", 0.0, 0.0), ("bench.mark", 6.0, 0.0)]}


def test_trace_reduction_on_known_intervals():
    tr = _toy_trace()
    assert trace.busy_seconds(tr) == pytest.approx(3.5)
    mods = trace.module_times(tr)
    assert mods["jit_run"] == [pytest.approx(2.5), 2]
    assert mods["jit__write_block"] == [pytest.approx(1.0), 1]
    ops = trace.op_times(tr)
    assert ops["jit_run/fusion.1"] == pytest.approx(2.0)
    assert ops["jit__write_block/copy.9"] == pytest.approx(1.0)
    assert not any("while" in k for k in ops)
    gaps = trace.idle_gaps(tr, 0.0, 6.0, [("lm.prefill", 1.5, 2.0),
                                          ("step.other", 1.0, 3.0),
                                          ("loop.idle_wait", 4.0, 5.0)])
    assert gaps == {"lm.prefill": pytest.approx(0.5),
                    "step.other": pytest.approx(1.0),
                    "loop.idle_wait": pytest.approx(1.0)}
    cut = trace.clip(tr, 0.5, 3.5)
    assert trace.busy_seconds(cut) == pytest.approx(1.5)


def test_trace_round_trips_through_its_file(tmp_path):
    tr = _toy_trace()
    path = str(tmp_path / "t.json.gz")
    trace.save(tr, path)
    assert trace.busy_seconds(trace.load(path)) == trace.busy_seconds(tr)


RECORDED = os.path.join(BENCH, "data", "small_trace.json.gz")


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace beside the benchmark")
    return trace.load(RECORDED)


def test_recorded_trace_busy_idle_and_programs(recorded):
    with gzip.open(RECORDED, "rt") as f:
        meta = json.load(f)["meta"]
    busy = trace.busy_seconds(recorded)
    assert busy == pytest.approx(meta["busy_s"], rel=1e-9)
    assert 0 < busy <= meta["window_s"]
    mods = trace.module_times(recorded)
    assert "jit_run" in mods and mods["jit_run"][1] >= 1
    for name, (secs, runs) in meta["modules"].items():
        assert mods[name] == [pytest.approx(secs, rel=1e-9), runs]
    # the leaf ops of all programs add up to the busy time, bar overlaps
    assert sum(trace.op_times(recorded).values()) >= busy * 0.999


def test_recorded_trace_shares_are_above_0_and_at_most_100(recorded):
    """Every share of a roofline or a peak goes through the peaks table;
    on the recorded decode dispatches each lies in (0, 100]."""
    with gzip.open(RECORDED, "rt") as f:
        meta = json.load(f)["meta"]
    cfg = _cfg(meta["config"])
    secs, runs = trace.module_times(recorded)["jit_run"]
    k = cfg["serving"]["decode_steps"]
    flops, nbytes = counts.decode_step_work(cfg, meta["contexts"])
    share, bound = peaks.roofline_share(flops * k * runs, nbytes * k * runs,
                                        secs, meta["device_kind"])
    assert 0 < share <= 100 and bound == "memory"
    assert 0 < peaks.mfu(flops * k * runs, meta["window_s"],
                         meta["device_kind"]) <= 100


# -- manifest -----------------------------------------------------------------

def test_manifest_is_whole_and_every_metric_has_its_reader():
    man = manifest.Manifest()
    data = man.data
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in data["end_to_end"]}
    assert "setup_s" in e2e
    for m in data["per_layer"]:
        assert m["moves"] in e2e
        fn, _ = man.reader(m["name"])
        assert callable(fn)
    for w in data["workloads"]:
        cfg, mix = man.config(w), man.mix(w)
        assert {"source", "reduced", "assumed", "departures"} <= set(cfg)
        assert mix["why"] and mix["who"]
        assert len(w["why"]) <= 200 and w["chips"] == 1
        names = {m["name"] for m in man.end_to_end(w["name"])}
        assert "setup_s" in names and len(names) >= 2
        assert man.per_layer(w["name"])
        for m in man.per_layer(w["name"]):
            assert m["moves"] in names
    with pytest.raises(KeyError, match="no workload"):
        man.cell("nonesuch")
