"""The family `qwen3_next` beside the benchmark: its four files found by the
configuration's `family` key, its counts against hand sums at the published
widths (33.7 M parameters a Gated DeltaNet mixer, 27.3 M an attention one,
3.99 B held, 7.98 GB), its reference's delta rule and partial rotation
against values computed by hand in float64, the configuration against the
catalog, and the new cell's metrics: entries and files, their readers
finding nothing to read on a program without their counters."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, manifest

MAN = manifest.Manifest()
CELL = MAN.cell("qwen3-next-80b-a3b-instruct.long-context")
CFG = MAN.config(CELL)
FAM = MAN.family(CFG)
REF = FAM.reference


def test_the_family_is_four_files_found_by_name():
    assert CFG["family"] == "qwen3_next" == FAM.name
    top = os.path.join(manifest.ROOT, "benchmark", "families", FAM.name)
    assert sorted(f for f in os.listdir(top) if f.endswith(".py")) == [
        "counts.py", "program.py", "reference.py", "weights.py"]
    for part, fn in (("weights", "make_weights"), ("reference", "logits_at"),
                     ("counts", "decode_step_work"), ("counts", "prefill_work"),
                     ("counts", "cache_bytes"),
                     ("counts", "delta_state_step_bytes"),
                     ("program", "build"), ("program", "derive")):
        assert callable(getattr(getattr(FAM, part), fn))
    # the reference, the counts and the weights import nothing of the program
    for part in ("reference", "counts", "weights"):
        with open(os.path.join(top, part + ".py")) as f:
            assert "idunno_tpu" not in f.read().split('"""', 2)[2]


def test_counts_at_the_published_widths():
    """The sums by hand: a Gated DeltaNet mixer 33.72 M, a gated
    attention one 27.26 M, a layer's router, shared expert and gate 4.20 M,
    an expert 3.15 M; four layers with 256 experts each and the vocabulary
    twice: 3.99 B, 7.98 GB. A slot: 3 x 2.10 MB of float32 state, K/V at
    2 KiB a token."""
    c = FAM.counts
    assert c.mixer_params(CFG, c.DELTA) == (
        2048 * (2 * 2048 + 2 * 4096 + 64) + 4 * 8192 + 4096 * 2048)
    assert round(c.mixer_params(CFG, c.DELTA) / 1e6, 2) == 33.72
    assert c.mixer_params(CFG, c.ATTENTION) == (2048 * 8192 + 2 * 2048 * 512
                                                + 4096 * 2048)
    assert round(c.mixer_params(CFG, c.ATTENTION) / 1e6, 2) == 27.26
    assert round(c.outside_expert_params(CFG) / 1e6, 2) == 4.20
    assert c.expert_params(CFG) == 3 * 2048 * 512
    assert round(c.params_total(CFG) / 1e9, 2) == 3.99
    assert round(c.weight_bytes(CFG) / 1e9, 2) == 7.98
    assert c.state_bytes_per_slot(CFG) == 3 * 32 * 128 * 128 * 4
    assert round(c.state_bytes_per_slot(CFG) / 3 / 1e6, 2) == 2.10
    assert c.window_bytes_per_slot(CFG) == 3 * 3 * 8192 * 2
    assert c.kv_bytes_per_token(CFG) == 2 * 2 * 256 * 2 == 2048
    # the slot cache at 16 x 33792: K/V 1.11 GB, states and windows 0.10
    s = CFG["serving"]
    assert round(s["slots"] * s["max_len"] * 2048 / 1e9, 2) == 1.11
    assert round(s["slots"] * (c.state_bytes_per_slot(CFG)
                               + c.window_bytes_per_slot(CFG)) / 1e9, 2) == 0.1
    # 10 picks of 512 a token: 8 rows touch about 37 of the 256 held, 16 rows
    # about 69 (an even router)
    assert round(c.experts_touched(CFG, 8)) == 37
    assert round(c.experts_touched(CFG, 16)) == 69
    assert c.picks_held(CFG) == 5.0


def test_a_step_counts_the_mixers_the_experts_it_picks_and_the_caches():
    """A decode step's operations are two a parameter a token over the
    mixers, the shared expert, its picks and the head, plus the delta rule
    and attention over its context; its bytes hold the weights outside the
    experts, the experts its rows touch, the head, and the caches."""
    c = FAM.counts
    per_token = (3 * c.mixer_params(CFG, c.DELTA)
                 + c.mixer_params(CFG, c.ATTENTION)
                 + 4 * (c.outside_expert_params(CFG)
                        + 5.0 * c.expert_params(CFG))
                 + c.head_params(CFG))
    assert c.token_params(CFG) == pytest.approx(per_token)
    f1, b1 = c.decode_step_work(CFG, [20000])
    delta = 3 * (2 * 4 * 8192 + 32 * 7 * 128 * 128)
    attn = 4 * 16 * 256 * 20000
    assert f1 == pytest.approx(2 * per_token + delta + attn)
    caches = 2048 * 20001 + 2 * (c.state_bytes_per_slot(CFG)
                                 + c.window_bytes_per_slot(CFG))
    assert c.cache_bytes(CFG, [20000]) == caches
    assert c.delta_state_step_bytes(CFG, 16) == 16 * 2 * (
        c.state_bytes_per_slot(CFG) + c.window_bytes_per_slot(CFG))
    weights1 = c.streamed_weight_bytes(CFG, 1)
    assert b1 == pytest.approx(weights1 + caches, rel=0.001)
    # 16 rows read some 69 experts a layer, not all 256
    assert c.streamed_weight_bytes(CFG, 16) < 0.45 * c.streamed_weight_bytes(
        CFG, 1e9)
    fp, bp = c.prefill_work(CFG, 4096)
    body = per_token - c.head_params(CFG)
    assert 2 * body * 4096 < fp < 1.1 * 2 * body * 4096 + 4 * 16 * 256 * 4096 ** 2
    assert bp > c.streamed_weight_bytes(CFG, 4096) + 4096 * 2048
    # chunked: the second chunk's queries attend the first's keys too
    f2, _b = c.prefill_work(CFG, 4096, 4096)
    assert f2 - fp == pytest.approx(4 * 16 * 256 * 4096 * 4096)


def test_the_delta_rule_is_the_recurrence_by_hand():
    """`S = exp(g) S; S += k (beta (v - S^T k))^T; o = S^T q`, a token at a
    time in float64, against the reference's scan."""
    rng = np.random.default_rng(0)
    t, h, dk, dv = 40, 2, 5, 3
    q, k = rng.standard_normal((2, t, h, dk))
    v = rng.standard_normal((t, h, dv))
    g = -rng.uniform(0, 2, (t, h))
    beta = rng.uniform(0, 1, (t, h))
    s = np.zeros((h, dk, dv))
    want = np.zeros((t, h, dv))
    for i in range(t):
        s = s * np.exp(g[i])[:, None, None]
        err = v[i] - np.einsum("hd,hde->he", k[i], s)
        s = s + k[i][..., None] * (beta[i][:, None] * err)[:, None, :]
        want[i] = np.einsum("hd,hde->he", q[i], s)
    with jax.default_matmul_precision("highest"):
        got = REF._delta_rule(*(jnp.asarray(x, jnp.float32)
                                for x in (q, k, v, g, beta)))
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()
    # the read before the write: the plain outer-product update is not it
    plain, out = np.zeros((h, dk, dv)), np.zeros((t, h, dv))
    for i in range(t):
        plain = (plain * np.exp(g[i])[:, None, None]
                 + k[i][..., None] * (beta[i][:, None] * v[i])[:, None, :])
        out[i] = np.einsum("hd,hde->he", q[i], plain)
    assert np.abs(out - want).max() > 0.5


def test_the_partial_rotation_and_the_l2_norm_by_hand():
    """Rotate-half over the first 8 of 12 channels: channel i turns with
    channel i + 4 by `t * theta^(-i / 4)`, the last 4 pass."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((6, 2, 12))
    got = np.asarray(REF._rope(jnp.asarray(x, jnp.float32), 1e7, 8))
    assert np.abs(got[:, :, 8:] - x[:, :, 8:]).max() < 1e-6
    assert np.abs(got[0] - x[0]).max() < 1e-6
    for t in (1, 5):
        for i in range(4):
            ang = t * 1e7 ** (-i / 4)
            want = (x[t, :, i] * np.cos(ang) - x[t, :, i + 4] * np.sin(ang),
                    x[t, :, i + 4] * np.cos(ang) + x[t, :, i] * np.sin(ang))
            assert np.abs(got[t, :, i] - want[0]).max() < 1e-5
            assert np.abs(got[t, :, i + 4] - want[1]).max() < 1e-5
    n = np.asarray(REF._l2norm(jnp.asarray(x, jnp.float32)))
    assert np.abs(np.linalg.norm(n, axis=-1) - 1).max() < 1e-5


def test_the_weights_at_the_published_widths_and_drawn_small():
    """Shapes at the published size (nothing of it is drawn here: the
    projections in the published layout, grouped by key head), and at the
    rehearsal's widths the sizes the configuration's `assumed` states."""
    spec = {name: (shape, scale) for name, shape, _k, scale, _s
            in FAM.weights.spec(CFG)}
    assert spec["embed"][0] == (16, 9496, 2048)          # folded to [V, h]
    assert spec["head"][0] == (2048, 151936)
    assert spec["r0_w_qkvz"][0] == (3, 2048, 16, 768)
    assert spec["r0_w_ba"][0] == (3, 2048, 16, 4)
    assert spec["r0_conv_w"][0] == (3, 4, 8192)
    assert spec["r0_w_out"][0] == (3, 32, 128, 2048)
    assert spec["r1_wq"][0] == (1, 2048, 16, 512)
    assert spec["r1_wk"][0] == spec["r1_wv"][0] == (1, 2048, 2, 256)
    assert spec["r1_wo"][0] == (1, 16, 256, 2048)
    assert spec["r0_router"][0] == (3, 2048, 512)
    assert spec["r0_w1"][0] == (3, 256, 2048, 1024)
    assert spec["r1_wsg"][0] == (1, 2048, 1)
    assert spec["r0_router"][1] == pytest.approx(2 / 2048 ** 0.5)
    tiny = dict(CFG, **{k: v for k, v in CFG["rehearse"].items()
                        if k not in ("serving", "dtype", "check_limit")},
                as_run={"dtype": "float32"})
    w = FAM.weights.make_weights(tiny, 5)
    assert w["embed"].shape == (512, 64) and w["head"].shape == (64, 512)
    a = np.exp(np.asarray(w["r0_A_log"]))
    assert a.dtype == np.float32 and (0 <= a).all() and (a <= 16).all()
    assert np.asarray(w["r0_dt_bias"]).tolist() == [[1.0] * 4] * 2
    assert np.asarray(w["r1_qn"]).tolist() == [[2.0] * 16]
    assert np.asarray(w["norm_f"]).tolist() == [1.0] * 64
    again = FAM.weights.make_weights(tiny, 5)
    assert all(np.array_equal(np.asarray(w[k]), np.asarray(again[k]))
               for k in w)


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's `config` is in the file unchanged, but
    for those `reduced` names, whose published values it keeps; what is
    not in the catalog is `assumed`."""
    catalog = os.environ.get("MODEL_CATALOG", "")
    if not os.path.exists(catalog):
        pytest.skip("MODEL_CATALOG names no catalog of published configs")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert CFG["source"] == row["source_url"]
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_experts"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG["published"][key] == value
        else:
            assert CFG[key] == value, key
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert CFG["layer_types"] == period
    assert CFG["published"]["layer_types"] == period * 12
    assert CFG["num_experts"] == 256 and CFG["first_local_expert"] == 0
    assert "12 pipeline stages" in CFG["deployment"]
    for key in ("qkvz_layout", "norms", "delta_init", "router",
                "shared_expert", "layer_types", "weights", "torch_dtype"):
        assert key in CFG["assumed"]
    assert any("multi-token-prediction" in d for d in CFG["departures"])
    assert any("padded position" in d for d in CFG["departures"])
    assert CFG["as_run"]["dtype"] == "bfloat16"
    # weights and the pool fill at least 50% of the chip
    assert CFG["memory_reckoning_gb"]["reckoned"] / 16.909 > 0.5


def _run(stats0, stats1):
    return harness.RunData(
        cfg=CFG, device={"kind": "TPU v5 lite"}, family=FAM, w0=0.0, w1=50.0,
        stats0=dict(stats0, dispatches=1, prefix_cache={}),
        stats1=dict(stats1, dispatches=9, prefix_cache={}), spans=[],
        modules={"jit_run": [1.0, 4]})


@pytest.mark.parametrize("metric", ["delta_state_moved_share",
                                    "expert_read_over_touched"])
def test_the_readers_find_nothing_in_a_run_without_their_counters(metric):
    """The parent's program keeps no `experts_read`, and a stack without a
    state-space or Gated DeltaNet layer no `state_rows_*`; a window with no
    decode step has nothing to divide by: None, and no exception."""
    fn, kw = MAN.reader(metric)
    assert fn(_run({}, {}), **kw) is None
    idle = {"state_rows_moved": 5, "state_rows_held": 8,
            "experts_read": 7, "experts_touched": 7}
    assert fn(_run(idle, idle), **kw) is None


def test_the_readers_read_the_window():
    """What the window's steps added: 3 live rows of 16 over three Gated
    DeltaNet layers and 40 steps; 37 experts touched a layer a step and
    read on the grouped branch (1.0), all 36 held read where 35 were
    touched on granite's all-experts branch."""
    share, kw = MAN.reader("delta_state_moved_share")
    before = {"state_rows_moved": 100, "state_rows_held": 400}
    after = {"state_rows_moved": 100 + 3 * 3 * 40,
             "state_rows_held": 400 + 16 * 3 * 40}
    assert share(_run(before, after), **kw) == pytest.approx(3 / 16)
    ratio, kw = MAN.reader("expert_read_over_touched")
    grouped = {"experts_read": 4 * 40 * 37, "experts_touched": 4 * 40 * 37}
    assert ratio(_run({}, grouped), **kw) == 1.0
    every = {"experts_read": 10 * 36, "experts_touched": 10 * 35}
    assert ratio(_run({"experts_read": 0, "experts_touched": 0}, every),
                 **kw) == pytest.approx(36 / 35)


def test_the_new_metrics_and_cell_are_entries_and_files():
    per_layer = {m["name"]: m for m in MAN.data["per_layer"]}
    assert per_layer["delta_state_moved_share"]["workloads"] == [CELL["name"]]
    assert per_layer["expert_read_over_touched"]["workloads"] == [
        "granite-4.0-h-small.chat", CELL["name"]]
    for name in ("delta_state_moved_share", "expert_read_over_touched"):
        m = per_layer[name]
        assert (m["moves"], m["better"], m["source"]) == (
            "tpot_p90_ms", "lower", "program_counter")
        assert m["layer"] == "model step and kernels"
    reported = {m["name"] for m in MAN.per_layer(CELL["name"])}
    assert {"delta_state_moved_share", "expert_read_over_touched",
            "recurrent_state_gb", "state_splice_p50_ms", "prefill_chunk_ms",
            "decode_step_roofline", "prefill_roofline",
            "serve_mfu"} <= reported
    # lists that tests of earlier cells pin whole
    assert not {"state_moved_share", "experts_touched_share",
                "expert_held_pick_share", "expert_load_max_over_mean",
                "mixer_cache_share", "context_read_share"} & reported
    assert {m["name"] for m in MAN.end_to_end(CELL["name"])} == {
        "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200
    mix = MAN.mix(CELL)
    assert mix["loop"] == "open" and mix["arrivals"] == "poisson"
    assert mix["order"] == "fixed" and mix["warm_s"] == 10
    assert mix["sampling"]["temperature"] == 0.0 and mix["sharing"] is None
    assert mix["prompt"] == {"dist": "loguniform", "min": 8192,
                             "max": 32768}
    assert mix["output"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.6, "min": 64, "max": 1024}
    s = CFG["serving"]
    assert mix["prompt"]["max"] <= max(s["prompt_buckets"])
    assert mix["prompt"]["max"] + mix["output"]["max"] <= s["max_len"]
    assert s["max_len"] % s["kv_block_size"] == 0
    assert s["prefill_chunk"] == 4096 and s["slots"] == 16


def test_the_check_limit_lies_between_its_readings():
    """The limit on `max_gap` lies above every reading of the program and
    under every reading of the fp8 control the configuration records."""
    chk = CFG["check"]
    program = chk["readings"]["program_max_gap"]
    control = chk["readings"]["fp8_control_max_gap"]
    assert len(program) >= 6 and len(control) >= 3
    limit = chk["limit"]
    assert max(program) < limit < min(control)
    assert CFG["rehearse"]["check_limit"] > 0
