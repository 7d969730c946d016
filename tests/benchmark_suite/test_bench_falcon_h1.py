"""The family `falcon_h1` beside the benchmark: its four files found by the
configuration's `family` key, its counts against hand sums at the published
widths (430 M parameters a layer, 10.5 GB), its reference's scan, grouped
norm and rotary positions against values computed by hand in float64, the
configuration against the catalog, the new cell walked at toy widths, and
its reader finding nothing to read on a run of a program without its
gauges."""
import argparse
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, manifest

MAN = manifest.Manifest()
CELL = MAN.cell("falcon-h1-34b-instruct.reasoning")
CFG = MAN.config(CELL)
FAM = MAN.family(CFG)
REF = FAM.reference


def test_the_family_is_four_files_found_by_name():
    assert CFG["family"] == "falcon_h1" == FAM.name
    top = os.path.join(manifest.ROOT, "benchmark", "families", FAM.name)
    assert sorted(f for f in os.listdir(top) if f.endswith(".py")) == [
        "counts.py", "program.py", "reference.py", "weights.py"]
    for part, fn in (("weights", "make_weights"), ("reference", "logits_at"),
                     ("counts", "decode_step_work"), ("counts", "prefill_work"),
                     ("counts", "cache_bytes"), ("program", "build"),
                     ("program", "derive")):
        assert callable(getattr(getattr(FAM, part), fn))
    # the reference, the counts and the weights import nothing of the program
    for part in ("reference", "counts", "weights"):
        with open(os.path.join(top, part + ".py")) as f:
            assert "idunno_tpu" not in f.read().split('"""', 2)[2]


def test_counts_at_the_published_widths():
    """ISSUE 36's arithmetic: attention 31.5 M, the state-space mixer 68.4 M,
    the MLP 330.3 M: 430 M a layer, 0.86 GB; six layers and the whole
    vocabulary twice 10.5 GB."""
    c = FAM.counts
    assert c.attention_params(CFG) == (5120 * 2560 + 2 * 5120 * 512
                                       + 2560 * 5120)
    assert round(c.attention_params(CFG) / 1e6, 1) == 31.5
    assert c.ssm_params(CFG) == 5120 * 9248 + 4096 * 5120 + 5 * 5120
    assert round(c.ssm_params(CFG) / 1e6, 1) == 68.3
    assert c.mlp_params(CFG) == 3 * 5120 * 21504
    assert round(c.mlp_params(CFG) / 1e6, 1) == 330.3
    assert round(c.layer_params(CFG) / 1e6) == 430
    assert round(2 * c.layer_params(CFG) / 1e9, 2) == 0.86
    assert c.head_params(CFG) == 261120 * 5120
    assert round(2 * 2 * c.head_params(CFG) / 1e9, 2) == 5.35
    assert c.params_total(CFG) == 6 * c.layer_params(CFG) + 2 * 261120 * 5120
    assert round(c.weight_bytes(CFG) / 1e9, 1) == 10.5
    # a slot: 6 x 4.19 MB of float32 state, 6 x 30 KB of window, K/V at 12 KB
    # a token
    assert c.state_bytes_per_slot(CFG) == 6 * 32 * 128 * 256 * 4
    assert round(c.state_bytes_per_slot(CFG) / 1e6, 1) == 25.2
    assert c.window_bytes_per_slot(CFG) == 6 * 3 * 5120 * 2
    assert c.kv_bytes_per_token(CFG) == 6 * 2 * 4 * 128 * 2 == 12288


def test_a_step_counts_both_mixers_the_mlp_and_the_head():
    """Nothing is left out of a decode step or a prefill: the operations
    are at least two a parameter a token over both mixers, the MLP and the
    head, and the bytes hold the weights, the head and BOTH caches."""
    c = FAM.counts
    per_token = 6 * (c.attention_params(CFG) + c.ssm_params(CFG)
                     + c.mlp_params(CFG)) + c.head_params(CFG)
    assert c.token_params(CFG) == per_token
    f1, b1 = c.decode_step_work(CFG, [500])
    f24, b24 = c.decode_step_work(CFG, [500] * 24)
    assert f24 == pytest.approx(24 * f1)
    scan = 6 * 4 * 32 * 128 * 256
    attn = 6 * 4 * 20 * 128 * 500
    assert f1 == pytest.approx(2 * per_token + scan + attn)
    caches1 = 12288 * 501 + 2 * (c.state_bytes_per_slot(CFG)
                                 + c.window_bytes_per_slot(CFG))
    assert c.cache_bytes(CFG, [500]) == caches1
    assert b1 == pytest.approx(2 * per_token + caches1, rel=0.001)
    # a further row: its caches and 1.85 MB of activations (3%)
    assert b24 - b1 == pytest.approx(23 * caches1, rel=0.04)
    # the two caches of 32 full rows: 1.6 GB of K/V read, 1.6 GB of state
    # read and written, against 7.8 GB of weights and head
    full = c.cache_bytes(CFG, [4095] * 32)
    assert round(full / 1e9, 1) == 3.2
    assert round(c.streamed_weight_bytes(CFG) / 1e9, 1) == 7.8
    fp, bp = c.prefill_work(CFG, 512)
    body = per_token - c.head_params(CFG)
    assert fp > 2 * body * 512 + 2 * c.head_params(CFG)
    assert fp < 1.05 * 2 * body * 512 + 2 * c.head_params(CFG)
    assert bp > c.streamed_weight_bytes(CFG) + 512 * 12288
    # chunked: the second chunk's queries attend the first's keys too
    f2, _b = c.prefill_work(CFG, 512, 512)
    assert f2 - fp == pytest.approx(6 * 4 * 20 * 128 * 512 * 512)


def test_the_scan_by_groups_is_the_recurrence():
    """`S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t`, `y_t = S_t
    C_t`, a token at a time in float64 with TWO groups (each group's heads
    read their own B and C), against the reference's blocked product."""
    rng = np.random.default_rng(0)
    t, g, e, p, n = REF._SBLOCK * 2, 2, 3, 4, 5
    x = rng.standard_normal((t, g, e, p))
    delta = rng.uniform(0.001, 0.3, (t, g, e))
    a = -np.array([[1.0, 4.0, 16.0], [2.0, 8.0, 3.0]])
    bm, cm = rng.standard_normal((2, t, g, n))
    want = np.zeros((t, g, e, p))
    state = np.zeros((g, e, p, n))
    for i in range(t):
        state = (np.exp(delta[i] * a)[..., None, None] * state
                 + (delta[i][..., None] * x[i])[..., None]
                 * bm[i][:, None, None, :])
        want[i] = np.einsum("gepn,gn->gep", state, cm[i])
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        got = REF._scan(*(jnp.asarray(v, f32) for v in (x, delta, a, bm, cm)),
                        None)
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()
    # the groups do not read each other's B and C
    swapped = REF._scan(*(jnp.asarray(v, f32) for v in (
        x, delta, a, bm[:, ::-1], cm)), None)
    assert np.abs(np.asarray(swapped) - want).max() > 0.1


def test_the_grouped_norm_and_the_rotation_by_hand():
    rng = np.random.default_rng(1)
    y = rng.standard_normal((7, 12)) * np.repeat([1.0, 5.0], 6)
    scale = 1 + 0.1 * rng.standard_normal(12)
    want = np.concatenate([
        h / np.sqrt((h ** 2).mean(-1, keepdims=True) + 1e-5)
        for h in (y[:, :6], y[:, 6:])], -1) * scale
    got = REF._rms_grouped(jnp.asarray(y, jnp.float32),
                           jnp.asarray(scale, jnp.float32), 1e-5, 2)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    # rotate-half at theta 1e11: channel i turns with channel i + 4 by
    # t * theta^(-i / 4); position 0 stays, and a query . key depends on
    # the distance alone
    x = rng.standard_normal((6, 2, 8))
    got = np.asarray(REF._rope(jnp.asarray(x, jnp.float32), 1e11))
    assert np.abs(got[0] - x[0]).max() < 1e-6
    for t in (1, 5):
        for i in range(4):
            ang = t * 1e11 ** (-i / 4)
            want = (x[t, :, i] * np.cos(ang) - x[t, :, i + 4] * np.sin(ang),
                    x[t, :, i + 4] * np.cos(ang) + x[t, :, i] * np.sin(ang))
            assert np.abs(got[t, :, i] - want[0]).max() < 1e-5
            assert np.abs(got[t, :, i + 4] - want[1]).max() < 1e-5
    q = np.tile(x[:1], (6, 1, 1))
    turned = np.asarray(REF._rope(jnp.asarray(q, jnp.float32), 1e11))
    dots = np.einsum("thd,thd->th", turned[1:], turned[:-1])
    assert np.abs(dots - dots[0]).max() < 1e-4


def test_the_weights_are_drawn_for_the_published_multipliers():
    """Shapes at the published size (nothing of it is drawn here), and at
    the rehearsal's widths the sizes the configuration's `assumed.weights`
    states: activation x multiplier of order 1."""
    spec = {name: (shape, scale) for name, shape, _k, scale, _s
            in FAM.weights.spec(CFG)}
    assert spec["embed"][0] == (255, 1024, 5120)       # folded to [V, h]
    assert spec["head"][0] == (5120, 261120)
    assert spec["w_in"][0] == (6, 5120, 4096 + 5120 + 32)
    assert spec["conv_w"][0] == (6, 4, 5120)
    assert spec["wq"][0] == (6, 5120, 20, 128)
    assert spec["wk"][0] == (6, 5120, 4, 128)
    assert spec["wo"][0] == (6, 20, 128, 5120)
    assert spec["wg"][0] == spec["wu"][0] == (6, 5120, 21504)
    assert spec["wd"][0] == (6, 21504, 5120)
    assert spec["embed"][1] == pytest.approx(1 / 5.656854249492381)
    assert spec["head"][1] == pytest.approx(128 / 5120 ** 0.5)
    assert spec["wk"][1] == pytest.approx(
        1 / (5120 ** 0.5 * 0.011048543456039804))
    tiny = dict(CFG, **{k: v for k, v in CFG["rehearse"].items()
                        if k not in ("serving", "dtype", "check_limit")},
                as_run={"dtype": "float32"})
    w = FAM.weights.make_weights(tiny, 5)
    assert w["embed"].shape == (512, 64) and w["head"].shape == (64, 512)
    a = -np.exp(np.asarray(w["A_log"]))
    assert a.dtype == np.float32 and (-16 <= a).all() and (a <= -1).all()
    dt = np.log1p(np.exp(np.asarray(w["dt_bias"])))
    assert (1e-3 * 0.99 <= dt).all() and (dt <= 1e-1 * 1.01).all()
    assert np.asarray(w["D"]).tolist() == [[1.0] * 4] * 3
    assert np.asarray(w["norm_f"]).tolist() == [1.0] * 64
    # each segment of the in-projection at 1 / (0.25 x its multiplier)
    w_in = np.asarray(w["w_in"])
    seg = np.split(w_in, np.cumsum([64, 64, 32, 32]), axis=-1)
    for part, mult in zip(seg, CFG["ssm_multipliers"]):
        assert part.std() * 0.25 * mult * 8 == pytest.approx(1, rel=0.15)
    again = FAM.weights.make_weights(tiny, 5)
    assert all(np.array_equal(np.asarray(w[k]), np.asarray(again[k]))
               for k in w)


def test_the_rehearsal_walks_the_new_cell():
    """The whole command at toy widths on the CPU: every request served as
    the reference has it, both caches' gauges read by the new reader."""
    args = argparse.Namespace(workload=CELL["name"], seed=2147484001,
                              seconds=3.0, trace=1, rehearse=True,
                              control="", root=None)
    result, summary = harness.run(args, harness.clock())
    assert result["rehearse"] is True and result["correct"] is False
    assert result["verdict_at_toy_size"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    got = result["metrics"]
    assert 0.0 < got["rehearse.mixer_cache_share"]["value"] < 1.0
    assert got["rehearse.recurrent_state_gb"]["value"] > 0
    assert "rehearse.state_splice_p50_ms" in got
    assert "rehearse.sparse_attended_share" not in got
    assert "rehearse.expert_held_pick_share" not in got
    assert summary["compiles_in_window"] == 0


def _run(stats1, **kw):
    return harness.RunData(
        cfg=CFG, device={"kind": "TPU v5 lite"}, family=FAM, w0=0.0, w1=50.0,
        stats0={"dispatches": 1, "prefix_cache": {}}, stats1=stats1,
        spans=[], modules={"jit_run": [1.0, 4]}, **kw)


def test_the_reader_finds_nothing_in_a_run_without_its_source():
    """The parent's program has no `kv_cache_bytes`; another family's counts
    do not tell the caches apart; a window without a decode step has no
    step: the reader returns None and does not raise."""
    fn, kw = MAN.reader("mixer_cache_share")
    steps = [(1.0, 1.1, 2, [700, 900])]
    assert fn(_run({"dispatches": 9, "recurrent_state_bytes": 5},
                   window_step_contexts=steps), **kw) is None
    assert fn(_run({"kv_cache_bytes": 7, "recurrent_state_bytes": 5}),
              **kw) is None
    other = MAN.family(MAN.config(MAN.cell("granite-4.0-h-small.chat")))
    run = _run({"kv_cache_bytes": 7, "recurrent_state_bytes": 5},
               window_step_contexts=steps)
    run.family = other
    assert fn(run, **kw) is None


def test_the_reader_reads_what_the_program_holds():
    """32 slots x 4096: 1.61 GB of K/V read and 0.81 GB of state read and
    written, against the weights and the head: about 0.29; where the
    program counts that its steps read a quarter of the context it holds,
    the K/V's part falls to a quarter."""
    fn, _kw = MAN.reader("mixer_cache_share")
    c = FAM.counts
    kv = 32 * 4096 * c.kv_bytes_per_token(CFG)
    state = 32 * (c.state_bytes_per_slot(CFG) + c.window_bytes_per_slot(CFG))
    steps = [(1.0, 1.1, 2, [700, 900]), (1.1, 1.2, 0, [])]
    got = fn(_run({"kv_cache_bytes": kv, "recurrent_state_bytes": state},
                  window_step_contexts=steps))
    other = c.decode_step_work(CFG, [700, 900])[1] - c.cache_bytes(
        CFG, [700, 900])
    assert got == pytest.approx((kv + 2 * state) / (kv + 2 * state + other))
    assert 0.28 < got < 0.30
    less = fn(_run({"kv_cache_bytes": kv, "recurrent_state_bytes": state,
                    "decode_context_read": 250,
                    "decode_context_held": 1000},
                   window_step_contexts=steps))
    assert less == pytest.approx(
        (kv / 4 + 2 * state) / (kv / 4 + 2 * state + other))


def test_the_new_metric_and_cell_are_entries_and_files():
    per_layer = {m["name"]: m for m in MAN.data["per_layer"]}
    m = per_layer["mixer_cache_share"]
    assert m["workloads"] == [CELL["name"]]
    assert (m["moves"], m["better"], m["unit"]) == ("tpot_p90_ms", "lower",
                                                    "share")
    assert m["layer"] == "model step and kernels"
    reported = {m["name"] for m in MAN.per_layer(CELL["name"])}
    assert {"mixer_cache_share", "recurrent_state_gb", "state_splice_p50_ms",
            "prefill_chunk_ms", "decode_step_roofline", "prefill_roofline",
            "serve_mfu"} <= reported
    assert not {"sparse_attended_share", "expert_held_pick_share",
                "context_read_share"} & reported
    assert {m["name"] for m in MAN.end_to_end(CELL["name"])} == {
        "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200
    mix = MAN.mix(CELL)
    assert mix["loop"] == "open" and mix["order"] == "fixed"
    assert mix["warm_s"] == 10 and mix["sampling"]["temperature"] == 0.0
    assert mix["prompt"] == {"dist": "lognormal", "median": 384,
                             "sigma": 0.8, "min": 64, "max": 2048}
    assert mix["output"] == {"dist": "lognormal", "median": 640,
                             "sigma": 0.5, "min": 256, "max": 1536}
    s = CFG["serving"]
    assert mix["prompt"]["max"] <= max(s["prompt_buckets"])
    assert mix["prompt"]["max"] + mix["output"]["max"] <= s["max_len"]
    assert s["max_len"] % s["kv_block_size"] == 0


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's `config` is in the file unchanged, but
    for the depth, which `reduced` names; what is not in it is `assumed`."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert CFG["source"] == row["source_url"]
    assert CFG["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG["published"][key] == value
        else:
            assert CFG[key] == value, key
    assert CFG["num_hidden_layers"] == 6 and row["layers"] == 72
    assert "twelve pipeline stages" in CFG["deployment"]
    for key in ("mamba_split", "mamba_init", "rope", "multipliers",
                "weights", "torch_dtype"):
        assert key in CFG["assumed"]
    assert CFG["as_run"]["dtype"] == "bfloat16" and CFG["departures"]
    entry = next(c for c in MAN.data["configs"] if c["name"] == CFG["name"])
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"] and len(entry["why"]) <= 200
    # weights and the pool fill at least 70% of the chip
    gb = CFG["memory_reckoning_gb"]
    assert gb["reckoned"] / 16.909 > 0.7
