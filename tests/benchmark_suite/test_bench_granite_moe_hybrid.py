"""The family `granite_moe_hybrid` beside the benchmark: its four files
found by the configuration's `family` key, its reference against values
computed by hand at one tiny size (the scan against its token-by-token
recurrence, the routed layer against a loop over tokens), its counts
against hand sums at the published widths, the new cell walked at toy
widths, and its readers finding nothing to read on a run of a program
without their counters."""
import argparse
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, manifest

MAN = manifest.Manifest()
CELL = MAN.cell("granite-4.0-h-small.chat")
CFG = MAN.config(CELL)
FAM = MAN.family(CFG)
REF = FAM.reference
READERS = ("expert_held_pick_share", "expert_load_max_over_mean",
           "experts_touched_share")


def test_the_family_is_four_files_found_by_name():
    assert CFG["family"] == "granite_moe_hybrid" == FAM.name
    top = os.path.join(manifest.ROOT, "benchmark", "families", FAM.name)
    assert sorted(f for f in os.listdir(top) if f.endswith(".py")) == [
        "counts.py", "program.py", "reference.py", "weights.py"]
    for part, fn in (("weights", "make_weights"), ("reference", "logits_at"),
                     ("counts", "decode_step_work"), ("counts", "prefill_work"),
                     ("program", "build"), ("program", "derive")):
        assert callable(getattr(getattr(FAM, part), fn))
    # the reference and the counts import nothing of the program
    for part in ("reference", "counts", "weights"):
        with open(os.path.join(top, part + ".py")) as f:
            assert "idunno_tpu" not in f.read().split('"""', 2)[2]


def test_the_quadratic_form_is_the_recurrence():
    """`S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t`, `y_t = S_t
    C_t`, a token at a time in float64, against the reference's blocked
    product."""
    rng = np.random.default_rng(0)
    t, g, e, p, n = REF._SBLOCK * 2, 1, 3, 4, 5
    x = rng.standard_normal((t, g, e, p))
    delta = rng.uniform(0.001, 0.3, (t, g, e))
    a = -np.array([[1.0, 4.0, 16.0]])
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


def test_the_routed_layer_by_hand():
    """Top-2 of 6 router logits, a softmax over those two alone, the held
    experts' (2..4) terms only: a loop over tokens in float64."""
    rng = np.random.default_rng(1)
    t, d, e, f, first, held = 20, 8, 6, 4, 2, 3
    u = rng.standard_normal((t, d))
    router = rng.standard_normal((d, e))
    w1 = rng.standard_normal((held, d, 2 * f)) * 0.3
    w2 = rng.standard_normal((held, f, d)) * 0.3
    want = np.zeros((t, d))
    for i in range(t):
        logits = u[i] @ router
        top = np.argsort(-logits)[:2]
        gates = np.exp(logits[top] - logits[top].max())
        gates /= gates.sum()
        for j, g in zip(top, gates):
            if first <= j < first + held:
                h = u[i] @ w1[j - first]
                want[i] += g * ((h[:f] / (1 + np.exp(-h[:f])) * h[f:])
                                @ w2[j - first])
    at = {k: jnp.asarray(v, jnp.float32) for k, v in
          (("router", router), ("w1", w1), ("w2", w2))}.__getitem__
    uj = jnp.asarray(u, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = REF._routed(uj, uj, at, 2, first, None)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    assert np.abs(want).max() > 0.1


def test_the_convolution_is_causal_with_zeros_before_the_first_token():
    """`c'_t = silu(sum_j w[j] c_{t-3+j} + b)`: through `_mamba` with an
    identity in-projection that would be long; by the reference's own
    padding rule on one channel instead."""
    c = np.arange(1.0, 7.0)
    w = np.array([0.5, -1.0, 2.0, 1.0])
    padded = np.pad(c, (3, 0))
    want = [float(padded[i:i + 4] @ w) for i in range(6)]
    assert want[:2] == [1.0, 2.0 + 2.0]        # c_0; 2 c_0 + c_1
    got = sum(jnp.pad(jnp.asarray(c), (3, 0))[j:j + 6] * w[j]
              for j in range(4))
    assert np.allclose(np.asarray(got), want)


def test_counts_at_the_published_widths():
    c = FAM.counts
    assert c.mixer_params(CFG, "mamba") == (
        4096 * (8192 + 8448 + 128) + 8192 * 4096)
    assert round(c.mixer_params(CFG, "mamba") / 1e6, 1) == 102.2
    assert c.mixer_params(CFG, "attention") == (
        2 * 4096 * 4096 + 2 * 4096 * 1024)
    assert c.expert_params(CFG) == 4096 * 1536 + 768 * 4096
    assert round(c.expert_params(CFG) / 1e6, 3) == 9.437
    assert c.outside_expert_params(CFG) == (
        4096 * 3072 + 1536 * 4096 + 4096 * 72)
    assert c.picks_held(CFG) == 5.0                  # 10 x 36 / 72
    # ISSUE 33's arithmetic: 9.93 GB with the norms and the convolution
    # (0.9 M parameters), which these counts leave out
    assert round(c.weight_bytes(CFG) / 1e9, 2) == 9.92
    assert c.state_bytes_per_slot(CFG) == 9 * 128 * 64 * 128 * 4
    assert round(c.state_bytes_per_slot(CFG) / 1e6, 1) == 37.7
    assert c.window_bytes_per_slot(CFG) == 9 * 3 * 8448 * 2
    assert c.kv_bytes_per_token(CFG) == 4096
    # what one token multiplies: 5 of the 36 held experts a layer, not 36
    assert c.token_params(CFG) == pytest.approx(
        9 * c.mixer_params(CFG, "mamba") + c.mixer_params(CFG, "attention")
        + 10 * (c.outside_expert_params(CFG) + 5 * c.expert_params(CFG))
        + 100352 * 4096)
    assert c.token_params(CFG) < 0.42 * c.params_total(CFG)


def test_a_decode_step_streams_the_experts_its_rows_pick():
    c = FAM.counts
    assert c.experts_touched(CFG, 1) == pytest.approx(5.0)
    assert c.experts_touched(CFG, 20) == pytest.approx(
        36 * (1 - (62 / 72) ** 20))
    assert 0.94 < c.experts_touched(CFG, 20) / 36 < 0.96
    f1, b1 = c.decode_step_work(CFG, [500])
    f32, b32 = c.decode_step_work(CFG, [500] * 32)
    fixed = c.params_total(CFG) - 10 * 36 * c.expert_params(CFG)
    # one row: 5 experts a layer, one state and window, 500 tokens of K/V
    assert b1 == pytest.approx(
        2 * (fixed + 10 * 5 * c.expert_params(CFG))
        + 2 * (c.state_bytes_per_slot(CFG) + c.window_bytes_per_slot(CFG))
        + 4096 * 501, rel=0.01)
    # 32 rows touch nearly every held expert: under the whole weights,
    # plus 32 states read and written
    assert b32 < c.weight_bytes(CFG) + 32 * 2 * 38.2e6 + 32 * 501 * 4096 + 4e7
    assert b32 > 0.98 * c.weight_bytes(CFG) + 32 * 2 * 37.7e6
    assert f32 == pytest.approx(32 * f1)
    fp, bp = c.prefill_work(CFG, 512)
    assert fp > 2 * (c.token_params(CFG) - 100352 * 4096) * 512
    assert fp < 1.1 * 2 * (c.token_params(CFG) - 100352 * 4096) * 512 + 1e10
    assert bp > c.weight_bytes(CFG) * 0.99


def test_the_weights_are_the_share_and_the_router_is_whole():
    """Shapes alone (nothing of the published size is drawn here)."""
    spec = {name: shape for name, shape, *_ in FAM.weights.spec(CFG)}
    assert spec["embed"] == (100352, 4096) and "w_head" not in spec
    assert spec["r0_router"] == (5, 4096, 72)
    assert spec["r0_w1"] == (5, 36, 4096, 1536)
    assert spec["r0_w2"] == (5, 36, 768, 4096)
    assert spec["r0_w_in"] == (5, 4096, 8192 + 8448 + 128)
    assert spec["r0_conv_w"] == (5, 4, 8448)
    assert spec["r1_wq"] == (1, 4096, 32, 128)
    assert spec["r1_wk"] == (1, 4096, 8, 128)
    assert spec["r2_ws1"] == (4, 4096, 3072)
    assert FAM.weights.runs_of(CFG) == [("mamba", 5), ("attention", 1),
                                        ("mamba", 4)]
    tiny = dict(CFG, **{k: v for k, v in CFG["rehearse"].items()
                        if k not in ("serving", "dtype", "check_limit")},
                as_run={"dtype": "float32"})
    w = FAM.weights.make_weights(tiny, 5)
    a = -np.exp(np.asarray(w["r0_A_log"]))
    assert a.dtype == np.float32 and (-16 <= a).all() and (a <= -1).all()
    dt = np.log1p(np.exp(np.asarray(w["r0_dt_bias"])))
    assert (1e-3 * 0.99 <= dt).all() and (dt <= 1e-1 * 1.01).all()
    assert set(np.unique(np.asarray(w["norm_f"]))) == {-1.0, 1.0}
    assert np.asarray(w["r0_D"]).tolist() == [[1.0] * 4] * 2
    again = FAM.weights.make_weights(tiny, 5)
    assert all(np.array_equal(np.asarray(w[k]), np.asarray(again[k]))
               for k in w)


def test_the_rehearsal_walks_the_new_cell():
    """The whole command at toy widths on the CPU: every request served as
    the reference has it, the pool's counters read by the new readers."""
    args = argparse.Namespace(workload=CELL["name"], seed=2147484001,
                              seconds=3.0, trace=1, rehearse=True,
                              control="", root=None)
    result, summary = harness.run(args, harness.clock())
    assert result["rehearse"] is True and result["correct"] is False
    assert result["verdict_at_toy_size"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    got = result["metrics"]
    assert 0.3 < got["rehearse.expert_held_pick_share"]["value"] < 0.7
    assert got["rehearse.expert_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 < got["rehearse.experts_touched_share"]["value"] <= 1.0
    assert got["rehearse.recurrent_state_gb"]["value"] > 0
    assert "rehearse.state_splice_p50_ms" in got
    assert "rehearse.sparse_attended_share" not in got
    assert summary["compiles_in_window"] == 0


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_a_run_without_its_source(name):
    """The parent's program has no such counter: the reader returns None
    and does not raise."""
    fn, kw = MAN.reader(name)
    run = harness.RunData(
        cfg=CFG, device={"kind": "TPU v5 lite"}, family=FAM, w0=0.0, w1=50.0,
        stats0={"dispatches": 1, "prefix_cache": {}},
        stats1={"dispatches": 9, "prefix_cache": {},
                "recurrent_state_bytes": 5},
        spans=[], modules={"jit_run": [1.0, 4]})
    assert fn(run, **kw) is None


def test_the_readers_read_what_the_program_counts():
    run = harness.RunData(
        cfg=CFG, device={}, family=FAM, w0=0.0, w1=50.0,
        stats0={"expert_tokens_routed": 100, "expert_tokens_offered": 200,
                "expert_load_max": 10, "expert_load_mean": 8.0,
                "experts_touched": 30, "experts_touchable": 36},
        stats1={"expert_tokens_routed": 1100, "expert_tokens_offered": 2200,
                "expert_load_max": 70, "expert_load_mean": 58.0,
                "experts_touched": 930, "experts_touchable": 1036})
    got = {name: MAN.reader(name)[0](run) for name in READERS}
    assert got == {"expert_held_pick_share": pytest.approx(0.5),
                   "expert_load_max_over_mean": pytest.approx(1.2),
                   "experts_touched_share": pytest.approx(0.9)}


def test_the_new_metrics_are_entries_and_files():
    per_layer = {m["name"]: m for m in MAN.data["per_layer"]}
    for name in READERS:
        m = per_layer[name]
        assert m["workloads"] == [CELL["name"]]
        assert m["moves"] == "tpot_p90_ms"
        assert m["layer"] == "model step and kernels"
    reported = {m["name"] for m in MAN.per_layer(CELL["name"])}
    assert set(READERS) | {"recurrent_state_gb", "state_splice_p50_ms",
                           "prefill_chunk_ms", "decode_step_roofline",
                           "prefill_roofline", "serve_mfu"} <= reported
    assert "sparse_attended_share" not in reported
    assert {m["name"] for m in MAN.end_to_end(CELL["name"])} == {
        "ttft_p90_ms", "tpot_p90_ms", "setup_s"}


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's `config` is in the file unchanged, but
    for the three that `reduced` names; what is not in it is `assumed`."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    assert CFG["source"] == row["source_url"]
    assert sorted(CFG["reduced"]) == ["layer_types", "num_hidden_layers",
                                      "num_local_experts"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG["published"][key] == value
        else:
            assert CFG[key] == value, key
    assert CFG["layer_types"] == row["config"]["layer_types"][:10]
    assert CFG["layer_types"].count("attention") == 1
    assert CFG["num_local_experts"] == 36 and CFG["num_hidden_layers"] == 10
    assert "4 pipeline stages" in CFG["deployment"]
    for key in ("expert_width", "mamba_init", "router", "weights"):
        assert key in CFG["assumed"]
