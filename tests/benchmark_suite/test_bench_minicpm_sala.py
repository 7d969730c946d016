"""The family `minicpm_sala` beside the benchmark: its reference against
hand-computed values at one tiny size (the recurrence against its quadratic
form, the block selection against a brute-force loop), its counts against a
hand count at the published widths, and its readers finding nothing to read
on a run of a program without their spans and counters."""
import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import harness, manifest

MAN = manifest.Manifest()
CELL = MAN.cell("minicpm-sala.long-doc")
CFG = MAN.config(CELL)
FAM = MAN.family(CFG)
REF = FAM.reference

GEOM = dict(kernel_size=4, kernel_stride=2, block_size=8, topk=2,
            init_blocks=1, window_size=16, dense_len=32)


def test_the_quadratic_form_is_the_recurrence():
    """`S_t = exp(-s) S_{t-1} + k_t^T v_t`, `o_t = q_t S_t / sqrt(d)`, a
    token at a time in float64, against the reference's blocked product."""
    rng = np.random.default_rng(0)
    t, h, d = REF._QBLOCK * 2, 3, 8
    q, k, v = (rng.standard_normal((t, h, d)) for _ in range(3))
    slope = np.array([0.5, 0.05, 0.001])
    want = np.zeros((t, h, d))
    for head in range(h):
        state = np.zeros((d, d))
        for i in range(t):
            state = np.exp(-slope[head]) * state + np.outer(k[i, head],
                                                            v[i, head])
            want[i, head] = q[i, head] @ state / np.sqrt(d)
    got = REF._lightning(*(jnp.asarray(x, jnp.float32) for x in (q, k, v)),
                         jnp.asarray(slope, jnp.float32), None)
    assert np.abs(np.asarray(got) - want).max() < 1e-3 * np.abs(want).max()


def test_slopes_follow_the_published_index():
    cfg = dict(CFG)
    s9, s20 = REF.slopes(cfg, 9), REF.slopes(cfg, 20)
    assert s9.shape == (32,)
    assert s9[0] == pytest.approx(2 ** -0.25 * (1 - 9 / 31 + 1e-5))
    assert s20[31] == pytest.approx(2 ** -8 * (1 - 20 / 31 + 1e-5))


def _brute_selection(score, t, g):
    """The blocks query ``t`` attends, by the words of the configuration's
    `assumed.selected_blocks`, one block at a time."""
    bs = g["block_size"]
    mine = t // bs
    if t + 1 <= g["dense_len"]:
        return set(range(mine + 1))
    first_window = max(t - g["window_size"] + 1, 0) // bs
    chosen = {b for b in range(mine + 1)
              if b < g["init_blocks"] or b >= first_window}
    others = sorted((b for b in range(mine + 1) if b not in chosen),
                    key=lambda b: (-score[b], b))
    return chosen | set(others[:g["topk"]])


@pytest.mark.parametrize("t", [5, 31, 32, 33, 47, 100, 127])
def test_selection_is_the_brute_force_one(t):
    rng = np.random.default_rng(t)
    nb = 16
    score = rng.random(nb).astype(np.float32)
    got = np.asarray(REF.selected_blocks(jnp.asarray(score), t, GEOM))
    assert set(np.flatnonzero(got)) == _brute_selection(score, t, GEOM)


def test_pooled_keys_and_block_scores_by_hand():
    """Kernel j is the mean of keys [2j, 2j + 4); block b (8 tokens) takes
    the largest score among kernels 4b - 1 .. 4b + 3, those that overlap
    it."""
    k = jnp.arange(20, dtype=jnp.float32).reshape(20, 1, 1)
    pooled = np.asarray(REF.pooled_keys(k, GEOM))[:, 0, 0]
    assert pooled.tolist() == [1.5 + 2 * j for j in range(9)]
    p = jnp.asarray([0, 1, 2, 9, 3, 0, 0, 0, 4, 0, 0], jnp.float32)
    # block 0: kernels 0..3; block 1: 3..7; block 2: 7..11 (8 is the last)
    assert np.asarray(REF.block_scores(p, GEOM, 3)).tolist() == [9, 9, 4]


def test_counts_at_the_published_widths():
    c = FAM.counts
    assert c.params_per_layer(CFG, "minicpm4") == (
        2 * 4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096 + 3 * 4096 * 16384)
    assert round(c.params_per_layer(CFG, "minicpm4") / 1e6, 1) == 253.8
    assert round(c.params_per_layer(CFG, "lightning-attn") / 1e6, 1) == 285.2
    assert c.params_total(CFG) == (3 * c.params_per_layer(CFG, "minicpm4")
                                   + 9 * c.params_per_layer(
                                       CFG, "lightning-attn")
                                   + 2 * 73448 * 4096)
    assert round(c.weight_bytes(CFG) / 1e9, 2) == 7.86
    assert c.kv_bytes_per_token(CFG) == 3072
    assert c.pooled_bytes_per_token(CFG) == 3 * 512 / 16
    assert c.state_bytes_per_slot(CFG, layers=1) == 2 * 2 ** 20
    assert c.state_bytes_per_slot(CFG) == 9 * 2 * 2 ** 20


def test_a_decode_step_reads_the_selection_not_the_context():
    c = FAM.counts
    assert c.attended(CFG, 8192) == 8192
    assert c.attended(CFG, 8193) == 2048 + 64 * 64 + 64
    f1, b1 = c.decode_step_work(CFG, [12000] * 4)
    f2, b2 = c.decode_step_work(CFG, [16000] * 4)
    # 4000 tokens more a row cost the pooled keys alone: 96 B a token
    assert b2 - b1 == pytest.approx(4 * 4000 * 96)
    assert f2 > f1
    weights = c.matmul_params(CFG) * 2
    state = 4 * 2 * c.state_bytes_per_slot(CFG)
    kv = 3072 * (4 * 6208 + 4)
    assert b1 > weights + state + kv
    assert b1 - (weights + state + kv) < 0.01 * b1     # pooled keys, rows
    fp, bp = c.prefill_work(CFG, 12000)
    assert fp > 2 * (c.matmul_params(CFG) - 73448 * 4096) * 12000
    assert bp == weights + 3072 * 12000 + c.state_bytes_per_slot(CFG)


READERS = ("sparse_attended_share", "recurrent_state_gb", "prefill_chunk_ms",
           "state_splice_p50_ms")


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_a_run_without_its_source(name):
    """The parent's program has no such counter, span or program: the
    reader returns None and does not raise."""
    fn, kw = MAN.reader(name)
    run = harness.RunData(
        cfg=CFG, device={"kind": "TPU v5 lite"}, family=FAM, w0=0.0, w1=50.0,
        stats0={"dispatches": 1, "prefix_cache": {}},
        stats1={"dispatches": 9, "prefix_cache": {}},
        spans=[{"name": "lm.prefill", "t_start": 1.0, "t_end": 2.0,
                "attrs": {}}],
        modules={"jit_run": [1.0, 4], "jit__prefill": [0.5, 2]})
    assert fn(run, **kw) is None


def test_the_readers_read_what_the_program_records():
    run = harness.RunData(
        cfg=CFG, device={}, family=FAM, w0=0.0, w1=50.0,
        stats0={"sparse_tokens_attended": 100, "sparse_tokens_in_context": 200,
                "recurrent_state_bytes": 3 * 10 ** 8},
        stats1={"sparse_tokens_attended": 700, "sparse_tokens_in_context": 1400,
                "recurrent_state_bytes": 3 * 10 ** 8},
        spans=[{"name": "state.splice", "t_start": 1.0, "t_end": 1.002,
                "attrs": {}},
               {"name": "state.splice", "t_start": 60.0, "t_end": 60.1,
                "attrs": {}}],
        modules={"jit__prefill_chunk": [1.2, 10]})
    got = {name: MAN.reader(name)[0](run) for name in READERS}
    assert got == {"sparse_attended_share": pytest.approx(0.5),
                   "recurrent_state_gb": pytest.approx(0.3),
                   "prefill_chunk_ms": pytest.approx(120.0),
                   "state_splice_p50_ms": pytest.approx(2.0)}


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's `config` is in the file unchanged, but
    for the two that `reduced` names; what is not in it is `assumed`."""
    import json
    import os
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG["published"][key] == value
        else:
            assert CFG[key] == value, key
    assert CFG["mixer_types"] == row["config"]["mixer_types"][9:21]
    assert CFG["layer_ids"] == list(range(9, 21))
    assert "sparse_config" in CFG["assumed"]
