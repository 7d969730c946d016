"""LM bench machinery (`idunno_tpu/utils/lm_bench.py`) on the CPU mesh.

The numbers only mean something on TPU; these tests pin the RECORD SHAPE —
every phase present, token accounting sane — so an unattended TPU capture
can't silently emit a gutted record.
"""
import time

import pytest

from idunno_tpu.utils.lm_bench import (lm_bench_config,
                                        prefix_bench_workload, run_lm_bench,
                                        run_lm_cluster_prefix_bench,
                                        run_lm_prefix_bench)

TINY = {
    "BENCH_LM_DIM": "64", "BENCH_LM_DEPTH": "1", "BENCH_LM_HEADS": "2",
    "BENCH_LM_VOCAB": "128", "BENCH_LM_SLOTS": "2", "BENCH_LM_PROMPT": "8",
    "BENCH_LM_MAXNEW": "16", "BENCH_LM_MAXLEN": "64",
    "BENCH_LM_DECODE_STEPS": "4", "BENCH_LM_PREFILL_BATCH": "2",
    "BENCH_LM_PREFILL_SEQ": "32", "BENCH_LM_GQA_KV_HEADS": "1",
}


@pytest.fixture
def tiny_env(monkeypatch):
    for k, v in TINY.items():
        monkeypatch.setenv(k, v)


def test_config_env_overrides(tiny_env):
    cfg = lm_bench_config("cpu")
    assert cfg["dim"] == 64 and cfg["slots"] == 2
    assert cfg["decode_steps"] == 4


def test_full_suite_record_shape(tiny_env):
    rec = run_lm_bench("cpu", "cpu", 1, None,
                       deadline=time.perf_counter() + 600, compact=False)
    assert rec["n_params"] > 0 and rec["param_bytes"] > 0
    assert rec["prefill"]["tokens_per_s"] > 0
    assert rec["flash_attention"] == "n/a (cpu)"
    assert rec["decode"]["tokens_per_s"] > 0
    assert rec["decode"]["slots"] == 2
    assert rec["int8_decode"]["tokens_per_s"] > 0
    assert rec["gqa_decode"]["tokens_per_s"] > 0
    assert rec["gqa_decode"]["kv_heads"] == 1
    # slot-scaling point: 4x the base slots, sane token accounting (a
    # config bump that makes the big pool inadmissible must fail HERE,
    # not silently become an {"error": ...} record in a live capture)
    assert rec["decode_slots_scaling"]["slots"] == 8
    assert rec["decode_slots_scaling"]["tokens_per_s"] > 0
    # tiled prefill: tokens/s must reflect tile*b*t tokens per dispatch
    assert rec["prefill"]["scan_tile"] == 1     # cpu default


def test_compact_skips_optional_phases(tiny_env):
    rec = run_lm_bench("cpu", "cpu", 1, None,
                       deadline=time.perf_counter() + 600, compact=True)
    assert "int8_decode" not in rec
    assert "gqa_decode" not in rec and "decode_slots_scaling" not in rec
    assert "xla_full_attention" not in rec["prefill"]
    assert rec["decode"]["tokens_per_s"] > 0


def test_deadline_skips_optional_phases(tiny_env):
    rec = run_lm_bench("cpu", "cpu", 1, None,
                       deadline=time.perf_counter() - 1, compact=False)
    assert "int8_decode" not in rec
    assert "decode_slots_scaling" not in rec
    assert rec["decode"]["tokens_per_s"] > 0


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_default_config_phases_fit_serving_limits(platform, monkeypatch):
    """The unattended defaults must keep EVERY phase admissible — a knob
    bump that overflows a validate() limit silently turns a capture phase
    into an error record."""
    for k in TINY:
        monkeypatch.delenv(k, raising=False)   # pin the SHIPPED defaults
    cfg = lm_bench_config(platform)
    # plain/int8/gqa rows
    assert cfg["prompt_len"] + cfg["max_new"] <= cfg["max_len"]
    # _steady_decode_tok_s times k = (max_new-1)//decode_steps - 1 ≥ 1
    # FULL dispatches after the untimed first one; anything less and the
    # max(1, ...) floor counts a partial dispatch as a full one
    assert cfg["max_new"] >= 2 * cfg["decode_steps"] + 1
    assert cfg["heads"] % max(cfg["gqa_kv_heads"], 1) == 0
    assert cfg["dim"] % cfg["heads"] == 0


def test_prefix_suite_record_shape_and_saves_prefill(tiny_env):
    """BENCH_SUITE=lm_prefix (`run_lm_prefix_bench`): on the shared-
    prefix workload the cache-on pool must compute strictly fewer
    admission prefill tokens than cache-off with a nonzero hit rate and
    identical decode output volume — the acceptance bar for the paged
    KV pool + radix prefix cache: prefill work actually reduced, not
    just counters present."""
    rec = run_lm_prefix_bench("cpu", "cpu", 1, None,
                              deadline=time.perf_counter() + 600,
                              compact=False)
    for k in ("config", "kv_block_size", "workload", "cache_on",
              "cache_off"):
        assert k in rec, f"missing {k}"
    on, off = rec["cache_on"], rec["cache_off"]
    assert on["tokens_per_s"] > 0 and off["tokens_per_s"] > 0
    assert on["tokens_generated"] == off["tokens_generated"], \
        "both pools must produce the same decode volume"
    assert on["prefill_tokens"] < off["prefill_tokens"], \
        "the cache's whole point: less admission prefill work"
    assert rec["prefill_tokens_ratio"] < 1.0
    pc = on["prefix_cache"]
    assert pc["prefix_hit_rate"] > 0 and pc["cached_tokens_saved"] > 0
    assert "prefix_cache" not in off


def test_cluster_prefix_suite_record_shape(tiny_env):
    """BENCH_SUITE=lm_cluster_prefix (`run_lm_cluster_prefix_bench`): the
    warmed replica's first request must structurally prefill ONLY the
    unpublished suffix (the acceptance bar for warm-at-spawn: positive
    suffix fraction, warm blocks actually fetched, remote hit counted on
    the cold replica) — not just emit TTFT numbers."""
    rec = run_lm_cluster_prefix_bench("cpu", "cpu", 1, None,
                                      deadline=time.perf_counter() + 600,
                                      compact=False)
    for k in ("config", "kv_block_size", "workload", "publisher",
              "baseline", "cold", "warmed"):
        assert k in rec, f"missing {k}"
    assert rec["publisher"]["published_chains"] > 0
    assert rec["publisher"]["ring_blobs"] > 0
    # cold replica: the admission itself probed + fetched the chain
    assert rec["cold"]["prefix_remote_hits"] >= 1
    assert rec["cold"]["prefix_fetch_bytes"] > 0
    assert rec["cold"]["prefill_tokens"] \
        < rec["baseline"]["prefill_tokens"]
    # warmed replica: blocks arrived BEFORE the first request, which
    # then prefills only the suffix without a remote round-trip
    assert rec["warmed"]["warm_blocks"] > 0
    assert rec["warmed"]["prefix_remote_hits"] == 0
    assert rec["warmed"]["prefill_tokens"] \
        < rec["baseline"]["prefill_tokens"]
    assert rec["suffix_prefill_fraction"] > 0
    assert rec["cold_suffix_prefill_fraction"] > 0
    assert rec["warmed"]["tokens_per_s"] > 0
    assert rec["warmed"]["ttft_s"] > 0 and rec["baseline"]["ttft_s"] > 0
    assert rec["ring_bytes_fetched"] > 0


def test_prefix_workload_shape(tiny_env):
    """The workload helper must emit block-aligned shared heads shorter
    than the prompt and a bucket ladder whose smallest rung fits the
    unique tail (otherwise a hit can't shrink the prefill bucket)."""
    cfg = lm_bench_config("cpu")
    prompts, shared, buckets = prefix_bench_workload(cfg, 4)
    assert len(prompts) == cfg["slots"] * 3
    assert 0 < shared < cfg["prompt_len"] and shared % 4 == 0
    assert all(len(p) == cfg["prompt_len"] for p in prompts)
    head = prompts[0][:shared]
    assert all(p[:shared] == head for p in prompts)
    assert min(buckets) <= cfg["prompt_len"] - shared
    assert max(buckets) == cfg["prompt_len"]
