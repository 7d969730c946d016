"""The benchmark's `correct`, at a size a test run can hold: the plain
reference against the program on the CPU (prefix hits included), the control
(the reference in float8 put in the program's place), and the timed path
broken underneath. Each test drives the harness as a run does, short of its
look for a chip."""
import argparse
import json
import os

import pytest
import scratch_root

from benchmark import check, harness, manifest

# every cell the benchmark has (a later PR's cell is compared with no edit
# here), and the shared-prefix mix that is kept for later
PREFIX_CELL = "starcoder2-3b.repo-prefix"
CELLS = [w["name"] for w in manifest.Manifest().data["workloads"]] \
    + [PREFIX_CELL]


def _run(workload, *, seed=3_000_000_019, trace=0, seconds=3.0, root=None):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, rehearse=True, control="",
                              root=root)
    return harness.run(args, harness.clock())


@pytest.fixture(scope="module")
def prefix_run(tmp_path_factory):
    """The shared-prefix mix as a cell of `starcoder2-3b`: it is kept as a
    traffic file (PERF.md, Open questions) and becomes a cell by entries in
    a copy of BENCHMARK.json, as a later PR would add it."""
    root = tmp_path_factory.mktemp("with_repo_prefix")
    scratch_root.make(root)
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["workloads"].append({
        "name": PREFIX_CELL, "config": "starcoder2-3b",
        "traffic": "repo-prefix", "chips": 1, "why": "shared prefixes"})
    for m in data["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "tpot_p90_ms"):
            m["workloads"].append(PREFIX_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return _run(PREFIX_CELL, trace=1, root=str(root))


@pytest.mark.parametrize("cell", CELLS)
def test_reference_agrees_with_the_program(cell, prefix_run):
    result, summary = (prefix_run if cell == PREFIX_CELL else _run(cell))
    cmp = result["compared"]
    assert summary["requests_due"] > 0 and summary["failed"] == 0
    assert cmp["checked_tokens"]["value"] >= 30
    # float32 on both sides: the served token is the reference's best
    assert cmp["max_gap"]["value"] <= 1e-3
    assert cmp["agree"]["value"] >= 0.99
    assert result["verdict_at_toy_size"] is True
    assert summary["compiles_in_window"] == 0


def test_prefix_hits_were_among_the_compared(prefix_run):
    result, summary = prefix_run
    assert result["metrics"]["rehearse.prefix_hit_share"]["value"] > 0.8
    longest = max(summary["_sample"], key=lambda r: len(r["tokens"]))
    assert longest["prompt_len"] > 96        # a shared prefix and a suffix


def test_a_rehearsal_never_reads_as_a_measurement(prefix_run):
    result, _ = prefix_run
    assert result["correct"] is False and result["rehearse"] is True
    assert result["metrics"]
    assert all(k.startswith("rehearse.") for k in result["metrics"])
    assert result["device"]["platform"] == "cpu"


def test_the_control_comes_out_not_correct(prefix_run):
    """The reference computed in float8, the nearest precision below the
    configuration's, serves tokens that the comparison refuses."""
    _, summary = prefix_run
    cfg, limit = summary["_cfg"], summary["_limit"]
    ctl = check.served_gaps(summary["_family"].reference.logits_at,
                            summary["_weights"], cfg, summary["_sample"],
                            quant="fp8")
    assert ctl["tokens"] >= 30 and ctl["agree"] < 0.99
    ok, _ = check.decide({"max_gap": (ctl["max_gap"], limit)})
    assert ctl["max_gap"] > limit and not ok


def _shifted_pick(monkeypatch):
    """The first token of every request altered where prefill produces it."""
    from idunno_tpu.engine import serve_lm
    real = serve_lm._pick_first

    def pick(logits, *a):
        tok, key = real(logits, *a)
        return (tok + 1) % logits.shape[-1], key
    monkeypatch.setattr(serve_lm, "_pick_first", pick)


def _shifted_decode(monkeypatch):
    """Every decoded token altered where the decode step produces it."""
    import jax.numpy as jnp
    from idunno_tpu.engine import serve_lm
    real = serve_lm.fused_decode_tail

    def tail(logits, *a, **kw):
        return real(jnp.roll(logits, 1, axis=-1), *a, **kw)
    monkeypatch.setattr(serve_lm, "fused_decode_tail", tail)


@pytest.mark.parametrize("fault", [_shifted_pick, _shifted_decode])
def test_an_altered_token_reads_as_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result, summary = _run("starcoder2-7b.completion", seed=77)
    cmp = result["compared"]
    assert summary["failed"] == 0            # every request still answers
    assert cmp["max_gap"]["value"] > cmp["max_gap"]["limit"]
    assert result["verdict_at_toy_size"] is False
    assert result["correct"] is False


def test_a_request_that_never_finishes_reads_as_not_correct():
    ok, cmp = check.decide({"max_gap": (0.0, 0.1), "unfinished": (1.0, 0.0)})
    assert not ok and cmp["unfinished"] == {"value": 1.0, "limit": 0.0}
    ok, _ = check.decide({"max_gap": (float("nan"), 0.1)})
    assert not ok                             # nothing compared: not correct
