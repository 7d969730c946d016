"""A model family is four files found by a configuration's `family` key
(`benchmark/families/<family>/{weights,reference,counts,program}.py`): the
move of StarCoder2 into them held to the bit against the parent's digests,
and a second family landing in a scratch root as new files and entries
alone, with a run that shows each of its four files was the one used."""
import argparse
import hashlib
import json
import os

import numpy as np
import pytest
import scratch_root

from benchmark import check, harness, manifest, peaks, system, trace

ROOT = manifest.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "parent_digests.json")) as f:
    # recorded from PR 26's tree before anything moved, on this CPU backend
    # (/root/scratch/record.py of PR 27: rehearsal widths, float32)
    PARENT = json.load(f)


def _sha(x, dtype=None):
    return hashlib.sha256(np.asarray(x, dtype).tobytes()).hexdigest()


@pytest.mark.parametrize("name", sorted(PARENT["weights"]))
def test_the_move_is_held_to_the_bit(name):
    man = manifest.Manifest()
    raw = man.config({"config": name})
    fam = man.family(raw)
    cfg = system.model_config(raw, True, fam)
    w = fam.weights.make_weights(cfg, PARENT["seed"])
    got = {k: f"{_sha(v)}:{v.dtype}:{'x'.join(map(str, v.shape))}"
           for k, v in w.items()}
    assert got == PARENT["weights"][name]
    toks, want = PARENT["tokens"], PARENT["positions"]
    logits = fam.reference.logits_at(w, cfg, toks, want)
    assert _sha(logits, np.float32) == PARENT["logits"][name]
    low = fam.reference.logits_at(w, cfg, toks, want, quant="fp8")
    assert _sha(low, np.float32) == PARENT["logits_fp8"][name]
    c = fam.counts
    assert {
        "decode_1000x8": list(c.decode_step_work(raw, [1000] * 8)),
        "decode_2000x8": list(c.decode_step_work(raw, [2000] * 8)),
        "prefill_512": list(c.prefill_work(raw, 512)),
        "prefill_128_after_384": list(
            c.prefill_work(raw, 128, cached_tokens=384)),
        "weight_bytes": c.weight_bytes(raw),
        "kv_bytes_per_token": c.kv_bytes_per_token(raw),
    } == PARENT["counts"][name]


def test_a_family_that_is_not_there_fails_at_once_naming_its_files():
    with pytest.raises(FileNotFoundError) as e:
        manifest.Manifest().family({"name": "x-1b", "family": "nonesuch"})
    for part in manifest.FAMILY_PARTS:
        assert part + ".py" in str(e.value)
    assert os.path.join("families", "nonesuch") in str(e.value)


# -- a second family, as a later PR would bring it ---------------------------

# what each copied file computes is altered so that the run shows it was used
ALTERED = {
    # the embedding drawn at twice the scale
    "weights.py": ('("embed", (V, h), "embed", 1.0, False)',
                   '("embed", (V, h), "embed", 2.0, False)'),
    # the reference's logits negated ...
    "reference.py": ("return np.asarray(out)[:m]",
                     "return -np.asarray(out)[:m]"),
    # ... and the program's too (its head negated), so that the two agree
    # only where both files are this family's
    "program.py": ('"head": kb("w_head", "b_head"),',
                   '"head": {"kernel": -w["w_head"], "bias": -w["b_head"]},'),
}
DOUBLED_COUNTS = '''

_decode_step_work, _prefill_work = decode_step_work, prefill_work


def decode_step_work(cfg, context_lengths):
    f, b = _decode_step_work(cfg, context_lengths)
    return 2 * f, 2 * b


def prefill_work(cfg, new_tokens, cached_tokens=0):
    f, b = _prefill_work(cfg, new_tokens, cached_tokens)
    return 2 * f, 2 * b
'''
# a new kernel's readers: the family's count, and its least time over the
# device time of the operation found by name in the run's table
KERNEL_READER = '''\
from benchmark import peaks


def step_gflop(run, rows=4, context=128):
    f, _b = run.family.counts.decode_step_work(run.cfg, [context] * rows)
    return f / 1e9


def op_roofline(run, op, rows=4, context=128):
    secs = sum(s for name, s in run.ops.items() if name.endswith("/" + op))
    if not secs:
        return None
    f, b = run.family.counts.decode_step_work(run.cfg, [context] * rows)
    return peaks.roofline_share(f, b, secs, run.device["kind"])[0]
'''


@pytest.fixture(scope="module")
def second_family(tmp_path_factory):
    """(scratch root, digests of what was there before, result, summary) of
    a `--rehearse --trace 1` run of a cell of the family `mirrored`."""
    root = tmp_path_factory.mktemp("second_family")
    bench, before = scratch_root.make(root)
    fam = bench / "families" / "mirrored"
    fam.mkdir()
    for name in (p + ".py" for p in manifest.FAMILY_PARTS):
        text = (bench / "families" / "starcoder2" / name).read_text()
        if name == "counts.py":
            text += DOUBLED_COUNTS
        else:
            old, new = ALTERED[name]
            assert text.count(old) == 1, (name, old)
            text = text.replace(old, new)
        (fam / name).write_text(text)
    cfg = json.loads((bench / "configs" / "starcoder2-7b.json").read_text())
    cfg.update(name="mirrored-7b", family="mirrored")
    (bench / "configs" / "mirrored-7b.json").write_text(json.dumps(cfg))
    (bench / "readers" / "kernel.py").write_text(KERNEL_READER)
    (bench / "metrics" / "step_gflop.json").write_text(json.dumps(
        {"reader": "kernel:step_gflop", "args": {}}))
    (bench / "metrics" / "weights_fusion_roofline.json").write_text(
        json.dumps({"reader": "kernel:op_roofline",
                    "args": {"op": "fusion.302"}}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "mirrored-7b", "source": "a paper",
        "file": "benchmark/configs/mirrored-7b.json",
        "reduced": ["num_hidden_layers"], "why": "another architecture"})
    cell = "mirrored-7b.completion"
    data["workloads"].append({
        "name": cell, "config": "mirrored-7b", "traffic": "completion",
        "chips": 1, "why": "the second family under the open loop"})
    for m in data["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "tpot_p90_ms"):
            m["workloads"].append(cell)
    for name, source in (("step_gflop", "program_counter"),
                         ("weights_fusion_roofline", "device_trace")):
        data["per_layer"].append({
            "name": name, "unit": "%" if "roofline" in name else "GFLOP",
            "better": "higher", "source": source,
            "layer": "model step and kernels", "moves": "tpot_p90_ms",
            "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    args = argparse.Namespace(workload=cell, seed=3_000_000_019, seconds=3.0,
                              trace=1, rehearse=True, control="",
                              root=str(root))
    result, summary = harness.run(args, harness.clock())
    return root, before, result, summary


def test_a_second_family_is_files_and_entries_alone(second_family):
    root, before, result, summary = second_family
    after = scratch_root.digest(root / "benchmark")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        *(os.path.join("families", "mirrored", p + ".py")
          for p in manifest.FAMILY_PARTS),
        os.path.join("configs", "mirrored-7b.json"),
        os.path.join("readers", "kernel.py"),
        os.path.join("metrics", "step_gflop.json"),
        os.path.join("metrics", "weights_fusion_roofline.json")}
    assert summary["_family"].name == "mirrored"
    assert summary["requests_due"] > 0 and summary["failed"] == 0
    # what moves the cell's end-to-end metrics is taken up as it stands
    assert "rehearse.batch_occupancy" in result["metrics"]


def test_its_weights_reference_counts_and_program_were_the_ones_used(
        second_family):
    _root, _before, result, summary = second_family
    cfg, w, sample = summary["_cfg"], summary["_weights"], summary["_sample"]
    first = manifest.Manifest().family({"family": "starcoder2"})
    # weights: the embedding at twice the scale, the rest as it was
    w1 = first.weights.make_weights(cfg, summary["seed"])
    assert np.array_equal(np.asarray(w["embed"]), 2 * np.asarray(w1["embed"]))
    assert np.array_equal(np.asarray(w["wq"]), np.asarray(w1["wq"]))
    # program and reference: both negate the logits, so the served tokens
    # are the reference's best only where both were this family's ...
    cmp = result["compared"]
    assert cmp["checked_tokens"]["value"] >= 30
    assert cmp["max_gap"]["value"] <= 1e-3 and result["verdict_at_toy_size"]
    # ... and under the first family's reference they are its worst
    gaps = check.served_gaps(first.reference.logits_at, w, cfg, sample)
    assert gaps["max_gap"] > 1.0 and gaps["agree"] < 0.01
    ok, _ = check.decide({"max_gap": (gaps["max_gap"], summary["_limit"])})
    assert not ok
    # counts: the new metric reads twice the first family's operations
    f1, _ = first.counts.decode_step_work(cfg, [128] * 4)
    assert result["metrics"]["rehearse.step_gflop"]["value"] == 2 * f1 / 1e9
    # the CPU's trace has no device operation: the kernel's reader finds
    # nothing to read and its metric is left out, not reported as 0
    assert "rehearse.weights_fusion_roofline" not in result["metrics"]


RECORDED = os.path.join(ROOT, "benchmark", "data", "small_trace.json.gz")


def test_a_kernel_reader_finds_its_operation_in_run_ops(second_family):
    """On the recorded chip trace the reader the second family brought finds
    its operation by name in `run.ops` and divides the family's count by
    its time."""
    root, _before, _result, summary = second_family
    man = manifest.Manifest(str(root), str(root / "benchmark"))
    fn, kw = man.reader("weights_fusion_roofline")
    ops = trace.op_times(trace.load(RECORDED))
    name = max(ops, key=ops.get).split("/")[1]
    run = harness.RunData(cfg=summary["_cfg"], device={"kind": "TPU v5 lite"},
                          family=summary["_family"], ops=ops)
    f, b = summary["_family"].counts.decode_step_work(run.cfg, [128] * 4)
    secs = sum(s for k, s in ops.items() if k.endswith("/" + name))
    assert fn(run, **dict(kw, op=name)) == pytest.approx(
        peaks.roofline_share(f, b, secs, "TPU v5 lite")[0])
    assert fn(run, **dict(kw, op="no_such_fusion")) is None


def test_fill_trace_keeps_the_whole_table_of_operations(tmp_path,
                                                        monkeypatch):
    """`run.ops` is every device operation of the traced window by
    '<program>/<op>'; `breakdown.device_ops` is its ten largest."""
    tr = trace.load(RECORDED)
    end = max(s + d for n, s, d in tr["devices"][0]["lines"]["XLA Ops"])
    tr["host"].append(["bench.mark", end, 1e-6])
    monkeypatch.setattr(trace, "find_xplane", lambda _dir: "recorded")
    monkeypatch.setattr(trace, "load_xplane", lambda _path: tr)
    run = harness.RunData(cfg={}, device={})
    harness.fill_trace(run, trace, str(tmp_path), [0.0, 0.001, 0.3])
    a = tr["host"][0][1]
    assert run.ops == trace.op_times(trace.clip(tr, a, end))
    assert len(run.ops) > 10 and all(
        "/" in k and v > 0 for k, v in run.ops.items())
    assert run.breakdown["device_ops"] == trace.top(run.ops)
    assert sum(run.ops.values()) >= run.busy_s * 0.999
    assert set(k.split("/")[0] for k in run.ops) <= set(run.modules)
