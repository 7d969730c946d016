"""The command as the driver runs it, and the harness taking a new cell, mix,
configuration and per-layer metric as new files alone."""
import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest
import scratch_root

from benchmark import harness, manifest

ROOT = manifest.ROOT
RUN = [sys.executable, os.path.join("benchmark", "run.py")]
# every cell the benchmark has: a later PR's cell is walked with no edit here
CELLS = [w["name"] for w in manifest.Manifest().data["workloads"]]


def _cli(extra, cwd=ROOT, env=None, timeout=600,
         workload="starcoder2-7b.completion"):
    cmd = RUN + ["--workload", workload, "--seed", "2147483999",
                 "--seconds", "3"] + extra
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_walks_the_whole_command(cell):
    p = _cli(["--trace", "1", "--rehearse"], workload=cell)
    assert p.returncode == 3, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["correct"] is False and last["attempted"] > 0
    assert all(k.startswith("rehearse.") for k in last["metrics"])
    assert {"busy_s", "window_s"} <= set(last["device"])
    assert list(last)[-1] == "compared"        # the numbers compared come last
    due = json.loads(p.stdout.strip().splitlines()[-2])
    assert {"requests_due", "completed", "failed",
            "gen_lateness_p99_ms"} <= set(due)
    tail = p.stderr.strip().splitlines()[-8:]
    assert any(l.startswith("compared max_gap: value") for l in tail)
    assert tail[-1] == "correct: False"


def test_the_measured_path_fails_where_it_finds_no_chip():
    p = _cli(["--trace", "0"])
    assert p.returncode not in (0, 3)
    assert "accelerator" in p.stderr
    assert not any(l.startswith('{"correct"') for l in p.stdout.splitlines())


def test_it_fails_where_the_program_is_missing(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = _cli(["--trace", "0", "--rehearse"], cwd=str(tmp_path), env=env)
    assert p.returncode not in (0, 3)
    assert not any(l.startswith('{"correct"') for l in p.stdout.splitlines())


def test_new_cell_mix_config_and_metric_are_files_and_entries(tmp_path):
    """What a later PR does: new files under the benchmark's directory and
    new entries in BENCHMARK.json; no file that is there changes."""
    bench, before = scratch_root.make(tmp_path)
    # a configuration, a mix, a reader and a metric of its own
    cfg = json.loads((bench / "configs" / "starcoder2-7b.json").read_text())
    cfg["name"] = "starcoder2-7b-d8"
    cfg["num_hidden_layers"] = 8
    (bench / "configs" / "starcoder2-7b-d8.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "completion.json").read_text())
    mix.update(rate_rps=6.0, arrivals="uniform",
               burst={"period_s": 2.0, "on_s": 0.5, "factor": 2.0})
    (bench / "traffic" / "bursty.json").write_text(json.dumps(mix))
    (bench / "readers" / "steps.py").write_text(
        "def count(run, scale=1):\n"
        "    n = len([s for s in run.steps if run.w0 <= s[1] < run.w1])\n"
        "    return float(n * scale) if n else None\n")
    (bench / "metrics" / "steps_in_window.json").write_text(json.dumps(
        {"reader": "steps:count", "args": {"scale": 2}}))
    data = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    data["configs"].append({
        "name": "starcoder2-7b-d8", "source": data["configs"][1]["source"],
        "file": "benchmark/configs/starcoder2-7b-d8.json",
        "reduced": ["num_hidden_layers"], "why": "a shallower cut"})
    data["workloads"].append({
        "name": "starcoder2-7b-d8.bursty", "config": "starcoder2-7b-d8",
        "traffic": "bursty", "chips": 1, "why": "bursts"})
    for m in data["end_to_end"]:
        if "workloads" in m and m["name"] in ("ttft_p90_ms", "tpot_p90_ms"):
            m["workloads"].append("starcoder2-7b-d8.bursty")
    data["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "pool loop and admission",
        "moves": "tpot_p90_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    args = argparse.Namespace(workload="starcoder2-7b-d8.bursty", seed=5,
                              seconds=3.0, trace=1, rehearse=True,
                              control="", root=str(tmp_path))
    result, summary = harness.run(args, harness.clock())
    got = result["metrics"]
    assert got["rehearse.steps_in_window"]["value"] > 0
    # the new cell took up the metrics that move what it reports, as they stand
    assert "rehearse.batch_occupancy" in got
    assert "rehearse.prefix_hit_share" in got
    assert "rehearse.batch_mfu" not in got      # out_tok_s is not its metric
    assert summary["requests_due"] > 0 and result["verdict_at_toy_size"]
    after = scratch_root.digest(bench)
    assert {k: after[k] for k in before} == before
