"""The reader of the decode step's context ladder
(`benchmark/readers/context.py`): the ratio of the program's two counters
over the window, None for a program that keeps none (the hybrid pool, the
parent commit), on hand-made runs and on real pools at rehearsal widths;
and the two metrics as entries and files alone."""
import numpy as np
import pytest

from benchmark import harness, manifest, system

MAN = manifest.Manifest()
NEW = {"context_read_share": "starcoder2-7b.completion",
       "context_read_share.batch": "starcoder2-3b.batch"}


def _run(stats0=None, stats1=None):
    return harness.RunData(cfg={}, device={}, w0=100.0, w1=150.0,
                           stats0=stats0 or {}, stats1=stats1 or {})


def _reader(name):
    fn, kw = MAN.reader(name)
    return lambda run: fn(run, **kw)


@pytest.mark.parametrize("name", list(NEW))
def test_the_share_is_read_over_the_window(name):
    read = _reader(name)
    a = {"decode_context_read": 1000, "decode_context_held": 4000}
    b = {"decode_context_read": 1000 + 3 * 512, "decode_context_held":
         4000 + 3 * 4096}
    assert read(_run(a, b)) == 0.125
    assert read(_run({}, b)) == pytest.approx(2536 / 16288)


@pytest.mark.parametrize("name", list(NEW))
def test_nothing_to_read_is_none(name):
    """No counters (the parent's program, a stack with its own step), or
    no dispatch in the window: None, never 0 and never a raise."""
    read = _reader(name)
    assert read(_run({"dispatches": 3}, {"dispatches": 9})) is None
    same = {"decode_context_read": 7, "decode_context_held": 70}
    assert read(_run(same, same)) is None


@pytest.mark.parametrize("name,cell", list(NEW.items()))
def test_the_new_metrics_are_new_entries_and_files(name, cell):
    by_name = {m["name"]: m for m in MAN.data["per_layer"]}
    assert [m["name"] for m in MAN.data["per_layer"]][-2:] == list(NEW)
    entry = by_name[name]
    assert entry["workloads"] == [cell]
    assert entry["layer"] == "model step and kernels"
    assert (entry["better"], entry["source"]) == ("lower", "program_counter")
    e2e = {m["name"]: m for m in MAN.data["end_to_end"]}[entry["moves"]]
    assert cell in e2e["workloads"]
    assert name in {m["name"] for m in MAN.per_layer(cell)}
    assert name not in {m["name"]
                        for m in MAN.per_layer("minicpm-sala.long-doc")}


def _served(cell_name: str) -> tuple[dict, dict]:
    """`stats()` of the cell's pool at rehearsal widths, before and after
    it served two requests."""
    cell = MAN.cell(cell_name)
    family = MAN.family(MAN.config(cell))
    cfg = system.model_config(MAN.config(cell), True, family)
    loop, server = system.build(cfg, family.weights.make_weights(cfg, 3),
                                family)
    loop.stop(timeout=30.0)
    stats0 = server.stats()
    rng = np.random.default_rng(3)
    for n in (9, min(cfg["serving"]["prompt_buckets"])):
        server.submit(rng.integers(0, cfg["vocab_size"], n).tolist(),
                      max_new=6)
    server.run_until_drained()
    return stats0, server.stats()


def test_a_dense_pool_reports_the_share_and_a_hybrid_pool_none():
    """`starcoder2-3b.batch` at rehearsal widths (max_len 256: two rungs of
    128) serves two short rows on the first rung: the share is 0.5. The
    hybrid pool brings its own step and keeps no such counters."""
    read = _reader("context_read_share.batch")
    stats0, stats1 = _served("starcoder2-3b.batch")
    assert stats1["dispatches"] > 0
    assert read(_run(stats0, stats1)) == 0.5
    stats0, stats1 = _served("minicpm-sala.long-doc")
    assert stats1["dispatches"] > 0
    assert "decode_context_held" not in stats1
    assert read(_run(stats0, stats1)) is None
