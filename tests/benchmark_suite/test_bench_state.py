"""The reader of how much scan state the decode steps moved
(`benchmark/readers/state.py`): the ratio of the program's two counters over
the window, None for a program that keeps none (a stack without a Mamba-2
layer, the parent commit), on hand-made runs and on the two state-space
cells' pools at rehearsal widths; and the metric as an entry and a file
alone."""
import numpy as np
import pytest

from benchmark import harness, manifest, system

MAN = manifest.Manifest()
NAME = "state_moved_share"
CELLS = ["granite-4.0-h-small.chat", "falcon-h1-34b-instruct.reasoning"]


def _run(stats0=None, stats1=None):
    return harness.RunData(cfg={}, device={}, w0=100.0, w1=150.0,
                           stats0=stats0 or {}, stats1=stats1 or {})


def _reader():
    fn, kw = MAN.reader(NAME)
    return lambda run: fn(run, **kw)


def test_the_share_is_read_over_the_window():
    """Six Mamba-2 layers, 32 slots, four steps a dispatch: before the
    window 100 dispatches moved 40000 of 76800; in it 10 more with 17 rows
    live throughout moved 10 x 4 x 17 x 6 = 4080 of 10 x 4 x 32 x 6 = 7680."""
    read = _reader()
    a = {"state_rows_moved": 40000, "state_rows_held": 76800}
    b = {"state_rows_moved": 40000 + 4080, "state_rows_held": 76800 + 7680}
    assert read(_run(a, b)) == 17 / 32
    assert read(_run({}, b)) == pytest.approx(44080 / 84480)


def test_nothing_to_read_is_none():
    """No counters (the parent's program, a stack without a Mamba-2
    layer), or no dispatch in the window: None, never 0 and never a
    raise."""
    read = _reader()
    assert read(_run({"dispatches": 3}, {"dispatches": 9})) is None
    same = {"state_rows_moved": 7, "state_rows_held": 70}
    assert read(_run(same, same)) is None


def test_the_new_metric_is_a_new_entry_and_file():
    """Appended to `per_layer` (by membership: a later PR appends behind
    it), listing the two cells whose stacks hold Mamba-2 layers."""
    names = [m["name"] for m in MAN.data["per_layer"]]
    assert names.count(NAME) == 1
    entry = {m["name"]: m for m in MAN.data["per_layer"]}[NAME]
    assert entry["workloads"] == CELLS
    assert entry["layer"] == "model step and kernels"
    assert (entry["better"], entry["source"]) == ("lower", "program_counter")
    e2e = {m["name"]: m for m in MAN.data["end_to_end"]}[entry["moves"]]
    for cell in CELLS:
        assert cell in e2e["workloads"]
        assert NAME in {m["name"] for m in MAN.per_layer(cell)}
    for cell in ("minicpm-sala.long-doc", "starcoder2-7b.completion",
                 "starcoder2-3b.batch"):
        assert NAME not in {m["name"] for m in MAN.per_layer(cell)}


def _served(cell_name: str, new: tuple) -> tuple[dict, dict]:
    """`stats()` of the cell's pool at rehearsal widths, before and after
    it served two requests of ``new`` tokens each."""
    cell = MAN.cell(cell_name)
    family = MAN.family(MAN.config(cell))
    cfg = system.model_config(MAN.config(cell), True, family)
    loop, server = system.build(cfg, family.weights.make_weights(cfg, 3),
                                family)
    loop.stop(timeout=30.0)
    stats0 = server.stats()
    rng = np.random.default_rng(3)
    for n, m in zip((9, min(cfg["serving"]["prompt_buckets"])), new):
        server.submit(rng.integers(0, cfg["vocab_size"], n).tolist(),
                      max_new=m)
    server.run_until_drained()
    return server, stats0, server.stats()


@pytest.mark.parametrize("cell", CELLS)
def test_a_state_space_pool_reports_the_live_rows_share(cell):
    """Each cell's pool at rehearsal widths serves a row of 3 tokens and
    one of 11: every decoded token moved its row's state in every Mamba-2
    layer, and nothing else moved, so the share is (2 + 10) tokens over
    the slots' steps."""
    server, stats0, stats1 = _served(cell, (3, 11))
    layers = sum(m.endswith("mamba2") for m in server.model.mixers)
    assert layers > 0
    held = stats1["dispatches"] * server.decode_steps * server.slots
    assert stats1["state_rows_held"] == held * layers
    assert _reader()(_run(stats0, stats1)) == pytest.approx((2 + 10) / held)


def test_a_stack_without_a_mamba_layer_reports_none():
    """`minicpm-sala.long-doc`'s stack (sparse and linear attention) keeps
    no such counters."""
    _server, stats0, stats1 = _served("minicpm-sala.long-doc", (6, 6))
    assert stats1["dispatches"] > 0
    assert "state_rows_held" not in stats1
    assert _reader()(_run(stats0, stats1)) is None
