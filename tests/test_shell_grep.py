"""CLI shell (C12) + distributed grep (C14) tests over assembled Nodes."""
import time
from types import SimpleNamespace

import pytest

from idunno_tpu.cli.shell import Shell
from idunno_tpu.comm.inproc import InProcNetwork
from idunno_tpu.config import ClusterConfig
from idunno_tpu.serve.node import Node


class StubEngine:
    def infer(self, name, start, end, dataset_root=None):
        return SimpleNamespace(
            records=[(f"test_{i}.JPEG", f"class_{i % 1000}", 0.9)
                     for i in range(start, end + 1)],
            elapsed_s=0.001 * (end - start + 1))


@pytest.fixture
def nodes(tmp_path):
    cfg = ClusterConfig(hosts=("n0", "n1", "n2"), coordinator="n0",
                        standby_coordinator="n1", introducer="n0",
                        replication_factor=2, query_batch_size=50,
                        query_interval_s=0.0)
    net = InProcNetwork()
    out = {}
    for h in cfg.hosts:
        out[h] = Node(h, cfg, net.transport(h), str(tmp_path / h),
                      engine=StubEngine())
    for h in cfg.hosts:
        out[h].membership.join()
    for _ in range(3):
        for n in out.values():
            n.membership.ping_once()
    return cfg, net, out, tmp_path


def drain(nodes):
    for _ in range(10):
        if sum(n.inference.process_jobs_once() for n in nodes.values()) == 0:
            break


def test_shell_full_command_surface(nodes, tmp_path):
    cfg, net, nodes_d, tp = nodes
    outputs = []
    sh = Shell(nodes_d["n2"], out=outputs.append, async_inference=False)

    assert "n0" in sh.dispatch("list_mem")
    assert sh.dispatch("list_self").startswith("n2")
    assert "acting master: n0" in sh.dispatch("list_master")
    assert "list_mem" in sh.dispatch("help")
    assert "unknown command" in sh.dispatch("nonsense")

    # file store verbs
    local = tp / "up.txt"
    local.write_text("store me")
    assert "version 1" in sh.dispatch(f"put {local} remote.txt")
    assert "version" in sh.dispatch(f"get remote.txt {tp / 'down.txt'}")
    assert (tp / "down.txt").read_text() == "store me"
    ls_out = sh.dispatch("ls remote.txt")
    assert len(ls_out.splitlines()) >= cfg.replication_factor
    sh.dispatch(f"put {local} remote.txt")
    assert "versions [2, 1]" in sh.dispatch(
        f"get-versions remote.txt 2 {tp / 'both.txt'}")
    store_out = Shell(nodes_d["n0"], out=outputs.append).dispatch("store")
    assert "remote.txt" in store_out
    assert "deleted" in sh.dispatch("delete remote.txt")
    assert "error" in sh.dispatch(f"get remote.txt {tp / 'x.txt'}")

    # inference + stats
    assert "queries=[1]" in sh.dispatch("inference 0 49 resnet")
    drain(nodes_d)
    master_sh = Shell(nodes_d["n0"], out=outputs.append)
    assert "finished_images=50" in master_sh.dispatch("c1")
    assert "avg=" in master_sh.dispatch("c2")
    c4_path = tp / "result.txt"
    assert "50 records" in master_sh.dispatch(f"c4 {c4_path}")
    assert c4_path.exists()
    assert "resnet#1" in master_sh.dispatch("cq")
    assert "n0:" in master_sh.dispatch("cvm")

    # membership verbs
    assert "left" in sh.dispatch("leave")
    assert "joined" in sh.dispatch("join")


def test_shell_lm_and_train_commands(nodes):
    """The train/lm-serve/lm-submit/lm-poll shell verbs drive the node's
    control service end-to-end: train a tiny LM from a store corpus, serve
    it through the continuous-batching pool, fetch the completion."""
    import numpy as np

    from idunno_tpu.engine.data_lm import save_corpus

    cfg, net, nodes_d, tp = nodes
    outputs = []
    sh = Shell(nodes_d["n1"], out=outputs.append)
    try:
        # usage/validation surfaces
        assert "usage" in sh.dispatch("train onlyname")
        assert "key=value" in sh.dispatch("train a b 3 junk")
        assert "unknown train option" in sh.dispatch("train a b 3 zz=1")
        assert "error" in sh.dispatch("train-status nosuch")
        assert "no training job" in sh.dispatch("train-stop nosuch")
        assert "no serving pool" in sh.dispatch("lm-stop nosuch")

        pattern = np.random.default_rng(0).integers(0, 16, size=13)
        save_corpus(nodes_d["n0"].store, "corpus/shell",
                    np.tile(pattern, 300).astype(np.int32))
        assert "started" in sh.dispatch(
            "train shelllm corpus/shell 6 vocab=16 dim=16 depth=1 "
            "num_heads=2 batch_size=4 seq_len=8 checkpoint_every=3")
        deadline = time.time() + 120.0
        status = ""
        while time.time() < deadline and "done" not in status:
            status = sh.dispatch("train-status shelllm")
            assert "ERROR" not in status, status
            time.sleep(0.1)
        assert "done" in status and "step=6" in status

        assert "2 slots" in sh.dispatch(
            "lm-serve shelllm 4 10 slots=2")
        assert "already serving" in sh.dispatch("lm-serve shelllm 4 10")
        assert "request 0 queued" in sh.dispatch(
            "lm-submit shelllm 4 3 1 2")
        # sampler options parse and land in the pool (top_k new)
        assert "request 1 queued" in sh.dispatch(
            "lm-submit shelllm 2 temperature=0.8 top_k=3 top_p=0.9 "
            "seed=5 3 1 2")
        assert "unknown lm-submit option" in sh.dispatch(
            "lm-submit shelllm 2 bogus=1 3")
        deadline = time.time() + 60.0
        seen = ""
        while time.time() < deadline and not (
                "#0:" in seen and "#1:" in seen):
            seen += sh.dispatch("lm-poll shelllm") + "\n"
            time.sleep(0.05)
        assert "#0:" in seen and "#1:" in seen and "prompt_len=3" in seen
        line0 = next(ln for ln in seen.splitlines() if ln.startswith("#0:"))
        toks = line0.split(":")[1].split("(")[0].split()
        assert len(toks) == 3 + 4                  # prompt + max_new
        stats = sh.dispatch("lm-stats shelllm")
        assert "completed=2" in stats and "tokens_generated=6" in stats
        assert "live=0/2" in stats
        assert "stopped" in sh.dispatch("lm-stop shelllm")
    finally:
        nodes_d["n1"].control.close()


def test_lm_serve_answers_a_removed_option_and_sends_nothing():
    """`draft=` is an option the shell no longer knows: the unknown-option
    reply, and no `lm_serve` leaves the shell."""
    from types import SimpleNamespace

    sent = []
    node = SimpleNamespace(control=SimpleNamespace(
        _dispatch=lambda verb, payload: sent.append((verb, payload)) or {}))
    sh = Shell(node, out=lambda _line: None)
    assert sh.dispatch("lm-serve m 4 16 slots=2 draft=small draft_len=3") \
        == "unknown lm-serve option(s): ['draft', 'draft_len']"
    assert sent == []
    assert "draft" not in sh.dispatch("lm-serve m")      # the usage text
    assert "draft" not in sh.dispatch("help")


def test_distributed_grep(nodes):
    cfg, net, nodes_d, tp = nodes
    # each node logs something distinctive through its own logger
    for h, n in nodes_d.items():
        n.log.info("needle-%s found in haystack", h)
        for handler in n.log.handlers:
            handler.flush()
    sh_out = []
    sh = Shell(nodes_d["n1"], out=sh_out.append)
    text = sh.dispatch("grep needle-.*haystack")
    assert "TOTAL: 3 matching lines" in text
    for h in cfg.hosts:
        assert f"needle-{h}" in text
    # pattern errors surface per host, shell survives
    err = sh.dispatch("grep [unclosed")
    assert "ERROR" in err


def test_threaded_node_end_to_end(tmp_path):
    """Full runtime: Node.start() threads, paced query pump, completion."""
    cfg = ClusterConfig(hosts=("n0", "n1", "n2"), coordinator="n0",
                        standby_coordinator="n1", introducer="n0",
                        replication_factor=2, query_batch_size=50,
                        query_interval_s=0.0, ping_interval_s=0.05,
                        failure_timeout_s=0.5, metadata_interval_s=0.1)
    net = InProcNetwork()
    nodes = {h: Node(h, cfg, net.transport(h), str(tmp_path / h),
                     engine=StubEngine()) for h in cfg.hosts}
    try:
        for n in nodes.values():
            n.start()
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if all(len(n.membership.members.alive_hosts()) == 3
                   for n in nodes.values()):
                break
            time.sleep(0.05)
        qnums = nodes["n2"].inference.inference("resnet", 0, 149, pace_s=0.0)
        assert qnums == [1, 2, 3]
        deadline = time.time() + 10.0
        master = nodes["n0"].inference
        while time.time() < deadline:
            if all(master.query_done("resnet", q) for q in qnums):
                break
            time.sleep(0.05)
        assert all(master.query_done("resnet", q) for q in qnums)
        total = sum(len(master.results("resnet", q)) for q in qnums)
        assert total == 150
    finally:
        for n in nodes.values():
            n.stop()
