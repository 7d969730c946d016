"""The LM user journey across components: train → checkpoint into the
replicated store → restore on a DIFFERENT node → KV-cached generation —
plus rollback to a historical version. Exercises engine/train_lm,
engine/checkpoint, store/sdfs and engine/generate together, the workflow
the reference could never do (no checkpointing, no sequence models)."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from idunno_tpu.comm.inproc import InProcNetwork
from idunno_tpu.config import ClusterConfig
from idunno_tpu.engine.checkpoint import (
    checkpoint_holders, restore_train_state, restore_variables,
    restore_version, save_train_state, save_variables)
from idunno_tpu.engine.generate import generate
from idunno_tpu.engine.train import flat_tx
from idunno_tpu.engine.train_lm import (
    create_lm_train_state, make_lm_train_step)
from idunno_tpu.membership.epoch import EpochFence, FenceRegistry
from idunno_tpu.membership.service import MembershipService
from idunno_tpu.models.transformer import TransformerLM
from idunno_tpu.store.sdfs import FileStoreService

from tests.test_membership import FakeClock, pump


@pytest.fixture
def stores(tmp_path):
    cfg = ClusterConfig(hosts=("n0", "n1", "n2"), coordinator="n0",
                        standby_coordinator="n1", introducer="n0",
                        replication_factor=2)
    net = InProcNetwork()
    clock = FakeClock()
    members, stores = {}, {}
    for h in cfg.hosts:
        t = net.transport(h)
        members[h] = MembershipService(h, cfg, t, clock=clock)
        stores[h] = FileStoreService(h, cfg, t, members[h],
                                     str(tmp_path / h))
    for h in cfg.hosts:
        members[h].join()
        clock.advance(0.01)
    pump(members, clock)
    return stores


def test_lm_served_through_cluster_control(stores, tmp_path):
    """The full LM serving story: train → save_lm into the store → a
    DIFFERENT node serves `generate` over the control RPC, matching a
    local decode from the same weights."""
    from idunno_tpu.comm.message import Message
    from idunno_tpu.engine.generate import load_lm, save_lm
    from idunno_tpu.serve.control import ControlService
    from idunno_tpu.utils.types import MessageType

    model = TransformerLM(vocab=32, dim=32, depth=2, num_heads=4)
    tx = optax.adam(1e-2)
    state = create_lm_train_state(model, jax.random.PRNGKey(0), 16, tx)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 32)
    step = jax.jit(make_lm_train_step(model, tx))
    for _ in range(5):
        state, _ = step(state, toks)
    save_lm(stores["n0"], "tiny", model, state.params)

    # reconstruct on another node: architecture + weights round-trip
    model2, params2 = load_lm(stores["n2"], "tiny")
    assert model2 == model
    prompt = toks[:2, :4]
    want = generate(model, state.params, prompt, prompt_len=4, max_new=5)

    # serve over the control RPC from a node wired to n2's store
    node = type("NodeStub", (), {})()
    # minimal fence surface for ControlService._handle's epoch check
    node.membership = SimpleNamespace(epoch=EpochFence(), scopes=FenceRegistry())
    node.host, node.store = "n2", stores["n2"]
    node.transport = stores["n2"].transport
    ctl = ControlService(node)
    out = ctl._handle("control", Message(
        MessageType.INFERENCE, "client",
        {"verb": "generate", "name": "tiny",
         "prompt": [[int(t) for t in row] for row in prompt],
         "max_new": 5}))
    assert out.type is MessageType.ACK, out.payload
    np.testing.assert_array_equal(np.asarray(out.payload["tokens"]),
                                  np.asarray(want))
    assert "tiny" in ctl._lms                      # cached for later calls

    # penalized one-shot generation over RPC (ADVICE r4 low: the verb
    # used to silently drop the penalty fields): greedy + penalties is
    # deterministic, so it must match the library call exactly. max_new
    # is 10 here, not 5: the penalty only bites once the greedy stream
    # repeats a generated token, and this tiny model's first repeat
    # lands past position 5 — 10 keeps the inequality check below real
    want_pen = generate(model, state.params, prompt, prompt_len=4,
                        max_new=10, presence_penalty=1.5,
                        frequency_penalty=0.5)
    want_plain = generate(model, state.params, prompt, prompt_len=4,
                          max_new=10)
    out_pen = ctl._handle("control", Message(
        MessageType.INFERENCE, "client",
        {"verb": "generate", "name": "tiny",
         "prompt": [[int(t) for t in row] for row in prompt],
         "max_new": 10, "presence_penalty": 1.5,
         "frequency_penalty": 0.5}))
    assert out_pen.type is MessageType.ACK, out_pen.payload
    np.testing.assert_array_equal(np.asarray(out_pen.payload["tokens"]),
                                  np.asarray(want_pen))
    assert not np.array_equal(np.asarray(want_pen), np.asarray(want_plain))

    # beam search over the same verb: matches the library call, scores
    # included; samplers are rejected (beam is a search, not a sampler)
    from idunno_tpu.engine.generate import beam_search
    want_seqs, want_scores = beam_search(model, state.params, prompt,
                                         prompt_len=4, max_new=5,
                                         beam_width=3)
    out_beam = ctl._handle("control", Message(
        MessageType.INFERENCE, "client",
        {"verb": "generate", "name": "tiny",
         "prompt": [[int(t) for t in row] for row in prompt],
         "max_new": 5, "beam_width": 3}))
    assert out_beam.type is MessageType.ACK, out_beam.payload
    np.testing.assert_array_equal(np.asarray(out_beam.payload["tokens"]),
                                  np.asarray(want_seqs))
    np.testing.assert_allclose(np.asarray(out_beam.payload["log_probs"]),
                               np.asarray(want_scores), rtol=1e-5)
    out_bad = ctl._handle("control", Message(
        MessageType.INFERENCE, "client",
        {"verb": "generate", "name": "tiny", "prompt": [[1, 2]],
         "max_new": 2, "beam_width": 3, "temperature": 0.7}))
    assert out_bad.type is MessageType.ERROR
    # penalties are sampler knobs too — beam must reject, not ignore them
    out_bad_pen = ctl._handle("control", Message(
        MessageType.INFERENCE, "client",
        {"verb": "generate", "name": "tiny", "prompt": [[1, 2]],
         "max_new": 2, "beam_width": 3, "presence_penalty": 1.0}))
    assert out_bad_pen.type is MessageType.ERROR

    # re-save with a DIFFERENT architecture: versions pair config+weights
    # atomically, the cache serves old weights until reload=true
    model_v2 = TransformerLM(vocab=32, dim=16, depth=1, num_heads=2,
                             dtype=jnp.bfloat16)
    params_v2 = model_v2.init(jax.random.PRNGKey(3),
                              jnp.zeros((1, 8), jnp.int32))["params"]
    save_lm(stores["n0"], "tiny", model_v2, params_v2)
    out_stale = ctl._handle("control", Message(
        MessageType.INFERENCE, "client",
        {"verb": "generate", "name": "tiny",
         "prompt": [[1, 2, 3, 4]], "max_new": 2}))
    assert out_stale.type is MessageType.ACK       # cache: old model still
    out_new = ctl._handle("control", Message(
        MessageType.INFERENCE, "client",
        {"verb": "generate", "name": "tiny", "reload": True,
         "prompt": [[1, 2, 3, 4]], "max_new": 2}))
    assert out_new.type is MessageType.ACK
    reloaded_model, _ = ctl._lms["tiny"]
    assert reloaded_model.dim == 16                # new architecture served
    assert reloaded_model.dtype == jnp.bfloat16    # dtype round-trips

    # historical version 1 still pairs the ORIGINAL architecture+weights
    old_model, old_params = load_lm(stores["n1"], "tiny", version=1)
    assert old_model.dim == 32
    np.testing.assert_array_equal(
        np.asarray(generate(old_model, old_params, prompt, prompt_len=4,
                            max_new=5)),
        np.asarray(want))

    # storable-architecture guards: code-only closures refuse loudly
    custom = TransformerLM(vocab=32, dim=16, depth=1, num_heads=2,
                           ffn_factory=lambda **kw: None)
    with pytest.raises(ValueError, match="custom"):
        save_lm(stores["n0"], "custom", custom, state.params)
    odd_attn = TransformerLM(vocab=32, dim=16, depth=1, num_heads=2,
                             attn_fn=lambda q, k, v, causal=True: v)
    with pytest.raises(ValueError, match="attn_fn"):
        save_lm(stores["n0"], "oddattn", odd_attn, state.params)


def test_moe_lm_persists_and_serves_from_store(stores):
    """Switch-MoE LMs round-trip through the store (the factory's
    declarative twin travels in the header) and serve from ANY node —
    generation from the reconstructed model is exact."""
    from idunno_tpu.engine.generate import load_lm, save_lm
    from idunno_tpu.models.moe import MoETransformerLM

    moe = MoETransformerLM(vocab=32, dim=16, depth=2, num_heads=2,
                           n_experts=4, capacity_factor=4.0, k=2,
                           moe_every=2)
    params = moe.init(jax.random.PRNGKey(2),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    assert save_lm(stores["n0"], "moe", moe, params) == 1

    loaded, lparams = load_lm(stores["n2"], "moe")
    assert loaded.ffn_factory.lm_store_ffn == {
        "kind": "switch", "n_experts": 4, "capacity_factor": 4.0,
        "hidden_ratio": 4, "k": 2}
    assert loaded.ffn_every == 2
    prompt = jnp.asarray([[3, 7, 11]], jnp.int32)
    want = generate(moe, params, prompt, prompt_len=3, max_new=6)
    got = generate(loaded, lparams, prompt, prompt_len=3, max_new=6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_continuous_batching_served_over_control_rpc(stores):
    """lm_serve / lm_submit / lm_poll: a store-persisted LM served through
    the node's continuous-batching decode pool, with submissions arriving
    from several RPC threads at once — every completion must match a
    standalone `generate` of its own prompt."""
    import threading
    import time

    from idunno_tpu.comm.message import Message
    from idunno_tpu.engine.generate import save_lm
    from idunno_tpu.serve.control import ControlService
    from idunno_tpu.utils.types import MessageType

    model = TransformerLM(vocab=32, dim=32, depth=2, num_heads=4)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    save_lm(stores["n0"], "pool", model, params)

    node = type("NodeStub", (), {})()
    # minimal fence surface for ControlService._handle's epoch check
    node.membership = SimpleNamespace(epoch=EpochFence(), scopes=FenceRegistry())
    node.host, node.store = "n2", stores["n2"]
    node.transport = stores["n2"].transport
    ctl = ControlService(node)

    def call(payload):
        out = ctl._handle("control", Message(
            MessageType.INFERENCE, "client", payload))
        return out

    try:
        out = call({"verb": "lm_submit", "name": "pool",
                    "prompt": [1], "max_new": 1})
        assert out.type is MessageType.ERROR          # pool not started yet
        assert "lm_serve" in out.payload["error"]

        out = call({"verb": "lm_serve", "name": "pool", "slots": 2,
                    "prompt_len": 6, "max_len": 20})
        assert out.type is MessageType.ACK and out.payload["slots"] == 2

        rng = np.random.default_rng(3)
        prompts = [[int(t) for t in rng.integers(0, 32, size=n)]
                   for n in (3, 6, 2, 4, 5)]
        ids: dict[int, list[int]] = {}
        lock = threading.Lock()

        def submit(prompt):
            out = call({"verb": "lm_submit", "name": "pool",
                        "prompt": prompt, "max_new": 8})
            assert out.type is MessageType.ACK, out.payload
            with lock:
                ids[out.payload["id"]] = prompt

        threads = [threading.Thread(target=submit, args=(p,))
                   for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        done = {}
        deadline = time.time() + 180.0
        while time.time() < deadline and len(done) < len(prompts):
            out = call({"verb": "lm_poll", "name": "pool"})
            assert out.type is MessageType.ACK, out.payload
            assert "errors" not in out.payload, out.payload
            for c in out.payload["completions"]:
                done[c["id"]] = c
            time.sleep(0.05)
        assert len(done) == len(prompts), f"only {len(done)} completed"

        for rid, c in done.items():
            prompt = ids[rid]
            assert c["prompt_len"] == len(prompt)
            want = generate(model, params,
                            jnp.asarray([prompt], jnp.int32),
                            prompt_len=len(prompt), max_new=8)
            assert c["tokens"] == [int(t) for t in np.asarray(want[0])], rid

        # oversized prompt: validation error surfaces on the RPC
        out = call({"verb": "lm_submit", "name": "pool",
                    "prompt": list(range(9)), "max_new": 1})
        assert out.type is MessageType.ERROR
        assert "bucket" in out.payload["error"]

        out = call({"verb": "lm_stop", "name": "pool"})
        assert out.type is MessageType.ACK and out.payload["stopped"]
    finally:
        ctl.close()


def _control_on(store):
    """A `ControlService` on a node stub over ``store``."""
    from idunno_tpu.serve.control import ControlService

    node = type("NodeStub", (), {})()
    # minimal fence surface for ControlService._handle's epoch check
    node.membership = SimpleNamespace(epoch=EpochFence(), scopes=FenceRegistry())
    node.host, node.store = store.host, store
    node.transport = store.transport
    return ControlService(node)


@pytest.fixture
def loads(monkeypatch):
    """The names `load_lm` was asked for, in order."""
    import idunno_tpu.engine.generate as gen

    asked, real = [], gen.load_lm

    def counting(store, name, *a, **kw):
        asked.append(name)
        return real(store, name, *a, **kw)
    monkeypatch.setattr(gen, "load_lm", counting)
    return asked


@pytest.mark.parametrize("key, value", [("draft", "small-lm"),
                                        ("draft_len", 3)])
def test_lm_serve_refuses_a_removed_option_by_name(stores, loads, key, value):
    """A payload that still carries `draft` or `draft_len` is refused by
    name before any model is loaded, and the node serves the next
    `lm_serve` of the same name."""
    from idunno_tpu.comm.message import Message
    from idunno_tpu.engine.generate import save_lm
    from idunno_tpu.utils.types import MessageType

    model = TransformerLM(vocab=32, dim=32, depth=1, num_heads=4)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    save_lm(stores["n0"], "plain", model, params)
    ctl = _control_on(stores["n1"])

    def call(payload):
        return ctl._handle("control", Message(
            MessageType.INFERENCE, "client", payload))

    serve = {"verb": "lm_serve", "name": "plain", "slots": 1,
             "prompt_len": 4, "max_len": 12}
    try:
        out = call(dict(serve, **{key: value}))
        assert out.type is MessageType.ERROR
        assert repr(key) in out.payload["error"], out.payload
        assert loads == []
        out = call({"verb": "lm_submit", "name": "plain",
                    "prompt": [1], "max_new": 1})
        assert out.type is MessageType.ERROR          # nothing serves yet
        out = call(serve)
        assert out.type is MessageType.ACK and out.payload["slots"] == 1
        assert loads == ["plain"]
    finally:
        ctl.close()


def test_manager_serve_of_a_removed_option_registers_no_pool(stores, loads):
    """`LMPoolManager.serve` of a spec carrying `draft`: the node's refusal
    comes back through the failed-build path and no pool stays registered,
    so nothing respawns it."""
    from idunno_tpu.serve.lm_manager import LMPoolManager

    ctl = _control_on(stores["n1"])

    class ToTheNode:
        def call(self, node, component, msg, timeout=30.0):
            return ctl._handle(component, msg)

    membership = SimpleNamespace(
        is_acting_master=True, epoch=EpochFence(), scopes=FenceRegistry(),
        members=SimpleNamespace(alive_hosts=lambda: ["n1"]),
        on_change=lambda cb: None, acting_master=lambda: "n1")
    cfg = ClusterConfig(hosts=("n1",), coordinator="n1",
                        standby_coordinator="n1", introducer="n1")
    mgr = LMPoolManager("n1", cfg, ToTheNode(), membership)
    try:
        with pytest.raises(ValueError, match="'draft'"):
            mgr.serve({"name": "plain", "slots": 1, "prompt_len": 4,
                       "max_len": 12, "draft": "small-lm"})
        assert not mgr.has_pool("plain") and mgr._pools == {}
        assert loads == []
    finally:
        ctl.close()


def test_train_job_over_rpc_then_serve(stores):
    """The whole LM story with NO out-of-band steps: publish a corpus into
    the store → train_start over the control RPC (background job,
    checkpoints into the store) → train_status until done (loss improved)
    → lm_serve the published model → lm_submit/lm_poll completions match a
    local generate from the job's own weights."""
    import time

    from idunno_tpu.comm.message import Message
    from idunno_tpu.engine.data_lm import save_corpus
    from idunno_tpu.engine.generate import load_lm
    from idunno_tpu.serve.control import ControlService
    from idunno_tpu.utils.types import MessageType

    rng = np.random.default_rng(0)
    # a learnable corpus: short periodic pattern, not uniform noise
    pattern = rng.integers(0, 32, size=17)
    save_corpus(stores["n0"], "corpus/tiny",
                np.tile(pattern, 400).astype(np.int32))

    node = type("NodeStub", (), {})()
    # minimal fence surface for ControlService._handle's epoch check
    node.membership = SimpleNamespace(epoch=EpochFence(), scopes=FenceRegistry())
    node.host, node.store = "n1", stores["n1"]
    node.transport = stores["n1"].transport
    ctl = ControlService(node)

    def call(payload):
        return ctl._handle("control", Message(
            MessageType.INFERENCE, "client", payload))

    try:
        out = call({"verb": "train_start", "name": "rpclm",
                    "corpus": "corpus/tiny",
                    "model": {"vocab": 32, "dim": 32, "depth": 1,
                              "num_heads": 4},
                    "steps": 12, "batch_size": 4, "seq_len": 16,
                    "checkpoint_every": 5, "lr": 1e-2})
        assert out.type is MessageType.ACK, out.payload

        st = {}
        deadline = time.time() + 300.0
        while time.time() < deadline:
            out = call({"verb": "train_status", "name": "rpclm"})
            assert out.type is MessageType.ACK, out.payload
            st = out.payload
            assert st["error"] is None, st
            if st["done"]:
                break
            time.sleep(0.1)
        assert st.get("done"), f"train job never finished: {st}"
        assert st["step"] == 12
        assert st["checkpoint_version"] >= 2      # periodic + final
        assert st["served_version"] is not None
        assert st["loss"] < st["first_loss"]      # it learned something

        # the published LM is servable: continuous batching pool over RPC
        out = call({"verb": "lm_serve", "name": "rpclm", "slots": 2,
                    "prompt_len": 4, "max_len": 12})
        assert out.type is MessageType.ACK, out.payload
        prompt = [int(t) for t in pattern[:4]]
        out = call({"verb": "lm_submit", "name": "rpclm",
                    "prompt": prompt, "max_new": 6})
        assert out.type is MessageType.ACK, out.payload
        rid = out.payload["id"]
        got = None
        deadline = time.time() + 180.0
        while time.time() < deadline and got is None:
            out = call({"verb": "lm_poll", "name": "rpclm"})
            for c in out.payload["completions"]:
                if c["id"] == rid:
                    got = c
            time.sleep(0.05)
        assert got is not None, "completion never arrived"

        model, params = load_lm(stores["n2"], "rpclm")
        want = generate(model, params, jnp.asarray([prompt], jnp.int32),
                        prompt_len=4, max_new=6)
        assert got["tokens"] == [int(t) for t in np.asarray(want[0])]
    finally:
        ctl.close()


def test_train_job_stop_and_resume(stores):
    """train_stop checkpoints and exits; a resume=True restart continues
    from the checkpointed step, not from scratch."""
    import time

    from idunno_tpu.engine.data_lm import save_corpus
    from idunno_tpu.engine.train_job import LMTrainJob

    rng = np.random.default_rng(1)
    save_corpus(stores["n0"], "corpus/stop",
                rng.integers(0, 32, size=4000).astype(np.int32))
    cfg = {"vocab": 32, "dim": 16, "depth": 1, "num_heads": 2}

    job = LMTrainJob(stores["n1"], "stoplm", corpus="corpus/stop",
                     model_config=cfg, steps=10_000, batch_size=4,
                     seq_len=16, checkpoint_every=3)
    deadline = time.time() + 300.0
    while time.time() < deadline and job.status()["step"] < 4:
        time.sleep(0.05)
    assert job.status()["step"] >= 4, job.status()
    job.stop()
    st = job.status()
    assert st["stopped"] and not st["done"] and st["error"] is None, st
    assert st["checkpoint_version"] is not None
    stopped_at = st["step"]

    resumed = LMTrainJob(stores["n2"], "stoplm", corpus="corpus/stop",
                         model_config=cfg, steps=stopped_at + 3,
                         batch_size=4, seq_len=16, checkpoint_every=100,
                         resume=True)
    resumed.join(timeout=120.0)
    st = resumed.status()
    assert st["error"] is None, st
    assert st["done"], st
    assert st["start_step"] == stopped_at     # continued, didn't restart
    assert st["step"] == stopped_at + 3


def test_train_job_resumes_per_tensor_era_checkpoint(stores):
    """A checkpoint written BEFORE the flat-optimizer layout (per-tensor
    adam opt_state trees) must still resume: the job detects the
    structure mismatch against its flat template and continues on the
    checkpoint's original layout instead of erroring (train_job.py's
    layout-probe fallback)."""
    import time

    from idunno_tpu.engine.data_lm import save_corpus
    from idunno_tpu.engine.train_job import LMTrainJob

    rng = np.random.default_rng(5)
    save_corpus(stores["n0"], "corpus/era",
                rng.integers(0, 32, size=4000).astype(np.int32))
    cfg = {"vocab": 32, "dim": 16, "depth": 1, "num_heads": 2}

    # hand-write a per-tensor-era checkpoint under the job's name: the
    # exact save path train_job used before flat_tx landed
    model = TransformerLM(**cfg)
    tx_pt = optax.adam(1e-2)
    state = create_lm_train_state(model, jax.random.PRNGKey(0), 16, tx_pt)
    step = jax.jit(make_lm_train_step(model, tx_pt))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, 32)
    for _ in range(3):
        state, _ = step(state, toks[:, :16])
    save_train_state(stores["n0"], "eralm", state)

    resumed = LMTrainJob(stores["n1"], "eralm", corpus="corpus/era",
                         model_config=cfg, steps=5, batch_size=4,
                         seq_len=16, checkpoint_every=100, resume=True)
    resumed.join(timeout=300.0)
    st = resumed.status()
    assert st["error"] is None, st
    assert st["done"], st
    assert st["start_step"] == 3, st      # continued from the checkpoint
    assert st["step"] == 5, st


def test_training_resume_is_exact(stores):
    """Full TrainState checkpoint/resume: train 5 steps, checkpoint, train
    5 more — a resume from the checkpoint on ANOTHER node must land on
    bit-identical losses and params (adam moments and step survive).
    Uses the FLAT optimizer layout `train_job` ships
    (engine/train.py:flat_tx), so the flat opt_state's store roundtrip is
    covered by the same exactness bar."""
    model = TransformerLM(vocab=32, dim=32, depth=1, num_heads=4)
    tx = flat_tx(optax.adam(1e-2))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 32)
    step = jax.jit(make_lm_train_step(model, tx))

    state = create_lm_train_state(model, jax.random.PRNGKey(0), 16, tx)
    for _ in range(5):
        state, _ = step(state, toks)
    save_train_state(stores["n0"], "lmjob", state)

    cont_losses = []
    for _ in range(5):
        state, m = step(state, toks)
        cont_losses.append(float(m["loss"]))

    template = create_lm_train_state(model, jax.random.PRNGKey(9), 16, tx)
    resumed, version = restore_train_state(stores["n2"], "lmjob", template)
    assert version == 1
    assert int(resumed.step) == 5
    resumed_losses = []
    for _ in range(5):
        resumed, m = step(resumed, toks)
        resumed_losses.append(float(m["loss"]))

    np.testing.assert_allclose(resumed_losses, cont_losses,
                               rtol=1e-6, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6),
        resumed.params, state.params)


def test_train_checkpoint_restore_generate(stores):
    model = TransformerLM(vocab=32, dim=32, depth=2, num_heads=4)
    tx = optax.adam(1e-2)
    state = create_lm_train_state(model, jax.random.PRNGKey(0), 16, tx)

    # v1: the untrained weights (rollback target)
    v1 = save_variables(stores["n0"], "lm", {"params": state.params})
    assert v1 == 1

    step = jax.jit(make_lm_train_step(model, tx))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 32)
    for _ in range(10):
        state, metrics = step(state, toks)
    v2 = save_variables(stores["n0"], "lm", {"params": state.params})
    assert v2 == 2
    assert len(checkpoint_holders(stores["n1"], "lm")) >= 2  # replicated

    # restore on a DIFFERENT node, structure from a fresh template
    template = {"params": model.init(jax.random.PRNGKey(9),
                                     jnp.zeros((1, 16), jnp.int32))["params"]}
    restored, version = restore_variables(stores["n2"], "lm", template)
    assert version == 2
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), restored["params"], state.params)

    # generation from the restored weights == generation from the live ones
    prompt = toks[:2, :4]
    out_live = generate(model, state.params, prompt, prompt_len=4,
                        max_new=6)
    out_restored = generate(model, restored["params"], prompt, prompt_len=4,
                            max_new=6)
    np.testing.assert_array_equal(np.asarray(out_live),
                                  np.asarray(out_restored))

    # a trained LM should continue its own training distribution better
    # than random init: compare next-token loss on the training batch
    logits_trained = model.apply({"params": restored["params"]}, toks)
    rolled = restore_version(stores["n1"], "lm", template, version=1)
    logits_init = model.apply({"params": rolled["params"]}, toks)

    def ce(logits):
        lp = jax.nn.log_softmax(logits[:, :-1])
        tgt = toks[:, 1:]
        return float(-jnp.take_along_axis(
            lp, tgt[..., None], axis=-1).mean())

    assert ce(logits_trained) < ce(logits_init) * 0.8

    # rollback generation differs from the trained one (sanity that
    # versioned restore really returned the old weights)
    out_rolled = generate(model, rolled["params"], prompt, prompt_len=4,
                          max_new=6)
    assert (np.asarray(out_rolled) != np.asarray(out_live)).any()


def test_int8_kv_cache_pool_over_rpc(stores):
    """`lm_serve kv_cache_dtype=int8` on a store-persisted NATIVE-cache
    model: the serve-time override swaps the cache layout without
    touching the stored weights, and completions match the int8-cache
    generate stream."""
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from idunno_tpu.engine.generate import generate, save_lm
    from idunno_tpu.models.transformer import TransformerLM
    from idunno_tpu.serve.control import ControlService
    from idunno_tpu.comm.message import Message
    from idunno_tpu.utils.types import MessageType

    model = TransformerLM(vocab=32, dim=32, depth=1, num_heads=4)
    params = model.init(jax.random.PRNGKey(5),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    save_lm(stores["n0"], "kv8", model, params)

    node = type("NodeStub", (), {})()
    # minimal fence surface for ControlService._handle's epoch check
    node.membership = SimpleNamespace(epoch=EpochFence(), scopes=FenceRegistry())
    node.host, node.store = "n1", stores["n1"]
    node.transport = stores["n1"].transport
    ctl = ControlService(node)

    def call(payload):
        return ctl._handle("control", Message(
            MessageType.INFERENCE, "client", payload))

    try:
        out = call({"verb": "lm_serve", "name": "kv8", "slots": 2,
                    "prompt_len": 4, "max_len": 16,
                    "kv_cache_dtype": "int8"})
        assert out.type is MessageType.ACK, out.payload
        prompt = [3, 9, 14]
        rid = call({"verb": "lm_submit", "name": "kv8",
                    "prompt": prompt, "max_new": 6}).payload["id"]
        got = None
        deadline = time.time() + 180.0
        while time.time() < deadline and got is None:
            for c in call({"verb": "lm_poll",
                           "name": "kv8"}).payload["completions"]:
                if c["id"] == rid:
                    got = c
            time.sleep(0.05)
        assert got is not None
        m8 = dataclasses.replace(model, kv_cache_dtype="int8")
        want = generate(m8, params, jnp.asarray([prompt], jnp.int32),
                        prompt_len=3, max_new=6)
        assert got["tokens"] == [int(t) for t in np.asarray(want[0])]
    finally:
        ctl.close()


def test_bad_kv_cache_dtype_does_not_kill_live_pool(stores):
    """A typo'd `kv_cache_dtype` on a reload must be rejected BEFORE the
    old serving loop is stopped — a live pool must never be destroyed by
    a bad option."""
    import jax
    import jax.numpy as jnp

    from idunno_tpu.engine.generate import save_lm
    from idunno_tpu.models.transformer import TransformerLM
    from idunno_tpu.serve.control import ControlService
    from idunno_tpu.comm.message import Message
    from idunno_tpu.utils.types import MessageType

    model = TransformerLM(vocab=32, dim=32, depth=1, num_heads=4)
    params = model.init(jax.random.PRNGKey(6),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    save_lm(stores["n0"], "kvbad", model, params)

    node = type("NodeStub", (), {})()
    # minimal fence surface for ControlService._handle's epoch check
    node.membership = SimpleNamespace(epoch=EpochFence(), scopes=FenceRegistry())
    node.host, node.store = "n1", stores["n1"]
    node.transport = stores["n1"].transport
    ctl = ControlService(node)

    def call(payload):
        return ctl._handle("control", Message(
            MessageType.INFERENCE, "client", payload))

    try:
        out = call({"verb": "lm_serve", "name": "kvbad", "slots": 1,
                    "prompt_len": 4, "max_len": 12})
        assert out.type is MessageType.ACK, out.payload
        out = call({"verb": "lm_serve", "name": "kvbad", "slots": 1,
                    "prompt_len": 4, "max_len": 12, "reload": True,
                    "kv_cache_dtype": "int8x"})
        assert out.type is MessageType.ERROR
        assert "kv_cache_dtype" in out.payload["error"]
        # the ORIGINAL loop still serves
        out = call({"verb": "lm_submit", "name": "kvbad",
                    "prompt": [1, 2], "max_new": 2})
        assert out.type is MessageType.ACK, out.payload
    finally:
        ctl.close()
