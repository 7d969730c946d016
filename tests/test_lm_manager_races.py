"""LMPoolManager placement/recovery races, unit-level (no cluster).

The initial ``serve()``/``train()`` build is a slow RPC (tens of seconds for
a cold TPU shape), and the pump runs many times while it is in
flight. The registry entry exists with node=None for that whole window, so
without a guard the pump's orphan-recovery path would concurrently place a
SECOND copy — leaking whichever live loop loses the race (the same leak
class as the ADVICE-r3 resize orphan, via placement instead of resize).
These tests drive the race deterministically: the fake transport invokes
the racing action from inside the build RPC, exactly when the manager has
released its lock to wait on the network.
"""
from types import SimpleNamespace

import pytest

from idunno_tpu.comm.message import Message
from idunno_tpu.config import ClusterConfig
from idunno_tpu.membership.epoch import EpochFence, FenceRegistry
from idunno_tpu.scheduler.fair import FairScheduler
from idunno_tpu.serve.lm_manager import LMPoolManager
from idunno_tpu.utils.types import MessageType

HOSTS = ("n0", "n1")


class HookedTransport:
    """Records control RPCs; ``on_build`` fires from INSIDE the first
    lm_serve/train_start call — the moment the manager is blocked on the
    network with its lock released."""

    def __init__(self):
        self.calls = []                      # (node, payload) in order
        self.on_build = None
        self._next_sub = 0

    def call(self, node, component, msg, timeout=30.0):
        p = dict(msg.payload)
        self.calls.append((node, p))
        verb = p.get("verb")
        if verb in ("lm_serve", "train_start") and self.on_build is not None:
            hook, self.on_build = self.on_build, None
            hook()
        if verb == "lm_serve":
            return Message(MessageType.ACK, node, {"slots": p.get("slots")})
        if verb == "lm_submit":
            self._next_sub += 1
            return Message(MessageType.ACK, node, {"id": self._next_sub})
        return Message(MessageType.ACK, node, {"completions": []})

    def verbs(self, *names):
        return [(n, p) for n, p in self.calls if p.get("verb") in names]


class FakeMembership:
    def __init__(self, hosts=HOSTS):
        self.is_acting_master = True
        self.members = SimpleNamespace(alive_hosts=lambda: list(hosts))
        self.epoch = EpochFence()
        self.scopes = FenceRegistry()
        self._hosts = hosts

    def on_change(self, cb):
        pass

    def acting_master(self):
        return self._hosts[0]


@pytest.fixture
def mgr():
    cfg = ClusterConfig(hosts=HOSTS, coordinator="n0",
                        standby_coordinator="n1", introducer="n0")
    service = SimpleNamespace(scheduler=FairScheduler(cfg))
    transport = HookedTransport()
    return (LMPoolManager("n0", cfg, transport, FakeMembership(),
                          inference_service=service), transport)


def test_pump_during_initial_build_does_not_double_place(mgr):
    m, tr = mgr
    tr.on_build = m.pump_once        # the pump fires mid-build
    out = m.serve({"name": "chat", "slots": 4, "prompt_len": 4,
                   "max_len": 32})
    assert out["node"] is not None
    serves = tr.verbs("lm_serve")
    assert len(serves) == 1, f"double placement: {serves}"
    assert m._pools["chat"]["node"] == serves[0][0]
    assert not m._pools["chat"].get("_recovering")


def test_pump_during_initial_train_does_not_double_start(mgr):
    m, tr = mgr
    tr.on_build = m.pump_once
    out = m.train({"name": "job", "model": "lm", "steps": 10})
    assert out["started"]
    starts = tr.verbs("train_start")
    assert len(starts) == 1, f"double start: {starts}"
    assert m._jobs["job"]["node"] == starts[0][0]
    assert not m._jobs["job"].get("_recovering")


def test_stop_racing_initial_build_stops_the_fresh_loop(mgr):
    m, tr = mgr
    tr.on_build = lambda: m.stop("chat")     # lm_stop wins the race
    out = m.serve({"name": "chat", "slots": 4, "prompt_len": 4,
                   "max_len": 32})
    assert out.get("stopped") and out["node"] is None
    assert "chat" not in m._pools
    # the freshly built loop must not keep serving unaccounted
    (build_node, _), = tr.verbs("lm_serve")
    stops = tr.verbs("lm_stop")
    assert (build_node, "chat") in [(n, p["name"]) for n, p in stops]


def test_stop_racing_recovery_stops_the_fresh_loop(mgr):
    m, tr = mgr
    m.serve({"name": "chat", "slots": 4, "prompt_len": 4, "max_len": 32})
    m._pools["chat"]["node"] = None          # orphaned (node died)
    tr.calls.clear()
    tr.on_build = lambda: m.stop("chat")     # stop wins the recovery race
    m._recover_pool("chat")
    assert "chat" not in m._pools
    (build_node, _), = tr.verbs("lm_serve")
    stops = tr.verbs("lm_stop")
    assert (build_node, "chat") in [(n, p["name"]) for n, p in stops]


def test_replaced_generation_survives_first_builds_commit(mgr):
    """stop + re-serve of the same name while the FIRST build's RPC is in
    flight replaces the registry entry with a new generation. The first
    build must not commit its node into (or un-guard, or delete) the new
    entry — identity, not name, decides — and must stop its own now-
    unaccounted loop."""
    m, tr = mgr

    def stop_and_reserve():
        m.stop("chat")
        m.serve({"name": "chat", "slots": 2, "prompt_len": 4,
                 "max_len": 32})         # generation B, nested build

    tr.on_build = stop_and_reserve
    out = m.serve({"name": "chat", "slots": 4, "prompt_len": 4,
                   "max_len": 32})       # generation A
    assert out.get("stopped") and out["node"] is None
    # generation B's entry is intact: its own slots, guard cleared by its
    # OWN build, node committed by its own build
    pool = m._pools["chat"]
    assert pool["slots_cap"] == 2 and not pool.get("_recovering")
    assert pool["node"] is not None
    # generation A stopped the loop its build created
    assert tr.verbs("lm_stop")


def test_resize_racing_stop_stops_the_fresh_loop(mgr):
    m, tr = mgr
    m.serve({"name": "chat", "slots": 8, "prompt_len": 4, "max_len": 32})
    node = m._pools["chat"]["node"]
    tr.calls.clear()
    tr.on_build = lambda: m.stop("chat")     # stop lands mid-rebuild
    m._resize_pool("chat", node, 4)
    assert "chat" not in m._pools
    stops = tr.verbs("lm_stop")
    assert (node, "chat") in [(n, p["name"]) for n, p in stops]
