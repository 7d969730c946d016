"""Run one cluster node with the interactive shell.

    python -m idunno_tpu --host node0 [--config cluster.json] \
        [--data-dir ./node0-data] [--dataset ./images] [--no-shell]

The config JSON mirrors ``ClusterConfig`` (hosts, coordinator,
standby_coordinator, introducer, ports, timeouts); an ``addresses`` map
{host: ip} may be included for multi-machine deployments — otherwise all
hosts resolve to 127.0.0.1 with per-host port offsets (single-machine
clusters), replacing the reference's hardcoded IP tables (`utils.py:70-92`).
"""
from __future__ import annotations

import argparse
import json
import sys


def build_addr_of(config, addresses: dict[str, str]):
    def addr_of(host: str):
        ip = addresses.get(host, "127.0.0.1")
        # distinct ports per host when everything is local
        offset = (0 if addresses.get(host) else
                  100 * config.hosts.index(host))
        return (ip, config.ports.store + offset,
                config.ports.membership + offset)
    return addr_of


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="idunno_tpu")
    ap.add_argument("--host", required=True, help="this node's name")
    ap.add_argument("--config", help="cluster config JSON")
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--dataset", default=None,
                    help="local dataset root (test_<N>.JPEG files)")
    ap.add_argument("--no-shell", action="store_true",
                    help="run headless (no interactive shell)")
    ap.add_argument("--cpu", action="store_true",
                    help="run the engine on the CPU on purpose (ops "
                         "testing; several local nodes can't share one "
                         "TPU chip). Without it a node that finds no "
                         "accelerator refuses to start")
    ap.add_argument("--jax-coordinator", default=None,
                    help="ip:port for jax.distributed bring-up (multi-host "
                         "mesh over DCN); all nodes must pass the same value")
    ap.add_argument("--jax-num-processes", type=int, default=None)
    ap.add_argument("--jax-process-id", type=int, default=None)
    args = ap.parse_args(argv)

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    from idunno_tpu.utils.compile_cache import enable_persistent_cache
    enable_persistent_cache()

    if args.jax_coordinator:
        from idunno_tpu.parallel.mesh import initialize_distributed
        initialize_distributed(args.jax_coordinator,
                               num_processes=args.jax_num_processes,
                               process_id=args.jax_process_id)

    if not args.cpu:
        import jax
        if jax.default_backend() == "cpu":
            # a deployment that meant the chip must not serve from the
            # host in silence: the CPU is only ever chosen, never found
            ap.error("no accelerator found (JAX backend is 'cpu'); pass "
                     "--cpu to run the engine on the CPU on purpose")

    from idunno_tpu.cli.shell import Shell
    from idunno_tpu.comm.net import NetTransport
    from idunno_tpu.config import ClusterConfig
    from idunno_tpu.serve.node import Node

    addresses: dict[str, str] = {}
    engine_config = None
    if args.config:
        with open(args.config) as f:
            raw = json.load(f)
        addresses = raw.pop("addresses", {})
        engine_raw = raw.pop("engine", None)
        if engine_raw is not None:
            from idunno_tpu.config import EngineConfig
            engine_config = EngineConfig(**engine_raw)
        if "ports" in raw:
            from idunno_tpu.config import PortConfig
            raw["ports"] = PortConfig(**raw["ports"])
        if "hosts" in raw:
            raw["hosts"] = tuple(raw["hosts"])
        config = ClusterConfig(**raw)
    else:
        config = ClusterConfig.from_env()
    if args.host not in config.hosts:
        ap.error(f"--host {args.host!r} not in configured hosts")

    transport = NetTransport(args.host, build_addr_of(config, addresses))
    node = Node(args.host, config, transport,
                data_dir=args.data_dir or f"./{args.host}-data",
                engine_config=engine_config,
                dataset_root=args.dataset)
    node.start()
    try:
        if args.no_shell:
            import threading
            threading.Event().wait()
        else:
            Shell(node).run()
    except KeyboardInterrupt:
        pass
    finally:
        node.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
