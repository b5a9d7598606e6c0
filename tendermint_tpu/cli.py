"""Command-line interface.

Reference: cmd/tendermint/ — main.go:20-43 registers init, node,
testnet, gen_validator, gen_node_key, show_node_id, show_validator,
unsafe_reset_all, version (cobra; argparse here). `--home` mirrors the
reference's root-dir flag.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import signal
import sys
import time

from tendermint_tpu.config import (
    Config,
    default_config,
    load_config,
    write_config_file,
)
from tendermint_tpu.config.config import (
    DEFAULT_CONFIG_DIR,
    DEFAULT_CONFIG_FILE,
    ensure_root,
)
from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.p2p.key import NodeKey, load_or_gen_node_key
from tendermint_tpu.privval import load_or_gen_file_pv
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.version import TM_CORE_SEMVER

DEFAULT_HOME = os.path.expanduser("~/.tendermint_tpu")


def load_or_default_config(home: str) -> Config:
    path = os.path.join(home, DEFAULT_CONFIG_DIR, DEFAULT_CONFIG_FILE)
    cfg = load_config(path) if os.path.exists(path) else default_config()
    cfg.set_root(home)
    err = cfg.validate_basic()
    if err:
        raise SystemExit(f"invalid config: {err}")
    return cfg


# -- commands --------------------------------------------------------------


def cmd_init(args) -> None:
    """Reference commands/init.go: config + genesis + privval + node key."""
    home = args.home
    ensure_root(home)
    cfg = load_or_default_config(home)
    cfg_file = os.path.join(home, DEFAULT_CONFIG_DIR, DEFAULT_CONFIG_FILE)
    if not os.path.exists(cfg_file):
        write_config_file(cfg_file, cfg)

    pv = load_or_gen_file_pv(
        cfg.base.priv_validator_key_file(),
        cfg.base.priv_validator_state_file(),
        key_type=cfg.base.priv_validator_key_type,
    )
    load_or_gen_node_key(cfg.base.node_key_file())

    genesis_file = cfg.base.genesis_file()
    if not os.path.exists(genesis_file):
        # BLS keys carry a proof-of-possession in genesis — the
        # rogue-key admission gate for aggregated commits
        # (docs/bls-aggregation.md)
        pop = (
            pv.key.priv_key.register_possession()
            if pv.key.priv_key.type_name == "bls12-381"
            else b""
        )
        doc = GenesisDoc(
            chain_id=args.chain_id or f"test-chain-{os.urandom(3).hex()}",
            genesis_time_ns=time.time_ns(),
            validators=[
                GenesisValidator(
                    pub_key=pv.get_pub_key(), power=10, name="",
                    proof_of_possession=pop,
                )
            ],
        )
        doc.validate_and_complete()
        doc.save_as(genesis_file)
        print(f"Generated genesis file {genesis_file}")
    print(f"Initialized node in {home}")


def cmd_node(args) -> None:
    """Reference commands/run_node.go."""
    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.rpc.server import RPCServer

    cfg = load_or_default_config(args.home)
    if args.proxy_app:
        cfg.base.proxy_app = args.proxy_app
    if args.p2p_laddr:
        cfg.p2p.laddr = args.p2p_laddr
    if args.rpc_laddr:
        cfg.rpc.laddr = args.rpc_laddr
    if args.persistent_peers:
        cfg.p2p.persistent_peers = args.persistent_peers

    async def run() -> None:
        node = default_new_node(cfg)
        node.rpc_server = RPCServer(node)
        await node.start()
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        print(f"node {node.node_key.id} started (chain {node.genesis_doc.chain_id})")
        await stop.wait()
        await node.stop()

    asyncio.run(run())


def cmd_version(args) -> None:
    print(TM_CORE_SEMVER)


def cmd_gen_validator(args) -> None:
    """Print a fresh priv validator key json (reference gen_validator.go)."""
    priv = Ed25519PrivKey.generate()
    pub = priv.pub_key()
    print(
        json.dumps(
            {
                "address": pub.address().hex(),
                "pub_key": {"type": "ed25519", "value": pub.bytes().hex()},
                "priv_key": {"type": "ed25519", "value": priv.bytes().hex()},
            },
            indent=2,
        )
    )


def cmd_gen_node_key(args) -> None:
    cfg = load_or_default_config(args.home)
    ensure_root(args.home)
    nk = load_or_gen_node_key(cfg.base.node_key_file())
    print(nk.id)


def cmd_show_node_id(args) -> None:
    cfg = load_or_default_config(args.home)
    nk = NodeKey.load(cfg.base.node_key_file())
    print(nk.id)


def cmd_show_validator(args) -> None:
    cfg = load_or_default_config(args.home)
    from tendermint_tpu.privval import load_file_pv

    pv = load_file_pv(
        cfg.base.priv_validator_key_file(), cfg.base.priv_validator_state_file()
    )
    print(
        json.dumps(
            {"type": "ed25519", "value": pv.get_pub_key().bytes().hex()}, indent=2
        )
    )


def cmd_unsafe_reset_all(args) -> None:
    """Wipe data dir + reset privval state (reference reset_priv_validator.go)."""
    cfg = load_or_default_config(args.home)
    data_dir = cfg.base.db_path()
    if os.path.isdir(data_dir):
        for entry in os.listdir(data_dir):
            p = os.path.join(data_dir, entry)
            if os.path.basename(p) == os.path.basename(
                cfg.base.priv_validator_state_file()
            ):
                continue
            shutil.rmtree(p) if os.path.isdir(p) else os.unlink(p)
    if os.path.exists(cfg.base.priv_validator_key_file()):
        pv = load_or_gen_file_pv(
            cfg.base.priv_validator_key_file(), cfg.base.priv_validator_state_file()
        )
        pv.reset()
    print(f"Reset {data_dir}")


def cmd_testnet(args) -> None:
    """Generate N-node testnet config dirs (reference commands/testnet.go)."""
    n = args.v
    out = args.o
    starting_port = args.starting_port
    chain_id = args.chain_id or f"chain-{os.urandom(3).hex()}"

    pvs = []
    node_keys = []
    for i in range(n):
        home = os.path.join(out, f"node{i}")
        ensure_root(home)
        cfg = default_config().set_root(home)
        pv = load_or_gen_file_pv(
            cfg.base.priv_validator_key_file(), cfg.base.priv_validator_state_file()
        )
        pvs.append(pv)
        node_keys.append(load_or_gen_node_key(cfg.base.node_key_file()))

    genesis = GenesisDoc(
        chain_id=chain_id,
        genesis_time_ns=time.time_ns(),
        validators=[
            GenesisValidator(pub_key=pv.get_pub_key(), power=1, name=f"node{i}")
            for i, pv in enumerate(pvs)
        ],
    )
    genesis.validate_and_complete()

    if args.hostname_suffix and not args.hostname_prefix:
        print(
            "testnet: --hostname-suffix requires --hostname-prefix "
            "(IP-based peer lists have no hostname to suffix)",
            file=sys.stderr,
        )
        raise SystemExit(1)
    if args.hostname_prefix:
        # docker-style: each node at <prefix><octet+i><suffix>:26656
        # (reference testnet.go --hostname-prefix/--hostname-suffix/
        # --populate-persistent-peers). A suffix like ".myapp" makes the
        # names Kubernetes headless-service FQDNs
        # (tools/mintnet-kubernetes): tm-tpu-0.myapp, tm-tpu-1.myapp, ...
        peers = ",".join(
            f"{node_keys[i].id}@{args.hostname_prefix}{args.starting_ip_octet + i}"
            f"{args.hostname_suffix}:26656"
            for i in range(n)
        )
    else:
        peers = ",".join(
            f"{node_keys[i].id}@127.0.0.1:{starting_port + 2 * i}" for i in range(n)
        )
    for i in range(n):
        home = os.path.join(out, f"node{i}")
        cfg = default_config().set_root(home)
        cfg.base.moniker = f"node{i}"
        if args.hostname_prefix:
            cfg.p2p.laddr = "tcp://0.0.0.0:26656"
            cfg.rpc.laddr = "tcp://0.0.0.0:26657"
        else:
            cfg.p2p.laddr = f"tcp://127.0.0.1:{starting_port + 2 * i}"
            cfg.rpc.laddr = f"tcp://127.0.0.1:{starting_port + 2 * i + 1}"
        cfg.p2p.persistent_peers = ",".join(
            p for j, p in enumerate(peers.split(",")) if j != i
        )
        cfg.p2p.allow_duplicate_ip = True
        if not args.hostname_prefix and i > 0:
            # every node of the 127.0.0.1 layout runs on THIS machine,
            # and a chip belongs to one process: node0 keeps the
            # default device provider, the others verify on the host
            cfg.base.crypto_provider = "cpu"
        write_config_file(
            os.path.join(home, DEFAULT_CONFIG_DIR, DEFAULT_CONFIG_FILE), cfg
        )
        genesis.save_as(cfg.base.genesis_file())
    print(f"Successfully initialized {n} node directories in {out}")
    if not args.hostname_prefix and n > 1:
        print(
            f'node0 keeps crypto_provider = "tpu"; node1..node{n - 1} were '
            'given crypto_provider = "cpu" (one process per chip on one machine)'
        )


def cmd_light(args) -> None:
    """Reference cmd/tendermint/commands/lite.go: verifying RPC proxy."""

    async def run() -> None:
        from tendermint_tpu.crypto.batch import make_provider, set_default_provider
        from tendermint_tpu.db.memdb import MemDB
        from tendermint_tpu.light import LightClient, TrustOptions

        # the light client's entire job is commit verification — select
        # the batched device provider (non-blocking compile discipline)
        provider = make_provider(args.crypto_provider, block_on_compile=False)
        set_default_provider(provider)
        if hasattr(provider, "warmup"):
            provider.warmup(background=True)
        from tendermint_tpu.light.provider import HTTPProvider
        from tendermint_tpu.light.proxy import VerifyingClient
        from tendermint_tpu.light.proxy_server import make_light_proxy_server
        from tendermint_tpu.light.store import TrustedStore
        from tendermint_tpu.rpc.client import HTTPClient

        http = HTTPClient(args.primary)
        primary = HTTPProvider(args.chain_id, http)
        trusted_hash = bytes.fromhex(args.trusted_hash) if args.trusted_hash else None
        if trusted_hash is None:
            sh = await primary.signed_header(args.trusted_height)
            trusted_hash = sh.hash()
            print(f"WARNING: trusting fetched hash {trusted_hash.hex()} at height {args.trusted_height}")
        witnesses = [
            HTTPProvider(args.chain_id, HTTPClient(w)) for w in args.witness
        ]
        lc = LightClient(
            args.chain_id,
            TrustOptions(
                period_ns=args.trust_period_hours * 3600 * 10**9,
                height=args.trusted_height,
                hash=trusted_hash,
            ),
            primary,
            witnesses,
            TrustedStore(MemDB()),
        )
        await lc.initialize()
        server = make_light_proxy_server(VerifyingClient(http, lc), args.laddr)
        await server.start()
        print(f"light proxy listening at {server.listen_addr} (chain {args.chain_id})")
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        await server.stop()

    asyncio.run(run())


def cmd_replay(args) -> None:
    """Reference commands/replay.go: replay the WAL through a fresh
    consensus state over the stored chain."""

    async def run() -> None:
        from tendermint_tpu.node import default_new_node

        cfg = load_or_default_config(args.home)
        node = default_new_node(cfg)
        await node.start()  # handshake + WAL catchup IS the replay
        cs = node.consensus_state
        print(
            f"replayed to height {cs.state.last_block_height}, "
            f"round state {cs.rs.height_round_step()}"
        )
        await node.stop()

    asyncio.run(run())


async def _collect_debug_dump(rpc_laddr: str, out: str, home: str) -> None:
    """Shared collection for `debug dump` / `debug kill` (reference
    cmd/tendermint/commands/debug/util.go dumpStatus/dumpNetInfo/
    dumpConsensusState + WAL copy)."""
    from tendermint_tpu.rpc.client import HTTPClient

    os.makedirs(out, exist_ok=True)
    c = HTTPClient(rpc_laddr.replace("tcp://", ""))
    for route in ("status", "net_info", "dump_consensus_state", "consensus_state",
                  "num_unconfirmed_txs"):
        try:
            res = await c.call(route)
            with open(os.path.join(out, f"{route}.json"), "w") as fp:
                json.dump(res, fp, indent=2)
            print(f"wrote {route}.json")
        except Exception as e:
            print(f"failed {route}: {e}")
    # copy the consensus WAL group (debug/kill.go copyWAL)
    wal_dir = os.path.join(home, "data", "cs.wal")
    if os.path.isdir(wal_dir):
        import shutil

        dst = os.path.join(out, "cs.wal")
        shutil.copytree(wal_dir, dst, dirs_exist_ok=True)
        print(f"copied WAL -> {dst}")


def cmd_debug(args) -> None:
    """Reference cmd/tendermint/commands/debug/: `dump` collects
    status/net_info/consensus dumps over RPC; `kill` additionally
    SIGKILLs a running node after the evidence is safely on disk
    (debug/kill.go:36)."""

    async def run() -> None:
        if args.mode == "kill" and args.pid <= 0:
            # os.kill(0, ...) would signal OUR whole process group
            print("debug kill requires a positive node pid", file=sys.stderr)
            raise SystemExit(2)
        await _collect_debug_dump(args.rpc_laddr, args.out, args.home)
        if args.mode == "kill":
            import signal as _signal

            print(f"killing node process {args.pid}")
            os.kill(args.pid, _signal.SIGKILL)

    asyncio.run(run())


def cmd_replay_console(args) -> None:
    """Reference consensus/replay_file.go:34 RunReplayFile with console=
    true: step through the WAL interactively — `next [N]` feeds the next
    N messages into a fresh state machine, `rs` prints the round state,
    `quit` exits."""

    async def run() -> None:
        from tendermint_tpu.consensus.replay import WALReplayConsole

        cfg = load_or_default_config(args.home)
        console = WALReplayConsole(cfg)
        await console.open()
        try:
            print(f"{console.remaining()} WAL messages loaded; "
                  "commands: next [N] | rs | quit")
            src = open(args.script) if args.script else sys.stdin
            try:
                await _console_loop(console, src)
            finally:
                if src is not sys.stdin:
                    src.close()
        finally:
            await console.close()

    asyncio.run(run())


async def _console_loop(console, src) -> None:
    import sys as _sys

    while True:
        if src is _sys.stdin:
            print("> ", end="", flush=True)
        line = src.readline()
        if not line:
            break
        parts = line.strip().split()
        if not parts:
            continue
        if parts[0] in ("quit", "exit", "q"):
            break
        try:
            if parts[0] == "next":
                n = int(parts[1]) if len(parts) > 1 else 1
                fed = await console.step(n)
                print(f"fed {fed} message(s); rs={console.round_state()}")
            elif parts[0] == "rs":
                print(console.round_state())
            else:
                print(f"unknown command {parts[0]!r}")
        except Exception as e:
            print(f"error: {e}")


def cmd_signer_harness(args) -> None:
    """Reference tools/tm-signer-harness: acceptance-test a remote
    signer. The harness listens; point the signer under test at the
    printed address."""

    async def run() -> None:
        from tendermint_tpu.privval.harness import HarnessFailure, run_harness

        expected = None
        if args.key_file:
            from tendermint_tpu.privval.file import FilePVKey

            expected = FilePVKey.load(args.key_file).pub_key
        try:
            await run_harness(
                args.laddr, args.chain_id, expected_pub_key=expected,
                accept_timeout_s=args.accept_timeout,
            )
        except HarnessFailure as e:
            print(f"SIGNER HARNESS FAILED: {e}", file=sys.stderr)
            raise SystemExit(1)

    asyncio.run(run())


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tendermint-tpu", description="TPU-native BFT state-machine replication"
    )
    p.add_argument("--home", default=os.environ.get("TMHOME", DEFAULT_HOME))
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("init", help="initialize a node (config, genesis, keys)")
    sp.add_argument("--chain-id", default="")
    sp.set_defaults(func=cmd_init)

    sp = sub.add_parser("node", help="run a node")
    sp.add_argument("--proxy_app", default="")
    sp.add_argument("--p2p.laddr", dest="p2p_laddr", default="")
    sp.add_argument("--rpc.laddr", dest="rpc_laddr", default="")
    sp.add_argument("--p2p.persistent_peers", dest="persistent_peers", default="")
    sp.set_defaults(func=cmd_node)

    for name, fn in (
        ("version", cmd_version),
        ("gen_validator", cmd_gen_validator),
        ("gen_node_key", cmd_gen_node_key),
        ("show_node_id", cmd_show_node_id),
        ("show_validator", cmd_show_validator),
        ("unsafe_reset_all", cmd_unsafe_reset_all),
    ):
        sp = sub.add_parser(name)
        sp.set_defaults(func=fn)

    sp = sub.add_parser("light", help="run a light-client verifying RPC proxy")
    sp.add_argument("--primary", required=True, help="primary node RPC addr (host:port)")
    sp.add_argument("--witness", action="append", default=[], help="witness RPC addr (repeatable)")
    sp.add_argument("--chain-id", required=True)
    sp.add_argument("--trusted-height", type=int, default=1)
    sp.add_argument("--trusted-hash", default="", help="hex hash at trusted height (default: fetch)")
    sp.add_argument("--laddr", default="tcp://127.0.0.1:8888")
    sp.add_argument("--trust-period-hours", type=int, default=168)
    sp.add_argument(
        "--crypto-provider", default="tpu", choices=("tpu", "cpu"),
        help="batch verifier backend for header verification",
    )
    sp.set_defaults(func=cmd_light)

    sp = sub.add_parser("replay", help="replay the consensus WAL through a fresh state machine")
    sp.set_defaults(func=cmd_replay)

    sp = sub.add_parser(
        "replay_console",
        help="step through the consensus WAL interactively (next/rs/quit)",
    )
    sp.add_argument("--script", default="", help="read console commands from a file")
    sp.set_defaults(func=cmd_replay_console)

    sp = sub.add_parser("debug", help="dump node state via RPC (and optionally kill it)")
    sp.add_argument("mode", nargs="?", default="dump", choices=("dump", "kill"))
    sp.add_argument("pid", nargs="?", type=int, default=0, help="node pid (kill mode)")
    sp.add_argument("--rpc-laddr", default="tcp://127.0.0.1:26657")
    sp.add_argument("--out", default="./debug_dump")
    sp.set_defaults(func=cmd_debug)

    sp = sub.add_parser(
        "signer_harness", help="acceptance-test a remote signer (tm-signer-harness)"
    )
    sp.add_argument("--laddr", default="tcp://127.0.0.1:0")
    sp.add_argument("--chain-id", default="test-chain")
    sp.add_argument("--key-file", default="", help="expected privval key file (optional)")
    sp.add_argument("--accept-timeout", type=float, default=30.0)
    sp.set_defaults(func=cmd_signer_harness)

    sp = sub.add_parser("testnet", help="generate testnet config dirs")
    sp.add_argument("--v", type=int, default=4, help="number of validators")
    sp.add_argument("--o", default="./mytestnet", help="output directory")
    sp.add_argument("--starting-port", type=int, default=26656)
    sp.add_argument("--chain-id", default="")
    sp.add_argument(
        "--hostname-suffix", default="",
        help="appended after each node's ordinal (e.g. '.myapp' for "
        "Kubernetes headless-service names, reference testnet.go "
        "--hostname-suffix)",
    )
    sp.add_argument(
        "--hostname-prefix", default="",
        help="docker mode: peer IPs become <prefix><octet+i>:26656 "
             "(e.g. 192.167.10.)",
    )
    sp.add_argument("--starting-ip-octet", type=int, default=2)
    sp.set_defaults(func=cmd_testnet)

    return p


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    args.func(args)


if __name__ == "__main__":
    main()
