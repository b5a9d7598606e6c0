#!/usr/bin/env python3
"""p2p scenario driver — the reference's test/p2p/ rig, runnable with
process-backed nodes (no docker needed) or against the docker compose
localnet.

Reference: test/p2p/local_testnet_start.sh, basic/, atomic_broadcast/,
fast_sync/, kill_all/, pex/, persistent_peers.sh. Each scenario there
is a shell script driving docker containers; here one driver owns
node lifecycle + RPC assertions and the thin shell wrappers keep the
reference's entry-point names. Backend selection:

  TM_P2P_BACKEND=procs   (default) N `tendermint_tpu node` processes
  TM_P2P_BACKEND=docker  docker compose -f networks/local/docker-compose.yml

Usage:
  python test/p2p/driver.py all            # every scenario, procs backend
  python test/p2p/driver.py basic pex      # selected scenarios
  python test/p2p/driver.py --keep basic   # leave the net running
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
N_NODES = 4


def log(msg: str) -> None:
    print(f"[p2p] {msg}", flush=True)


def rpc(port, method, timeout=5, **params):
    body = json.dumps(
        {"jsonrpc": "2.0", "id": 1, "method": method, "params": params}
    ).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        doc = json.loads(resp.read())
    if doc.get("error"):
        raise RuntimeError(doc["error"])
    return doc["result"]


def wait_for(cond, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            if cond():
                return
        except Exception:
            pass
        time.sleep(0.4)
    raise TimeoutError(what)


def free_port_range(n, start=29000, end=60000):
    import random

    for _ in range(200):
        base = random.randrange(start, end, 16)
        socks = []
        try:
            for i in range(n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no contiguous free port range found")


class ProcNet:
    """Process-backed localnet (the reference rig's containers become
    host processes; config layout is identical `testnet` output)."""

    def __init__(self, out_dir, n=N_NODES, pex_topology=False):
        self.out = out_dir
        self.n = n
        self.base_port = free_port_range(2 * n)
        self.procs: dict = {}
        subprocess.run(
            [sys.executable, "-m", "tendermint_tpu", "testnet", "--v", str(n),
             "--o", self.out, "--chain-id", "p2p-scenario-chain",
             "--starting-port", str(self.base_port)],
            check=True, capture_output=True, cwd=REPO,
        )
        if pex_topology:
            self._rewrite_for_pex()

    def _rewrite_for_pex(self) -> None:
        """pex scenario topology (reference test/p2p/pex): node0 is the
        only seed; every other node knows ONLY node0 and must discover
        the rest through PEX address exchange."""
        sys.path.insert(0, REPO)
        from tendermint_tpu.config.config import load_config, write_config_file

        node0_cfg = load_config(self._cfg_path(0)).set_root(self._home(0))
        peers = node0_cfg.p2p.persistent_peers.split(",")
        # peers list excludes self; reconstruct node0's own address
        node0_addr = None
        for i in range(1, self.n):
            cfg_i = load_config(self._cfg_path(i)).set_root(self._home(i))
            for p in cfg_i.p2p.persistent_peers.split(","):
                if p.endswith(f":{self.base_port}"):
                    node0_addr = p
        assert node0_addr, "node0 address not found"
        for i in range(1, self.n):
            cfg_i = load_config(self._cfg_path(i)).set_root(self._home(i))
            cfg_i.p2p.persistent_peers = ""
            cfg_i.p2p.seeds = node0_addr
            cfg_i.p2p.pex = True
            write_config_file(self._cfg_path(i), cfg_i)

    def _home(self, i):
        return os.path.join(self.out, f"node{i}")

    def _cfg_path(self, i):
        return os.path.join(self._home(i), "config", "config.toml")

    def rpc_port(self, i):
        return self.base_port + 2 * i + 1

    def start(self, i):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["TM_CRYPTO_PROVIDER"] = "cpu"
        env.pop("FAIL_TEST_INDEX", None)
        logf = open(os.path.join(self.out, f"node{i}.log"), "ab")
        p = subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu", "--home", self._home(i), "node"],
            env=env, cwd=REPO, stdout=logf, stderr=logf,
        )
        self.procs[i] = p
        return p

    def start_all(self):
        # one machine, one chip, N node processes: every node is pinned
        # to the host verifier (start() sets TM_CRYPTO_PROVIDER=cpu)
        print(f"starting {self.n} nodes with crypto_provider=cpu (JAX_PLATFORMS=cpu)")
        for i in range(self.n):
            self.start(i)

    def stop(self, i, sig=signal.SIGTERM, timeout=15):
        p = self.procs.get(i)
        if p is None or p.poll() is not None:
            return
        p.send_signal(sig)
        try:
            p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()

    def kill(self, i):
        self.stop(i, sig=signal.SIGKILL, timeout=5)

    def stop_all(self):
        for i in list(self.procs):
            self.stop(i)

    def height(self, i):
        return int(rpc(self.rpc_port(i), "status")["sync_info"]["latest_block_height"])

    def n_peers(self, i):
        return int(rpc(self.rpc_port(i), "net_info")["n_peers"])


class DockerNet:
    """docker compose backend (networks/local). Requires docker; the
    scenarios then run against the compose services' published RPC
    ports (26657, 26660, ...)."""

    def __init__(self, out_dir, n=N_NODES, pex_topology=False):
        if shutil.which("docker") is None:
            raise RuntimeError("docker not available; use TM_P2P_BACKEND=procs")
        if pex_topology:
            raise RuntimeError("pex topology is procs-backend only for now")
        self.n = n
        self.compose = os.path.join(REPO, "networks", "local", "docker-compose.yml")
        subprocess.run(
            ["docker", "compose", "-f", self.compose, "up", "-d", "--build"],
            check=True, cwd=REPO,
        )
        self.procs = {}

    def rpc_port(self, i):
        return 26657 + 3 * i  # compose publishes sequential port triples

    def start(self, i):
        subprocess.run(
            ["docker", "compose", "-f", self.compose, "start", f"node{i}"], check=True
        )

    def start_all(self):
        pass  # `up` already started everything

    def stop(self, i, **_):
        subprocess.run(
            ["docker", "compose", "-f", self.compose, "stop", f"node{i}"], check=True
        )

    def kill(self, i):
        subprocess.run(
            ["docker", "compose", "-f", self.compose, "kill", f"node{i}"], check=True
        )

    def stop_all(self):
        subprocess.run(
            ["docker", "compose", "-f", self.compose, "down", "-v"], check=True
        )

    def height(self, i):
        return int(rpc(self.rpc_port(i), "status")["sync_info"]["latest_block_height"])

    def n_peers(self, i):
        return int(rpc(self.rpc_port(i), "net_info")["n_peers"])


def make_net(out_dir, pex_topology=False):
    backend = os.environ.get("TM_P2P_BACKEND", "procs")
    cls = DockerNet if backend == "docker" else ProcNet
    return cls(out_dir, pex_topology=pex_topology)


# -- scenarios (reference test/p2p/<name>/test.sh) ---------------------------


def scenario_basic(net):
    """All nodes make progress (reference test/p2p/basic/test.sh)."""
    wait_for(
        lambda: all(net.height(i) >= 3 for i in range(net.n)),
        120, "nodes never reached height 3",
    )
    log("basic OK: all nodes at height >= 3")


def scenario_atomic_broadcast(net):
    """A tx sent to node0 is readable everywhere (reference
    test/p2p/atomic_broadcast/test.sh)."""
    res = rpc(net.rpc_port(0), "broadcast_tx_commit", timeout=20, tx=b"p2p=rig".hex())
    assert res["deliver_tx"]["code"] == 0, res
    for i in range(net.n):
        wait_for(
            lambda i=i: bytes.fromhex(
                rpc(net.rpc_port(i), "abci_query", path="/store", data=b"p2p".hex())
                ["response"]["value"]
            ) == b"rig",
            60, f"tx never replicated to node{i}",
        )
    log("atomic_broadcast OK: tx visible on every node")


def scenario_fast_sync(net):
    """One node stops, the chain advances, the node restarts and
    catches up (reference test/p2p/fast_sync/test.sh)."""
    victim = net.n - 1
    net.stop(victim)
    h = net.height(0)
    wait_for(lambda: net.height(0) >= h + 4, 120, "chain stalled without victim")
    net.start(victim)
    wait_for(
        lambda: net.height(victim) >= net.height(0) - 2,
        180, "victim never caught up",
    )
    log(f"fast_sync OK: node{victim} caught up after restart")


def scenario_kill_all(net):
    """SIGKILL every node; restart; the chain continues from where it
    stopped (reference test/p2p/kill_all/test.sh + WAL replay)."""
    h_before = max(net.height(i) for i in range(net.n))
    for i in range(net.n):
        net.kill(i)
    for i in range(net.n):
        net.start(i)
    wait_for(
        lambda: all(net.height(i) >= h_before + 2 for i in range(net.n)),
        180, "chain never resumed after kill_all",
    )
    log(f"kill_all OK: resumed past height {h_before}")


def scenario_pex(net):
    """Nodes knowing only the seed discover the full mesh via PEX
    (reference test/p2p/pex/test.sh dial_seeds)."""
    want = net.n - 1
    wait_for(
        lambda: all(net.n_peers(i) >= want for i in range(net.n)),
        180, "PEX never filled the mesh",
    )
    wait_for(
        lambda: all(net.height(i) >= 3 for i in range(net.n)),
        120, "pex net never made progress",
    )
    log(f"pex OK: every node discovered {want} peers through the seed")


SCENARIOS = {
    "basic": (scenario_basic, False),
    "atomic_broadcast": (scenario_atomic_broadcast, False),
    "fast_sync": (scenario_fast_sync, False),
    "kill_all": (scenario_kill_all, False),
    "pex": (scenario_pex, True),  # needs the seed-only topology
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("scenarios", nargs="+", help=f"{'|'.join(SCENARIOS)}|all")
    ap.add_argument("--keep", action="store_true", help="leave the net running")
    ap.add_argument("--out", default=None, help="testnet dir (default: temp)")
    args = ap.parse_args(argv)

    names = list(SCENARIOS) if args.scenarios == ["all"] else args.scenarios
    for nm in names:
        if nm not in SCENARIOS:
            ap.error(f"unknown scenario {nm!r}")

    # pex needs its own topology; run it on a separate net
    normal = [n for n in names if not SCENARIOS[n][1]]
    special = [n for n in names if SCENARIOS[n][1]]
    rc = 0
    for group, pex_topology in ((normal, False), (special, True)):
        if not group:
            continue
        out = args.out or tempfile.mkdtemp(prefix="p2p-rig-")
        log(f"net dir: {out} (pex_topology={pex_topology})")
        net = make_net(out, pex_topology=pex_topology)
        try:
            net.start_all()
            if not pex_topology:
                # every node's RPC answering before any scenario runs:
                # scenarios call net.height() unguarded, and a subset
                # run that skips `basic` (which used to absorb startup)
                # hit ConnectionRefused on a fresh net. The pex net is
                # exempt: its nodes must DISCOVER the quorum first, the
                # scenario budgets its own 180s for that, and its
                # wait_for loops already swallow connection errors.
                wait_for(
                    lambda: all(net.height(i) >= 1 for i in range(net.n)),
                    120, "net never came up",
                )
            for nm in group:
                log(f"--- scenario {nm} ---")
                SCENARIOS[nm][0](net)
        except Exception as e:
            log(f"FAIL: {e!r}")
            rc = 1
        finally:
            if not args.keep:
                net.stop_all()
    log("ALL SCENARIOS PASSED" if rc == 0 else "SCENARIOS FAILED")
    return rc


if __name__ == "__main__":
    sys.exit(main())
