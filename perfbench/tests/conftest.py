"""A copy of ``perfbench/`` in a temporary checkout, with two tiny cells
added to it as files and manifest entries only — the way a later PR adds
a cell. Runs go through the copy's own ``run.py`` in a child process, on
the CPU, with ``--rehearse`` (the harness's look for a chip is the one
thing skipped); ``crypto_provider: cpu`` keeps them to seconds.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

TINY = {
    "validators": 16, "voting_power": 10, "key_type": "ed25519", "sign_bytes_len": 160,
    "block_time_ns": 1000000000, "crypto_provider": "cpu", "reduced": [],
}


class BenchCopy:
    tiny = TINY

    def __init__(self, root):
        self.root = str(root)
        self.bench = os.path.join(self.root, "perfbench")
        shutil.copytree(BENCH, self.bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
        self.manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
        self.add_config("tiny-16", {**TINY, "chain_id": "pb-tiny", "entry": "verify_commit"})
        self.add_config(
            "tiny-light",
            {**TINY, "chain_id": "pb-tinylight", "entry": "verify_chain", "heights": 4,
             "trusting_period_ns": 10800000000000},
        )
        self.add_cell("tiny-commit", "tiny-16", "commit-stream-partial")
        self.add_cell("tiny-chain", "tiny-light", "seq-chain-128")

    def write(self, rel: str, text: str) -> None:
        path = os.path.join(self.bench, rel)
        assert not os.path.exists(path), f"{rel} is there already: a later PR may not edit it"
        with open(path, "w") as fh:
            fh.write(text)

    def add_config(self, name: str, body: dict) -> None:
        self.write(f"configs/{name}.json", json.dumps({"name": name, **body}))
        self.manifest["configs"].append({
            "name": name, "source": "perfbench/tests", "file": f"perfbench/configs/{name}.json",
            "reduced": [], "why": "test size",
        })
        self._save()

    def add_cell(self, name: str, config: str, traffic: str) -> None:
        self.manifest["workloads"].append(
            {"name": name, "config": config, "traffic": traffic, "chips": 1, "why": "test size"}
        )
        self._save()

    def add_layer_metric(self, name: str, unit: str, moves: str, workloads) -> None:
        self.manifest["per_layer"].append({
            "name": name, "unit": unit, "better": "higher", "source": "program_counter",
            "layer": "test", "moves": moves, "workloads": list(workloads),
        })
        self._save()

    def _save(self) -> None:
        with open(os.path.join(self.root, "BENCHMARK.json"), "w") as fh:
            json.dump(self.manifest, fh)

    def run(self, workload: str, seed: int, seconds: float = 0.5, trace: int = 0, script: str = "run.py"):
        """(exit code, the last line of standard output parsed, stderr)."""
        cmd = [sys.executable, os.path.join(self.bench, script), "--workload", workload,
               "--seed", str(seed), "--seconds", str(seconds), "--rehearse"]
        if script == "run.py":
            cmd += ["--trace", str(trace)]
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
        p = subprocess.run(cmd, cwd=self.root, env=env, capture_output=True, text=True, timeout=300)
        lines = p.stdout.strip().splitlines()
        return p.returncode, (json.loads(lines[-1]) if lines else None), p.stderr


@pytest.fixture
def bench_copy(tmp_path):
    return BenchCopy(tmp_path)
