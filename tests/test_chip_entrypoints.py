"""How the program reaches (or refuses) a device, and where its caches
live — what replaced the subprocess backend probe and the CPU fallbacks:

- a missing chip makes chip_smoke.py's `device` phase and bench.py exit
  non-zero (here under JAX_PLATFORMS=cpu, tiny, nothing compiles);
- the bench supervisor never imports JAX and starts the cold-start
  child only after the measuring child has exited;
- one helper decides the cache directory: JAX_COMPILATION_CACHE_DIR
  verbatim when set, else a fixed git-ignored path in the checkout,
  with the AOT and table caches under the same root;
- launchers that put several nodes on one machine give all but one the
  host provider.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- chip_smoke.py ------------------------------------------------------------


@pytest.mark.parametrize(
    "extra_env",
    [{}, {"TM_CRYPTO_PROVIDER": "tpu", "JAX_COMPILATION_CACHE_DIR": "/nonexistent/cache"}],
    ids=["plain", "whatever-else-is-set"],
)
def test_chip_smoke_exits_nonzero_at_device_phase_without_a_tpu(extra_env, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "TM_CRYPTO_PROVIDER"}
    env.update(JAX_PLATFORMS="cpu", **extra_env)
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
    )
    assert res.returncode != 0, res.stdout[-2000:]
    assert '"ok"' not in res.stdout
    assert "=== phase device" in res.stdout and "not a TPU" in res.stdout
    # it stopped there: no later phase started, nothing was built
    assert "phase commit-10k" not in res.stdout


def test_chip_smoke_refuses_a_host_override(monkeypatch):
    import chip_smoke

    monkeypatch.setenv("TM_CRYPTO_PROVIDER", "cpu")
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.refuse_host_overrides()
    monkeypatch.setenv("TM_CRYPTO_PROVIDER", "tpu")
    monkeypatch.delenv("TM_FAULTS", raising=False)
    chip_smoke.refuse_host_overrides()


# -- require_accelerator / bench.py --------------------------------------------


def test_require_accelerator_never_picks_the_cpu_on_its_own(monkeypatch):
    from tendermint_tpu.utils.jaxenv import require_accelerator

    # the caller asked for the CPU: granted, and named
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert require_accelerator("t").platform == "cpu"
    # the backend merely IS the CPU (no chip, nobody asked): refused
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as ei:
        require_accelerator("t")
    assert ei.value.code not in (0, None) and "not a TPU" in str(ei.value.code)


def test_bench_child_exits_nonzero_without_a_chip(monkeypatch):
    import bench

    monkeypatch.setenv("TM_BENCH_INNER", "1")
    monkeypatch.delenv("TM_BENCH_COLDSTART", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(bench, "run_bench", lambda platform: pytest.fail("bench ran on the CPU"))
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code not in (0, None)


class _FakeChild:
    """Stands in for the measuring child: `on_wait` runs when the
    supervisor waits for it, i.e. it is what the child did before it
    exited."""

    def __init__(self, rc, on_wait):
        self.rc, self.on_wait, self.exited = rc, on_wait, False

    def wait(self, timeout=None):
        self.on_wait()
        self.exited = True
        return self.rc

    def kill(self):
        pass


@pytest.fixture()
def supervised(monkeypatch, tmp_path):
    import bench

    monkeypatch.setattr(bench, "_STATE_PATH", str(tmp_path / "state" / "bench_state.json"))
    monkeypatch.setattr(bench, "_LAST_TPU_PATH", str(tmp_path / "last_tpu_result.json"))
    monkeypatch.setenv("TM_BENCH_NO_GUARD", "1")
    return bench


def test_bench_supervisor_propagates_a_child_that_found_no_chip(supervised, monkeypatch, capsys):
    bench = supervised
    child = _FakeChild(1, lambda: None)
    monkeypatch.setattr(subprocess, "Popen", lambda *a, **kw: child)
    monkeypatch.setattr(bench, "_run_coldstart", lambda: pytest.fail("cold start without a line"))
    assert bench._supervise() == 1
    assert capsys.readouterr().out.strip() == ""  # no result line for a run that measured nothing


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_bench_supervisor_runs_coldstart_only_after_the_child_exited(
    supervised, monkeypatch, capsys, platform
):
    bench = supervised
    line = {"metric": "m", "value": 1.0, "platform": platform, "bench_n": 16}

    def hand_over():
        with open(bench._STATE_PATH, "w") as fp:
            json.dump({"line": line}, fp)

    child = _FakeChild(0, hand_over)
    monkeypatch.setattr(subprocess, "Popen", lambda *a, **kw: child)
    cold = []

    def coldstart():
        cold.append(child.exited)
        return {"coldstart_first_verify_s": 2.5}

    monkeypatch.setattr(bench, "_run_coldstart", coldstart)
    assert bench._supervise() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["platform"] == platform  # every number carries where it ran
    if platform == "cpu":
        assert cold == [] and "coldstart_first_verify_s" not in out
    else:
        assert cold == [True], "the cold-start child needs the chip the parent's child held"
        assert out["coldstart_first_verify_s"] == 2.5


def test_bench_supervisor_never_imports_jax():
    """Importing bench is the supervisor's whole footprint before it
    spawns children: a process that has not touched JAX cannot hold the
    chip its children need."""
    res = subprocess.run(
        [sys.executable, "-c", "import sys, bench; print(' '.join(sorted(sys.modules)))"],
        cwd=REPO, capture_output=True, text=True, timeout=60, check=True,
    )
    assert "jax" not in res.stdout.split()


# -- one cache root -----------------------------------------------------------


def test_cache_root_honours_the_variable_verbatim_else_fixed_in_checkout(monkeypatch):
    from tendermint_tpu.models import aot_cache
    from tendermint_tpu.utils import jaxenv

    for var in ("TM_AOT_CACHE_DIR", "TM_TABLES_CACHE_DIR"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert jaxenv.compile_cache_dir() == "/some/dir"
    assert aot_cache.cache_dir() == "/some/dir/aot"
    assert aot_cache.tables_dir() == "/some/dir/tables"

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".cache", "jax")
    assert jaxenv.compile_cache_dir() == fixed  # no /tmp, pid, temp name or time
    assert aot_cache.cache_dir() == os.path.join(fixed, "aot")
    assert aot_cache.tables_dir() == os.path.join(fixed, "tables")
    with open(os.path.join(REPO, ".gitignore")) as fp:
        assert ".cache/" in fp.read().split()
    # their own variables still relocate the two repo caches
    monkeypatch.setenv("TM_AOT_CACHE_DIR", "/elsewhere/aot")
    assert aot_cache.cache_dir() == "/elsewhere/aot"


def test_enable_compile_cache_sets_no_other_directory(monkeypatch):
    import jax

    from tendermint_tpu.utils import jaxenv

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert jaxenv.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == "/some/dir"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_no_jax_private_import_or_dead_jax_version_branch():
    import re

    with open(os.path.join(REPO, "tendermint_tpu", "utils", "jaxenv.py")) as fp:
        assert "jax._src" not in fp.read()
    pat = re.compile(r'hasattr\(jax, "shard_map"\)|inspect\.signature\(deserialize_and_load\)')
    for root, _, files in os.walk(os.path.join(REPO, "tendermint_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fp:
                    assert not pat.search(fp.read()), os.path.join(root, f)


# -- launchers ----------------------------------------------------------------


@pytest.mark.parametrize("docker_layout", [False, True], ids=["one-machine", "one-host-each"])
def test_testnet_gives_one_node_the_chip(tmp_path, monkeypatch, capsys, docker_layout):
    from tendermint_tpu.cli import main as cli_main
    from tendermint_tpu.config import load_config

    monkeypatch.delenv("TM_CRYPTO_PROVIDER", raising=False)
    out = str(tmp_path / "net")
    argv = ["testnet", "--v", "3", "--o", out, "--chain-id", "pin-chain"]
    if docker_layout:
        argv += ["--hostname-prefix", "node"]
    cli_main(argv)
    provs = [
        load_config(os.path.join(out, f"node{i}", "config", "config.toml")).base.crypto_provider
        for i in range(3)
    ]
    said = capsys.readouterr().out
    if docker_layout:
        assert provs == ["tpu", "tpu", "tpu"]
    else:
        assert provs == ["tpu", "cpu", "cpu"]
        assert 'crypto_provider = "cpu"' in said  # the launcher says so
