"""Persistent compilation cache across processes: a second-process run
that demonstrably skips compilation.

Two fresh interpreters compile the same verify bucket against the same
JAX_COMPILATION_CACHE_DIR; the second must hit the cache (entries
written by the first, JAX's own hit and miss counts in the second)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import collections, json, os, sys
import jax.monitoring
events = collections.Counter()
jax.monitoring.register_event_listener(lambda name, **kw: events.update([name]))
from tendermint_tpu.models.verifier import VerifierModel
import __graft_entry__ as g

model = VerifierModel()
pks, msgs, sigs = g._example_batch(16)
ok = model.verify(pks, msgs, sigs)
assert ok.all(), "valid signatures must verify"
cache = os.environ["JAX_COMPILATION_CACHE_DIR"]
entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
print(json.dumps({
    "cache_entries": entries,
    "hits": events["/jax/compilation_cache/cache_hits"],
    "misses": events["/jax/compilation_cache/cache_misses"],
}))
"""


def _run(cache_dir: str) -> dict:
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0.1",
        # isolate the layer under test: with the AOT executable cache
        # active (models/aot_cache.py) a warm machine LOADS executables
        # and the XLA persistent cache never gets written at all
        TM_AOT_CACHE="0",
        PYTHONPATH=os.pathsep.join(
            p for p in (REPO, os.environ.get("PYTHONPATH", "")) if p
        ),
    )
    res = subprocess.run(
        [sys.executable, "-c", _CHILD],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_second_process_hits_persistent_cache(tmp_path):
    cache = str(tmp_path / "jax_cache")
    first = _run(cache)
    assert first["cache_entries"] > 0, "first process wrote no cache entries"
    second = _run(cache)
    # deterministic signal: the second process compiled NOTHING new
    assert second["cache_entries"] == first["cache_entries"], (first, second)
    # ...because every program it asked for was in the cache (JAX's own
    # counters; how much faster a load is than a compile is a timing on
    # a shared host, and no test's to judge)
    assert first["misses"] >= first["cache_entries"], (first, second)
    assert second["hits"] >= first["cache_entries"], (first, second)
    assert second["misses"] == 0, (first, second)
