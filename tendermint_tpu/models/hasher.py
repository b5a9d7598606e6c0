"""MerkleHasher: the jit-compiled batched SHA-256 merkle engine.

Hashes every leaf of an RFC-6962-style tree in one device pass and
reduces inner levels LEVEL-BY-LEVEL — the reference recursion
(crypto/merkle/simple_tree.go getSplitPoint) is exactly equivalent to
"pair adjacent nodes, promote an odd last node", so each level is one
data-parallel dispatch instead of n recursive hashlib calls
(crypto/merkle.py documents the equivalence proof sketch).

Latency discipline mirrors models/verifier.py:

- leaf counts pad up to power-of-two-ish BUCKETS so any live tree size
  hits a warm executable; padding rows carry block count 0 and are
  masked out of every level by the logical node count.
- leaf byte lengths pad up to block-count buckets (_BLOCK_BUCKETS);
  leaves beyond MAX_LEAF_BLOCKS fall back to the host path (few huge
  leaves are bandwidth-bound — hashlib/OpenSSL wins there and the
  device engine is for the many-small-leaf shape: tx roots, validator
  sets, commit sig hashes).
- ``block_on_compile=False`` (live node): a cold bucket returns None —
  callers fall back to the host path for THIS tree while a daemon
  thread compiles the bucket's dispatch chain; consensus never stalls
  on XLA (same contract as VerifierModel._get_fn).

The dispatch chain per tree: one leaf-state dispatch per block column,
then one merkle_inner dispatch per level, until the level width reaches
HOST_TAIL_WIDTH — the narrow top of the tree is latency-bound serial
work where per-dispatch overhead beats compute, so hashlib finishes it
(and the root path's device->host transfer is one (8, tail) state
array). The chain is one small program per level width rather than one
fused tree program so that executables are keyed by width only and any
leaf count in a bucket reuses them.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.ops import sha256 as ops_sha
from tendermint_tpu.utils import faultinject as faults
from tendermint_tpu.utils import trace
from tendermint_tpu.utils.jaxenv import enable_compile_cache
from tendermint_tpu.utils.log import get_logger
from tendermint_tpu.utils.watchdog import CircuitBreaker

# the hasher may be the first jax user in light-client / tooling processes
enable_compile_cache()

# Leaf-count buckets (padded row counts). 10240 sits just above the 10k
# commit-sig / validator-row shape for the same reason as the verifier's
# bucket list; entries need not be powers of two — the level reducer
# handles odd widths by carrying the last column.
_BUCKETS = [16, 64, 256, 1024, 4096, 10240, 16384, 65536]

# Largest device-hashed leaf in 64-byte message blocks (16 covers
# ~950-byte txs). Block count needs NO bucketing: the leaf executables
# are keyed by row width only — the same block-update program runs
# however many block columns a tree needs — so exact counts cost no
# extra compiles and no padding waste.
MAX_LEAF_BLOCKS = int(os.environ.get("TM_MERKLE_MAX_LEAF_BLOCKS", "16"))

# Stop device reduction at this level width and finish on host: the top
# of the tree is a handful of serial hashes where dispatch overhead
# dwarfs compute.
HOST_TAIL_WIDTH = int(os.environ.get("TM_MERKLE_DEVICE_TAIL", "128"))

MAX_LEAVES = _BUCKETS[-1]


def _bucket(n: int, buckets) -> Optional[int]:
    for b in buckets:
        if n <= b:
            return b
    return None


def _host_inner(left: bytes, right: bytes) -> bytes:
    import hashlib

    return hashlib.sha256(b"\x01" + left + right).digest()


class _Bucket:
    __slots__ = ("ready", "compiling", "failed", "compile_s")

    def __init__(self):
        self.ready = False
        self.compiling = False
        # set on a compile/dispatch failure: the bucket stays on the
        # host path instead of re-running a deterministic failure (same
        # contract as the verifier's _TablesEntry.failed). No longer a
        # PERMANENT latch: the engine's circuit breaker clears it for a
        # half-open retry probe after its cooldown.
        self.failed = False
        self.compile_s: Optional[float] = None


class MerkleHasher:
    """Batched merkle-tree hashing with bucketed jit compilation.

    ``tree(items)`` returns (levels, counts) — levels[0] the leaf
    digests, levels[-1] a single root row — or None when the engine
    cannot serve the shape (size caps, or a cold bucket in non-blocking
    mode); callers fall back to the host path. ``root(items)`` is the
    root-only fast path (device keeps intermediate levels on device)."""

    def __init__(self, block_on_compile: bool = True, logger=None, router=None):
        self.block_on_compile = block_on_compile
        self.logger = logger or get_logger("merkle-hasher")
        # MeshRouter (parallel/topology.py): when set, the leaf stage
        # of qualifying trees shards across the admitted devices; the
        # inner reduction stays on the default device (the tree narrows
        # too fast for collectives to pay past the leaves)
        self.router = router
        self._lock = threading.Lock()
        # readiness is per LEAF-COUNT bucket: every executable is keyed
        # by row width, so one warm pass at a width covers any leaf
        # block count
        self._buckets: Dict[int, _Bucket] = {}
        # jits are shared across buckets; jax specializes per shape
        self._leaf_state = jax.jit(ops_sha.leaf_block_state)
        self._leaf_update = jax.jit(ops_sha.leaf_block_update)
        self._inner = jax.jit(ops_sha.merkle_inner)
        self.stats: Dict[str, int] = {
            "device_roots": 0,
            "device_proof_sets": 0,
            "device_leaves": 0,
            "fallback_cold": 0,
            "fallback_shape": 0,
        }
        # compile-failure breaker: replaces the permanent _Bucket.failed
        # latch with fail-stop + a half-open retry after cooldown
        self.compile_breaker = CircuitBreaker("merkle.compile", failure_threshold=1)

    # -- bucket/compile management ----------------------------------------

    def _shape(self, items: Sequence[bytes]) -> Optional[Tuple[int, int]]:
        n = len(items)
        n_pad = _bucket(n, _BUCKETS)
        if n_pad is None:
            return None
        max_len = max((len(x) for x in items), default=0)
        blocks = ops_sha.leaf_blocks_needed(max_len)
        if blocks > MAX_LEAF_BLOCKS:
            return None
        return n_pad, blocks

    def _bucket_entry(self, key: int) -> _Bucket:
        with self._lock:
            e = self._buckets.get(key)
            if e is None:
                e = self._buckets[key] = _Bucket()
            return e

    def _warm(self, n_pad: int) -> None:
        """Compile the full dispatch chain for a leaf-count bucket: a
        FULL two-block tree of the bucket's padded size compiles the
        leaf kernels (leaf_block_state AND leaf_block_update — further
        block columns reuse the update executable) and every level
        width the live calls will dispatch."""
        t0 = time.perf_counter()
        faults.maybe("merkle.compile")
        leaf = b"\x01" * (2 * 64 - 73)
        self._device_levels([leaf] * n_pad, n_pad, 2)
        e = self._buckets[n_pad]
        e.compile_s = time.perf_counter() - t0
        e.ready = True
        self.compile_breaker.record_success()
        self.logger.info(
            "merkle bucket compiled", leaves=n_pad,
            seconds=round(e.compile_s, 2),
        )

    def _ensure_bucket(self, key: int) -> bool:
        """True when the bucket is warm (or blocking mode compiles it
        inline); False -> caller must take the host path."""
        e = self._bucket_entry(key)
        probed = False  # did WE take the half-open probe token?
        if e.failed:
            # fail-stop per tree, breaker-gated: one half-open probe per
            # cooldown clears the flag and retries the compile below
            if not self.compile_breaker.allow():
                return False
            probed = True
            with self._lock:
                e.failed = False
        if e.ready:
            return True
        if self.block_on_compile:
            e.ready = True  # first call compiles inline
            return True
        with self._lock:
            if e.compiling or e.ready:
                if probed and not e.ready:
                    # a compile is already in flight; return OUR probe
                    # token (never someone else's) — the running
                    # compile records its own verdict on the breaker
                    self.compile_breaker.release_probe()
                return e.ready
            e.compiling = True

        def work():
            try:
                self._warm(key)
            except Exception as ex:  # pragma: no cover - defensive
                e.failed = True
                self.compile_breaker.record_failure()
                self.logger.error("merkle bucket compile failed", err=repr(ex))
            finally:
                e.compiling = False

        t = threading.Thread(
            target=work, daemon=True, name=f"merkle-compile-{key}"
        )
        t.start()
        return False

    def warmup(self, sizes=(1024, 10240), background: bool = False):
        """Pre-compile buckets (node-start path). Leaf byte length needs
        no sizing input: the two-block warm probe compiles both leaf
        executables for the width, which any block count then reuses.
        Returns the thread in background mode."""
        keys = []
        for s in sizes:
            n_pad = _bucket(min(int(s), MAX_LEAVES), _BUCKETS)
            if n_pad and n_pad not in keys:
                keys.append(n_pad)

        def work():
            for key in keys:
                e = self._bucket_entry(key)
                with self._lock:
                    if e.ready or e.compiling or e.failed:
                        continue
                    e.compiling = True
                try:
                    self._warm(key)
                except Exception as ex:  # pragma: no cover - defensive
                    e.failed = True  # breaker-gated, like the live path
                    self.compile_breaker.record_failure()
                    self.logger.error(
                        "merkle warmup failed", bucket=key, err=repr(ex)
                    )
                finally:
                    e.compiling = False

        if background:
            t = threading.Thread(target=work, daemon=True, name="merkle-warmup")
            t.start()
            return t
        work()
        return None

    # -- device tree ------------------------------------------------------

    def _mesh_leaf_state(self, blocks: np.ndarray, nb: np.ndarray, n_blocks: int):
        """Leaf-level mesh reduction: padded leaf rows split into
        contiguous per-device chunks, each chunk's blocks committed to
        its device so the shared leaf executables dispatch
        concurrently. Leaf digests are row-independent, so the
        concatenated (8, n_pad) state is bit-identical to the single
        dispatch; it re-lands on the default device for the inner
        levels. None -> take the single-device leaf path."""
        r = self.router
        if r is None or not r.topology.has_placement:
            return None
        plan = r.plan(blocks.shape[0])
        if not plan.collective:
            return None

        def dispatch(s):
            blk = jax.device_put(np.ascontiguousarray(blocks[s.lo : s.hi]), s.device)
            st = self._leaf_state(blk[:, 0])
            nbs = nb[s.lo : s.hi]
            for i in range(1, n_blocks):
                # nbs > i rides along uncommitted and follows st's device
                st = self._leaf_update(st, blk[:, i], nbs > i)
            return st

        def combine(outs):
            return jnp.asarray(
                np.concatenate([np.asarray(o) for o in outs], axis=1)
            )

        try:
            return r.run(plan, dispatch, combine)
        except Exception as e:
            self.logger.error(
                "mesh leaf shard failed; single-device fallback", err=repr(e)
            )
            return None

    def _device_levels(self, items: Sequence[bytes], n_pad: int, n_blocks: int):
        """Run the dispatch chain: returns (device_levels, counts) where
        device_levels[l] is the (8, C_l) u32 state array of level l and
        counts[l] its logical node count. Reduction stops once the
        width is <= HOST_TAIL_WIDTH (or one node)."""
        blocks, nb = ops_sha.pack_leaf_blocks(items, n_pad, n_blocks)
        st = self._mesh_leaf_state(blocks, nb, n_blocks)
        if st is None:
            st = self._leaf_state(jnp.asarray(np.ascontiguousarray(blocks[:, 0])))
            for i in range(1, n_blocks):
                st = self._leaf_update(
                    st,
                    jnp.asarray(np.ascontiguousarray(blocks[:, i])),
                    jnp.asarray(nb > i),
                )
        levels = [st]
        counts = [len(items)]
        cnt = len(items)
        while int(levels[-1].shape[1]) > HOST_TAIL_WIDTH and cnt > 1:
            levels.append(self._inner(levels[-1], np.int32(cnt)))
            cnt = (cnt + 1) // 2
            counts.append(cnt)
        return levels, counts

    @staticmethod
    def _host_finish(digests: List[bytes]) -> List[List[bytes]]:
        """Pair-and-promote reduction of the host tail; returns the
        remaining levels (excluding the input level)."""
        levels = []
        level = digests
        while len(level) > 1:
            nxt = [
                _host_inner(level[i], level[i + 1])
                for i in range(0, len(level) - 1, 2)
            ]
            if len(level) % 2:
                nxt.append(level[-1])
            levels.append(nxt)
            level = nxt
        return levels

    def root(self, items: Sequence[bytes]) -> Optional[bytes]:
        """Merkle root, or None -> host fallback. Caller guarantees
        len(items) >= 2 (empty/single-leaf trees are host territory)."""
        shape = self._shape(items)
        if shape is None:
            self.stats["fallback_shape"] += 1
            trace.instant("merkle.device_fallback", reason="shape", leaves=len(items))
            return None
        if not self._ensure_bucket(shape[0]):
            self.stats["fallback_cold"] += 1
            trace.instant("merkle.device_fallback", reason="cold", leaves=len(items))
            return None
        try:
            faults.maybe("device.hash")
            dev_levels, counts = self._device_levels(items, *shape)
        except Exception:
            # a failing compile/dispatch likely fails identically on the
            # next retry: park the bucket on the host path (breaker-gated
            # retry after cooldown) and re-raise for the caller's
            # fallback handling (crypto/merkle.py catches)
            self._bucket_entry(shape[0]).failed = True
            self.compile_breaker.record_failure()
            raise
        self.compile_breaker.record_success()  # closes a half-open probe
        tail = ops_sha.state_to_digests(np.asarray(dev_levels[-1]))
        level = [bytes(tail[i]) for i in range(counts[-1])]
        host = self._host_finish(level)
        self.stats["device_roots"] += 1
        self.stats["device_leaves"] += len(items)
        return host[-1][0] if host else level[0]

    def tree(
        self, items: Sequence[bytes]
    ) -> Optional[Tuple[List[np.ndarray], List[int]]]:
        """All levels as (count_l, 32) u8 digest arrays (trimmed to the
        logical counts) plus the counts — the proof/aunt extraction
        input. None -> host fallback."""
        shape = self._shape(items)
        if shape is None:
            self.stats["fallback_shape"] += 1
            trace.instant("merkle.device_fallback", reason="shape", leaves=len(items))
            return None
        if not self._ensure_bucket(shape[0]):
            self.stats["fallback_cold"] += 1
            trace.instant("merkle.device_fallback", reason="cold", leaves=len(items))
            return None
        try:
            faults.maybe("device.hash")
            dev_levels, counts = self._device_levels(items, *shape)
        except Exception:
            self._bucket_entry(shape[0]).failed = True
            self.compile_breaker.record_failure()
            raise
        self.compile_breaker.record_success()  # closes a half-open probe
        levels = [
            ops_sha.state_to_digests(np.asarray(lv))[:c]
            for lv, c in zip(dev_levels, counts)
        ]
        tail = [bytes(levels[-1][i]) for i in range(counts[-1])]
        for lv in self._host_finish(tail):
            levels.append(
                np.frombuffer(b"".join(lv), dtype=np.uint8).reshape(len(lv), 32)
            )
            counts.append(len(lv))
        self.stats["device_proof_sets"] += 1
        self.stats["device_leaves"] += len(items)
        return levels, counts

    def compile_stats(self) -> Dict[int, Optional[float]]:
        with self._lock:
            return {k: e.compile_s for k, e in self._buckets.items() if e.ready}

    def engine_stats(self) -> Dict[str, object]:
        """The unified engine-telemetry protocol (models/telemetry.py).
        Host-path counts live at the routing seam (crypto/merkle.py
        merges them in via its module-level engine_stats wrapper)."""
        from tendermint_tpu.models.telemetry import breaker_view, bucket_view

        with self._lock:
            buckets = bucket_view(dict(self._buckets))
            counters = dict(self.stats)
        return {
            "engine": "merkle",
            "device_rows": float(counters.get("device_leaves", 0)),
            "host_rows": 0.0,
            "buckets": buckets,
            "breakers": breaker_view(self.compile_breaker),
            "queue_wait_ms": None,
            "counters": counters,
        }
