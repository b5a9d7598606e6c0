"""Batched tx-key SHA-256 for mempool admission.

``mempool.tx_key`` is plain ``sha256(tx)`` — no merkle leaf prefix — so
the merkle engine's packer (ops/sha256.pack_leaf_blocks) can't be
reused directly, but its kernels can: ``leaf_block_state`` /
``leaf_block_update`` compress pre-padded 64-byte blocks row-parallel,
one block column per dispatch (so executables are keyed by row count
only), and ``state_to_digests`` materializes bytes host-side.
This module owns the prefix-free packer plus a small bucketed engine in
the models/hasher.py mold: leaf-count buckets are powers of two with
logical-count masking of pad rows, executables compile in a background
thread (``block_on_compile=False``, the live-node setting) and a cold
or out-of-shape bundle falls back to host hashlib — bit-identical
digests either way, which the ingest property suite pins.
"""

from __future__ import annotations

import hashlib
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from tendermint_tpu.utils import faultinject as faults
from tendermint_tpu.utils import trace
from tendermint_tpu.utils.log import get_logger

# Shape caps mirroring models/hasher.py: beyond these a bundle is not
# worth a device dispatch (or would retrace an unbounded set of shapes).
MIN_BUCKET = 16
MAX_BUCKET = 1 << 16
MAX_TX_BLOCKS = 33  # ~2 KiB txs; longer rows go host


def host_keys(items: Sequence[bytes]) -> List[bytes]:
    """The reference path: per-tx hashlib (what mempool.tx_key does)."""
    return [hashlib.sha256(bytes(t)).digest() for t in items]


def pack_msg_blocks(
    items: Sequence[bytes], n_pad: int, n_blocks: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Plain-sha256 packing: the merkle packer without its 0x00 leaf
    prefix (msg || 0x80 || zeros || 64-bit big-endian bit length) —
    ONE implementation of the vectorized padding math, shared with the
    merkle engine (ops/sha256.pack_leaf_blocks)."""
    from tendermint_tpu.ops.sha256 import pack_leaf_blocks

    return pack_leaf_blocks(items, n_pad, n_blocks, prefix_len=0)


def _bucket_npad(n: int) -> int:
    p = MIN_BUCKET
    while p < n:
        p <<= 1
    return p


class _Bucket:
    __slots__ = ("ready", "compiling", "failed")

    def __init__(self):
        self.ready = False
        self.compiling = False
        self.failed = False


# One process-wide jitted kernel pair: executables are keyed by input
# shape inside jax.jit, so every TxKeyHasher (node batcher, bench arms,
# tests) shares the same compiled buckets instead of re-tracing.
_fns_lock = threading.Lock()
_jitted = None


def _jit_fns():
    global _jitted
    with _fns_lock:
        if _jitted is None:
            import jax

            from tendermint_tpu.ops import sha256 as ops
            from tendermint_tpu.utils.jaxenv import enable_compile_cache

            enable_compile_cache()
            _jitted = (jax.jit(ops.leaf_block_state), jax.jit(ops.leaf_block_update))
        return _jitted


class TxKeyHasher:
    """Bucketed device SHA-256 over raw tx bytes.

    ``keys(items)`` returns the (N,) list of 32-byte digests, or None
    when the device declines (cold bucket still compiling, out-of-shape
    bundle, backend error) — the caller then runs :func:`host_keys`.
    One executable pair per (n_pad, n_blocks) bucket; compiles happen
    in a background thread when ``block_on_compile=False`` so admission
    never stalls on XLA."""

    def __init__(self, block_on_compile: bool = True, logger=None, router=None):
        from tendermint_tpu.utils.watchdog import CircuitBreaker

        self.block_on_compile = block_on_compile
        self.logger = logger or get_logger("ingest.hash")
        # MeshRouter (parallel/topology.py): when set, qualifying
        # bundles split into per-device row chunks at the seam
        self.router = router
        self._lock = threading.Lock()
        self._buckets: Dict[Tuple[int, int], _Bucket] = {}
        # fail-stop per bundle, breaker-gated: a transient compile
        # failure must not disable device hashing for a bucket until
        # process restart (the models/hasher.py _ensure_bucket
        # discipline from PR 4 — no permanent latches)
        self.compile_breaker = CircuitBreaker("ingest.hash.compile", failure_threshold=1)
        # counters, read via stats() (pump + bench)
        self.device_bundles = 0
        self.device_rows = 0
        self.host_bundles = 0
        self.host_rows = 0
        self.fallback_cold = 0
        self.fallback_shape = 0

    def _run_state(self, blocks, counts):
        state_fn, update_fn = _jit_fns()
        st = state_fn(blocks[:, 0])
        for b in range(1, blocks.shape[1]):
            st = update_fn(st, blocks[:, b], counts > b)
        return st

    def _run(self, blocks: np.ndarray, counts: np.ndarray) -> np.ndarray:
        from tendermint_tpu.ops.sha256 import state_to_digests

        faults.maybe("device.hash")
        return state_to_digests(np.asarray(self._run_state(blocks, counts)))

    def _run_meshed(self, blocks: np.ndarray, counts: np.ndarray) -> Optional[np.ndarray]:
        """Rows split into contiguous per-device chunks, each chunk's
        blocks committed to its device so the shared jitted kernels
        dispatch concurrently (jit follows input placement). SHA-256
        rows are independent, so concatenating the per-chunk states is
        bit-identical to the single dispatch. None means the router
        declined (or a shard failed) — take the single-device path."""
        r = self.router
        if r is None or not r.topology.has_placement:
            return None
        plan = r.plan(blocks.shape[0])
        if not plan.collective:
            return None
        import jax

        from tendermint_tpu.ops.sha256 import state_to_digests

        def dispatch(s):
            blk = jax.device_put(blocks[s.lo : s.hi], s.device)
            return self._run_state(blk, counts[s.lo : s.hi])

        def combine(outs):
            return state_to_digests(
                np.concatenate([np.asarray(o) for o in outs], axis=1)
            )

        try:
            return r.run(plan, dispatch, combine)
        except Exception as e:
            self.logger.error(
                "mesh tx-key shard failed; single-device fallback", err=repr(e)
            )
            return None

    def _ensure(self, key: Tuple[int, int]) -> bool:
        """True when the bucket's executables are warm; otherwise kicks
        a background compile and reports cold. A failed compile is
        breaker-gated, not latched: one half-open probe per cooldown
        clears the flag and retries."""
        probed = False  # did WE take the half-open probe token?
        with self._lock:
            b = self._buckets.get(key)
            if b is None:
                b = self._buckets[key] = _Bucket()
        if b.failed:
            if not self.compile_breaker.allow():
                return False
            probed = True
            with self._lock:
                b.failed = False
        with self._lock:
            if b.ready:
                if probed:
                    self.compile_breaker.release_probe()
                return True
            if self.block_on_compile:
                b.ready = True  # compile happens inline on first _run
                if probed:
                    self.compile_breaker.release_probe()
                return True
            if b.compiling:
                if probed:
                    # a compile is already in flight; return OUR probe
                    # token — the running compile records its verdict
                    self.compile_breaker.release_probe()
                return False
            b.compiling = True

        def work():
            try:
                n_pad, n_blocks = key
                blocks = np.zeros((n_pad, n_blocks, 64), dtype=np.uint8)
                counts = np.ones(n_pad, dtype=np.int32)
                self._run(blocks, counts)
                with self._lock:
                    b.ready = True
                self.compile_breaker.record_success()
            except Exception as e:  # backend missing/compile error
                self.logger.error("tx-key bucket compile failed", err=repr(e))
                with self._lock:
                    b.failed = True
                self.compile_breaker.record_failure()
            finally:
                with self._lock:
                    b.compiling = False

        threading.Thread(target=work, daemon=True, name="ingest-hash-compile").start()
        return False

    def keys(self, items: Sequence[bytes]) -> Optional[List[bytes]]:
        n = len(items)
        if n == 0:
            return []
        max_len = max(len(t) for t in items)
        n_blocks = (max_len + 72) // 64
        n_pad = _bucket_npad(n)
        if n_pad > MAX_BUCKET or n_blocks > MAX_TX_BLOCKS:
            with self._lock:
                self.fallback_shape += 1
            return None
        key = (n_pad, n_blocks)
        if not self._ensure(key):
            with self._lock:
                self.fallback_cold += 1
            return None
        try:
            blocks, counts = pack_msg_blocks(items, n_pad, n_blocks)
            with trace.span("ingest.hash_keys", rows=n, blocks=n_blocks):
                digests = self._run_meshed(blocks, counts)
                if digests is None:
                    digests = self._run(blocks, counts)
        except Exception as e:
            # runtime failure on a warm bucket (backend lost, OOM, an
            # injected device.hash fault): fail-stop THIS bucket behind
            # the breaker so the admission hot path stops re-paying a
            # failing XLA dispatch per bundle; a half-open probe per
            # cooldown retries (covers blocking mode too, where _ensure
            # marks buckets ready without a warm-up compile)
            self.logger.error("device tx-key hash failed; host fallback", err=repr(e))
            with self._lock:
                b = self._buckets.get(key)
                if b is not None:
                    b.ready = False
                    b.failed = True
            self.compile_breaker.record_failure()
            return None
        if self.compile_breaker.state() != "closed":
            # a half-open probe that hashed clean re-closes the breaker
            self.compile_breaker.record_success()
        with self._lock:
            self.device_bundles += 1
            self.device_rows += n
        return [digests[i].tobytes() for i in range(n)]

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "hash_device_bundles": self.device_bundles,
                "hash_device_rows": self.device_rows,
                "hash_host_bundles": self.host_bundles,
                "hash_host_rows": self.host_rows,
                "hash_fallback_cold": self.fallback_cold,
                "hash_fallback_shape": self.fallback_shape,
            }

    def engine_stats(self) -> Dict[str, object]:
        """The unified engine-telemetry protocol (models/telemetry.py):
        the one engine that owns BOTH sides of its device/host split
        (keys_or_host routes internally)."""
        from tendermint_tpu.models.telemetry import breaker_view, bucket_view

        with self._lock:
            buckets = bucket_view(dict(self._buckets))
            counters = {
                "device_bundles": self.device_bundles,
                "host_bundles": self.host_bundles,
                "fallback_cold": self.fallback_cold,
                "fallback_shape": self.fallback_shape,
            }
            device_rows, host_rows = self.device_rows, self.host_rows
        return {
            "engine": "txhash",
            "device_rows": float(device_rows),
            "host_rows": float(host_rows),
            "buckets": buckets,
            "breakers": breaker_view(self.compile_breaker),
            "queue_wait_ms": None,
            "counters": counters,
        }

    def keys_or_host(self, items: Sequence[bytes], threshold: int) -> List[bytes]:
        """The routing entry the batcher calls: device when the bundle
        clears ``threshold`` rows and the bucket is warm, else host —
        identical digests either way."""
        if len(items) >= max(1, threshold):
            out = self.keys(items)
            if out is not None:
                return out
        with self._lock:
            self.host_bundles += 1
            self.host_rows += len(items)
        return host_keys(items)
