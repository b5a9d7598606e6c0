"""BLSEngine: jit-bucketed device execution of the BLS12-381 kernels.

The models/hasher.py discipline one curve up: row counts pad to
power-of-two BUCKETS so live shapes hit warm executables; a cold bucket
in non-blocking mode returns None (callers fall back to the pure-Python
oracle, ops/ref_bls12.py) while a daemon thread compiles; compile or
dispatch failures are breaker-gated fail-stop with a half-open retry
probe (``bls.compile``), never a permanent latch. Chaos site
``bls.pairing`` fires on every device dispatch so the fault-injection
rig (docs/robustness.md) can prove the fallback path live.

Three engine surfaces, one per kernel in ops/bls12.py:

- verify_rows: per-row pairing checks e(pk, H(m)) == e(G1, sig) — the
  BLS analogue of the ed25519 batch verify (crypto/bls.BLSBatchVerifier
  routes here).
- map_rows: hash-to-G2 tails for host-expanded field elements (RFC 9380
  expand_message_xmd stays host-side — hashlib in a traced function
  would freeze into the executable, the jit-purity rule).
- aggregate: masked pubkey sums over a validator table — the
  AggregatedCommit accumulation.

Pad rows carry a known-good triple (generator-based) and are sliced off
the result, so padding can never flip a real row's verdict.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.ops import bls12 as ops_bls
from tendermint_tpu.ops import ref_bls12 as ref
from tendermint_tpu.utils import faultinject as faults
from tendermint_tpu.utils.jaxenv import enable_compile_cache
from tendermint_tpu.utils.log import get_logger
from tendermint_tpu.utils.watchdog import CircuitBreaker

# persistent compilation cache (one directory for every engine)
enable_compile_cache()

# Row-count buckets per kernel. BLS rows are ~5 orders heavier than
# ed25519 rows (a pairing vs a scalar mult), so buckets stay small.
_ROW_BUCKETS = [2, 8, 32, 128]
MAX_ROWS = _ROW_BUCKETS[-1]
# Aggregation table sizes (power of two, the kernel's tree requirement).
_AGG_BUCKETS = [16, 64, 256, 1024, 4096]
MAX_AGG = _AGG_BUCKETS[-1]


def _bucket(n: int, buckets) -> Optional[int]:
    for b in buckets:
        if n <= b:
            return b
    return None


# Known-good padding row: (G1 gen, H("pad"), sk=1 signature) verifies.
_PAD_HM = ref.hash_to_curve_g2(b"tendermint-tpu-bls-pad", ref.DST_SIG)
_PAD_PK = ref.G1_GEN
_PAD_SIG = _PAD_HM  # sk = 1: signature IS the hashed point


def _pack_fp(vals: Sequence[int]) -> np.ndarray:
    return np.stack([ops_bls.to_mont(v) for v in vals])


def _pack_fp2(vals: Sequence[Tuple[int, int]]) -> np.ndarray:
    return np.stack([ops_bls.f2_to_mont(v) for v in vals])


class _Bucket:
    __slots__ = ("ready", "compiling", "failed", "compile_s")

    def __init__(self):
        self.ready = False
        self.compiling = False
        self.failed = False  # breaker-gated, not permanent (hasher contract)
        self.compile_s: Optional[float] = None


class BLSEngine:
    """Bucketed BLS kernel execution with oracle fallback.

    Every public method returns None when the device cannot serve the
    shape (size caps, cold bucket in non-blocking mode, tripped
    breaker) — callers MUST fall back to ops/ref_bls12, which is
    verdict-bit-identical by the differential test suite."""

    # BLS pairings are ~5 orders heavier than ed25519 rows: a handful
    # of rows already pays for per-device dispatch, so the mesh floor
    # is engine-local instead of the router's (ed25519-tuned) default.
    MESH_MIN_ROWS = 8

    def __init__(self, block_on_compile: bool = True, logger=None, router=None):
        self.block_on_compile = block_on_compile
        self.logger = logger or get_logger("bls-engine")
        # MeshRouter (parallel/topology.py): when set, verify_rows
        # splits per-row pairing checks into per-device chunks
        self.router = router
        self._lock = threading.Lock()
        self._buckets: Dict[Tuple[str, int], _Bucket] = {}
        self._verify_fn = jax.jit(ops_bls.pairing_check_rows)
        self._map_fn = jax.jit(ops_bls.map_to_g2)
        self._agg_fn = jax.jit(ops_bls.g1_aggregate)
        self.stats: Dict[str, int] = {
            "device_rows": 0,
            "device_calls": 0,
            "device_maps": 0,
            "device_aggregates": 0,
            "fallback_cold": 0,
            "fallback_shape": 0,
        }
        self.compile_breaker = CircuitBreaker("bls.compile", failure_threshold=1)

    # -- bucket management (models/hasher.py shape) ------------------------

    def _bucket_entry(self, key: Tuple[str, int]) -> _Bucket:
        with self._lock:
            e = self._buckets.get(key)
            if e is None:
                e = self._buckets[key] = _Bucket()
            return e

    def _warm(self, key: Tuple[str, int]) -> None:
        kind, n = key
        t0 = time.perf_counter()
        faults.maybe("bls.compile")
        if kind == "verify":
            self._dispatch_verify([(_PAD_PK, _PAD_HM, _PAD_SIG)] * n)
        elif kind == "map":
            u = ref.hash_to_field_fp2(b"warm", ref.DST_SIG, 2)
            self._dispatch_map([(u[0], u[1])] * n)
        else:  # "agg"
            xs = np.broadcast_to(_pack_fp([_PAD_PK[0]]), (1, n, ops_bls.LIMBS))
            ys = np.broadcast_to(_pack_fp([_PAD_PK[1]]), (1, n, ops_bls.LIMBS))
            self._agg_fn(
                jnp.asarray(np.ascontiguousarray(xs)),
                jnp.asarray(np.ascontiguousarray(ys)),
                jnp.ones((1, n), dtype=bool),
            )
        e = self._buckets[key]
        e.compile_s = time.perf_counter() - t0
        e.ready = True
        self.compile_breaker.record_success()
        self.logger.info(
            "bls bucket compiled", kind=kind, rows=n,
            seconds=round(e.compile_s, 2),
        )

    def _ensure_bucket(self, key: Tuple[str, int]) -> bool:
        e = self._bucket_entry(key)
        probed = False
        if e.failed:
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
                    self.compile_breaker.release_probe()
                return e.ready
            e.compiling = True

        def work():
            try:
                self._warm(key)
            except Exception as ex:  # pragma: no cover - defensive
                e.failed = True
                self.compile_breaker.record_failure()
                self.logger.error("bls bucket compile failed", err=repr(ex))
            finally:
                e.compiling = False

        threading.Thread(
            target=work, daemon=True, name=f"bls-compile-{key[0]}-{key[1]}"
        ).start()
        return False

    def warmup(self, kinds=(("verify", 8), ("map", 8), ("agg", 64)),
               background: bool = False):
        """Pre-compile buckets (node-start path)."""
        keys = []
        for kind, size in kinds:
            buckets = _AGG_BUCKETS if kind == "agg" else _ROW_BUCKETS
            b = _bucket(int(size), buckets)
            if b is not None and (kind, b) not in keys:
                keys.append((kind, b))

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
                    e.failed = True
                    self.compile_breaker.record_failure()
                    self.logger.error("bls warmup failed", bucket=key, err=repr(ex))
                finally:
                    e.compiling = False

        if background:
            t = threading.Thread(target=work, daemon=True, name="bls-warmup")
            t.start()
            return t
        work()
        return None

    # -- dispatch helpers ---------------------------------------------------

    def _verify_arrays(self, rows, device=None):
        """The packed pairing-check dispatch; ``device`` commits the
        inputs so the shared jit runs there (mesh chunks), None takes
        the default placement. Returns the un-materialized device
        array so chunk dispatches overlap."""
        pkx = _pack_fp([r[0][0] for r in rows])
        pky = _pack_fp([r[0][1] for r in rows])
        hmx = _pack_fp2([r[1][0] for r in rows])
        hmy = _pack_fp2([r[1][1] for r in rows])
        sgx = _pack_fp2([r[2][0] for r in rows])
        sgy = _pack_fp2([r[2][1] for r in rows])
        if device is not None:
            put = lambda a: jax.device_put(a, device)  # noqa: E731
        else:
            put = jnp.asarray
        return self._verify_fn(
            put(pkx), put(pky), put(hmx), put(hmy), put(sgx), put(sgy)
        )

    def _dispatch_verify(self, rows) -> np.ndarray:
        return np.asarray(self._verify_arrays(rows))

    def _mesh_verify(self, rows) -> Optional[np.ndarray]:
        """Per-device chunked pairing checks: each chunk pads to its
        own row bucket with the known-good pad triple (verdicts can't
        flip) and commits to its device. Row checks are independent,
        so concatenation is bit-identical to the single dispatch.
        None -> take the single-device path."""
        r = self.router
        if r is None or not r.topology.has_placement:
            return None
        plan = r.plan(len(rows), min_rows=self.MESH_MIN_ROWS)
        if not plan.collective:
            return None
        for s in plan.slots:
            c_pad = _bucket(s.rows, _ROW_BUCKETS)
            if c_pad is None or not self._ensure_bucket(("verify", c_pad)):
                r.release(plan)  # cold chunk bucket: no collective today
                return None

        def dispatch(s):
            c_pad = _bucket(s.rows, _ROW_BUCKETS)
            padded = list(rows[s.lo : s.hi]) + [
                (_PAD_PK, _PAD_HM, _PAD_SIG)
            ] * (c_pad - s.rows)
            return self._verify_arrays(padded, device=s.device)[: s.rows]

        def combine(outs):
            return np.concatenate([np.asarray(o) for o in outs])

        try:
            return r.run(plan, dispatch, combine)
        except Exception as e:
            self.logger.error(
                "mesh pairing shard failed; single-device fallback", err=repr(e)
            )
            return None

    def _dispatch_map(self, us) -> List[Tuple]:
        u0 = _pack_fp2([u[0] for u in us])
        u1 = _pack_fp2([u[1] for u in us])
        ax, ay, inf = self._map_fn(jnp.asarray(u0), jnp.asarray(u1))
        ax = np.asarray(ax)
        ay = np.asarray(ay)
        inf = np.asarray(inf)
        out = []
        for i in range(len(us)):
            if inf[i]:  # pragma: no cover - cofactor-cleared maps never hit
                out.append(None)
            else:
                out.append((
                    (ops_bls.from_limbs(ax[i][0]), ops_bls.from_limbs(ax[i][1])),
                    (ops_bls.from_limbs(ay[i][0]), ops_bls.from_limbs(ay[i][1])),
                ))
        return out

    # -- public surfaces ----------------------------------------------------

    def verify_rows(self, rows) -> Optional[np.ndarray]:
        """rows: [(pk_point, hm_point, sig_point)] (oracle affine
        tuples, all valid curve points) -> (N,) bool, or None for the
        oracle fallback."""
        n = len(rows)
        n_pad = _bucket(n, _ROW_BUCKETS)
        if n == 0 or n_pad is None:
            self.stats["fallback_shape"] += 1
            return None
        ok = self._mesh_verify(rows)
        if ok is not None:
            self.stats["device_rows"] += n
            self.stats["device_calls"] += 1
            return ok
        if not self._ensure_bucket(("verify", n_pad)):
            self.stats["fallback_cold"] += 1
            return None
        try:
            faults.maybe("bls.pairing")
            padded = list(rows) + [(_PAD_PK, _PAD_HM, _PAD_SIG)] * (n_pad - n)
            ok = self._dispatch_verify(padded)
        except Exception:
            self._bucket_entry(("verify", n_pad)).failed = True
            self.compile_breaker.record_failure()
            raise
        self.compile_breaker.record_success()
        self.stats["device_rows"] += n
        self.stats["device_calls"] += 1
        return ok[:n]

    def map_rows(self, us) -> Optional[List[Tuple]]:
        """us: [(u0, u1)] hash_to_field outputs -> G2 affine points
        (oracle tuples), or None for the oracle fallback. Output is
        bit-identical to ref.clear_cofactor_g2(map+map) per row."""
        n = len(us)
        n_pad = _bucket(n, _ROW_BUCKETS)
        if n == 0 or n_pad is None:
            self.stats["fallback_shape"] += 1
            return None
        if not self._ensure_bucket(("map", n_pad)):
            self.stats["fallback_cold"] += 1
            return None
        try:
            faults.maybe("bls.pairing")
            pad_u = ref.hash_to_field_fp2(b"pad", ref.DST_SIG, 2)
            padded = list(us) + [(pad_u[0], pad_u[1])] * (n_pad - n)
            out = self._dispatch_map(padded)
        except Exception:
            self._bucket_entry(("map", n_pad)).failed = True
            self.compile_breaker.record_failure()
            raise
        self.compile_breaker.record_success()
        self.stats["device_maps"] += 1
        return out[:n]

    def aggregate(
        self, points: Sequence[Tuple[int, int]], masks: np.ndarray
    ) -> Optional[List[Optional[Tuple[int, int]]]]:
        """Masked sums over a G1 point table: points (V affine tuples),
        masks (B, V) bool -> B aggregate points (None = infinity), or
        None for the oracle fallback."""
        v = len(points)
        masks = np.asarray(masks, dtype=bool)
        v_pad = _bucket(v, _AGG_BUCKETS)
        if v == 0 or v_pad is None or masks.ndim != 2 or masks.shape[1] != v:
            self.stats["fallback_shape"] += 1
            return None
        if not self._ensure_bucket(("agg", v_pad)):
            self.stats["fallback_cold"] += 1
            return None
        try:
            faults.maybe("bls.pairing")
            xs = _pack_fp([pt[0] for pt in points] + [_PAD_PK[0]] * (v_pad - v))
            ys = _pack_fp([pt[1] for pt in points] + [_PAD_PK[1]] * (v_pad - v))
            b = masks.shape[0]
            mp = np.zeros((b, v_pad), dtype=bool)
            mp[:, :v] = masks
            ax, ay, inf = self._agg_fn(
                jnp.asarray(np.broadcast_to(xs, (b,) + xs.shape).copy()),
                jnp.asarray(np.broadcast_to(ys, (b,) + ys.shape).copy()),
                jnp.asarray(mp),
            )
        except Exception:
            self._bucket_entry(("agg", v_pad)).failed = True
            self.compile_breaker.record_failure()
            raise
        self.compile_breaker.record_success()
        self.stats["device_aggregates"] += 1
        ax = np.asarray(ax)
        ay = np.asarray(ay)
        inf = np.asarray(inf)
        out: List[Optional[Tuple[int, int]]] = []
        for i in range(b):
            if inf[i]:
                out.append(None)
            else:
                out.append(
                    (ops_bls.from_limbs(ax[i]), ops_bls.from_limbs(ay[i]))
                )
        return out

    def compile_stats(self) -> Dict[str, Optional[float]]:
        with self._lock:
            return {
                f"{k[0]}/{k[1]}": e.compile_s
                for k, e in self._buckets.items()
                if e.ready
            }

    def engine_stats(self) -> Dict[str, object]:
        """The unified engine-telemetry protocol (models/telemetry.py).
        Host (oracle) row counts live in the provider
        (crypto/bls.BLSBatchVerifier) — the engine reports what IT
        executed."""
        from tendermint_tpu.models.telemetry import breaker_view, bucket_entry

        with self._lock:
            buckets = {
                f"{kind}/{n}": bucket_entry(e)
                for (kind, n), e in self._buckets.items()
            }
            counters = dict(self.stats)
        return {
            "engine": "bls",
            "device_rows": float(counters.get("device_rows", 0)),
            "host_rows": 0.0,
            "buckets": buckets,
            "breakers": breaker_view(self.compile_breaker),
            "queue_wait_ms": None,
            "counters": counters,
        }
