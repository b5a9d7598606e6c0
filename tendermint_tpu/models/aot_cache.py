"""Serialized-executable (AOT) cache for the verify pipeline.

The XLA persistent compilation cache (JAX_COMPILATION_CACHE_DIR) keeps a
restarting node from re-OPTIMIZING programs, but every process start
still pays trace + lower + cache lookup + program load — measured
15-23s for the staged verify pipeline on a v5e even with a warm
persistent cache (BENCHMARKS.md round 2). The reference's serial
verifier has zero warmup (crypto/ed25519/ed25519.go:151), so a
restarting validator must not fall that far behind.

This cache serializes the jax.stages.Compiled executable itself
(jax.experimental.serialize_executable): deserialize_and_load skips
trace, lowering AND compilation, handing back a loaded executable in
~100ms per stage. Keyed by a fingerprint of jaxlib version + backend
platform + device kind + the source of the ops/ modules, plus the
stage name and argument shapes — any mismatch or load failure falls
back to a normal jit compile; the cache is an optimization, never a
correctness dependency.

Disable with TM_AOT_CACHE=0. This cache and the built valset tables
live under the compile-cache root (utils/jaxenv.compile_cache_dir:
``<root>/aot``, ``<root>/tables``) so one externally placed directory
warms all three; TM_AOT_CACHE_DIR / TM_TABLES_CACHE_DIR relocate each.
"""

from __future__ import annotations

import hashlib
import os
import threading
from typing import Any, Dict, Optional, Tuple

import jax

from tendermint_tpu.utils.jaxenv import compile_cache_dir
from tendermint_tpu.utils.log import get_logger

_log = get_logger("aot-cache")

_FINGERPRINT: Optional[str] = None
_fp_lock = threading.Lock()


def enabled() -> bool:
    return os.environ.get("TM_AOT_CACHE", "1") != "0"


def cache_dir() -> str:
    return os.environ.get("TM_AOT_CACHE_DIR") or os.path.join(
        compile_cache_dir(), "aot"
    )


def _code_digest() -> str:
    """Digest of the kernel source files: a changed kernel must never
    load a stale executable. Env-tunable kernel parameters (TM_SPLITS
    changes every table shape and scan program) fold in too — a table
    or executable built at one value must miss at another."""
    import tendermint_tpu.models.verifier as _v
    import tendermint_tpu.ops as _ops
    from tendermint_tpu.ops import curve as _curve

    h = hashlib.sha256()
    h.update(f"splits={_curve.SPLITS}".encode())
    roots = [os.path.dirname(_ops.__file__), _v.__file__]
    files = []
    for r in roots:
        if os.path.isdir(r):
            files.extend(
                os.path.join(r, f) for f in sorted(os.listdir(r)) if f.endswith(".py")
            )
        else:
            files.append(r)
    for f in files:
        with open(f, "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()[:16]


def _host_machine_sig() -> str:
    """Host ISA identity: arch + the CPU feature flags XLA:CPU compiles
    against. A serialized CPU executable built on a host with (say)
    avx512 loads fine on a host without it and then SIGILLs at dispatch
    — XLA only warns ("Machine type used for XLA:CPU compilation
    doesn't match the machine type for execution"). Baking the flags
    into the fingerprint makes such a blob a cache MISS instead."""
    import platform as _platform

    parts = [_platform.machine()]
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    parts.append(" ".join(sorted(line.split(":", 1)[1].split())))
                    break
    except OSError:
        parts.append(_platform.processor() or "?")
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:12]


def fingerprint() -> str:
    """Backend + host + code identity baked into every cache filename."""
    global _FINGERPRINT
    with _fp_lock:
        if _FINGERPRINT is None:
            dev = jax.devices()[0]
            platform = getattr(dev, "platform", "?")
            raw = "|".join(
                [
                    jax.__version__,
                    platform,
                    getattr(dev, "device_kind", "?"),
                    # only XLA:CPU lowers to host ISA; a TPU executable is
                    # host-agnostic and must stay shareable across hosts
                    _host_machine_sig() if platform == "cpu" else "",
                    _code_digest(),
                ]
            )
            _FINGERPRINT = hashlib.sha256(raw.encode()).hexdigest()[:20]
        return _FINGERPRINT


def _arg_sig(args: Tuple[Any, ...]) -> str:
    # tree_leaves: container args (e.g. the sharded scan's tuple of
    # table shards) contribute each leaf's shape — a bare getattr would
    # map every tuple to '?' and collide executables across different
    # shard counts. Flat array args flatten to themselves, so existing
    # cache keys are unchanged.
    import jax

    parts = []
    for a in jax.tree_util.tree_leaves(args):
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        parts.append(f"{tuple(shape) if shape is not None else '?'}:{dtype}")
    return hashlib.sha256(";".join(parts).encode()).hexdigest()[:16]


def _path(stage: str, args: Tuple[Any, ...]) -> str:
    return os.path.join(cache_dir(), f"{fingerprint()}-{stage}-{_arg_sig(args)}.jaxexe")


def load(stage: str, args: Tuple[Any, ...]):
    """A loaded Compiled for (stage, arg shapes), or None."""
    if not enabled():
        return None
    try:
        p = _path(stage, args)
        if not os.path.exists(p):
            return None
        from jax.experimental.serialize_executable import deserialize_and_load
        import pickle

        with open(p, "rb") as fh:
            payload, in_tree, out_tree, device_ids = pickle.load(fh)
        # restore the original device assignment: deserialize_and_load
        # defaults to ALL local devices, which breaks a single-device
        # executable on a multi-device host (and vice versa)
        by_id = {d.id: d for d in jax.devices()}
        return deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in device_ids],
        )
    except Exception as ex:  # stale/incompatible blob: recompile
        _log.info("aot load failed (recompiling)", stage=stage, err=repr(ex))
        return None


def save(stage: str, args: Tuple[Any, ...], compiled) -> None:
    """Best-effort: serialize `compiled` for the next process."""
    if not enabled():
        return
    try:
        from jax.experimental.serialize_executable import serialize
        import pickle

        payload, in_tree, out_tree = serialize(compiled)
        device_ids = [d.id for d in compiled.runtime_executable().local_devices()]
        os.makedirs(cache_dir(), exist_ok=True)
        p = _path(stage, args)
        tmp = p + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:
            pickle.dump((payload, in_tree, out_tree, device_ids), fh)
        os.replace(tmp, p)
    except Exception as ex:  # backend without executable serialization
        _log.info("aot save failed", stage=stage, err=repr(ex))


# -- built valset tables (pure data) -----------------------------------
#
# The split tables a valset build produces are deterministic int32
# arrays (~12KB/validator). Persisting THEM — not just the build
# executable — lets a restarting node device_put ~120MB of data instead
# of loading a ~200MB t-build executable AND re-running the build
# (measured 15.9s load + ~14-30s run at 10k validators on a v5e).
# Keyed by the code digest only: tables are device-independent data,
# so a CPU-built table is valid on TPU and vice versa.

_TABLES_KEEP = int(os.environ.get("TM_TABLES_CACHE_KEEP", "4"))


def tables_dir() -> str:
    return os.environ.get("TM_TABLES_CACHE_DIR") or os.path.join(
        compile_cache_dir(), "tables"
    )


_CODE_DIGEST: Optional[str] = None


def _code_digest_cached() -> str:
    global _CODE_DIGEST
    if _CODE_DIGEST is None:
        _CODE_DIGEST = _code_digest()
    return _CODE_DIGEST


def _tables_path(valset_key: bytes, v: int, dir_path: Optional[str] = None) -> str:
    return os.path.join(
        dir_path or tables_dir(),
        f"{_code_digest_cached()}-{valset_key.hex()[:32]}-{v}.npz",
    )


def load_tables(valset_key: bytes, v: int, pk_digest: bytes):
    """(tables, a_ok) numpy arrays for this valset, or None.

    pk_digest = sha256 of the (padded) pubkey matrix the caller is about
    to verify against. The stored digest must match: a stale blob under
    a reused key, a truncated-hex collision, or a tampered cache file
    would otherwise silently substitute wrong precomputed tables into
    signature verification — a consensus-safety issue, not a perf one."""
    if not enabled():
        return None
    try:
        import numpy as np

        p = _tables_path(valset_key, v)
        if not os.path.exists(p):
            return None
        with np.load(p) as z:
            tables, a_ok = z["tables"], z["a_ok"]
            stored = z["pk_sha"].tobytes() if "pk_sha" in z else b""
        if stored != pk_digest:
            _log.info("tables pubkey digest mismatch (rebuilding)",
                      path=os.path.basename(p))
            return None
        if tables.shape[0] < v:  # truncated/foreign blob
            return None
        try:
            os.utime(p)  # LRU recency for _prune_tables
        except OSError:
            pass  # read-only cache dir (e.g. baked into an image): the
            # load itself succeeded and that's what matters
        return tables, a_ok
    except Exception as ex:
        _log.info("tables load failed (rebuilding)", err=repr(ex))
        return None


def save_tables(
    valset_key: bytes, tables, a_ok, pk_digest: bytes,
    dir_path: Optional[str] = None,
) -> None:
    """Best-effort atomic persist of built tables (uncompressed: field
    elements don't compress and savez_compressed is ~10x slower). The
    pubkey digest is stored alongside so load_tables can refuse a blob
    that doesn't belong to the pubkeys being verified. dir_path lets an
    async builder pin the directory it resolved at BUILD time (the env
    var may point elsewhere by the time a background thread saves)."""
    if not enabled():
        return
    try:
        import numpy as np

        os.makedirs(dir_path or tables_dir(), exist_ok=True)
        p = _tables_path(valset_key, int(a_ok.shape[0]), dir_path)
        tmp = p + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:
            np.savez(
                fh, tables=np.asarray(tables), a_ok=np.asarray(a_ok),
                pk_sha=np.frombuffer(pk_digest, dtype=np.uint8),
            )
        os.replace(tmp, p)
        _prune_tables()
    except Exception as ex:
        _log.info("tables save failed", err=repr(ex))


def _prune_tables() -> None:
    """Bound the on-disk table cache to the newest _TABLES_KEEP files
    (a 10k-valset file is ~120MB; an unbounded dir would eat the disk
    across valset changes)."""
    try:
        d = tables_dir()
        files = [
            os.path.join(d, f) for f in os.listdir(d) if f.endswith(".npz")
        ]
        files.sort(key=os.path.getmtime, reverse=True)
        for p in files[_TABLES_KEEP:]:
            try:
                os.remove(p)
            except OSError:
                pass
    except Exception:
        pass


# ONE compile/deserialize at a time, process-wide. Background warm
# threads (verifier._compile_tabled_async, register_valset) compile
# concurrently with live-path compiles; with the persistent caches in
# play that interleaving segfaulted inside jax's compilation-cache
# read (zstd deserialize) twice in full-suite runs — same stack both
# times, never reproducible single-threaded. Serializing costs nothing
# real: XLA compiles saturate the host cores anyway.
_COMPILE_SERIAL = threading.Lock()


class AotJit:
    """jit wrapper that persists compiled executables across processes.

    Call like the underlying function; per distinct argument shapes it
    (1) tries the on-disk executable, (2) falls back to lower+compile
    and saves the result. In-process, the loaded/compiled executable is
    memoized exactly like jit's own cache.

    A deserialized executable is VALIDATED on its first use (synchronous
    block inside a try): some backends' AOT loaders accept a blob and
    then fail at dispatch (observed on XLA:CPU for large programs with
    subcomputations — "Function ... not found"). A dispatch failure
    drops the stale file, recompiles, and re-runs — the cache can slow
    a start down, never break it.

    ``fragile=True`` marks a stage whose executable does not SURVIVE
    XLA:CPU (de)serialization: full-suite runs segfaulted inside the
    compilation-cache read for the templated-prepare program (three
    runs, same stack, never reproducible in a fresh process). On the
    CPU backend such stages skip persistence entirely — ours AND
    jax's own cache (toggled off around the compile; we hold
    _COMPILE_SERIAL, so no other model compile sees the toggle).
    Non-CPU backends serialize through a different path and keep full
    caching (the cold-start budget needs it).
    """

    def __init__(self, fn, stage: str, jit_fn=None, fragile: bool = False):
        self._jit = jit_fn if jit_fn is not None else jax.jit(fn)
        self.stage = stage
        self.fragile = fragile
        self._compiled: Dict[str, Any] = {}  # sig -> [callable, needs_validation]
        self._lock = threading.Lock()
        self.last_source: Optional[str] = None  # "aot" | "compile" (tests/metrics)

    def _no_persist(self) -> bool:
        return self.fragile and jax.default_backend() == "cpu"

    def _compile_uncached(self, args):
        # jax_enable_compilation_cache gates BOTH the cache read and
        # the post-compile serialize-and-write inside
        # compile_or_get_cached (clearing the dir does not: an
        # already-initialized cache keeps its handle — observed as a
        # segfault in _cache_write with the dir set to None)
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            return self._jit.lower(*args).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)

    def _get(self, sig: str, args):
        rec = self._compiled.get(sig)
        if rec is None:
            with self._lock:
                rec = self._compiled.get(sig)
                if rec is None:
                    with _COMPILE_SERIAL:
                        if self._no_persist():
                            c = self._compile_uncached(args)
                            self.last_source = "compile"
                            rec = [c, False]
                        else:
                            c = load(self.stage, args)
                            if c is not None:
                                self.last_source = "aot"
                                rec = [c, True]
                            else:
                                c = self._jit.lower(*args).compile()
                                self.last_source = "compile"
                                save(self.stage, args, c)
                                rec = [c, False]
                    self._compiled[sig] = rec
        return rec

    def _recompile(self, sig: str, args):
        try:
            os.remove(_path(self.stage, args))
        except OSError:
            pass
        with _COMPILE_SERIAL:
            if self._no_persist():
                c = self._compile_uncached(args)
                self.last_source = "compile"
            else:
                c = self._jit.lower(*args).compile()
                self.last_source = "compile"
                save(self.stage, args, c)
        with self._lock:
            self._compiled[sig] = [c, False]
        return c

    def __call__(self, *args):
        sig = _arg_sig(args)
        rec = self._get(sig, args)
        c, needs_validation = rec
        if not needs_validation:
            return c(*args)
        try:
            out = c(*args)
            jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
        except Exception as ex:
            _log.info(
                "aot executable failed validation (recompiling)",
                stage=self.stage, err=repr(ex),
            )
            return self._recompile(sig, args)(*args)
        rec[1] = False
        return out
