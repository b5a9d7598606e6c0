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


# -- key tables ----------------------------------------------------------------
#
# The split tables a build produces are deterministic int32 arrays
# (~30KB/validator key). Persisting THEM — not just the build
# executable — lets a restarting node device_put ~315MB of data instead
# of loading a ~200MB t-build executable AND re-running the build
# (measured 15.9s load + ~14-30s run at 10k validators on a v5e).
# One file a BUILD DISPATCH, rows keyed by the pubkeys stored beside
# them: a key's row is found whichever file holds it and whichever set
# asks, so a set that shares all but one key with the last one writes
# one row, not a second copy of the set. Named by the code digest only:
# tables are device-independent data, so a CPU-built table is valid on
# TPU and vice versa.

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


def key_rows(pubkeys) -> list:
    """The rows of a C-contiguous (n, 32) u8 key matrix as bytes."""
    raw = pubkeys.tobytes()
    return [raw[i : i + 32] for i in range(0, len(raw), 32)]


# file -> the keys it holds, read once a process: a file is named by
# the hash of its keys, so what a name holds never changes
_FILE_KEYS: Dict[str, Dict[bytes, int]] = {}


def load_tables(pubkeys):
    """Rows of the table files for the keys ``pubkeys`` (n, 32) u8:
    (found (n,) bool, tables (n, ...) int32, a_ok (n,) bool) with the
    rows not found left zero, or None when no file holds any of them.

    A row is taken only from under its own 32 key bytes, stored beside
    it: a stale or foreign file cannot put another key's tables into
    signature verification — a consensus-safety issue, not a perf one."""
    if not enabled():
        return None
    try:
        import numpy as np

        d = tables_dir()
        prefix = _code_digest_cached() + "-"
        files = sorted(
            (os.path.join(d, f) for f in os.listdir(d)
             if f.startswith(prefix) and f.endswith(".npz")),
            key=os.path.getmtime, reverse=True,
        ) if os.path.isdir(d) else []
        want = {}
        for i, k in enumerate(key_rows(np.ascontiguousarray(pubkeys, dtype=np.uint8))):
            want.setdefault(k, []).append(i)
        found = np.zeros(len(pubkeys), dtype=bool)
        tables = a_ok = None
        for p in files:
            if not want:
                break
            try:
                stored = _FILE_KEYS.get(p)
                if stored is not None and want.keys().isdisjoint(stored):
                    continue
                with np.load(p) as z:
                    if stored is None:
                        pk = np.ascontiguousarray(z["pk"], dtype=np.uint8)
                        stored = _FILE_KEYS[p] = {k: r for r, k in enumerate(key_rows(pk))}
                    hits = [k for k in want if k in stored]
                    if not hits:
                        continue
                    f_tables, f_a_ok = z["tables"], z["a_ok"]
            except Exception as ex:
                _log.info("tables load failed (skipping file)",
                          path=os.path.basename(p), err=repr(ex))
                continue
            if f_tables.shape[0] != len(stored) or f_a_ok.shape[0] != len(stored):
                continue  # truncated/foreign blob
            if tables is None:
                tables = np.zeros((len(pubkeys),) + f_tables.shape[1:], dtype=f_tables.dtype)
                a_ok = np.zeros(len(pubkeys), dtype=bool)
            for k in hits:
                at, r = want.pop(k), stored[k]
                tables[at], a_ok[at], found[at] = f_tables[r], f_a_ok[r], True
            try:
                os.utime(p)  # LRU recency for _prune_tables
            except OSError:
                pass  # read-only cache dir (e.g. baked into an image): the
                # load itself succeeded and that's what matters
        return (found, tables, a_ok) if tables is not None else None
    except Exception as ex:
        _log.info("tables load failed (rebuilding)", err=repr(ex))
        return None


def save_tables(pubkeys, tables, a_ok, dir_path: Optional[str] = None) -> None:
    """Best-effort atomic persist of one build's rows (uncompressed:
    field elements don't compress and savez_compressed is ~10x slower):
    the keys ``pubkeys`` (k, 32) u8 and their k rows of tables and a_ok,
    so load_tables finds each row under its key. dir_path lets an async
    builder pin the directory it resolved at BUILD time (the env var
    may point elsewhere by the time a background thread saves)."""
    if not enabled():
        return
    try:
        import hashlib

        import numpy as np

        pk = np.ascontiguousarray(pubkeys, dtype=np.uint8)
        d = dir_path or tables_dir()
        os.makedirs(d, exist_ok=True)
        name = hashlib.sha256(pk.tobytes()).hexdigest()[:32]
        p = os.path.join(d, f"{_code_digest_cached()}-{name}-{pk.shape[0]}.npz")
        tmp = p + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:
            np.savez(fh, pk=pk, tables=np.asarray(tables), a_ok=np.asarray(a_ok))
        os.replace(tmp, p)
        _prune_tables()
    except Exception as ex:
        _log.info("tables save failed", err=repr(ex))


def _prune_tables() -> None:
    """Bound the on-disk table cache to the bytes of _TABLES_KEEP files
    of the largest build it holds, newest first (a 10k-valset file is
    ~315MB; an unbounded dir would eat the disk across valset changes,
    and a count alone would let a few one-key files of later changes
    push the set's own file out)."""
    try:
        d = tables_dir()
        files = [
            os.path.join(d, f) for f in os.listdir(d) if f.endswith(".npz")
        ]
        files.sort(key=os.path.getmtime, reverse=True)
        sizes = [os.path.getsize(p) for p in files]
        room = _TABLES_KEEP * max(sizes, default=0)
        for p, size in zip(files, sizes):
            room -= size
            if room < 0:
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
