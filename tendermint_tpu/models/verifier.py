"""VerifierModel: the jit-compiled, mesh-shardable batch verifier.

Latency discipline for the <2ms VerifyCommit target (SURVEY.md section
7.3.6): the kernel is compiled ONCE per (padded-N, msg-len) bucket and
re-used; batch sizes are padded up to bucket boundaries so a live
validator set of any size hits a warm executable. Padding rows carry an
always-invalid signature and zero voting power, so they can't affect
results.

Two verify pipelines share the buckets:

- the GENERIC staged pipeline (prepare/scan/finish) for arbitrary
  (pubkey, msg, sig) batches;
- the CACHED-TABLE pipeline (``verify_rows_cached``): validator
  pubkeys are stable across heights, so affine-cached split tables of
  each key (built once per KEY into the device-resident key pool,
  ``_KeyPool``, whichever sets the key appears in; least-recently-used
  keys go under MAX_TABLE_BYTES) remove decompression, the per-row
  table build, and 7/8 of the scan doublings from the per-commit
  program. A launch's table operand is the pool's columns of the keys
  its commits are checked against: the pool as it lies where a set is
  all of it, else a slab gathered on the device. On one device, whole
  commits of sets they mostly fill run in SLOT ORDER (``plan_slots``):
  each row goes to its key's slot and stage 2 reads the tables where
  they lie; sparse or unordered batches, a mesh and sharded tables
  GATHER each row's table by index. Streams past MAX_DEVICE_ROWS as
  in-flight launches; ``register_valset`` pre-builds at node start.

Two compile disciplines:

- ``block_on_compile=True`` (bench/tests): the first call per bucket
  pays the compile inline.
- ``block_on_compile=False`` (live node): a cold bucket falls back to
  the host verifier for THIS call while a background thread compiles
  the device program; subsequent calls hit the warm executable.
  Consensus never stalls on XLA. Compiled executables persist across
  processes via the AOT cache (models/aot_cache.py).

Multi-chip: the mesh path uses ``shard_map`` so the per-device program
is exactly the single-device program (compile cost does not scale with
mesh size, unlike whole-graph GSPMD partitioning). Rows shard over the
batch axis, cached tables replicate, verdicts come back; no stage runs
a collective. A commit's voting-power tally is a column sum on the
host over those verdicts (crypto/batch.BatchVerifier
.verify_commit_batch, types/validator_set.py) on one device and on a
mesh alike.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from tendermint_tpu.ops import curve as ops_curve
from tendermint_tpu.ops import ed25519 as ops_ed
from tendermint_tpu.ops import stage2_kernel as ops_stage2
from tendermint_tpu.parallel import pad_to_multiple
from tendermint_tpu.parallel.mesh import BATCH_AXIS
from tendermint_tpu.utils import faultinject as faults
from tendermint_tpu.utils.jaxenv import enable_compile_cache
from tendermint_tpu.utils.log import get_logger
from tendermint_tpu.utils.trace import span

# Persistent compilation cache: the verifier graph is large; pay compile
# once per machine, not per process.
enable_compile_cache()

# Batch-size buckets (padded row counts) to bound recompilation. 10240
# sits just above MaxVotesCount (types/vote_set.py) so a full 10k-
# validator commit pads by 2.4%, not 64%.
_BUCKETS = [16, 64, 256, 1024, 4096, 10240, 16384]

# Largest single device dispatch; bigger batches stream as windows of
# this size (one final sync). See VerifierModel.verify.
MAX_DEVICE_ROWS = 16384

# Template-count buckets for the templated message source: a live
# commit is one (commit, nil) template pair; a cross-height batch has
# one pair per height. Padding T to a bucket keeps the stage-1 program
# count bounded instead of compiling per distinct height count.
_TPL_BUCKETS = [2, 8, 32, 128, 512, 1024]


def _bucket(n: int, multiple: int) -> int:
    for b in _BUCKETS:
        if n <= b and b % multiple == 0:
            return b
    return pad_to_multiple(n, max(multiple, 16384))


# In-flight background compile threads. They are daemon threads (a
# stuck XLA compile must never block a node that is being killed), but
# the interpreter tearing one down MID-COMPILE aborts the process from
# XLA's C++ ("FATAL: exception not rethrown", exit 134) — so an atexit
# hook joins them first. Escape hatch: TM_NO_COMPILE_JOIN=1 skips the
# join (fast exit, possible abort message).
_compile_threads: list = []
_compile_threads_lock = threading.Lock()

# One background WARM body at a time (see _compile_tabled_async): the
# compile steps inside are already serialized by the AOT layer's
# _COMPILE_SERIAL, but the interleaved eager device ops between them
# were implicated in flaky cross-thread trace corruption.
_WARM_SERIAL = threading.Lock()


def _track_compile_thread(t: threading.Thread) -> None:
    with _compile_threads_lock:
        # prune only threads that RAN and finished: a tracked-but-not-
        # yet-started thread also reports is_alive() == False and must
        # not be dropped from the join list
        _compile_threads[:] = [
            x for x in _compile_threads if x.ident is None or x.is_alive()
        ]
        _compile_threads.append(t)


# Bounded: the join exists to avoid the mid-compile abort, but neither a
# wedged backend nor a slow compile may stall shutdown unboundedly. 60s
# covers a cold STAGED TPU compile (~37s) and every warm-persistent-
# cache case; only a first-boot compile on a machine with an empty
# cache can outlive it, where the worst case is an abort message (and
# exit 134) during interpreter teardown instead of a multi-minute hang
# on a SIGTERM'd node.
_JOIN_TIMEOUT_S = float(os.environ.get("TM_COMPILE_JOIN_TIMEOUT_S", "60"))


def _join_compile_threads() -> None:  # pragma: no cover - exit path
    if os.environ.get("TM_NO_COMPILE_JOIN") == "1":
        return
    deadline = time.monotonic() + _JOIN_TIMEOUT_S
    with _compile_threads_lock:
        pending = list(_compile_threads)
    for t in pending:
        if t.ident is None:
            continue  # tracked but never started: nothing to join
        t.join(timeout=max(0.0, deadline - time.monotonic()))


import atexit  # noqa: E402

atexit.register(_join_compile_threads)


class _Entry:
    __slots__ = ("fn", "ready", "compiling", "compile_s")

    def __init__(self, fn):
        self.fn = fn
        self.ready = False
        self.compiling = False
        self.compile_s: Optional[float] = None


# One key's cached tables: SPLITS*8 affine-cached points of 3*LIMBS
# int32 limbs, ~30 KB — a 10k set is ~315 MB of HBM.
TABLE_KEY_BYTES = 4 * ops_curve.SPLITS * 8 * 3 * ops_curve.F.LIMBS

# Slot order against gathered order (plan_slots): a batch goes to its
# validators' slots when the slots it would launch are at most this
# many times the padded rows the gathered pair would launch for it.
# From the two costs read on a v5e (PERF.md section 6, PR 30): a whole
# warm call of 10,240 gathered rows 43.5 ms against 28.8 ms in slot
# order, 16,384 rows 75.4 against 47.4 ms — a gathered row costs
# 1.51-1.59 slots; the lower edge, rounded down.
_SLOT_GATHER_RATIO = 1.5

# Largest valset served by ONE device table, and the most rows a table,
# a shard or a table-build dispatch holds. The reference caps commits
# at 10k votes (types/vote_set.go:18 MaxVotesCount); beyond ~16k rows a
# single table's gathers go pathological (the 50k-ingest eval measured
# the whole process slowing ~50x while a 65536-row table was resident —
# round-4 ledger), and the build's affine conversion holds
# (rows*SPLITS*8, 20, 20) int32 intermediates: one 65536-row dispatch
# wants ~30GB of HBM (observed OOM at 50k validators) while 16384 rows
# stay ~3.4GB in flight. Larger sets up to MAX_SHARDED_VALSET ride
# SHARDED tables: equal shards of this many rows, each built by its own
# dispatch and gathered bounded in one program
# (ops_ed.verify_stage_scan_tabled_sharded).
MAX_TABLED_VALSET = MAX_DEVICE_ROWS

# Largest valset for the sharded-table path (HBM is the bound:
# ~30KB/validator => ~2GB at 65536). The figure is SINGLE-device; on a
# live N-device mesh the shard tables replicate to every chip while
# each chip also works its 1/N row shard, so the per-device table
# budget divides by N — VerifierModel.sharded_valset_cap() computes
# the live cap from the mesh size (N=1 reproduces this constant
# exactly). Beyond the cap the generic pipeline takes over.
MAX_SHARDED_VALSET = 1 << 16

# What the device keeps of key tables, in bytes: the same ~2 GB, however
# the keys are spread over sets — as many pooled keys as the largest
# set has (_KeyPool drops the least recently used beyond it), and the
# whole-set entries of a mesh or of sets past MAX_TABLED_VALSET
# together (_tables_entry).
MAX_TABLE_BYTES = MAX_SHARDED_VALSET * TABLE_KEY_BYTES


class SlotPlan(NamedTuple):
    """Where a batch's rows go in slot order (plan_slots)."""

    slots: np.ndarray  # (n,) slot of row r, the launches laid end to end
    launches: Tuple[Tuple[int, int, int], ...]  # (first row, end row, commits C) a launch


def _gathered_rows(n: int) -> int:
    """Padded rows the gathered pair launches for n rows on one device:
    one bucket, or the full windows and the tail's bucket."""
    tail = n % MAX_DEVICE_ROWS if n > MAX_DEVICE_ROWS else n
    return n - tail + (_bucket(tail, 1) if tail else 0)


def _commits_per_launch(v: int) -> int:
    """Whole commits of V slots one slot-order launch holds."""
    return max(1, MAX_DEVICE_ROWS // v)


def _tail_commits(rest: int, per: int) -> int:
    """The commits' worth of slots the launch of the last ``rest``
    commits (fewer than ``per``) is rounded up to: a power of two."""
    return min(per, 1 << (rest - 1).bit_length()) if rest else 0


def plan_slots(row_idx, v: int) -> Optional[SlotPlan]:
    """Slot order for a batch against a V-row table, or None where the
    gathered pair is cheaper. Pure numpy, from row_idx and V alone.

    row_idx is cut into maximal strictly increasing runs — a commit's
    rows are one run; a duplicate or a step back starts the next, so
    unordered indices (the trusting path's lookups by address, a vote
    drain) become many short runs. Run k is "commit" k: its rows go to
    slots k*V + row_idx. Runs are dealt to launches of MAX_DEVICE_ROWS
    // V commits, the last launch's count rounded up to a power of two
    (a set meets at most log2 of that many + 1 program shapes). Slots
    nobody signed for carry zeros and their verdicts are never read.

    Slot order is taken when its slots are at most _SLOT_GATHER_RATIO
    times the padded rows of the gathered pair (_gathered_rows): whole
    commits of a set they mostly fill. A sparse or unordered batch has
    slots far beyond its rows and stays gathered."""
    idx = np.asarray(row_idx, dtype=np.int64)
    n = idx.shape[0]
    if n == 0 or not 0 < v <= MAX_DEVICE_ROWS or idx.min() < 0 or idx.max() >= v:
        return None
    run_start = np.flatnonzero(idx[1:] <= idx[:-1]) + 1  # rows that open a run
    runs = run_start.shape[0] + 1
    per = _commits_per_launch(v)
    full, rest = divmod(runs, per)
    last = _tail_commits(rest, per)
    if (full * per + last) * v > _SLOT_GATHER_RATIO * _gathered_rows(n):
        return None
    run_of = np.zeros(n, dtype=np.int64)
    run_of[run_start] = 1
    bounds = np.concatenate([[0], run_start, [n]])  # each run's first row, then n
    launches = tuple(
        (int(bounds[k]), int(bounds[min(k + per, runs)]), per if k + per <= runs else last)
        for k in range(0, runs, per)
    )
    return SlotPlan(np.cumsum(run_of) * v + idx, launches)


def _to_slots(rows: np.ndarray, at: np.ndarray, n_slots: int) -> np.ndarray:
    """rows scattered to their slots of a zeroed (n_slots, ...) array."""
    out = np.zeros((n_slots,) + rows.shape[1:], dtype=rows.dtype)
    out[at] = rows
    return out


class _TablesEntry:
    __slots__ = (
        "tables", "shards", "a_ok", "pk_dev", "v", "ready", "building",
        "failed", "build_s", "source",
    )

    def __init__(self, v: int):
        self.tables = None
        self.shards = None  # tuple of per-shard tables for V > MAX_TABLED_VALSET
        self.a_ok = None
        self.pk_dev = None  # (V_pad, 32) u8 device copy for stage-1 gather
        self.v = v
        self.ready = False
        self.building = False
        # latched on a build failure (e.g. device OOM): the cached path
        # stays disabled for this valset instead of retrying a
        # deterministic failure on every verify
        self.failed = False
        self.build_s: Optional[float] = None
        self.source: Optional[str] = None  # "build" | "disk" | "pool"


def _pool_view(tables, a_ok, pk_dev, v: int) -> _TablesEntry:
    """A launch's table operand out of the key pool, in the form the
    stages take a whole-set entry in."""
    e = _TablesEntry(v)
    e.tables, e.a_ok, e.pk_dev = tables, a_ok, pk_dev
    e.ready, e.source = True, "pool"
    return e


class _KeyPool:
    """The device's key tables of one unmeshed model: one column a
    validator KEY, built once whichever sets the key appears in.

    ``view`` answers a GroupKeys (the distinct keys of a launch's
    commits, crypto/batch.py) with the launch's table operand. Keys the
    pool lacks are read from the table files or built — every missing
    key of the call in ONE build dispatch, padded to a batch bucket —
    and appended; their columns are memoised under the digest, so a set
    or a chain seen before costs one dictionary lookup. Where the keys
    are columns 0..U-1 of a pool exactly their bucket wide — a node's
    one set, built whole — the operand is the pool's arrays as they lie
    and nothing is copied; else it is a slab of bucket(U) columns
    gathered on the device (ops_ed.table_slab: ~30 KB a column), so a
    set that shares all but one key with the last builds one row and
    launches the shapes of a set its size. The pool keeps a set built
    whole in the form the stages read, (P, SPLITS, 8, 3*LIMBS); once a
    second build joins it, tables lie one 7,680-int32 row a key, the
    form a slab is gathered from without laying the pool out again
    (ops_ed.table_slab).

    Bound: MAX_TABLE_BYTES of columns. Beyond it the least recently
    used keys go and the rest move up (one device gather; the memo is
    dropped) — never a key of the call that asks: a launch's operand is
    gathered under the same lock, and a gathered or adopted array is
    immutable, so a launch in flight keeps what it was given. In
    non-blocking mode a missing key starts one background build and the
    call declines (None) until it lands; a failed build trips the
    model's tables breaker, as a whole-set build does."""

    def __init__(self, model):
        from tendermint_tpu.crypto.batch import TABLE_COUNTS
        from tendermint_tpu.models.aot_cache import key_rows

        self._model = model
        self._counts = TABLE_COUNTS
        self._rows = key_rows  # a key matrix's rows as bytes
        self._lock = threading.Lock()  # columns, arrays, memo
        self._build_serial = threading.Lock()  # one fetch of missing keys at a time
        self._col: Dict[bytes, int] = {}  # key -> column
        self._used = 0  # columns 0.._used-1 hold keys
        self._keys = np.zeros((0, 32), dtype=np.uint8)  # host copy of the key column
        self._stamp = np.zeros(0, dtype=np.int64)  # column -> tick of its last use
        self._tick = 0
        # device arrays of cap columns: tables (cap, 7680) a row a key,
        # None while the pool is one set as its build left it (_whole)
        self.tables = self.a_ok = self.pk = None
        self._whole: Optional[_TablesEntry] = None  # the pool as an operand, stages' form
        self._memo: "OrderedDict[bytes, np.ndarray]" = OrderedDict()  # digest -> columns
        self._memo_cols = 0
        self.building = False  # a background fetch is running
        self.failed = False  # the last fetch failed (the breaker gates the retry)
        self.build_s = 0.0  # seconds spent fetching, dispatches made
        self.dispatches = 0

    @staticmethod
    def max_keys() -> int:
        return MAX_TABLE_BYTES // TABLE_KEY_BYTES

    def __len__(self) -> int:
        return self._used

    def capacity(self) -> int:
        return 0 if self.a_ok is None else int(self.a_ok.shape[0])

    # the engine_stats bucket protocol (models/telemetry.bucket_entry):
    # the pool is one "tables:" bucket, ready once it holds keys and no
    # background build runs
    @property
    def ready(self) -> bool:
        return self._used > 0 and not self.building

    @property
    def compiling(self) -> bool:
        return self.building

    @property
    def compile_s(self) -> Optional[float]:
        return self.build_s or None

    def nbytes(self) -> int:
        held = (self.tables is not None) + (self._whole is not None)
        return held * self.capacity() * TABLE_KEY_BYTES

    def _flat(self):
        """The tables a row a key (lock held): laid out so once, when a
        set built whole stops being all of the pool."""
        if self.tables is None:
            cap = self.capacity()
            self.tables, self._whole = self._whole.tables.reshape(cap, -1), None
        return self.tables

    # -- the one entry point ------------------------------------------------

    def view(self, keys) -> Optional[_TablesEntry]:
        pk = np.ascontiguousarray(keys.pubkeys, dtype=np.uint8)
        u = int(pk.shape[0])
        if not 0 < u <= min(MAX_TABLED_VALSET, self.max_keys()):
            return None
        with self._lock:
            cols = self._columns(keys.digest, pk)
            if cols is not None:
                return self._operand(cols)
        missing = self._missing(pk)
        model = self._model
        probed = False
        if self.failed:
            # fail-stop until the breaker's cooldown, then one probe
            if not model.tables_breaker.allow():
                return None
            probed = True
        if not model.block_on_compile:
            with self._lock:
                if self.building:
                    if probed:
                        model.tables_breaker.release_probe()
                    return None
                self.building = True

            def work():
                try:
                    if self._fetch(missing, pinned=pk):
                        with self._lock:  # the slab's shape too, off the live path
                            cols = self._columns(keys.digest, pk)
                            if cols is not None:
                                self._operand(cols)
                except Exception as ex:  # pragma: no cover - defensive
                    model.logger.error("key table warm failed", err=repr(ex))
                finally:
                    self.building = False

            t = threading.Thread(target=work, daemon=True, name="key-tables")
            _track_compile_thread(t)
            t.start()
            return None
        if not self._fetch(missing, pinned=pk):
            return None
        with self._lock:
            cols = self._columns(keys.digest, pk)
            return None if cols is None else self._operand(cols)

    # -- columns --------------------------------------------------------------

    def _columns(self, digest: bytes, pk: np.ndarray) -> Optional[np.ndarray]:
        """The keys' columns (lock held), None while one is missing."""
        cols = self._memo.get(digest)
        if cols is None:
            get = self._col.get
            cols = np.fromiter(
                (get(k, -1) for k in self._rows(pk)), dtype=np.int32, count=pk.shape[0]
            )
            if cols.size and cols.min() < 0:
                return None
            self._memo[digest] = cols
            self._memo_cols += cols.size
            while self._memo_cols > self.max_keys() and len(self._memo) > 1:
                self._memo_cols -= self._memo.popitem(last=False)[1].size
        else:
            self._memo.move_to_end(digest)
        self._counts.add(keys_reused=int(cols.size))
        return cols

    def _missing(self, pk: np.ndarray) -> np.ndarray:
        """The distinct keys of pk the pool lacks, in pk's order."""
        with self._lock:
            have = self._col
            seen = set()
            rows = [
                i for i, k in enumerate(self._rows(pk))
                if k not in have and not (k in seen or seen.add(k))
            ]
        return pk[rows]

    def _operand(self, cols: np.ndarray) -> _TablesEntry:
        """The columns as a launch's table operand (lock held)."""
        self._tick += 1
        self._stamp[cols] = self._tick
        u = int(cols.size)
        u_pad = _bucket(u, 1)
        cap = self.capacity()
        if cap == u_pad and cols[0] == 0 and cols[-1] == u - 1 and (np.diff(cols) == 1).all():
            if self._whole is None:
                self._whole = _pool_view(
                    self.tables.reshape(ops_ed._table_shape(cap)), self.a_ok, self.pk, cap
                )
            return self._whole
        padded = np.zeros(u_pad, dtype=np.int32)  # a padding slot reads column 0: never a row's
        padded[:u] = cols
        self._counts.add(slabs=1, slab_columns=u_pad)
        return _pool_view(*self._model._slab(self._flat(), self.a_ok, self.pk, padded), u_pad)

    # -- missing keys -----------------------------------------------------------

    def _fetch(self, pk: np.ndarray, pinned: np.ndarray) -> bool:
        """Read from the table files, or build in one dispatch, the
        tables of keys the pool lacks, and append them. The build runs
        outside the columns' lock: calls for pooled keys go on."""
        from tendermint_tpu.models import aot_cache

        model = self._model
        with self._build_serial, span("tables.build", keys=int(pk.shape[0])) as sp:
            pk = self._missing(pk)  # another call's fetch may have brought some
            try:
                if pk.shape[0]:
                    faults.maybe("device.tables")
                    t0 = time.perf_counter()
                    tables_dir = aot_cache.tables_dir()  # resolved now: see _build_tables
                    found = aot_cache.load_tables(pk)
                    loaded = 0
                    if found is not None:
                        mask, tables, a_ok = found
                        loaded = int(np.count_nonzero(mask))
                        if loaded:
                            self._append(pk[mask], tables[mask], a_ok[mask], pinned)
                        pk = pk[~mask]
                    k = int(pk.shape[0])
                    if k:
                        k_pad = _bucket(k, 1)
                        tables, a_ok = model._program("t-build")(
                            jnp.asarray(model._pad(pk, k_pad))
                        )
                        self.dispatches += 1
                        self._append(pk, tables, a_ok, pinned)
                        aot_cache.save_tables(
                            pk, np.asarray(tables)[:k], np.asarray(a_ok)[:k],
                            dir_path=tables_dir,
                        )
                    self.a_ok.block_until_ready()
                    self.build_s += time.perf_counter() - t0
                    self._counts.add(keys_built=k, keys_loaded=loaded)
                    sp.set(built=k, loaded=loaded, dispatches=int(k > 0))
                    model.logger.info(
                        "key tables ready", built=k, loaded=loaded, pooled=self._used,
                        seconds=round(time.perf_counter() - t0, 2),
                    )
                self.failed = False
                model.tables_breaker.record_success()
                return True
            except Exception as ex:
                # None-means-fallback, never an exception into commit
                # verification; fail-stop until the breaker's probe
                self.failed = True
                model.tables_breaker.record_failure()
                model.logger.error("key table build failed", err=repr(ex))
                return False

    def _append(self, pk: np.ndarray, tables, a_ok, pinned: np.ndarray) -> None:
        """New keys at the pool's end: tables and a_ok hold their rows
        first (k of bucket(k) device rows, or k host rows)."""
        model = self._model
        k = int(pk.shape[0])
        k_pad = _bucket(k, 1)
        if isinstance(tables, np.ndarray):
            tables, a_ok = model._pad(tables, k_pad), model._pad(a_ok, k_pad)
        pk_pad = model._pad(pk, k_pad)
        with self._lock:
            rows = self._rows(pk)
            if self._used + k > self.max_keys():
                self._evict(self._used + k - self.max_keys(), pinned)
            need = self._used + k
            if self.a_ok is None and k_pad == _bucket(need, 1):
                # a set built whole into an empty pool: its build IS the pool
                self.a_ok, self.pk = jnp.asarray(a_ok), jnp.asarray(pk_pad)
                self._whole = _pool_view(jnp.asarray(tables), self.a_ok, self.pk, k_pad)
                cap = k_pad
            else:
                cap = self._grow(need)
                at = np.full(k_pad, cap, dtype=np.int32)  # past the end: dropped
                at[:k] = np.arange(self._used, need)
                self.tables, self.a_ok, self.pk = model._program("t-put")(
                    self.tables, self.a_ok, self.pk, jnp.asarray(at),
                    jnp.asarray(tables), jnp.asarray(a_ok), jnp.asarray(pk_pad),
                )
                self._whole = None
            if self._keys.shape[0] < cap:
                self._keys = model._pad(self._keys, cap)
                self._stamp = model._pad(self._stamp, cap)
            self._keys[self._used : need] = pk
            self._stamp[self._used : need] = self._tick
            for i, key in enumerate(rows):
                self._col[key] = self._used + i
            self._used = need

    def _grow(self, need: int) -> int:
        """The pool's arrays, tables a row a key, at least ``need``
        columns wide (lock held)."""
        cap = _bucket(need, 1)
        if self.a_ok is None:
            self.tables = jnp.zeros((cap, TABLE_KEY_BYTES // 4), dtype=jnp.int32)
            self.a_ok = jnp.zeros((cap,), dtype=bool)
            self.pk = jnp.zeros((cap, 32), dtype=jnp.uint8)
        have = self.capacity()
        self._flat()
        if have >= cap:
            return have
        grow = lambda a: jnp.pad(a, [(0, cap - have)] + [(0, 0)] * (a.ndim - 1))  # noqa: E731
        self.tables, self.a_ok, self.pk = grow(self.tables), grow(self.a_ok), grow(self.pk)
        return cap

    def _evict(self, n: int, pinned: np.ndarray) -> None:
        """Drop the n least recently used keys, none of ``pinned``, and
        move the rest up in their order (lock held)."""
        free = np.ones(self._used, dtype=bool)
        for key in self._rows(np.ascontiguousarray(pinned, dtype=np.uint8)):
            c = self._col.get(key)
            if c is not None:
                free[c] = False
        cand = np.flatnonzero(free)
        if cand.size < n:
            raise RuntimeError(f"key pool: {n} keys over MAX_TABLE_BYTES and all in use")
        gone = cand[np.argsort(self._stamp[cand], kind="stable")[:n]]
        keep = np.delete(np.arange(self._used, dtype=np.int32), gone)
        cap = _bucket(max(int(keep.size), 1), 1)
        padded = np.zeros(cap, dtype=np.int32)
        padded[: keep.size] = keep
        tables, self.a_ok, self.pk = self._model._slab(
            self._flat(), self.a_ok, self.pk, padded
        )
        self.tables = tables.reshape(cap, -1)
        keys, stamp = self._keys[keep], self._stamp[keep]
        self._keys, self._stamp = self._model._pad(keys, cap), self._model._pad(stamp, cap)
        self._used = int(keep.size)
        self._col = {k: i for i, k in enumerate(self._rows(np.ascontiguousarray(keys)))}
        self._memo.clear()
        self._memo_cols = 0
        self._whole = None
        self._counts.add(keys_evicted=n)


# Every device program of the model: AOT tag -> (function, in_specs,
# out_specs). One device jits the function as it is; a mesh shard_maps
# it with the specs (VerifierModel._program), so the per-device program
# is the single-device one. Rows (_B) shard over the batch axis; the
# valset's tables, a_ok and pubkey matrix and the KB-scale templates
# replicate (_R): each device gathers its rows from a full local copy,
# ~30KB/validator/device, no cross-device gather. No specs = a plain jit
# on a mesh too: the table build gives every device the full table (a
# sharded build would save build time but force a cross-device gather
# per verify), slot order runs on one device only (plan_slots), and
# the sharded scan takes its shards as a tuple.
_B, _R = PartitionSpec(BATCH_AXIS), PartitionSpec()
_PROGRAMS = {
    "prepare": (ops_ed.verify_stage_prepare, (_B,) * 3, (_B,) * 8),
    "scan": (ops_ed.verify_stage_scan, (_B,) * 6, (_B,) * 4),
    "finish": (ops_ed.verify_stage_finish, (_B,) * 7, _B),
    "t-prepare-g": (
        ops_ed.verify_stage_prepare_tabled_gathered, (_R, _B, _B, _B), (_B,) * 3,
    ),
    "t-scan": (ops_ed.verify_stage_scan_tabled, (_B, _B, _R, _R, _B), (_B,) * 5),
    "t-finish": (ops_ed.verify_stage_finish_blocked, (_B,) * 7, _B),
    "t-build": (ops_ed.build_valset_tables, None, None),
    "t-materialize": (ops_ed.materialize_sign_bytes, (_R, _B, _B), _B),
    "t-prepare-s": (ops_ed.verify_stage_prepare_tabled_slots, None, None),
    "t-scan-s": (ops_ed.verify_stage_scan_tabled_slots, None, None),
    "t-scan-sh": (ops_ed.verify_stage_scan_tabled_sharded, None, None),
    "t-slab": (ops_ed.table_slab, None, None),
    "t-put": (ops_ed.table_put, None, None),
}
# Skip executable persistence on XLA:CPU (aot_cache.AotJit): the
# materializer is the crash class that motivated splitting it from
# prepare, and is trivial to recompile.
_FRAGILE = frozenset({"t-materialize"})


class VerifierModel:
    def __init__(
        self, mesh=None, block_on_compile: bool = True, logger=None,
        row_counts=None,
    ):
        from tendermint_tpu.crypto.batch import (
            GENERIC_COUNTS, H2D_COUNTS, TABLED_COUNTS, CPUBatchVerifier, RowCounts,
        )
        from tendermint_tpu.utils.watchdog import CircuitBreaker

        self.mesh = mesh
        self.block_on_compile = block_on_compile
        self.logger = logger or get_logger("verifier")
        # rows by where they were VERIFIED (engine_stats device_rows /
        # host_rows): device rows are added once an executable's result
        # has been read back, host rows by the host verifier that
        # serves every cold-bucket and ragged-batch fallback below
        self.row_counts = row_counts if row_counts is not None else RowCounts()
        self._cpu = CPUBatchVerifier(row_counts=self.row_counts)
        self._tabled_counts = TABLED_COUNTS
        self._generic_counts = GENERIC_COUNTS
        self._h2d_counts = H2D_COUNTS
        self._lock = threading.Lock()
        self._entries: Dict[tuple, _Entry] = {}  # see compile_stats
        self._programs: Dict[str, object] = {}  # tag -> AotJit (_program)
        # key tables: the pool of an unmeshed model (sets up to
        # MAX_TABLED_VALSET), and whole-set entries for what the pool
        # does not serve — a mesh (replicated once at build) and sets
        # past MAX_TABLED_VALSET (sharded) — an insertion-ordered LRU
        self.key_pool = _KeyPool(self)
        self._valset_tables: Dict[bytes, _TablesEntry] = {}
        # Table-build failure used to latch `e.failed` FOREVER: one
        # transient device hiccup (OOM during a vote storm, a wedged
        # runtime) downgraded that valset to the generic path until
        # restart. The breaker keeps the fast fail-stop behavior — no
        # retry per verify — but allows a half-open rebuild probe after
        # the cooldown (docs/robustness.md).
        self.tables_breaker = CircuitBreaker("verifier.tables", failure_threshold=1)

    # -- compiled function cache ------------------------------------------

    def _program(self, tag: str):
        """The model's one AotJit for `tag` (_PROGRAMS), made on first
        use: the plain jit on one device; on a mesh the same function
        shard_mapped with the tag's specs (plain where it has none),
        its AOT tag suffixed with the mesh shape."""
        prog = self._programs.get(tag)  # every launch looks its stages up here
        if prog is not None:
            return prog
        with self._lock:
            prog = self._programs.get(tag)
            if prog is None:
                from tendermint_tpu.models.aot_cache import AotJit

                fn, in_specs, out_specs = _PROGRAMS[tag]
                fragile = tag in _FRAGILE
                if self.mesh is None:
                    prog = AotJit(fn, tag, fragile=fragile)
                else:
                    if out_specs is not None:
                        fn = jax.shard_map(
                            fn, mesh=self.mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False,
                        )
                    prog = AotJit(
                        None, f"{tag}-mesh{tuple(self.mesh.shape.values())}",
                        jit_fn=jax.jit(fn), fragile=fragile,
                    )
                self._programs[tag] = prog
            return prog

    def _stages(self):
        """Generic stages 1 and 2. They depend only on input shapes, so
        one wrapper serves every bucket (jit re-specializes per shape
        internally): the dominant scan is traced and compiled once per
        n_pad, not once per msg_len."""
        return self._program("prepare"), self._program("scan")

    def _build(self):
        """The generic verify callable: THREE chained stages (prepare /
        scan / finish) rather than one graph. XLA compile time is
        superlinear in program size — the fused graph compiles in ~220s
        on a v5e, the stages in ~33s total. Intermediates stay
        device-resident between stages, so warm latency is unchanged
        (two extra ~0.1ms dispatches). On a mesh the stages are
        shard_mapped independently and every intermediate is sharded
        over the batch axis, so nothing moves between devices."""
        s1, s2 = self._stages()
        s3 = self._program("finish")

        def fn(pk, mg, sg):
            pre = s1(pk, mg, sg)
            coords = s2(*pre[:6])
            return s3(*coords, sg, pre[6], pre[7])

        return fn

    def _entry(self, n_pad: int, msg_len: int) -> _Entry:
        key = ("verify", n_pad, msg_len)
        with self._lock:
            e = self._entries.get(key)
        if e is None:
            fn = self._build()  # takes the lock itself
            with self._lock:
                e = self._entries.setdefault(key, _Entry(fn))
        return e

    def _warm_entry(self, e: _Entry, n_pad: int, msg_len: int) -> None:
        """Force compilation AND a first full execution by running on
        zeros. The device-to-host read makes the warm-up end where a
        live call ends (results on the host), so whatever a first
        execution pays beyond the compile — program load, the first
        d2h copy — is paid here and not by the first live commit.
        Whether block_until_ready alone would do on an attached chip
        is to be re-measured."""
        t0 = time.perf_counter()
        # zeros from HOST arrays exactly like the live call sites: jit
        # specializes on input layout provenance, so warming with
        # device-native jnp.zeros compiles an executable the live
        # host-transferred inputs then miss (observed: a second ~11s
        # compile on the first real call after warmup)
        np.asarray(
            e.fn(*(
                jnp.asarray(np.zeros((n_pad, w), dtype=np.uint8))
                for w in (32, msg_len, 64)
            ))
        )
        e.compile_s = time.perf_counter() - t0
        e.ready = True
        self.logger.info(
            "verifier bucket compiled",
            kind="verify", rows=n_pad, msg_len=msg_len,
            seconds=round(e.compile_s, 2),
        )

    def _claim_compile(self, e: _Entry) -> bool:
        """Atomically claim the right to compile an entry (warmup and
        live calls race for the same buckets)."""
        with self._lock:
            if e.compiling or e.ready:
                return False
            e.compiling = True
            return True

    def _compile_async(self, e: _Entry, n_pad: int, msg_len: int) -> None:
        if not self._claim_compile(e):
            return

        def work():
            try:
                self._warm_entry(e, n_pad, msg_len)
            except Exception as ex:  # pragma: no cover - defensive
                self.logger.error("background compile failed", err=repr(ex))
            finally:
                e.compiling = False

        t = threading.Thread(target=work, daemon=True, name=f"compile-verify-{n_pad}")
        _track_compile_thread(t)
        t.start()

    def _get_fn(self, n_pad: int, msg_len: int):
        """Returns the compiled callable, or None when non-blocking and
        the bucket is still cold (background compile kicked off)."""
        e = self._entry(n_pad, msg_len)
        if e.ready:
            return e.fn
        if self.block_on_compile:
            e.ready = True  # first call compiles inline
            return e.fn
        self._compile_async(e, n_pad, msg_len)
        return None

    # -- padding ----------------------------------------------------------

    def _pad_multiple(self) -> int:
        if self.mesh is not None:
            return int(np.prod(list(self.mesh.shape.values())))
        return 1

    def _window_size(self, cap: int) -> int:
        """Largest streaming window <= cap that the mesh divides (the
        shard_map batch axis must split evenly across devices)."""
        mult = self._pad_multiple()
        return max((cap // mult) * mult, mult)

    def _kernel_rows(self, n_pad: int) -> int:
        """n_pad where a generic launch of that many rows has the kernel
        form of its three stages (each device's rows under a mesh), else
        0: stage 2's generic_scan and the chains of stages 1 and 3 follow
        the one rule."""
        per_device = n_pad // self._pad_multiple()
        return n_pad if ops_stage2.kernel_form(per_device, jax.default_backend()) else 0

    def _pad(self, arr: np.ndarray, n_pad: int) -> np.ndarray:
        n = arr.shape[0]
        if n == n_pad:
            return arr
        pad = np.zeros((n_pad - n,) + arr.shape[1:], dtype=arr.dtype)
        return np.concatenate([arr, pad], axis=0)

    def _to_device(self, *arrays: np.ndarray) -> tuple:
        """Host arrays of a served launch on the device, their bytes
        counted (crypto/batch.H2D_COUNTS)."""
        self._h2d_counts.add(bytes=sum(int(a.nbytes) for a in arrays))
        return tuple(jnp.asarray(a) for a in arrays)

    # -- public API --------------------------------------------------------

    def verify(self, pubkeys, msgs, sigs, msg_lens=None) -> np.ndarray:
        """(N,32) u8, (N,L) u8, (N,64) u8 -> (N,) bool numpy.

        Ragged batches (msg_lens set with differing lengths) fall back to
        the host path -- the consensus hot paths are always uniform.

        Batches beyond MAX_DEVICE_ROWS stream through the largest bucket
        as back-to-back windows with ONE final sync: a single giant
        program is SLOWER (its (N,20,20) scan intermediates blow past
        what XLA can keep fused at ~500k rows — measured 0.76x vs
        per-height calls on the eval-3 full config) and each new giant
        shape would pay its own compile.
        """
        n = int(pubkeys.shape[0])
        if n == 0:
            return np.zeros(0, dtype=bool)
        if msg_lens is not None and len(set(int(x) for x in msg_lens)) > 1:
            return self._cpu.verify_batch(pubkeys, msgs, sigs, msg_lens)
        msg_len = int(msgs.shape[1]) if msg_lens is None else int(msg_lens[0])
        msgs = np.asarray(msgs)[:, :msg_len]
        if n > MAX_DEVICE_ROWS:
            return self._verify_windowed(pubkeys, msgs, sigs, msg_len)
        n_pad = _bucket(n, self._pad_multiple())
        fn = self._get_fn(n_pad, msg_len)
        if fn is None:  # cold bucket, non-blocking: host fallback
            return self._cpu.verify_batch(pubkeys, msgs, sigs)
        faults.maybe("device.verify")
        with span("generic.launch", rows=n, bucket=n_pad):
            with span("launch.stage"):
                args = self._to_device(*(
                    self._pad(np.asarray(a, dtype=np.uint8), n_pad) for a in (pubkeys, msgs, sigs)
                ))
            with span("launch.dispatch"):
                ok = fn(*args)
        with span("launch.readback"):
            out = np.asarray(ok)[:n]
        self.row_counts.add(device=n)
        self._generic_counts.add(
            rows=n, pad_rows=n_pad - n, launches=1, kernel_rows=self._kernel_rows(n_pad)
        )
        return out

    def _verify_windowed(self, pubkeys, msgs, sigs, msg_len: int) -> np.ndarray:
        """Stream >MAX_DEVICE_ROWS batches as in-flight full windows; the
        sub-window tail reuses the direct bucketed path (a tail of 1 row
        must not pay a full-window execution)."""
        n = int(pubkeys.shape[0])
        window = self._window_size(MAX_DEVICE_ROWS)
        fn = self._get_fn(window, msg_len)
        if fn is None:  # cold bucket, non-blocking: host fallback
            return self._cpu.verify_batch(pubkeys, msgs, sigs)
        pk = np.asarray(pubkeys, dtype=np.uint8)
        mg = np.asarray(msgs, dtype=np.uint8)
        sg = np.asarray(sigs, dtype=np.uint8)
        # every full window in flight, exactly `window` rows a slice
        tail_start = (n // window) * window
        outs = []
        for off in range(0, tail_start, window):
            with span("generic.launch", rows=window, bucket=window):
                with span("launch.stage"):
                    args = self._to_device(*(a[off : off + window] for a in (pk, mg, sg)))
                with span("launch.dispatch"):
                    outs.append(fn(*args))
        with span("launch.readback"):
            parts = [np.asarray(o) for o in outs]
        self.row_counts.add(device=tail_start)
        self._generic_counts.add(
            rows=tail_start, windows=len(outs), launches=len(outs),
            kernel_rows=self._kernel_rows(window) * len(outs),
        )
        if tail_start < n:
            parts.append(self.verify(pk[tail_start:], mg[tail_start:], sg[tail_start:]))
        return np.concatenate(parts)

    # -- per-valset cached tables ------------------------------------------
    #
    # Validator pubkeys are stable across heights (the reference
    # re-verifies the same keys every block, types/validator_set.go:641).
    # build_valset_tables hoists everything key-dependent out of the
    # per-commit program: decompression, the per-row table build and 240
    # of 256 shared doublings (256 - 4*SPLIT_W). verify_rows_cached is
    # the resulting fast path: challenge hash + 16-doubling (4*SPLIT_W)
    # split scan + blocked-inversion encode, with each row's table read
    # in place (slot order, plan_slots) or gathered by validator index
    # on device.

    def _table_stage_fns(self):
        """Gathered tabled stages 1-3 and the table build."""
        return tuple(
            self._program(t) for t in ("t-prepare-g", "t-scan", "t-finish", "t-build")
        )

    def _materialize_fn(self):
        """The tiny templated-message materializer (one program per
        (t_pad, n_pad) shape): its u8 output feeds the SAME compiled
        prepare executables the materialized path uses — see
        ops_ed.materialize_sign_bytes for why this is a separate
        program."""
        return self._program("t-materialize")

    def _slot_stage_fns(self):
        """Single-device tabled stages 1 and 2 in SLOT ORDER (plan_slots):
        C whole commits of V slots a launch, the set's pubkey matrix and
        key tables consumed as they lie — no index, no per-row gather
        of ~30 KB of table (the gathered scan's copy is 315 MB a
        10,240-row launch, 16.7 of its 39.4 ms on a v5e). C = 1 is a full
        commit's shape."""
        return self._program("t-prepare-s"), self._program("t-scan-s")

    def _build_tables(self, e: _TablesEntry, key: bytes, pubkeys: np.ndarray) -> None:
        from tendermint_tpu.models import aot_cache

        faults.maybe("device.tables")
        t0 = time.perf_counter()
        v = pubkeys.shape[0]
        v_pad = _bucket(v, 1)
        pubkeys = np.ascontiguousarray(pubkeys, dtype=np.uint8)
        pk_pad = self._pad(pubkeys, v_pad)
        # resolve the cache dir NOW: on the async-build path the env
        # var may point somewhere else by the time the thread saves
        tables_dir = aot_cache.tables_dir()
        # Sets past the single-table bound keep their tables as equal
        # MAX_TABLED_VALSET-row shards, each built by its own dispatch
        # (the build's HBM bound) and gathered bounded by the sharded
        # scan instead of one pathological huge-table gather.
        sharded = v_pad > MAX_TABLED_VALSET
        shard_rows = MAX_TABLED_VALSET if sharded else v_pad
        found = aot_cache.load_tables(pubkeys)
        loaded = None
        if found is not None and found[0].all():
            # rows of padding are never a row's: zeros do
            loaded = self._pad(found[1], v_pad), self._pad(found[2], v_pad)
        tables = shards = None
        if loaded is not None:
            # restart path: pure data from disk, no build program at all
            if sharded:
                shards = tuple(
                    jnp.asarray(loaded[0][off : off + shard_rows])
                    for off in range(0, v_pad, shard_rows)
                )
            else:
                tables = jnp.asarray(loaded[0])
            a_ok = jnp.asarray(loaded[1])
            e.source = "disk"
        else:
            build = self._program("t-build")
            if sharded:
                parts = [
                    build(jnp.asarray(pk_pad[off : off + shard_rows]))
                    for off in range(0, v_pad, shard_rows)
                ]
                shards = tuple(t for t, _ in parts)
                a_ok = jnp.concatenate([a for _, a in parts])
            else:
                tables, a_ok = build(jnp.asarray(pk_pad))
            e.source = "build"
        # device-resident pubkey matrix for the gathered stage-1: rows
        # gather by validator index ON DEVICE, so per-commit H2D carries
        # idx (4B/row) instead of a host-fancy-indexed pubkey copy
        # (32B/row)
        pk_dev = jnp.asarray(pk_pad)
        if self.mesh is not None:
            # replicate ONCE at build: the shard_map scan consumes the
            # tables with a replicated spec, and leaving them committed
            # to one device would re-broadcast ~30KB/validator to every
            # device on every verify dispatch (sharded entries only
            # reach a mesh when the set fits sharded_valset_cap())
            rep = NamedSharding(self.mesh, _R)
            if sharded:
                shards = tuple(jax.device_put(s, rep) for s in shards)
            else:
                tables = jax.device_put(tables, rep)
            a_ok = jax.device_put(a_ok, rep)
            pk_dev = jax.device_put(pk_dev, rep)
        if sharded:
            shards[-1].block_until_ready()
            e.shards = shards
        else:
            tables.block_until_ready()
        e.tables, e.a_ok, e.pk_dev = tables, a_ok, pk_dev
        e.build_s = time.perf_counter() - t0
        e.ready = True
        self.tables_breaker.record_success()
        self.logger.info(
            "valset tables ready",
            validators=v, key=key[:8].hex(), source=e.source,
            shards=len(shards) if sharded else 1,
            seconds=round(e.build_s, 2),
        )
        if e.source == "build":
            flat = (
                np.concatenate([np.asarray(s) for s in shards])
                if sharded
                else np.asarray(tables)
            )
            aot_cache.save_tables(
                pubkeys, flat[:v], np.asarray(a_ok)[:v], dir_path=tables_dir
            )

    def sharded_valset_cap(self) -> int:
        """Largest valset the sharded-tables path serves on THIS model.

        MAX_SHARDED_VALSET is the single-device HBM bound; on an
        N-device mesh the shard tables replicate to every chip while
        each chip also works its 1/N row shard, so the per-device
        table budget divides by N. The degenerate 1-device mesh gets
        exactly the single-device cap — the unmeshed path, pinned
        bit-identical."""
        if self.mesh is None:
            return MAX_SHARDED_VALSET
        n_dev = int(np.prod(list(self.mesh.shape.values())))
        return MAX_SHARDED_VALSET // max(1, n_dev)

    def _tables_pending(self, key: bytes, pubkeys: np.ndarray) -> bool:
        """Whether a background build that could serve these keys runs."""
        if self.mesh is None and int(pubkeys.shape[0]) <= MAX_TABLED_VALSET:
            return self.key_pool.building
        with self._lock:
            e = self._valset_tables.get(key)
        return e is not None and e.building

    def _tables_entry(self, key: bytes, pubkeys: np.ndarray) -> Optional[_TablesEntry]:
        """The ready table operand for the keys `pubkeys`, named by
        `key`, or None when still cold (async build kicked off in
        non-blocking mode) or the set is too large for the tabled path.
        One device and a set up to MAX_TABLED_VALSET: the key pool's
        columns of these keys (_KeyPool.view). A mesh, or a larger set:
        a whole-set entry — past MAX_TABLED_VALSET the tables go
        SHARDED, past sharded_valset_cap() (the per-device HBM bound
        — MAX_SHARDED_VALSET divided by the mesh size) the generic
        pipeline takes over. The entries together stay under
        MAX_TABLE_BYTES, least recently used first out."""
        from tendermint_tpu.crypto.batch import GroupKeys

        v = int(pubkeys.shape[0])
        if self.mesh is None and v <= MAX_TABLED_VALSET:
            return self.key_pool.view(GroupKeys(key, pubkeys))
        if v > MAX_TABLED_VALSET and v > self.sharded_valset_cap():
            return None
        with self._lock:
            e = self._valset_tables.get(key)
            if e is not None:
                # true LRU: refresh recency on every hit, else two cold
                # lookups (e.g. historical sets for evidence) would
                # evict the hot current set
                self._valset_tables.pop(key)
                self._valset_tables[key] = e
            else:
                e = _TablesEntry(v)
                self._valset_tables[key] = e
                held = sum(_bucket(x.v, 1) for x in self._valset_tables.values())
                for old in list(self._valset_tables):
                    if held * TABLE_KEY_BYTES <= MAX_TABLE_BYTES or old == key:
                        break
                    held -= _bucket(self._valset_tables.pop(old).v, 1)
        if e.ready:
            return e
        probed = False  # did WE take the half-open probe token below?
        if e.failed:
            # failed build: circuit breaker instead of a permanent
            # latch — fail-stop until the cooldown, then ONE half-open
            # probe clears the latch and retries the build; everyone
            # else keeps the generic path meanwhile
            if not self.tables_breaker.allow():
                return None
            probed = True
            e.failed = False
        if self.block_on_compile:
            with self._lock:
                if e.building:
                    if probed:
                        # another thread mid-build records its own
                        # verdict; return OUR token so the breaker
                        # can't latch half-open (only the holder may
                        # release — flipping someone else's in-flight
                        # probe would break the single-probe gate)
                        self.tables_breaker.release_probe()
                    return None
                e.building = True
            try:
                if not e.ready:
                    self._build_tables(e, key, pubkeys)
                elif probed:
                    self.tables_breaker.release_probe()  # raced ready: no build, no verdict
            except Exception as ex:
                # the contract is None-means-fallback, never an exception
                # escaping into commit verification
                e.failed = True
                self.tables_breaker.record_failure()
                self.logger.error("valset table build failed", err=repr(ex))
                return None
            finally:
                e.building = False
            return e
        with self._lock:
            if e.building or e.ready:
                if probed:
                    # no build attempt by US: in-flight builds record
                    # their own verdict, a raced-ready entry records
                    # nothing — either way return the token we hold
                    self.tables_breaker.release_probe()
                return e if e.ready else None
            e.building = True
        pk_copy = np.array(pubkeys, dtype=np.uint8, copy=True)

        def work():
            try:
                self._build_tables(e, key, pk_copy)
            except Exception as ex:  # pragma: no cover - defensive
                # fail-stop (don't retry a doomed build per verify), but
                # breaker-gated: a half-open probe retries after cooldown
                e.failed = True
                self.tables_breaker.record_failure()
                self.logger.error("valset table build failed", err=repr(ex))
            finally:
                e.building = False

        t = threading.Thread(target=work, daemon=True, name="valset-tables")
        _track_compile_thread(t)
        t.start()
        return None

    def verify_rows_cached(
        self, valset_key: bytes, all_pubkeys, row_idx, msgs, sigs
    ) -> Optional[np.ndarray]:
        """Verify rows whose pubkeys are all_pubkeys[row_idx] against the
        per-valset cached tables (single device, or a mesh: rows shard
        over the batch axis, tables replicate). Returns (N,) bool, or
        None when the cached path is unavailable (tables or a bucket
        cold in non-blocking mode) — callers fall back to verify().

        row_idx MUST index into all_pubkeys; rows are independent, so
        duplicate indices are fine (the trusting path may produce them).
        Its shape alone picks the table operand: whole commits in
        validator order go to their validators' slots (plan_slots),
        anything else gathers.
        """
        src = ("mat", np.asarray(msgs, dtype=np.uint8))
        return self._rows_cached_arrays(valset_key, all_pubkeys, row_idx, src, sigs)

    def verify_rows_cached_templated(
        self, valset_key: bytes, all_pubkeys, row_idx,
        templates=None, tmpl_idx=None, ts8=None, sigs=None,
    ) -> Optional[np.ndarray]:
        """verify_rows_cached with TEMPLATED messages: row r's sign
        bytes are templates[tmpl_idx[r]] with ts8[r] (8 bytes,
        big-endian i64) spliced at the timestamp offset — materialized
        ON DEVICE (ops_ed.materialize_sign_bytes). Per-row H2D drops
        from ~228 B to ~80 B; what that buys on an attached chip is to
        be re-measured.

        templates (T, 160) u8 — T is padded up to a small bucket so
        cross-height batches (one template pair per height) don't
        compile per T. Same None-means-fallback contract.

        row_idx may be a crypto/batch.RowGroups (whole commits, the
        other row arguments None): still one call and one sync, the
        verdicts of every row in row order — but each slot-order
        launch's worth of commits is taken from the source only after
        the launch before it is dispatched, so the caller's seam packs
        it while the device runs (_group_pieces). Each group brings the
        keys its rows index (RowGroups.keys; valset_key and all_pubkeys
        where it gives None), so a chain over many sets stays one call.
        None, at any point, leaves the source to its owner to finish."""
        from tendermint_tpu.crypto.batch import GroupKeys, RowGroups

        if not isinstance(row_idx, RowGroups):
            src = self._tpl_src(templates, tmpl_idx, ts8)
            return self._rows_cached_arrays(valset_key, all_pubkeys, row_idx, src, sigs)
        own = GroupKeys(valset_key, np.asarray(all_pubkeys, dtype=np.uint8))
        return self._rows_cached_core(self._group_pieces(own, row_idx))

    def _slab(self, tables, a_ok, pk, cols: np.ndarray):
        """Columns ``cols`` of the key pool's arrays, gathered on the
        device (ops_ed.table_slab)."""
        with span("tables.slab", columns=int(cols.shape[0])):
            return self._program("t-slab")(tables, a_ok, pk, *self._to_device(cols))

    def table_bytes(self) -> int:
        """Device bytes of key tables this model holds: the pool's
        columns and the whole-set entries."""
        with self._lock:
            sets = sum(_bucket(e.v, 1) for e in self._valset_tables.values() if e.ready)
        return self.key_pool.nbytes() + sets * TABLE_KEY_BYTES

    # -- shared cached-path machinery (mat | tpl message sources) ---------

    @staticmethod
    def _table_rows(e: _TablesEntry) -> int:
        if e.shards is not None:
            return sum(int(s.shape[0]) for s in e.shards)
        return int(e.tables.shape[0])

    def _slot_table_rows(self, e: _TablesEntry) -> int:
        """V, the rows of the one table slot order reads in place; 0
        under a mesh or with sharded tables, which gather (plan_slots
        declines)."""
        if self.mesh is None and e.shards is None:
            return int(e.tables.shape[0])
        return 0

    def _scan_rows(self, e: _TablesEntry, sd, kd, idx_dev):
        """Dispatch the right stage-2 flavor: single table (gathered)
        or sharded per-shard bounded gathers."""
        if e.shards is not None:
            return self._program("t-scan-sh")(sd, kd, e.a_ok, idx_dev, e.shards)
        return self._program("t-scan")(sd, kd, e.tables, e.a_ok, idx_dev)

    @staticmethod
    def _src_msg_len(src) -> int:
        return int(src[1].shape[1])

    @staticmethod
    def _src_tpl_pad(src) -> int:
        """Padded template count (0 for the mat source): bounds
        recompiles across cross-height batches of varying heights."""
        if src[0] == "mat":
            return 0
        t = int(src[1].shape[0])
        for b in _TPL_BUCKETS:
            if t <= b:
                return b
        return pad_to_multiple(t, _TPL_BUCKETS[-1])

    @staticmethod
    def _src_slice(src, sl: slice):
        """Row-slice a message source (templates are shared, per-row
        columns slice)."""
        if src[0] == "mat":
            return ("mat", src[1][sl])
        return ("tpl", src[1], src[2][sl], src[3][sl])

    @staticmethod
    def _src_to_slots(src, at: np.ndarray, n_slots: int):
        """The source's rows scattered to slots `at` of a zeroed
        n_slots-row source (templates are shared)."""
        if src[0] == "mat":
            return ("mat", _to_slots(src[1], at, n_slots))
        return (
            "tpl", src[1], _to_slots(src[2], at, n_slots), _to_slots(src[3], at, n_slots),
        )

    def _src_rows(self, src, n_pad: int) -> tuple:
        """The source's host arrays for one launch, rows padded to n_pad:
        (messages,) or (templates, tmpl_idx, ts8), the templates padded
        to their bucket."""
        if src[0] == "mat":
            return (self._pad(src[1], n_pad),)
        _, templates, tmpl_idx, ts8 = src
        return (
            self._pad(templates, self._src_tpl_pad(src)),
            self._pad(tmpl_idx, n_pad), self._pad(ts8, n_pad),
        )

    def _messages(self, msg_dev: tuple):
        """The (n_pad, W) u8 messages on the device from _src_rows'
        arrays there. Both sources converge on the SAME prepare
        executables: the templated source materializes its messages on
        device first (one tiny extra dispatch; the H2D saving is the
        point)."""
        if len(msg_dev) == 1:
            return msg_dev[0]
        return self._materialize_fn()(*msg_dev)

    def _gathered_launch(self, e: _TablesEntry, msg_dev: tuple, idx_dev, sg_dev):
        """Stages 1-3 of the gathered pair over one padded launch;
        returns the device verdicts."""
        sd, kd, s_ok = self._program("t-prepare-g")(
            e.pk_dev, idx_dev, self._messages(msg_dev), sg_dev
        )
        px, py, pz, pt, a_ok = self._scan_rows(e, sd, kd, idx_dev)
        return self._program("t-finish")(px, py, pz, pt, sg_dev, a_ok, s_ok)

    def _slot_launch(self, e: _TablesEntry, msg_dev: tuple, sg_dev):
        """Stages 1-3 in slot order over one launch of C*V slots
        (messages and sg_dev already scattered to them)."""
        s1, s2 = self._slot_stage_fns()
        sd, kd, s_ok = s1(e.pk_dev, self._messages(msg_dev), sg_dev)
        px, py, pz, pt, a_ok = s2(sd, kd, e.tables, e.a_ok)
        return self._program("t-finish")(px, py, pz, pt, sg_dev, a_ok, s_ok)

    @staticmethod
    def _tpl_src(templates, tmpl_idx, ts8):
        return (
            "tpl",
            np.asarray(templates, dtype=np.uint8),
            np.asarray(tmpl_idx, dtype=np.int32),
            np.asarray(ts8, dtype=np.uint8),
        )

    def _rows_cached_arrays(
        self, valset_key: bytes, all_pubkeys, row_idx, src, sigs
    ) -> Optional[np.ndarray]:
        """A batch handed over as arrays: one piece."""
        if len(row_idx) == 0:
            return np.zeros(0, dtype=bool)
        with span("launch.plan"):
            e = self._tables_entry(valset_key, np.asarray(all_pubkeys, dtype=np.uint8))
        if e is None:
            return None
        piece = (
            e, np.asarray(row_idx, dtype=np.int32), src, np.asarray(sigs, dtype=np.uint8), (),
        )
        return self._rows_cached_core((piece,))

    def _group_pieces(self, own, groups):
        """A RowGroups as pieces for _rows_cached_core: the whole
        commits one slot-order launch holds, each group taken — packed
        by the seam, on this thread — when the loop comes back for it,
        the launches before it dispatched, and each with the table
        operand of its own keys (``own`` where the source names none).
        A group is MAX_DEVICE_ROWS // V commits, V the bucket of its
        next commit's set (the last group is rounded by plan_slots as
        any batch's tail is), halved while the distinct keys of its
        commits' sets together pass that bucket — a wider operand would
        launch every commit of the group over that many more slots: a
        chain that changes one key a height launches the shapes of a
        chain that changes none. Where slot order never applies (a mesh
        and sharded tables gather) everything at once: an eager batch.
        None where the source declines or a group's tables are cold.

        The first piece names the shape of the last group's launch
        ahead of it, by arithmetic from the commits left (C the power
        of two over the rest, a template pair a commit): a chain
        shorter or longer than the last has another tail shape, and one
        that is cold is found before anything is dispatched, not after
        the launches before it ran."""

        def keys_of(commits: int):
            return groups.keys(commits) or own

        ahead_due = True
        while groups.left:
            with span("launch.plan"):
                v1 = _bucket(int(keys_of(1).pubkeys.shape[0]), 1)
                slotted = self.mesh is None and v1 <= MAX_DEVICE_ROWS
                per = _commits_per_launch(v1) if slotted else groups.left
                keys = keys_of(per)
                while slotted and per > 1 and _bucket(int(keys.pubkeys.shape[0]), 1) > v1:
                    per //= 2
                    keys = keys_of(per)
                e = self._tables_entry(keys.digest, keys.pubkeys)
            if e is None:
                yield None
                return
            full, rest = divmod(groups.left, per)
            got = groups.take(per)
            if got is None:
                yield None
                return
            idx, templates, tmpl_idx, ts8, sg = got
            src = self._tpl_src(templates, tmpl_idx, ts8)
            ahead = ()
            v = self._slot_table_rows(e)
            if ahead_due and full and rest and v:
                tail = ("tpl", np.empty((2 * rest, self._src_msg_len(src)), dtype=np.uint8))
                ahead = ((_tail_commits(rest, per) * v, tail, True),)
            ahead_due = False
            yield (
                e, np.asarray(idx, dtype=np.int32), src, np.asarray(sg, dtype=np.uint8), ahead,
            )

    def _plan_launches(self, e: _TablesEntry, idx: np.ndarray) -> list:
        """(first row, end row, padded rows, the rows' slots) a launch
        of a piece. Whole commits of a set they mostly fill go to
        their validators' slots, C*V a launch, and stage 2 reads the
        tables in place (plan_slots). Anything else gathers (slots
        None): one bucketed launch, or past MAX_DEVICE_ROWS
        (cross-height streaming, eval 3) full windows and a bucketed
        tail."""
        plan = plan_slots(idx, self._slot_table_rows(e))
        if plan is not None:
            v = int(e.tables.shape[0])
            bases = np.cumsum([0] + [c * v for _, _, c in plan.launches])
            return [
                (lo, hi, c * v, plan.slots[lo:hi] - base)
                for (lo, hi, c), base in zip(plan.launches, bases)
            ]
        n = int(idx.shape[0])
        window = self._window_size(MAX_DEVICE_ROWS)
        launches = [
            (lo, lo + window, window, None) for lo in range(0, n - window + 1, window)
        ]
        if n % window:
            tail = _bucket(n % window, self._pad_multiple())
            launches.append((n - n % window, n, tail, None))
        return launches

    def _rows_cached_core(self, pieces) -> Optional[np.ndarray]:
        """Verify a batch that comes in pieces — (table operand,
        row_idx into it, src, sigs, shapes ahead) each: the one piece
        of an array call, or a RowGroups' groups (_group_pieces), each
        pulled only after the launches of the one before it are
        dispatched. Every launch in
        flight, one sync, the verdicts in row order; only the real rows
        count as device rows. The per-window decompress and table build
        the generic path pays are already hoisted into the cached
        tables.

        None means fallback, at any point and never an exception into
        commit verification: a shape cold in non-blocking mode, a
        source that declines, a transient device or compile failure.
        Every shape of a piece, and every shape it names ahead (the
        last group's), must be warm before any of its launches is
        dispatched: a cold tail found after the windows ran would throw
        that device work away. NOT latched as a failed build — the tables
        themselves are fine and the next call may succeed. What was
        dispatched is dropped uncounted."""
        outs = []  # (device verdicts, the rows' places in them) a launch
        cold = {}  # id -> entry this call compiles inline
        rows = slot_rows = slots = kernel_slots = 0
        t0 = None
        try:
            for piece in pieces:
                if piece is None:
                    return None
                with span("launch.plan"):
                    e, idx, src, sg, ahead = piece
                    launches = self._plan_launches(e, idx)
                    shapes = [(pad, src, at is not None) for _, _, pad, at in launches]
                    if not self.block_on_compile:
                        shapes.extend(ahead)
                    # the entry key includes the table's padded row count
                    # (_tabled_bucket_entry): a valset that grows past its
                    # pad bucket must re-warm, not run a synchronous
                    # compile on the live path
                    fresh = [
                        (ent, pad, of, in_slots)
                        for pad, of, in_slots in shapes
                        for ent in [self._tabled_bucket_entry(e, pad, of, slots=in_slots)]
                        if not ent.ready
                    ]
                if fresh and not self.block_on_compile:
                    for ent, pad, of, in_slots in fresh:
                        self._compile_tabled_async(ent, e, pad, of, slots=in_slots)
                    return None
                cold.update((id(ent), ent) for ent, *_ in fresh)
                if t0 is None:
                    faults.maybe("device.verify")
                    t0 = time.perf_counter()
                for lo, hi, pad, at in launches:
                    rows_src = self._src_slice(src, slice(lo, hi))
                    outs.append(self._launch(e, idx[lo:hi], rows_src, sg[lo:hi], pad, at))
                    if at is not None:
                        slot_rows += hi - lo
                        slots += pad
                        if ops_stage2.kernel_form(self._slot_table_rows(e), jax.default_backend()):
                            kernel_slots += pad
                rows += int(idx.shape[0])
            with span("launch.readback"):
                got = [np.asarray(o) for o, _ in outs]
            out = (
                np.concatenate([ok[take] for ok, (_, take) in zip(got, outs)])
                if outs else np.zeros(0, dtype=bool)
            )
            self.row_counts.add(device=rows)
            self._tabled_counts.add(
                slot_rows=slot_rows, slot_pad=slots - slot_rows,
                gathered_rows=rows - slot_rows, kernel_slots=kernel_slots,
            )
        except faults.InjectedFault:
            raise
        except Exception as ex:
            self.logger.error(
                "tabled verify failed (falling back)", rows=rows, err=repr(ex)[:200]
            )
            return None
        for ent in cold.values():
            ent.compile_s = time.perf_counter() - t0
            ent.ready = True
        return out

    def _launch(self, e: _TablesEntry, idx, src, sg, n_pad: int, at):
        """Dispatch one launch over the rows given: in slot order when
        ``at`` gives their slots of the n_pad = C*V, else gathered and
        padded to n_pad. (device verdicts, the rows' places in them)."""
        with span("launch.stage"):
            if at is None:
                idx_dev, sg_dev = self._to_device(self._pad(idx, n_pad), self._pad(sg, n_pad))
            else:
                src = self._src_to_slots(src, at, n_pad)
                sg_dev, = self._to_device(_to_slots(sg, at, n_pad))
            msg_dev = self._to_device(*self._src_rows(src, n_pad))
        with span("launch.dispatch"):
            if at is None:
                return self._gathered_launch(e, msg_dev, idx_dev, sg_dev), slice(0, int(idx.shape[0]))
            return self._slot_launch(e, msg_dev, sg_dev), at

    def _tabled_bucket_entry(
        self, e: _TablesEntry, n_pad: int, src, slots: bool = False
    ) -> _Entry:
        kind = ("slots" if slots else "tabled") + ("" if src[0] == "mat" else "-tpl")
        n_shards = len(e.shards) if e.shards is not None else 1
        key = (
            kind, n_pad, self._src_msg_len(src), self._src_tpl_pad(src),
            self._table_rows(e), n_shards,
        )
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                ent = _Entry(None)
                self._entries[key] = ent
            return ent

    def register_valset(self, valset_key: bytes, all_pubkeys, msg_len: int = 160) -> None:
        """Pre-build the cached tables for a valset and warm its tabled
        shapes — a full commit in slot order and the gathered pair at
        the set's bucket, in BOTH message flavors: the live commit path sends
        templated messages, while vote ingest and fallbacks still send
        materialized ones (node-start path: a restarting validator's
        FIRST commit should already ride the tabled pipeline, not wait
        for a lazy build on the live path). Non-blocking when the model
        is; safe to call for an already-registered set."""
        pk = np.asarray(all_pubkeys, dtype=np.uint8)
        e = self._tables_entry(valset_key, pk)  # non-blocking: kicks the async build
        if e is None and (self.block_on_compile or not self._tables_pending(valset_key, pk)):
            return
        n = int(pk.shape[0])
        # oversized sets dispatch as <=MAX_DEVICE_ROWS windows; warming
        # a bigger bucket would compile a shape no call ever uses
        n_pad = _bucket(min(n, MAX_DEVICE_ROWS), self._pad_multiple())
        warm_srcs = (
            ("mat", np.zeros((n, msg_len), dtype=np.uint8)),
            (
                "tpl",
                np.zeros((2, msg_len), dtype=np.uint8),
                np.zeros(n, dtype=np.int32),
                np.zeros((n, 8), dtype=np.uint8),
            ),
        )

        def warm_bucket(e):
            # what a full commit of the set takes (slot order, one
            # commit a launch), and the gathered pair at the set's
            # bucket for vote drains and lookups out of order
            v = self._slot_table_rows(e)
            shapes = ([(v, True)] if 0 < v <= MAX_DEVICE_ROWS else []) + [(n_pad, False)]
            for src in warm_srcs:
                for rows, slots in shapes:
                    ent = self._tabled_bucket_entry(e, rows, src, slots=slots)
                    if not ent.ready:
                        self._compile_tabled_async(ent, e, rows, src, slots=slots)

        if e is not None:
            warm_bucket(e)
            return

        def warm_when_built():
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline:
                pending = self._tables_pending(valset_key, pk)
                e = self._tables_entry(valset_key, pk)
                if e is not None:
                    warm_bucket(e)
                    return
                if not pending:
                    return  # build failed (logged where it failed): stop polling
                time.sleep(0.25)

        t = threading.Thread(target=warm_when_built, daemon=True, name="tabled-warmup")
        _track_compile_thread(t)
        t.start()

    def _src_zero(self, src, n_pad: int):
        """Zero-filled source with src's static shape signature, padded
        to n_pad rows — compiles the same executables the live call
        will hit."""
        if src[0] == "mat":
            return ("mat", np.zeros((n_pad, self._src_msg_len(src)), dtype=np.uint8))
        return (
            "tpl",
            np.zeros((self._src_tpl_pad(src), self._src_msg_len(src)), dtype=np.uint8),
            np.zeros(n_pad, dtype=np.int32),
            np.zeros((n_pad, 8), dtype=np.uint8),
        )

    def _compile_tabled_async(
        self, ent: _Entry, e: _TablesEntry, n_pad: int, src, slots: bool = False
    ) -> None:
        """Warm one tabled shape in the background, on zeros, to the
        host: the gathered pair at n_pad rows, or (slots) the
        slot-order pair at n_pad = C*V slots."""
        if not self._claim_compile(ent):
            return
        zsrc = self._src_zero(src, n_pad)

        def one_pass():
            t0 = time.perf_counter()
            sg = jnp.asarray(np.zeros((n_pad, 64), dtype=np.uint8))
            msg_dev = tuple(jnp.asarray(a) for a in self._src_rows(zsrc, n_pad))
            if slots:
                ok = self._slot_launch(e, msg_dev, sg)
            else:
                idx = jnp.asarray(np.zeros(n_pad, dtype=np.int32))
                ok = self._gathered_launch(e, msg_dev, idx, sg)
            np.asarray(ok)
            ent.compile_s = time.perf_counter() - t0
            ent.ready = True
            self.logger.info(
                "tabled bucket compiled", rows=n_pad, kind=src[0],
                order="slots" if slots else "gathered",
                msg_len=self._src_msg_len(src),
                seconds=round(ent.compile_s, 2),
            )

        def work():
            # _WARM_SERIAL: one warm body at a time. Two warm threads
            # tracing simultaneously while the live thread dispatches
            # produced flaky trace-corruption errors (KeyError(Var...),
            # phantom shape mismatches) on CPU builds; those same
            # errors then vanished single-threaded — so serialize, and
            # retry once since a poisoned first trace can succeed clean
            # on the second pass.
            try:
                with _WARM_SERIAL:
                    try:
                        one_pass()
                    except Exception as ex:
                        self.logger.info(
                            "tabled warm retrying", err=repr(ex)[:120]
                        )
                        one_pass()
            except Exception as ex:  # pragma: no cover - defensive
                self.logger.error("tabled compile failed", err=repr(ex))
            finally:
                ent.compiling = False

        t = threading.Thread(target=work, daemon=True, name=f"compile-tabled-{n_pad}")
        _track_compile_thread(t)
        t.start()

    # -- warmup ------------------------------------------------------------

    def warmup(self, sizes=(16, 1024), msg_len: int = 160, background: bool = False):
        """Pre-compile the generic buckets (one program chain a bucket)
        so live calls pay no compile.

        ``background=True`` returns immediately; a daemon thread warms
        each bucket in turn (node-start path). Returns the thread (or
        None when synchronous).
        """
        # sizes beyond the window cap stream through the largest bucket
        pads = sorted(
            {_bucket(min(s, MAX_DEVICE_ROWS), self._pad_multiple()) for s in sizes}
        )

        def work():
            for n_pad in pads:
                e = self._entry(n_pad, msg_len)
                if not self._claim_compile(e):
                    continue  # a live call is already compiling it
                try:
                    self._warm_entry(e, n_pad, msg_len)
                except Exception as ex:
                    self.logger.error(
                        "warmup compile failed", kind="verify", rows=n_pad,
                        err=repr(ex),
                    )
                    return
                finally:
                    e.compiling = False

        if background:
            t = threading.Thread(target=work, daemon=True, name="verifier-warmup")
            _track_compile_thread(t)
            t.start()
            return t
        work()
        return None

    def compile_stats(self) -> Dict[tuple, Optional[float]]:
        """Ready entry -> compile seconds (None = inline/unknown). A
        generic bucket's key is ("verify", rows, msg_len); a tabled
        shape's is (kind, rows or slots, msg_len, padded templates,
        table rows, shards) with kind "tabled" or "slots", "-tpl" for
        templated messages (_tabled_bucket_entry)."""
        with self._lock:
            return {k: e.compile_s for k, e in self._entries.items() if e.ready}
