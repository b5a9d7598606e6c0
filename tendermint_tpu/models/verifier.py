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
- the per-valset CACHED-TABLE pipeline (``verify_rows_cached``):
  validator pubkeys are stable across heights, so affine-cached split
  tables of each key (built once per valset digest, LRU of
  MAX_CACHED_VALSETS, device-resident) remove decompression, the
  per-row table build, and 7/8 of the scan doublings from the
  per-commit program. On one device, whole commits of a set they
  mostly fill run in SLOT ORDER (``plan_slots``): each row goes to its
  validator's slot and stage 2 reads the tables where they lie; sparse
  or unordered batches, a mesh and sharded tables GATHER each row's
  table by validator index. Streams past MAX_DEVICE_ROWS as in-flight
  launches; ``register_valset`` pre-builds at node start.

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
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from tendermint_tpu.ops import ed25519 as ops_ed
from tendermint_tpu.parallel import pad_to_multiple
from tendermint_tpu.parallel.mesh import BATCH_AXIS
from tendermint_tpu.utils import faultinject as faults
from tendermint_tpu.utils.jaxenv import enable_compile_cache
from tendermint_tpu.utils.log import get_logger

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


# Per-valset cached tables kept device-resident (LRU): ~30KB/validator
# (SPLITS*8 affine-cached points), so a 10k set is ~315MB of HBM per
# entry. Two entries cover the live pattern (current set + next set
# around a validator-set change).
MAX_CACHED_VALSETS = 2

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
        self.source: Optional[str] = None  # "build" | "disk"


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
        from tendermint_tpu.crypto.batch import TABLED_COUNTS, CPUBatchVerifier, RowCounts
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
        self._lock = threading.Lock()
        self._entries: Dict[tuple, _Entry] = {}  # see compile_stats
        self._programs: Dict[str, object] = {}  # tag -> AotJit (_program)
        self._valset_tables: Dict[bytes, _TablesEntry] = {}  # insertion-ordered LRU
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

    def _pad(self, arr: np.ndarray, n_pad: int) -> np.ndarray:
        n = arr.shape[0]
        if n == n_pad:
            return arr
        pad = np.zeros((n_pad - n,) + arr.shape[1:], dtype=arr.dtype)
        return np.concatenate([arr, pad], axis=0)

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
        ok = fn(
            jnp.asarray(self._pad(np.asarray(pubkeys, dtype=np.uint8), n_pad)),
            jnp.asarray(self._pad(np.asarray(msgs, dtype=np.uint8), n_pad)),
            jnp.asarray(self._pad(np.asarray(sigs, dtype=np.uint8), n_pad)),
        )
        out = np.asarray(ok)[:n]
        self.row_counts.add(device=n)
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
        outs = [
            fn(*(jnp.asarray(a[off : off + window]) for a in (pk, mg, sg)))
            for off in range(0, tail_start, window)
        ]
        parts = [np.asarray(o) for o in outs]
        self.row_counts.add(device=tail_start)
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
        pk_pad = self._pad(np.asarray(pubkeys, dtype=np.uint8), v_pad)
        import hashlib

        pk_digest = hashlib.sha256(pk_pad.tobytes()).digest()
        # resolve the cache dir NOW: on the async-build path the env
        # var may point somewhere else by the time the thread saves
        tables_dir = aot_cache.tables_dir()
        # Sets past the single-table bound keep their tables as equal
        # MAX_TABLED_VALSET-row shards, each built by its own dispatch
        # (the build's HBM bound) and gathered bounded by the sharded
        # scan instead of one pathological huge-table gather.
        sharded = v_pad > MAX_TABLED_VALSET
        shard_rows = MAX_TABLED_VALSET if sharded else v_pad
        loaded = aot_cache.load_tables(key, v_pad, pk_digest)
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
                key, flat, np.asarray(a_ok), pk_digest,
                dir_path=tables_dir,
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

    def _tables_entry(self, key: bytes, pubkeys: np.ndarray) -> Optional[_TablesEntry]:
        """The ready tables entry for `key`, or None when still cold
        (async build kicked off in non-blocking mode) or the set is too
        large for the tabled path: past MAX_TABLED_VALSET the tables go
        SHARDED, past sharded_valset_cap() (the per-device HBM bound
        — MAX_SHARDED_VALSET divided by the mesh size) the generic
        pipeline takes over."""
        v = int(pubkeys.shape[0])
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
                e = _TablesEntry(int(pubkeys.shape[0]))
                self._valset_tables[key] = e
                while len(self._valset_tables) > MAX_CACHED_VALSETS:
                    old = next(iter(self._valset_tables))
                    if old == key:
                        break
                    del self._valset_tables[old]
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

        row_idx may be a crypto/batch.RowGroups (whole commits of the
        set, the other row arguments None): still one call and one
        sync, the verdicts of every row in row order — but each
        slot-order launch's worth of commits is taken from the source
        only after the launch before it is dispatched, so the caller's
        seam packs it while the device runs (_group_pieces). None, at
        any point, leaves the source to its owner to finish."""
        from tendermint_tpu.crypto.batch import RowGroups

        if not isinstance(row_idx, RowGroups):
            src = self._tpl_src(templates, tmpl_idx, ts8)
            return self._rows_cached_arrays(valset_key, all_pubkeys, row_idx, src, sigs)
        e = self._tables_entry(valset_key, np.asarray(all_pubkeys, dtype=np.uint8))
        if e is None:
            return None
        return self._rows_cached_core(e, self._group_pieces(e, row_idx))

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

    def _src_messages(self, src, n_pad: int):
        """The source's (n_pad, W) u8 messages on the device, rows
        padded to n_pad here. Both sources converge on the SAME prepare
        executables: the templated source materializes its messages on
        device first (one tiny extra dispatch; the H2D saving is the
        point)."""
        if src[0] == "mat":
            return jnp.asarray(self._pad(src[1], n_pad))
        _, templates, tmpl_idx, ts8 = src
        return self._materialize_fn()(
            jnp.asarray(self._pad(templates, self._src_tpl_pad(src))),
            jnp.asarray(self._pad(tmpl_idx, n_pad)),
            jnp.asarray(self._pad(ts8, n_pad)),
        )

    def _gathered_launch(self, e: _TablesEntry, src, n_pad: int, idx_dev, sg_dev):
        """Stages 1-3 of the gathered pair over one padded launch;
        returns the device verdicts."""
        sd, kd, s_ok = self._program("t-prepare-g")(
            e.pk_dev, idx_dev, self._src_messages(src, n_pad), sg_dev
        )
        px, py, pz, pt, a_ok = self._scan_rows(e, sd, kd, idx_dev)
        return self._program("t-finish")(px, py, pz, pt, sg_dev, a_ok, s_ok)

    def _slot_launch(self, e: _TablesEntry, src, n_slots: int, sg_dev):
        """Stages 1-3 in slot order over one launch of n_slots = C*V
        slots (src and sg_dev already scattered to them)."""
        s1, s2 = self._slot_stage_fns()
        sd, kd, s_ok = s1(e.pk_dev, self._src_messages(src, n_slots), sg_dev)
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
        e = self._tables_entry(valset_key, np.asarray(all_pubkeys, dtype=np.uint8))
        if e is None:
            return None
        piece = (
            np.asarray(row_idx, dtype=np.int32), src, np.asarray(sigs, dtype=np.uint8), (),
        )
        return self._rows_cached_core(e, (piece,))

    def _group_pieces(self, e: _TablesEntry, groups):
        """A RowGroups as pieces for _rows_cached_core: the whole
        commits one slot-order launch holds (MAX_DEVICE_ROWS // V; the
        last group is rounded by plan_slots as any batch's tail is),
        each taken — packed by the seam, on this thread — when the loop
        comes back for it, the launches before it dispatched. Where
        slot order never applies (a mesh and sharded tables gather)
        everything at once: an eager batch. None where the source
        declines.

        The first piece names the shape of the last group's launch
        ahead of it, by arithmetic from the commits left (C the power
        of two over the rest, a template pair a commit): a chain
        shorter or longer than the last has another tail shape, and one
        that is cold is found before anything is dispatched, not after
        the launches before it ran."""
        v = self._slot_table_rows(e)
        per = _commits_per_launch(v) if 0 < v <= MAX_DEVICE_ROWS else max(1, groups.left)
        full, rest = divmod(groups.left, per)
        while groups.left:
            got = groups.take(per)
            if got is None:
                yield None
                return
            idx, templates, tmpl_idx, ts8, sg = got
            src = self._tpl_src(templates, tmpl_idx, ts8)
            ahead = ()
            if full and rest:
                tail = ("tpl", np.empty((2 * rest, self._src_msg_len(src)), dtype=np.uint8))
                ahead = ((_tail_commits(rest, per) * v, tail, True),)
                rest = 0
            yield (
                np.asarray(idx, dtype=np.int32), src, np.asarray(sg, dtype=np.uint8), ahead,
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

    def _rows_cached_core(self, e: _TablesEntry, pieces) -> Optional[np.ndarray]:
        """Verify a batch that comes in pieces — (row_idx, src, sigs,
        shapes ahead) each: the one piece of an array call, or a
        RowGroups' groups (_group_pieces), each pulled only after the
        launches of the one before it are dispatched. Every launch in
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
        that device work away. NOT latched as e.failed — the tables
        themselves are fine and the next call may succeed. What was
        dispatched is dropped uncounted."""
        outs = []  # (device verdicts, the rows' places in them) a launch
        cold = {}  # id -> entry this call compiles inline
        rows = slot_rows = slots = 0
        t0 = None
        try:
            for piece in pieces:
                if piece is None:
                    return None
                idx, src, sg, ahead = piece
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
                rows += int(idx.shape[0])
            out = (
                np.concatenate([np.asarray(o)[take] for o, take in outs])
                if outs else np.zeros(0, dtype=bool)
            )
            self.row_counts.add(device=rows)
            self._tabled_counts.add(
                slot_rows=slot_rows, slot_pad=slots - slot_rows,
                gathered_rows=rows - slot_rows,
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
        if at is None:
            n = int(idx.shape[0])
            return self._gathered_launch(
                e, src, n_pad,
                jnp.asarray(self._pad(idx, n_pad)), jnp.asarray(self._pad(sg, n_pad)),
            ), slice(0, n)
        return self._slot_launch(
            e, self._src_to_slots(src, at, n_pad), n_pad,
            jnp.asarray(_to_slots(sg, at, n_pad)),
        ), at

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
        if self.block_on_compile:
            e = self._tables_entry(valset_key, pk)
        else:
            self._tables_entry(valset_key, pk)  # kicks the async build
            with self._lock:
                e = self._valset_tables.get(valset_key)
        if e is None:
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

        def warm_bucket():
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

        if e.ready:
            warm_bucket()
            return

        def warm_when_built():
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline:
                if e.ready:
                    warm_bucket()
                    return
                if not e.building:
                    return  # build failed (logged by _build_tables): stop polling
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
            if slots:
                ok = self._slot_launch(e, zsrc, n_pad, sg)
            else:
                idx = jnp.asarray(np.zeros(n_pad, dtype=np.int32))
                ok = self._gathered_launch(e, zsrc, n_pad, idx, sg)
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
