"""BatchVerifier: the device-boundary seam for signature verification.

This interface does not exist in the reference -- v0.33.4 verifies every
signature serially (crypto/ed25519/ed25519.go:151, looped at
types/validator_set.go:641 and types/vote_set.go:201). Per the BASELINE
north star, this seam is where VoteSet.add_vote, ValidatorSet
.verify_commit and the light client drain (pubkey, msg, sig) triples into
batched device calls; the quorum tally is a column sum on the host over
the verdicts that come back.

Providers:
- "cpu": serial loop over host ed25519 (OpenSSL) -- the reference-parity
  baseline and the fallback when no accelerator is present.
- "tpu": vmap'd JAX ed25519 (tendermint_tpu.ops.ed25519), jit-compiled
  once per (batch, msg-len) bucket, sharded over a device mesh when one is
  configured (tendermint_tpu.parallel).

Select via config ``crypto.provider`` or ``set_default_provider``.
"""

from __future__ import annotations

import threading
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np


class RowCounts:
    """Thread-safe (device_rows, host_rows) pair, counted by the code
    that DOES the work: the model adds device rows once a device
    executable's result has been read back, the host verifier adds the
    rows it loops over. One instance is shared by a provider, its
    models and its host verifier, so a cold bucket, an unbuilt table, a
    sub-threshold batch and a failed device call all show up as host
    rows — never as rows merely handed to a provider (the
    engine_stats protocol, models/telemetry.py)."""

    __slots__ = ("_lock", "device", "host")

    def __init__(self):
        self._lock = threading.Lock()
        self.device = 0
        self.host = 0

    def add(self, device: int = 0, host: int = 0) -> None:
        with self._lock:
            self.device += device
            self.host += host

    def snapshot(self) -> Tuple[int, int]:
        with self._lock:
            return self.device, self.host


class NamedCounts:
    """Thread-safe monotonic counts under one prefix, counted where the
    work is done: ``add(name=n, ...)``; ``snapshot()`` gives
    ``{prefix_name: count}`` (engine_stats()["counters"],
    ``tendermint_crypto_*``)."""

    __slots__ = ("_lock", "_prefix", "_n")

    def __init__(self, prefix: str, names):
        self._lock = threading.Lock()
        self._prefix = prefix
        self._n = dict.fromkeys(names, 0)

    def add(self, **counts: int) -> None:
        with self._lock:
            for name, n in counts.items():
                self._n[name] += n

    def snapshot(self) -> dict:
        with self._lock:
            return {f"{self._prefix}_{name}": n for name, n in self._n.items()}


# The verify seam's column form (types/block.CommitColumns,
# ValidatorSet._commit_batch_arrays): ``column_rows`` signature slots
# read into columns (once per Commit object — a count that stands still
# while commits are verified means a memo outlived its commit),
# ``packed_rows`` rows packed from columns for a provider,
# ``fixup_rows`` those of them off the common shape — a non-64-byte
# signature or a non-ed25519 key (verified row by row outside the
# batch) or an unknown address (dropped). An all-ed25519 commit reads 0
# fix-up rows; a BLS or mixed set shows the other side.
# ``overlapped_rows`` are the packed rows a provider pulled from a
# RowGroups after an earlier group of the same call — packed while the
# device ran the launch before them; 7/8 of a 128-commit chain of 1,024
# slots, 0 for one commit. ``multiset_rows`` are the rows of spec lists
# over more than one validator set that the cached tables answered: a
# window that straddled a set change and still rode the tables. The
# seam packs before a provider is chosen (types/ knows none), so its
# counts are the process's, as crypto/merkle.device_stats() are.
SEAM_COUNTS = NamedCounts(
    "seam",
    ("column_rows", "packed_rows", "fixup_rows", "overlapped_rows", "multiset_rows"),
)

# Which table operand the cached-table path took (models/verifier.py
# plan_slots): ``slot_rows`` real rows verified in slot order (tables
# read in place), ``slot_pad`` the empty slots launched with them,
# ``gathered_rows`` rows whose ~30 KB key tables were gathered (sparse
# or unordered batches, a mesh, sharded tables), ``kernel_slots`` the
# slots (rows and pad) launched into a stage-2 program whose body is the
# Pallas kernel form (ops/stage2_kernel.kernel_form: 0 on the CPU and
# for a table operand off its shape rule). Process-wide too.
TABLED_COUNTS = NamedCounts(
    "tabled", ("slot_rows", "slot_pad", "gathered_rows", "kernel_slots")
)

# The key pool behind those tables (models/verifier._KeyPool): one key
# table a validator key, whichever sets it appears in. ``keys_built``
# keys whose table the device built, ``keys_loaded`` keys read back from
# the table files, ``keys_reused`` keys a call asked for and found
# pooled, ``keys_evicted`` least-recently-used keys dropped under the
# byte bound, ``slabs`` launches whose table operand was gathered from
# the pool (0 for a set that is the pool as it lies) and
# ``slab_columns`` the columns those gathers copied. Process-wide.
TABLE_COUNTS = NamedCounts(
    "table",
    ("keys_built", "keys_loaded", "keys_reused", "keys_evicted", "slabs", "slab_columns"),
)

# The generic verify family (models/verifier.VerifierModel.verify: rows
# with no validator set — key decompression, a per-row table, 256
# doublings on the device): ``rows`` real rows verified, ``pad_rows`` the
# empty rows launched with them up to a bucket, ``windows`` the full
# MAX_DEVICE_ROWS windows a batch past that size streamed, ``launches``
# every three-stage launch, a streamed batch's tail included,
# ``kernel_rows`` the rows (real and pad) launched into a launch whose
# three stages take the Pallas kernel form — stage 2's generic_scan,
# stage 1's and stage 3's field_chain (decompression's square root, the
# encode's inversion) — by the one rule (ops/field_kernel.kernel_form: 0
# on the CPU and in the buckets below 1,024 rows). Counted once the
# verdicts are read back; process-wide.
GENERIC_COUNTS = NamedCounts(
    "generic", ("rows", "pad_rows", "windows", "launches", "kernel_rows")
)

# Host-to-device bytes of served launches (models/verifier.py
# VerifierModel._to_device): every array a tabled or generic launch, or a
# launch's table slab, copies to the device, signatures, messages or
# their templates, indices and pad slots alike. Table builds and the
# warm passes on zeros are set-up and not counted. Process-wide.
H2D_COUNTS = NamedCounts("h2d", ("bytes",))


class GroupKeys(NamedTuple):
    """The distinct ed25519 keys some whole commits are checked
    against: ``pubkeys`` (U, 32) u8, in an order that keeps every one
    of those commits' own validator order (the seam gives address
    order), and ``digest``, which names exactly this matrix (a
    ValidatorSet.batch_cache() key for one set, a hash over the sets'
    keys for several) — what a provider memoises the keys' tables
    under."""

    digest: bytes
    pubkeys: np.ndarray


class RowGroups:
    """The rows of whole commits of all-ed25519 validator sets — one
    set or a set a commit — in commit order, packed when a provider
    asks for them (the seam's source is types/validator_set._SpecRows).
    It stands in for the row arguments of
    ``verify_rows_cached_templated`` with a provider whose
    ``takes_row_groups`` is true. The call's ``valset_key`` and
    ``all_pubkeys`` are then the FIRST commit's set: what the size gate
    is held against, and what a group's rows index where ``keys`` gives
    None; each group's own keys come from ``keys``.

    The provider takes a launch's worth of commits, dispatches the
    launch, and only then takes the next: device dispatch is
    asynchronous, so the caller's thread packs group k+1 while the
    device runs launch k. It answers with the verdicts of every row it
    took, in the order taken, having taken them all — or with None,
    at any point, and the seam finishes the packing and sends every
    row down the generic path."""

    left = 0  # whole commits not yet taken

    def keys(self, commits: int) -> Optional[GroupKeys]:
        """The distinct keys of the next ``commits`` commits' sets
        (fewer at the end), nothing packed: what ``take(commits)``'s
        row_idx will index. A provider asks before it takes, so that a
        group whose sets together pass a table bucket is taken as fewer
        commits. None: the call's own ``all_pubkeys``, every commit of
        the one set."""
        return None

    def take(self, commits: int):
        """Pack the next ``commits`` commits (fewer at the end):
        (row_idx (n,) i32, templates (2k, 160) u8, tmpl_idx (n,) i32,
        ts8 (n, 8) u8, sigs (n, 64) u8), the templates the group's own
        and row_idx each row's place in ``keys(commits).pubkeys``, a
        commit's rows one increasing run. None when a row is off the
        common shape (a non-64-byte signature): the provider answers
        None in turn."""
        raise NotImplementedError


class BatchVerifier:
    """Batch signature verification over rectangular u8 arrays."""

    name = "abstract"
    # whether verify_rows_cached_templated takes a RowGroups for its
    # row arguments and feeds its launches as the groups are packed
    takes_row_groups = False

    def verify_batch(
        self,
        pubkeys: np.ndarray,
        msgs: np.ndarray,
        sigs: np.ndarray,
        msg_lens: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """pubkeys (N,32) u8, msgs (N,L) u8, sigs (N,64) u8 -> (N,) bool.

        `msg_lens` (N,) gives each row's true message length when rows
        are zero-padded to a common L; None means every row is exactly L
        (the fixed-width sign-bytes hot path).
        """
        raise NotImplementedError

    def verify_commit_batch(
        self,
        pubkeys: np.ndarray,
        msgs: np.ndarray,
        sigs: np.ndarray,
        powers: np.ndarray,
        counted: np.ndarray,
    ) -> Tuple[np.ndarray, int]:
        """Verify, then tally voting power over the verdicts on the host.

        `powers` (N,) int64 voting power per signer; `counted` (N,) bool --
        whether this row's power counts toward the tally (e.g. votes for
        the right BlockID). Returns (ok (N,) bool, talled power int where
        ok & counted). The one definition: every provider, wrapped or
        not, inherits it over its own verify_batch.
        """
        ok = self.verify_batch(pubkeys, msgs, sigs)
        talled = int(np.sum(np.where(ok & counted.astype(bool), powers, 0)))
        return ok, talled

    def verify_rows_cached(
        self,
        valset_key: bytes,
        all_pubkeys: np.ndarray,
        row_idx: np.ndarray,
        msgs: np.ndarray,
        sigs: np.ndarray,
    ) -> Optional[np.ndarray]:
        """Verify rows whose pubkeys are ``all_pubkeys[row_idx]`` using
        per-valset precomputed tables keyed by ``valset_key``.

        Validator sets are stable across heights; providers that
        precompute per-key tables (the TPU path) hoist decompression and
        most of the scalar-mult doublings out of the per-commit program.
        Returns None when no cached path is available — callers MUST
        fall back to verify_batch (this default does exactly that
        signal)."""
        return None

    def verify_rows_cached_templated(
        self,
        valset_key: bytes,
        all_pubkeys: np.ndarray,
        row_idx,
        templates: Optional[np.ndarray] = None,
        tmpl_idx: Optional[np.ndarray] = None,
        ts8: Optional[np.ndarray] = None,
        sigs: Optional[np.ndarray] = None,
    ) -> Optional[np.ndarray]:
        """verify_rows_cached with TEMPLATED messages: row r's sign
        bytes are templates[tmpl_idx[r]] (T, 160) with ts8[r] (8 bytes)
        spliced at the timestamp offset (codec/signbytes.py layout).
        Device providers materialize rows on device, cutting per-row
        H2D from ~228 B to ~80 B. Same None-means-fallback contract.

        A provider whose ``takes_row_groups`` is true also takes a
        RowGroups as ``row_idx`` (the other row arguments left out): still
        ONE call that answers with every row's verdict in row order,
        but its launches are fed as the groups are packed — it takes
        group k+1 only after dispatching launch k, and syncs once at
        the end. None at any point (tables or a shape cold, a failed
        launch, a row off the common shape) throws the launches away:
        the caller finishes the packing and sends every row down the
        generic path, so each row is verified and counted once. The
        provider knows no row count when it starts: a size gate is held
        against the slots the commits span, and the set's tables are
        looked up (or their build started) even if every spec then
        fails its pre-checks and brings no row."""
        return None


class CPUBatchVerifier(BatchVerifier):
    """Serial host verification -- reference-parity behavior.

    ``row_counts`` is where the rows served here
    are counted as host rows; a device provider passes its own so its
    host fallbacks land in the same pair as its device rows."""

    name = "cpu"

    def __init__(self, row_counts=None):
        self.row_counts = row_counts if row_counts is not None else RowCounts()

    def verify_batch(self, pubkeys, msgs, sigs, msg_lens=None) -> np.ndarray:
        from tendermint_tpu.crypto.keys import Ed25519PubKey

        n = len(pubkeys)
        self.row_counts.add(host=n)
        out = np.zeros(n, dtype=bool)
        for i in range(n):
            try:
                pk = Ed25519PubKey(bytes(bytearray(pubkeys[i])))
            except ValueError:
                continue
            msg = bytes(bytearray(msgs[i]))
            if msg_lens is not None:
                msg = msg[: int(msg_lens[i])]
            out[i] = pk.verify(msg, bytes(bytearray(sigs[i])))
        return out


class TPUBatchVerifier(BatchVerifier):
    """Batched JAX ed25519 on the accelerator.

    ``block_on_compile=False`` (the live-node setting) keeps consensus
    latency-safe: a cold batch bucket is verified on host while a
    background thread compiles the device program; warm buckets run on
    device. ``min_device_batch`` routes tiny batches (below the device
    dispatch break-even) to the host verifier."""

    name = "tpu"

    # The admitted-device set changes rarely (a breaker trip or
    # recovery); cache a few meshed models so flapping between two
    # cohorts doesn't rebuild executables every bundle.
    _MAX_MESH_MODELS = 4

    def __init__(
        self,
        mesh=None,
        block_on_compile: bool = True,
        min_device_batch: int = 2,
        router=None,
    ):
        from tendermint_tpu.models import verifier as _verifier_model

        self._verifier_model = _verifier_model
        self._block_on_compile = block_on_compile
        # one device/host row pair for this provider, its models and
        # its sub-min_device_batch host route (engine_stats reads it)
        self.row_counts = RowCounts()
        self._model = _verifier_model.VerifierModel(
            mesh=mesh, block_on_compile=block_on_compile, row_counts=self.row_counts
        )
        self._cpu = CPUBatchVerifier(row_counts=self.row_counts)
        self.min_device_batch = min_device_batch
        self.router = router
        self._mesh_lock = threading.Lock()
        self._mesh_models: dict = {}  # mesh_key tuple -> VerifierModel
        self._valsets: dict = {}  # valset_key -> all_pubkeys (re-register on rebuild)

    @property
    def model(self):
        return self._model

    def warmup(self, sizes=(16, 1024), msg_len: int = 160, background: bool = False):
        return self._model.warmup(sizes=sizes, msg_len=msg_len, background=background)

    # -- mesh routing (the seam: engines stay single-device-shaped) ------

    def _plan(self, n: int):
        if self.router is None:
            return None
        return self.router.plan(n)

    def _collective_model(self, plan):
        """The VerifierModel shard_mapped over exactly the plan's
        devices (None when the topology has no jax placement)."""
        key = self.router.mesh_key(plan)
        with self._mesh_lock:
            model = self._mesh_models.get(key)
            if model is not None:
                return model
            mesh = self.router.jax_mesh(plan)
            if mesh is None:
                return None
            model = self._verifier_model.VerifierModel(
                mesh=mesh, block_on_compile=self._block_on_compile,
                row_counts=self.row_counts,
            )
            for vk, pks in self._valsets.items():
                model.register_valset(vk, pks)
            if len(self._mesh_models) >= self._MAX_MESH_MODELS:
                self._mesh_models.pop(next(iter(self._mesh_models)))
            self._mesh_models[key] = model
            return model

    def _meshed(self, n: int, call):
        """Run ``call(model)`` over the admitted mesh when the router
        says collective; any failure (or a None no-cached-path result)
        falls through to the single-device path — bit-identical."""
        plan = self._plan(n)
        if plan is None or not plan.collective:
            return False, None
        model = self._collective_model(plan)
        if model is None:
            self.router.release(plan)
            return False, None
        try:
            return True, self.router.run_collective(plan, lambda: call(model))
        except Exception:
            return False, None

    def verify_batch(self, pubkeys, msgs, sigs, msg_lens=None) -> np.ndarray:
        if len(pubkeys) < self.min_device_batch:
            return self._cpu.verify_batch(pubkeys, msgs, sigs, msg_lens=msg_lens)
        ran, out = self._meshed(
            len(pubkeys), lambda m: m.verify(pubkeys, msgs, sigs, msg_lens=msg_lens)
        )
        if ran:
            return out
        return self._model.verify(pubkeys, msgs, sigs, msg_lens=msg_lens)

    def verify_rows_cached(self, valset_key, all_pubkeys, row_idx, msgs, sigs):
        if len(row_idx) < self.min_device_batch:
            return None
        ran, out = self._meshed(
            len(row_idx),
            lambda m: m.verify_rows_cached(valset_key, all_pubkeys, row_idx, msgs, sigs),
        )
        if ran and out is not None:
            return out
        return self._model.verify_rows_cached(
            valset_key, all_pubkeys, row_idx, msgs, sigs
        )

    @property
    def takes_row_groups(self) -> bool:
        # a router plans over a row count no lazy source can state
        return self.router is None

    def verify_rows_cached_templated(
        self, valset_key, all_pubkeys, row_idx, templates=None, tmpl_idx=None,
        ts8=None, sigs=None,
    ):
        if isinstance(row_idx, RowGroups):
            # no row is packed yet: the gate is held against the slots
            # the commits span, the most rows they can bring
            if row_idx.left * len(all_pubkeys) < self.min_device_batch:
                return None
            return self._model.verify_rows_cached_templated(
                valset_key, all_pubkeys, row_idx
            )
        if len(row_idx) < self.min_device_batch:
            return None
        ran, out = self._meshed(
            len(row_idx),
            lambda m: m.verify_rows_cached_templated(
                valset_key, all_pubkeys, row_idx, templates, tmpl_idx, ts8, sigs
            ),
        )
        if ran and out is not None:
            return out
        return self._model.verify_rows_cached_templated(
            valset_key, all_pubkeys, row_idx, templates, tmpl_idx, ts8, sigs
        )

    def register_valset(self, valset_key, all_pubkeys) -> None:
        """Pre-build the per-valset cached tables (node-start warmup)."""
        with self._mesh_lock:
            self._valsets[valset_key] = all_pubkeys
            models = list(self._mesh_models.values())
        self._model.register_valset(valset_key, all_pubkeys)
        for m in models:
            m.register_valset(valset_key, all_pubkeys)


class MeshRoutedVerifier(BatchVerifier):
    """Seam-level chunked mesh routing over ANY inner verifier.

    Where :class:`TPUBatchVerifier` runs ONE shard_map program across
    the admitted mesh, this wrapper splits the bundle into contiguous
    per-device row chunks and dispatches the inner verifier once per
    chunk — the same MeshRouter admission/breaker semantics with no
    jax dependency, which is exactly what the simulator's determinism
    rig and the degraded-topology tests need (logical host lanes).
    Verdict order is preserved by concatenation (the inherited quorum
    tally sums over the concatenated verdicts), so results are
    bit-identical to the unrouted inner verifier by construction."""

    def __init__(self, inner: BatchVerifier, router):
        self.inner = inner
        self.router = router
        self.name = f"mesh({inner.name})"

    def warmup(self, *a, **kw):
        fn = getattr(self.inner, "warmup", None)
        return fn(*a, **kw) if fn else None

    def register_valset(self, valset_key, all_pubkeys) -> None:
        fn = getattr(self.inner, "register_valset", None)
        if fn:
            fn(valset_key, all_pubkeys)

    def engine_stats(self):
        fn = getattr(self.inner, "engine_stats", None)
        return fn() if fn else None

    @property
    def row_counts(self):
        return getattr(self.inner, "row_counts", None)

    def verify_batch(self, pubkeys, msgs, sigs, msg_lens=None) -> np.ndarray:
        plan = self.router.plan(len(pubkeys))
        if not plan.collective:
            return self.inner.verify_batch(pubkeys, msgs, sigs, msg_lens=msg_lens)
        try:
            return self.router.run(
                plan,
                lambda s: self.inner.verify_batch(
                    pubkeys[s.lo : s.hi],
                    msgs[s.lo : s.hi],
                    sigs[s.lo : s.hi],
                    msg_lens=None if msg_lens is None else msg_lens[s.lo : s.hi],
                ),
                lambda outs: np.concatenate(outs),
            )
        except Exception:
            return self.inner.verify_batch(pubkeys, msgs, sigs, msg_lens=msg_lens)

    def verify_rows_cached(self, valset_key, all_pubkeys, row_idx, msgs, sigs):
        plan = self.router.plan(len(row_idx))
        if not plan.collective:
            return self.inner.verify_rows_cached(
                valset_key, all_pubkeys, row_idx, msgs, sigs
            )

        def _combine(outs):
            if any(o is None for o in outs):
                return None  # a chunk had no cached path: whole-bundle fallback
            return np.concatenate(outs)

        try:
            return self.router.run(
                plan,
                lambda s: self.inner.verify_rows_cached(
                    valset_key,
                    all_pubkeys,
                    row_idx[s.lo : s.hi],
                    msgs[s.lo : s.hi],
                    sigs[s.lo : s.hi],
                ),
                _combine,
            )
        except Exception:
            return self.inner.verify_rows_cached(
                valset_key, all_pubkeys, row_idx, msgs, sigs
            )

    def verify_rows_cached_templated(
        self, valset_key, all_pubkeys, row_idx, templates, tmpl_idx, ts8, sigs
    ):
        plan = self.router.plan(len(row_idx))
        if not plan.collective:
            return self.inner.verify_rows_cached_templated(
                valset_key, all_pubkeys, row_idx, templates, tmpl_idx, ts8, sigs
            )

        def _combine(outs):
            if any(o is None for o in outs):
                return None
            return np.concatenate(outs)

        try:
            # templates replicate to every chunk; tmpl_idx stays valid.
            return self.router.run(
                plan,
                lambda s: self.inner.verify_rows_cached_templated(
                    valset_key,
                    all_pubkeys,
                    row_idx[s.lo : s.hi],
                    templates,
                    tmpl_idx[s.lo : s.hi],
                    ts8[s.lo : s.hi],
                    sigs[s.lo : s.hi],
                ),
                _combine,
            )
        except Exception:
            return self.inner.verify_rows_cached_templated(
                valset_key, all_pubkeys, row_idx, templates, tmpl_idx, ts8, sigs
            )


_lock = threading.Lock()
_default: Optional[BatchVerifier] = None


def get_default_provider() -> BatchVerifier:
    global _default
    with _lock:
        if _default is None:
            _default = CPUBatchVerifier()
        return _default


def set_default_provider(v: BatchVerifier) -> None:
    global _default
    with _lock:
        _default = v


def make_provider(
    name: str, mesh=None, block_on_compile: bool = True, router=None
) -> BatchVerifier:
    if name == "cpu":
        return CPUBatchVerifier()
    if name == "tpu":
        return TPUBatchVerifier(
            mesh=mesh, block_on_compile=block_on_compile, router=router
        )
    raise ValueError(f"unknown crypto provider {name!r}")


# -- convenience for list-of-bytes call sites -------------------------------


def pack_triples(
    pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Pack byte triples into rectangular u8 arrays.

    Ragged messages are zero-padded to the max length and their true
    lengths returned as `msg_lens` (None when already uniform -- the
    fixed-width sign-bytes hot path).
    """
    n = len(pubkeys)
    assert len(msgs) == n and len(sigs) == n
    max_len = max((len(m) for m in msgs), default=0)
    uniform = all(len(m) == max_len for m in msgs)
    pk = np.zeros((n, 32), dtype=np.uint8)
    mg = np.zeros((n, max_len), dtype=np.uint8)
    sg = np.zeros((n, 64), dtype=np.uint8)
    for i in range(n):
        pk[i, : min(len(pubkeys[i]), 32)] = np.frombuffer(pubkeys[i][:32], dtype=np.uint8)
        mg[i, : len(msgs[i])] = np.frombuffer(msgs[i], dtype=np.uint8)
        sg[i, : min(len(sigs[i]), 64)] = np.frombuffer(sigs[i][:64], dtype=np.uint8)
    lens = None if uniform else np.asarray([len(m) for m in msgs], dtype=np.int32)
    return pk, mg, sg, lens


def verify_many(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    provider: Optional[BatchVerifier] = None,
) -> List[bool]:
    if not pubkeys:
        return []
    pk, mg, sg, lens = pack_triples(pubkeys, msgs, sigs)
    v = provider or get_default_provider()
    return [bool(b) for b in v.verify_batch(pk, mg, sg, msg_lens=lens)]
