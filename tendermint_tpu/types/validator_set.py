"""ValidatorSet: ordered validators + proposer rotation + commit verification.

Reference: types/validator_set.go -- ValidatorSet :42,
IncrementProposerPriority :86, RescalePriorities :130,
UpdateWithChangeSet :803 region, VerifyCommit :629, VerifyCommitTrusting
:754.

The TPU-first change: ``verify_commit`` / ``verify_commit_trusting`` do
NOT loop ``pubkey.verify`` per signature like the reference
(types/validator_set.go:641-668). They pack all present signatures into
rectangular arrays and make ONE BatchVerifier call for the verdicts,
then replay the reference's sequential-early-return semantics (tally
included) over the returned ok/power vectors on the host so acceptance
is bit-for-bit identical to the serial loop.
"""

from __future__ import annotations

from fractions import Fraction
from itertools import repeat
from operator import attrgetter
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from tendermint_tpu.codec.binary import Reader, Writer
from tendermint_tpu.codec.signbytes import splice_timestamps
from tendermint_tpu.crypto import merkle
from tendermint_tpu.crypto.batch import (
    SEAM_COUNTS, BatchVerifier, GroupKeys, RowGroups, get_default_provider,
)
from tendermint_tpu.types.block import BLOCK_ID_FLAG_COMMIT, MAX_SIGNATURE_SIZE, first_true
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.utils.trace import span

MAX_TOTAL_VOTING_POWER = (1 << 63) // 8
PRIORITY_WINDOW_SIZE_FACTOR = 2


class ErrTotalVotingPowerOverflow(Exception):
    pass


class ErrNotEnoughVotingPower(Exception):
    pass


class ErrInvalidCommitSignature(Exception):
    pass


class ErrInvalidCommit(Exception):
    pass


class ValidatorSet:
    def __init__(self, validators: Sequence[Validator]):
        vals = [v.copy() for v in validators]
        vals.sort(key=lambda v: v.address)
        addrs = [v.address for v in vals]
        if len(set(addrs)) != len(addrs):
            raise ValueError("duplicate validator address")
        self.validators: List[Validator] = vals
        self.proposer: Optional[Validator] = None
        self._total_voting_power: Optional[int] = None
        self._addr_index: Dict[bytes, int] = {v.address: i for i, v in enumerate(vals)}
        if vals:
            self._update_total_voting_power()
            self.increment_proposer_priority(1)

    # -- basic accessors ---------------------------------------------------

    def __len__(self) -> int:
        return len(self.validators)

    def size(self) -> int:
        return len(self.validators)

    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def has_address(self, addr: bytes) -> bool:
        return addr in self._addr_index

    def get_by_address(self, addr: bytes) -> Tuple[int, Optional[Validator]]:
        i = self._addr_index.get(addr)
        if i is None:
            return -1, None
        return i, self.validators[i]

    def get_by_index(self, index: int) -> Tuple[bytes, Optional[Validator]]:
        if index < 0 or index >= len(self.validators):
            return b"", None
        v = self.validators[index]
        return v.address, v

    def total_voting_power(self) -> int:
        if self._total_voting_power is None:
            self._update_total_voting_power()
        return self._total_voting_power  # type: ignore[return-value]

    def _update_total_voting_power(self) -> None:
        total = 0
        for v in self.validators:
            total += v.voting_power
            if total > MAX_TOTAL_VOTING_POWER:
                raise ErrTotalVotingPowerOverflow(total)
        self._total_voting_power = total
        self._dev_arrays = None  # membership/power changed: drop the cache
        self._dev_key = None
        self._addr_col = None
        self._bls_cache = None
        self._hash = None  # (pubkey, power) merkle root changed too

    def copy(self) -> "ValidatorSet":
        new = ValidatorSet.__new__(ValidatorSet)
        new.validators = [v.copy() for v in self.validators]
        new.proposer = self.proposer.copy() if self.proposer else None
        new._total_voting_power = self._total_voting_power
        new._addr_index = dict(self._addr_index)
        # the pubkey/power arrays are immutable once built (fancy indexing
        # copies them at use sites) and every membership/power mutation
        # drops them via _update_total_voting_power — safe to share, and
        # propagating keeps the hot-path cache alive across the per-height
        # copies in state/execution.py
        new._dev_arrays = getattr(self, "_dev_arrays", None)
        new._dev_key = getattr(self, "_dev_key", None)
        new._addr_col = getattr(self, "_addr_col", None)
        new._hash = getattr(self, "_hash", None)
        new._bls_cache = getattr(self, "_bls_cache", None)
        return new

    def hash(self) -> bytes:
        """Merkle root over validator (pubkey, power) encodings
        (reference ValidatorSet.Hash types/validator_set.go:307).
        Memoized: covers only membership/power, which every mutation
        path routes through _update_total_voting_power (the same
        invalidation point as the device-array caches) — proposer
        priorities are deliberately NOT part of the hash."""
        h = getattr(self, "_hash", None)
        if h is None:
            h = merkle.hash_from_byte_slices(
                [v.hash_bytes() for v in self.validators]
            )
            self._hash = h
        return h

    # -- proposer rotation (reference :86-:189) ---------------------------

    def increment_proposer_priority(self, times: int) -> None:
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("times must be positive")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority_once()
        self.proposer = proposer

    def _increment_proposer_priority_once(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = _safe_add(v.proposer_priority, v.voting_power)
        most = self._validator_with_most_priority()
        most.proposer_priority = _safe_sub(most.proposer_priority, self.total_voting_power())
        return most

    def _validator_with_most_priority(self) -> Validator:
        res = self.validators[0]
        for v in self.validators[1:]:
            res = res.compare_proposer_priority(v)
        return res

    def _compute_avg_proposer_priority(self) -> int:
        n = len(self.validators)
        total = sum(v.proposer_priority for v in self.validators)
        # Reference uses big.Int.Div (Euclidean), which for positive n is
        # floor division -- Python's // (types/validator_set.go:156).
        return total // n

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        for v in self.validators:
            v.proposer_priority = _safe_sub(v.proposer_priority, avg)

    def rescale_priorities(self, diff_max: int) -> None:
        """Scale priorities so max-min <= diff_max (reference :130)."""
        if diff_max <= 0:
            return
        diff = _compute_max_min_priority_diff(self.validators)
        ratio = (diff + diff_max - 1) // diff_max if diff > 0 else 1
        if diff > diff_max:
            for v in self.validators:
                # truncate toward zero like Go
                p = v.proposer_priority
                v.proposer_priority = -((-p) // ratio) if p < 0 else p // ratio

    def get_proposer(self) -> Optional[Validator]:
        if not self.validators:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        res = None
        for v in self.validators:
            res = v if res is None else res.compare_proposer_priority(v)
        return res  # type: ignore[return-value]

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        cp = self.copy()
        cp.increment_proposer_priority(times)
        return cp

    # -- updates (reference UpdateWithChangeSet :803) ----------------------

    def update_with_change_set(self, changes: Sequence[Validator]) -> None:
        self._update_with_change_set(changes, allow_deletes=True)

    def _update_with_change_set(self, changes: Sequence[Validator], allow_deletes: bool) -> None:
        if not changes:
            return
        # verify: sorted-by-address unique changes, valid powers
        seen = set()
        updates, removals = [], []
        for c in changes:
            if c.address in seen:
                raise ValueError(f"duplicate address in changes: {c.address.hex()}")
            seen.add(c.address)
            if c.voting_power < 0:
                raise ValueError("voting power can't be negative")
            if c.voting_power > MAX_TOTAL_VOTING_POWER:
                raise ValueError("voting power too high")
            if c.voting_power == 0:
                if not allow_deletes:
                    raise ValueError("can't delete validator in this context")
                removals.append(c)
            else:
                updates.append(c)

        # check removals exist
        for c in removals:
            if c.address not in self._addr_index:
                raise ValueError(f"removing non-existent validator {c.address.hex()}")

        # compute the new total power for priority assignment of new vals
        by_addr = {v.address: v for v in self.validators}
        new_total = self.total_voting_power()
        for c in updates:
            prev = by_addr.get(c.address)
            new_total += c.voting_power - (prev.voting_power if prev else 0)
        for c in removals:
            new_total -= by_addr[c.address].voting_power
        if new_total > MAX_TOTAL_VOTING_POWER:
            raise ErrTotalVotingPowerOverflow(new_total)
        if new_total <= 0:
            raise ValueError("applying the changes would empty the validator set")

        # apply: new validators get priority -(total + total>>3)
        # (reference computeNewPriorities :744 -- -1.125 * new total power)
        new_priority = -(new_total + (new_total >> 3))
        for c in updates:
            prev = by_addr.get(c.address)
            if prev is not None:
                prev.voting_power = c.voting_power
            else:
                v = c.copy()
                v.proposer_priority = new_priority
                by_addr[v.address] = v
        for c in removals:
            del by_addr[c.address]

        vals = sorted(by_addr.values(), key=lambda v: v.address)
        self.validators = vals
        self._addr_index = {v.address: i for i, v in enumerate(vals)}
        self._total_voting_power = None
        self._update_total_voting_power()

        # rescale and recenter, then recompute proposer
        self.rescale_priorities(PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
        self._shift_by_avg_proposer_priority()
        self.proposer = self._find_proposer()

    # -- commit verification (THE hot path) --------------------------------

    def _device_arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Cached (N,32) pubkeys + (N,) powers + (N,) ed25519-mask for
        this set, built once — commit verification reuses them every
        height until the set changes (any mutation path ends in
        _update_total_voting_power, which drops the cache).

        Rows whose key is not a 32-byte ed25519 key (e.g. secp256k1,
        crypto/secp256k1.py) are masked out: the batch kernel is
        ed25519-only, so those rows verify serially via their own key
        type instead of being silently truncated into garbage. BLS
        rows get their own mask + (N,48) matrix (_bls_arrays) and ride
        the BLS batch provider."""
        cached = getattr(self, "_dev_arrays", None)
        if cached is not None:
            return cached
        from tendermint_tpu.crypto.keys import is_batch_ed25519

        n = len(self.validators)
        pk = np.zeros((n, 32), dtype=np.uint8)
        ed = np.zeros(n, dtype=bool)
        for i, v in enumerate(self.validators):
            raw = v.pub_key.bytes()
            if is_batch_ed25519(v.pub_key):
                pk[i] = np.frombuffer(raw, dtype=np.uint8)
                ed[i] = True
        powers = np.asarray([v.voting_power for v in self.validators], dtype=np.int64)
        self._dev_arrays = (pk, powers, ed)
        return self._dev_arrays

    def bls_cache(self) -> Tuple[np.ndarray, np.ndarray]:
        """Cached (N,48) BLS pubkey matrix + (N,) BLS mask (the
        batch_cache companion for the aggregation track; every set
        mutation clears it in _update_total_voting_power, exactly like
        _dev_arrays)."""
        cached = getattr(self, "_bls_cache", None)
        if cached is not None:
            return cached
        from tendermint_tpu.crypto.bls import is_batch_bls

        n = len(self.validators)
        pk = np.zeros((n, 48), dtype=np.uint8)
        blsm = np.zeros(n, dtype=bool)
        for i, v in enumerate(self.validators):
            if is_batch_bls(v.pub_key):
                pk[i] = np.frombuffer(v.pub_key.bytes(), dtype=np.uint8)
                blsm[i] = True
        self._bls_cache = (pk, blsm)
        return self._bls_cache

    def batch_cache(self) -> Tuple[bytes, np.ndarray, np.ndarray]:
        """(cache key, pubkey matrix (V,32), ed mask) for providers with
        per-valset precomputed tables (crypto/batch.verify_rows_cached).
        The key is a digest of the pubkey matrix — cheaper than the
        merkle hash() and exactly what the tables depend on; cached and
        propagated across per-height copies like _dev_arrays."""
        pk, _, ed = self._device_arrays()
        key = getattr(self, "_dev_key", None)
        if key is None:
            import hashlib

            key = hashlib.sha256(pk.tobytes()).digest()
            self._dev_key = key
        return key, pk, ed

    def address_column(self) -> np.ndarray:
        """The validators' 20-byte addresses as one (V,) bytes column,
        ascending as the set is; cached like batch_cache(). What several
        sets' keys are merged by (_SpecRows.keys)."""
        col = getattr(self, "_addr_col", None)
        if col is None:
            col = np.array([v.address for v in self.validators], dtype="S20")
            self._addr_col = col
        return col

    def _commit_batch_arrays(self, chain_id: str, commit, by_address: bool) -> Tuple:
        """Pack a commit's present signatures into device-ready arrays.

        `by_address=False` maps signature index i straight to validator i
        (verify_commit: commit produced by THIS set); `by_address=True`
        looks each signer up by address, skipping unknowns
        (verify_commit_trusting: commit from another set).

        Every array is a fancy index into the commit's columns
        (Commit.columns, read once per commit) or the per-set cache
        (_device_arrays); no CommitSig is visited here. Rows off the
        common shape are told apart by what the columns show: a
        signature that is not 64 bytes or a non-ed25519 key leaves the
        ``ed`` mask (those rows verify one by one, _serial_fill_non_ed),
        an unknown address under ``by_address`` is dropped.

        Returns (idxs(N,) i64, vals_idx(N,) i64, pubkeys(N,32),
        msgs(N,160), sigs(N,64), powers(N,), counted(N,), ed(N,), tpl)
        where idxs maps rows back to signature indices and vals_idx to
        validator indices (for duplicate-signer detection during the
        sequential replay -- NOT here, so that a duplicate after quorum
        doesn't reject like the reference doesn't). tpl is the commit's
        templated sign-bytes (templates(2,160), tmpl_idx(N,), ts8(N,8))
        row-gathered like msgs — device providers materialize rows on
        device so per-row H2D carries 12 message bytes instead of 160.
        """
        cols = commit.columns()
        idxs, sig_lens = cols.present, cols.present_sig_lens
        r = first_true(sig_lens > MAX_SIGNATURE_SIZE)
        if r >= 0:
            # reference MaxSignatureSize (widened to 96 for BLS G2
            # rows); must never be truncated into a valid prefix
            # (commit-hash malleability).
            raise ErrInvalidCommit(f"signature #{idxs[r]} too big ({sig_lens[r]})")
        # the (n, 64) matrix feeds the ed25519 kernel only; BLS /
        # other-type rows re-read the full signature bytes from the
        # commit (_serial_fill_non_ed), so clamping here cannot
        # change any verdict
        sg = cols.sig_rows(64)
        unknown = 0
        if by_address:
            vals_idx = np.fromiter(
                map(
                    self._addr_index.get,
                    map(attrgetter("validator_address"), commit.signatures),
                    repeat(-1),
                ),
                dtype=np.int64,
                count=len(commit.signatures),
            )[idxs]
            known = vals_idx >= 0
            unknown = idxs.size - int(np.count_nonzero(known))
            if unknown:
                idxs, vals_idx, sig_lens, sg = (
                    idxs[known], vals_idx[known], sig_lens[known], sg[known]
                )
        else:
            vals_idx = idxs
        all_pk, all_powers, all_ed = self._device_arrays()
        # an ed25519 row whose signature is not 64 bytes must NOT ride
        # the clamped / padded batch matrix — the serial path rejects
        # any non-64-byte ed25519 signature, and truncating or padding
        # could reconstitute a valid one (verdict divergence)
        ed = all_ed[vals_idx] & (sig_lens == 64)
        SEAM_COUNTS.add(
            packed_rows=idxs.size,
            fixup_rows=unknown + idxs.size - int(np.count_nonzero(ed)),
        )
        # ONE sign_bytes_parts call feeds both forms: the templated
        # parts (what device providers consume) and the host-side
        # materialization mg (fallback paths + non-ed rows). Absent
        # rows are not among idxs, so the absent-row zeroing that
        # sign_bytes_matrix does is not needed here.
        templates, tmpl_idx_all, ts8_all = commit.sign_bytes_parts(chain_id)
        tpl = (templates, tmpl_idx_all[idxs], ts8_all[idxs])
        # fancy indexing already allocates a fresh array
        mg = splice_timestamps(templates[tpl[1]], tpl[2])
        return (
            idxs,
            vals_idx,
            all_pk[vals_idx],
            mg,
            sg,
            all_powers[vals_idx],
            cols.flags[idxs] == BLOCK_ID_FLAG_COMMIT,
            ed,
            tpl,
        )

    def _verify_rows(
        self, commit, idxs, vals_idx, pk, mg, sg, ed, provider, tpl=None,
        sig_cache=None, row_keys=None,
    ) -> np.ndarray:
        """Per-row signature validity: ed25519 rows go to the batch
        provider in one call; rows with other key types (secp256k1, ...)
        verify serially through their own PubKey.verify — the
        reference accepts any registered key type for validators
        (types/validator_set.go:641 calls the interface method)."""
        # verify_batch: verdicts only (the host replay tallies them),
        # and this kernel is the one vote ingest already keeps warm.
        if ed.all():
            return self._ed_rows(
                provider, np.asarray(vals_idx, dtype=np.int64), pk, mg, sg,
                tpl, sig_cache, row_keys,
            )
        ok = np.zeros(len(idxs), dtype=bool)
        sub = np.nonzero(ed)[0]
        if sub.size:
            sub_idx = np.asarray(vals_idx, dtype=np.int64)[sub]
            sub_tpl = (
                (tpl[0], tpl[1][sub], tpl[2][sub]) if tpl is not None else None
            )
            sub_keys = (
                [row_keys[int(r)] for r in sub] if row_keys is not None else None
            )
            ok[sub] = self._ed_rows(
                provider, sub_idx, pk[sub], mg[sub], sg[sub], sub_tpl,
                sig_cache, sub_keys,
            )
        self._serial_fill_non_ed(ok, commit, idxs, vals_idx, mg, ed)
        return ok

    def _ed_rows(
        self, provider, vals_idx, pk, mg, sg, tpl, sig_cache, row_keys=None
    ) -> np.ndarray:
        """Ed25519 rows: SigCache front, then the provider's cached
        tables, then the generic kernel.

        The cache keys are the TEMPLATED form (crypto/pipeline.SigCache
        .key_templated) — byte-identical to the keys vote ingest inserts
        on every verified precommit (types/vote_set.py), so verifying a
        block's LastCommit whose votes this node already ingested live
        is a hash lookup per row, not a device round trip. The same
        commit is validated up to three times per height (prevote
        validate, lock validate, finalize validate); with the cache the
        signatures are verified once. Only successful verifies are
        inserted, and the signature is part of the key — the SigCache
        soundness argument unchanged."""
        n = pk.shape[0]
        if sig_cache is None or sig_cache.capacity <= 0 or tpl is None or not n:
            cached = self._rows_cached(provider, vals_idx, mg, sg, tpl)
            if cached is not None:
                return cached
            return np.asarray(provider.verify_batch(pk, mg, sg))
        templates, tmpl_idx, ts8 = tpl
        if row_keys is not None:
            # verify_commit already derived (and memoized on the commit)
            # these exact keys in _commit_row_keys — never re-hash
            keys = row_keys
        else:
            from tendermint_tpu.crypto.pipeline import SigCache

            keys = [
                SigCache.key_templated(
                    pk[r].tobytes(),
                    templates[int(tmpl_idx[r])].tobytes(),
                    ts8[r].tobytes(),
                    sg[r].tobytes(),
                )
                for r in range(n)
            ]
        miss = [r for r in range(n) if not sig_cache.seen(keys[r])]
        if not miss:
            return np.ones(n, dtype=bool)
        m = np.asarray(miss, dtype=np.int64)
        sub_tpl = (templates, np.asarray(tmpl_idx)[m], np.asarray(ts8)[m])
        got = self._ed_rows(
            provider, np.asarray(vals_idx)[m], pk[m], mg[m], sg[m], sub_tpl, None
        )
        for j, r in enumerate(miss):
            if bool(got[j]):
                sig_cache.add(keys[r])
        if len(miss) == n:
            return got
        out = np.ones(n, dtype=bool)
        out[m] = got
        return out

    def _rows_cached(self, provider, vals_idx, mg, sg, tpl=None) -> Optional[np.ndarray]:
        """Try the provider's per-valset cached-table path (None = use
        the generic batch kernel). Rows must all be ed25519. The
        templated form goes first — it uploads ~12 message bytes/row
        instead of 160 (the dominant transport cost per commit)."""
        key, all_pk, _ = self.batch_cache()
        idx32 = np.asarray(vals_idx, dtype=np.int32)
        if tpl is not None:
            f_t = getattr(provider, "verify_rows_cached_templated", None)
            if f_t is not None:
                out = f_t(key, all_pk, idx32, tpl[0], tpl[1], tpl[2], sg)
                if out is not None:
                    return np.asarray(out)
        f = getattr(provider, "verify_rows_cached", None)
        if f is None:
            return None
        out = f(key, all_pk, idx32, mg, sg)
        return None if out is None else np.asarray(out)

    def _serial_fill_non_ed(self, ok, commit, idxs, vals_idx, mg, ed, mg_off=0) -> None:
        """Fill ok[] for the non-ed25519 rows: BLS rows go to the BLS
        batch provider in ONE call (device pairing checks when warm),
        remaining key types (secp256k1, sr25519, multisig) verify
        serially via their own PubKey.verify. A key type whose verify()
        raises on malformed input counts as an invalid signature for
        that row (never aborts the batch)."""
        from tendermint_tpu.crypto.bls import (
            BLS_SIGNATURE_SIZE,
            get_default_bls_provider,
            is_batch_bls,
        )

        rest = []
        bls_rows = []
        for r in np.nonzero(~ed)[0]:
            v = self.validators[vals_idx[r]]
            sig = commit.signatures[idxs[r]].signature
            # only exact-width signatures ride the rectangular batch: a
            # short sig zero-padded to 96 bytes could reconstitute a
            # VALID encoding, diverging from the serial verdict (which
            # rejects any non-96-byte sig) — pad-truncation malleability
            if is_batch_bls(v.pub_key) and len(sig) == BLS_SIGNATURE_SIZE:
                bls_rows.append((int(r), v))
            else:
                rest.append((int(r), v))
        if bls_rows:
            n = len(bls_rows)
            pk = np.zeros((n, 48), dtype=np.uint8)
            sg = np.zeros((n, BLS_SIGNATURE_SIZE), dtype=np.uint8)
            bm = np.zeros((n, mg.shape[1]), dtype=np.uint8)
            for j, (r, v) in enumerate(bls_rows):
                pk[j] = np.frombuffer(v.pub_key.bytes(), dtype=np.uint8)
                sig = commit.signatures[idxs[r]].signature
                sg[j] = np.frombuffer(sig, dtype=np.uint8)
                bm[j] = mg[mg_off + r]
            res = np.asarray(get_default_bls_provider().verify_batch(pk, bm, sg))
            for j, (r, _v) in enumerate(bls_rows):
                ok[mg_off + r] = bool(res[j])
        for r, v in rest:
            sig = commit.signatures[idxs[r]].signature
            try:
                ok[mg_off + r] = bool(v.pub_key.verify(mg[mg_off + r].tobytes(), sig))
            except Exception:
                ok[mg_off + r] = False

    def _verify_commit_basic(self, commit, height: int, block_id) -> None:
        """Shared pre-checks (reference verifyCommitBasic,
        types/validator_set.go:813): structural validity, height and
        BlockID match."""
        err = commit.validate_basic()
        if err:
            raise ErrInvalidCommit(err)
        if height != commit.height:
            raise ErrInvalidCommit(f"wrong height: {height} vs {commit.height}")
        if block_id != commit.block_id:
            raise ErrInvalidCommit(f"wrong block ID: {block_id} vs {commit.block_id}")

    def verify_commit(
        self,
        chain_id: str,
        block_id,
        height: int,
        commit,
        provider: Optional[BatchVerifier] = None,
        sig_cache=None,
    ) -> None:
        """Verify +2/3 of this set signed `block_id` at `height`.

        Reference semantics (types/validator_set.go:629-668): iterate
        signatures in order, fail on the first invalid signature, succeed
        as soon as tallied for-block power exceeds 2/3 of total. Here the
        signatures are verified in ONE device batch; the sequential
        early-return acceptance is then replayed over the result vectors,
        so the accepted language is identical.

        An AggregatedCommit (types/aggregate.py — one BLS signature +
        signer bitmap) dispatches to verify_aggregated_commit: same
        accept/reject verdicts over the same vote sets, one pairing
        check instead of N signature verifications.
        """
        from tendermint_tpu.types.aggregate import AggregatedCommit

        if isinstance(commit, AggregatedCommit):
            return self.verify_aggregated_commit(chain_id, block_id, height, commit)
        self._check_commit_size(commit)
        self._verify_commit_basic(commit, height, block_id)

        if self._cached_commit_replay(chain_id, commit, sig_cache):
            return
        with span("verify.pack"):
            idxs, vals_idx, pk, mg, sg, powers, counted, ed, tpl = (
                self._commit_batch_arrays(chain_id, commit, by_address=False)
            )
        v = provider or get_default_provider()
        # reuse the memoized per-row keys the fast path just derived
        # (None when any row is non-ed25519 or no cache is in play)
        row_keys = None
        if sig_cache is not None and sig_cache.capacity > 0:
            all_keys = self._commit_row_keys(chain_id, commit)
            if all_keys is not None:
                row_keys = [all_keys[i] for i in idxs.tolist()]
        ok = self._verify_rows(
            commit, idxs, vals_idx, pk, mg, sg, ed, v, tpl,
            sig_cache=sig_cache, row_keys=row_keys,
        )
        with span("verify.replay"):
            self._replay_commit_full(commit, ok, idxs, powers, counted)

    def _commit_row_keys(self, chain_id: str, commit) -> Optional[list]:
        """Per-signature SigCache keys for a commit whose rows map
        straight to this set (by_address=False), memoized ON the commit
        (immutable once assembled; the memo is keyed by chain id + this
        set's pubkey-table digest so a different valset never reuses
        it). None when any present row is non-ed25519 or has a
        non-64-byte signature — those take the slow path."""
        from tendermint_tpu.crypto.pipeline import SigCache

        key, _all_pk, _ = self.batch_cache()
        memo_key = (chain_id, key)
        cached = getattr(commit, "_row_keys", None)
        if cached is not None and cached[0] == memo_key:
            return cached[1]
        all_pk, _powers, all_ed = self._device_arrays()
        templates, tmpl_idx, ts8 = commit.sign_bytes_parts(chain_id)
        tpl_bytes = (templates[0].tobytes(), templates[1].tobytes())
        keys: list = []
        for i, cs in enumerate(commit.signatures):
            if cs.absent_():
                keys.append(None)
                continue
            if not all_ed[i] or len(cs.signature) != 64:
                return None
            keys.append(
                SigCache.key_templated(
                    all_pk[i].tobytes(),
                    tpl_bytes[int(tmpl_idx[i])],
                    ts8[i].tobytes(),
                    cs.signature,
                )
            )
        commit._row_keys = (memo_key, keys)
        return keys

    def _cached_commit_replay(self, chain_id: str, commit, sig_cache) -> bool:
        """The zero-device-work validate path: when EVERY present
        signature's templated key is already in ``sig_cache`` (its votes
        were verified at ingest, or an earlier validation pass verified
        this same commit), skip array packing entirely and run the
        sequential quorum replay directly — the replay's verdict
        (including ErrNotEnoughVotingPower) is identical to the slow
        path's, whose ok-vector would be all-True for these rows.
        Returns False when any row is uncached or unkeyable (caller
        falls through to the full batched verification)."""
        if sig_cache is None or sig_cache.capacity <= 0:
            return False
        keys = self._commit_row_keys(chain_id, commit)
        if keys is None:
            return False
        cols = commit.columns()
        idxs = cols.present
        if not all(map(sig_cache.seen, map(keys.__getitem__, idxs.tolist()))):
            return False
        _pk, all_powers, _ed = self._device_arrays()
        ok = np.ones(idxs.size, dtype=bool)
        self._replay_commit_full(
            commit, ok, idxs, all_powers[idxs], cols.flags[idxs] == BLOCK_ID_FLAG_COMMIT
        )
        return True

    def _check_commit_size(self, commit) -> None:
        if len(self.validators) != len(commit.signatures):
            raise ErrInvalidCommit(
                f"wrong set size: {len(self.validators)} vs {len(commit.signatures)}"
            )

    def verify_aggregated_commit(
        self,
        chain_id: str,
        block_id,
        height: int,
        agg_commit,
        bls_provider=None,
    ) -> None:
        """Verify +2/3 of this set signed `block_id` at `height` as ONE
        aggregate BLS signature over the canonical commit message
        (types/aggregate.AggregatedCommit).

        Verdict contract (pinned by tests/test_bls.py against per-sig
        verify over the same vote fleets): quorum is tallied over the
        signer bitmap EXACTLY like _replay_commit_full tallies for-block
        rows; the signature check is one pairing against the aggregated
        pubkey of the set bits. Raises the same error types as
        verify_commit. Every flagged signer must hold a BLS key with a
        VERIFIED proof-of-possession (crypto/bls.has_possession) — a
        bitmap bit on a non-BLS or PoP-less validator is an invalid
        commit, not a fallback. The PoP gate is what makes the single
        aggregated pairing sound: without it a rogue key
        pk' = pk_atk - pk_victim forges the victim into aggregates
        (demonstrated in tests/test_bls.py)."""
        from tendermint_tpu.crypto.bls import (
            get_default_bls_provider,
            has_possession,
        )

        err = agg_commit.validate_basic()
        if err:
            raise ErrInvalidCommit(err)
        if height != agg_commit.height:
            raise ErrInvalidCommit(
                f"wrong height: {height} vs {agg_commit.height}"
            )
        if block_id != agg_commit.block_id:
            raise ErrInvalidCommit(
                f"wrong block ID: {block_id} vs {agg_commit.block_id}"
            )
        if len(agg_commit.signers) != len(self.validators):
            raise ErrInvalidCommit(
                f"wrong signer bitmap size: {len(self.validators)} vs "
                f"{len(agg_commit.signers)}"
            )
        pk_table, bls_mask = self.bls_cache()
        mask = agg_commit.signers.as_numpy()
        if not bool(np.all(bls_mask[mask])):
            raise ErrInvalidCommit(
                "aggregated commit flags a validator without a BLS key"
            )
        for i in np.nonzero(mask)[0]:
            if not has_possession(pk_table[i].tobytes()):
                raise ErrInvalidCommit(
                    f"aggregated commit flags validator {int(i)} without a "
                    "verified proof-of-possession (rogue-key defense)"
                )
        _, all_powers, _ = self._device_arrays()
        talled = int(all_powers[mask].sum())
        voting_power_needed = self.total_voting_power() * 2 // 3
        if talled <= voting_power_needed:
            raise ErrNotEnoughVotingPower(
                f"have {talled}, need > {voting_power_needed}"
            )
        v = bls_provider or get_default_bls_provider()
        msg = agg_commit.sign_bytes(chain_id)
        rows = [bytes(pk_table[i].tobytes()) for i in range(len(self.validators))]
        if not v.verify_aggregate(rows, mask, msg, agg_commit.agg_sig):
            raise ErrInvalidCommitSignature(
                "aggregate signature does not verify against the signer set"
            )

    @staticmethod
    def _validate_trust_level(trust_level) -> None:
        """Trust level must be in [1/3, 1] (reference ValidateTrustLevel)."""
        if (
            trust_level is None
            or trust_level.denominator == 0
            or trust_level.numerator * 3 < trust_level.denominator
            or trust_level.numerator > trust_level.denominator
        ):
            raise ValueError(f"trust level must be within [1/3, 1], got {trust_level}")

    @staticmethod
    def _replay_visited(powers, counted, needed: int) -> Tuple[int, int]:
        """How far the reference's loop gets (types/validator_set.go
        :641-668: it returns at the first row it reaches with the tally
        already past ``needed``): (number of rows it visits, the
        for-block power tallied over them). int64 holds every partial
        sum up to that row — a set's total is capped at 2^60."""
        after = (powers * counted).cumsum()
        r = first_true(after > needed)
        if r < 0:
            return after.size, int(after[-1]) if after.size else 0
        # the row that carries the tally past `needed` is still visited
        return r + 1, int(after[r])

    def _replay_commit_full(self, commit, ok, idxs, powers, counted) -> None:
        """Sequential-early-return acceptance over batched results
        (reference loop types/validator_set.go:641-668), as reductions:
        the first visited row with a bad signature rejects; else the
        tally over the visited rows decides."""
        voting_power_needed = self.total_voting_power() * 2 // 3
        visited, talled = self._replay_visited(powers, counted, voting_power_needed)
        r = first_true(~np.asarray(ok[:visited], dtype=bool))
        if r >= 0:
            i = int(idxs[r])
            raise ErrInvalidCommitSignature(
                f"wrong signature #{i} ({commit.signatures[i].validator_address.hex()})"
            )
        if talled > voting_power_needed:
            return
        raise ErrNotEnoughVotingPower(f"have {talled}, need > {voting_power_needed}")

    def verify_commit_trusting(
        self,
        chain_id: str,
        block_id,
        height: int,
        commit,
        trust_level: Fraction,
        provider: Optional[BatchVerifier] = None,
    ) -> None:
        """Verify that `trust_level` (e.g. 1/3) of THIS set signed the
        commit, looking validators up by address (the commit was produced
        by a possibly different set). Reference VerifyCommitTrusting
        types/validator_set.go:754 including verifyCommitBasic; the trust
        level must be in [1/3, 1] (reference ValidateTrustLevel).

        Duplicate-signer detection happens inside the sequential replay,
        after the batched device verification, so a duplicate appearing
        AFTER quorum does not reject -- matching the reference's
        early-return loop exactly."""
        self._validate_trust_level(trust_level)
        self._verify_commit_basic(commit, height, block_id)

        with span("verify.pack"):
            idxs, vals_idx, pk, mg, sg, powers_arr, counted_arr, ed, tpl = (
                self._commit_batch_arrays(chain_id, commit, by_address=True)
            )
        v = provider or get_default_provider()
        ok = self._verify_rows(commit, idxs, vals_idx, pk, mg, sg, ed, v, tpl)
        with span("verify.replay"):
            self._replay_commit_trusting(ok, idxs, vals_idx, powers_arr, counted_arr, trust_level)

    def _replay_commit_trusting(
        self, ok, idxs, vals_idx, powers_arr, counted_arr, trust_level: Fraction
    ) -> None:
        """Sequential replay for the trusting variant (reference loop
        types/validator_set.go:754 region), incl. duplicate-signer check."""
        total = self.total_voting_power()
        needed = total * trust_level.numerator // trust_level.denominator
        visited, talled = self._replay_visited(powers_arr, counted_arr, needed)
        vi = np.asarray(vals_idx[:visited], dtype=np.int64)
        # a row is a double vote when its validator index has a lower
        # visited row; it is checked before the signature on that row
        dup = np.zeros(visited, dtype=bool)
        if visited and np.bincount(vi).max() > 1:
            dup[:] = True
            dup[np.unique(vi, return_index=True)[1]] = False
        r = first_true(dup | ~np.asarray(ok[:visited], dtype=bool))
        if r >= 0:
            if dup[r]:
                raise ErrInvalidCommit(f"double vote from validator index {vi[r]}")
            raise ErrInvalidCommitSignature(f"wrong signature #{idxs[r]}")
        if talled > needed:
            return
        raise ErrNotEnoughVotingPower(f"have {talled}, need > {needed}")

    # -- encoding ----------------------------------------------------------

    def encode(self) -> bytes:
        w = Writer()
        w.write_uvarint(len(self.validators))
        for v in self.validators:
            w.write_bytes(v.encode())
        if self.proposer is not None:
            w.write_bool(True).write_bytes(self.proposer.address)
        else:
            w.write_bool(False)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "ValidatorSet":
        r = Reader(data)
        n = r.read_uvarint()
        vals = [Validator.decode(r.read_bytes()) for _ in range(n)]
        addrs = [v.address for v in vals]
        if len(set(addrs)) != len(addrs):
            raise ValueError("duplicate validator address in encoded set")
        vs = cls.__new__(cls)
        vs.validators = sorted(vals, key=lambda v: v.address)
        vs._addr_index = {v.address: i for i, v in enumerate(vs.validators)}
        vs._total_voting_power = None
        vs.proposer = None
        if r.read_bool():
            addr = r.read_bytes()
            _, vs.proposer = vs.get_by_address(addr)
        if vs.validators:
            vs._update_total_voting_power()
        return vs

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, ValidatorSet)
            and [(v.address, v.voting_power) for v in self.validators]
            == [(v.address, v.voting_power) for v in other.validators]
        )

    def __repr__(self) -> str:
        return f"ValidatorSet{{n={len(self.validators)} power={self.total_voting_power()}}}"


def _safe_add(a: int, b: int) -> int:
    c = a + b
    hi, lo = (1 << 63) - 1, -(1 << 63)
    return hi if c > hi else lo if c < lo else c


def _safe_sub(a: int, b: int) -> int:
    return _safe_add(a, -b)


def _compute_max_min_priority_diff(vals: List[Validator]) -> int:
    ps = [v.proposer_priority for v in vals]
    return max(ps) - min(ps)


# -- cross-height batched commit verification --------------------------------


class CommitVerifySpec:
    """One commit check inside a multi-commit device batch.

    ``mode`` is "full" (ValidatorSet.verify_commit semantics,
    types/validator_set.go:629) or "trusting" (VerifyCommitTrusting :754,
    requires ``trust_level``). The batched driver runs every spec's
    signatures through ONE device call and then replays each spec's
    sequential acceptance on its slice, so per-spec accept/reject is
    identical to calling the method directly.
    """

    __slots__ = ("valset", "chain_id", "block_id", "height", "commit", "mode", "trust_level")

    def __init__(self, valset, chain_id, block_id, height, commit,
                 mode="full", trust_level=None):
        self.valset = valset
        self.chain_id = chain_id
        self.block_id = block_id
        self.height = height
        self.commit = commit
        self.mode = mode
        self.trust_level = trust_level


# what a group none of whose specs passed its pre-checks hands over
_NO_ROWS = tuple(
    np.zeros(shape, dtype=dtype)
    for shape, dtype in (
        (0, np.int32), ((0, 160), np.uint8), (0, np.int32), ((0, 8), np.uint8),
        ((0, 64), np.uint8),
    )
)


class _SpecRows(RowGroups):
    """The rows of a spec list, packed a spec at a time in spec order.

    Packing a spec is its host pre-checks (structure, height/BlockID
    match, set size) and its columns gathered into arrays
    (_commit_batch_arrays); it needs nothing from any other spec. A
    spec that fails contributes no rows and leaves its exception in
    ``results``. ``keys`` and ``take`` are a provider's side
    (crypto/batch.RowGroups), ``finish`` and the parts lists are
    verify_commits_batched's."""

    def __init__(self, specs, results):
        self.specs, self.results = specs, results
        self.left = len(specs)
        # (spec_idx, idxs, vals_idx, powers, counted, rows, ed) of the
        # specs packed so far that passed their pre-checks
        self.segments: list = []
        self.pk, self.mg, self.sg, self.tpl = [], [], [], []
        self._keys = None  # (first spec, end spec, GroupKeys, set key -> places)

    def _pack(self, count: int) -> None:
        """Pack the next ``count`` specs."""
        done = len(self.specs) - self.left
        self.left -= count
        for si in range(done, done + count):
            s = self.specs[si]
            try:
                if s.mode == "trusting":
                    ValidatorSet._validate_trust_level(s.trust_level)
                else:
                    s.valset._check_commit_size(s.commit)
                s.valset._verify_commit_basic(s.commit, s.height, s.block_id)
                with span("verify.pack"):
                    idxs, vals_idx, pk, mg, sg, powers, counted, ed, tpl = (
                        s.valset._commit_batch_arrays(
                            s.chain_id, s.commit, by_address=(s.mode == "trusting")
                        )
                    )
            except Exception as e:
                self.results[si] = e
                continue
            self.segments.append((si, idxs, vals_idx, powers, counted, len(idxs), ed))
            self.pk.append(pk)
            self.mg.append(mg)
            self.sg.append(sg)
            self.tpl.append(tpl)

    def finish(self) -> None:
        """Pack every spec not yet packed."""
        self._pack(self.left)

    def ed25519_sets(self) -> int:
        """How many distinct validator sets the specs check against
        where every key of every one is ed25519 — the shape the cached
        key tables serve, one set or many — else 0."""
        sets: Dict[bytes, bool] = {}
        for s in self.specs:
            key, _, ed = s.valset.batch_cache()
            if key not in sets:
                sets[key] = bool(ed.all())
        return len(sets) if all(sets.values()) else 0

    def keys_of(self, lo: int, hi: int) -> Tuple[GroupKeys, dict]:
        """The distinct keys of specs lo..hi's sets, and each set's
        validators' places among them (None: the set as it lies). One
        set: its own matrix under its batch_cache() key. Several: their
        keys merged in address order, which is every set's own order,
        under a digest of the sets' keys."""
        if self._keys is not None and self._keys[:2] == (lo, hi):
            return self._keys[2:]
        sets = {}
        for s in self.specs[lo:hi]:
            sets.setdefault(s.valset.batch_cache()[0], s.valset)
        if len(sets) == 1:
            (key, vs), = sets.items()
            keys, places = GroupKeys(key, vs.batch_cache()[1]), {key: None}
        else:
            import hashlib

            addrs = [vs.address_column() for vs in sets.values()]
            merged, first = np.unique(np.concatenate(addrs), return_index=True)
            pk = np.concatenate([vs.batch_cache()[1] for vs in sets.values()])[first]
            keys = GroupKeys(hashlib.sha256(b"".join(sets)).digest(), pk)
            places = {k: np.searchsorted(merged, a) for k, a in zip(sets, addrs)}
        self._keys = (lo, hi, keys, places)
        return keys, places

    def keys(self, commits: int) -> GroupKeys:
        done = len(self.specs) - self.left
        return self.keys_of(done, done + min(commits, self.left))[0]

    def stacked(self, lo: int, hi: int, places: dict) -> Tuple:
        """Templated row arguments of segments lo..hi but the
        signatures: (row_idx i32, templates (2k, 160), tmpl_idx, ts8),
        row_idx each row's validator's place among the keys ``places``
        came with (keys_of), each segment's template pair at its offset
        in the stacked template matrix."""
        tpl = self.tpl[lo:hi]
        idx = []
        for seg in self.segments[lo:hi]:
            at = places[self.specs[seg[0]].valset.batch_cache()[0]]
            idx.append(np.asarray(seg[2] if at is None else at[seg[2]], dtype=np.int32))
        return (
            np.concatenate(idx),
            np.concatenate([t[0] for t in tpl], axis=0),
            np.concatenate([t[1] + 2 * k for k, t in enumerate(tpl)]),
            np.concatenate([t[2] for t in tpl], axis=0),
        )

    def take(self, commits: int):
        overlapped = self.left < len(self.specs)
        lo = len(self.segments)
        done = len(self.specs) - self.left
        count = min(commits, self.left)
        _, places = self.keys_of(done, done + count)
        self._pack(count)
        segs = self.segments[lo:]
        if not segs:
            return _NO_ROWS
        if not all(seg[6].all() for seg in segs):
            return None
        if overlapped:
            SEAM_COUNTS.add(overlapped_rows=sum(seg[5] for seg in segs))
        with span("verify.pack"):
            return self.stacked(lo, len(self.segments), places) + (
                np.concatenate(self.sg[lo:], axis=0),
            )


def verify_commits_batched(
    specs: Sequence[CommitVerifySpec],
    provider: Optional[BatchVerifier] = None,
) -> List[Optional[Exception]]:
    """Verify many commits (typically many HEIGHTS) in one device call.

    This is the SURVEY §5.7 chain-length axis: the reference verifies one
    header's commit at a time (lite2/client.go:687 per bisection step,
    blockchain/v2/processor_context.go:42 per fast-sync block); here the
    light client's whole pivot/sequence chain and the fast-sync processor's
    fetched window pack into a single rectangular batch.

    Returns one entry per spec: None on acceptance, else the exception the
    direct method call would have raised. Host-side pre-checks (structure,
    height/BlockID match, set-size) run per spec before packing; a spec
    failing pre-checks contributes no device rows.

    "One device call" is one synchronous provider call whose launches
    are fed as they are packed: when the specs are full-mode commits of
    all-ed25519 validator sets — one set, or a set a height that
    changes a key at a time (a light client's chain, a fast-sync
    window, a pipeline bundle) — and the provider takes row groups
    (crypto/batch.RowGroups), it pulls a launch's worth of commits with
    the distinct keys of their sets, dispatches the launch and pulls
    the next, so all but the first group are packed while the device
    runs (SEAM_COUNTS ``overlapped_rows``; ``multiset_rows`` where the
    list spans several sets). A trusting spec or a provider that cannot
    stream: the rows are packed first and go as one eager batch against
    the keys of all the list's sets. A non-ed25519 key anywhere: the
    generic kernel. Either way the provider may decline (None) at any
    point — cold tables or shape, a failed launch, a non-64-byte
    signature met mid-list: the packing is finished here and the whole
    list goes down the generic path with the rows an eager call would
    have sent, each verified and counted once; the replay runs per spec
    on its own slice.
    """
    results: List[Optional[Exception]] = [None] * len(specs)
    rows = _SpecRows(specs, results)
    v = provider or get_default_provider()
    # whole commits of all-ed25519 sets in validator order: the shape a
    # provider can take a group at a time
    sets = rows.ed25519_sets()
    ok = None
    streamed = (
        sets > 0
        and all(s.mode == "full" for s in specs)
        and getattr(v, "takes_row_groups", False)
    )
    if streamed:
        key0, all_pk0, _ = specs[0].valset.batch_cache()
        ok = v.verify_rows_cached_templated(key0, all_pk0, rows)
    tabled = ok is not None
    rows.finish()
    segments = rows.segments
    if not segments:
        return results
    if ok is None:
        ok, tabled = _verify_packed(specs, rows, v, ed25519=sets > 0, declined=streamed)
    ok = np.asarray(ok)
    if tabled and sets > 1:
        SEAM_COUNTS.add(multiset_rows=len(ok))

    with span("verify.replay"):
        off = 0
        for si, idxs, vals_idx, powers, counted, n, _ed in segments:
            s = specs[si]
            ok_slice = ok[off : off + n]
            off += n
            try:
                if s.mode == "trusting":
                    s.valset._replay_commit_trusting(
                        ok_slice, idxs, vals_idx, powers, counted, s.trust_level
                    )
                else:
                    s.valset._replay_commit_full(s.commit, ok_slice, idxs, powers, counted)
            except Exception as e:
                results[si] = e
    return results


def _verify_packed(
    specs, rows: _SpecRows, v, ed25519: bool, declined: bool
) -> Tuple[np.ndarray, bool]:
    """Verdicts of a fully packed spec list as ONE eager batch, and
    whether the cached key tables gave them: taken when every key of
    every set is ed25519 (``ed25519``; templated form first, unless
    the provider has just declined these rows as groups), else the
    generic kernel, with non-ed25519 rows verified serially by their
    own key type."""
    segments = rows.segments
    pk = np.concatenate(rows.pk, axis=0)
    mg = np.concatenate(rows.mg, axis=0)
    sg = np.concatenate(rows.sg, axis=0)
    ed_all = np.concatenate([seg[6] for seg in segments])
    if not ed_all.all():
        # non-ed25519 validator keys verify serially via their own type
        ok = np.zeros(len(ed_all), dtype=bool)
        sub = np.nonzero(ed_all)[0]
        if sub.size:
            ok[sub] = np.asarray(v.verify_batch(pk[sub], mg[sub], sg[sub]))
        off0 = 0
        for si, idxs, vals_idx, powers, counted, n, ed in segments:
            specs[si].valset._serial_fill_non_ed(
                ok, specs[si].commit, idxs, vals_idx, mg, ed, mg_off=off0
            )
            off0 += n
        return ok, False
    # Every row an ed25519 key's (the fast-sync window / light-client
    # sequential shape: the sets are stable across heights, or change
    # a key at a time): the whole cross-height batch rides the cached
    # key tables — per-window decompression and table builds are
    # hoisted out entirely (eval 3) — against the distinct keys of the
    # list's sets (_SpecRows.keys_of: the one set's own matrix, or the
    # sets' keys merged). The templated form uploads one template pair
    # per HEIGHT plus 12 B/row of deltas instead of 160 B/row of
    # materialized messages — the message upload was the measured
    # bottleneck of the whole multi-height eval (the device sat idle
    # behind H2D).
    ok = None
    if ed25519:
        keys, places = rows.keys_of(0, len(specs))
        all_idx, templates, tmpl_idx, ts8 = rows.stacked(0, len(segments), places)
        f_t = getattr(v, "verify_rows_cached_templated", None)
        if f_t is not None and not declined:
            ok = f_t(keys.digest, keys.pubkeys, all_idx, templates, tmpl_idx, ts8, sg)
        if ok is None:
            f = getattr(v, "verify_rows_cached", None)
            if f is not None:
                ok = f(keys.digest, keys.pubkeys, all_idx, mg, sg)
    if ok is None:
        return np.asarray(v.verify_batch(pk, mg, sg)), False  # ★ ONE device call, all heights
    return np.asarray(ok), True
