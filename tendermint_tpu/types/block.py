"""Block, Header, Commit, CommitSig, BlockID.

Reference: types/block.go -- Block :38, Header :282, Header.Hash :393
(merkle root of 14 field encodings), Commit :572, CommitSig :468,
Commit.VoteSignBytes :637, BlockID :957 region.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from operator import attrgetter
from typing import List, Optional

import numpy as np

from tendermint_tpu.codec import signbytes
from tendermint_tpu.codec.binary import Reader, Writer
from tendermint_tpu.codec.signbytes import PRECOMMIT_TYPE
from tendermint_tpu.crypto import merkle
from tendermint_tpu.crypto.batch import SEAM_COUNTS
from tendermint_tpu.types.tx import Txs
from tendermint_tpu.utils.trace import span
from tendermint_tpu.version import BLOCK_PROTOCOL

MAX_HEADER_BYTES = 653

# Max signature width over the registered key schemes: ed25519/
# secp256k1/sr25519 = 64, BLS12-381 G2 = 96 (crypto/bls.py). Reference
# MaxSignatureSize, widened for the signature-aggregation track —
# every sig-size bound (CommitSig/Vote/Proposal validate_basic, the
# VoteSet byte cap, commit batch packing) derives from here so the
# accepted wire language can never drift per call site.
MAX_SIGNATURE_SIZE = 96

# CommitSig BlockIDFlag (reference types/block.go:437-447)
BLOCK_ID_FLAG_ABSENT = 1
BLOCK_ID_FLAG_COMMIT = 2
BLOCK_ID_FLAG_NIL = 3


@dataclass
class PartSetHeader:
    total: int = 0
    hash: bytes = b""

    def is_zero(self) -> bool:
        return self.total == 0 and len(self.hash) == 0

    def validate_basic(self) -> Optional[str]:
        if self.total < 0:
            return "negative Total"
        if len(self.hash) not in (0, 32):
            return "wrong Hash size"
        return None

    def encode(self) -> bytes:
        return Writer().write_u32(self.total).write_bytes(self.hash).bytes()

    @classmethod
    def decode(cls, data: bytes) -> "PartSetHeader":
        r = Reader(data)
        return cls(total=r.read_u32(), hash=r.read_bytes())

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, PartSetHeader)
            and self.total == other.total
            and self.hash == other.hash
        )

    def __repr__(self) -> str:
        return f"{self.total}:{self.hash.hex()[:12]}"


@dataclass
class BlockID:
    hash: bytes = b""
    parts: PartSetHeader = field(default_factory=PartSetHeader)

    def is_zero(self) -> bool:
        return len(self.hash) == 0 and self.parts.is_zero()

    def is_complete(self) -> bool:
        return len(self.hash) == 32 and self.parts.total > 0 and len(self.parts.hash) == 32

    def validate_basic(self) -> Optional[str]:
        if len(self.hash) not in (0, 32):
            return "wrong Hash"
        err = self.parts.validate_basic()
        if err:
            return f"wrong PartsHeader: {err}"
        return None

    def key(self) -> bytes:
        """Map key for vote tallies (reference BlockID.Key types/block.go:993)."""
        return self.hash + self.parts.encode()

    def encode(self) -> bytes:
        return Writer().write_bytes(self.hash).write_bytes(self.parts.encode()).bytes()

    @classmethod
    def decode(cls, data: bytes) -> "BlockID":
        r = Reader(data)
        h = r.read_bytes()
        ps = PartSetHeader.decode(r.read_bytes())
        return cls(hash=h, parts=ps)

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, BlockID) and self.hash == other.hash and self.parts == other.parts
        )

    def __hash__(self) -> int:
        return hash(self.key())

    def __repr__(self) -> str:
        return f"{self.hash.hex()[:12]}:{self.parts}"


@dataclass
class CommitSig:
    """One validator's signature slot in a commit (types/block.go:468)."""

    block_id_flag: int = BLOCK_ID_FLAG_ABSENT
    validator_address: bytes = b""
    timestamp_ns: int = 0
    signature: bytes = b""

    @classmethod
    def absent(cls) -> "CommitSig":
        return cls(block_id_flag=BLOCK_ID_FLAG_ABSENT)

    def for_block(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_COMMIT

    def absent_(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_ABSENT

    def block_id(self, commit_block_id: BlockID) -> BlockID:
        """Reconstruct the vote's BlockID from the flag
        (reference CommitSig.BlockID types/block.go:530)."""
        if self.block_id_flag == BLOCK_ID_FLAG_COMMIT:
            return commit_block_id
        return BlockID()

    def validate_basic(self) -> Optional[str]:
        if self.block_id_flag not in (
            BLOCK_ID_FLAG_ABSENT,
            BLOCK_ID_FLAG_COMMIT,
            BLOCK_ID_FLAG_NIL,
        ):
            return f"unknown BlockIDFlag: {self.block_id_flag}"
        if self.block_id_flag == BLOCK_ID_FLAG_ABSENT:
            if self.validator_address:
                return "validator address is present for absent CommitSig"
            if self.signature:
                return "signature is present for absent CommitSig"
        else:
            if len(self.validator_address) != 20:
                return "expected ValidatorAddress size 20"
            if not self.signature:
                return "signature is missing"
            if len(self.signature) > MAX_SIGNATURE_SIZE:
                return "signature too big"
        return None

    def encode(self) -> bytes:
        w = Writer()
        w.write_u8(self.block_id_flag)
        w.write_bytes(self.validator_address)
        w.write_i64(self.timestamp_ns)
        w.write_bytes(self.signature)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "CommitSig":
        r = Reader(data)
        return cls(r.read_u8(), r.read_bytes(), r.read_i64(), r.read_bytes())


_FLAG_OF = attrgetter("block_id_flag")
_TIMESTAMP_OF = attrgetter("timestamp_ns")
_SIGNATURE_OF = attrgetter("signature")
_ADDRESS_OF = attrgetter("validator_address")


def _int_column(values, n: int) -> np.ndarray:
    """(n,) column of the integers ``values()`` yields, read in one C
    pass: u8 when every value fits a byte (all a decoded commit can
    hold), i64 for what only code can construct (a flag of 300, a
    400-byte signature) — the checks that follow read either alike."""
    try:
        return np.frombuffer(bytes(values()), dtype=np.uint8)
    except ValueError:
        return np.fromiter(values(), dtype=np.int64, count=n)


# What CommitSig.validate_basic admits, by flag value (take(...,
# mode="clip") reads a flag that is no byte as 0 or 255, unknown
# both): the address length, the least and the most signature bytes.
# A flag outside 1-3 admits no signature length at all.
_SLOT_LIMITS = np.empty((3, 256), dtype=np.int64)
_SLOT_LIMITS[:] = [[0], [1], [0]]
_SLOT_LIMITS[:, BLOCK_ID_FLAG_ABSENT] = 0
_SLOT_LIMITS[:, [BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL]] = [[20], [1], [MAX_SIGNATURE_SIZE]]


def first_true(mask: np.ndarray) -> int:
    """Index of the first True of a 1-D mask, -1 when it has none."""
    if not mask.size:
        return -1
    i = int(mask.argmax())
    return i if mask[i] else -1


class CommitColumns:
    """A commit's signature slots read ONCE into columns; the verify
    seam's structural check, sign-bytes parts, pack and replay
    (types/validator_set.py) work on these arrays, so a 10,000-slot
    commit is never walked object by object.

    ``flags`` (N,) block-id flags, ``timestamp_ns`` (N,) i64,
    ``sig_lens`` / ``addr_lens`` (N,) byte lengths, ``present`` (P,)
    i64 indices of the non-absent slots in order with their
    ``present_sig_lens`` (P,), ``sig_buf`` every slot's signature bytes
    back to back (absent slots hold none in a valid commit, so the
    buffer is the present rows'). All read-only: they are shared by
    every verification of the commit."""

    __slots__ = (
        "flags", "timestamp_ns", "sig_lens", "addr_lens",
        "present", "present_sig_lens", "sig_buf",
    )

    def __init__(self, signatures: List["CommitSig"]):
        n = len(signatures)
        sigs = list(map(_SIGNATURE_OF, signatures))
        self.flags = _int_column(lambda: map(_FLAG_OF, signatures), n)
        self.timestamp_ns = np.fromiter(
            map(_TIMESTAMP_OF, signatures), dtype=np.int64, count=n
        )
        self.sig_lens = _int_column(lambda: map(len, sigs), n)
        self.addr_lens = _int_column(
            lambda: map(len, map(_ADDRESS_OF, signatures)), n
        )
        self.present = (self.flags != BLOCK_ID_FLAG_ABSENT).nonzero()[0]
        self.present.flags.writeable = False
        self.present_sig_lens = self.sig_lens[self.present]
        self.sig_buf = np.frombuffer(b"".join(sigs), dtype=np.uint8)
        SEAM_COUNTS.add(column_rows=n)

    def first_invalid(self) -> int:
        """Index of the first slot CommitSig.validate_basic rejects,
        -1 when it accepts every one (the same checks as masks)."""
        want_addr, min_sig, max_sig = _SLOT_LIMITS.take(self.flags, axis=1, mode="clip")
        return first_true(
            (self.addr_lens != want_addr)
            | (self.sig_lens < min_sig)
            | (self.sig_lens > max_sig)
        )

    def sig_rows(self, width: int) -> np.ndarray:
        """(P, width) u8: the present rows' signatures clamped or
        zero-padded to ``width`` — the buffer itself when every one of
        them is ``width`` bytes, a gather by offsets when not."""
        lens = self.present_sig_lens
        if self.sig_buf.size == width * lens.size and not np.count_nonzero(lens != width):
            return self.sig_buf.reshape(lens.size, width)
        starts = (self.sig_lens.cumsum(dtype=np.int64) - self.sig_lens)[self.present]
        col = np.arange(width)
        padded = np.concatenate([self.sig_buf, np.zeros(width, dtype=np.uint8)])
        return np.where(
            col < lens[:, None], padded[starts[:, None] + col], 0
        ).astype(np.uint8)


@dataclass
class Commit:
    """+2/3 precommits for a block (types/block.go:572)."""

    height: int
    round: int
    block_id: BlockID
    signatures: List[CommitSig]

    _hash: Optional[bytes] = field(default=None, repr=False, compare=False)

    def __deepcopy__(self, memo):
        """Deep copies get a MEMO-FREE commit: the hash / encode /
        validate / column / row-key caches assume immutability (the
        columns hold the signature bytes themselves), and the one
        legitimate reason to deep-copy a commit is to build a variant
        (tests tamper with signatures; evidence construction mutates) —
        a carried row-key cache on a then-mutated copy could otherwise
        vouch for bytes that were never verified."""
        import copy as _copy

        return Commit(
            height=self.height,
            round=self.round,
            block_id=_copy.deepcopy(self.block_id, memo),
            signatures=_copy.deepcopy(self.signatures, memo),
        )

    def columns(self) -> CommitColumns:
        """The signature slots as columns, read once per Commit object
        (memoized under hash()'s immutability contract, like
        ``_parts_cache``; a deep copy starts without it)."""
        cols = getattr(self, "_cols_cache", None)
        if cols is None:
            with span("verify.columns"):
                cols = self._cols_cache = CommitColumns(self.signatures)
        return cols

    def vote_sign_bytes(self, chain_id: str, idx: int) -> bytes:
        """Canonical sign-bytes for signature `idx` (reference
        Commit.VoteSignBytes types/block.go:637). Fixed 160-byte layout --
        N of these stack into the (N,160) device batch."""
        cs = self.signatures[idx]
        bid = cs.block_id(self.block_id)
        return signbytes.canonical_sign_bytes(
            msg_type=PRECOMMIT_TYPE,
            height=self.height,
            round_=self.round,
            block_hash=bid.hash,
            parts_total=bid.parts.total,
            parts_hash=bid.parts.hash,
            timestamp_ns=cs.timestamp_ns,
            chain_id=chain_id,
        )

    def sign_bytes_parts(self, chain_id: str):
        """Templated canonical sign-bytes for ALL signatures:
        (templates (2, 160) u8 [row 0 = for-block, row 1 = nil],
        tmpl_idx (N,) i32, ts8 (N, 8) u8 big-endian i64 timestamps).

        Within one commit the rows differ only in timestamp and the
        nil-vs-commit BlockID variant (the property the fixed-width
        layout exists for — reference Commit.VoteSignBytes
        types/block.go:637 varies only CommitSig fields), so row r is
        templates[tmpl_idx[r]] with ts8[r] spliced at the timestamp
        offset. Device verifiers materialize rows ON DEVICE
        (ops/ed25519.materialize_sign_bytes) so per-row H2D carries 12
        bytes instead of 160; sign_bytes_matrix() is the host-side
        materialization of the same parts. Absent rows get tmpl_idx 1 —
        callers filter them before verification.

        Memoized per chain id: the same commit is re-verified at every
        validation pass (prevote / lock / finalize all validate the
        block), and signatures are never mutated after construction —
        hash() relies on the same immutability."""
        cached = getattr(self, "_parts_cache", None)
        if cached is not None and cached[0] == chain_id:
            return cached[1]
        template = signbytes.canonical_sign_bytes(
            msg_type=PRECOMMIT_TYPE,
            height=self.height,
            round_=self.round,
            block_hash=self.block_id.hash,
            parts_total=self.block_id.parts.total,
            parts_hash=self.block_id.parts.hash,
            timestamp_ns=0,
            chain_id=chain_id,
        )
        templates = np.frombuffer(template * 2, dtype=np.uint8).reshape(2, -1).copy()
        templates[1, signbytes.BLOCK_ID_OFFSET : signbytes.BLOCK_ID_END] = 0
        cols = self.columns()
        ts8 = cols.timestamp_ns.astype(">i8").view(np.uint8).reshape(-1, 8)
        tmpl_idx = (cols.flags != BLOCK_ID_FLAG_COMMIT).astype(np.int32)
        out = (templates, tmpl_idx, ts8)
        self._parts_cache = (chain_id, out)
        return out

    def sign_bytes_matrix(self, chain_id: str) -> "np.ndarray":
        """Vectorized canonical sign-bytes for ALL signatures at once:
        (N, 160) uint8 (absent rows are zeros — callers filter by index).
        Host-side materialization of sign_bytes_parts — ~50x cheaper
        than N Python struct.pack calls on a 10k-validator commit."""
        templates, tmpl_idx, ts8 = self.sign_bytes_parts(chain_id)
        mat = templates[tmpl_idx]
        mat[:, signbytes.TIMESTAMP_OFFSET : signbytes.TIMESTAMP_OFFSET + 8] = ts8
        absent = self.columns().flags == BLOCK_ID_FLAG_ABSENT
        if absent.any():
            mat[absent] = 0
        return mat

    def get_vote(self, val_idx: int) -> "Vote":
        """Reconstruct the precommit Vote behind signature `val_idx`
        (reference Commit.GetVote types/block.go:619)."""
        from tendermint_tpu.types.vote import Vote

        cs = self.signatures[val_idx]
        return Vote(
            vote_type=PRECOMMIT_TYPE,
            height=self.height,
            round=self.round,
            block_id=cs.block_id(self.block_id),
            timestamp_ns=cs.timestamp_ns,
            validator_address=cs.validator_address,
            validator_index=val_idx,
            signature=cs.signature,
        )

    def size(self) -> int:
        return len(self.signatures)

    def is_commit(self) -> bool:
        return len(self.signatures) > 0

    def bit_array(self):
        from tendermint_tpu.utils.bits import BitArray

        ba = BitArray(len(self.signatures))
        for i, cs in enumerate(self.signatures):
            ba.set_index(i, not cs.absent_())
        return ba

    def hash(self) -> bytes:
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [cs.encode() for cs in self.signatures]
            )
        return self._hash

    def validate_basic(self) -> Optional[str]:
        # memoized (commit immutable once assembled — same contract as
        # hash()): every verify_commit pass re-runs these per-signature
        # structural checks
        cached = getattr(self, "_vb_cache", None)
        if cached is not None:
            return cached[0]
        err = self._validate_basic_uncached()
        self._vb_cache = (err,)
        return err

    def _validate_basic_uncached(self) -> Optional[str]:
        if self.height < 0:
            return "negative Height"
        if self.round < 0:
            return "negative Round"
        if self.height >= 1:
            if self.block_id.is_zero():
                return "commit cannot be for nil block"
            if not self.signatures:
                return "no signatures in commit"
            i = self.columns().first_invalid()
            if i >= 0:
                return f"wrong CommitSig #{i}: {self.signatures[i].validate_basic()}"
        return None

    def encode(self) -> bytes:
        # memoized: commits are immutable once assembled (hash() shares
        # the contract); block/state saves re-encode the same commit
        enc = getattr(self, "_enc_cache", None)
        if enc is not None:
            return enc
        w = Writer()
        w.write_u64(self.height).write_i64(self.round)
        w.write_bytes(self.block_id.encode())
        w.write_uvarint(len(self.signatures))
        for cs in self.signatures:
            w.write_bytes(cs.encode())
        enc = w.bytes()
        self._enc_cache = enc
        return enc

    @classmethod
    def decode(cls, data: bytes) -> "Commit":
        r = Reader(data)
        height = r.read_u64()
        rnd = r.read_i64()
        bid = BlockID.decode(r.read_bytes())
        n = r.read_uvarint()
        sigs = [CommitSig.decode(r.read_bytes()) for _ in range(n)]
        return cls(height, rnd, bid, sigs)

    def __repr__(self) -> str:
        return f"Commit{{h={self.height} r={self.round} bid={self.block_id} n={len(self.signatures)}}}"


def new_commit(height: int, round_: int, block_id: BlockID, sigs: List[CommitSig]) -> Commit:
    return Commit(height=height, round=round_, block_id=block_id, signatures=sigs)


@dataclass
class Header:
    """Block header; hash is the merkle root of the 14 field encodings
    (reference Header.Hash types/block.go:393)."""

    chain_id: str = ""
    height: int = 0
    time_ns: int = 0
    last_block_id: BlockID = field(default_factory=BlockID)
    last_commit_hash: bytes = b""
    data_hash: bytes = b""
    validators_hash: bytes = b""
    next_validators_hash: bytes = b""
    consensus_hash: bytes = b""
    app_hash: bytes = b""
    last_results_hash: bytes = b""
    evidence_hash: bytes = b""
    proposer_address: bytes = b""
    version_block: int = BLOCK_PROTOCOL
    version_app: int = 0

    def hash(self) -> Optional[bytes]:
        # Reference returns nil if ValidatorsHash unset (header not complete).
        if not self.validators_hash:
            return None
        fields = [
            Writer().write_u64(self.version_block).write_u64(self.version_app).bytes(),
            self.chain_id.encode("utf-8"),
            Writer().write_u64(self.height).bytes(),
            Writer().write_i64(self.time_ns).bytes(),
            self.last_block_id.encode(),
            self.last_commit_hash,
            self.data_hash,
            self.validators_hash,
            self.next_validators_hash,
            self.consensus_hash,
            self.app_hash,
            self.last_results_hash,
            self.evidence_hash,
            self.proposer_address,
        ]
        return merkle.hash_from_byte_slices(fields)

    def validate_basic(self) -> Optional[str]:
        if len(self.chain_id) > 50:
            return "chainID is too long"
        if self.height < 0:
            return "negative Height"
        if self.height == 0:
            return "zero Height"
        err = self.last_block_id.validate_basic()
        if err:
            return f"wrong LastBlockID: {err}"
        for name, h in (
            ("LastCommitHash", self.last_commit_hash),
            ("DataHash", self.data_hash),
            ("EvidenceHash", self.evidence_hash),
            ("ValidatorsHash", self.validators_hash),
            ("NextValidatorsHash", self.next_validators_hash),
            ("ConsensusHash", self.consensus_hash),
            ("LastResultsHash", self.last_results_hash),
        ):
            if len(h) not in (0, 32):
                return f"wrong {name}"
        if len(self.proposer_address) not in (0, 20):
            return "invalid ProposerAddress length"
        return None

    def encode(self) -> bytes:
        w = Writer()
        w.write_u64(self.version_block).write_u64(self.version_app)
        w.write_str(self.chain_id).write_u64(self.height).write_i64(self.time_ns)
        w.write_bytes(self.last_block_id.encode())
        for h in (
            self.last_commit_hash,
            self.data_hash,
            self.validators_hash,
            self.next_validators_hash,
            self.consensus_hash,
            self.app_hash,
            self.last_results_hash,
            self.evidence_hash,
            self.proposer_address,
        ):
            w.write_bytes(h)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "Header":
        r = Reader(data)
        vb = r.read_u64()
        va = r.read_u64()
        cid = r.read_str()
        height = r.read_u64()
        t = r.read_i64()
        lbi = BlockID.decode(r.read_bytes())
        (
            lch,
            dh,
            vh,
            nvh,
            ch,
            ah,
            lrh,
            eh,
            pa,
        ) = (r.read_bytes() for _ in range(9))
        return cls(
            chain_id=cid,
            height=height,
            time_ns=t,
            last_block_id=lbi,
            last_commit_hash=lch,
            data_hash=dh,
            validators_hash=vh,
            next_validators_hash=nvh,
            consensus_hash=ch,
            app_hash=ah,
            last_results_hash=lrh,
            evidence_hash=eh,
            proposer_address=pa,
            version_block=vb,
            version_app=va,
        )


@dataclass
class Data:
    """Block body: transactions (types/block.go Data)."""

    txs: Txs = field(default_factory=Txs)
    _hash: Optional[bytes] = field(default=None, repr=False, compare=False)

    def hash(self) -> bytes:
        if self._hash is None:
            self._hash = self.txs.hash()
        return self._hash

    def encode(self) -> bytes:
        w = Writer()
        w.write_uvarint(len(self.txs))
        for tx in self.txs:
            w.write_bytes(bytes(tx))
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "Data":
        r = Reader(data)
        n = r.read_uvarint()
        return cls(txs=Txs([r.read_bytes() for _ in range(n)]))


@dataclass
class EvidenceData:
    evidence: list = field(default_factory=list)
    _hash: Optional[bytes] = field(default=None, repr=False, compare=False)

    def hash(self) -> bytes:
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices([ev.bytes_() for ev in self.evidence])
        return self._hash

    def encode(self) -> bytes:
        from tendermint_tpu.types.evidence import encode_evidence

        w = Writer()
        w.write_uvarint(len(self.evidence))
        for ev in self.evidence:
            w.write_bytes(encode_evidence(ev))
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "EvidenceData":
        from tendermint_tpu.types.evidence import decode_evidence

        r = Reader(data)
        n = r.read_uvarint()
        return cls(evidence=[decode_evidence(r.read_bytes()) for _ in range(n)])


@dataclass
class Block:
    header: Header
    data: Data
    evidence: EvidenceData
    last_commit: Optional[Commit]

    def hash(self) -> Optional[bytes]:
        # Memoized after the first complete hash: a block is immutable
        # once assembled (the reference re-derives it per call, but a
        # 256-node simulation hashes the same decoded block ~10x per
        # node on the validate/commit path). fill_header() is keyed on
        # the same completeness check, so a cached hash can only exist
        # for a filled header.
        h = getattr(self, "_hash_cache", None)
        if h is not None:
            return h
        if self.last_commit is None and self.header.height > 1:
            return None
        self.fill_header()
        h = self.header.hash()
        if h is not None:
            self._hash_cache = h
        return h

    def fill_header(self) -> None:
        """Populate derived header hashes (reference Block.fillHeader
        types/block.go:98)."""
        h = self.header
        if not h.last_commit_hash and self.last_commit is not None:
            h.last_commit_hash = self.last_commit.hash()
        if not h.data_hash:
            h.data_hash = self.data.hash()
        if not h.evidence_hash:
            h.evidence_hash = self.evidence.hash()

    def validate_basic(self) -> Optional[str]:
        # memoized like hash(): blocks are immutable once assembled, and
        # validate_block re-runs this at every validation pass
        cached = getattr(self, "_vb_cache", None)
        if cached is not None:
            return cached[0]
        err = self._validate_basic_uncached()
        self._vb_cache = (err,)
        return err

    def _validate_basic_uncached(self) -> Optional[str]:
        err = self.header.validate_basic()
        if err:
            return f"invalid header: {err}"
        if self.last_commit is None:
            if self.header.height != 1:
                return "nil LastCommit"
        else:
            err = self.last_commit.validate_basic()
            if self.header.height > 1 and err:
                return f"wrong LastCommit: {err}"
            if self.last_commit.hash() != self.header.last_commit_hash:
                return "wrong LastCommitHash"
        if self.data.hash() != self.header.data_hash:
            return "wrong DataHash"
        if self.evidence.hash() != self.header.evidence_hash:
            return "wrong EvidenceHash"
        return None

    def make_part_set(self, part_size: int = 65536):
        from tendermint_tpu.types.part_set import PartSet

        self.fill_header()
        return PartSet.from_data(self.encode(), part_size)

    def encode(self) -> bytes:
        w = Writer()
        w.write_bytes(self.header.encode())
        w.write_bytes(self.data.encode())
        w.write_bytes(self.evidence.encode())
        if self.last_commit is None:
            w.write_bool(False)
        else:
            w.write_bool(True).write_bytes(self.last_commit.encode())
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "Block":
        r = Reader(data)
        header = Header.decode(r.read_bytes())
        body = Data.decode(r.read_bytes())
        ev = EvidenceData.decode(r.read_bytes())
        lc = Commit.decode(r.read_bytes()) if r.read_bool() else None
        return cls(header=header, data=body, evidence=ev, last_commit=lc)

    def __repr__(self) -> str:
        h = self.hash()
        return f"Block{{h={self.header.height} hash={h.hex()[:12] if h else None}}}"


def make_block(
    height: int,
    txs: Txs,
    last_commit: Optional[Commit],
    evidence: list,
) -> Block:
    """Reference MakeBlock types/block.go:1004."""
    block = Block(
        header=Header(height=height),
        data=Data(txs=txs),
        evidence=EvidenceData(evidence=list(evidence)),
        last_commit=last_commit,
    )
    block.fill_header()
    return block
