"""Batch ed25519 verification: device kernel vs reference acceptance.

The security-critical property: the device batch accepts a signature IFF
the serial reference (Go x/crypto semantics, mirrored by
ops/ref_ed25519.py and by OpenSSL for honest inputs) accepts it --
including s-malleability rejection and corrupted R/A/msg rows mixed into
the same batch. RFC 8032 vector 1 is pinned as a golden.
"""

import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tendermint_tpu.ops import ed25519 as dev
from tendermint_tpu.ops import ref_ed25519 as ref

rng = random.Random(42)
MSG_LEN = 160


def _pack(rows):
    pks = np.stack([np.frombuffer(r[0], dtype=np.uint8) for r in rows])
    msgs = np.stack([np.frombuffer(r[1], dtype=np.uint8) for r in rows])
    sigs = np.stack([np.frombuffer(r[2], dtype=np.uint8) for r in rows])
    return jnp.asarray(pks), jnp.asarray(msgs), jnp.asarray(sigs)


@pytest.fixture(scope="module")
def mixed_batch():
    rows, want = [], []
    for i in range(15):
        seed = bytes(rng.randrange(256) for _ in range(32))
        msg = bytes(rng.randrange(256) for _ in range(MSG_LEN))
        pk = ref.pubkey_from_seed(seed)
        sig = ref.sign(seed, msg)
        kind = i % 5
        if kind == 1:
            sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
        elif kind == 2:
            msg = bytes([msg[0] ^ 0xFF]) + msg[1:]
        elif kind == 3:
            sig = bytes([sig[0] ^ 4]) + sig[1:]
        elif kind == 4:
            pk = bytes(rng.randrange(256) for _ in range(32))
        rows.append((pk, msg, sig))
        want.append(ref.verify(pk, msg, sig))
    # non-canonical s (s + L): valid mod L but must be rejected
    seed = b"\x07" * 32
    msg = b"m" * MSG_LEN
    sig = ref.sign(seed, msg)
    s = int.from_bytes(sig[32:], "little")
    assert s + ref.L < 2**256
    rows.append(
        (ref.pubkey_from_seed(seed), msg, sig[:32] + (s + ref.L).to_bytes(32, "little"))
    )
    want.append(False)
    return rows, want


def test_verify_core_matches_reference(mixed_batch):
    rows, want = mixed_batch
    pks, msgs, sigs = _pack(rows)
    ok = np.asarray(jax.jit(dev.verify_core)(pks, msgs, sigs))
    assert [bool(b) for b in ok] == want


def test_rfc8032_vector():
    pk = bytes.fromhex("d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a")
    sig = bytes.fromhex(
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
        "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"
    )
    # empty message -> pad batch row with L=0 message array
    pks = jnp.asarray(np.frombuffer(pk, dtype=np.uint8)[None].repeat(16, 0))
    msgs = jnp.zeros((16, 0), dtype=jnp.uint8)
    sigs = jnp.asarray(np.frombuffer(sig, dtype=np.uint8)[None].repeat(16, 0))
    ok = np.asarray(jax.jit(dev.verify_core)(pks, msgs, sigs))
    assert ok.all()


class TestVerifierModel:
    def test_model_verify_and_commit(self, mixed_batch):
        from tendermint_tpu.models.verifier import VerifierModel

        from tendermint_tpu.crypto.batch import TPUBatchVerifier

        rows, want = mixed_batch
        pks, msgs, sigs = _pack(rows)
        prov = TPUBatchVerifier()
        model = prov.model
        assert isinstance(model, VerifierModel)
        ok = model.verify(np.asarray(pks), np.asarray(msgs), np.asarray(sigs))
        assert [bool(b) for b in ok] == want

        # the commit form: the same program, the tally summed on the
        # host over its verdicts (a verified row that is not counted —
        # a nil vote — adds nothing)
        powers = np.arange(1, len(rows) + 1, dtype=np.int64) * 7
        counted = np.ones(len(rows), dtype=bool)
        counted[0] = False
        ok2, tally = prov.verify_commit_batch(
            np.asarray(pks), np.asarray(msgs), np.asarray(sigs), powers, counted
        )
        assert [bool(b) for b in ok2] == want
        assert tally == sum(int(p) for p, w, c in zip(powers, want, counted) if w and c)

    def test_model_sharded_matches_unsharded(self, mixed_batch, cpu_mesh):
        from tendermint_tpu.models.verifier import VerifierModel

        rows, want = mixed_batch
        pks, msgs, sigs = _pack(rows)
        model = VerifierModel(mesh=cpu_mesh)
        ok = model.verify(np.asarray(pks), np.asarray(msgs), np.asarray(sigs))
        assert [bool(b) for b in ok] == want


class TestTPUProviderIntegration:
    """The full seam: ValidatorSet.verify_commit through the TPU provider."""

    def test_commit_verification_device_vs_host(self):
        from tendermint_tpu.crypto.batch import make_provider
        from tests.test_validator_set import make_commit, make_vals

        vs, by_addr = make_vals([1] * 8)
        commit, bid = make_commit(vs, by_addr)
        tpu = make_provider("tpu")
        vs.verify_commit("test-chain", bid, 5, commit, provider=tpu)

        # corrupt a needed signature: both providers must reject (on a
        # deep copy — a verified commit is immutable, its memos vouch
        # for its bytes; the copy starts without them)
        import copy

        commit = copy.deepcopy(commit)
        commit.signatures[0].signature = bytes(64)
        import pytest as _pytest

        from tendermint_tpu.types.validator_set import ErrInvalidCommitSignature

        with _pytest.raises(ErrInvalidCommitSignature):
            vs.verify_commit("test-chain", bid, 5, commit, provider=tpu)
