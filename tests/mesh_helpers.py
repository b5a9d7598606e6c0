"""The signed batch and the pair of models (8-device mesh, single device)
that the mesh test files share (test_mesh_parity.py,
test_mesh_parity_tabled.py, test_mesh_router.py: the batch only)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from tendermint_tpu.crypto.batch import BatchVerifier
from tendermint_tpu.models.verifier import VerifierModel
from tendermint_tpu.parallel import make_mesh

N_DEV = 8


def signed_batch(n, msg_len=96, seed=11):
    try:
        from cryptography.hazmat.primitives import serialization
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )
    except ImportError:  # no OpenSSL wheel: pure-Python fallback
        from tendermint_tpu.crypto.fallback import Ed25519PrivateKey, serialization

    rng = np.random.RandomState(seed)
    keys = [
        Ed25519PrivateKey.from_private_bytes(bytes(rng.bytes(32)))
        for _ in range(min(n, 16))
    ]
    pubs = [
        k.public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw
        )
        for k in keys
    ]
    pks = np.zeros((n, 32), dtype=np.uint8)
    msgs = np.zeros((n, msg_len), dtype=np.uint8)
    sigs = np.zeros((n, 64), dtype=np.uint8)
    for i in range(n):
        msg = rng.bytes(msg_len)
        pks[i] = np.frombuffer(pubs[i % len(keys)], dtype=np.uint8)
        msgs[i] = np.frombuffer(msg, dtype=np.uint8)
        sigs[i] = np.frombuffer(keys[i % len(keys)].sign(msg), dtype=np.uint8)
    return pks, msgs, sigs


class _OverModel(BatchVerifier):
    """A provider over one VerifierModel: verify_batch is model.verify,
    verify_commit_batch the host tally every provider inherits."""

    def __init__(self, model):
        self.model = model

    def verify_batch(self, pubkeys, msgs, sigs, msg_lens=None):
        return self.model.verify(pubkeys, msgs, sigs, msg_lens=msg_lens)


def verify_then_tally(model, pk, mg, sg, powers, counted):
    return _OverModel(model).verify_commit_batch(pk, mg, sg, powers, counted)


@pytest.fixture(scope="module")
def models():
    devs = jax.devices()
    if len(devs) < N_DEV:
        pytest.skip(f"need {N_DEV} virtual devices, have {len(devs)}")
    return (
        VerifierModel(mesh=make_mesh(devs[:N_DEV]), block_on_compile=True),
        VerifierModel(block_on_compile=True),
    )
