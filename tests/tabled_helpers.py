"""Signed rows for the tabled-verify test files (kernels, the model's
cached-table path, the templated commit-shaped path)."""

import numpy as np

from tendermint_tpu.ops import ref_ed25519 as ref


def sign_rows(n, msg_len=100, seed=7):
    rng = np.random.default_rng(seed)
    seeds = [rng.bytes(32) for _ in range(n)]
    pks = [ref.pubkey_from_seed(s) for s in seeds]
    msgs = [rng.bytes(msg_len) for _ in range(n)]
    sigs = [ref.sign(s, m) for s, m in zip(seeds, msgs)]
    return pks, msgs, sigs


def arrs(pks, msgs, sigs):
    n = len(pks)
    return (
        np.frombuffer(b"".join(pks), dtype=np.uint8).reshape(n, 32),
        np.frombuffer(b"".join(msgs), dtype=np.uint8).reshape(n, len(msgs[0])),
        np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(n, 64),
    )
