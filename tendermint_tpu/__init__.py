"""tendermint-tpu: a TPU-native BFT state-machine-replication framework.

A from-scratch reimplementation of the capabilities of Tendermint Core
v0.33.4 (the reference implementation lives at /root/reference), designed
TPU-first:

- The consensus/gossip/state machinery is host-side Python (asyncio event
  loops replace goroutines; determinism of the consensus transition loop is
  preserved by a single-task design, mirroring the reference's single
  ``receiveRoutine`` at consensus/state.go:602).
- The cryptographic hot path -- ed25519 vote-signature verification and
  voting-power quorum tally (reference: types/vote_set.go:142,
  types/validator_set.go:629, lite2/verifier.go) -- runs on TPU as batched
  JAX programs: vmap'd limb-arithmetic ed25519 in ``tendermint_tpu.ops``
  returning a verdict per signature (the tally is a host column sum over
  them), sharded over a ``jax.sharding.Mesh`` for multi-chip scale in
  ``tendermint_tpu.parallel``.

Layer map (mirrors SURVEY.md section 1):

    cli/, node/          L7/L6  operator tooling, node assembly, RPC
    consensus/, blockchain/, mempool/, evidence   L5  reactors
    state/, store/       L4  block execution + storage
    abci/                L3  application boundary
    p2p/                 L2  networking (transport, secret conn, mconn)
    types/, crypto/      L1  domain types + crypto interfaces
    utils/, codec/, config/   L0  support libraries
    ops/, parallel/, models/  TPU compute: kernels, sharding, jitted programs
"""

from tendermint_tpu.version import TM_CORE_SEMVER, ABCI_SEMVER  # noqa: F401

__version__ = TM_CORE_SEMVER
