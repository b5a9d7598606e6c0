"""The one device-backed commit-verification core for every light stack.

Before this module, ``light/verifier.py`` (lite2 semantics) and
``lite/verifier.py`` (the deprecated v1 FullCommit stack) each carried
their own copy of the commit-check plumbing: build the spec, pick a
provider, run the batched device call, replay the sequential
acceptance. The v1 stack additionally re-implemented the host-side
header/valset consistency checks inline. Both stacks — and the
``lightserve`` aggregator — now drain through THIS module, so there is
exactly one seam between light-client semantics and the accelerator:

- :func:`full_spec` / :func:`trusting_spec` build the
  ``CommitVerifySpec`` forms (types/validator_set.py);
- :func:`verify_specs` dispatches a batch of specs through the
  provider. When the provider is the node's ``PipelinedVerifier`` the
  specs are SUBMITTED (``submit_commit``) so concurrent callers — the
  fast-sync window, gossip ingest, and a thousand light clients —
  coalesce into one cross-height device call; liveness failures
  (pipeline shutdown / watchdog deadline) fall back to a direct serial
  call against the inner provider, the same no-hang contract as
  ``PipelinedVerifier._await_or_serial``;
- :func:`ensure_basic` / :func:`ensure_valset_matches` are the shared
  host-side checks (typed errors the consumers map onto their own
  error taxonomies);
- :func:`verify_header` / :func:`verify_header_trusting` are the two
  whole-header shapes (full +2/3 check; trust-level check) that the v1
  ``BaseVerifier``/``DynamicVerifier`` and ``LightClient.initialize``
  previously each spelled out by hand.
"""

from __future__ import annotations

from fractions import Fraction
from typing import List, Optional, Sequence

from tendermint_tpu.crypto.batch import BatchVerifier, get_default_provider
from tendermint_tpu.types.validator_set import (
    CommitVerifySpec,
    verify_commits_batched,
)


class CoreVerifyError(Exception):
    """Base for the core's host-side check failures."""


class ErrBadHeader(CoreVerifyError):
    """SignedHeader.validate_basic failed."""


class ErrValsetMismatch(CoreVerifyError):
    """header.validators_hash != supplied valset.hash()."""


# -- spec constructors ------------------------------------------------------


def full_spec(valset, chain_id: str, shdr) -> CommitVerifySpec:
    """+2/3-of-`valset` check on `shdr`'s commit (verify_commit shape)."""
    return CommitVerifySpec(
        valset, chain_id, shdr.commit.block_id, shdr.header.height, shdr.commit
    )


def trusting_spec(
    valset, chain_id: str, shdr, trust_level: Fraction
) -> CommitVerifySpec:
    """trust_level-of-`valset` check, signers matched by address
    (verify_commit_trusting shape)."""
    return CommitVerifySpec(
        valset, chain_id, shdr.commit.block_id, shdr.header.height, shdr.commit,
        mode="trusting", trust_level=trust_level,
    )


# -- host-side shared checks ------------------------------------------------


def ensure_basic(chain_id: str, shdr) -> None:
    err = shdr.validate_basic(chain_id)
    if err:
        raise ErrBadHeader(err)


def ensure_valset_matches(shdr, valset) -> None:
    if shdr.header.validators_hash != valset.hash():
        raise ErrValsetMismatch(
            f"header vhash {shdr.header.validators_hash.hex()} "
            f"!= valset hash {valset.hash().hex()}"
        )


# -- dispatch ---------------------------------------------------------------


def _is_liveness_error(e: Exception) -> bool:
    from tendermint_tpu.crypto.pipeline import _is_liveness_error as f

    return f(e)


def verify_specs(
    specs: Sequence[CommitVerifySpec],
    provider: Optional[BatchVerifier] = None,
) -> List[Optional[Exception]]:
    """One entry per spec: None on acceptance, else the exception the
    direct ``verify_commit[_trusting]`` call would have raised.

    A pipelined provider gets the specs via ``submit_commit`` so that
    concurrent callers share one cross-height device bundle; everything
    else goes through ``verify_commits_batched`` directly (still ONE
    device call for this spec list). Either way "one device call" is one
    synchronous provider call a spec list or bundle: full-mode specs of
    one validator set are packed a launch's worth of commits at a time,
    each group while the device runs the launch before it; if the
    provider declines (None) at any point the packing is finished and
    every row goes down the generic path once — the results are those of
    the direct calls in every case (``verify_commits_batched``)."""
    if not specs:
        return []
    p = provider or get_default_provider()
    submit = getattr(p, "submit_commit", None)
    if submit is None:
        return verify_commits_batched(list(specs), provider=p)
    futs = [submit(s) for s in specs]
    out: List[Optional[Exception]] = [None] * len(specs)
    retry: List[int] = []
    for i, f in enumerate(futs):
        try:
            out[i] = f.result()
        except Exception as e:
            # the pipeline failed this REQUEST, not the signatures:
            # re-verify serially against the inner provider (the exact
            # call a caller would have made with the pipeline disabled)
            if not _is_liveness_error(e):
                raise
            retry.append(i)
    if retry:
        inner = getattr(p, "inner", None) or p
        redo = verify_commits_batched([specs[i] for i in retry], provider=inner)
        for i, r in zip(retry, redo):
            out[i] = r
    return out


def verify_one(
    spec: CommitVerifySpec, provider: Optional[BatchVerifier] = None
) -> None:
    """Verify a single spec, raising what the direct call would raise."""
    err = verify_specs([spec], provider=provider)[0]
    if err is not None:
        raise err


# -- whole-header shapes ----------------------------------------------------


def verify_header(
    chain_id: str, shdr, valset, provider: Optional[BatchVerifier] = None
) -> None:
    """The full-trust header check both stacks share: basic validity,
    the header's validators_hash matches `valset`, and +2/3 of `valset`
    signed the commit (one batched device call)."""
    ensure_basic(chain_id, shdr)
    ensure_valset_matches(shdr, valset)
    verify_one(full_spec(valset, chain_id, shdr), provider=provider)


def verify_header_trusting(
    chain_id: str,
    valset,
    shdr,
    trust_level: Fraction,
    provider: Optional[BatchVerifier] = None,
) -> None:
    """trust_level of `valset` signed `shdr`'s commit (signers matched
    by address; the skip-verification half-check)."""
    verify_one(
        trusting_spec(valset, chain_id, shdr, trust_level), provider=provider
    )
