"""Mesh-vs-single-device parity for the batched verifier (round 3).

The sharded program (shard_map over the virtual 8-device CPU mesh the
conftest forces) must accept EXACTLY the rows the single-device program
accepts, and the host tally over its verdicts must be the same —
including rows corrupted in every shard, uneven (non-divisible) batch
sizes, and non-uniform voting powers. The driver's dryrun_multichip re-checks this at 4k rows. The
cached-table programs' parity is in test_mesh_parity_tabled.py: a file
is one worker's under the tier-1 run's --dist loadfile. Both take the
signed batch and the two models from tests/mesh_helpers.py.
"""

import numpy as np

from tests.mesh_helpers import (  # noqa: F401  (models: the fixture)
    N_DEV, models, signed_batch, verify_then_tally,
)


def test_mesh_parity_mixed_rows_per_shard_negatives(models):
    mesh_m, single_m = models
    # bucket-exact, 32 rows per shard; the same 256 bucket as the
    # uneven batch below, so the two share their compiled programs
    n = 256
    pk, mg, sg = signed_batch(n)
    shard = n // N_DEV
    bad = [s * shard + 3 * s for s in range(N_DEV)]  # one per shard
    for r in bad:
        sg[r, 9] ^= 0x20
    powers = np.arange(1, n + 1, dtype=np.int64)
    counted = np.ones(n, dtype=bool)
    counted[3] = False  # an uncounted (nil-vote) row

    ok_m, tally_m = verify_then_tally(mesh_m, pk, mg, sg, powers, counted)
    ok_s, tally_s = verify_then_tally(single_m, pk, mg, sg, powers, counted)
    np.testing.assert_array_equal(ok_m, ok_s)
    assert tally_m == tally_s
    want_bad = np.zeros(n, dtype=bool)
    want_bad[bad] = True
    np.testing.assert_array_equal(~ok_m, want_bad)
    assert tally_m == int(powers[counted & ok_m].sum())


def test_mesh_parity_uneven_batch(models):
    mesh_m, single_m = models
    n = 137  # not divisible by 8: exercises pad/remainder handling
    pk, mg, sg = signed_batch(n, seed=12)
    sg[0, 0] ^= 1
    sg[n - 1, 63] ^= 0x80
    powers = np.full(n, 5, dtype=np.int64)
    counted = np.ones(n, dtype=bool)
    ok_m, tally_m = verify_then_tally(mesh_m, pk, mg, sg, powers, counted)
    ok_s, tally_s = verify_then_tally(single_m, pk, mg, sg, powers, counted)
    np.testing.assert_array_equal(ok_m, ok_s)
    assert tally_m == tally_s == 5 * (n - 2)
    assert not ok_m[0] and not ok_m[n - 1] and ok_m[1 : n - 1].all()


def test_mesh_parity_verify_only_path(models):
    mesh_m, single_m = models
    n = 64
    pk, mg, sg = signed_batch(n, seed=13)
    sg[17] = 0
    ok_m = mesh_m.verify(pk, mg, sg)
    ok_s = single_m.verify(pk, mg, sg)
    np.testing.assert_array_equal(ok_m, ok_s)
    assert not ok_m[17] and ok_m.sum() == n - 1


# Every AOT tag the model may produce, letter for letter: a tag is part
# of an executable's file name on disk (models/aot_cache._path), so a
# renamed one silently recompiles at every restart of every node.
AOT_TAGS = (
    "prepare", "scan", "finish",
    "t-prepare-g", "t-scan", "t-finish", "t-build", "t-materialize",
    "t-prepare-s", "t-scan-s", "t-scan-sh", "t-slab", "t-put",
)


def test_mesh_provider_commit_tally_and_program_tags(models):
    """The provider-level commit form on a mesh — rows shard, verdicts
    come back, the inherited host tally sums them — equals the host
    verifier's on a batch with an invalid row and an uncounted row;
    and every program of a meshed and an unmeshed model carries one of
    the AOT tags above and the name the traces know it by."""
    from tendermint_tpu.crypto.batch import CPUBatchVerifier, TPUBatchVerifier
    from tendermint_tpu.models.verifier import _PROGRAMS

    mesh_m, single_m = models
    n = 64
    pk, mg, sg = signed_batch(n, seed=13)
    sg[17] = 0
    powers = np.arange(1, n + 1, dtype=np.int64)
    counted = np.ones(n, dtype=bool)
    counted[3] = False
    prov = TPUBatchVerifier(mesh=mesh_m.mesh)
    assert "verify_commit_batch" not in vars(TPUBatchVerifier)  # the inherited one
    ok, tally = prov.verify_commit_batch(pk, mg, sg, powers, counted)
    want_ok, want_tally = CPUBatchVerifier().verify_commit_batch(pk, mg, sg, powers, counted)
    np.testing.assert_array_equal(ok, want_ok)
    assert tally == want_tally == int(powers.sum()) - 18 - 4
    assert prov.row_counts.snapshot() == (n, 0)  # verified on the mesh

    assert set(_PROGRAMS) == set(AOT_TAGS)
    for tag in AOT_TAGS:
        assert single_m._program(tag).stage == tag
        assert mesh_m._program(tag).stage == f"{tag}-mesh({N_DEV},)"
        assert single_m._program(tag) is single_m._program(tag)  # one a model
        assert single_m._program(tag)._jit.__name__ == _PROGRAMS[tag][0].__name__
    # the jitted names the benchmark's trace reduction reads
    names = {_PROGRAMS[tag][0].__name__ for tag in AOT_TAGS}
    assert {
        "verify_stage_prepare_tabled_slots", "verify_stage_scan_tabled_slots",
        "verify_stage_prepare_tabled_gathered", "verify_stage_scan_tabled",
        "verify_stage_finish_blocked", "materialize_sign_bytes", "table_slab",
    } <= names
