"""Mesh-vs-single-device parity for the per-valset cached-table programs
(materialized and templated rows): rows sharded, tables replicated. The
generic programs' parity is test_mesh_parity.py's; the signed batch and
the two models are tests/mesh_helpers.py's.
"""

import numpy as np

from tests.mesh_helpers import models, signed_batch  # noqa: F401  (models: the fixture)


def test_mesh_parity_tabled_path(models):
    """The per-valset cached-table path on a mesh (rows sharded, tables
    replicated) must match the single-device tabled path bit-for-bit."""
    mesh_m, single_m = models
    n = 128
    pk, mg, sg = signed_batch(n, seed=14)
    all_pk = pk[:16].copy()  # 16 distinct keys repeated: valset matrix
    idx = (np.arange(n) % 16).astype(np.int32)
    sg[9] = 0
    sg[77, 3] ^= 1
    ok_m = mesh_m.verify_rows_cached(b"mesh-valset", all_pk, idx, mg, sg)
    ok_s = single_m.verify_rows_cached(b"mesh-valset", all_pk, idx, mg, sg)
    assert ok_m is not None and ok_s is not None
    np.testing.assert_array_equal(ok_m, ok_s)
    assert not ok_m[9] and not ok_m[77] and ok_m.sum() == n - 2


def test_mesh_parity_tabled_templated_path(models):
    """The TEMPLATED tabled path (templates replicate, per-row columns
    shard, rows materialize on device) must match the materialized
    mesh run and the single-device templated run bit-for-bit."""
    mesh_m, single_m = models
    n = 128
    pk, mg, sg = signed_batch(n, seed=14)
    all_pk = pk[:16].copy()
    idx = (np.arange(n) % 16).astype(np.int32)
    sg[9] = 0
    sg[77, 3] ^= 1
    # each row as its own template with the ts span spliced out:
    # materialization must reproduce mg exactly
    templates = mg.copy()
    templates[:, 93:101] = 0
    ts8 = mg[:, 93:101].copy()
    tmpl_idx = np.arange(n, dtype=np.int32)
    ok_mat = mesh_m.verify_rows_cached(b"mesh-valset-t", all_pk, idx, mg, sg)
    ok_m = mesh_m.verify_rows_cached_templated(
        b"mesh-valset-t", all_pk, idx, templates, tmpl_idx, ts8, sg
    )
    ok_s = single_m.verify_rows_cached_templated(
        b"mesh-valset-t", all_pk, idx, templates, tmpl_idx, ts8, sg
    )
    assert ok_mat is not None and ok_m is not None and ok_s is not None
    np.testing.assert_array_equal(ok_m, ok_mat)
    np.testing.assert_array_equal(ok_m, ok_s)
    assert not ok_m[9] and not ok_m[77] and ok_m.sum() == n - 2
