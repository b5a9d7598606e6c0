"""Batched ed25519 verification -- the framework's north-star kernel.

Replaces the reference's serial loop (crypto/ed25519/ed25519.go:151,
looped per signature at types/validator_set.go:641 and
types/vote_set.go:201) with ONE branch-free device program over a
rectangular batch:

    ok[i] = s_i < L
          & decompress(A_i) succeeds
          & encode([s_i]B + [k_i](-A_i)) == R_i    (byte equality)
    with k_i = SHA512(R_i || A_i || M_i) mod L

This is exactly Go x/crypto's cofactorless acceptance (R is never
decompressed; non-canonical A.y accepted mod p), so a batch accepts a
signature iff the reference's serial verifier does -- consensus-safe.

The kernels return one verdict bit per row and nothing else: summing
voting power over the verified rows (the reference's tally loop at
types/validator_set.go:656) is a column sum on the host
(crypto/batch.BatchVerifier.verify_commit_batch).
"""

from __future__ import annotations

import jax.numpy as jnp

from tendermint_tpu.ops import curve
from tendermint_tpu.ops import sc
from tendermint_tpu.ops import stage2_kernel
from tendermint_tpu.ops.sha512 import sha512


def verify_core(
    pubkeys: jnp.ndarray, msgs: jnp.ndarray, sigs: jnp.ndarray
) -> jnp.ndarray:
    """(N,32) u8, (N,L) u8, (N,64) u8 -> (N,) bool."""
    pre = verify_stage_prepare(pubkeys, msgs, sigs)
    coords = verify_stage_scan(*pre[:6])
    return verify_stage_finish(*coords, sigs, pre[6], pre[7])


# -- the same program as three chainable stages ------------------------------
#
# XLA compile time is superlinear in program size: the fused verify graph
# compiles in ~220s on a v5e while the three stages below total ~33s.
# VerifierModel jits each stage separately and chains them; intermediates
# stay device-resident, so warm latency is unchanged (three dispatches at
# ~0.1ms each) but cold start drops ~7x.


def verify_stage_prepare(pubkeys, msgs, sigs):
    """Stage 1: challenge hash, pubkey decompression, signed-digit
    recode. Returns (sd, kd, -A coords x4, a_ok, s_ok) where sd/kd are
    SIGNED window digits in [-8, 8) (signed_digits applied) — exactly
    what verify_stage_scan / double_scalar_mul_signed consume; raw
    nibble digits would silently compute wrong points."""
    s_bytes = sigs[:, 32:].astype(jnp.int32)

    s_ok = sc.is_canonical(s_bytes)
    a_point, a_ok = curve.decompress(pubkeys)
    neg_a = curve.negate(a_point)

    preimage = jnp.concatenate(
        [sigs[:, :32].astype(jnp.int32), pubkeys.astype(jnp.int32), msgs.astype(jnp.int32)],
        axis=1,
    )
    k_bytes = sc.reduce512(sha512(preimage))

    sd = curve.signed_digits(curve.nibble_digits(s_bytes))
    kd = curve.signed_digits(curve.nibble_digits(k_bytes))
    return sd, kd, neg_a.x, neg_a.y, neg_a.z, neg_a.t, a_ok, s_ok


def verify_stage_scan(sd, kd, nx, ny, nz, nt):
    """Stage 2: the Straus double-scalar-mult scan (the dominant cost).
    Lowered for a TPU with rows a multiple of 1,024 the table build and
    the scan run in the rows-on-lanes Pallas kernel
    (stage2_kernel.generic_scan), bit-equal coordinates."""
    p = stage2_kernel.double_scalar_mul_rows(sd, kd, curve.Point(nx, ny, nz, nt))
    return p.x, p.y, p.z, p.t


def verify_stage_finish(px, py, pz, pt, sigs, a_ok, s_ok):
    """Stage 3: encode the result and compare against R."""
    enc = curve.encode(curve.Point(px, py, pz, pt))
    r_match = jnp.all(enc == sigs[:, :32].astype(jnp.int32), axis=-1)
    return r_match & a_ok & s_ok


# -- per-valset cached-table pipeline ----------------------------------------
#
# Validator pubkeys are stable across heights; the reference re-verifies
# the same keys every block (types/validator_set.go:641). Precomputing
# split tables of each -A once per valset (curve.build_split_tables)
# removes from the per-commit path: pubkey decompression (~16ms @10k),
# the per-row [1..8]Q table build, and 240 of the 256 shared doublings
# (256 - 4*SPLIT_W). The per-commit program is then: sha512 challenge +
# digit recode + a 16-doubling/96-mixed-add scan (64 key-side + 32
# base-comb adds) + blocked-inversion encode.


def build_valset_tables(pubkeys: jnp.ndarray):
    """(V, 32) u8 -> (tables (V, SPLITS, 8, 3*LIMBS) int32, a_ok (V,)).

    Decompression (and its Go x/crypto acceptance of non-canonical y)
    happens HERE, once per valset; a_ok is cached alongside the tables
    and ANDed into every subsequent verify."""
    a_point, a_ok = curve.decompress(pubkeys)
    return curve.build_split_tables(curve.negate(a_point)), a_ok


def _table_shape(rows: int) -> tuple:
    return (rows, curve.SPLITS, curve._TBL, 3 * curve.F.LIMBS)


def table_slab(tables, a_ok, pubkeys, cols):
    """One launch's table operand cut from the key pool
    (models/verifier._KeyPool): columns ``cols`` (U,) i32 of the pool's
    tables, (P,) a_ok and (P, 32) pubkeys, in the order given — a copy
    of ~30 KB a column where the columns lie; nothing is computed. The
    pool holds a key's table as ONE row of (P, SPLITS*8*3*LIMBS): 7,680
    int32, sixty lanes-wide vectors, which the gather copies as they
    lie. Gathered from the (P, SPLITS, 8, 3*LIMBS) form the stages read,
    the TPU compiler first lays the WHOLE pool out again (268 MB of
    temporaries for a 4,096-key pool, none this way). The operand goes
    out in the stages' form. A set that is the pool as it lies takes the
    pool's arrays themselves and never runs this."""
    u = cols.shape[0]
    return (
        jnp.take(tables, cols, axis=0).reshape(_table_shape(u)),
        jnp.take(a_ok, cols, axis=0),
        jnp.take(pubkeys, cols, axis=0),
    )


def table_put(tables, a_ok, pubkeys, cols, new_tables, new_a_ok, new_pubkeys):
    """The pool (tables a row a key, see table_slab) with freshly built
    rows written at columns ``cols`` (K,) i32; a column past the pool's
    end is dropped — the padding of a build bucket."""
    k = cols.shape[0]
    return (
        tables.at[cols].set(new_tables.reshape(k, -1), mode="drop"),
        a_ok.at[cols].set(new_a_ok, mode="drop"),
        pubkeys.at[cols].set(new_pubkeys, mode="drop"),
    )


def verify_stage_prepare_tabled(pubkeys, msgs, sigs):
    """Tabled stage 1: challenge hash + canonical-s + signed recode.
    No decompression — the tables already encode -A. pubkeys are still
    hashed (k = SHA512(R || A || M)). s recodes to SIGNED BASE-256
    digits (the base side rides the doubling-free 8-bit MXU comb);
    k keeps signed nibbles for the per-key split tables."""
    s_bytes = sigs[:, 32:].astype(jnp.int32)
    s_ok = sc.is_canonical(s_bytes)
    preimage = jnp.concatenate(
        [sigs[:, :32].astype(jnp.int32), pubkeys.astype(jnp.int32), msgs.astype(jnp.int32)],
        axis=1,
    )
    k_bytes = sc.reduce512(sha512(preimage))
    sd8 = curve.signed_digits_base256(s_bytes)
    kd = curve.signed_digits(curve.nibble_digits(k_bytes))
    return sd8, kd, s_ok


def verify_stage_prepare_tabled_gathered(pk_all, idx, msgs, sigs):
    """Tabled stage 1 with DEVICE-side pubkey gather: pk_all is the
    valset's device-resident (V, 32) pubkey matrix (cached alongside
    the split tables), idx the per-row validator index. The old stage
    shipped a host-gathered (N, 32) copy per call — 32 of the 260 H2D
    bytes/row, plus the host fancy-index itself, for data the device
    already holds."""
    return verify_stage_prepare_tabled(jnp.take(pk_all, idx, axis=0), msgs, sigs)


# -- templated sign-bytes -----------------------------------------------------
#
# Within one commit the 160-byte canonical sign-bytes differ per row
# ONLY in the 8-byte timestamp and the nil-vs-commit BlockID variant
# (codec/signbytes.py layout; reference Commit.VoteSignBytes
# types/block.go:637 — CommitSig carries just Timestamp + BlockIDFlag).
# A nil row is simply a SECOND template with the BlockID span zeroed,
# so a whole commit is (templates (T,160), tmpl_idx (N,), ts8 (N,8)):
# ~13 H2D bytes/row instead of 160. Rows materialize ON DEVICE before
# SHA-512, which drops total per-row H2D from ~228 B (msgs+sigs+idx)
# to ~80 B and spares the host the (N,160) splice. How much of a
# commit's latency the upload is on an attached chip is to be
# re-measured.

from tendermint_tpu.codec.signbytes import (  # noqa: E402
    TIMESTAMP_OFFSET as SIGN_BYTES_TS_OFFSET,
)


def materialize_sign_bytes(templates, tmpl_idx, ts8):
    """templates (T, W) u8, tmpl_idx (N,) i32, ts8 (N, 8) u8 big-endian
    i64 timestamps -> (N, W) uint8 messages.

    Runs as its OWN tiny program whose device-resident output feeds the
    STANDARD prepare stages — the templated path reuses the exact
    compiled prepare executables the materialized path warms, and the
    big sha512 prepare program never needs a templated variant (a fused
    form segfaulted XLA:CPU executable (de)serialization three times in
    full-suite runs; see models/aot_cache.AotJit's fragile note).

    T is static and tiny (2 per commit; one pair per height in a
    cross-height batch), so the per-row template gather reads ~160 B
    rows from a KB-scale table — nothing like the pathological
    30 KB-row valset-table gathers (models/verifier.MAX_TABLED_VALSET)."""
    if templates.shape[0] == 1:
        rows = jnp.broadcast_to(
            templates, (tmpl_idx.shape[0],) + templates.shape[1:]
        )
    else:
        rows = jnp.take(templates, tmpl_idx, axis=0)
    o = SIGN_BYTES_TS_OFFSET
    return jnp.concatenate([rows[:, :o], ts8, rows[:, o + 8 :]], axis=1)


def verify_stage_scan_tabled(sd, kd, tables, a_ok, idx):
    """Tabled stage 2, GATHERED: gather each row's key table by
    validator index and run the 4*SPLIT_W-doubling split scan. For
    batches whose rows are few or out of validator order (vote drains,
    trusting lookups), and under a mesh. The gather and its re-layouts
    copy ~30 KB a row three times over: 39.4 ms a 10,240-row launch on
    a v5e where the slot-order form below takes 22.75 ms for the same
    arithmetic (PERF.md section 6, PR 30) — whole commits go there
    (models/verifier.plan_slots)."""
    row_tables = jnp.take(tables, idx, axis=0)
    p = curve.double_scalar_mul_tabled(sd, kd, row_tables)
    return p.x, p.y, p.z, p.t, jnp.take(a_ok, idx, axis=0)


def verify_stage_scan_tabled_sharded(sd, kd, a_ok, idx, tables):
    """Tabled stage 2 for LARGE valsets: `tables` is a tuple of
    equal-size shards along the validator axis (each <= the 16384-row
    bound that gathers fine — models/verifier.MAX_TABLED_VALSET). Each
    shard is gathered with a clipped local index and the true shard's
    rows selected by mask: S bounded gathers replace one huge-table
    gather, which measured ~50x pathological at 65536 rows (round-4
    ledger). One dispatch either way — the extra gathers cost HBM
    reads, not round trips."""
    shard = tables[0].shape[0]
    row_tables = None
    for s, t in enumerate(tables):
        local = jnp.clip(idx - s * shard, 0, t.shape[0] - 1)
        g = jnp.take(t, local, axis=0)
        sel = (idx >= s * shard) & (idx < s * shard + t.shape[0])
        g = jnp.where(sel[:, None, None, None], g, 0)
        row_tables = g if row_tables is None else row_tables + g
    p = curve.double_scalar_mul_tabled(sd, kd, row_tables)
    return p.x, p.y, p.z, p.t, jnp.take(a_ok, idx, axis=0)


def verify_stage_prepare_tabled_slots(pk_all, msgs, sigs):
    """Tabled stage 1 in SLOT ORDER: the rows are C whole commits of
    V slots each (C static, read from the shapes), slot c*V + i holding
    validator i's row of commit c or zeros, so the pubkeys are the
    set's device-resident (V, 32) matrix as it lies, repeated over the
    commit axis: no index, no gather. C = 1 is the full-commit shape."""
    c = sigs.shape[0] // pk_all.shape[0]
    return verify_stage_prepare_tabled(jnp.tile(pk_all, (c, 1)), msgs, sigs)


def verify_stage_scan_tabled_slots(sd, kd, tables, a_ok):
    """Tabled stage 2 in SLOT ORDER (see verify_stage_prepare_tabled_slots):
    slot c*V + i reads validator i's key table where it lies in the
    set's (V, SPLITS, 8, 3*LIMBS) tables. The gathered form above
    copies ~30 KB of table to every row of every launch (315 MB at
    10,240 rows); here each row's ~90 bytes went to its validator's
    slot on the host instead, and curve._tree_select broadcasts the
    table over the commit axis. Same digits, same additions, same
    verdict bit per row; empty slots (zero signatures) compute a
    verdict nobody reads. Lowered for a TPU with V a multiple of 1,024
    the point arithmetic runs in the rows-on-lanes Pallas kernels
    (stage2_kernel.kernel_form), bit-equal coordinates."""
    c = kd.shape[0] // tables.shape[0]
    p = stage2_kernel.double_scalar_mul_slots(sd, kd, tables)
    return p.x, p.y, p.z, p.t, jnp.tile(a_ok, c)


def verify_stage_finish_blocked(px, py, pz, pt, sigs, a_ok, s_ok):
    """Tabled stage 3: encode via blocked Montgomery inversion (~6
    muls/row instead of a ~254-step per-row chain) and compare to R."""
    enc = curve.encode(curve.Point(px, py, pz, pt), blocked=True)
    r_match = jnp.all(enc == sigs[:, :32].astype(jnp.int32), axis=-1)
    return r_match & a_ok & s_ok
