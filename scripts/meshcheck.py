#!/usr/bin/env python3
"""meshcheck CLI — multichip preflight for the mesh runtime.

Run this BEFORE enabling ``mesh_enabled`` on new hardware: it proves,
against the in-tree MeshRouter and engines, that the local device
topology produces BIT-IDENTICAL verification verdicts between the mesh
and single-device paths — including rows corrupted inside every shard
(a chip that loses a negative is the failure mode that matters), an
uneven remainder batch, and tabled-valset negative controls — and that
the per-device breaker shed/readmit drill re-shards with verdicts
intact. Any divergence exits non-zero.

Usage:
    python scripts/meshcheck.py                # local device inventory
    python scripts/meshcheck.py --devices 4    # cap the mesh size
    python scripts/meshcheck.py --virtual 8    # force N virtual CPU devices
                                               # (preflight a box with no accelerator)
    python scripts/meshcheck.py --skip-device  # router/breaker drills only (no XLA)

Exit codes: 0 parity holds, 1 divergence/drill failure, 2 environment error.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np  # noqa: E402


def log(msg: str) -> None:
    print(f"[meshcheck] {msg}", file=sys.stderr, flush=True)


def _signed_batch(n, msg_len=96, seed=11):
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


# -- device parity checks ---------------------------------------------------


def _verify_then_tally(model, pk, mg, sg, powers, counted):
    """model.verify, then the host's column sum over its verdicts (what
    BatchVerifier.verify_commit_batch does over a provider)."""
    ok = model.verify(pk, mg, sg)
    return ok, int(np.sum(np.where(ok & counted, powers, 0)))


def check_shardmap_verifier(
    devs, n: int = 1024, msg_len: int = 96, seed: int = 11, models=None
) -> list:
    """The shard_map verifier: mesh vs single-device bit-equality on an
    n-row commit with one corrupted row per shard, an uncounted row,
    non-uniform powers, an uneven remainder batch, and tabled negative
    controls. ``models`` = (mesh model, single-device model) lets a
    caller keep them (chip_smoke.py runs this at 10,000 rows of 160
    bytes and then prints where the models' arrays live)."""
    from tendermint_tpu.models.verifier import VerifierModel, _bucket
    from tendermint_tpu.parallel import make_mesh

    n_dev = len(devs)
    fails = []
    mesh_m, single_m = models or (
        VerifierModel(mesh=make_mesh(devs), block_on_compile=True),
        VerifierModel(block_on_compile=True),
    )

    # per-shard negatives: the device shards split the PADDED batch
    pk, mg, sg = _signed_batch(n, msg_len, seed)
    shard = _bucket(n, n_dev) // n_dev
    bad = [s * shard + (7 * s) % shard for s in range(n_dev)]
    assert bad[-1] < n, "last shard holds only padding"
    for r in bad:
        sg[r, 9] ^= 0x20
    powers = np.arange(1, n + 1, dtype=np.int64)
    counted = np.ones(n, dtype=bool)
    counted[3] = False
    t0 = time.perf_counter()
    ok_m, tally_m = _verify_then_tally(mesh_m, pk, mg, sg, powers, counted)
    log(f"mesh verify_commit@{n} ({n_dev} dev): {time.perf_counter()-t0:.1f}s (compile+run)")
    ok_s, tally_s = _verify_then_tally(single_m, pk, mg, sg, powers, counted)
    ok_m, ok_s = np.asarray(ok_m), np.asarray(ok_s)
    if not (ok_m == ok_s).all() or int(tally_m) != int(tally_s):
        fails.append(
            f"shard_map verify_commit@{n}: mesh verdicts/tally diverge "
            f"from single device (tally {int(tally_m)} vs {int(tally_s)})"
        )
    want_bad = np.zeros(n, dtype=bool)
    want_bad[bad] = True
    if not (~ok_m == want_bad).all():
        fails.append(
            f"shard_map verify_commit@{n}: per-shard corrupted rows not "
            f"rejected in place (a shard lost a negative)"
        )

    # uneven remainder: not divisible by the mesh size
    n2 = 137
    pk, mg, sg = _signed_batch(n2, msg_len, seed + 1)
    sg[0, 0] ^= 1
    sg[n2 - 1, 63] ^= 0x80
    powers = np.full(n2, 5, dtype=np.int64)
    counted = np.ones(n2, dtype=bool)
    ok_m, tally_m = _verify_then_tally(mesh_m, pk, mg, sg, powers, counted)
    ok_s, tally_s = _verify_then_tally(single_m, pk, mg, sg, powers, counted)
    if not (np.asarray(ok_m) == np.asarray(ok_s)).all() or int(tally_m) != int(
        tally_s
    ):
        fails.append(f"shard_map verify_commit@{n2} (remainder): diverged")
    elif int(tally_m) != 5 * (n2 - 2):
        fails.append(f"shard_map verify_commit@{n2}: wrong tally {int(tally_m)}")

    # tabled path with negative controls
    n3 = 128
    pk, mg, sg = _signed_batch(n3, msg_len, seed + 3)
    all_pk = pk[:16].copy()
    idx = (np.arange(n3) % 16).astype(np.int32)
    sg[9] = 0
    sg[77, 3] ^= 1
    ok_m = mesh_m.verify_rows_cached(b"meshcheck-valset", all_pk, idx, mg, sg)
    ok_s = single_m.verify_rows_cached(b"meshcheck-valset", all_pk, idx, mg, sg)
    if ok_m is None or ok_s is None:
        fails.append("tabled path unavailable (tables did not build)")
    else:
        ok_m, ok_s = np.asarray(ok_m), np.asarray(ok_s)
        if not (ok_m == ok_s).all():
            fails.append(f"tabled verify_rows_cached@{n3}: mesh diverged")
        if ok_m[9] or ok_m[77] or int(ok_m.sum()) != n3 - 2:
            fails.append(
                f"tabled verify_rows_cached@{n3}: negative controls not "
                f"rejected ({int(ok_m.sum())}/{n3} accepted)"
            )
    return fails


def check_chunked_engines(devs) -> list:
    """The chunked seams (tx-key SHA-256, merkle leaf stage) routed
    over a real-device MeshRouter: digests byte-equal to the
    single-device engines."""
    from tendermint_tpu.ingest.hashing import TxKeyHasher
    from tendermint_tpu.models.hasher import MerkleHasher
    from tendermint_tpu.parallel import DeviceTopology, MeshRouter

    fails = []
    router = MeshRouter(
        DeviceTopology(devs, platform=devs[0].platform), min_rows=8
    )
    rng = np.random.RandomState(5)
    txs = [bytes(rng.bytes(20 + (i % 60))) for i in range(1000)]
    meshed = TxKeyHasher(block_on_compile=True, router=router).keys(txs)
    plain = TxKeyHasher(block_on_compile=True).keys(txs)
    if meshed is None or plain is None or meshed != plain:
        fails.append("tx-key hasher: mesh digests != single-device digests")
    if router.stats()["collective_bundles"] < 1:
        fails.append("tx-key hasher: collective path never engaged")

    leaves = [bytes(rng.bytes(45)) for _ in range(4096)]
    root_m = MerkleHasher(block_on_compile=True, router=router).root(leaves)
    root_s = MerkleHasher(block_on_compile=True).root(leaves)
    if root_m is None or root_m != root_s:
        fails.append("merkle hasher: mesh root != single-device root")
    return fails


# -- router/breaker drills (no XLA required) --------------------------------


def check_router_drills() -> list:
    """Shed/readmit/threshold semantics over logical lanes, with
    verdicts checked through the chunked verifier seam."""
    from tendermint_tpu.crypto.batch import CPUBatchVerifier, MeshRoutedVerifier
    from tendermint_tpu.parallel import DeviceTopology, MeshRouter
    from tendermint_tpu.utils.watchdog import CircuitBreaker

    fails = []
    topo = DeviceTopology.logical(4)
    topo.breakers = [
        CircuitBreaker(
            f"mesh.device{i}", failure_threshold=1, cooldown_s=3600.0
        )
        for i in range(4)
    ]
    router = MeshRouter(topo, min_rows=4)
    v = MeshRoutedVerifier(CPUBatchVerifier(), router)
    n = 64
    pk, mg, sg = _signed_batch(n, seed=31)
    sg[5, 0] ^= 1
    want = CPUBatchVerifier().verify_batch(pk, mg, sg)

    ok = v.verify_batch(pk, mg, sg)
    if not (ok == want).all():
        fails.append("router drill: healthy collective verdicts diverged")
    if router.stats()["collective_bundles"] != 1:
        fails.append("router drill: collective path never engaged")

    # shed: a tripped chip is excluded at the NEXT bundle
    topo.breakers[2].force_open()
    ok = v.verify_batch(pk, mg, sg)
    st = router.stats()
    if not (ok == want).all():
        fails.append("router drill: post-shed verdicts diverged")
    if st["admitted"] != 3 or st["sheds"] != 1:
        fails.append(f"router drill: shed not recorded ({st['admitted']} admitted)")

    # readmit: cooldown elapses, the half-open probe brings it back
    topo.breakers[2]._cooldown_s = 0.0
    ok = v.verify_batch(pk, mg, sg)
    st = router.stats()
    if not (ok == want).all():
        fails.append("router drill: post-readmit verdicts diverged")
    if st["admitted"] != 4 or st["readmits"] != 1:
        fails.append(
            f"router drill: readmit not recorded ({st['admitted']} admitted)"
        )
    if topo.breakers[2].state() != "closed":
        fails.append("router drill: probed breaker did not close on success")

    # sub-threshold bundles stay off the collective path
    before = router.stats()["collective_bundles"]
    v.verify_batch(pk[:3], mg[:3], sg[:3])
    if router.stats()["collective_bundles"] != before:
        fails.append("router drill: sub-min_rows bundle entered the collective path")
    return fails


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=0, help="cap the mesh size")
    ap.add_argument(
        "--virtual", type=int, default=0,
        help="force N virtual CPU devices (preflight without an accelerator)",
    )
    ap.add_argument(
        "--skip-device", action="store_true",
        help="router/breaker drills only (no XLA, no compiles)",
    )
    args = ap.parse_args()

    if args.virtual:
        from tendermint_tpu.utils.jaxenv import force_cpu_platform

        force_cpu_platform(args.virtual)

    failures = []

    log("router/breaker drills (logical lanes)")
    failures += check_router_drills()

    if not args.skip_device:
        try:
            import jax

            devs = jax.devices()
        except Exception as e:
            log(f"no jax backend: {e!r} (use --virtual N or --skip-device)")
            return 2
        if args.virtual and (devs[0].platform != "cpu" or len(devs) < args.virtual):
            log("a JAX backend initialized before --virtual could apply")
            return 2
        if args.devices > 0:
            devs = devs[: args.devices]
        if len(devs) < 2:
            log(
                f"single {devs[0].platform} device: nothing to preflight "
                "(use --virtual 8 for a virtual sweep) — device checks skipped"
            )
        else:
            log(f"device parity over {len(devs)} {devs[0].platform} device(s)")
            failures += check_shardmap_verifier(devs)
            failures += check_chunked_engines(devs)

    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        print(f"meshcheck: {len(failures)} failure(s) — do NOT enable mesh_enabled")
        return 1
    print("meshcheck: all parity checks and drills passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
