#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call,
at the width users would call real, on ONE TPU chip, in ONE process
(the process that holds the chip):

    device      jax.devices() in this process; anything but a TPU fails
    commit-10k  10,000-validator ValidatorSet.verify_commit (templated
                cached tables) + verify_commit_batch (generic 10,240
                bucket), with negative controls, against the host
                verifier on the same rows
    light-1k    1,000 validators x 32 heights through the light client's
                verify_chain: >16,384 rows in one call, the windowed path
    node-128    a live node (CLI-initialised home, 128-validator genesis,
                kvstore, default crypto_provider="tpu", pipeline, RPC)
                with the other 127 validators voting through the peer
                vote path; txs over HTTP; counters from the engines route

    python chip_smoke.py [--seed N]      # one chip (what the driver runs)
    python chip_smoke.py --chips 4       # ONLY the multi-chip phase

Everything is generated from --seed; no network, no git. The script
sets no JAX_PLATFORMS and never retries on the CPU. Its last stdout
line is {"ok": true, "device": {"platform", "kind", "count"}}; a failed
phase exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import hashlib
import json
import logging
import os
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

COMMIT_VALS = 10_000  # MaxVotesCount (types/vote_set.py), the north-star width
LIGHT_VALS, LIGHT_HEIGHTS = 1_000, 32  # BASELINE.json config 3, cut to a smoke
NODE_VALS = 128  # BASELINE.json config 1
MESH_ROWS = 10_000
NODE_WARM_DEADLINE_S = 600.0  # boot-time warm-up must finish by then
NODE_HEIGHTS_AFTER_WARM = 3
NODE_TXS = 3


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- host overrides, device, caches -----------------------------------------


def refuse_host_overrides() -> None:
    """An environment setting that would quietly move work to the host
    is reported and fails the run."""
    bad = []
    prov = os.environ.get("TM_CRYPTO_PROVIDER")
    if prov and prov != "tpu":
        bad.append(f"TM_CRYPTO_PROVIDER={prov} pins the node's verifier to the host")
    if os.environ.get("TM_FAULTS"):
        bad.append("TM_FAULTS arms injected device faults (host fallbacks)")
    for b in bad:
        say(f"REFUSED: {b}")
    check(not bad, "unset the overrides above; the smoke measures the device path")


def phase_device(chips: int) -> dict:
    import jax
    import jax.numpy as jnp
    import jaxlib
    import numpy as np

    try:
        import libtpu

        libtpu_v = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_v = "not installed"
    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu_v}")
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    say(f"devices: {info}")
    check(d.platform == "tpu", f"JAX's backend is {d.platform!r}, not a TPU")
    check(len(devs) >= chips, f"need {chips} chip(s), JAX reports {len(devs)}")
    x = jnp.asarray(np.ones((8, 128), np.float32))
    check(float(np.asarray(x @ x.T)[0, 0]) == 128.0, "the chip returned a wrong product")
    return info


def cache_entries(path: str) -> int:
    """Files under the cache root (JAX's entries plus the aot/ and
    tables/ sub-directories)."""
    return sum(len(files) for _, _, files in os.walk(path))


# -- data from the seed -------------------------------------------------------


def make_validators(seed: int, n: int, tag: str):
    from tendermint_tpu.lightserve import loadgen

    privs = loadgen.keys(n, f"smoke-{seed}-{tag}")
    return {p.pub_key().address(): p for p in privs}, loadgen.valset(privs)


def signed_commit(by_addr, vals, chain_id: str, height: int, block_id, t_ns: int):
    """A commit for `block_id` signed by every validator of `vals`, each
    with its own timestamp (so the templated splice has work to do).
    Built directly, without VoteSet: nothing is verified on the way in."""
    from tendermint_tpu.types.block import BLOCK_ID_FLAG_COMMIT, Commit, CommitSig

    sigs = [
        CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, t_ns + i, b"")
        for i, v in enumerate(vals.validators)
    ]
    rows = Commit(height, 0, block_id, sigs).sign_bytes_matrix(chain_id)
    for cs, row in zip(sigs, rows):
        cs.signature = by_addr[cs.validator_address].sign(row.tobytes())
    # a Commit is immutable once read (its memos hold its slots as
    # columns, signatures too): the signed slots get a Commit of their own
    return Commit(height, 0, block_id, sigs)


def make_block_id(seed: int, tag: str):
    from tendermint_tpu.types.block import BlockID, PartSetHeader

    h = hashlib.sha256(f"smoke-{seed}-{tag}".encode()).digest()
    return BlockID(h, PartSetHeader(1, hashlib.sha256(h).digest()))


def outcome(fn):
    """None on acceptance, else (exception type name, message)."""
    try:
        fn()
    except Exception as e:  # the verdict IS the exception; compared, not handled
        return (type(e).__name__, str(e))
    return None


# -- what "the device did the work" means ------------------------------------


class ErrorLog(logging.Handler):
    """Collects ERROR records of the verifier's logger: the model turns a
    device exception into a logged 'falling back' and a quiet None —
    right for a node, fatal for this script."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.records = []

    def emit(self, record):
        self.records.append(f"{record.getMessage()} {getattr(record, 'kv', '')}")


def watch_verifier_errors() -> ErrorLog:
    from tendermint_tpu.utils.log import get_logger

    get_logger("verifier")  # installs the stderr handler first
    h = ErrorLog()
    logging.getLogger("verifier").addHandler(h)
    return h


def engine_report(stats: dict) -> str:
    from tendermint_tpu.models.telemetry import bucket_counts

    return json.dumps(
        {
            name: {
                "device_rows": st.get("device_rows"),
                "host_rows": st.get("host_rows"),
                "buckets": bucket_counts(st),
                "compile_s": {
                    k: round(b["compile_s"], 2)
                    for k, b in (st.get("buckets") or {}).items()
                    if b.get("compile_s")
                },
                "breakers": {k: b["state"] for k, b in (st.get("breakers") or {}).items()},
                "counters": st.get("counters"),
            }
            for name, st in stats.items()
        },
        sort_keys=True,
    )


def check_engines_healthy(stats: dict) -> None:
    from tendermint_tpu.models.telemetry import bucket_counts

    for name, st in stats.items():
        check("error" not in st, f"engine {name} reported {st.get('error')}")
        check(bucket_counts(st)["failed"] == 0, f"engine {name} has a failed bucket")
        for bname, b in (st.get("breakers") or {}).items():
            check(b["state"] == "closed", f"breaker {bname} is {b['state']}")


def provider_stats(prov) -> dict:
    """engine_stats() of a bare TPU provider, through the pipeline
    wrapper that implements the protocol for it."""
    from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
    from tendermint_tpu.models.telemetry import collect_engine_stats

    with PipelinedVerifier(prov, cache=SigCache()) as pv:
        return collect_engine_stats([pv])


def check_device_did_the_work(prov, errors: ErrorLog, rows0, submitted: int, want_kind: str):
    stats = provider_stats(prov)
    say(f"engines: {engine_report(stats)}")
    say(f"table bytes resident: {prov.model.table_bytes()}")
    dev, host = prov.row_counts.snapshot()
    check(
        any(k[0] == want_kind and e.ready for k, e in prov.model._entries.items()),
        f"no ready {want_kind!r} entry: {sorted(map(str, prov.model._entries))}",
    )
    check(
        dev - rows0[0] >= submitted,
        f"device_rows grew by {dev - rows0[0]}, {submitted} rows were submitted",
    )
    check(host == rows0[1], f"host_rows grew by {host - rows0[1]}: rows were served on the host")
    check_engines_healthy(stats)
    check(not errors.records, f"the verifier logged errors: {errors.records}")
    say(f"device rows +{dev - rows0[0]} (submitted {submitted}), host rows +0")


# -- commit-10k ---------------------------------------------------------------


def phase_commit(seed: int, prov, errors: ErrorLog, n_vals: int = COMMIT_VALS, k_bad: int = 7):
    import numpy as np

    from tendermint_tpu.crypto.batch import CPUBatchVerifier
    from tendermint_tpu.types.block import CommitSig

    chain_id, height = f"smoke-{seed}", 5
    t0 = time.perf_counter()
    by_addr, vals = make_validators(seed, n_vals, "commit")
    block_id = make_block_id(seed, "commit")
    good = signed_commit(by_addr, vals, chain_id, height, block_id, 1_700_000_000_000_000_000)
    say(f"built a {n_vals}-validator set and its commit in {time.perf_counter() - t0:.1f}s (host)")

    bad_rows = [(j * n_vals) // k_bad + 3 for j in range(k_bad)]
    corrupted = copy.deepcopy(good)
    for r in bad_rows:
        s = bytearray(corrupted.signatures[r].signature)
        s[r % 64] ^= 0x10
        corrupted.signatures[r].signature = bytes(s)
    minority = copy.deepcopy(good)
    for r in range((n_vals * 3) // 5, n_vals):  # 60% of equal powers sign: below 2/3
        minority.signatures[r] = CommitSig.absent()

    cpu = CPUBatchVerifier()
    key, all_pk, _ = vals.batch_cache()
    rows0 = prov.row_counts.snapshot()
    submitted = 0
    # (label, chain id, commit, accepted?, rows that must be rejected)
    cases = [
        ("valid", chain_id, good, True, []),
        (f"{k_bad} corrupted signatures", chain_id, corrupted, False, bad_rows),
        ("wrong chain id", chain_id + "-other", good, False, list(range(n_vals))),
        ("signers below 2/3", chain_id, minority, False, []),
    ]
    for label, cid, commit, accepted, rejected in cases:
        t0 = time.perf_counter()
        got = outcome(lambda: vals.verify_commit(cid, block_id, height, commit))
        t_commit = time.perf_counter() - t0
        want = outcome(lambda: vals.verify_commit(cid, block_id, height, commit, provider=cpu))
        check(got == want, f"{label}: verify_commit says {got}, the host reference {want}")
        check((got is None) == accepted, f"{label}: verify_commit returned {got}")

        idxs, vals_idx, pk, mg, sg, powers, counted, ed, tpl = vals._commit_batch_arrays(
            cid, commit, by_address=False
        )
        n = len(idxs)
        ok_ref, tally_ref = cpu.verify_commit_batch(pk, mg, sg, powers, counted)
        t0 = time.perf_counter()
        ok_tpl = prov.verify_rows_cached_templated(
            key, all_pk, np.asarray(vals_idx, np.int32), tpl[0], tpl[1], tpl[2], sg
        )
        t_tpl = time.perf_counter() - t0
        check(ok_tpl is not None, f"{label}: the templated cached-table path refused the batch")
        t0 = time.perf_counter()
        ok_gen, tally_gen = prov.verify_commit_batch(pk, mg, sg, powers, counted)
        t_gen = time.perf_counter() - t0
        submitted += 3 * n
        ok_tpl = np.asarray(ok_tpl)
        check((ok_tpl == ok_ref).all(), f"{label}: templated verdicts differ from the host's")
        check((np.asarray(ok_gen) == ok_ref).all(), f"{label}: generic verdicts differ from the host's")
        check(int(tally_gen) == int(tally_ref), f"{label}: tally {tally_gen} != host {tally_ref}")
        tally_tpl = int(powers[ok_tpl & counted].sum())
        check(tally_tpl == int(tally_ref), f"{label}: templated tally {tally_tpl} != host {tally_ref}")
        not_ok = sorted(int(idxs[i]) for i in np.nonzero(~ok_ref)[0])
        check(not_ok == sorted(rejected), f"{label}: rejected rows {not_ok[:10]}, expected {rejected[:10]}")
        say(
            f"{label}: verdict={got or 'accepted'} rows={n} rejected={len(not_ok)} "
            f"tally={int(tally_ref)} | verify_commit {t_commit:.3f}s "
            f"templated {t_tpl:.3f}s generic {t_gen:.3f}s (first case includes compile and table build)"
        )
    check_device_did_the_work(prov, errors, rows0, submitted, "slots-tpl")
    rows = vals._commit_batch_arrays(chain_id, good, by_address=False)
    check_stage2_forms(prov, key, all_pk, *rows[3:5])
    check_generic_forms(prov, *rows[2:5])


def check_stage2_forms(prov, key, all_pk, msgs, sigs):
    """Stage 2 in both forms on the same slots, on the chip: the Pallas
    kernel form the slot-order launches above ran (ops/stage2_kernel.py)
    against the XLA body that stays as its oracle, over the valid
    commit's rows at their validators' slots of the set's table bucket."""
    import jax
    import numpy as np

    from tendermint_tpu.crypto.batch import TABLED_COUNTS
    from tendermint_tpu.ops import curve, ed25519, field

    e = prov.model._tables_entry(key, all_pk)
    v = int(e.tables.shape[0])
    slots = lambda a: np.pad(np.asarray(a, dtype=np.uint8), ((0, v - len(a)), (0, 0)))  # noqa: E731
    sd, kd, _ = prov.model._program("t-prepare-s")(e.pk_dev, slots(msgs), slots(sigs))
    t0 = time.perf_counter()
    kern = jax.jit(ed25519.verify_stage_scan_tabled_slots)(sd, kd, e.tables, e.a_ok)[:4]
    xla = jax.jit(curve.double_scalar_mul_tabled)(sd, kd, e.tables)
    same = jax.jit(lambda a, b: [(field.canonical(x) == field.canonical(y)).all() for x, y in zip(a, b)])
    equal = [bool(x) for x in same(kern, tuple(xla))]
    check(all(equal), f"stage 2: kernel form and XLA body differ in coordinates {equal} of x, y, z, t")
    kernel_slots = TABLED_COUNTS.snapshot()["tabled_kernel_slots"]
    check(kernel_slots > 0, "tabled_kernel_slots is 0: no slot-order launch had the kernel form")
    say(
        f"stage 2 in both forms over {v} slots: x, y, z, t equal (canonical), "
        f"tabled_kernel_slots={kernel_slots} ({time.perf_counter() - t0:.1f}s, both compiles included)"
    )


def check_generic_forms(prov, pk, msgs, sigs):
    """The generic stage 2 in both forms on the same rows, on the chip:
    the Pallas kernel form (stage2_kernel.generic_scan) that the generic
    verify_commit_batch launches above ran, against the XLA body
    curve.double_scalar_mul_signed that stays as its oracle, over the
    valid commit's rows padded to their 10,240-row bucket. Bit-equal:
    every limb of x, y, z, t."""
    import jax
    import numpy as np

    from tendermint_tpu.crypto.batch import GENERIC_COUNTS
    from tendermint_tpu.models.verifier import _bucket
    from tendermint_tpu.ops import curve, ed25519

    n = _bucket(len(pk), 1)
    pad = lambda a: np.pad(np.asarray(a, dtype=np.uint8), ((0, n - len(a)), (0, 0)))  # noqa: E731
    sd, kd, nx, ny, nz, nt, _, _ = prov.model._program("prepare")(pad(pk), pad(msgs), pad(sigs))
    t0 = time.perf_counter()
    kern = jax.jit(ed25519.verify_stage_scan)(sd, kd, nx, ny, nz, nt)
    xla = jax.jit(curve.double_scalar_mul_signed)(sd, kd, curve.Point(nx, ny, nz, nt))
    equal = [bool((np.asarray(x) == np.asarray(y)).all()) for x, y in zip(kern, xla)]
    check(all(equal), f"generic stage 2: kernel form and XLA body differ in coordinates {equal} of x, y, z, t")
    kernel_rows = GENERIC_COUNTS.snapshot()["generic_kernel_rows"]
    check(kernel_rows > 0, "generic_kernel_rows is 0: no generic launch had the kernel form")
    say(
        f"generic stage 2 in both forms over {n} rows: x, y, z, t bit-equal, "
        f"generic_kernel_rows={kernel_rows} ({time.perf_counter() - t0:.1f}s, both compiles included)"
    )


# -- light-1k -----------------------------------------------------------------


def make_light_chain(by_addr, vals, chain_id: str, heights: int):
    """heights+1 signed headers over ONE validator set (lightserve's
    loadgen shape, signed directly)."""
    from tendermint_tpu.light.types import SignedHeader
    from tendermint_tpu.types.block import BlockID, Header, PartSetHeader

    t0_ns, block_ns = 1_700_000_000_000_000_000, 1_000_000_000
    vhash = vals.hash()
    parts = PartSetHeader(1, b"\xab" * 32)
    chain, last = [], BlockID()
    for h in range(1, heights + 2):
        header = Header(
            chain_id=chain_id, height=h, time_ns=t0_ns + h * block_ns,
            last_block_id=last, validators_hash=vhash, next_validators_hash=vhash,
            consensus_hash=b"\x01" * 32, app_hash=b"",
            proposer_address=vals.validators[0].address,
        )
        last = BlockID(header.hash(), parts)
        commit = signed_commit(by_addr, vals, chain_id, h, last, header.time_ns)
        chain.append(SignedHeader(header, commit))
    return chain, t0_ns + (heights + 2) * block_ns


def phase_light(
    seed: int, prov, errors: ErrorLog, n_vals: int = LIGHT_VALS, heights: int = LIGHT_HEIGHTS
):
    from tendermint_tpu.crypto.batch import CPUBatchVerifier
    from tendermint_tpu.light import verifier as light
    from tendermint_tpu.light.types import SignedHeader
    from tendermint_tpu.models.verifier import MAX_DEVICE_ROWS
    from tendermint_tpu.types.validator_set import CommitVerifySpec, verify_commits_batched

    chain_id = f"smoke-light-{seed}"
    t0 = time.perf_counter()
    by_addr, vals = make_validators(seed, n_vals, "light")
    chain, now_ns = make_light_chain(by_addr, vals, chain_id, heights)
    say(f"built {heights + 1} signed headers x {n_vals} validators in {time.perf_counter() - t0:.1f}s (host)")
    rows = heights * n_vals
    check(rows > MAX_DEVICE_ROWS or n_vals < LIGHT_VALS, f"{rows} rows do not pass the window")

    bad_h = heights // 2 + 1  # chain[bad_h] is the header at height bad_h + 1
    tampered_commit = copy.deepcopy(chain[bad_h].commit)
    s = bytearray(tampered_commit.signatures[5].signature)
    s[9] ^= 0x01
    tampered_commit.signatures[5].signature = bytes(s)
    tampered = list(chain)
    tampered[bad_h] = SignedHeader(chain[bad_h].header, tampered_commit)

    cpu = CPUBatchVerifier()
    period = 3 * 3600 * 10**9
    rows0 = prov.row_counts.snapshot()

    def run(c, provider):
        return outcome(
            lambda: light.verify_chain(
                chain_id, c[0], vals, [(sh, vals) for sh in c[1:]], period,
                now_ns=now_ns, provider=provider,
            )
        )

    submitted = 0
    for label, c, accepted in (("valid chain", chain, True), ("one tampered height", tampered, False)):
        t0 = time.perf_counter()
        got = run(c, prov)
        dt = time.perf_counter() - t0
        submitted += rows
        want = run(c, cpu)
        check(got == want, f"{label}: verify_chain says {got}, the host reference {want}")
        check((got is None) == accepted, f"{label}: verify_chain returned {got}")
        say(f"{label}: verdict={got or 'accepted'} rows={rows} in {dt:.3f}s (first includes compile)")

    # the same rows as one verify_commits_batched call: exactly the
    # tampered height's spec must carry an error
    specs = [
        CommitVerifySpec(vals, chain_id, sh.commit.block_id, sh.header.height, sh.commit)
        for sh in tampered[1:]
    ]
    res = verify_commits_batched(specs, provider=prov)
    submitted += rows
    failed = [i for i, e in enumerate(res) if e is not None]
    check(failed == [bad_h - 1], f"verify_commits_batched rejected specs {failed}, expected [{bad_h - 1}]")
    say(f"verify_commits_batched: {len(specs)} specs, rejected spec {failed} ({res[failed[0]]!r})")
    if rows > MAX_DEVICE_ROWS:
        check(
            any(k[0] == "slots-tpl" and k[1] == MAX_DEVICE_ROWS and e.ready
                for k, e in prov.model._entries.items()),
            "the chain never ran a full MAX_DEVICE_ROWS launch in slot order",
        )
    check_device_did_the_work(prov, errors, rows0, submitted, "slots-tpl")


# -- node-128 -----------------------------------------------------------------


def init_node_home(seed: int, home: str, n_vals: int):
    """`init` through the CLI, then the deployment's own settings: the
    128-validator genesis, free local ports, no fast sync (no peers)."""
    import socket

    from tendermint_tpu.cli import main as cli_main
    from tendermint_tpu.config import load_config
    from tendermint_tpu.config.config import write_config_file
    from tendermint_tpu.lightserve import loadgen
    from tendermint_tpu.privval import load_file_pv
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    shutil.rmtree(home, ignore_errors=True)
    chain_id = f"smoke-node-{seed}"
    cli_main(["--home", home, "init", "--chain-id", chain_id])
    cfg_path = os.path.join(home, "config", "config.toml")
    cfg = load_config(cfg_path).set_root(home)
    check(cfg.base.crypto_provider == "tpu", f"config default provider is {cfg.base.crypto_provider!r}")
    check(cfg.base.crypto_pipeline, "config default has the pipeline off")

    ports = []
    for _ in range(2):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
    cfg.p2p.laddr = f"tcp://127.0.0.1:{ports[0]}"
    cfg.rpc.laddr = f"tcp://127.0.0.1:{ports[1]}"
    cfg.base.fast_sync = False
    # a tx can meet a round whose proposer is a swarm validator (who
    # proposes nothing): propose timeout, nil round, then the block
    cfg.rpc.timeout_broadcast_tx_commit_ms = 30_000
    write_config_file(cfg_path, cfg)

    pv = load_file_pv(cfg.base.priv_validator_key_file(), cfg.base.priv_validator_state_file())
    swarm = loadgen.keys(n_vals - 1, f"smoke-{seed}-node")
    # The local validator proposes three rounds in five and needs the
    # swarm for every quorum: 190 of 317 is below 2/3, so no step ends
    # before the swarm's votes for it have been verified. (Were it to
    # hold quorum alone, their votes would arrive after the height had
    # moved on and be checked one by one on the host.)
    doc = GenesisDoc(
        chain_id=chain_id,
        genesis_time_ns=time.time_ns(),
        validators=[
            GenesisValidator(pub_key=pv.get_pub_key(), power=3 * (n_vals - 1) // 2, name="local")
        ]
        + [GenesisValidator(pub_key=p.pub_key(), power=1, name=f"swarm{i}") for i, p in enumerate(swarm)],
    )
    doc.validate_and_complete()
    doc.save_as(cfg.base.genesis_file())
    return cfg, swarm


async def swarm_votes(node, swarm, chain_id: str, stop: asyncio.Event, log: list):
    """The validators this process does not run: each echoes the local
    validator's vote (block or nil) for every (height, round, type)
    through the normal peer-vote path, as one burst — the shape gossip
    delivers to a node behind a busy peer."""
    from tendermint_tpu.codec.signbytes import PRECOMMIT_TYPE, PREVOTE_TYPE
    from tendermint_tpu.types.vote import Vote

    cs = node.consensus_state
    local = node.priv_validator.get_pub_key().address()
    by_addr = {p.pub_key().address(): p for p in swarm}
    done = set()
    while not stop.is_set():
        rs = cs.rs
        if rs.votes is not None:
            sims = [
                (i, by_addr[v.address])
                for i, v in enumerate(rs.validators.validators)
                if v.address in by_addr
            ]
            for vtype, votes in (
                (PREVOTE_TYPE, rs.votes.prevotes(rs.round)),
                (PRECOMMIT_TYPE, rs.votes.precommits(rs.round)),
            ):
                k = (rs.height, rs.round, vtype)
                mine = votes.get_by_address(local) if votes is not None else None
                if mine is None or k in done:
                    continue
                done.add(k)
                t_burst = time.monotonic()  # a burst counts from its first vote
                for vi, priv in sims:
                    v = Vote(
                        vote_type=vtype, height=rs.height, round=rs.round,
                        block_id=mine.block_id, timestamp_ns=mine.timestamp_ns + 1 + vi,
                        validator_address=priv.pub_key().address(), validator_index=vi,
                    )
                    v.signature = priv.sign(v.sign_bytes(chain_id))
                    await cs.add_vote_from_peer(v, "smoke-swarm")
                log.append((t_burst, len(sims)))
        await asyncio.sleep(0.005)


def pipeline_view(engines: dict) -> dict:
    check("pipeline" in engines, f"the engines route has no verifier stanza: {sorted(engines)}")
    return engines["pipeline"]


def node_is_warm(pipe: dict) -> bool:
    """The boot-time warm-up (node.py on_start) is done: this chain's
    tables are built, a commit's templated slot-order shape is ready
    and nothing is still compiling."""
    buckets = pipe.get("buckets") or {}
    states = [b["state"] for b in buckets.values()]
    return (
        any(k.startswith("tables:") and b["state"] == "ready" for k, b in buckets.items())
        and any(k.startswith("fn:slots-tpl/") and b["state"] == "ready" for k, b in buckets.items())
        and not any(s in ("compiling", "cold") for s in states)
    )


async def run_node(seed: int, cfg, swarm, errors: ErrorLog):
    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.p2p.conn import native_frames
    from tendermint_tpu.rpc.client import HTTPClient
    from tendermint_tpu.rpc.server import RPCServer

    say(f"frame codec: {'native libsecretconn' if native_frames.load() else 'python (cryptography)'}")
    node = default_new_node(cfg)
    node.rpc_server = RPCServer(node)
    chain_id = node.genesis_doc.chain_id
    rpc = HTTPClient(cfg.rpc.laddr.replace("tcp://", ""))
    stop, injected = asyncio.Event(), []
    t_boot = time.monotonic()
    await node.start()
    swarm_task = asyncio.create_task(swarm_votes(node, swarm, chain_id, stop, injected))
    try:
        # 1. the boot-time warm-up, by a stated deadline
        while True:
            pipe = pipeline_view((await rpc.engines())["engines"])
            if node_is_warm(pipe):
                break
            check(
                time.monotonic() - t_boot < NODE_WARM_DEADLINE_S,
                f"not warm {NODE_WARM_DEADLINE_S:.0f}s after boot: "
                f"{ {k: b['state'] for k, b in pipe['buckets'].items()} }",
            )
            check(not swarm_task.done(), f"the swarm stopped: {swarm_task}")
            await asyncio.sleep(1.0)
        h_warm = (await rpc.status())["sync_info"]["latest_block_height"]
        say(f"warm {time.monotonic() - t_boot:.1f}s after boot, at height {h_warm}")

        # 2. the asserted window opens at a quiet point: let bursts that
        # were injected while cold finish on whatever path they took
        await asyncio.sleep(1.0)
        pipe0 = pipeline_view((await rpc.engines())["engines"])
        # after the counters' first reading, and a burst is timed by its
        # first vote: one under way while the engines route answers is then
        # in neither the sum nor the difference
        t_open = time.monotonic()
        h0 = int((await rpc.status())["sync_info"]["latest_block_height"])

        # 3. a few txs over HTTP, each read back
        for i in range(NODE_TXS):
            k, v = f"smoke{seed}k{i}", f"v{i}"
            t0 = time.monotonic()
            res = await rpc.broadcast_tx_commit(tx=f"{k}={v}".encode().hex())
            check(
                res["check_tx"]["code"] == 0 and res["deliver_tx"]["code"] == 0,
                f"tx {k} was not committed: {res}",
            )
            q = await rpc.abci_query(path="", data=k.encode().hex())
            got = bytes.fromhex(q["response"]["value"]).decode()
            check(got == v, f"abci_query({k}) read back {got!r}, wrote {v!r}")
            say(f"tx {k}={v} committed at height {res['height']} in {time.monotonic() - t0:.2f}s, read back")

        # 4. at least NODE_HEIGHTS_AFTER_WARM further heights
        await node.consensus_state.wait_for_height(h0 + NODE_HEIGHTS_AFTER_WARM, timeout_s=180)

        # 5. close the window: stop the swarm, let the last burst land
        stop.set()
        await swarm_task
        await asyncio.sleep(1.0)
        engines = (await rpc.engines())["engines"]
        pipe1 = pipeline_view(engines)
        h1 = int((await rpc.status())["sync_info"]["latest_block_height"])
    finally:
        stop.set()
        swarm_task.cancel()
        await asyncio.gather(swarm_task, return_exceptions=True)
        await node.stop()

    say(f"engines: {engine_report(engines)}")
    window = [(t, n) for t, n in injected if t >= t_open]
    swarm_rows = sum(n for _, n in window)
    d_dev = pipe1["device_rows"] - pipe0["device_rows"]
    d_host = pipe1["host_rows"] - pipe0["host_rows"]
    # the local validator's own votes are single rows, below
    # min_device_batch: one per burst the swarm answered, plus the
    # round in flight at either edge of the window
    local_single = len(window) + 4
    say(
        f"window: heights {h0}->{h1}, {len(window)} swarm bursts = {swarm_rows} votes; "
        f"device_rows +{d_dev:.0f}, host_rows +{d_host:.0f} (local single votes <= {local_single})"
    )
    check(h1 >= h0 + NODE_HEIGHTS_AFTER_WARM, f"only {h1 - h0} heights committed after warm")
    check(swarm_rows > 0, "the swarm injected nothing after warm")
    check(d_dev >= swarm_rows, f"device_rows grew {d_dev:.0f} < {swarm_rows} swarm votes injected after warm")
    check(d_host <= local_single, f"host_rows grew {d_host:.0f} > {local_single}: votes were served on the host")
    check_engines_healthy(engines)
    c = pipe1["counters"]
    check(
        c["fallback_serial"] == 0 and c["worker_restarts"] == 0,
        f"pipeline deadline failures: {c['fallback_serial']} serial fallbacks, {c['worker_restarts']} restarts",
    )
    check(not errors.records, f"the verifier logged errors: {errors.records}")


def phase_node(seed: int, errors: ErrorLog, n_vals: int = NODE_VALS):
    home = os.path.join(REPO, ".cache", "smoke-node")
    cfg, swarm = init_node_home(seed, home, n_vals)
    try:
        asyncio.run(run_node(seed, cfg, swarm, errors))
    finally:
        shutil.rmtree(home, ignore_errors=True)


# -- the multi-chip phase (--chips 4) -----------------------------------------


def phase_mesh(seed: int, errors: ErrorLog, n_dev: int = 4, n_rows: int = MESH_ROWS):
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import meshcheck

    from tendermint_tpu.models.verifier import VerifierModel, _bucket
    from tendermint_tpu.parallel import make_mesh

    devs = jax.devices()[:n_dev]
    mesh_m = VerifierModel(mesh=make_mesh(devs), block_on_compile=True)
    single_m = VerifierModel(block_on_compile=True)
    t0 = time.perf_counter()
    fails = meshcheck.check_shardmap_verifier(
        devs, n=n_rows, msg_len=160, seed=seed, models=(mesh_m, single_m)
    )
    say(f"shard_map verifier vs single device on {n_rows} rows: {time.perf_counter() - t0:.1f}s, {len(fails)} failure(s)")
    t0 = time.perf_counter()
    fails += meshcheck.check_chunked_engines(devs)
    say(f"chunk-routed engines: {time.perf_counter() - t0:.1f}s")
    for f in fails:
        say(f"FAIL: {f}")
    check(not fails, f"{len(fails)} mesh parity failure(s)")

    # where the arrays actually live: rows must spread, tables replicate
    n_pad = _bucket(n_rows, n_dev)
    s1, _ = mesh_m._stages()
    pre = s1(
        jnp.asarray(np.zeros((n_pad, 32), np.uint8)),
        jnp.asarray(np.zeros((n_pad, 160), np.uint8)),
        jnp.asarray(np.zeros((n_pad, 64), np.uint8)),
    )
    shards = [(s.device.id, tuple(s.data.shape)) for s in pre[0].addressable_shards]
    say(f"stage-1 output sharding: {pre[0].sharding} shards: {shards}")
    check(len({d for d, _ in shards}) == n_dev, f"rows live on {len({d for d, _ in shards})} device(s)")
    check(all(shape[0] == n_pad // n_dev for _, shape in shards), f"uneven row shards: {shards}")
    for key, e in mesh_m._valset_tables.items():
        say(
            f"tables {key[:12]!r}: {e.tables.sharding} on devices "
            f"{sorted(s.device.id for s in e.tables.addressable_shards)}"
        )
        check(len(e.tables.addressable_shards) == n_dev, "tables are not on every device")
    for d in devs:
        ms = d.memory_stats() or {}
        say(f"device {d.id}: bytes_in_use={ms.get('bytes_in_use')} peak={ms.get('peak_bytes_in_use')}")
    dev, host = mesh_m.row_counts.snapshot()
    check(dev > 0 and host == 0, f"mesh model rows: device {dev}, host {host}")
    check(not errors.records, f"the verifier logged errors: {errors.records}")


# -- driver -------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    phase_s, device = {}, None

    def phase(name, fn):
        say(f"=== phase {name}")
        t0 = time.perf_counter()
        out = fn()
        phase_s[name] = round(time.perf_counter() - t0, 1)
        say(f"=== phase {name} OK in {phase_s[name]}s")
        return out

    try:
        refuse_host_overrides()
        device = phase("device", lambda: phase_device(args.chips))
        try:
            from tendermint_tpu.crypto.batch import make_provider, set_default_provider
            from tendermint_tpu.utils.jaxenv import enable_compile_cache
        except ImportError as e:
            raise SmokeFailure(f"the tendermint_tpu package is not beside this script: {e}")
        cache = enable_compile_cache()
        n_cache0 = cache_entries(cache)
        say(f"compile cache: {cache} ({n_cache0} entries at start)")
        errors = watch_verifier_errors()
        if args.chips == 4:
            phase("mesh-4", lambda: phase_mesh(args.seed, errors))
        else:
            prov = make_provider("tpu", block_on_compile=True)
            set_default_provider(prov)
            phase("commit-10k", lambda: phase_commit(args.seed, prov, errors))
            phase("light-1k", lambda: phase_light(args.seed, prov, errors))
            phase("node-128", lambda: phase_node(args.seed, errors))
        say(f"compile cache: {cache} ({cache_entries(cache)} entries at end, {n_cache0} at start)")
        say(f"seconds per phase: {json.dumps(phase_s)}")
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    except Exception:
        traceback.print_exc()
        say("FAILED: unexpected exception (traceback on stderr)")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
