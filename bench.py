"""Benchmark: batched ed25519 commit verification on the local accelerator.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The headline metric is VerifyCommit wall latency for a 10k-validator
commit (BASELINE.json north star: <2ms on v5e-1, >=50x Go serial).
vs_baseline is measured against the serial host verifier (OpenSSL via
`cryptography` -- itself faster than Go's x/crypto, so the ratio is
conservative vs the reference).

Processes: a supervisor that never imports JAX runs the measuring
child under a hard deadline and, once that child has EXITED, the
cold-start child (a chip belongs to one process at a time). The
measuring child takes JAX's default backend and says which it got;
where that is not a TPU it exits non-zero, unless the caller asked for
the CPU with JAX_PLATFORMS=cpu — then every number is labelled cpu.
- any unexpected error still prints a JSON line with an "error" field;
- cold/warm compile seconds and cache status go to stderr.

Details go to stderr; stdout carries exactly the one JSON line.
"""

import json
import os
import sys
import time

from tendermint_tpu.utils.jaxenv import compile_cache_dir, scope_tables_cache

CACHE_DIR = compile_cache_dir()
# Bench-scoped table cache: the synthetic b"bench-valset" tables
# (~120MB at 10k) stay out of the production dir. The coldstart child
# inherits this.
scope_tables_cache("bench")

BENCH_N = int(os.environ.get("TM_BENCH_N", "10000"))  # override for smoke tests
MSG_LEN = 160
# Hard deadline: emit SOMETHING before an external timeout can kill the
# process with no output. Overridable for slow rigs.
DEADLINE_S = int(os.environ.get("TM_BENCH_DEADLINE_S", "540"))

_partial = {"value_ms": None, "vs_baseline": None, "note": "deadline before first measurement"}


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def emit(value_ms, vs_baseline, **extra):
    line = {
        "metric": "verify_commit_p50_latency_10k_validators",
        "value": value_ms,
        "unit": "ms",
        "vs_baseline": vs_baseline,
    }
    line.update(extra)
    print(json.dumps(line), flush=True)


def _keyring(n, seed=1234):
    """The deterministic signing keyring behind make_batch: row i signs
    with keyring[i % len(keyring)]."""
    import numpy as np

    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )
    except ImportError:  # no OpenSSL wheel: pure-Python fallback
        from tendermint_tpu.crypto.fallback import Ed25519PrivateKey

    rng = np.random.RandomState(seed)
    n_keys = min(n, 64)
    return [
        Ed25519PrivateKey.from_private_bytes(bytes(rng.bytes(32)))
        for _ in range(n_keys)
    ]


def make_batch(n, msg_len=MSG_LEN, seed=1234):
    """n rows of distinct valid (pubkey, msg, sig) triples, signed with a
    small keyring (distinct messages per row)."""
    import numpy as np

    try:
        from cryptography.hazmat.primitives import serialization
    except ImportError:  # no OpenSSL wheel: pure-Python fallback
        from tendermint_tpu.crypto.fallback import serialization

    keys = _keyring(n, seed)
    n_keys = len(keys)
    rng = np.random.RandomState(seed)
    for _ in range(n_keys):
        rng.bytes(32)  # advance past the key seeds _keyring consumed
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
        k = keys[i % n_keys]
        pks[i] = np.frombuffer(pubs[i % n_keys], dtype=np.uint8)
        msgs[i] = np.frombuffer(msg, dtype=np.uint8)
        sigs[i] = np.frombuffer(k.sign(msg), dtype=np.uint8)
    return pks, msgs, sigs


def _verify_then_tally(model, pks, msgs, sigs, powers, counted):
    """model.verify, then the host's column sum over its verdicts (what
    BatchVerifier.verify_commit_batch does over a provider)."""
    import numpy as np

    ok = model.verify(pks, msgs, sigs)
    return ok, int(np.sum(np.where(ok & counted, powers, 0)))


def stream_windows(fn, dev_args, n_calls: int) -> float:
    """Launch n_calls invocations of the warm jitted `fn` on
    device-resident args, sync on the LAST output only; returns elapsed
    seconds. A single TPU core executes its stream in order, so the
    last output being ready implies every prior dispatch completed,
    and the one sync keeps host round trips out of a device rate. Used
    by the pipelined-rate sections below and benchmarks/micro.py."""
    import numpy as np

    out = fn(*dev_args)
    np.asarray(out[0] if isinstance(out, tuple) else out)  # warm + real sync
    t0 = time.perf_counter()
    out = None
    for _ in range(n_calls):
        out = fn(*dev_args)
    np.asarray(out[0] if isinstance(out, tuple) else out)
    return time.perf_counter() - t0


_LAST_TPU_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "benchmarks", "last_tpu_result.json"
)


def _record_tpu_result(line: dict) -> None:
    """Persist the latest real-accelerator measurement as the
    regression guard's baseline (a run-time file, git-ignored; it is
    never reported in place of a measurement). Atomic write: a kill
    mid-dump must not destroy the previous good record (same pattern
    as privval/file.py _atomic_write)."""
    try:
        import datetime
        import subprocess
        import tempfile

        line = dict(line)
        line["measured_at"] = datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y-%m-%dT%H:%MZ"
        )
        try:
            line["git_rev"] = (
                subprocess.run(
                    ["git", "rev-parse", "--short", "HEAD"],
                    capture_output=True, text=True, timeout=10,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                ).stdout.strip()
                or None
            )
        except Exception:
            line["git_rev"] = None
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(_LAST_TPU_PATH), prefix=".last_tpu_"
        )
        with os.fdopen(fd, "w") as fp:
            json.dump(line, fp)
        os.replace(tmp, _LAST_TPU_PATH)
    except Exception as e:  # never fail the bench over bookkeeping
        log(f"could not record tpu result: {e!r}")


_LAST_TPU_MAX_AGE_DAYS = 14


def _last_tpu_result():
    """The recorded measurement, or None when unreadable or too old to
    be meaningful (it carries measured_at + git_rev so a consumer can
    see exactly which code produced it)."""
    try:
        import datetime

        with open(_LAST_TPU_PATH) as fp:
            line = json.load(fp)
        ts = datetime.datetime.strptime(
            line.get("measured_at", ""), "%Y-%m-%dT%H:%MZ"
        ).replace(tzinfo=datetime.timezone.utc)
        age = datetime.datetime.now(datetime.timezone.utc) - ts
        if age.days > _LAST_TPU_MAX_AGE_DAYS:
            return None
        return line
    except Exception:
        return None


# -- bench provenance ------------------------------------------------------
#
# Nothing in the json used to say WHICH backend produced each section,
# so a CPU number could be compared against a TPU baseline without
# complaint. Every section now stamps the JAX platform that actually
# executed it (``<section>_platform``), the emitted line carries the
# run-wide jax_platform/jax_device, and the regression guard refuses —
# LOUDLY, via GUARD_SKIPS in the line — to compare a key across
# mismatched platforms instead of silently judging apples by oranges.


def _jax_platform() -> str:
    try:
        import jax

        return jax.devices()[0].platform
    except Exception:
        return "unknown"


def _jax_provenance() -> dict:
    """Run-wide provenance keys for the emitted line."""
    try:
        import jax

        d = jax.devices()[0]
        return {
            "jax_platform": d.platform,
            "jax_device": str(d),
            "jax_device_count": len(jax.devices()),
        }
    except Exception as e:
        return {"jax_platform": "unknown", "jax_error": repr(e)[:120]}


def _stamped(section: str, out: dict) -> dict:
    """Stamp a section's result dict with the platform that ran it."""
    out = dict(out)
    out[f"{section}_platform"] = _jax_platform()
    return out


# -- regression guard ------------------------------------------------------
#
# Round-3 lesson: the flagship tabled path was broken by a last-minute
# refactor and the bench silently degraded to the generic path — the
# builder's own rig must catch that. When a previous real-accelerator
# record exists, a sub-path that previously measured and now errors, or
# that regresses beyond tolerance, hard-fails the bench (exit code 3)
# with the failures listed in the emitted line.

_GUARD_TOL = float(os.environ.get("TM_BENCH_GUARD_TOL", "0.20"))
_GUARD_KEYS = [
    ("value", "lower"),
    ("generic_p50_ms", "lower"),
    ("tabled_p50_ms", "lower"),
    ("tabled_tpl_p50_ms", "lower"),
    ("tabled_pipelined_ms", "lower"),
    ("device_pipelined_ms", "lower"),
    ("tabled_sigs_per_sec_sustained", "higher"),
    ("sigs_per_sec_sustained", "higher"),
    ("replay_speedup", "higher"),
    ("merkle_root_speedup", "higher"),
    ("lightserve_clients_per_sec", "higher"),
    ("lightserve_speedup", "higher"),
    ("ingest_txs_per_sec", "higher"),
    ("ingest_speedup", "higher"),
    ("deliver_speedup", "higher"),
    ("e2e_txs_per_sec", "higher"),
    ("bls_commit_bytes_ratio", "higher"),
    ("bls_verify_speedup", "higher"),
    ("sim_heights_per_sec", "higher"),
    ("sim_recovery_s", "lower"),
    ("sim_byz_commit_rate", "higher"),
    ("mesh_sigs_per_sec", "higher"),
    ("mesh_speedup", "higher"),
    ("flightrec_overhead_pct", "lower"),
    ("coldstart_first_verify_s", None),   # presence-only: timing varies
    ("coldstart_tabled_first_s", None),
]

# guard key -> the section-provenance key that must MATCH between the
# recorded baseline and this run for the comparison to mean anything
_KEY_SECTION_PLATFORM = {
    "replay_speedup": "replay_platform",
    "merkle_root_speedup": "merkle_platform",
    "lightserve_clients_per_sec": "lightserve_platform",
    "lightserve_speedup": "lightserve_platform",
    "ingest_txs_per_sec": "ingest_platform",
    "ingest_speedup": "ingest_platform",
    "deliver_speedup": "exec_platform",
    "e2e_txs_per_sec": "exec_platform",
    "bls_commit_bytes_ratio": "bls_platform",
    "bls_verify_speedup": "bls_platform",
    "sim_heights_per_sec": "sim_platform",
    "sim_recovery_s": "sim_platform",
    "sim_byz_commit_rate": "sim_platform",
    "mesh_sigs_per_sec": "mesh_platform",
    "mesh_speedup": "mesh_platform",
    "flightrec_overhead_pct": "trace_platform",
}

# provenance-mismatch skip notes from the LAST _regression_guard call —
# logged to stderr and attached to the emitted line as "guard_skips",
# so a skipped comparison is loud in the artifact, never silent
GUARD_SKIPS: list = []


def _regression_guard(line: dict, platform: str) -> list:
    """Failure strings comparing `line` to the last recorded accelerator
    result; empty when clean (or no comparable record). Comparisons
    whose provenance doesn't match (a TPU-measured baseline vs a
    CPU-fallback run, run-wide or per-section) are SKIPPED LOUDLY via
    GUARD_SKIPS rather than judged."""
    global GUARD_SKIPS
    GUARD_SKIPS = []
    if os.environ.get("TM_BENCH_NO_GUARD") == "1":
        return []
    last = _last_tpu_result()
    if platform == "cpu":
        if last and last.get("platform") not in (None, "cpu"):
            msg = (
                "guard skipped entirely: this run executed on the CPU "
                f"fallback but the recorded baseline is {last.get('platform')} "
                "— TPU-guarded keys are not comparable (the r04/r05 "
                "carried-numbers trap)"
            )
            GUARD_SKIPS.append(msg)
            log(f"GUARD SKIP: {msg}")
        return []
    if not last or last.get("platform") == "cpu":
        return []
    if int(last.get("bench_n", 10000)) != BENCH_N:
        return []  # different batch size: numbers aren't comparable
    fails = []
    for key, direction in _GUARD_KEYS:
        prev, cur = last.get(key), line.get(key)
        if not isinstance(prev, (int, float)):
            continue
        sec = _KEY_SECTION_PLATFORM.get(key)
        if sec is not None:
            prev_p, cur_p = last.get(sec), line.get(sec)
            if prev_p and cur_p and prev_p != cur_p:
                msg = (
                    f"{key}: baseline measured on {prev_p}, this run's "
                    f"section ran on {cur_p} — not comparable, skipping"
                )
                GUARD_SKIPS.append(msg)
                log(f"GUARD SKIP: {msg}")
                continue
        if not isinstance(cur, (int, float)):
            fails.append(f"{key}: previously {prev}, now missing/errored")
        elif direction == "lower" and cur > prev * (1 + _GUARD_TOL):
            fails.append(f"{key}: {prev} -> {cur} (regressed >{_GUARD_TOL:.0%})")
        elif direction == "higher" and cur < prev * (1 - _GUARD_TOL):
            fails.append(f"{key}: {prev} -> {cur} (regressed >{_GUARD_TOL:.0%})")
    return fails


def run_bench(platform: str):
    import numpy as np
    import jax

    from tendermint_tpu.models.verifier import VerifierModel

    devs = jax.devices()
    log(f"devices: {devs}")
    model = VerifierModel()

    n = BENCH_N
    pks, msgs, sigs = make_batch(n)
    powers = np.full(n, 10, dtype=np.int64)
    counted = np.ones(n, dtype=bool)

    # -- serial host baseline (sampled) -----------------------------------
    from tendermint_tpu.crypto.batch import CPUBatchVerifier

    sample = 512
    cpu = CPUBatchVerifier()
    t0 = time.perf_counter()
    ok_cpu = cpu.verify_batch(pks[:sample], msgs[:sample], sigs[:sample])
    cpu_per_sig = (time.perf_counter() - t0) / sample
    assert ok_cpu.all()
    baseline_10k = cpu_per_sig * n
    log(f"host serial: {cpu_per_sig*1e6:.1f} us/sig -> {baseline_10k*1e3:.1f} ms per 10k commit")

    # -- device: compile/warm (persistent cache makes re-runs cheap) ------
    cache_before = len(os.listdir(CACHE_DIR)) if os.path.isdir(CACHE_DIR) else 0
    t0 = time.perf_counter()
    ok, tally = _verify_then_tally(model, pks, msgs, sigs, powers, counted)
    cold_s = time.perf_counter() - t0
    assert ok.all() and tally == n * 10, (int(ok.sum()), tally)
    cache_after = len(os.listdir(CACHE_DIR)) if os.path.isdir(CACHE_DIR) else 0
    log(
        f"first call (compile+run): {cold_s:.1f} s  "
        f"(persistent cache entries {cache_before} -> {cache_after})"
    )

    # -- measure p50 over repeated runs (adaptive count: an asked-for
    # CPU run takes tens of seconds per call, not ms) --------------------
    t0 = time.perf_counter()
    ok, tally = _verify_then_tally(model, pks, msgs, sigs, powers, counted)
    first_warm = time.perf_counter() - t0
    _partial.update(
        value_ms=round(first_warm * 1e3, 3),
        vs_baseline=round(baseline_10k / first_warm, 2),
        note="single warm run (deadline)",
    )
    _save_partial(platform)
    iters = 9 if first_warm < 0.5 else 1
    times = [first_warm]
    for _ in range(iters):
        t0 = time.perf_counter()
        ok, tally = _verify_then_tally(model, pks, msgs, sigs, powers, counted)
        times.append(time.perf_counter() - t0)
    p50 = sorted(times)[len(times) // 2]
    thr = n / p50
    log(f"VerifyCommit@10k p50: {p50*1e3:.2f} ms  ({thr:,.0f} sigs/s)")
    log(f"all times (ms): {[round(t*1e3,2) for t in times]}")

    # negative control on the warm path
    sigs_bad = sigs.copy()
    sigs_bad[7, 3] ^= 1
    ok_bad, _ = _verify_then_tally(model, pks, msgs, sigs_bad, powers, counted)
    assert not ok_bad[7] and ok_bad.sum() == n - 1

    # -- per-valset cached-table path (round 3) ---------------------------
    # The live verify_commit hot path: tables of each -A precomputed once
    # per valset (pubkeys are stable across heights), leaving sha512 +
    # a 16-doubling (4*SPLIT_W) scan + blocked-inversion encode per commit.
    tabled = {}
    tabled_p50 = None
    try:
        key = b"bench-valset"
        idx = np.arange(n, dtype=np.int32)
        t0 = time.perf_counter()
        ok_t = model.verify_rows_cached(key, pks, idx, msgs, sigs)
        tabled_cold_s = time.perf_counter() - t0
        if ok_t is not None:
            assert ok_t.all(), int(ok_t.sum())
            pool = model.key_pool
            tabled["tables_build_s"] = round(pool.build_s, 2) if pool.build_s else None
            # "disk" means persisted key tables were reused: build_s is then
            # load time, NOT comparable to a prior round's device build
            tabled["tables_source"] = "build" if pool.dispatches else "disk"
            tabled["tabled_cold_s"] = round(tabled_cold_s, 1)
            t_times = []
            for _ in range(5):
                t0 = time.perf_counter()
                ok_t = model.verify_rows_cached(key, pks, idx, msgs, sigs)
                t_times.append(time.perf_counter() - t0)
            tabled_p50 = sorted(t_times)[len(t_times) // 2]
            tabled["tabled_p50_ms"] = round(tabled_p50 * 1e3, 2)
            log(
                f"tabled VerifyCommit@10k p50: {tabled_p50*1e3:.2f} ms "
                f"({n/tabled_p50:,.0f} sigs/s; build {tabled['tables_build_s']}s)"
            )
            # negative control through the cached path
            ok_tb = model.verify_rows_cached(key, pks, idx, msgs, sigs_bad)
            assert ok_tb is not None and not ok_tb[7] and ok_tb.sum() == n - 1

            # TEMPLATED messages — the live single-commit hot path
            # (validator_set._rows_cached tries this first): per-row
            # message H2D is 12 bytes (tmpl_idx + ts8) instead of 160.
            # Build a real commit-shaped batch: ONE template, per-row 8-byte
            # timestamp splice, rows re-signed over the materialized
            # bytes so the device must reconstruct them exactly.
            tpl = msgs[:1].copy()
            t_idx = np.zeros(n, dtype=np.int32)
            ts8 = msgs[:, 93:101].copy()
            mt = np.broadcast_to(tpl, (n, tpl.shape[1])).copy()
            mt[:, 93:101] = ts8
            ring = _keyring(n)
            sg_t = np.stack(
                [
                    np.frombuffer(
                        ring[i % len(ring)].sign(mt[i].tobytes()), dtype=np.uint8
                    )
                    for i in range(n)
                ]
            )
            ok_tpl = model.verify_rows_cached_templated(
                key, pks, idx, tpl, t_idx, ts8, sg_t
            )
            if ok_tpl is not None:
                assert ok_tpl.all(), int(ok_tpl.sum())
                tt_times = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    model.verify_rows_cached_templated(
                        key, pks, idx, tpl, t_idx, ts8, sg_t
                    )
                    tt_times.append(time.perf_counter() - t0)
                tpl_p50 = sorted(tt_times)[len(tt_times) // 2]
                tabled["tabled_tpl_p50_ms"] = round(tpl_p50 * 1e3, 2)
                log(
                    f"tabled templated VerifyCommit@10k p50: "
                    f"{tpl_p50*1e3:.2f} ms ({n/tpl_p50:,.0f} sigs/s)"
                )
                # negative control: corrupt one timestamp byte
                ts8_bad = ts8.copy()
                ts8_bad[7, 3] ^= 0xFF
                ok_tpl_b = model.verify_rows_cached_templated(
                    key, pks, idx, tpl, t_idx, ts8_bad, sg_t
                )
                assert (
                    ok_tpl_b is not None
                    and not ok_tpl_b[7]
                    and ok_tpl_b.sum() == n - 1
                )
            # pipelined: K chained stage dispatches, one sync
            import jax as _jax
            import jax.numpy as jnp

            s3 = model._table_stage_fns()[2]
            s1d, s2d = model._slot_stage_fns()
            # the table's own padded row count, NOT a hardcoded 10240:
            # TM_BENCH_N smoke runs build smaller tables
            n_pad = int(e.tables.shape[0])
            mg_d = _jax.device_put(jnp.asarray(model._pad(msgs, n_pad)))
            sg_d = _jax.device_put(jnp.asarray(model._pad(sigs, n_pad)))
            pk_d = e.pk_dev[:n_pad]
            tb_d, ao_d = e.tables[:n_pad], e.a_ok[:n_pad]

            def chain():
                # slot order, one commit a launch: no index gathers anywhere
                sd, kd, s_ok = s1d(pk_d, mg_d, sg_d)
                px, py, pz, pt, a_ok = s2d(sd, kd, tb_d, ao_d)
                return s3(px, py, pz, pt, sg_d, a_ok, s_ok)

            # deep queue, one final sync — stream_windows owns the sync
            # discipline (chain takes no args, so dev_args is empty).
            # Depth matters: host enqueue cost per dispatch makes a
            # shallow queue under-measure the device (earlier remote
            # chip: K=16 -> 30.3 ms/commit, K=128 -> 26.3; to be
            # re-measured)
            K = 128
            tp = stream_windows(chain, (), K) / K
            tabled["tabled_pipelined_ms"] = round(tp * 1e3, 2)
            tabled["tabled_sigs_per_sec_sustained"] = round(n / tp)
            log(
                f"tabled pipelined: {tp*1e3:.1f} ms/commit "
                f"({n/tp:,.0f} sigs/s sustained)"
            )
    except Exception as ex:  # keep the main line; the guard below flags it
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"tabled measurement failed: {ex!r}")
        tabled["tabled_error"] = repr(ex)[:200]

    # -- pipelined device rate: launch K calls, sync once -----------------
    # Amortizing K in-flight calls over one sync takes per-call
    # transfer/sync latency out and isolates device throughput.
    pipelined_ms = None
    try:
        import jax as _jax
        import jax.numpy as jnp

        fn = model._get_fn(10240, MSG_LEN)
        if fn is not None and n <= 10240:
            pad = lambda a: model._pad(np.asarray(a), 10240)
            dev = [
                _jax.device_put(jnp.asarray(pad(x.astype(np.uint8))))
                for x in (pks, msgs, sigs)
            ]
            K = 64  # the generic chain is ~70 ms/commit: less depth needed
            pipelined_ms = stream_windows(fn, dev, K) / K
            log(
                f"pipelined device rate: {pipelined_ms*1e3:.1f} ms/commit "
                f"({n/pipelined_ms:,.0f} sigs/s sustained)"
            )
    except Exception as ex:  # diagnostic only; never forfeit the main line
        log(f"pipelined measurement failed: {ex!r}")

    # -- fast-sync replay: pipelined dispatch vs synchronous --------------
    try:
        from tendermint_tpu.crypto.batch import TPUBatchVerifier

        tpv = TPUBatchVerifier()
        tpv._model = model  # reuse the warmed buckets from the sections above
        replay_extra = _stamped("replay", replay_bench(tpv))
    except Exception as ex:  # diagnostic only; never forfeit the main line
        log(f"replay provider setup failed: {ex!r}")
        replay_extra = _stamped("replay", {"replay_error": repr(ex)[:200]})

    # -- lightserve: batched client fleet vs per-client serial ------------
    try:
        _ls_provider = tpv  # the warmed device provider from the replay section
    except NameError:
        _ls_provider = None
    lightserve_extra = _stamped("lightserve", lightserve_bench(_ls_provider))

    # -- ingest: batched mempool admission vs per-tx serial CheckTx -------
    ingest_extra = _stamped("ingest", ingest_bench(_ls_provider, e2e=False))

    # -- execution: DeliverBatch lane vs serial per-tx DeliverTx ----------
    exec_extra = _stamped("exec", exec_bench(_ls_provider))

    # -- merkle engine: device vs host root + part-set split --------------
    merkle_extra = _stamped("merkle", merkle_bench())

    # -- BLS aggregation: bytes/commit + verify latency vs per-sig --------
    bls_extra = _stamped("bls", bls_bench())

    # -- simulator: nodes x heights sweep on the deterministic net --------
    sim_extra = _stamped("sim", sim_bench())

    # -- mesh runtime: weak scaling across the local device inventory -----
    mesh_extra = _stamped("mesh", mesh_bench())

    # -- degraded mode: circuit-broken fallback + idle watchdog cost ------
    degraded_extra = _stamped("degraded", degraded_mode_bench())

    # -- flight recorder: overhead + per-stage breakdown ------------------
    trace_extra = _stamped("trace", trace_overhead_bench())

    extra = {}
    if pipelined_ms is not None:
        extra = {
            "device_pipelined_ms": round(pipelined_ms * 1e3, 2),
            "sigs_per_sec_sustained": round(n / pipelined_ms),
        }
    # headline = the best path a live node would take (the cached-table
    # path IS the verify_commit hot path when tables are warm; the
    # templated flavor is what validator_set actually sends)
    candidates = [p50]
    if tabled_p50 is not None:
        candidates.append(tabled_p50)
    if tabled.get("tabled_tpl_p50_ms") is not None:
        candidates.append(tabled["tabled_tpl_p50_ms"] / 1e3)
    best_p50 = min(candidates)
    if tabled.get("tabled_sigs_per_sec_sustained") and (
        not extra.get("sigs_per_sec_sustained")
        or tabled["tabled_sigs_per_sec_sustained"] > extra["sigs_per_sec_sustained"]
    ):
        extra["sigs_per_sec_sustained"] = tabled["tabled_sigs_per_sec_sustained"]
    line = {
        "metric": "verify_commit_p50_latency_10k_validators",
        "value": round(best_p50 * 1e3, 3),
        "unit": "ms",
        "vs_baseline": round(baseline_10k / best_p50, 2),
        "platform": platform,
        **_jax_provenance(),
        "bench_n": n,
        "cold_compile_s": round(cold_s, 1),
        "host_baseline_ms": round(baseline_10k * 1e3, 1),
        "generic_p50_ms": round(p50 * 1e3, 3),
        **extra,
        **tabled,
        **replay_extra,
        **lightserve_extra,
        **ingest_extra,
        **exec_extra,
        **merkle_extra,
        **bls_extra,
        **sim_extra,
        **mesh_extra,
        **degraded_extra,
        **trace_extra,
    }
    # The supervisor finishes the line: it runs the cold-start child
    # once THIS process has exited (and released the chip), then the
    # regression guard, then prints.
    with open(_STATE_PATH, "w") as fp:
        json.dump({"line": line}, fp)


def _finish(line: dict) -> int:
    """Guard, record and print the finished line; returns the exit code
    (3 = the regression guard's verdict)."""
    platform = line["platform"]
    regressions = _regression_guard(line, platform)
    if GUARD_SKIPS:
        line["guard_skips"] = list(GUARD_SKIPS)
    if regressions:
        # keep the PREVIOUS record as the baseline (recording the bad
        # run would mask the regression on the next comparison), emit
        # the line with the failures spelled out, and exit nonzero
        line["regressions"] = regressions
        for r in regressions:
            log(f"REGRESSION: {r}")
        print(json.dumps(line), flush=True)
        return 3
    if platform != "cpu":
        _record_tpu_result(line)
    print(json.dumps(line), flush=True)
    return 0


# -- merkle: device-batched SHA-256 engine vs host hashlib -----------------
#
# The commit/propose loop's non-signature hot path: tx roots, part-set
# roots, validator-set hashes (crypto/merkle.py). Measures the device
# engine (models/hasher.py) against the iterative host path over a
# MERKLE_N-leaf tree, plus a PartSet.from_data block-split case (root +
# every part proof in one batched pass). merkle_root_speedup joins the
# regression guard next to replay_speedup.

MERKLE_N = int(os.environ.get("TM_BENCH_MERKLE_N", "10000"))


def merkle_bench() -> dict:
    """Returns the merkle_* bench keys; never raises (the main line
    must survive a broken engine — the guard then flags the missing
    key against the previous record)."""
    try:
        import numpy as np

        from tendermint_tpu.crypto import merkle

        rng = np.random.RandomState(99)
        # 45-byte leaves: validator hash_bytes / commit-sig scale, one
        # message block per leaf
        items = [rng.bytes(45) for _ in range(MERKLE_N)]

        merkle.configure_device(False)
        t0 = time.perf_counter()
        for _ in range(3):
            root_host = merkle.hash_from_byte_slices(items)
        host_s = (time.perf_counter() - t0) / 3

        merkle.configure_device(True, threshold=2, block_on_compile=True)
        t0 = time.perf_counter()
        root_dev = merkle.hash_from_byte_slices(items)
        cold_s = time.perf_counter() - t0
        assert root_dev == root_host, "device root != host root"
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            root_dev = merkle.hash_from_byte_slices(items)
            times.append(time.perf_counter() - t0)
        dev_s = sorted(times)[len(times) // 2]
        assert root_dev == root_host
        # negative control: one flipped leaf byte must change the root
        tampered = list(items)
        tampered[7] = bytes([items[7][0] ^ 1]) + items[7][1:]
        assert merkle.hash_from_byte_slices(tampered) != root_host

        # PartSet.from_data: block split into small parts so the part
        # count clears the device threshold (root + every part proof)
        from tendermint_tpu.types.part_set import PartSet

        data = rng.bytes(512 * 1024)
        merkle.configure_device(False)
        t0 = time.perf_counter()
        ps_host = PartSet.from_data(data, part_size=256)
        ps_host_s = time.perf_counter() - t0
        merkle.configure_device(True, threshold=2, block_on_compile=True)
        ps_dev = PartSet.from_data(data, part_size=256)  # compile pass
        t0 = time.perf_counter()
        ps_dev = PartSet.from_data(data, part_size=256)
        ps_dev_s = time.perf_counter() - t0
        assert ps_dev.header() == ps_host.header(), "part-set root mismatch"
        p = ps_dev.get_part(3)
        assert ps_host.get_part(3).proof.aunts == p.proof.aunts

        out = {
            "merkle_n_leaves": MERKLE_N,
            "merkle_host_ms": round(host_s * 1e3, 2),
            "merkle_device_ms": round(dev_s * 1e3, 2),
            "merkle_cold_compile_s": round(cold_s, 1),
            "merkle_root_speedup": round(host_s / dev_s, 2),
            "merkle_partset_host_ms": round(ps_host_s * 1e3, 2),
            "merkle_partset_device_ms": round(ps_dev_s * 1e3, 2),
        }
        log(
            f"merkle root@{MERKLE_N}: host {host_s*1e3:.1f} ms, device "
            f"{dev_s*1e3:.1f} ms ({out['merkle_root_speedup']}x; cold {cold_s:.1f}s); "
            f"partset 2048x256B: host {ps_host_s*1e3:.1f} ms, device {ps_dev_s*1e3:.1f} ms"
        )
        return out
    except Exception as ex:
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"merkle measurement failed: {ex!r}")
        return {"merkle_error": repr(ex)[:200]}
    finally:
        # leave the engine off for the rest of the bench process
        try:
            from tendermint_tpu.crypto import merkle as _m

            _m.configure_device(False)
        except Exception:
            pass


# -- mesh runtime: weak scaling across the local device inventory ----------
#
# The ISSUE-16 headline: VerifyCommit sharded over 1/2/4/8-device
# meshes from the local inventory (virtual on CPU via
# XLA_FLAGS=--xla_force_host_platform_device_count=8 works too). Every
# size must produce bit-identical verdicts; the throughput keys feed
# the regression guard like any other section. A single-device or
# no-accelerator run SKIPS the sweep LOUDLY and still runs the
# chunked-seam parity drill — mesh_platform provenance keeps a TPU
# baseline from ever being judged against a CPU run.

MESH_BENCH_N = int(
    os.environ.get("TM_BENCH_MESH_N", "0")
)  # 0 = pick by platform below
MESH_SIZES = (1, 2, 4, 8)  # sweep points, capped by the local inventory


def mesh_bench(device: bool = True) -> dict:
    """Returns the mesh_* bench keys; never raises (the main line must
    survive a broken mesh runtime — the guard then flags the missing
    keys against the previous record)."""
    out: dict = {}
    try:
        import numpy as np

        from tendermint_tpu.crypto.batch import (
            CPUBatchVerifier,
            MeshRoutedVerifier,
        )
        from tendermint_tpu.parallel import DeviceTopology, MeshRouter

        # chunked-seam parity drill: runs on EVERY backend (logical
        # lanes, no XLA) so even a CPU-fallback bench still proves the
        # router's split/concat seam cannot flip a verdict
        n_par = 512
        pks, msgs, sigs = make_batch(n_par)
        sigs = sigs.copy()
        sigs[5, 0] ^= 1
        sigs[443, 9] ^= 0x40
        want = CPUBatchVerifier().verify_batch(pks, msgs, sigs)
        router = MeshRouter(DeviceTopology.logical(4), min_rows=4)
        got = MeshRoutedVerifier(CPUBatchVerifier(), router).verify_batch(
            pks, msgs, sigs
        )
        assert (got == want).all(), "mesh chunked-seam parity diverged"
        assert router.stats()["collective_bundles"] == 1
        assert not want[5] and not want[443] and int(want.sum()) == n_par - 2
        out["mesh_parity_ok"] = 1

        import jax

        devs = jax.devices()
        if not device and os.environ.get("TM_BENCH_FORCE_DEVICE") != "1":
            out["mesh_skipped"] = (
                "no accelerator: weak-scaling sweep needs the device path"
            )
            log(f"MESH SKIP: {out['mesh_skipped']}")
            return out
        if len(devs) < 2:
            out["mesh_skipped"] = (
                f"single {devs[0].platform} device: no mesh to scale across "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                "for a virtual sweep)"
            )
            log(f"MESH SKIP: {out['mesh_skipped']}")
            return out

        from tendermint_tpu.models.verifier import VerifierModel
        from tendermint_tpu.parallel import make_mesh

        # CPU XLA grinds for minutes at 10k rows (see the fallback note
        # in run_bench); the virtual-device sweep drops to 2048 unless
        # TM_BENCH_MESH_N pins a size
        n = MESH_BENCH_N or (
            BENCH_N if devs[0].platform != "cpu" else 2048
        )
        pks, msgs, sigs = make_batch(n)
        powers = np.full(n, 10, dtype=np.int64)
        counted = np.ones(n, dtype=bool)
        sizes = [d for d in MESH_SIZES if d <= len(devs)]
        base_rate = rate = None
        ok_ref = tally_ref = None
        for d in sizes:
            model = VerifierModel(
                mesh=make_mesh(devs[:d]) if d > 1 else None,
                block_on_compile=True,
            )
            # compile + warm
            ok, tally = _verify_then_tally(model, pks, msgs, sigs, powers, counted)
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                ok, tally = _verify_then_tally(model, pks, msgs, sigs, powers, counted)
                times.append(time.perf_counter() - t0)
            p50 = sorted(times)[len(times) // 2]
            ok = np.asarray(ok)
            if ok_ref is None:
                ok_ref, tally_ref = ok.copy(), int(tally)
                assert ok_ref.all() and tally_ref == n * 10
            else:
                assert (ok == ok_ref).all() and int(tally) == tally_ref, (
                    f"mesh@{d}dev: verdicts diverged from single-device"
                )
            rate = n / p50
            if d == 1:
                base_rate = rate
            out[f"mesh_p50_ms_{d}dev"] = round(p50 * 1e3, 3)
            log(
                f"mesh weak-scaling {d} dev @ {n} rows: {p50*1e3:.1f} ms/commit "
                f"({rate:,.0f} rows/s)"
            )
        out["mesh_devices_measured"] = sizes[-1]
        out["mesh_rows"] = n
        out["mesh_sigs_per_sec"] = round(rate)
        out["mesh_speedup"] = round(rate / base_rate, 2)
        log(
            f"mesh weak scaling 1 -> {sizes[-1]} devices: "
            f"{out['mesh_speedup']}x ({out['mesh_sigs_per_sec']:,} rows/s)"
        )
        return out
    except Exception as ex:
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"mesh measurement failed: {ex!r}")
        out["mesh_error"] = repr(ex)[:200]
        return out


# -- degraded mode: circuit-broken device path + idle watchdog cost --------
#
# The robustness layer's two numbers (docs/robustness.md): (1) what a
# circuit-breaker trip actually costs — the same verify/hash workload
# with the device path OPEN (host fallback) vs healthy, which is the
# degradation a node rides while a breaker cools down; (2) what the
# watchdog costs when nothing is wrong — supervising thread + probes +
# future deadlines must stay under a 1% overhead budget on a hot
# workload, or nobody would leave it on in production.

DEGRADED_N = int(os.environ.get("TM_BENCH_DEGRADED_N", "10000"))
WATCHDOG_BENCH_ITERS = int(os.environ.get("TM_BENCH_WATCHDOG_ITERS", "40"))


def degraded_mode_bench() -> dict:
    """Returns the degraded_* bench keys; never raises (the main line
    must survive a broken robustness layer)."""
    try:
        import numpy as np

        from tendermint_tpu.crypto import merkle
        from tendermint_tpu.utils.watchdog import Watchdog

        rng = np.random.RandomState(7)
        items = [rng.bytes(45) for _ in range(DEGRADED_N)]

        # healthy: device merkle engine serves the tree
        merkle.configure_device(True, threshold=2, block_on_compile=True)
        root_dev = merkle.hash_from_byte_slices(items)  # compile pass
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            root_dev = merkle.hash_from_byte_slices(items)
            times.append(time.perf_counter() - t0)
        healthy_s = sorted(times)[len(times) // 2]

        # circuit-broken: inject ONE device failure — trips the engine
        # breaker (threshold 1) and latches the bucket to host, the
        # exact state a real device fault leaves — then re-measure; the
        # root must stay bit-identical through the host fallback
        from tendermint_tpu.utils import faultinject as faults

        faults.arm("device.hash", "raise", times=1)
        merkle.hash_from_byte_slices(items)  # the tripping call
        faults.disarm()
        h = merkle._device_hasher()
        assert h.compile_breaker.state() == "open", "breaker must be tripped"
        dev_roots_before = merkle.device_stats()["device_roots"]
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            root_host = merkle.hash_from_byte_slices(items)
            times.append(time.perf_counter() - t0)
        degraded_s = sorted(times)[len(times) // 2]
        assert root_host == root_dev, "degraded root must be bit-identical"
        assert merkle.device_stats()["device_roots"] == dev_roots_before, (
            "breaker open: no call may reach the device"
        )
        merkle.configure_device(False)

        # idle watchdog overhead: interleaved min-of-6 arms over the
        # host merkle root (same methodology as trace_overhead_bench),
        # with a REALISTIC supervision load registered: 2 workers, a
        # progress probe, a heartbeat and a steady trickle of watched
        # futures that resolve in time.
        from concurrent.futures import Future

        merkle.configure_device(False)

        def workload():
            acc = 0
            for _ in range(WATCHDOG_BENCH_ITERS):
                acc ^= merkle.hash_from_byte_slices(items[:768])[0]
            return acc

        workload()  # warm caches

        def arm_off():
            return _bench_time(workload)

        wd = Watchdog(interval_s=0.05)
        t = __import__("threading").current_thread()
        wd.register_worker("bench.self", t.is_alive, lambda: None)
        wd.register_worker("bench.self2", t.is_alive, lambda: None)
        wd.register_progress("bench.prog", time.monotonic, stall_after_s=60)
        wd.register_heartbeat("bench.beat", stall_after_s=60)

        def arm_on():
            f = Future()
            wd.watch_future(f, 30.0, name="bench")
            out = _bench_time(workload)
            f.set_result(None)
            return out

        # primary instrument: amortized cost of one tick with the full
        # supervision load registered, reported as the duty cycle at
        # the PRODUCTION interval (config default watchdog_interval_ms)
        # — that IS the steady-state overhead of a periodic daemon: it
        # burns tick_cost once per interval on one core. Deterministic
        # to sub-ppm, which a <1% budget needs; a differential A/B over
        # a ~50 ms workload cannot resolve it on a small shared VM
        # (scheduler noise there measures +-10% either sign).
        f = Future()
        wd.watch_future(f, 3600.0, name="bench.tick")
        n_ticks = 10_000
        t0 = time.perf_counter()
        for _ in range(n_ticks):
            wd.check_once()
        tick_s = (time.perf_counter() - t0) / n_ticks
        f.set_result(None)

        from tendermint_tpu.config.config import BaseConfig

        interval_s = BaseConfig().watchdog_interval_ms / 1000.0
        overhead_pct = tick_s / interval_s * 100.0

        # secondary observable: interleaved wall-time A/B with the
        # thread running at a 20x-production interval (0.05 s). Noisy on
        # shared hardware — recorded for the record, not the budget.
        on, off = [], []
        for _ in range(6):
            wd.start()
            on.append(arm_on())
            wd.stop()
            off.append(arm_off())
        wd_on, wd_off = min(on), min(off)
        ab_pct = (wd_on - wd_off) / wd_off * 100.0

        out = {
            "degraded_n_leaves": DEGRADED_N,
            "degraded_healthy_ms": round(healthy_s * 1e3, 2),
            "degraded_broken_ms": round(degraded_s * 1e3, 2),
            "degraded_slowdown": (
                round(degraded_s / healthy_s, 2) if healthy_s > 0 else None
            ),
            "watchdog_tick_us": round(tick_s * 1e6, 2),
            "watchdog_interval_ms": round(interval_s * 1e3),
            "watchdog_overhead_pct": round(overhead_pct, 4),
            "watchdog_overhead_ok": overhead_pct < 1.0,
            "watchdog_ab_on_ms": round(wd_on * 1e3, 2),
            "watchdog_ab_off_ms": round(wd_off * 1e3, 2),
            "watchdog_ab_pct": round(ab_pct, 2),
        }
        log(
            f"degraded mode @{DEGRADED_N} leaves: healthy {healthy_s*1e3:.1f} ms, "
            f"circuit-broken {degraded_s*1e3:.1f} ms "
            f"({out['degraded_slowdown']}x slowdown); idle watchdog tick "
            f"{tick_s*1e6:.1f} us @ {interval_s*1e3:.0f} ms interval -> "
            f"{overhead_pct:.4f}% duty (<1% budget: {out['watchdog_overhead_ok']}; "
            f"A/B arms {ab_pct:+.2f}%)"
        )
        return out
    except Exception as ex:
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"degraded-mode measurement failed: {ex!r}")
        return {"degraded_error": repr(ex)[:200]}
    finally:
        try:
            from tendermint_tpu.crypto import merkle as _m

            _m.configure_device(False)
        except Exception:
            pass


def _bench_time(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# -- flight recorder: tracing overhead + per-stage latency breakdown -------
#
# The observability contract (docs/tracing.md): span tracing must cost
# <3% on an instrumented hot path when ENABLED, and ~nothing when
# disabled. Measured on the host merkle root (an instrumented real
# consensus stage: ~1 span per call through crypto/merkle.py) plus the
# pipelined verify dispatch (pipeline.prep/execute/resolve spans per
# bundle). The per-stage aggregate from the enabled run is the
# latency-attribution breakdown the BENCH json carries.

TRACE_BENCH_LEAVES = int(os.environ.get("TM_BENCH_TRACE_LEAVES", "768"))
TRACE_BENCH_ITERS = int(os.environ.get("TM_BENCH_TRACE_ITERS", "40"))


def trace_overhead_bench() -> dict:
    """Returns the trace_* bench keys; never raises (the main line must
    survive a broken tracer)."""
    from tendermint_tpu.utils import trace as _tr

    prev_tracer = _tr.get_tracer()
    try:
        import numpy as np

        from tendermint_tpu.crypto import merkle
        from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
        from tendermint_tpu.crypto.batch import CPUBatchVerifier

        rng = np.random.RandomState(7)
        items = [rng.bytes(45) for _ in range(TRACE_BENCH_LEAVES)]
        merkle.configure_device(False)

        # explicit tracer object (set_tracer bypasses the TM_TRACE env
        # override on purpose: the bench must control both arms).
        tracer = _tr.set_tracer(_tr.Tracer(enabled=True, buffer_events=1 << 16))

        def iteration(i: int) -> None:
            # one instrumented-workload iteration: a host merkle root
            # plus the cross-node propagation pair (origin = span-id
            # alloc + flow-start, link = receiver-side flow-end), so
            # the <3% budget covers tracing WITH propagation enabled —
            # disabled, origin() is one flag check returning None and
            # link(None) returns immediately
            merkle.hash_from_byte_slices(items)
            ctx = tracer.origin(height=i)
            tracer.link(ctx, "consensus.proposal_link", height=i)

        def arm_ms(iters: int) -> float:
            t0 = time.perf_counter()
            for i in range(iters):
                iteration(i)
            return (time.perf_counter() - t0) * 1e3

        for i in range(3):
            iteration(i)  # warm

        # The budget check is an ATTRIBUTED ratio, not a differential
        # A/B: on a shared host, back-to-back ~100ms blocks differ by
        # 3-10x the true ~25us/iteration instrumentation cost (the
        # measured sign even flips run to run), so a subtraction of two
        # noisy walls can never hold a 3% threshold. The primitive
        # costs ARE stable under a tight loop, and the recorder counts
        # its own events exactly, so:
        #     overhead = events-cost per iteration / iteration wall
        # with the iteration wall taken from the uninstrumented arm's
        # min (the only place min-of-N is still needed).
        def _events() -> int:
            return tracer.stats()["events_recorded"]

        def _tight(fn, k: int):
            # min over blocks: the first block absorbs cold-path costs
            # (lazy inits, ring growth, branch warmup) that a single
            # pass would bill to the steady-state per-call cost
            block = max(k // 4, 1)
            e0 = _events()
            best = None
            for _ in range(4):
                t0 = time.perf_counter()
                for i in range(block):
                    fn(i)
                dt = (time.perf_counter() - t0) / block
                best = dt if best is None or dt < best else best
            return best, (_events() - e0) / (block * 4)

        probes = max(TRACE_BENCH_ITERS * 25, 500)

        # span probe: a complete enter/exit pair per call
        def _span_probe(i):
            with tracer.span("bench.overhead_probe", height=i):
                pass

        span_cost, span_ev = _tight(_span_probe, probes)
        ctx_holder = {}

        def _origin_probe(i):
            ctx_holder["ctx"] = tracer.origin(height=i)

        origin_cost, origin_ev = _tight(_origin_probe, probes)

        def _link_probe(i):
            tracer.link(ctx_holder["ctx"], "consensus.proposal_link", height=i)

        link_cost, link_ev = _tight(_link_probe, probes)

        # flight recorder (consensus/flightrec.py): the ALWAYS-ON
        # consensus black box cannot hide behind a trace_enabled flag,
        # so its cost is attributed with the same tight-loop
        # methodology — per-record() cost (one lock + one deque append
        # of a 5-tuple, the vote.in shape, the hottest hook) billed at
        # a generous per-iteration event density and held to a < 1%
        # budget (docs/observability.md).
        from tendermint_tpu.consensus.flightrec import FlightRecorder

        frec = FlightRecorder(capacity=4096, node_id="bench")

        def _rec_tight(k: int) -> float:
            block = max(k // 4, 1)
            best = None
            for _ in range(4):
                t0 = time.perf_counter()
                for i in range(block):
                    frec.record("vote.in", i, 0, (1, i & 7, "bench-peer"))
                dt = (time.perf_counter() - t0) / block
                best = dt if best is None or dt < best else best
            return best

        frec_cost = _rec_tight(probes)
        # recorder events billed per workload iteration. The iteration
        # (one host merkle root) models ONE hashing slice of a height,
        # not the whole height, so the density billed against it is the
        # busiest comparable slice — a vote burst: ~8 vote.in + its
        # step enter/exits + vote.out + proposal/part arrivals. (A full
        # height is ~24 events spread across many such slices plus
        # timeouts/fsync; billing all of them against one slice would
        # overstate the per-work cost ~20x.)
        frec_events_per_iter = 12.0

        # exact instrumentation density of the workload iteration
        e0 = _events()
        on_ms = arm_ms(TRACE_BENCH_ITERS)
        events_per_iter = (_events() - e0) / TRACE_BENCH_ITERS

        # uninstrumented iteration wall (min over short blocks)
        tracer.enabled = False
        off_blocks = []
        block = max(TRACE_BENCH_ITERS // 4, 1)
        for _ in range(8):
            off_blocks.append(arm_ms(block) / block)
        tracer.enabled = True
        off_iter_ms = min(off_blocks)
        off_ms = off_iter_ms * TRACE_BENCH_ITERS

        # origin/link are costed per CALL; the remaining events are
        # workload spans, costed per span-probe EVENT
        span_events = max(events_per_iter - origin_ev - link_ev, 0.0)
        per_span_event = span_cost / span_ev if span_ev else span_cost
        instr_ms_per_iter = (
            origin_cost + link_cost + per_span_event * span_events
        ) * 1e3
        overhead_pct = (
            instr_ms_per_iter / off_iter_ms * 100 if off_iter_ms > 0 else None
        )
        frec_ms_per_iter = frec_cost * frec_events_per_iter * 1e3
        frec_pct = (
            frec_ms_per_iter / off_iter_ms * 100 if off_iter_ms > 0 else None
        )

        # drive the instrumented pipeline so the breakdown includes the
        # bundle lifecycle stages, not just merkle routing
        pk, mg, sg = make_batch(256, seed=777)
        with PipelinedVerifier(CPUBatchVerifier(), cache=SigCache()) as pv:
            futs = [pv.submit_batch(pk, mg, sg, dedupe=True) for _ in range(4)]
            for f in futs:
                assert f.result().all()

        breakdown = tracer.timeline()["stages"]
        out = {
            # informational differential reading (single pass per arm;
            # noisy on shared hosts — the budget uses the attributed
            # ratio below)
            "trace_disabled_ms": round(off_ms, 2),
            "trace_enabled_ms": round(on_ms, 2),
            "trace_events_per_iter": round(events_per_iter, 2),
            "trace_cost_us": {
                "span_event": round(per_span_event * 1e6, 3),
                "origin_call": round(origin_cost * 1e6, 3),
                "link_call": round(link_cost * 1e6, 3),
            },
            "trace_overhead_pct": round(overhead_pct, 2)
            if overhead_pct is not None
            else None,
            "trace_overhead_ok": bool(
                overhead_pct is not None and overhead_pct < 3.0
            ),
            "trace_events_recorded": tracer.stats()["events_recorded"],
            "trace_stage_breakdown": breakdown,
            "flightrec_cost_us": round(frec_cost * 1e6, 3),
            "flightrec_events_per_iter": frec_events_per_iter,
            "flightrec_overhead_pct": round(frec_pct, 3)
            if frec_pct is not None
            else None,
            "flightrec_overhead_ok": bool(
                frec_pct is not None and frec_pct < 1.0
            ),
        }
        log(
            f"trace overhead: {instr_ms_per_iter*1e3:.1f} us attributed per "
            f"{off_iter_ms:.2f} ms iteration = {out['trace_overhead_pct']}% "
            f"({events_per_iter:.1f} events/iter; span "
            f"{per_span_event*1e6:.1f} us, origin {origin_cost*1e6:.1f} us, "
            f"link {link_cost*1e6:.1f} us; "
            f"{len(breakdown)} stages in breakdown)"
        )
        log(
            f"flight recorder: {frec_cost*1e6:.2f} us/record x "
            f"{frec_events_per_iter:.0f} events/iter = "
            f"{out['flightrec_overhead_pct']}% of the "
            f"{off_iter_ms:.2f} ms iteration"
        )
        if not out["trace_overhead_ok"]:
            log("WARNING: tracing overhead exceeds the 3% budget")
        if not out["flightrec_overhead_ok"]:
            log("WARNING: flight-recorder overhead exceeds the 1% budget")
        return out
    except Exception as ex:
        log(f"trace overhead measurement failed: {ex!r}")
        return {"trace_error": repr(ex)[:200]}
    finally:
        _tr.set_tracer(prev_tracer)


# -- fast-sync replay: pipelined dispatch vs synchronous per-commit --------
#
# The reactor-shaped measurement for the verification dispatch layer
# (crypto/pipeline.py): a multi-height chain of commits, each delivered
# REPLAY_DUP times (gossip redundancy: multiple peers serve the same
# commit), verified (a) synchronously — one blocking provider call per
# delivery, the serial v0 reactor shape — and (b) through the
# PipelinedVerifier — all deliveries in flight, micro-batched into
# device-sized bundles, redeliveries collapsed by the dedupe cache.
# Emits the pipeline/cache counters alongside the throughput keys.

REPLAY_HEIGHTS = int(os.environ.get("TM_BENCH_REPLAY_HEIGHTS", "6"))
REPLAY_VALS = int(os.environ.get("TM_BENCH_REPLAY_VALS", str(min(BENCH_N, 256))))
REPLAY_DUP = int(os.environ.get("TM_BENCH_REPLAY_DUP", "3"))


def replay_bench(inner) -> dict:
    """Replay REPLAY_HEIGHTS commits x REPLAY_DUP deliveries through
    `inner` twice (sync vs pipelined); returns the bench keys, or an
    error key — never raises (the main line must survive)."""
    try:
        import numpy as np

        from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache

        chain = [
            make_batch(REPLAY_VALS, seed=4321 + h) for h in range(REPLAY_HEIGHTS)
        ]
        deliveries = [b for b in chain for _ in range(REPLAY_DUP)]

        # synchronous: verify each delivery with one blocking call
        t0 = time.perf_counter()
        for pk, mg, sg in deliveries:
            ok = inner.verify_batch(pk, mg, sg)
            assert ok.all()
        sync_s = time.perf_counter() - t0

        # pipelined: everything in flight, dedupe collapses redelivery
        # (context manager: the dispatch/exec threads must not outlive
        # this section even when an assert fires)
        with PipelinedVerifier(inner, cache=SigCache()) as pv:
            t0 = time.perf_counter()
            futs = [
                pv.submit_batch(pk, mg, sg, dedupe=True)
                for pk, mg, sg in deliveries
            ]
            for f in futs:
                assert f.result().all()
            pipe_s = time.perf_counter() - t0
            stats = pv.stats()

        rows = REPLAY_HEIGHTS * REPLAY_VALS * REPLAY_DUP
        out = {
            "replay_heights": REPLAY_HEIGHTS,
            "replay_validators": REPLAY_VALS,
            "replay_dup_factor": REPLAY_DUP,
            "replay_sync_ms": round(sync_s * 1e3, 2),
            "replay_pipelined_ms": round(pipe_s * 1e3, 2),
            "replay_speedup": round(sync_s / pipe_s, 2) if pipe_s > 0 else None,
            "replay_sync_sigs_per_sec": round(rows / sync_s) if sync_s > 0 else None,
            "replay_pipelined_sigs_per_sec": (
                round(rows / pipe_s) if pipe_s > 0 else None
            ),
            "pipeline_bundles": stats["dispatched_bundles"],
            "pipeline_rows": stats["submitted_rows"],
            "pipeline_device_rows": stats["device_rows"],
            "pipeline_batch_occupancy_avg": round(stats["batch_occupancy_avg"], 2),
            "pipeline_max_queue_depth": stats["max_queue_depth"],
            "dedupe_cache_hits": stats["cache_hits"],
            "dedupe_cache_misses": stats["cache_misses"],
            "dedupe_bundle_dup_rows": stats["bundle_dup_rows"],
        }
        log(
            f"fast-sync replay: sync {sync_s*1e3:.1f} ms, pipelined "
            f"{pipe_s*1e3:.1f} ms ({out['replay_speedup']}x; "
            f"{stats['cache_hits']} cache hits + "
            f"{stats['bundle_dup_rows']} in-bundle dups collapsed, "
            f"{stats['device_rows']}/{stats['submitted_rows']} rows to device)"
        )
        return out
    except Exception as ex:
        log(f"replay measurement failed: {ex!r}")
        return {"replay_error": repr(ex)[:200]}


# -- lightserve: batched light-client fleet vs per-client serial -----------
#
# The verify-server measurement (lightserve/, docs/light-service.md):
# N synthetic clients each request a verified header near the tip of a
# K-height chain. The SERIAL arm runs every client's skip-verification
# independently (direct light/verifier.py calls — the naive proxy
# baseline); the BATCHED arm funnels all clients through one
# LightServeService (shared verified-header store + single-flight
# bisection + aggregator bundles through the provider). The headline is
# clients served per second; lightserve_speedup joins the regression
# guard next to replay_speedup.

LIGHTSERVE_CLIENTS = int(os.environ.get("TM_BENCH_LIGHTSERVE_CLIENTS", "64"))
LIGHTSERVE_HEIGHTS = int(os.environ.get("TM_BENCH_LIGHTSERVE_HEIGHTS", "16"))
LIGHTSERVE_VALS = int(os.environ.get("TM_BENCH_LIGHTSERVE_VALS", "8"))
LIGHTSERVE_TARGETS = int(os.environ.get("TM_BENCH_LIGHTSERVE_TARGETS", "4"))


def lightserve_bench(provider=None) -> dict:
    """Returns the lightserve_* bench keys; never raises (the main line
    must survive a broken service — the guard then flags the missing
    keys against the previous record)."""
    try:
        from tendermint_tpu.db.memdb import MemDB
        from tendermint_tpu.light.store import TrustedStore
        from tendermint_tpu.lightserve import loadgen
        from tendermint_tpu.lightserve.aggregator import RequestAggregator
        from tendermint_tpu.lightserve.service import LightServeService

        n_heights = max(2, LIGHTSERVE_HEIGHTS)
        headers, valsets = loadgen.make_chain(
            n_heights, base_keys=loadgen.keys(LIGHTSERVE_VALS)
        )
        now = loadgen.T0 + 600 * 10**9
        period = 30 * 24 * 3600 * 10**9
        # the fleet chases the tip: targets round-robin the newest
        # LIGHTSERVE_TARGETS heights (the overlap a real swarm has)
        n_targets = max(1, min(LIGHTSERVE_TARGETS, n_heights - 1))
        tips = list(range(n_heights - n_targets + 1, n_heights + 1))
        targets = [tips[i % n_targets] for i in range(LIGHTSERVE_CLIENTS)]

        serial_res, serial_s = loadgen.serial_fleet(
            headers, valsets, targets, period, now, provider=provider
        )

        agg = RequestAggregator(provider=provider, flush_s=0.002)
        svc = LightServeService(
            loadgen.CHAIN_ID,
            loadgen.ChainSource(headers, valsets),
            TrustedStore(MemDB()),
            aggregator=agg,
            trusting_period_ns=period,
        )
        try:
            batched_res, batched_s = loadgen.run_fleet(
                svc, targets, now, threads=16
            )
            stats = svc.stats()
        finally:
            svc.stop()
            agg.stop()
        assert batched_res == serial_res, "batched fleet verdicts != serial"

        out = {
            "lightserve_clients": LIGHTSERVE_CLIENTS,
            "lightserve_chain_heights": n_heights,
            "lightserve_validators": LIGHTSERVE_VALS,
            "lightserve_serial_ms": round(serial_s * 1e3, 2),
            "lightserve_batched_ms": round(batched_s * 1e3, 2),
            "lightserve_clients_per_sec": (
                round(LIGHTSERVE_CLIENTS / batched_s) if batched_s > 0 else None
            ),
            "lightserve_serial_clients_per_sec": (
                round(LIGHTSERVE_CLIENTS / serial_s) if serial_s > 0 else None
            ),
            "lightserve_speedup": (
                round(serial_s / batched_s, 2) if batched_s > 0 else None
            ),
            "lightserve_singleflight_hits": stats["singleflight_hits"],
            "lightserve_singleflight_runs": stats["singleflight_runs"],
            "lightserve_store_hits": stats["store_hits"],
            "lightserve_bundles": stats["bundles"],
            "lightserve_bundle_occupancy_avg": round(
                stats["bundle_occupancy_avg"], 2
            ),
        }
        log(
            f"lightserve fleet @{LIGHTSERVE_CLIENTS} clients: serial "
            f"{serial_s*1e3:.1f} ms, batched {batched_s*1e3:.1f} ms "
            f"({out['lightserve_speedup']}x; {out['lightserve_clients_per_sec']}"
            f" clients/s; {stats['singleflight_hits']} single-flight hits, "
            f"{stats['store_hits']} store hits, {stats['bundles']} bundles)"
        )
        return out
    except Exception as ex:
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"lightserve measurement failed: {ex!r}")
        return {"lightserve_error": repr(ex)[:200]}


# -- BLS aggregation: one signature per commit vs per-signature ------------
#
# The signature-aggregation A/B (crypto/bls.py, types/aggregate.py,
# docs/bls-aggregation.md; ROADMAP item 3 / arxiv 2302.00418), at >= 2
# validator-set sizes:
#
# - bytes per commit: an encoded per-sig Commit (one CommitSig per
#   validator) vs the encoded AggregatedCommit (one 96-byte signature +
#   V-bit bitmap). The ratio at the LARGEST size is the guarded
#   bls_commit_bytes_ratio — it grows ~linearly with V, so a regression
#   means the wire format fattened.
# - verify latency: per-signature BLS verification (one pairing check
#   per row — what a BLS valset costs WITHOUT aggregation; measured on
#   a row sample and scaled to V, the sample size is reported) vs ONE
#   aggregate check (pubkey sum + single pairing). The ratio at the
#   largest size is the guarded bls_verify_speedup — this is the
#   aggregation win itself, independent of which backend (device
#   kernels or the pure-Python oracle) runs the pairings, so the bench
#   pins use_device=False for run-to-run comparability on this box.
# - the ed25519 pipeline numbers for the same set sizes ride along
#   unguarded (bls_vs_ed25519_*): on a CPU-fallback box the pure-Python
#   pairing loses to OpenSSL ed25519 below ~200 validators — the
#   honest crossover the paper predicts; the BYTES win holds at every
#   size.

BLS_VALSETS = [
    int(x) for x in os.environ.get("TM_BENCH_BLS_VALS", "16,64").split(",")
]
BLS_PERSIG_SAMPLE = int(os.environ.get("TM_BENCH_BLS_SAMPLE", "6"))


def bls_bench() -> dict:
    """Returns the bls_* bench keys; never raises (the main line must
    survive a broken subsystem — the guard then flags the missing keys
    against the previous record)."""
    import time as _time

    try:
        from tendermint_tpu.crypto.bls import BLSBatchVerifier, BLSPrivKey
        from tendermint_tpu.ops import ref_bls12 as _ref
        from tendermint_tpu.types.aggregate import aggregate_commit_votes
        from tendermint_tpu.types.block import (
            BLOCK_ID_FLAG_COMMIT,
            BlockID,
            Commit,
            CommitSig,
            PartSetHeader,
        )
        from tendermint_tpu.types.validator import Validator
        from tendermint_tpu.types.validator_set import ValidatorSet

        chain = "bls-bench"
        bid = BlockID(hash=b"\x11" * 32, parts=PartSetHeader(1, b"\x22" * 32))
        out = {"bls_valsets": list(BLS_VALSETS)}
        provider = BLSBatchVerifier(use_device=False)
        # guard keys come from the LARGEST size regardless of the env
        # list's order (a non-ascending TM_BENCH_BLS_VALS must not
        # record a small-set ratio as the guard baseline)
        guard_size = max(BLS_VALSETS)
        ratio = speedup = None
        for v_count in BLS_VALSETS:
            privs = [
                BLSPrivKey.from_secret(b"bench-%d" % i) for i in range(v_count)
            ]
            for p in privs:
                p.register_possession()  # the aggregation admission gate
            vals = [
                Validator(pub_key=p.pub_key(), voting_power=10) for p in privs
            ]
            vs = ValidatorSet(vals)
            by_addr = {p.pub_key().address(): p for p in privs}

            # the canonical aggregate message + one sig per validator
            ts = 1_700_000_000 * 10**9
            from tendermint_tpu.types.aggregate import AggregatedCommit
            from tendermint_tpu.utils.bits import BitArray

            msg = AggregatedCommit(
                height=7, round=0, block_id=bid, timestamp_ns=ts,
                signers=BitArray(v_count), agg_sig=b"\x00" * 96,
            ).sign_bytes(chain)
            hm = _ref.hash_to_curve_g2(msg, _ref.DST_SIG)
            agg_sigs = []
            for val in vs.validators:
                sk = by_addr[val.address]._sk
                agg_sigs.append(_ref.g2_compress(_ref.g2_mul(sk, hm)))
            agg = aggregate_commit_votes(chain, 7, 0, bid, ts, v_count, agg_sigs)

            # per-sig commit bytes (every row carries its own 96 B sig)
            commit = Commit(
                height=7, round=0, block_id=bid,
                signatures=[
                    CommitSig(
                        block_id_flag=BLOCK_ID_FLAG_COMMIT,
                        validator_address=val.address,
                        timestamp_ns=ts,
                        signature=sig,
                    )
                    for val, sig in zip(vs.validators, agg_sigs)
                ],
            )
            persig_bytes = sum(len(cs.encode()) for cs in commit.signatures)
            agg_bytes = agg.wire_bytes()

            # verify latency: aggregate check vs per-row pairing sample
            t0 = _time.perf_counter()
            vs.verify_aggregated_commit(chain, bid, 7, agg, bls_provider=provider)
            agg_s = _time.perf_counter() - t0
            sample = min(BLS_PERSIG_SAMPLE, v_count)
            import numpy as _np

            pk_rows = _np.stack(
                [
                    _np.frombuffer(val.pub_key.bytes(), dtype=_np.uint8)
                    for val in vs.validators[:sample]
                ]
            )
            mg_rows = _np.broadcast_to(
                _np.frombuffer(msg, dtype=_np.uint8), (sample, len(msg))
            ).copy()
            sg_rows = _np.stack(
                [
                    _np.frombuffer(s, dtype=_np.uint8)
                    for s in agg_sigs[:sample]
                ]
            )
            t0 = _time.perf_counter()
            ok = provider.verify_batch(pk_rows, mg_rows, sg_rows)
            persig_sample_s = _time.perf_counter() - t0
            assert bool(ok.all()), "per-sig sample must verify"
            persig_s = persig_sample_s / sample * v_count

            out[f"bls_commit_bytes_persig_{v_count}"] = persig_bytes
            out[f"bls_commit_bytes_agg_{v_count}"] = agg_bytes
            out[f"bls_agg_verify_ms_{v_count}"] = round(agg_s * 1e3, 1)
            out[f"bls_persig_verify_ms_{v_count}"] = round(persig_s * 1e3, 1)
            size_ratio = round(persig_bytes / agg_bytes, 2)
            size_speedup = round(persig_s / agg_s, 2)
            if v_count == guard_size:
                ratio, speedup = size_ratio, size_speedup

            # the ed25519 pipeline at the same size (unguarded context)
            epk, emsgs, esigs = make_batch(v_count)
            from tendermint_tpu.crypto.batch import CPUBatchVerifier

            ecpu = CPUBatchVerifier()
            t0 = _time.perf_counter()
            eok = ecpu.verify_batch(epk[:v_count], emsgs[:v_count], esigs[:v_count])
            ed_s = _time.perf_counter() - t0
            assert bool(_np.asarray(eok).all())
            out[f"bls_vs_ed25519_verify_ms_{v_count}"] = round(ed_s * 1e3, 1)
            log(
                f"bls @{v_count} vals: bytes {persig_bytes} -> {agg_bytes} "
                f"({size_ratio}x), verify per-sig {persig_s*1e3:.0f} ms "
                f"(sample {sample}) vs aggregate {agg_s*1e3:.0f} ms "
                f"({size_speedup}x); ed25519 pipeline {ed_s*1e3:.1f} ms"
            )
        out["bls_persig_sample"] = BLS_PERSIG_SAMPLE
        out["bls_commit_bytes_ratio"] = ratio
        out["bls_verify_speedup"] = speedup
        return out
    except Exception as ex:
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"bls measurement failed: {ex!r}")
        return {"bls_error": repr(ex)[:200]}


# -- ingest: batched mempool admission vs per-tx serial CheckTx ------------
#
# The admission-side measurement (ingest/, docs/ingest.md): a fleet of
# ed25519-signed payment txs enters the mempool. The SERIAL arm is the
# reference lifecycle — one Mempool.check_tx per tx (per-tx hash + the
# app's host signature verify), then INGEST_RECHECKS post-commit recheck
# rounds in which the app re-verifies every pending tx (what stock
# CheckTx traffic costs while a deep pool rides across heights). The
# BATCHED arm funnels the same fleet through the IngestBatcher — bundled
# tx-key hashing, ONE pipeline sig pre-verification per bundle, SigCache-
# backed app checks — so rechecks resolve from the cache and, on real
# accelerators, the initial verify runs device-batched. Admission
# verdicts must be bit-identical across arms (asserted here and in the
# tests/test_ingest.py property suite). ingest_speedup and the batched
# admission rate join the regression guard next to replay_speedup. The
# optional live-node end-to-end arm (``e2e=True``) reports
# ingest_e2e_txs_per_sec; the main line now runs the end-to-end
# measurement through exec_bench instead (e2e_txs_per_sec, guarded),
# where blocks also execute through the batched DeliverBatch lane.

INGEST_TXS = int(os.environ.get("TM_BENCH_INGEST_TXS", "192"))
INGEST_ACCOUNTS = int(os.environ.get("TM_BENCH_INGEST_ACCOUNTS", "16"))
INGEST_RECHECKS = int(os.environ.get("TM_BENCH_INGEST_RECHECKS", "6"))
INGEST_E2E_TXS = int(os.environ.get("TM_BENCH_INGEST_E2E_TXS", "96"))


def ingest_bench(provider=None, e2e: bool = True) -> dict:
    """Returns the ingest_* bench keys; never raises (the main line must
    survive a broken subsystem — the guard then flags the missing keys
    against the previous record)."""
    import asyncio

    try:
        from tendermint_tpu.abci.client.local import LocalClient
        from tendermint_tpu.abci.examples.payments import (
            PaymentsApplication,
            sig_rows,
        )
        from tendermint_tpu.config import MempoolConfig
        from tendermint_tpu.crypto.batch import CPUBatchVerifier
        from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
        from tendermint_tpu.ingest import IngestBatcher
        from tendermint_tpu.ingest import loadgen as igen
        from tendermint_tpu.ingest.hashing import TxKeyHasher

        inner = provider if provider is not None else CPUBatchVerifier()
        privs, balances = igen.accounts(INGEST_ACCOUNTS)
        txs = igen.make_transfers(privs, INGEST_TXS, amount=1, fee=2)

        async def make_pool(app):
            client = LocalClient(app)
            await client.start()
            return Mempool(MempoolConfig(), client)

        from tendermint_tpu.mempool import Mempool

        def count_host_verifies(app):
            """Counts the app's own host signature verifies (one per
            CheckTx the SigCache did not answer) in calls[0]."""
            calls, verify = [0], app._host_verify

            def counted(pub, msg, sig):
                calls[0] += 1
                return verify(pub, msg, sig)

            app._host_verify = counted
            return calls

        async def arms():
            # serial arm: cache-less app — every CheckTx (and every
            # recheck) pays a host signature verify, the reference cost
            app_s = PaymentsApplication(dict(balances), sig_cache=False)
            serial_verifies = count_host_verifies(app_s)
            serial_v, serial_s = await igen.serial_admit(
                await make_pool(app_s), txs, rechecks=INGEST_RECHECKS
            )
            # batched arm: fresh SigCache shared by pipeline and app
            cache = SigCache()
            app_b = PaymentsApplication(dict(balances), sig_cache=cache)
            batched_verifies = count_host_verifies(app_b)
            pv = PipelinedVerifier(inner, cache=cache)
            hasher = TxKeyHasher(block_on_compile=True)
            batcher = IngestBatcher(
                await make_pool(app_b),
                verifier=pv,
                sig_extractor=sig_rows,
                hasher=hasher,
                hash_threshold=64,
            )
            # warm the tx-key hash bucket outside the timed window (the
            # live node compiles it in the background at boot)
            hasher.keys_or_host(txs[: min(len(txs), 256)], 64)
            try:
                batched_v, batched_s = await igen.batched_admit(
                    batcher, txs, rechecks=INGEST_RECHECKS
                )
                stats = batcher.stats()
            finally:
                await batcher.stop()
                pv.stop()
            stats["sigcache_hits"] = cache.hits
            stats["serial_host_verifies"] = serial_verifies[0]
            stats["batched_host_verifies"] = batched_verifies[0]
            return serial_v, serial_s, batched_v, batched_s, stats

        serial_v, serial_s, batched_v, batched_s, stats = asyncio.run(arms())
        assert serial_v == batched_v, "batched admission verdicts != serial"

        out = {
            "ingest_txs": INGEST_TXS,
            "ingest_accounts": INGEST_ACCOUNTS,
            "ingest_recheck_heights": INGEST_RECHECKS,
            "ingest_serial_ms": round(serial_s * 1e3, 2),
            "ingest_batched_ms": round(batched_s * 1e3, 2),
            "ingest_txs_per_sec": (
                round(INGEST_TXS * (1 + INGEST_RECHECKS) / batched_s)
                if batched_s > 0
                else None
            ),
            "ingest_serial_txs_per_sec": (
                round(INGEST_TXS * (1 + INGEST_RECHECKS) / serial_s)
                if serial_s > 0
                else None
            ),
            "ingest_speedup": (
                round(serial_s / batched_s, 2) if batched_s > 0 else None
            ),
            "ingest_bundles": stats["bundles"],
            "ingest_bundle_occupancy_avg": round(stats["bundle_occupancy_avg"], 2),
            "ingest_sig_rows": stats["sig_rows"],
            "ingest_hash_device_rows": stats["hash_device_rows"],
            "ingest_hash_host_rows": stats["hash_host_rows"],
            # the mechanism behind ingest_speedup, counted: the app's
            # CheckTx verifies on the host in the serial arm and reads
            # the shared SigCache in the batched one
            "ingest_sigcache_hits": stats["sigcache_hits"],
            "ingest_serial_host_verifies": stats["serial_host_verifies"],
            "ingest_batched_host_verifies": stats["batched_host_verifies"],
        }
        log(
            f"ingest admission @{INGEST_TXS} txs x{1 + INGEST_RECHECKS} checks: "
            f"serial {serial_s*1e3:.1f} ms, batched {batched_s*1e3:.1f} ms "
            f"({out['ingest_speedup']}x; {out['ingest_txs_per_sec']} tx-checks/s; "
            f"{stats['bundles']} bundles, {stats['hash_device_rows']} device-hashed keys)"
        )
        if e2e:
            out.update(_ingest_e2e(inner))
        return out
    except Exception as ex:
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"ingest measurement failed: {ex!r}")
        return {"ingest_error": repr(ex)[:200]}


def _ingest_e2e(inner) -> dict:
    """End-to-end tx/s through a LIVE single-validator node running the
    payments app: txs enter through the IngestBatcher and the number
    reported is committed-and-applied transfers per second, admission
    through consensus. Uses the in-process consensus harness
    (tests/cs_harness.py — the same rig the chaos suite drives)."""
    import asyncio

    try:
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))
        from cs_harness import make_genesis, make_node

        from tendermint_tpu.abci.examples.payments import (
            PaymentsApplication,
            sig_rows,
        )
        from tendermint_tpu.crypto.batch import CPUBatchVerifier
        from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
        from tendermint_tpu.ingest import IngestBatcher
        from tendermint_tpu.ingest import loadgen as igen

        async def go():
            privs, balances = igen.accounts(INGEST_ACCOUNTS)
            txs = igen.make_transfers(privs, INGEST_E2E_TXS, amount=1, fee=1)
            cache = SigCache()
            app = PaymentsApplication(dict(balances), sig_cache=cache)
            genesis, vals = make_genesis(1)
            node = await make_node(genesis, vals[0], app=app)
            pv = PipelinedVerifier(
                inner if inner is not None else CPUBatchVerifier(), cache=cache
            )
            batcher = IngestBatcher(
                node.mempool, verifier=pv, sig_extractor=sig_rows,
                hash_threshold=1 << 30,
            )
            await node.cs.start()
            t0 = time.perf_counter()
            try:
                await asyncio.gather(
                    *(batcher.check_tx(tx) for tx in txs), return_exceptions=True
                )
                deadline = time.monotonic() + 60
                while app.tx_applied < len(txs) and time.monotonic() < deadline:
                    await asyncio.sleep(0.05)
                elapsed = time.perf_counter() - t0
            finally:
                await node.cs.stop()
                await batcher.stop()
                pv.stop()
            return app.tx_applied, elapsed, node.cs.state.last_block_height

        applied, elapsed, height = asyncio.run(go())
        if applied < INGEST_E2E_TXS:
            raise RuntimeError(
                f"only {applied}/{INGEST_E2E_TXS} txs applied in {elapsed:.1f}s"
            )
        out = {
            "ingest_e2e_txs": applied,
            "ingest_e2e_heights": height,
            "ingest_e2e_txs_per_sec": round(applied / elapsed, 1),
        }
        log(
            f"ingest e2e: {applied} transfers through {height} live heights "
            f"in {elapsed:.2f}s ({out['ingest_e2e_txs_per_sec']} tx/s committed)"
        )
        return out
    except Exception as ex:
        log(f"ingest e2e measurement failed: {ex!r}")
        return {"ingest_e2e_error": repr(ex)[:200]}


# -- execution: DeliverBatch lane vs serial per-tx DeliverTx ---------------
#
# The block-body half of the paper's admission-to-commit story.
# deliver_speedup compares the pre-batching block body (per-tx
# DeliverTx, one host ed25519 verify each) against the DeliverBatch
# lane exactly as a live node runs it: admission already verified every
# signature, so the batch resolves the block by SigCache hit, schedules
# speculatively (state/parallel_exec.py) and lands the surviving
# write-sets in one bulk scatter. The workload is the scheduler's
# design-center — pairwise-disjoint transfers, zero conflicts; the
# conflict/re-run tail is pinned by tests/test_parallel_exec.py, not
# timed here. e2e_txs_per_sec promotes the PR-7 end-to-end arm to a
# guarded key: committed-and-applied transfers per second through a
# LIVE single-validator node with the batch lane on (admission through
# consensus through DeliverBatch), target 1000+ tx/s.

EXEC_TXS = int(os.environ.get("TM_BENCH_EXEC_TXS", "256"))
EXEC_E2E_TXS = int(os.environ.get("TM_BENCH_EXEC_E2E_TXS", "1024"))
EXEC_E2E_ACCOUNTS = int(os.environ.get("TM_BENCH_EXEC_E2E_ACCOUNTS", "64"))


def exec_bench(provider=None, e2e: bool = True) -> dict:
    """Returns the exec_* / deliver_speedup / e2e_* bench keys; never
    raises (the main line must survive a broken subsystem — the guard
    then flags the missing keys against the previous record)."""
    try:
        import numpy as np  # noqa: F401  (payments batch lane needs it)

        from tendermint_tpu.abci import types as abci_t
        from tendermint_tpu.abci.examples.payments import (
            PaymentsApplication,
            make_transfer,
        )
        from tendermint_tpu.crypto.batch import CPUBatchVerifier
        from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
        from tendermint_tpu.ingest import loadgen as igen

        inner = provider if provider is not None else CPUBatchVerifier()
        # pairwise-disjoint block: EXEC_TXS distinct senders paying
        # EXEC_TXS distinct recipients, one tx each
        privs, balances = igen.accounts(2 * EXEC_TXS, tag="exec")
        pubs = [p.pub_key().bytes() for p in privs]
        txs = [
            make_transfer(privs[i], 0, pubs[EXEC_TXS + i], 1, fee=1)
            for i in range(EXEC_TXS)
        ]

        # serial arm: the pre-batching deliver loop, host verify per tx
        app_s = PaymentsApplication(dict(balances), sig_cache=False)
        t0 = time.perf_counter()
        serial_res = [app_s.deliver_tx(abci_t.RequestDeliverTx(tx)) for tx in txs]
        serial_s = time.perf_counter() - t0

        # admission-shaped warm pass on a SCRATCH app sharing the cache:
        # one device bundle verifies the block and backfills every
        # verified triple — the same cache state a live node's
        # IngestBatcher leaves behind (also compiles the device bucket
        # outside the timed window)
        cache = SigCache()
        pv = PipelinedVerifier(inner, cache=cache)
        warm_app = PaymentsApplication(dict(balances), sig_cache=cache)
        warm_app.batch_verifier = pv
        warm_res = warm_app.deliver_batch(abci_t.RequestDeliverBatch(txs))

        app_b = PaymentsApplication(dict(balances), sig_cache=cache)
        app_b.batch_verifier = pv
        t0 = time.perf_counter()
        res_b = app_b.deliver_batch(abci_t.RequestDeliverBatch(txs))
        batched_s = time.perf_counter() - t0
        pv.stop()

        assert [(r.code, r.log) for r in serial_res] == [
            (r.code, r.log) for r in res_b.results
        ], "DeliverBatch verdicts != serial DeliverTx"
        assert app_s.commit().data == app_b.commit().data, (
            "DeliverBatch app hash != serial"
        )

        out = {
            "exec_txs": EXEC_TXS,
            "exec_serial_deliver_ms": round(serial_s * 1e3, 2),
            "exec_batched_deliver_ms": round(batched_s * 1e3, 2),
            "deliver_speedup": (
                round(serial_s / batched_s, 2) if batched_s > 0 else None
            ),
            "exec_conflicts": res_b.conflicts,
            "exec_serial_reruns": res_b.serial_reruns,
            "exec_warm_lane": warm_res.lane,
            "exec_warm_device_rows": warm_res.device_rows,
            "exec_warm_host_rows": warm_res.host_rows,
        }
        log(
            f"exec deliver @{EXEC_TXS} txs: serial {serial_s*1e3:.1f} ms, "
            f"batched {batched_s*1e3:.2f} ms ({out['deliver_speedup']}x; "
            f"warm bundle lane={warm_res.lane}, "
            f"{warm_res.device_rows} device rows)"
        )
        if e2e:
            out.update(_exec_e2e(inner))
        return out
    except Exception as ex:
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"exec measurement failed: {ex!r}")
        return {"exec_error": repr(ex)[:200]}


def _exec_e2e(inner) -> dict:
    """End-to-end tx/s through a LIVE single-validator node with the
    DeliverBatch lane engaged: the whole flash-crowd is admitted through
    the IngestBatcher first (SigCache-warm — admission *rate* is
    ingest_txs_per_sec's job), then consensus starts and the clock runs
    until every transfer is committed and applied. The number is the
    block pipeline's drain rate over a pre-queued crowd: propose, batch-
    deliver, commit, repeat."""
    import asyncio

    try:
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))
        from cs_harness import make_genesis, make_node

        from tendermint_tpu.abci.examples.payments import (
            PaymentsApplication,
            sig_rows,
        )
        from tendermint_tpu.crypto.batch import CPUBatchVerifier
        from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
        from tendermint_tpu.ingest import IngestBatcher
        from tendermint_tpu.ingest import loadgen as igen

        async def go():
            privs, balances = igen.accounts(EXEC_E2E_ACCOUNTS)
            txs = igen.make_transfers(privs, EXEC_E2E_TXS, amount=1, fee=1)
            cache = SigCache()
            app = PaymentsApplication(dict(balances), sig_cache=cache)
            genesis, vals = make_genesis(1)
            node = await make_node(genesis, vals[0], app=app)
            pv = PipelinedVerifier(
                inner if inner is not None else CPUBatchVerifier(), cache=cache
            )
            # the harness builds the executor bare — wire the batch lane
            # the way node/node.py does for a production node
            app.batch_verifier = pv
            node.cs._block_exec.exec_parallel = True
            batcher = IngestBatcher(
                node.mempool, verifier=pv, sig_extractor=sig_rows,
                hash_threshold=1 << 30,
            )
            # queue the crowd BEFORE consensus starts — otherwise block
            # cadence races trickle admission and every block carries a
            # handful of txs (measuring admission latency, not the
            # pipeline's drain rate)
            await asyncio.gather(
                *(batcher.check_tx(tx) for tx in txs), return_exceptions=True
            )
            queued = node.mempool.size()
            await node.cs.start()
            t0 = time.perf_counter()
            try:
                # done = every tx applied AND its block committed (commit
                # drains the pool via Mempool.update)
                def _done():
                    return app.tx_applied >= len(txs) and node.mempool.size() == 0

                deadline = time.monotonic() + 60
                while not _done() and time.monotonic() < deadline:
                    await asyncio.sleep(0.02)
                elapsed = time.perf_counter() - t0
            finally:
                await node.cs.stop()
                await batcher.stop()
                pv.stop()
            if queued < len(txs):
                raise RuntimeError(
                    f"only {queued}/{len(txs)} txs admitted before start"
                )
            return (
                app.tx_applied,
                elapsed,
                node.cs.state.last_block_height,
                node.cs._block_exec.exec_stats(),
            )

        applied, elapsed, height, xst = asyncio.run(go())
        if applied < EXEC_E2E_TXS:
            raise RuntimeError(
                f"only {applied}/{EXEC_E2E_TXS} txs applied in {elapsed:.1f}s"
            )
        if xst["batches"] == 0:
            raise RuntimeError(
                "e2e run never took the DeliverBatch lane — the number "
                "would measure the serial path under the batched label"
            )
        out = {
            "e2e_txs": applied,
            "e2e_heights": height,
            "e2e_batches": xst["batches"],
            "e2e_serial_reruns": xst["serial_reruns"],
            "e2e_txs_per_sec": round(applied / elapsed, 1),
        }
        log(
            f"exec e2e: {applied} transfers through {height} live heights "
            f"in {elapsed:.2f}s ({out['e2e_txs_per_sec']} tx/s committed, "
            f"{xst['batches']} batches, {xst['serial_reruns']} re-runs)"
        )
        return out
    except Exception as ex:
        log(f"exec e2e measurement failed: {ex!r}")
        return {"e2e_error": repr(ex)[:200]}


# -- simulator: nodes x heights sweep on the deterministic net -------------
#
# The PR13 rig (docs/simulator.md): hundreds of real ConsensusState
# instances under simulated time, all verify traffic through ONE shared
# pipeline. The bench reports simulated-consensus throughput
# (sim-heights per WALL second — simulated time is free, host work is
# what's being measured) and the shared engine's bundled signature rate.
# `sim_heights_per_sec` rides the regression guard like replay_speedup.

SIM_SWEEP = [(16, 10), (64, 8), (128, 6)]  # (nodes, heights)
SIM_VALIDATORS = int(os.environ.get("TM_BENCH_SIM_VALS", "8"))
SIM_SCHEDULE = "link(*,*):delay:ms=10,jitter_ms=4"
# recovery drill: one TRUE crash (WAL-replay rebuild, sim/durability.py)
# of a validator; sim_recovery_s = simulated seconds from the kill to
# that node's first post-replay commit — the restart-latency number the
# durable-node track guards (lower is better)
SIM_RECOVERY = {
    # seed chosen so the kill lands MID-HEIGHT: the rebuilt node has a
    # real in-flight WAL tail to replay (replayed_msgs > 0), not just a
    # clean post-commit boundary
    "nodes": 8, "validators": 4, "heights": 10, "seed": 42,
    "schedule": (
        "link(*,*):delay:ms=10,jitter_ms=4;crash:node=1,at_h=3,restart_h=5"
    ),
    "crash_node": 1,
}


def sim_bench() -> dict:
    """Returns the sim_* bench keys; never raises (the main line must
    survive a broken simulator — the guard then flags the missing keys
    against the previous record)."""
    try:
        from tendermint_tpu.sim.core import Simulation

        out = {}
        best = 0.0
        sigs_rate = 0.0
        for n, h in SIM_SWEEP:
            sim = Simulation(
                n_nodes=n,
                validators=min(SIM_VALIDATORS, n),
                heights=h,
                schedule=SIM_SCHEDULE,
                seed=1234,
                record_events=False,
            )
            res = sim.run()
            tag = f"sim_{n}x{h}"
            if not res.completed:
                out[f"{tag}_error"] = f"run wedged at {min(res.heights.values())}"
                continue
            hps = h / res.wall_seconds
            best = max(best, hps)
            eng = res.engine
            # rows the shared engine verified, wherever they ran (the
            # simulator's inner provider is the host verifier)
            sigs_rate = max(
                sigs_rate,
                (eng["device_rows"] + eng["host_rows"]) / res.wall_seconds,
            )
            out[f"{tag}_heights_per_sec"] = round(hps, 3)
            out[f"{tag}_wall_s"] = round(res.wall_seconds, 3)
            out[f"{tag}_deliveries"] = int(res.net["deliveries"])
            out[f"{tag}_multi_source_bundles"] = int(
                eng["counters"]["multi_source_bundles"]
            )
        if best > 0:
            out["sim_heights_per_sec"] = round(best, 3)
            out["sim_engine_sigs_per_sec"] = round(sigs_rate, 1)
        else:
            out["sim_error"] = "no sweep configuration completed"
        out.update(sim_recovery_bench())
        out.update(sim_byz_bench())
        return out
    except Exception as ex:
        log(f"sim bench failed: {ex!r}")
        return {"sim_error": repr(ex)[:200]}


SIM_BYZ = {
    # the adversary-tax drill: the same net twice — once clean, once
    # with the playbook's noisiest attackers (wire garbling, 4x flood
    # amplification, far-future probes) — and the ratio of commit
    # throughput under attack to clean throughput is the guarded
    # number. The defenses (typed rejects, duplicate shedding, height
    # window, quarantine) are what keep the ratio from cratering, so a
    # regression here means an attacker got more leverage per frame.
    "nodes": 7, "validators": 7, "heights": 6, "seed": 77,
    "clean_schedule": "link(*,*):delay:ms=8,jitter_ms=3",
    "byz_schedule": (
        "link(*,*):delay:ms=8,jitter_ms=3"
        ";byz:node=0,kind=garble,at_h=2"
        ";byz:node=1,kind=flood,at_h=2,rate=4"
        ";byz:node=1,kind=future,at_h=2,rate=4"
    ),
}


def sim_byz_bench() -> dict:
    """Commit throughput under the byzantine playbook vs a clean twin
    (``sim_byz_commit_rate``, higher is better — 1.0 would mean the
    attack cost nothing). Guarded like sim_heights_per_sec."""
    try:
        from tendermint_tpu.sim.core import Simulation

        cfg = SIM_BYZ

        def _run(schedule):
            sim = Simulation(
                n_nodes=cfg["nodes"],
                validators=cfg["validators"],
                heights=cfg["heights"],
                schedule=schedule,
                seed=cfg["seed"],
                record_events=False,
            )
            res = sim.run()
            # SIMULATED time for every node to commit the final height:
            # deterministic per seed, so the guarded ratio carries no
            # wall-clock noise
            done_ns = max(
                (ts.get(cfg["heights"], 0) for ts in sim.net.commit_times.values()),
                default=0,
            )
            return sim, res, done_ns

        _, clean, clean_ns = _run(cfg["clean_schedule"])
        byz_sim, byz, byz_ns = _run(cfg["byz_schedule"])
        if not clean.completed or clean_ns <= 0:
            return {"sim_byz_error": "clean twin wedged"}
        if not byz.completed or byz_ns <= 0:
            return {"sim_byz_error": "byz run wedged (liveness lost under attack)"}
        net = byz_sim.net
        if net.receive_crashes:
            return {"sim_byz_error": f"{net.receive_crashes} receive crash(es) under attack"}
        return {
            "sim_byz_commit_rate": round(clean_ns / byz_ns, 3),
            "sim_byz_heights_per_sec": round(cfg["heights"] / byz.wall_seconds, 3),
            "sim_byz_malformed_rejected": int(sum(net.malformed_by_class.values())),
            "sim_byz_floods_shed": int(net.floods_shed),
            "sim_byz_future_drops": int(net.future_drops),
            "sim_byz_quarantines": int(net.quarantines),
        }
    except Exception as ex:
        log(f"sim byz bench failed: {ex!r}")
        return {"sim_byz_error": repr(ex)[:200]}


def sim_recovery_bench() -> dict:
    """The crash-recovery drill: kill a validator mid-run (true crash —
    its ConsensusState dies, the durability domain survives), rebuild
    via handshake + WAL replay at restart_h, and report the simulated
    time from the kill event to the node's first commit after the
    rebuild (``sim_recovery_s``). Guarded like sim_heights_per_sec."""
    try:
        from tendermint_tpu.sim.core import Simulation

        cfg = SIM_RECOVERY
        sim = Simulation(
            n_nodes=cfg["nodes"],
            validators=cfg["validators"],
            heights=cfg["heights"],
            schedule=cfg["schedule"],
            seed=cfg["seed"],
            record_events=True,
        )
        res = sim.run()
        node = cfg["crash_node"]
        if not res.completed:
            return {"sim_recovery_error": "recovery run wedged"}
        t_crash = next(
            (e[1] for e in res.events if e[0] == "crash" and e[2] == node), None
        )
        restarts = sim.net.restart_times.get(node, [])
        if t_crash is None or not restarts:
            return {"sim_recovery_error": "crash/restart events missing"}
        t_restart = restarts[0]
        post = [
            t for t in sim.net.commit_times.get(node, {}).values()
            if t >= t_restart
        ]
        if not post:
            return {"sim_recovery_error": "no post-replay commit"}
        return {
            "sim_recovery_s": round((min(post) - t_crash) / 1e9, 3),
            "sim_recovery_replayed_msgs": int(sim.net.wal_replayed_msgs),
        }
    except Exception as ex:
        log(f"sim recovery bench failed: {ex!r}")
        return {"sim_recovery_error": repr(ex)[:200]}


# Handshake file between the supervisor and the measuring child: the
# child keeps its best partial numbers there and, when done, the
# finished line. One bench per checkout at a time (there is one chip),
# so the path is fixed.
_STATE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".cache", "bench_state.json"
)


def _save_partial(platform: str) -> None:
    with open(_STATE_PATH, "w") as fp:
        json.dump({**_partial, "platform": platform}, fp)


def _run_coldstart() -> dict:
    """The coldstart_* keys from a FRESH process with warm AOT + table
    caches (a restarting validator must reach its first device-verified
    commit in seconds, not a recompile window). Started by the
    supervisor only after the measuring child has exited: a process
    that holds the chip must never start a child that needs it."""
    import subprocess

    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=dict(os.environ, TM_BENCH_COLDSTART="1"),
            capture_output=True, text=True, timeout=180,
        )
    except subprocess.TimeoutExpired as ex:
        log(f"cold-start child timed out: {ex!r}")
        return {"coldstart_error": repr(ex)[:200]}
    out_lines = r.stdout.strip().splitlines()
    if r.returncode != 0 or not out_lines:
        # a dead child must fail LOUDLY: its stderr carries the actual
        # traceback
        for ln in r.stderr.strip().splitlines()[-20:]:
            log(f"  coldstart| {ln}")
        log(f"cold-start child FAILED: rc={r.returncode}")
        return {
            "coldstart_error": f"child rc={r.returncode}, "
            f"stdout lines={len(out_lines)} (stderr above)"
        }
    cs = json.loads(out_lines[-1])
    log(f"fresh-process cold start: {cs}")
    return {
        "coldstart_backend_init_s": cs.get("backend_init_s"),
        "coldstart_first_verify_s": cs.get("first_verify_s"),
        "coldstart_tabled_first_s": cs.get("tabled_first_s"),
        "coldstart_tables_source": cs.get("tables_source"),
    }


def _supervise() -> int:
    """Run the measuring child under a hard deadline (XLA compiles can
    hold the GIL for minutes, so in-process alarms/threads can't be
    trusted to fire), then the cold-start child, then finish the line.
    This process never imports JAX, so it never holds the chip."""
    import subprocess

    os.makedirs(os.path.dirname(_STATE_PATH), exist_ok=True)
    # seed the state file BEFORE spawning: a child that crashes at
    # import never reaches _save_partial
    with open(_STATE_PATH, "w") as fp:
        json.dump({**_partial, "platform": "unknown"}, fp)
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=dict(os.environ, TM_BENCH_INNER="1"),
    )
    rc = None
    try:
        rc = child.wait(timeout=DEADLINE_S)
    except subprocess.TimeoutExpired:
        log(f"bench deadline ({DEADLINE_S}s) hit; killing child")
        child.kill()
        child.wait()
    st = {}
    try:
        with open(_STATE_PATH) as fp:
            st = json.load(fp)
        os.unlink(_STATE_PATH)
    except (OSError, ValueError):
        pass
    line = st.get("line")
    if line is not None:
        # the child finished measuring (a nonzero rc after that is XLA
        # teardown noise, not a failure) and has exited: the chip is free
        if rc != 0:
            log(f"bench child exited rc={rc} after handing over its line")
        if line["platform"] != "cpu":
            line.update(_run_coldstart())
        return _finish(line)
    if rc is None:
        emit(
            st.get("value_ms"), st.get("vs_baseline"),
            platform=st.get("platform", "unknown"), deadline_hit=True,
            note=st.get("note", "bench child produced no output"),
        )
        return 0
    # the child reported its own failure (no chip, or an error line)
    log(f"bench child exited rc={rc}")
    return rc


def _coldstart() -> None:
    """Fresh-process measurement of the RESTARTING-VALIDATOR paths
    (round-2 verdict #2: first device-verified commit <5s, not a ~20s
    recompile window): backend init, then verify_commit with AOT-loaded
    stage executables, then the tabled path with the parent's persisted
    valset tables (pure data from disk — no build program). Prints one
    JSON line; run by the parent bench with warm AOT + table caches."""
    import numpy as np

    n = BENCH_N
    pks, msgs, sigs = make_batch(n)  # host prep excluded from the timing
    powers = np.full(n, 10, dtype=np.int64)
    counted = np.ones(n, dtype=bool)

    t0 = time.perf_counter()
    import jax

    jax.devices()
    init_s = time.perf_counter() - t0

    from tendermint_tpu.models.verifier import VerifierModel

    t0 = time.perf_counter()
    model = VerifierModel()
    ok, tally = _verify_then_tally(model, pks, msgs, sigs, powers, counted)
    first_s = time.perf_counter() - t0
    assert ok.all() and tally == n * 10

    # tabled restart: same valset key the parent measured under, so the
    # persisted tables are the ones a restarting node would find
    t0 = time.perf_counter()
    idx = np.arange(n, dtype=np.int32)
    ok_t = model.verify_rows_cached(b"bench-valset", pks, idx, msgs, sigs)
    tabled_s = time.perf_counter() - t0
    out = {
        "backend_init_s": round(init_s, 2),
        "first_verify_s": round(first_s, 2),
    }
    if ok_t is not None:
        assert ok_t.all()
        out["tabled_first_s"] = round(tabled_s, 2)
        out["tables_source"] = "build" if model.key_pool.dispatches else "disk"
    print(json.dumps(out), flush=True)


def main():
    if os.environ.get("TM_BENCH_COLDSTART") == "1":
        _coldstart()
        return
    if os.environ.get("TM_BENCH_INNER") != "1":
        sys.exit(_supervise())
    from tendermint_tpu.utils.jaxenv import require_accelerator

    platform = require_accelerator("bench").platform
    _save_partial(platform)
    try:
        run_bench(platform)
    except Exception as e:  # still emit the one line, with diagnostics
        import traceback

        traceback.print_exc(file=sys.stderr)
        emit(None, None, platform=platform, error=repr(e)[:400])
        # a total crash where a previous accelerator record exists is a
        # regression by definition: fail loudly like the guard would
        if platform != "cpu" and _last_tpu_result() is not None:
            sys.exit(3)
        sys.exit(0)


if __name__ == "__main__":
    main()
