"""Watchdog supervisor + circuit breakers + pipeline self-healing.

Covers the ISSUE-4 acceptance criteria pieces that are unit-testable:
- dead worker threads are detected and restarted;
- a pending pipeline future whose exec thread died resolves within its
  deadline (FutureDeadlineError) and sync callers fall back to serial
  verification — no caller hangs;
- circuit breakers trip open on failure, host fallback engages, and a
  half-open probe re-enables the device path after the cooldown, with
  the trip/recovery visible in tendermint_health_* counters.
"""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from tendermint_tpu.crypto.batch import CPUBatchVerifier
from tendermint_tpu.crypto.pipeline import (
    PipelinedVerifier,
    PipelineShutdownError,
    SigCache,
)
from tendermint_tpu.utils import faultinject as faults
from tendermint_tpu.utils import watchdog as wd_mod
from tendermint_tpu.utils.watchdog import (
    CircuitBreaker,
    FutureDeadlineError,
    Watchdog,
)


@pytest.fixture(autouse=True)
def _clean():
    faults.disarm()
    yield
    faults.disarm()
    wd_mod.set_breaker_defaults(failure_threshold=3, cooldown_s=30.0)


def make_batch(n, seed=7):
    # tmlint: disable=unused-import -- imported for its side effect (repo-root path setup)
    from tests.cs_harness import make_genesis  # noqa: F401
    from tendermint_tpu.crypto.keys import Ed25519PrivKey

    pks, msgs, sigs = [], [], []
    for i in range(n):
        sk = Ed25519PrivKey.from_secret(f"wdt-{seed}-{i}".encode())
        m = f"msg-{seed}-{i}".encode().ljust(64, b"\0")
        pks.append(np.frombuffer(sk.pub_key().bytes(), dtype=np.uint8))
        msgs.append(np.frombuffer(m, dtype=np.uint8))
        sigs.append(np.frombuffer(sk.sign(m), dtype=np.uint8))
    return np.stack(pks), np.stack(msgs), np.stack(sigs)


# -- CircuitBreaker ---------------------------------------------------------


def test_breaker_trip_cooldown_halfopen_recovery():
    b = CircuitBreaker("t", failure_threshold=2, cooldown_s=0.05, register=False)
    assert b.state() == "closed" and b.allow()
    b.record_failure()
    assert b.state() == "closed", "below threshold stays closed"
    b.record_failure()
    assert b.state() == "open" and b.stats()["trips"] == 1
    assert not b.allow(), "open within cooldown rejects"
    time.sleep(0.06)
    assert b.allow(), "cooldown elapsed: half-open probe allowed"
    assert b.state() == "half_open"
    assert not b.allow(), "only ONE probe at a time"
    b.record_success()
    assert b.state() == "closed" and b.stats()["recoveries"] == 1
    assert b.allow()


def test_breaker_failed_probe_reopens():
    b = CircuitBreaker("t2", failure_threshold=1, cooldown_s=0.05, register=False)
    b.record_failure()
    assert b.state() == "open"
    time.sleep(0.06)
    assert b.allow()
    b.record_failure()  # probe failed
    assert b.state() == "open" and b.stats()["trips"] == 2
    assert not b.allow(), "fresh cooldown after failed probe"


def test_breaker_success_resets_consecutive_failures():
    b = CircuitBreaker("t3", failure_threshold=2, cooldown_s=1.0, register=False)
    b.record_failure()
    b.record_success()
    b.record_failure()
    assert b.state() == "closed", "non-consecutive failures must not trip"


def test_breaker_release_probe_returns_token():
    """An indeterminate half-open probe (allow() granted but the
    protected path was never exercised — declined work, concurrent
    build) must NOT latch the breaker HALF_OPEN forever: release_probe
    returns to open with the original trip time, so the next allow()
    may probe again immediately."""
    b = CircuitBreaker("t5", failure_threshold=1, cooldown_s=0.05, register=False)
    b.record_failure()
    time.sleep(0.06)
    assert b.allow() and b.state() == "half_open"
    b.release_probe()
    assert b.state() == "open"
    assert b.allow(), "released token: re-probe allowed immediately"
    assert b.state() == "half_open"
    b.record_success()
    assert b.state() == "closed"
    # no-op when not half-open
    b.release_probe()
    assert b.state() == "closed"


def test_breaker_registry_replaces_by_name():
    """Rebuilding an engine re-registers its breaker under the same
    name; the registry must replace the old instance, not accumulate
    dead ones forever (configure_device flips + test fixtures would
    otherwise grow the metrics pump's iteration without bound)."""
    before = {b.name for b in wd_mod.breakers()}
    a = CircuitBreaker("t6.replaced", failure_threshold=1, cooldown_s=0.01)
    a.record_failure()
    assert wd_mod.breaker_stats()["t6.replaced"]["trips"] == 1
    b = CircuitBreaker("t6.replaced", failure_threshold=1, cooldown_s=0.01)
    live = wd_mod.breakers()
    assert [x for x in live if x.name == "t6.replaced"] == [b]
    assert wd_mod.breaker_stats()["t6.replaced"]["trips"] == 0
    assert len(live) == len(before | {"t6.replaced"})


def test_breaker_defaults_are_dynamic():
    b = CircuitBreaker("t4", register=False)
    wd_mod.set_breaker_defaults(failure_threshold=1, cooldown_s=0.01)
    b.record_failure()
    assert b.state() == "open"
    time.sleep(0.02)
    assert b.allow()


# -- Watchdog core ----------------------------------------------------------


def test_watchdog_restarts_dead_worker():
    wd = Watchdog(interval_s=0.01)
    alive = {"v": True}
    restarts = []
    wd.register_worker("w", lambda: alive["v"], lambda: restarts.append(1))
    wd.check_once()
    assert not restarts
    alive["v"] = False
    wd.check_once()
    assert len(restarts) == 1
    assert wd.stats()["workers"]["w"]["restarts"] == 1


def test_watchdog_progress_stall_once_per_episode():
    wd = Watchdog(interval_s=0.01)
    val = {"h": 1}
    seen = []
    wd.register_progress("h", lambda: val["h"], stall_after_s=0.03,
                         on_stall=lambda n, s: seen.append(n))
    wd.check_once()  # first sample
    time.sleep(0.05)
    wd.check_once()
    wd.check_once()  # same episode: no double count
    assert seen == ["h"]
    assert wd.stats()["stalls"]["h"]["stalls"] == 1
    val["h"] = 2  # progress clears the episode
    wd.check_once()
    time.sleep(0.05)
    wd.check_once()
    assert wd.stats()["stalls"]["h"]["stalls"] == 2


def test_watchdog_heartbeat_stall():
    wd = Watchdog(interval_s=0.01)
    wd.register_heartbeat("pump", stall_after_s=0.03)
    wd.heartbeat("pump")
    wd.check_once()
    assert wd.stats()["stalls"]["pump"]["stalls"] == 0
    time.sleep(0.05)
    wd.check_once()
    assert wd.stats()["stalls"]["pump"]["stalls"] == 1
    wd.heartbeat("pump")  # recovery rearms the episode
    wd.check_once()
    assert wd.stats()["stalls"]["pump"]["stalled"] == 0


def test_watchdog_future_deadline():
    wd = Watchdog(interval_s=0.01)
    fut: Future = Future()
    wd.watch_future(fut, 0.02, name="test")
    wd.check_once()
    assert not fut.done()
    time.sleep(0.03)
    wd.check_once()
    with pytest.raises(FutureDeadlineError):
        fut.result(timeout=0)
    assert wd.stats()["future_timeouts"] == 1


def test_watchdog_future_resolved_in_time_untouched():
    wd = Watchdog(interval_s=0.01)
    fut: Future = Future()
    wd.watch_future(fut, 0.01, name="ok")
    fut.set_result(41)
    time.sleep(0.02)
    wd.check_once()
    assert fut.result() == 41
    assert wd.stats()["future_timeouts"] == 0
    assert wd.stats()["futures_watched"] == 0, "done futures are dropped"


def test_watchdog_thread_lifecycle():
    wd = Watchdog(interval_s=0.01)
    alive = {"v": False}
    restarted = threading.Event()
    wd.register_worker("w", lambda: alive["v"], restarted.set)
    wd.start()
    assert wd.running
    assert restarted.wait(1.0), "watchdog thread must run checks"
    wd.stop()
    assert not wd.running


# -- pipeline self-healing --------------------------------------------------


def test_pipeline_exec_death_watchdog_restart_and_deadline_fallback():
    """The ISSUE-4 chaos acceptance core: kill the exec thread WITH a
    bundle in hand; the watchdog restarts it and the stranded caller is
    released by the future deadline, after which the sync interface
    falls back to serial verify — bit-identical results, no hang."""
    pv = PipelinedVerifier(CPUBatchVerifier(), cache=SigCache())
    wd = Watchdog(interval_s=0.02)
    pv.attach_watchdog(wd, deadline_s=0.2)
    wd.start()  # deadlines/restarts must fire while the caller BLOCKS
    try:
        pk, mg, sg = make_batch(4)
        assert pv.verify_batch(pk, mg, sg).all(), "healthy path sanity"

        old_exec = pv._exec_t
        faults.arm("pipeline.exec", "raise", times=1)
        t0 = time.perf_counter()
        ok = pv.verify_batch(pk, mg, sg)  # exec dies holding this bundle
        elapsed = time.perf_counter() - t0
        faults.disarm()
        assert ok.all(), "serial fallback must still verify correctly"
        assert elapsed < 5.0, "released by deadline/restart, not a hang"
        assert pv.fallback_serial >= 1
        assert pv.stats()["fallback_serial"] >= 1

        # watchdog notices the dead thread and restarts it
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            if pv._exec_t is not old_exec and pv._exec_t.is_alive():
                break
            time.sleep(0.01)
        assert pv._exec_t is not old_exec and pv._exec_t.is_alive()
        assert pv.worker_restarts >= 1

        # pipeline is healthy again end to end
        assert pv.verify_batch(pk, mg, sg).all()
    finally:
        faults.disarm()
        wd.stop()
        pv.stop(timeout=2.0)


def test_pipeline_dispatch_death_restart_loses_nothing():
    pv = PipelinedVerifier(CPUBatchVerifier(), cache=SigCache())
    wd = Watchdog(interval_s=0.01)
    pv.attach_watchdog(wd, deadline_s=5.0)
    try:
        # let the dispatch loop go idle, then kill it on its next wake
        pk, mg, sg = make_batch(3)
        assert pv.verify_batch(pk, mg, sg).all()
        faults.arm("pipeline.dispatch", "raise", times=1)
        fut = pv.submit_batch(pk, mg, sg)  # wakes dispatch -> it dies pre-pop
        for _ in range(300):
            if not pv._dispatch_t.is_alive():
                break
            time.sleep(0.01)
        faults.disarm()
        assert not pv._dispatch_t.is_alive()
        wd.check_once()  # restart
        assert pv._dispatch_t.is_alive()
        # the queued item was never lost: the replacement dispatches it
        assert fut.result(timeout=5.0).all()
    finally:
        faults.disarm()
        pv.stop(timeout=2.0)


def test_pipeline_stop_fails_leftover_futures():
    """Satellite: a wedged exec thread must not leave stop() callers
    blocked forever on fut.result() — leftovers fail with a shutdown
    error."""
    pv = PipelinedVerifier(CPUBatchVerifier(), cache=SigCache())
    pk, mg, sg = make_batch(2)
    faults.arm("pipeline.exec", "raise")  # every bundle kills the exec thread
    fut1 = pv.submit_batch(pk, mg, sg)
    for _ in range(300):
        if not pv._exec_t.is_alive():
            break
        time.sleep(0.01)
    assert not pv._exec_t.is_alive()
    # next submission parks in the queue/handoff with no exec to run it
    fut2 = pv.submit_batch(pk, mg, sg)
    time.sleep(0.1)  # let dispatch hand fut2's bundle off
    faults.disarm()
    pv.stop(timeout=0.5)
    for fut in (fut1, fut2):
        assert fut.done(), "no caller may be left hanging after stop()"
        with pytest.raises(PipelineShutdownError):
            fut.result(timeout=0)


def test_pipeline_stop_wedged_alive_exec_fails_inflight_bundle():
    """stop() with a wedged-but-STILL-ALIVE exec thread (hung device
    dispatch) must fail the in-flight bundle's futures too, not only
    the queued/handed-off ones — with no watchdog deadline configured
    this was the last way a fut.result() caller could hang forever."""
    release = threading.Event()

    class _WedgingVerifier(CPUBatchVerifier):
        def verify_batch(self, pubkeys, msgs, sigs, msg_lens=None):
            release.wait(10.0)  # wedge inside _run_bundle
            return super().verify_batch(pubkeys, msgs, sigs, msg_lens=msg_lens)

    pv = PipelinedVerifier(_WedgingVerifier(), cache=SigCache())
    pk, mg, sg = make_batch(2)
    try:
        fut = pv.submit_batch(pk, mg, sg)
        for _ in range(300):  # wait until the bundle is IN the exec thread
            if pv._inflight_bundle is not None:
                break
            time.sleep(0.01)
        assert pv._inflight_bundle is not None
        assert pv._exec_t.is_alive()
        pv.stop(timeout=0.2)  # join times out: exec is alive and wedged
        assert fut.done(), "in-flight bundle's caller must not hang"
        with pytest.raises(PipelineShutdownError):
            fut.result(timeout=0)
    finally:
        release.set()
        pv._exec_t.join(timeout=5.0)


def test_reactor_deadline_zero_disables_window_deadline():
    """config watchdog_future_deadline_ms=0 documents 'disable future
    deadlines': the node maps it to None, and the reactors must pass
    None through as wait-forever — NOT silently reset it to the 10 s
    default. Omitting the kwarg keeps the default."""
    import inspect

    from tendermint_tpu.blockchain.reactor_v0 import BlockchainReactorV0
    from tendermint_tpu.blockchain.reactor_v1 import BlockchainReactorV1
    from tendermint_tpu.blockchain.verify_window import (
        DEFAULT_AWAIT_DEADLINE_S,
        CommitVerifyWindow,
    )

    for cls in (BlockchainReactorV0, BlockchainReactorV1):
        sig = inspect.signature(cls.__init__)
        assert (
            sig.parameters["verify_deadline_s"].default == DEFAULT_AWAIT_DEADLINE_S
        ), f"{cls.__name__}: standalone construction keeps the default deadline"
    # the window honors an explicit None as wait-forever
    win = CommitVerifyWindow(depth=1, provider=None, await_deadline_s=None)
    assert win.await_deadline_s is None
    assert CommitVerifyWindow(depth=1).await_deadline_s == DEFAULT_AWAIT_DEADLINE_S


# -- breaker recovery through the device engines ----------------------------


def test_merkle_device_breaker_trip_and_halfopen_recovery(monkeypatch):
    """ISSUE-4 circuit-breaker acceptance (merkle side): injected device
    failures latch hashing to host; once injection stops, a half-open
    probe re-enables the device path; health counters show the trip and
    the recovery."""
    jax = pytest.importorskip("jax")
    from tendermint_tpu.crypto import merkle
    from tendermint_tpu.utils.metrics import HealthMetrics, Registry

    # the seam builds its engine anew: the breaker registry is keyed by
    # name, and an engine that another test of this worker built later
    # would have taken "merkle.compile" over from the seam's
    monkeypatch.setattr(merkle, "_HASHER", None)
    wd_mod.set_breaker_defaults(failure_threshold=2, cooldown_s=0.1)
    items = [bytes([i % 251]) * 20 for i in range(64)]
    try:
        merkle.configure_device(False)
        host_root = merkle.hash_from_byte_slices(items)

        merkle.configure_device(True, threshold=2, block_on_compile=True)
        # warm the device path once so the failure below is a RUNTIME
        # failure, not a cold compile
        assert merkle.hash_from_byte_slices(items) == host_root
        # the governing breaker: the hasher's compile/dispatch breaker
        # (threshold 1 — one device failure latches its bucket to host)
        breaker = merkle._device_hasher().compile_breaker
        base = breaker.stats()

        faults.arm("device.hash", "raise")
        r1 = merkle.hash_from_byte_slices(items)  # device raises -> trips
        assert r1 == host_root, "host fallback bit-identical"
        assert breaker.state() == "open"
        assert breaker.stats()["trips"] == base["trips"] + 1
        # while open: host path, no device attempt, fault site not evaluated
        evals = faults.stats()["sites"]["device.hash"]["evals"]
        assert merkle.hash_from_byte_slices(items) == host_root
        assert faults.stats()["sites"]["device.hash"]["evals"] == evals

        # injection stops; cooldown passes; half-open probe recovers
        faults.disarm()
        time.sleep(0.12)
        before = merkle.device_stats()["device_roots"]
        assert merkle.hash_from_byte_slices(items) == host_root
        assert breaker.state() == "closed"
        assert breaker.stats()["recoveries"] == base["recoveries"] + 1
        assert merkle.device_stats()["device_roots"] == before + 1, (
            "probe must have used the DEVICE path again"
        )

        # tendermint_health_* reflects the trip and the recovery
        reg = Registry()
        hm = HealthMetrics(reg)
        hm.update(None, wd_mod.breaker_stats(), faults.stats())
        text = reg.expose_text()
        assert 'tendermint_health_breaker_state{breaker="merkle.compile"} 0' in text
        trips_line = [
            l for l in text.splitlines()
            if l.startswith('tendermint_health_breaker_trips_total{breaker="merkle.compile"}')
        ]
        assert trips_line and float(trips_line[0].rsplit(" ", 1)[1]) >= 1
        recov_line = [
            l for l in text.splitlines()
            if l.startswith('tendermint_health_breaker_recoveries_total{breaker="merkle.compile"}')
        ]
        assert recov_line and float(recov_line[0].rsplit(" ", 1)[1]) >= 1
    finally:
        faults.disarm()
        merkle.configure_device(False)


def test_merkle_device_decline_during_probe_does_not_latch_halfopen():
    """A half-open probe whose device call DECLINES without an error
    (root() returns None: cold bucket, shape over the caps) records no
    verdict — the probe token must be released so the merkle.device
    breaker re-probes instead of latching HALF_OPEN forever (every
    allow() False = the permanent latch this PR removes)."""
    from tendermint_tpu.crypto import merkle

    class _DecliningHasher:
        def root(self, items):
            return None  # decline, never raise

    saved = (merkle._DEVICE_ENABLED, merkle._HASHER)
    br = merkle._device_breaker()
    items = [bytes([i % 251]) * 20 for i in range(64)]
    try:
        merkle.configure_device(True, threshold=2)
        merkle._HASHER = _DecliningHasher()
        br._cooldown_s = 0.05
        br.force_open()
        time.sleep(0.06)
        host_root = merkle.hash_from_byte_slices(items)  # probe declines
        assert host_root, "host path must still serve the root"
        assert br.state() != "half_open", "declined probe must not latch"
        assert br.allow(), "released token: a fresh probe is available"
        br.release_probe()
    finally:
        br._cooldown_s = None
        br.record_success()  # restore closed for other tests
        merkle._DEVICE_ENABLED, merkle._HASHER = saved


def test_verifier_tables_breaker_allows_retry_after_cooldown():
    """ISSUE-4 circuit-breaker acceptance (verify side): a failed
    per-valset table build latches that set to the generic path, and
    the half-open probe retries the build once injection stops."""
    pytest.importorskip("jax")
    from tendermint_tpu.models.verifier import VerifierModel

    wd_mod.set_breaker_defaults(failure_threshold=1, cooldown_s=0.1)
    model = VerifierModel(block_on_compile=True)
    model.tables_breaker = CircuitBreaker(
        "verifier.tables.test", failure_threshold=1, cooldown_s=0.1, register=False
    )
    pk, _, _ = make_batch(4, seed=99)
    key = b"valset-key-1"

    faults.arm("device.tables", "raise")
    e = model._tables_entry(key, pk)
    assert e is None, "failed build -> generic path"
    assert model.tables_breaker.state() == "open"
    # still open: no rebuild attempt, still generic
    assert model._tables_entry(key, pk) is None

    faults.disarm()
    time.sleep(0.12)
    e = model._tables_entry(key, pk)  # half-open probe rebuilds
    assert e is not None and e.ready, "recovered: tables built on probe"
    assert model.tables_breaker.state() == "closed"
    assert model.tables_breaker.stats()["recoveries"] == 1
