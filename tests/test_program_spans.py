"""The program's own spans on the device trace's clock: the tracer's
profiler sink (``utils/trace.py``), the spans the verify path records,
and their reduction beside the device's events
(``perfbench/program_trace.py`` and the readers of its metrics) on
traces built by hand. No profiler runs and nothing compiles."""

import os
import re

import pytest

from perfbench import program_trace as pt
from perfbench import trace
from perfbench.layer_metrics import (
    h2d_bytes_per_sig,
    idle_unattributed_pct,
    launch_host_ms,
    pack_host_ms,
    pipeline_hop_ms,
)
from perfbench.spans import PROVIDER_SPAN, REQUEST_SPAN
from tendermint_tpu.utils import trace as tt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1e6  # ns
CALLER, EXEC, DISPATCH = (1, 0), (1, 1), (1, 2)  # host-plane lines


# -- the tracer's profiler sink -------------------------------------------------


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: notes what it wraps."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        FakeAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture
def fake_profiler(monkeypatch):
    import jax.profiler

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    FakeAnnotation.log = []
    old = tt.get_tracer()
    yield
    tt.get_tracer().set_profiler_sink(False)
    tt.set_tracer(old)


def test_span_with_ring_and_sink_off_is_the_shared_noop(fake_profiler):
    t = tt.set_tracer(tt.Tracer(enabled=False))
    assert not t.active
    assert tt.span("launch.stage") is tt.NOOP_SPAN
    assert t.span("launch.stage") is tt.NOOP_SPAN
    with tt.span("launch.stage"):
        pass
    assert FakeAnnotation.log == [] and t.recorded == 0


def test_sink_alone_makes_a_span_an_annotation_and_records_nothing(fake_profiler):
    t = tt.set_tracer(tt.Tracer(enabled=False))
    tt.profiler_sink(True)
    assert t.active and not t.enabled
    with tt.span("verify.pack") as sp:
        sp.set(rows=3)  # what a call site may do while tracing: kept nowhere
        with tt.span("verify.columns"):
            pass
    assert FakeAnnotation.log == [
        ("enter", "verify.pack"), ("enter", "verify.columns"),
        ("exit", "verify.columns"), ("exit", "verify.pack"),
    ]
    assert t.recorded == 0
    tt.profiler_sink(False)
    assert not t.active and tt.span("verify.pack") is tt.NOOP_SPAN
    # a span another thread opens just as the sink goes off is the no-op
    assert t._open("verify.pack", {}) is tt.NOOP_SPAN


def test_sink_with_the_ring_records_and_annotates(fake_profiler):
    t = tt.set_tracer(tt.Tracer(enabled=True))
    tt.profiler_sink(True)
    with tt.span("launch.dispatch", rows=3):
        pass
    tt.profiler_sink(False)
    with tt.span("launch.readback"):
        pass
    assert FakeAnnotation.log == [("enter", "launch.dispatch"), ("exit", "launch.dispatch")]
    assert [e[1] for e in t._snapshot()] == ["launch.dispatch", "launch.readback"]
    # the ring's switch alone leaves the sink as it is
    t.enabled = False
    assert not t.active


def test_the_profiler_route_switches_the_sink(fake_profiler, monkeypatch, tmp_path):
    import jax

    from tendermint_tpu.utils import prof

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop", tt.get_tracer().active)))
    t = tt.set_tracer(tt.Tracer(enabled=False))
    assert prof.jax_trace("start", str(tmp_path)).startswith("tracing")
    assert t.active
    assert prof.jax_trace("stop").startswith("trace written")
    # the sink went off before the profile stopped
    assert calls == [("start", str(tmp_path)), ("stop", False)] and not t.active


# -- every name the reduction reads is one the program records ----------------


def _recorded_names():
    names = set()
    pattern = re.compile(r"""\bspan\(\s*["']([a-z][a-z0-9_.]*)["']""")
    for root, _, files in os.walk(os.path.join(REPO, "tendermint_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    names.update(pattern.findall(fh.read()))
    return names


@pytest.mark.parametrize("name", pt.PROGRAM_SPANS)
def test_each_program_span_is_recorded_by_the_program(name):
    assert name in _recorded_names(), f"{name} is read from profiles but no span records it"


def test_program_spans_are_the_three_kinds_without_overlap():
    kinds = (pt.WORK_SPANS, pt.WAIT_SPANS, pt.ENVELOPE_SPANS)
    assert sum(len(k) for k in kinds) == len(set(pt.PROGRAM_SPANS)) == 16
    assert set(pt.PIPELINE_WORK) <= set(pt.PROGRAM_SPANS)


# -- the reduction on a trace built by hand -------------------------------------


def hand_built():
    """Two requests on one chip, the device busy 4-6 ms and 17-19 ms.

    Request 1 [0, 10): the caller packs [0, 2), waits on the pipeline
    [2, 9), replays [9, 9.5); the exec thread runs a bundle [3, 8)
    whose launch stages [3, 4) and reads back [4.5, 7.5).
    Request 2 [11, 20): the caller packs [12, 16) while the dispatch
    thread preps [13, 15)."""
    bench = [
        (REQUEST_SPAN, 0.0, 10 * MS), (PROVIDER_SPAN, 3 * MS, 8 * MS),
        (REQUEST_SPAN, 11 * MS, 20 * MS),
    ]
    program = [
        ("verify.pack", 0.0, 2 * MS, CALLER),
        ("pipeline.wait", 2 * MS, 9 * MS, CALLER),
        ("verify.replay", 9 * MS, 9.5 * MS, CALLER),
        ("pipeline.execute", 3 * MS, 8 * MS, EXEC),
        ("launch.stage", 3 * MS, 4 * MS, EXEC),
        ("launch.readback", 4.5 * MS, 7.5 * MS, EXEC),
        ("verify.pack", 12 * MS, 16 * MS, CALLER),
        ("pipeline.prep", 13 * MS, 15 * MS, DISPATCH),
    ]
    modules = [("jit_a(1)", 4 * MS, 6 * MS), ("jit_b(2)", 17 * MS, 19 * MS)]
    ops = [("fusion.1", 4 * MS, 6 * MS), ("fusion.2", 17 * MS, 19 * MS)]
    devices = {"/device:TPU:0": {trace.MODULES_LINE: modules, trace.OPS_LINE: ops}}
    return devices, bench, program


def test_idle_time_is_charged_by_the_four_rules():
    r = pt.reduce(*hand_built())
    assert r.program_spans == 8 and r.requests == 2
    assert r.idle_by_span == pytest.approx({
        # rule 1: the work span open that started last, on any thread:
        # the dispatch thread's prep [13, 15) inside the caller's pack
        "verify.pack": 0.004, "pipeline.prep": 0.002, "launch.stage": 0.001, "verify.replay": 0.0005,
        # rule 2: no work span open, a wait is; the readback started after the
        # caller's wait, and an envelope (pipeline.execute) never claims
        "launch.readback": 0.0015, "pipeline.wait": 0.0025,
        # rule 3: inside a request, nothing open; rule 4: between requests
        "unattributed": 0.0035, "between_requests": 0.001,
    })


def test_idle_by_span_partitions_the_idle_time_idle_gaps_partitions():
    r = pt.reduce(*hand_built())
    assert sum(r.idle_by_span.values()) == pytest.approx(sum(s for _, s in r.idle_gaps))
    assert sum(r.idle_by_span.values()) == pytest.approx(r.window_s - r.busy_s)


def test_own_time_hop_and_median_per_request():
    r = pt.reduce(*hand_built())
    # own time: pipeline.execute less its two children; per traced request
    assert r.span_s == pytest.approx({
        "verify.pack": 0.003, "pipeline.wait": 0.0035, "verify.replay": 0.00025,
        "pipeline.execute": 0.0005, "launch.stage": 0.0005, "launch.readback": 0.0015,
        "pipeline.prep": 0.001,
    })
    # the caller's wait [2, 9) with no pipeline thread working: [2, 3) and [8, 9)
    assert r.hop_s == pytest.approx(0.001)
    assert r.request_p50_ms == pytest.approx(9.5)


def test_the_program_spans_leave_every_existing_field_as_it_was():
    """The existing reduction, on the existing hand-built trace and on
    this one, with and without the program's spans."""
    from perfbench.tests.test_trace_reduce import hand_built as existing

    devices, bench, program = hand_built()
    for dev, spans, prog in ((*existing(), program), (devices, bench, program), (devices, bench, [])):
        base, got = trace.reduce(dev, spans), pt.reduce(dev, spans, prog)
        assert {k: getattr(got, k) for k in vars(base)} == vars(base)


def test_two_chips_average_the_idle_time():
    devices, bench, program = hand_built()
    devices["/device:TPU:1"] = {trace.MODULES_LINE: [], trace.OPS_LINE: []}
    r = pt.reduce(devices, bench, program)
    # the second chip idles the whole 20 ms window
    assert sum(r.idle_by_span.values()) == pytest.approx((0.016 + 0.020) / 2)
    assert sum(r.idle_by_span.values()) == pytest.approx(sum(s for _, s in r.idle_gaps))


def test_spans_outside_the_window_and_unknown_names_are_left_out():
    devices, bench, program = hand_built()
    program = program + [("verify.pack", 30 * MS, 40 * MS, CALLER)]
    assert pt.reduce(devices, bench, program).span_s == pt.reduce(*hand_built()).span_s
    assert "something.else" not in pt.PROGRAM_SPANS


# -- the readers --------------------------------------------------------------


READERS = (idle_unattributed_pct, pack_host_ms, pipeline_hop_ms, launch_host_ms)


def test_readers_on_a_run():
    run = {"trace": pt.reduce(*hand_built())}
    assert idle_unattributed_pct.read(run) == pytest.approx(100 * 3.5 / 16)
    assert pack_host_ms.read(run) == pytest.approx(3.0)
    assert launch_host_ms.read(run) == pytest.approx(0.5)
    assert pipeline_hop_ms.read(run) == pytest.approx(1.0)


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_readers_give_nothing_without_program_spans(reader):
    devices, bench, _ = hand_built()
    for t in (None, trace.reduce(devices, bench), pt.reduce(devices, bench, []), pt.reduce({}, [], [])):
        assert reader.read({"trace": t}) is None


def test_pipeline_hop_gives_nothing_where_no_caller_waits():
    devices, bench, program = hand_built()
    program = [s for s in program if s[0] != "pipeline.wait"]
    assert pipeline_hop_ms.read({"trace": pt.reduce(devices, bench, program)}) is None


def test_h2d_bytes_per_sig_over_the_window_and_over_the_process():
    stats = lambda rows, b: {"device_rows": rows, "counters": {"h2d_bytes": b}}  # noqa: E731
    assert h2d_bytes_per_sig.read({"engine_stats": (stats(100, 1000), stats(300, 17_400))}) == pytest.approx(82.0)
    # no counters (no pipeline): the process's bytes over its device rows
    from tendermint_tpu.crypto.batch import H2D_COUNTS

    H2D_COUNTS.add(bytes=1)
    total = H2D_COUNTS.snapshot()["h2d_bytes"]
    got = h2d_bytes_per_sig.read({"engine_stats": ({"device_rows": 0}, {"device_rows": 4})})
    assert got == pytest.approx(total / 4)
    for before, after in (({}, {}), (stats(5, 10), stats(5, 10))):
        assert h2d_bytes_per_sig.read({"engine_stats": (before, after)}) is None


# -- the spans the verify path records -------------------------------------------


def _names_by_thread(tracer):
    out = {}
    for ph, name, _t0, _dur, tid, _args in tracer._snapshot():
        if ph == "X":
            out.setdefault(tid, []).append(name)
    return out


def test_a_commit_through_the_pipeline_records_the_seam_and_pipeline_spans():
    import threading

    from tendermint_tpu.crypto.batch import CPUBatchVerifier
    from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
    from tests.test_validator_set import make_commit, make_vals

    vs, by_addr = make_vals([10] * 4)
    commit, block_id = make_commit(vs, by_addr)
    old = tt.get_tracer()
    t = tt.set_tracer(tt.Tracer(enabled=True))
    try:
        with PipelinedVerifier(CPUBatchVerifier(), cache=SigCache()) as pv:
            vs.verify_commit("test-chain", block_id, 5, commit, provider=pv)
    finally:
        tt.set_tracer(old)
    threads = _names_by_thread(t)
    caller = threads.pop(threading.get_ident())
    assert caller == ["verify.columns", "verify.pack", "pipeline.submit", "pipeline.wait", "verify.replay"]
    others = sorted(n for names in threads.values() for n in names)
    assert others == ["pipeline.execute", "pipeline.prep", "pipeline.resolve"]


def test_a_light_chain_records_its_links_columns_pack_and_replay():
    from tendermint_tpu.crypto.batch import CPUBatchVerifier
    from tendermint_tpu.light.verifier import verify_chain
    from tests.light_helpers import CHAIN_ID, T0, gen_chain

    headers, vals = gen_chain(4)
    old = tt.get_tracer()
    t = tt.set_tracer(tt.Tracer(enabled=True))
    try:
        verify_chain(
            CHAIN_ID, headers[1], vals[1], [(headers[h], vals[h]) for h in (2, 3, 4)],
            3 * 3600 * 10**9, now_ns=T0 + 600 * 10**9,
            provider=CPUBatchVerifier(),
        )
    finally:
        tt.set_tracer(old)
    names = [e[1] for e in t._snapshot() if e[0] == "X" and e[1] in pt.PROGRAM_SPANS]
    assert names[0] == "verify.links" and names[-1] == "verify.replay"
    assert names.count("verify.pack") == 3
    assert set(names) == {"verify.links", "verify.columns", "verify.pack", "verify.replay"}


def test_program_spans_recorded_on_the_verify_path_are_all_in_the_reduction():
    """Nothing the verify path records under a verify./launch./pipeline.
    name escapes PROGRAM_SPANS (a new span there must be read too)."""
    shaped = {n for n in _recorded_names() if n.split(".")[0] in ("verify", "launch", "pipeline", "tables")}
    assert shaped <= set(pt.PROGRAM_SPANS)
