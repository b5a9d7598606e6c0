"""The reduction from a trace to busy/idle, per-module time and gap
attribution, on a trace built by hand. Compiles nothing."""

import pytest

from perfbench import trace
from perfbench.spans import PROVIDER_SPAN, REQUEST_SPAN

MS = 1e6  # ns


def hand_built():
    """Two requests of 10 ms, 2 ms apart. In each: 2 ms of packing, a
    provider call of 7 ms, 1 ms of replay. The device runs module A for
    3 ms (two overlapping ops) and module B for 2 ms inside each provider
    call, with 1 ms between them."""
    spans, modules, ops = [], [], []
    for r0 in (0.0, 12 * MS):
        spans.append((REQUEST_SPAN, r0, r0 + 10 * MS))
        spans.append((PROVIDER_SPAN, r0 + 2 * MS, r0 + 9 * MS))
        a0, b0 = r0 + 3 * MS, r0 + 7 * MS
        modules += [("jit_a(111)", a0, a0 + 3 * MS), ("jit_b(222)", b0, b0 + 2 * MS)]
        ops += [("fusion.1", a0, a0 + 2 * MS), ("fusion.2", a0 + 1 * MS, a0 + 3 * MS), ("copy.3", b0, b0 + 2 * MS)]
    spans.append(("something else", 0.0, 50 * MS))  # not the benchmark's: ignored
    devices = {"/device:TPU:0": {trace.MODULES_LINE: modules, trace.OPS_LINE: ops}}
    return devices, spans


def test_union_merges_overlaps_and_keeps_gaps():
    seconds, merged = trace.union_s([(0, 2e9), (1e9, 3e9), (5e9, 6e9), (6e9, 6e9)])
    assert seconds == pytest.approx(4.0)
    assert merged == [(0, 3e9), (5e9, 6e9)]


def test_window_busy_idle_and_modules():
    r = trace.reduce(*hand_built())
    assert r.requests == 2 and r.chips == 1
    assert r.window_s == pytest.approx(0.022)
    assert r.busy_s == pytest.approx(0.010)  # 2 x (3 ms of overlapping ops + 2 ms)
    assert r.module_s == pytest.approx({"jit_a": 0.006, "jit_b": 0.004})
    assert r.module_runs == {"jit_a": 2, "jit_b": 2}
    assert dict(r.device_ops) == pytest.approx(
        {"jit_a/fusion.1": 0.004, "jit_a/fusion.2": 0.004, "jit_b/copy.3": 0.004}
    )


def test_gaps_are_charged_to_what_the_host_was_in():
    gaps = dict(trace.reduce(*hand_built()).idle_gaps)
    # per request the device idles over [0,3): 2 ms of packing and 1 ms of the call;
    # [6,7) inside the call; [9,10) of replay; and the 2 ms between the requests.
    # A gap that spans several host states is cut at their edges.
    assert sum(gaps.values()) == pytest.approx(0.012)
    assert gaps["seam.pack"] == pytest.approx(0.004)
    assert gaps["provider.call"] == pytest.approx(0.004)
    assert gaps["seam.replay"] == pytest.approx(0.002)
    assert gaps["between_requests"] == pytest.approx(0.002)


def test_events_outside_the_traced_requests_are_clipped():
    devices, spans = hand_built()
    devices["/device:TPU:0"][trace.OPS_LINE].append(("late", 21 * MS, 30 * MS))
    devices["/device:TPU:0"][trace.MODULES_LINE].append(("jit_late(9)", 40 * MS, 41 * MS))
    r = trace.reduce(devices, spans)
    assert r.busy_s == pytest.approx(0.011)  # only the millisecond inside the window
    assert "jit_late" not in r.module_s


def test_two_chips_average_busy_and_sum_modules():
    devices, spans = hand_built()
    devices["/device:TPU:1"] = {trace.MODULES_LINE: [], trace.OPS_LINE: []}
    r = trace.reduce(devices, spans)
    assert r.chips == 2 and r.busy_s == pytest.approx(0.005)


def test_nothing_to_read_gives_nothing():
    assert trace.reduce({}, []).window_s == 0.0
    from perfbench.layer_metrics import device_idle_pct, launches_per_request, scan_us_per_sig, verify_roofline

    run = {"trace": trace.reduce({}, []), "traced_rows": 0, "peaks": None}
    for reader in (device_idle_pct, launches_per_request, scan_us_per_sig, verify_roofline):
        assert reader.read(run) is None
    run["trace"] = None
    assert device_idle_pct.read(run) is None


def test_module_name_strips_the_fingerprint_only():
    assert trace.module_name("jit_verify_stage_scan_tabled(123456789)") == "jit_verify_stage_scan_tabled"
    assert trace.module_name("jit_f") == "jit_f"
    assert trace.op_name("%while.408 = (s32[]{:T(128)}, s32[10240,20]) while((s32[]) %tuple.1)") == "while.408"
    assert trace.op_name("fusion.2") == "fusion.2"
