#!/usr/bin/env python3
"""perfbench attribution — one traced run of one cell with the program's
own spans in the profile, and every device-idle millisecond charged to
the host step that held the chip.

    python3 perfbench/attribute.py --workload <name> --seed <n> --seconds <s> [--alternate] [--traced-seconds <s>]

The run is ``run.py --trace 1``'s, step for step (``harness.run_cell``),
with one difference in the traced window: the program's profiler sink
(``tendermint_tpu.utils.trace.profiler_sink``) is turned on right after
the profile starts and off right before it stops, and the profile is
reduced with ``perfbench.program_trace``. ``--alternate`` turns the sink
on for every other traced request only, for its own cost in one window
(``sink_on_p50_ms`` against ``sink_off_p50_ms``; the attribution is then
partial). ``--traced-seconds`` profiles longer than the mix's
``trace.seconds``. The last line of standard output is the result line with an
``attribution`` object added: the idle time by span (seconds over the
window, as ``breakdown.idle_gaps``), each span's own time in ms a
request, the program-span metrics (``layer_metrics``) and the traced
requests' median.
"""

import time

T_PROCESS = time.time()  # set-up is counted from here, as run.py does

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("idle_unattributed_pct", "pack_host_ms", "pipeline_hop_ms", "launch_host_ms")


def sink_tracer(harness, alternate: bool, traced_seconds=None):
    """``harness.Tracer`` with the program's sink switched beside the
    profile (the whole window, or every other request), reducing with
    the program's spans."""
    from perfbench import program_trace, trace
    from perfbench.spans import REQUEST_SPAN as REQUEST
    from tendermint_tpu.utils import trace as tm_trace

    class SinkTracer(harness.Tracer):
        made = None  # the run's one tracer

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            if traced_seconds:
                self.seconds = traced_seconds
            self.reduced = None
            self.request_ms = []
            SinkTracer.made = self

        def on_request(self, i: int, now: float) -> None:
            super().on_request(i, now)
            if self.first is not None and self.last is None:
                tm_trace.profiler_sink(not alternate or (i - self.first) % 2 == 0)

        def stop(self, i: int) -> None:
            if self.first is not None and self.last is None:
                tm_trace.profiler_sink(False)
            super().stop(i)

        def reduce(self):
            if self.first is None:
                return None
            try:
                path = trace.find_xplane(self.dir)
                program = program_trace.read_program_spans(path)
                harness.say(f"program spans in the profile: {len(program)}")
                devices, bench = trace.read_xplane(path)
                self.request_ms = [(e - s) / 1e6 for n, s, e in sorted(bench, key=lambda x: x[1]) if n == REQUEST]
                self.reduced = program_trace.reduce(devices, bench, program)
                return self.reduced
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)

    return SinkTracer


def attribution(reduced, alternate_ms=None) -> dict:
    """What the traced window shows of the program's spans; with
    ``--alternate``, the medians of the traced requests with the sink on
    (the window's first, third, ...) and off (second, fourth, ...)."""
    from perfbench.harness import plugin

    run = {"trace": reduced}
    out = {
        "program_spans": reduced.program_spans,
        "traced_request_p50_ms": reduced.request_p50_ms,
        "idle_s": sum(reduced.idle_by_span.values()),
        "idle_by_span": reduced.idle_by_span,
        "span_ms_per_request": {k: 1e3 * v for k, v in sorted(reduced.span_s.items(), key=lambda kv: -kv[1])},
        "metrics": {name: plugin("layer_metrics", name).read(run) for name in READERS},
    }
    if alternate_ms and len(alternate_ms) > 1:
        out["sink_on_p50_ms"] = statistics.median(alternate_ms[0::2])
        out["sink_off_p50_ms"] = statistics.median(alternate_ms[1::2])
    return out


def execute(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--alternate", action="store_true", help="the sink on for every other traced request")
    ap.add_argument("--traced-seconds", type=float, default=None, help="profile this long (default: the mix's)")
    ap.add_argument("--rehearse", action="store_true", help="run on a backend that is not a TPU; no device metric")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from perfbench import harness

    tracer = harness.Tracer = sink_tracer(harness, args.alternate, args.traced_seconds)
    result = harness.run_cell(args.workload, args.seed, args.seconds, True, T_PROCESS, args.rehearse)
    reduced = tracer.made.reduced if tracer.made is not None else None
    if reduced is not None:
        result["attribution"] = attribution(reduced, tracer.made.request_ms if args.alternate else None)
        harness.say(f"idle by span (s over the window): {json.dumps(reduced.idle_by_span)}")
        harness.say(f"own time by span (ms a request): {json.dumps(result['attribution']['span_ms_per_request'])}")
    return result


if __name__ == "__main__":
    print(json.dumps(execute()), flush=True)
