"""One run of one cell: everything is found by the names ``BENCHMARK.json``
gives, so a later PR adds a cell, a mix, a deployment or a per-layer
metric as files and entries and edits nothing that is here.

    BENCHMARK.json workloads[].config   -> configs[].file        (a deployment)
    BENCHMARK.json workloads[].traffic  -> traffic/<mix>.json    (a mix)
    mix["generator"]["name"]            -> generators/<name>.py  generate(config, params, seed)
    mix["loop"]["kind"]                 -> loops/<kind>.py       run(entry, params, seconds, on_request)
    config["entry"]                     -> entries/<name>.py     Entry(dataset, config, mix, annotate)
    per_layer[].name                    -> layer_metrics/<name>.py  read(run) -> value or None
    device kind                         -> peaks.json

The order of a run: find the device, make the data from the seed, build
the system, warm the cell's own shapes (all of that is ``setup_s``), drive
the loop for ``--seconds``, read the counters and the device's memory
peak, stop the system, only then run the plain reference, compare, print.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench.spans import serve

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
REFERENCE_WORKERS = 8


def say(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def plugin(kind: str, name: str):
    """``perfbench/<kind>/<name>.py``, by name."""
    return importlib.import_module(f"perfbench.{kind}.{name}")


def find_cell(manifest: dict, workload: str):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perfbench: no workload {workload!r} in BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    mix = load_json(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    return cell, config, mix


def metric_applies(metric: dict, cell: dict) -> bool:
    return "workloads" not in metric or cell["name"] in metric["workloads"]


# -- the device ---------------------------------------------------------------


def find_device(chips: int, rehearse: bool) -> dict:
    """JAX's default backend, in this process. Anything but a TPU with
    enough chips ends the run with no result — unless the caller asked
    for a rehearsal, which prints no device metric."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    say(f"device: {json.dumps(info)}")
    if rehearse:
        return info
    if info["platform"] != "tpu":
        raise SystemExit(f"perfbench: JAX's backend is {info['platform']!r}, not a TPU (rehearse with --rehearse)")
    if len(devs) < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chip(s), JAX reports {len(devs)}")
    return info


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


class CompileCounter:
    """Counts XLA compilations and fetches from the persistent cache, to
    show that neither falls in the window."""

    EVENTS = (
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.n += 1


# -- the traced part of a traced run ------------------------------------------


class Tracer:
    """Profiles ``seconds`` of the window, from ``start_after`` seconds
    in, starting and stopping between requests."""

    def __init__(self, trace_dir: str, params: dict):
        self.dir = trace_dir
        self.start_after = float(params.get("start_after_s", 1.0))
        self.seconds = float(params["seconds"])
        self.first = self.last = None  # request indices [first, last)
        self.t_on = None
        self.t0 = None

    def on_request(self, i: int, now: float) -> None:
        if self.t0 is None:
            self.t0 = now
        if self.first is None and now - self.t0 >= self.start_after:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the benchmark's spans are TraceAnnotations, not Python frames
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.first, self.t_on = i, now
        elif self.first is not None and self.last is None and now - self.t_on >= self.seconds:
            self.stop(i)

    def stop(self, i: int) -> None:
        import jax

        if self.first is not None and self.last is None:
            jax.profiler.stop_trace()
            self.last = i

    def reduce(self):
        from perfbench import trace

        if self.first is None:
            return None
        try:
            return trace.reduce(*trace.read_xplane(trace.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)  # traces are large; nothing reads them twice


# -- comparison with the plain reference --------------------------------------


def compare(answers: List[dict], pool_of: List[int], reference: List[dict]) -> Dict[str, dict]:
    """Every request's answer against the reference's for its pool entry.
    Each number beside its limit; all comparisons are exact. The tally is
    in the verdict: "have x, need > y" where the power falls short."""
    verdicts = rows_off = rows = 0
    for a, k in zip(answers, pool_of):
        want = reference[k]
        verdicts += a["verdict"] != want["verdict"]
        n = min(len(a["rows"]), len(want["rows"]))
        rows += n
        rows_off += int(np.count_nonzero(a["rows"][:n] != want["rows"][:n]))
        rows_off += abs(len(a["rows"]) - len(want["rows"]))  # a row with no verdict is a wrong row
    return {
        "verdict_mismatches": {"value": int(verdicts), "limit": 0},
        "row_mismatches": {"value": int(rows_off), "limit": 0},
        "requests_compared": {"value": len(answers), "at_least": 1},
        "rows_compared": {"value": int(rows), "at_least": 1},
    }


def window_counters(config: dict, compiles: int, stats0: dict, stats1: dict) -> Dict[str, dict]:
    """What the deployment says may not happen inside a window
    (``window_limits`` of its file: a compilation, a row served on the
    host, a bundle re-verified serially), each count beside its limit."""
    grew = {"compiles_in_window": compiles}
    return {
        name: {"value": int(grew[name] if name in grew else _grew(stats0, stats1, name)), "limit": int(limit)}
        for name, limit in (config.get("window_limits") or {}).items()
    }


def all_hold(check: Dict[str, dict]) -> bool:
    return all(
        c["value"] <= c["limit"] if "limit" in c else c["value"] >= c["at_least"]
        for c in check.values()
    )


# -- metrics ------------------------------------------------------------------


def end_to_end(records, window_s: float, setup_s: float) -> Dict[str, float]:
    ms = np.array([1e3 * (r.t1 - r.t0) for r in records])
    return {
        "request_p50_ms": float(np.median(ms)),
        "request_p95_ms": float(np.percentile(ms, 95)),  # over all requests, linear between ranks
        "sigs_per_s": sum(r.rows for r in records) / window_s,
        "setup_s": setup_s,
    }


def metric_line(values: Dict[str, Optional[float]], metrics: List[dict]) -> Dict[str, dict]:
    return {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in metrics
        if values.get(m["name"]) is not None
    }


# -- one run ------------------------------------------------------------------


def run_cell(
    workload: str, seed: int, seconds: float, trace_on: bool, t_process: float,
    rehearse: bool = False, entry_name: Optional[str] = None,
) -> dict:
    """Runs the cell and returns the result line. ``entry_name`` puts
    another adapter in the place of the configuration's own: the control
    (``control.py``), which the comparison has to fail."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, mix = find_cell(manifest, workload)
    device = find_device(int(cell["chips"]), rehearse)
    on_chip = device["platform"] == "tpu"
    compiles = CompileCounter()

    t0 = time.perf_counter()
    dataset = plugin("generators", mix["generator"]["name"]).generate(config, mix["generator"]["params"], seed)
    say(f"data from seed {seed}: {time.perf_counter() - t0:.2f}s")
    entry = plugin("entries", entry_name or config["entry"]).Entry(
        dataset, config, mix, trace_on
    )
    loop = plugin("loops", mix["loop"]["kind"])

    t0 = time.perf_counter()
    for i in range(int(mix["warmup_requests"])):
        rec = serve(entry, i)
        say(f"warm-up request {i}: {rec.t1 - rec.t0:.3f}s, verdict {type(rec.outcome).__name__ if rec.outcome else 'accept'}")
    say(f"warm-up: {time.perf_counter() - t0:.2f}s, compilations so far {compiles.n}")
    if hasattr(entry, "setup_report"):
        say(f"set-up: {json.dumps(entry.setup_report())}")
    gc.collect()
    gc.freeze()  # the pool's objects are the harness's, not a node's: keep them out of every collection
    stats0 = entry.engine_stats()
    compiles0 = compiles.n
    setup_s = time.time() - t_process

    tracer = Tracer(os.path.join(ROOT, ".cache", "perfbench", "trace"), mix["trace"]) if trace_on else None
    records, w0, w1 = loop.run(entry, mix["loop"], seconds, tracer.on_request if tracer else None)
    if tracer:
        tracer.stop(len(records))
    window_s = w1 - w0
    stats1 = entry.engine_stats()
    compiles_in_window = compiles.n - compiles0
    peak = memory_peak_bytes()
    say(
        f"window: {len(records)} requests in {window_s:.3f}s, compilations inside it {compiles_in_window}, "
        f"fallback_serial +{_grew(stats0, stats1, 'fallback_serial')}, "
        f"host rows +{_grew(stats0, stats1, 'host_rows'):.0f}, device rows +{_grew(stats0, stats1, 'device_rows'):.0f}"
    )
    say(f"rows of each provider call, by request (shape of a request: how many had it): {_call_shapes(records)}")
    say(f"request_p50_ms by quarter of the window (a run's own steadiness; not a metric): {_quarter_medians(records)}")
    say(f"per-bucket compile_s (includes waits on the compile lock; not a metric): {_compile_seconds(stats1)}")

    records.sort(key=lambda r: r.index)
    answers = [entry.answer(r) for r in records]
    reduced = tracer.reduce() if tracer else None
    entry.close()  # the program's state goes before the reference starts
    t0 = time.perf_counter()
    reference = entry.reference_answers(workers=min(REFERENCE_WORKERS, os.cpu_count() or 1))
    say(f"reference: {time.perf_counter() - t0:.2f}s")
    check = {
        **compare(answers, [r.pool_index for r in records], reference),
        **window_counters(config, compiles_in_window, stats0, stats1),
    }
    failed = sum(a["verdict"][0] == "unread" for a in answers)

    result = {
        "correct": all_hold(check) and failed == 0,
        "attempted": len(records),
        "failed": int(failed),
        "metrics": {},
        "device": {**device, "memory_peak_bytes": peak},
    }
    if trace_on:
        traced = [r for r in records if tracer.first is not None and tracer.first <= r.index < tracer.last]
        run = {
            "records": records, "window_s": window_s, "engine_stats": (stats0, stats1),
            "trace": reduced if on_chip else None,
            "traced_rows": sum(r.rows for r in traced),
            "peaks": load_json(os.path.join(BENCH_DIR, "peaks.json")).get(device["kind"]) if on_chip else None,
            "device": device, "config": config, "mix": mix,
        }
        if on_chip and run["peaks"] is None:
            raise SystemExit(f"perfbench: no peaks for device kind {device['kind']!r} in peaks.json")
        wanted = [m for m in manifest["per_layer"] if metric_applies(m, cell)]
        values = {m["name"]: plugin("layer_metrics", m["name"]).read(run) for m in wanted}
        if reduced is not None and on_chip:
            result["device"].update(busy_s=reduced.busy_s, window_s=reduced.window_s)
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in reduced.device_ops],
                "idle_gaps": [[n, s] for n, s in reduced.idle_gaps],
            }
            say(f"traced {reduced.requests} requests, modules: {json.dumps(reduced.module_s)}")
    else:
        wanted = [m for m in manifest["end_to_end"] if metric_applies(m, cell)]
        values = end_to_end(records, window_s, setup_s)
    line = metric_line(values, wanted)
    # a time or a rate from anything but the chip is never written under a device metric's name
    result["metrics" if on_chip else "rehearsal_metrics"] = line
    result["check"] = check
    say("compared: " + ", ".join(
        f"{k}={c['value']} ({'limit' if 'limit' in c else 'at least'} {c.get('limit', c.get('at_least'))})"
        for k, c in check.items()
    ) + f", failed={failed} (limit 0)")
    return result


def _grew(stats0: dict, stats1: dict, key: str) -> float:
    """Growth of a counter of ``engine_stats`` over the window, at its
    top level or among its ``counters``."""
    before, after = ({**(st.get("counters") or {}), **st} for st in (stats0, stats1))
    return after.get(key, 0) - before.get(key, 0)


def _call_shapes(records) -> str:
    shapes: Dict[tuple, int] = {}
    for r in records:
        shape = tuple(len(a) for a in r.row_ok)
        shapes[shape] = shapes.get(shape, 0) + 1
    return json.dumps([[list(shape), n] for shape, n in sorted(shapes.items())][:12])


def _quarter_medians(records) -> str:
    ms = np.array([1e3 * (r.t1 - r.t0) for r in records])
    return json.dumps([round(float(np.median(q)), 3) for q in np.array_split(ms, 4) if len(q)])


def _compile_seconds(stats: dict) -> str:
    return json.dumps({
        k: round(b["compile_s"], 2) for k, b in (stats.get("buckets") or {}).items() if b.get("compile_s")
    })
