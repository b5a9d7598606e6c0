"""Profiling/debug HTTP endpoint — the pprof equivalent.

Reference: node/node.go:719-723 serves net/http/pprof when
`prof_laddr` is set; `tendermint debug kill` collects goroutine dumps.
Python equivalents here: /stacks (all thread stacks via faulthandler-
style traceback dump), /tasks (asyncio task dump — the goroutine-dump
analog), /gc (object counts), /health.
"""

from __future__ import annotations

import asyncio
import gc
import io
import sys
import traceback
from typing import Optional

from tendermint_tpu.utils import trace


def dump_thread_stacks() -> str:
    out = io.StringIO()
    frames = sys._current_frames()
    for tid, frame in frames.items():
        out.write(f"\n--- thread {tid} ---\n")
        traceback.print_stack(frame, file=out)
    return out.getvalue()


def dump_asyncio_tasks() -> str:
    out = io.StringIO()
    try:
        tasks = asyncio.all_tasks()
    except RuntimeError:
        return "no running event loop\n"
    out.write(f"{len(tasks)} tasks\n")
    for t in sorted(tasks, key=lambda t: t.get_name()):
        out.write(f"\n--- task {t.get_name()} done={t.done()} ---\n")
        stack = t.get_stack(limit=8)
        for frame in stack:
            out.write(
                f"  {frame.f_code.co_filename}:{frame.f_lineno} {frame.f_code.co_name}\n"
            )
    return out.getvalue()


_jax_trace_dir: Optional[str] = None


def jax_trace(action: str, trace_dir: str = "") -> str:
    """Start/stop a JAX profiler trace (xprof/tensorboard format) —
    the device-side analog of the reference's pprof CPU profiles
    (SURVEY §5.1: 'JAX profiler + xprof traces around kernel
    dispatch'). While it runs the tracer's profiler sink is on, so the
    node's own spans (utils/trace.py: the verify seam's, the pipeline's,
    each launch's) are host events beside the device's operations in
    the same profile. Lazy import: a node without device work never
    touches jax here."""
    global _jax_trace_dir
    try:
        import jax
    except Exception as e:  # pragma: no cover - jax is baked in
        return f"jax unavailable: {e!r}\n"
    if action == "start":
        if _jax_trace_dir is not None:
            return f"trace already running -> {_jax_trace_dir}\n"
        if not trace_dir:
            import tempfile

            # never a fixed path in world-writable /tmp (symlink games,
            # cross-process clobbering)
            trace_dir = tempfile.mkdtemp(prefix="tm_jax_trace_")
        try:
            jax.profiler.start_trace(trace_dir)
        except Exception as e:
            return f"start_trace failed: {e!r}\n"
        trace.profiler_sink(True)
        _jax_trace_dir = trace_dir
        return f"tracing -> {trace_dir}\n"
    if action == "stop":
        if _jax_trace_dir is None:
            return "no trace running\n"
        out, _jax_trace_dir = _jax_trace_dir, None
        trace.profiler_sink(False)
        try:
            # clear the marker FIRST: if stop raises (e.g. someone used
            # jax.profiler directly), start stays retryable instead of
            # the endpoint wedging until restart
            jax.profiler.stop_trace()
        except Exception as e:
            return f"stop_trace failed: {e!r}\n"
        return f"trace written -> {out}\n"
    return "actions: start stop\n"


def dump_gc_stats() -> str:
    counts = {}
    for obj in gc.get_objects():
        name = type(obj).__name__
        counts[name] = counts.get(name, 0) + 1
    top = sorted(counts.items(), key=lambda kv: -kv[1])[:40]
    return "\n".join(f"{n:10d} {name}" for name, n in top) + "\n"


class ProfServer:
    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self._host, self._port = host, port
        self._server = None
        self.bound_port: Optional[int] = None

    async def start(self) -> None:
        self._server = await asyncio.start_server(self._handle, self._host, self._port)
        self.bound_port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()

    async def _handle(self, reader, writer) -> None:
        try:
            line = await reader.readline()
            while (await reader.readline()) not in (b"\r\n", b"\n", b""):
                pass
            path = line.split()[1].decode() if len(line.split()) > 1 else "/"
            if path.startswith("/stacks"):
                body = dump_thread_stacks()
            elif path.startswith("/tasks"):
                body = dump_asyncio_tasks()
            elif path.startswith("/gc"):
                body = dump_gc_stats()
            elif path.startswith("/jax_trace"):
                # /jax_trace?action=start&dir=/tmp/trace | ?action=stop
                from urllib.parse import parse_qs, urlsplit

                q = parse_qs(urlsplit(path).query)
                # in an executor: stop_trace serializes the whole trace
                # to disk and must not freeze the event loop that also
                # runs consensus on the node being profiled
                body = await asyncio.get_running_loop().run_in_executor(
                    None,
                    jax_trace,
                    q.get("action", [""])[0],
                    q.get("dir", [""])[0],
                )
            else:
                body = "routes: /stacks /tasks /gc /jax_trace\n"
            data = body.encode()
            writer.write(
                b"HTTP/1.1 200 OK\r\nContent-Type: text/plain\r\n"
                + f"Content-Length: {len(data)}\r\nConnection: close\r\n\r\n".encode()
                + data
            )
            await writer.drain()
        finally:
            writer.close()
