"""Closed loop, one caller: the next request is sent when the last has its
verdict, back to back, from the main thread.

A request's object is made fresh (``entry.fresh_request``) before its
clock starts, and its time ends with the verdict in hand. A request that
was started before the deadline is finished, and the window is as long as
it took: rates are over all of it. More callers than one are refused:
the recorder charges provider calls to the one open request, and the
program carries no request id through its pipeline to do better.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional

from perfbench.spans import RequestRecord, serve


def run(entry, params: dict, seconds: float, on_request: Optional[Callable] = None):
    """Drive ``entry`` for ``seconds``; returns (records, t_start, t_end)
    on the ``time.perf_counter`` clock. ``on_request(i, now)`` is called
    between requests (the traced run starts and stops the profiler there,
    off every request's clock)."""
    if int(params.get("callers", 1)) != 1:
        raise SystemExit("perfbench: loops/closed.py drives one caller; see its docstring")
    records: List[RequestRecord] = []
    t_start = time.perf_counter()
    deadline = t_start + seconds
    i = 0
    while True:
        now = time.perf_counter()
        if now >= deadline:
            return records, t_start, now
        if on_request is not None:
            on_request(i, now)
        records.append(serve(entry, i))
        i += 1
