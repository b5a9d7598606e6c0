"""The provider stack as a node builds it (``node/node.py``): the
configuration's ``crypto_provider`` inside a ``PipelinedVerifier``,
installed as the process's default provider — with ``block_on_compile``
on, so that compilation is set-up and no row is quietly served on the
host while a program compiles. The benchmark's recording wrapper sits
under the pipeline, around the device provider. An adapter that hands
the provider to its entry itself asks for no pipeline and gets none.
"""

from __future__ import annotations

import os

import numpy as np

from perfbench.spans import RecordingProvider


def use_checkout_caches() -> str:
    """The repo's one cache root (``JAX_COMPILATION_CACHE_DIR`` verbatim,
    else ``<checkout>/.cache/jax`` with ``aot/``), and a table directory
    of the benchmark's own under it: seeded validator sets must not push
    a real node's tables out of the production directory's keep-N."""
    from tendermint_tpu.utils import jaxenv

    jaxenv.scope_tables_cache("perfbench")
    return jaxenv.enable_compile_cache()


def inner_provider(name: str):
    from tendermint_tpu.crypto.batch import make_provider

    return make_provider(name, block_on_compile=True)


class ProviderStack:
    def __init__(self, config: dict, annotate: bool, pipelined: bool = True):
        from tendermint_tpu.crypto.batch import set_default_provider
        from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache

        refuse_host_overrides()
        # before the first import of a module that jits, and so before any compile
        self.cache_dir = use_checkout_caches()
        self.recorder = RecordingProvider(inner_provider(config["crypto_provider"]), annotate=annotate)
        self.pipeline = None
        if pipelined:
            self.pipeline = PipelinedVerifier(self.recorder, cache=SigCache())
            set_default_provider(self.pipeline)

    def engine_stats(self) -> dict:
        """The pipeline's ``engine_stats``; with no pipeline, the parts of
        it that exist below one (the provider's row counts, the model's
        buckets), under the same keys."""
        if self.pipeline is not None:
            return self.pipeline.engine_stats()
        from tendermint_tpu.models.telemetry import bucket_view

        inner = self.recorder.inner
        device_rows, host_rows = inner.row_counts.snapshot()
        model = getattr(inner, "model", None)
        return {
            "engine": "provider", "device_rows": float(device_rows), "host_rows": float(host_rows),
            "buckets": {f"fn:{k}": b for k, b in bucket_view(dict(getattr(model, "_entries", None) or {})).items()},
        }

    def setup_report(self) -> dict:
        """Where the valset tables came from and how long they took (the
        model's own record; printed beside ``setup_s``, not a metric)."""
        model = getattr(self.recorder.inner, "model", None)
        return {
            "tables": [
                {"source": e.source, "build_s": round(e.build_s, 2)}
                for e in getattr(model, "_valset_tables", {}).values() if e.ready
            ]
        }

    def close(self) -> None:
        """Drain the pipeline and give the default provider back, so that
        the program's device state can be freed."""
        from tendermint_tpu.crypto.batch import CPUBatchVerifier, set_default_provider

        if self.pipeline is not None:
            self.pipeline.stop()
            set_default_provider(CPUBatchVerifier())
        self.pipeline = self.recorder = None


class StackEntry:
    """What the adapters that drive the verify path share: the stack, its
    counters, and the rows the recorder kept for a request."""

    def __init__(self, config: dict, annotate: bool, pipelined: bool = True):
        self.stack = ProviderStack(config, annotate, pipelined)
        self.recorder = self.stack.recorder

    def engine_stats(self) -> dict:
        return self.stack.engine_stats()

    def setup_report(self) -> dict:
        return self.stack.setup_report()

    def close(self) -> None:
        self.stack.close()
        self.recorder = None

    @staticmethod
    def rows_of(rec) -> np.ndarray:
        return np.concatenate(rec.row_ok) if rec.row_ok else np.zeros(0, dtype=bool)


def refuse_host_overrides() -> None:
    """An environment setting that would quietly move work to the host
    fails the run (``chip_smoke.py``'s rule)."""
    if os.environ.get("TM_FAULTS"):
        raise SystemExit("perfbench: unset TM_FAULTS: it arms injected device faults (host fallbacks)")
