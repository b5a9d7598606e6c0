"""JAX platform and cache plumbing.

Two decisions live here and nowhere else:

- which directory the three on-disk caches use (JAX's persistent
  compilation cache, the serialized-executable cache and the built
  valset tables — models/aot_cache.py): ``compile_cache_dir()``;
- how a caller that ASKED for the CPU gets it (tests, virtual-mesh
  preflights): ``force_cpu_platform()``. Nothing in the repo chooses
  the CPU on its own — a program that needs the chip takes JAX's
  default backend, says which it got, and fails if it is not a TPU.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """THE cache root. ``JAX_COMPILATION_CACHE_DIR`` verbatim when set
    (a directory placed from outside warms every process); otherwise a
    fixed git-ignored path inside the checkout — the path is part of
    what makes a cache reusable, so never a temp name, a pid or the
    time. The serialized-executable and valset-table caches default to
    ``<root>/aot`` and ``<root>/tables`` (models/aot_cache.py), so one
    directory carries all three."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".cache", "jax"
    )


def scope_tables_cache(name: str) -> None:
    """Give a harness its own valset-table directory under the root
    (``<root>/tables-<name>``) unless TM_TABLES_CACHE_DIR is already
    set: synthetic valsets must not evict a real node's persisted
    tables from the production directory's keep-N LRU."""
    os.environ.setdefault(
        "TM_TABLES_CACHE_DIR", os.path.join(compile_cache_dir(), f"tables-{name}")
    )


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at compile_cache_dir()
    and return it. Called by every module that jits (before its first
    compile); idempotent. With the variable set JAX already reads it,
    so no other value is ever written to the config.

    XLA:CPU entries are safe to share: jax 0.9.0's cache key hashes the
    serialized CPU topology, which lists the host's machine features
    (+avx512f, ...), so an executable built for another host's
    instruction set is a miss, not a load."""
    import jax

    d = compile_cache_dir()
    if jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return d


def force_cpu_platform(n_devices: Optional[int] = None) -> None:
    """Select the (virtual, if n_devices is set) CPU platform — for a
    caller that asked for it. Must run before the first backend
    initializes; callers that depend on it check ``jax.devices()``
    afterwards."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")


def require_accelerator(what: str):
    """``jax.devices()[0]`` for a program that measures the device:
    takes JAX's default backend IN THIS PROCESS (no subprocess probe —
    a chip belongs to one process), says which it got, and exits
    non-zero when that is not a TPU unless the caller asked for the CPU
    with ``JAX_PLATFORMS=cpu``. Nothing is retried on another
    backend."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(
        f"{what}: backend {dev.platform} x{len(devs)} ({dev.device_kind})",
        file=sys.stderr, flush=True,
    )
    asked_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if dev.platform != "tpu" and not asked_cpu:
        raise SystemExit(
            f"{what}: JAX's default backend is {dev.platform!r}, not a TPU; "
            "set JAX_PLATFORMS=cpu to run on the CPU on purpose"
        )
    return dev


_AOT_NOISE_TAG = b"cpu_aot_loader"
# A line is noise only when its TRIGGERING feature (the loader names
# it: "Target machine feature <X> is not supported") is one of the
# codegen tuning flags XLA bakes into every feature string. Merely
# CONTAINING the flag names is not enough — every modern blob's
# compile-feature dump lists them, including a genuinely foreign-ISA
# blob's — so a real mismatch (triggered by e.g. +avx512fp16 on an
# un-scoped shared cache dir) passes through.
_AOT_NOISE_TRIGGERS = (
    b"machine feature +prefer-no-scatter is not",
    b"machine feature +prefer-no-gather is not",
)


def is_cpu_aot_noise(line) -> bool:
    """True when `line` (str or bytes) is a KNOWN-false-positive
    cpu_aot_loader warning (see _AOT_NOISE_TRIGGERS). Shared by the fd
    filter below and tests/conftest's captured-output scrub."""
    if isinstance(line, str):
        line = line.encode("utf-8", "replace")
    return _AOT_NOISE_TAG in line and any(t in line for t in _AOT_NOISE_TRIGGERS)


def filter_cpu_aot_noise():
    """Filter the KNOWN-FALSE-POSITIVE cpu_aot_loader warnings from the
    C++ stderr stream (fd 2), passing everything else through.

    XLA bakes its own codegen tuning flags (+prefer-no-scatter,
    +prefer-no-gather) into the serialized executable's feature string
    and then compares that string against the host's CPU feature list
    at load — flags that are not CPU features and never appear in the
    host list, so EVERY load of a CPU executable warns "Machine type
    ... doesn't match ... could lead to SIGILL", including a blob
    compiled seconds earlier on this very machine (verified by
    save/load probe in one process pair on one host). A genuinely
    foreign executable cannot load (JAX's cache key and the AOT
    fingerprint both cover the host's CPU features), which makes the
    remaining warnings pure noise — drop exactly those lines.

    Returns a restore() callable. Escape hatch: TM_RAW_CPP_STDERR=1
    makes this a no-op."""
    if os.environ.get("TM_RAW_CPP_STDERR") == "1":
        return lambda: None
    import threading

    is_noise = is_cpu_aot_noise
    r, w = os.pipe()
    orig = os.dup(2)
    os.dup2(w, 2)
    os.close(w)
    out_fd = os.dup(orig)

    def pump():
        buf = b""
        with os.fdopen(r, "rb", 0) as rf:
            while True:
                chunk = rf.read(4096)
                if not chunk:
                    break
                buf += chunk
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    if not is_noise(line):
                        os.write(out_fd, line + b"\n")
            if buf and not is_noise(buf):
                os.write(out_fd, buf)
        os.close(out_fd)

    t = threading.Thread(target=pump, daemon=True, name="stderr-filter")
    t.start()

    def restore():
        sys.stderr.flush()
        os.dup2(orig, 2)  # drops the last ref to the pipe's write end
        os.close(orig)
        t.join(timeout=5)

    return restore
