"""kernels: the least time the chip could take for the traced rows
(``rooflines/ed25519_verify``, against ``peaks.json``) over the device
time of the verify programs' launches in the trace."""

from perfbench.rooflines import ed25519_verify

MODULE_PREFIXES = ("jit_verify_stage_", "jit_materialize_sign_bytes")


def read(run):
    t = run["trace"]
    if t is None or not run["traced_rows"] or run["peaks"] is None:
        return None
    kernel_s = sum(s for name, s in t.module_s.items() if name.startswith(MODULE_PREFIXES))
    if kernel_s <= 0:
        return None
    least = ed25519_verify.least_seconds(run["traced_rows"], run["peaks"])
    return 100.0 * least["seconds"] / kernel_s
