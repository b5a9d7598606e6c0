"""kernels: device time of the stage-2 scan programs per signature row
verified in the traced requests."""

MODULE_PREFIX = "jit_verify_stage_scan_tabled"


def read(run):
    t = run["trace"]
    if t is None or not run["traced_rows"]:
        return None
    scan_s = sum(s for name, s in t.module_s.items() if name.startswith(MODULE_PREFIX))
    if scan_s <= 0:
        return None
    return 1e6 * scan_s / run["traced_rows"]
