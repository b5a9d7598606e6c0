"""kernels: device time of the generic verify family's three stage
programs per signature row of the traced requests. The modules are named
exactly: the tabled programs share the ``jit_verify_stage_`` prefix."""

MODULES = ("jit_verify_stage_prepare", "jit_verify_stage_scan", "jit_verify_stage_finish")


def read(run):
    t = run["trace"]
    if t is None or not run["traced_rows"]:
        return None
    generic_s = sum(t.module_s.get(name, 0.0) for name in MODULES)
    if generic_s <= 0:
        return None
    return 1e6 * generic_s / run["traced_rows"]
