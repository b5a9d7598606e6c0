"""model: share of the slots launched in slot order that held no row
(absent validators, a table bucket's padding, a last launch rounded up
to a power of two of commits), from the program's ``TABLED_COUNTS``: the
stage-2 scan spends as long on an empty slot as on a signed one. None
where nothing ran in slot order."""

from perfbench.layer_metrics.tabled_rows_pct import tabled_counts


def read(run):
    got = tabled_counts(run)
    if got is None:
        return None
    counts, _ = got
    slots = counts["tabled_slot_rows"] + counts["tabled_slot_pad"]
    if slots <= 0:
        return None
    return 100.0 * counts["tabled_slot_pad"] / slots
