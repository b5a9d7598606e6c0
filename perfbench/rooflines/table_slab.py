"""Bytes a table slab must move, whatever implements it — the numerator
of ``table_slab_roofline``.

A slab is the table operand of one launch cut from the key pool: for
each of its columns the key's cached tables (16 splits of 8 affine-cached
points of 3 field elements of 20 int32 limbs: 30,720 bytes), its 32-byte
public key and its one-byte decompression verdict, read where the pool
holds them and written where the launch reads them. Nothing is computed:
the least time is the bytes over the memory bandwidth.
"""

from __future__ import annotations

BYTES_PER_COLUMN = 16 * 8 * 3 * 20 * 4 + 32 + 1


def work(columns: int) -> dict:
    """Bytes read and written for ``columns`` columns, and no operations."""
    return {"ops": 0, "bytes": 2 * columns * BYTES_PER_COLUMN}


def least_seconds(columns: int, peaks: dict) -> dict:
    w = work(columns)
    return {"seconds": w["bytes"] / peaks["hbm_bytes_per_s"], "bound": "hbm_bytes", **w}
