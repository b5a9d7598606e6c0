"""Ask the chip's compiler, without the chip: the main-path programs at
real width (10,240 rows, 160-byte sign bytes) compiled for a DESCRIBED
v5e:2x2 device. A pass says the TPU compiler accepts the program and
that it fits; it is never a chip run and says nothing about time or
results (chip_smoke.py does that, on the chip).

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped, non-autouse fixture that
skips where it cannot be; nothing touches jax.experimental.topologies
at import; the persistent cache is off around the compiles (such an
entry cannot be read back without a chip); no child process compiles.
All such tests live in THIS file: the worker that runs it keeps the TPU
library until it exits.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from tendermint_tpu.ops import ed25519 as E
from tendermint_tpu.parallel.mesh import BATCH_AXIS

N = 10_240  # the bucket above MaxVotesCount
HBM_BYTES = 16 * 10**9  # one v5e chip (Google Cloud documentation, "TPU v5e")
u8, i32 = jnp.uint8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture()
def compile_for(topo, no_persistent_cache):
    """compile_for(fn_or_jitted, limit_s, *shapes) -> Compiled, failing
    the test when the compile outlives its limit or the program does
    not fit one chip's memory. The limit is held against the lesser of
    the wall clock and the process's CPU seconds: a compile that shares
    its cores with five other test workers takes several times its own
    work on the wall, and one that spreads over threads more CPU than
    wall — a program that has grown too large for the compiler is long
    by both."""

    def run(fn, limit_s, *args):
        t0, c0 = time.perf_counter(), time.process_time()
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        compiled = jitted.lower(*args).compile()
        dt = min(time.perf_counter() - t0, time.process_time() - c0)
        m = compiled.memory_analysis()
        need = (
            m.generated_code_size_in_bytes + m.temp_size_in_bytes
            + m.argument_size_in_bytes + m.output_size_in_bytes
        )
        assert need < HBM_BYTES, f"{need} bytes do not fit one chip"
        assert dt < limit_s, f"compile took {dt:.0f}s, limit {limit_s}s"
        return compiled

    return run


def shapes(sharding):
    """S(shape, dtype) and like(eval_shape tree) bound to a sharding."""

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    def like(tree):
        return jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype), tree)

    return S, like


def test_templated_tabled_prepare_compiles_for_v5e(topo, compile_for):
    """Stage 1 of the live commit path: (templates, tmpl_idx, ts8)
    materialized on device, then the slot-order tabled prepare at C = 1."""
    S, like = shapes(SingleDeviceSharding(topo.devices[0]))
    tpl, tidx, ts8 = S((2, 160), u8), S((N,), i32), S((N, 8), u8)
    compile_for(E.materialize_sign_bytes, 30, tpl, tidx, ts8)
    mg = like(jax.eval_shape(E.materialize_sign_bytes, tpl, tidx, ts8))
    assert mg.shape == (N, 160) and mg.dtype == u8
    compile_for(E.verify_stage_prepare_tabled_slots, 90, S((N, 32), u8), mg, S((N, 64), u8))


# One launch of the XLA stage 2 at 10,240 slots was 21,469 fusions, ~28
# a field multiplication, each about a microsecond of mostly fixed cost
# (compile-only reading, ISSUE 36): the number the kernel form starts from.
XLA_STAGE2_FUSIONS = 21_469


@pytest.mark.parametrize("v,c", [(N, 1), (1024, 16)], ids=["commit-10240x1", "light-1024x16"])
def test_tabled_slot_scan_compiles_for_v5e(topo, compile_for, v, c):
    """Stage 2, the dominant kernel, in slot order at both cells'
    shapes — one commit of 10,240 slots against a 10,240-key table
    (~315 MB resident beside the program), 16 commits of 1,024 —
    lowered for the TPU: the point arithmetic is the two Pallas kernels
    (ops/stage2_kernel.py), and what XLA keeps around them (the digit
    and table hand-over, the comb's MXU select) is at most a tenth of
    the fusions the XLA body was, so the arithmetic cannot slide back
    into XLA unseen. It plans one transposed copy of the table and of
    the comb's entries, no more."""
    import re

    S, like = shapes(SingleDeviceSharding(topo.devices[0]))
    tables, a_ok = like(jax.eval_shape(E.build_valset_tables, S((v, 32), u8)))
    n = v * c
    sd, kd, _ = like(
        jax.eval_shape(E.verify_stage_prepare_tabled_slots, S((v, 32), u8), S((n, 160), u8), S((n, 64), u8))
    )
    compiled = compile_for(E.verify_stage_scan_tabled_slots, 120, sd, kd, tables, a_ok)
    assert compiled.memory_analysis().temp_size_in_bytes <= 700e6
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "stage2_window" in text and "stage2_comb" in text
    fusions = len(re.findall(r" fusion\(", text))
    assert fusions <= XLA_STAGE2_FUSIONS // 10, fusions


# The generic stage 2 as the XLA body at 16,384 rows: a rolled loop of
# 1,478 fusions and 130,903,040 bytes of temporaries (26,178,560 at
# 4,096 rows), compile-only readings of curve.double_scalar_mul_signed
# for v5e: what the kernel form starts from.
XLA_GENERIC_FUSIONS = 1_478
XLA_GENERIC_TEMP_BYTES = {16_384: 130_903_040, 4_096: 26_178_560}


@pytest.mark.parametrize("n", sorted(XLA_GENERIC_TEMP_BYTES, reverse=True))
def test_generic_scan_compiles_for_v5e(topo, compile_for, n):
    """Stage 2 of the generic family ("scan") at the batch cell's two
    buckets, lowered for the TPU: the table build and the 64 windows
    are ONE Pallas kernel, generic_scan (ops/stage2_kernel.py); what XLA
    keeps around it (the digits and points laid rows on lanes and back)
    is at most a tenth of the XLA body's fusions, and it plans no more
    temporaries than the XLA body did."""
    import re

    S, _ = shapes(SingleDeviceSharding(topo.devices[0]))
    args = (S((n, 64), i32),) * 2 + (S((n, 20), i32),) * 4
    compiled = compile_for(E.verify_stage_scan, 90, *args)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "generic_scan" in text
    fusions = len(re.findall(r" fusion\(", text))
    assert fusions <= XLA_GENERIC_FUSIONS // 10, fusions
    assert compiled.memory_analysis().temp_size_in_bytes <= XLA_GENERIC_TEMP_BYTES[n]


# The generic prepare and finish at 16,384 rows with the chains as rolled
# XLA loops: 1,047 and 501 fusions, 17,717,760 and 8,170,496 bytes of
# temporaries (compile-only readings of the parent for v5e): what the
# kernel form of the chains starts from.
XLA_CHAIN_STAGES = {"prepare": (1_047, 17_717_760), "finish": (501, 8_170_496)}


@pytest.mark.parametrize("stage", sorted(XLA_CHAIN_STAGES))
def test_generic_chain_stages_compile_for_v5e(topo, compile_for, stage):
    """Stages 1 and 3 of the generic family at the batch cell's window,
    lowered for the TPU: decompression's square root and the encode's
    inversion are each ONE Pallas kernel, field_chain
    (ops/field_kernel.py). The chain's loops leave XLA — the finish
    keeps at most a fifth of its fusions, the prepare (SHA-512, the
    recode, the rest of decompression) two thirds — and neither plans
    more temporaries than before."""
    import re

    n = 16_384
    S, _ = shapes(SingleDeviceSharding(topo.devices[0]))
    if stage == "prepare":
        compiled = compile_for(E.verify_stage_prepare, 180, S((n, 32), u8), S((n, 92), u8), S((n, 64), u8))
    else:
        limbs = S((n, 20), i32)
        flags = S((n,), jnp.bool_)
        compiled = compile_for(E.verify_stage_finish, 60, *(limbs,) * 4, S((n, 64), u8), flags, flags)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "field_chain" in text
    xla_fusions, xla_temp = XLA_CHAIN_STAGES[stage]
    fusions = len(re.findall(r" fusion\(", text))
    assert fusions <= xla_fusions // (5 if stage == "finish" else 1.5), fusions
    assert compiled.memory_analysis().temp_size_in_bytes <= xla_temp


def _mesh_scan(topo, compile_for, n, kernel):
    from tendermint_tpu.models.verifier import VerifierModel

    mesh = Mesh(np.array(topo.devices[:4]), (BATCH_AXIS,))
    S, like = shapes(NamedSharding(mesh, P(BATCH_AXIS)))
    pre = like(
        jax.eval_shape(E.verify_stage_prepare, S((n, 32), u8), S((n, 160), u8), S((n, 64), u8))
    )
    _, scan = VerifierModel(mesh=mesh)._stages()
    compiled = compile_for(scan._jit, 90, *pre[:6])
    out = compiled.output_shardings
    assert all(s.spec == P(BATCH_AXIS) for s in jax.tree_util.tree_leaves(out)), out
    assert ("generic_scan" in compiled.as_text()) == kernel


def test_shard_map_scan_compiles_for_four_chips(topo, compile_for):
    """The mesh form of the generic scan: rows shard over four devices,
    and the per-device program is the single-device one at N/4 rows —
    2,560, off the kernel form's rule: the XLA body."""
    _mesh_scan(topo, compile_for, N, kernel=False)


def test_shard_map_scan_on_the_rule_compiles_for_four_chips(topo, compile_for):
    """16,384 rows over four devices: 4,096 a device, on the rule, so
    each device's program holds the kernel form."""
    _mesh_scan(topo, compile_for, 16_384, kernel=True)


def test_table_slab_and_build_bucket_compile_for_v5e(topo, compile_for):
    """What a chain whose validator set changes adds to the device: the
    slab that cuts a launch's 1,024-column table operand out of a
    4,096-column key pool — a copy of ~31 MB that plans NO temporary:
    gathered from the (P, 16, 8, 60) form the stages read, the compiler
    lays the whole pool out again first (268 MB at this size), which is
    why the pool holds a key's table as one row —, the write of a
    build's rows into the pool, and the table build at its smallest
    bucket (one new key a height builds 16 rows)."""
    S, like = shapes(SingleDeviceSharding(topo.devices[0]))
    pool = S((4096, 16 * 8 * 60), i32), S((4096,), jnp.bool_), S((4096, 32), u8)
    cols = S((1024,), i32)
    slab = compile_for(E.table_slab, 30, *pool, cols)
    out_t, out_ok, out_pk = like(jax.eval_shape(E.table_slab, *pool, cols))
    tables, _ = like(jax.eval_shape(E.build_valset_tables, S((1024, 32), u8)))
    assert (out_t.shape, out_t.dtype) == (tables.shape, tables.dtype)  # what stage 2 takes
    assert out_ok.shape == (1024,) and out_pk.shape == (1024, 32)
    assert slab.memory_analysis().temp_size_in_bytes <= 1e6
    new_t, new_ok = like(jax.eval_shape(E.build_valset_tables, S((16, 32), u8)))
    compile_for(E.build_valset_tables, 120, S((16, 32), u8))
    put = compile_for(E.table_put, 30, *pool, S((16,), i32), new_t, new_ok, S((16, 32), u8))
    assert put.memory_analysis().temp_size_in_bytes <= 1e6
