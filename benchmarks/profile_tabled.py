#!/usr/bin/env python
"""Profile the tabled verify pipeline stage-by-stage on the live backend.

Prints per-stage wall times (pipelined over K dispatches, one sync) so
the optimization target is measured, not estimated:

    python benchmarks/profile_tabled.py            # 10240 rows
    TM_PROF_N=4096 python benchmarks/profile_tabled.py
    TM_PROF_TRACE=/tmp/xprof python benchmarks/profile_tabled.py

With TM_PROF_TRACE set, the warm stage loop also runs under
jax.profiler.trace for xprof/tensorboard analysis (the trace dir is
printed). Stage split (models/verifier.py cached-table path):

    s1  sha512 challenge + canonical-s + signed recode
    s2  table gather + 16-doubling/96-madd split scan    <- dominant
    s3  blocked-inversion encode + R compare

Reference loop being replaced: types/validator_set.go:641-668.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    n = int(os.environ.get("TM_PROF_N", "10240"))
    k = int(os.environ.get("TM_PROF_K", "8"))

    import bench as bench_mod

    pks, msgs, sigs = bench_mod.make_batch(n)

    import jax
    import jax.numpy as jnp

    from tendermint_tpu.utils.jaxenv import require_accelerator

    require_accelerator("profile_tabled")

    from tendermint_tpu.models.verifier import VerifierModel

    model = VerifierModel()
    idx = np.arange(n, dtype=np.int32)
    key = b"profile-valset"

    t0 = time.perf_counter()
    ok = model.verify_rows_cached(key, pks, idx, msgs, sigs)
    assert ok is not None and ok.all(), "tabled path must verify the batch"
    print(f"cold (tables+compile+run): {time.perf_counter()-t0:.1f}s", file=sys.stderr)

    e = model._tables_entry(key, pks)  # the set's table operand: the key pool as it lies
    s1, s2, s3 = model._table_stage_fns()[:3]
    mg_d = jax.device_put(jnp.asarray(msgs))
    sg_d = jax.device_put(jnp.asarray(sigs))
    idx_d = jax.device_put(jnp.asarray(idx))

    # warm every stage on device-resident args (pubkeys gather on device
    # from the cached e.pk_dev matrix — no per-call pubkey H2D)
    sd, kd, s_ok = s1(e.pk_dev, idx_d, mg_d, sg_d)
    px, py, pz, pt, a_ok = s2(sd, kd, e.tables, e.a_ok, idx_d)
    out = s3(px, py, pz, pt, sg_d, a_ok, s_ok)
    np.asarray(out)

    def timed(label, fn, baseline_s=0.0):
        """Pure DEVICE time per dispatch: enqueue k dispatches back-to-back
        and sync ONCE on the last output — queue depth amortizes the
        per-sync host round trip, which per-call timing would add to
        every stage. A measured empty-dispatch baseline is
        subtracted."""
        out = None
        t0 = time.perf_counter()
        for _ in range(k):
            out = fn()
        np.asarray(out[0] if isinstance(out, tuple) else out)
        dt = max((time.perf_counter() - t0) / k - baseline_s, 0.0)
        print(f"{label:34s} {dt*1e3:8.2f} ms/dispatch", file=sys.stderr)
        return dt

    noop = jax.jit(lambda a: a[:1] + 1)
    noop(sd).block_until_ready()
    base = timed("dispatch+sync baseline (noop)", lambda: noop(sd))
    # 3-dispatch baseline for the chained measurement: base bundles the
    # amortized sync once, so 3*base would subtract the sync share three
    # times; a 3-noop chain pays exactly 3 dispatches + sync/k like the
    # real chain does
    base3 = timed("3-dispatch chain baseline", lambda: noop(noop(noop(sd))))

    t1 = timed("s1 prepare (sha512+recode)", lambda: s1(e.pk_dev, idx_d, mg_d, sg_d), base)
    t2 = timed(
        "s2 scan (gather+split scan)",
        lambda: s2(sd, kd, e.tables, e.a_ok, idx_d),
        base,
    )
    t3 = timed(
        "s3 finish (blocked inv)",
        lambda: s3(px, py, pz, pt, sg_d, a_ok, s_ok),
        base,
    )

    # sub-kernels of s2: the gather and the scan arithmetic, separately
    gather = jax.jit(lambda t, i: jnp.take(t, i, axis=0))
    row_tables = gather(e.tables, idx_d)
    row_tables.block_until_ready()
    tg = timed("  s2a gather tables[idx] alone", lambda: gather(e.tables, idx_d), base)

    from tendermint_tpu.ops import curve as _curve

    scan_only = jax.jit(lambda a, b, t: _curve.double_scalar_mul_tabled(a, b, t).x)
    scan_only(sd, kd, row_tables).block_until_ready()
    ts = timed("  s2b split scan alone (pre-gathered)", lambda: scan_only(sd, kd, row_tables), base)

    def chain():
        a, b, c = s1(e.pk_dev, idx_d, mg_d, sg_d)
        x, y, z, t, w = s2(a, b, e.tables, e.a_ok, idx_d)
        return s3(x, y, z, t, sg_d, w, c)

    tc = timed("chained s1->s2->s3", chain, base3)
    print(
        f"baseline {base*1e3:.2f} ms; sum of stages {sum((t1,t2,t3))*1e3:.2f} ms; "
        f"chained {tc*1e3:.2f} ms; {n/tc:,.0f} sigs/s sustained\n"
        f"s2 split: gather {tg*1e3:.2f} + scan {ts*1e3:.2f} ms"
    )

    trace_dir = os.environ.get("TM_PROF_TRACE")
    if trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(3):
                np.asarray(chain())
        print(f"xprof trace written to {trace_dir}")


if __name__ == "__main__":
    main()
