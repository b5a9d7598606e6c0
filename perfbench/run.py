#!/usr/bin/env python3
"""perfbench — one process, one cell, once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Takes JAX's default backend and measures only on a TPU with the chips
the cell asks for; ``--rehearse`` runs the same path on whatever backend
there is (``JAX_PLATFORMS=cpu`` here) and prints no device metric. The
last line of standard output is the result; see perfbench/README.md.
"""

import time

T_PROCESS = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def execute(argv=None, entry_name=None) -> dict:
    """Parse the command line, run the cell, return the result line."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help="run on a backend that is not a TPU; no device metric")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)  # the checkout this file lies in, before anything installed
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu would log under /tmp
    from perfbench import harness

    return harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), T_PROCESS, args.rehearse, entry_name
    )


if __name__ == "__main__":
    print(json.dumps(execute()), flush=True)  # "check", the numbers compared beside their limits, comes last
