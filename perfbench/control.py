#!/usr/bin/env python3
"""Runs a cell with the control in the program's place:

    python3 perfbench/control.py --workload <name> --seed <n> --seconds <s> [--rehearse]

Same command line, harness, data from the seed and comparison as
``run.py``; the last line has to read ``"correct": false``. Exit code 0
when it does, 1 when the control passed (the comparison then cannot tell
a broken guarantee).
"""

import json
import sys

import run  # the sibling script: also starts the set-up clock

CONTROL = "control_quorum_only"

if __name__ == "__main__":
    result = run.execute(entry_name=CONTROL)
    result = {"control": CONTROL, **result}
    print(json.dumps(result), flush=True)
    sys.exit(0 if result["correct"] is False else 1)
