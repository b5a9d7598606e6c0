#!/usr/bin/env python3
"""``control.py`` for a cell whose chain has a validator set a height:

    python3 perfbench/control_sets.py --workload <name> --seed <n> --seconds <s> [--rehearse]

runs ``entries/control_first_set_only`` in the program's place (every
commit checked against the first height's set); the last line has to
read ``"correct": false``. Exit code 0 when it does, 1 when the control
passed (the comparison then cannot see a stale set).
"""

import json
import sys

import run  # the sibling script: also starts the set-up clock

CONTROL = "control_first_set_only"

if __name__ == "__main__":
    result = run.execute(entry_name=CONTROL)
    result = {"control": CONTROL, **result}
    print(json.dumps(result), flush=True)
    sys.exit(0 if result["correct"] is False else 1)
