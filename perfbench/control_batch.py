#!/usr/bin/env python3
"""``control.py`` for a cell whose request is a bare batch that streams
as windows:

    python3 perfbench/control_batch.py --workload <name> --seed <n> --seconds <s> [--rehearse]

runs ``entries/control_full_windows_only`` in the program's place (the
full windows checked, the tail taken as valid); the last line has to
read ``"correct": false``. Exit code 0 when it does, 1 when the control
passed (the comparison then cannot see a tail that was never read).
"""

import json
import sys

import run  # the sibling script: also starts the set-up clock

CONTROL = "control_full_windows_only"

if __name__ == "__main__":
    result = run.execute(entry_name=CONTROL)
    result = {"control": CONTROL, **result}
    print(json.dumps(result), flush=True)
    sys.exit(0 if result["correct"] is False else 1)
