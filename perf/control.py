"""Readings that set a cell's limits: the program's numbers over many
seeds, and the control's.

    python perf/control.py --workload <cell> --seeds 1 2 ... --control-seeds 1 2 3 \\
        [--seconds 2]

One process builds the cell's program once; per seed it runs a short
window exactly as a benchmark run does and compares the sampled
segments with the reference (the lower reading of each number).  On the
control seeds it also puts the reference itself in the program's place,
computing the event apply one precision below the configuration's
(``LOWER``: bfloat16 below float32, int8 below bfloat16), and compares
that with the reference at the stated precision from the same state
(the upper reading).  One JSON line per seed.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, os.path.dirname(HERE))

from perf import harness  # noqa: E402
from perf.reference import check  # noqa: E402

# the nearest precision below each stated one: the step that tempts
LOWER = {"float32": "bfloat16", "bfloat16": "int8"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell, devs = harness.open_cell(args.workload, HERE, True)
    precision = cell["config"]["apply_precision"]
    part, program = harness.build(cell, devs)
    ref = harness.reference_setup(cell, [precision, LOWER[precision]])
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        m = harness.measure(cell, program, seed, args.seconds, False,
                            time.perf_counter())
        lower = LOWER[precision] if seed in args.control_seeds else None
        prog, ctl = harness.reference_readings(cell, ref, m["samples"],
                                               precision, lower)
        print(json.dumps({"seed": seed, "segments": len(m["lat"]),
                          "sampled": [s[0] for s in m["samples"]],
                          "failed": harness._sum(m["stats"], lambda s: s.overflow)
                          + harness._sum(m["stats"], lambda s: s.deadline_miss),
                          "spikes_per_segment": harness._sum(
                              m["stats"], lambda s: s.spikes) / len(m["lat"]),
                          "program": check.worst(prog),
                          "control": check.worst(ctl) if ctl else None}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
