"""Record the trimmed trace that ``test_perf_trace.py`` reads.

    python perf/tests/record_trace.py [--workload mc021_1node.ground] [--segments 1]

Runs on a TPU: a traced window of the cell exactly as ``--trace 1`` runs
it, then keeps the device ops of the first ``--segments`` segment
programs (and the idle time up to the next one), the host's annotated
spans over them, and the stacks of the ops they name: their frames in
``src/repro``, each file from there on.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from perf import harness, trace  # noqa: E402


def _short(frame: str) -> str:
    path, _, func = frame.rpartition(":")
    return "src" + path[path.find("/repro/"):] + ":" + func


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mc021_1node.ground")
    ap.add_argument("--segments", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(
        HERE, "data", "trace_mc021_1node.json"))
    args = ap.parse_args()
    cell, devs = harness.open_cell(args.workload, harness.HERE, True)
    _, program = harness.build(cell, devs)
    n_win = cell["traffic"]["segment_windows"]
    m = harness.measure(cell, program, 5, 1.0, True, time.perf_counter())
    mod = program[1].lower(m["carry"], n_win).compile() \
        .runtime_executable().hlo_modules()[0]
    stacks = trace.op_stacks(mod.as_serialized_hlo_module_proto())
    tr = trace.read_xplane(m["trace_dir"], mod.name)
    chips = {}
    for k, c in tr["chips"].items():
        spans = c["modules"][:args.segments + 1]
        end = spans[-1][0]
        ops = [o for o in c["ops"] if spans[0][0] <= o[1] < end]
        chips[k] = {"ops": ops, "modules": spans[:-1]}
    t0 = min(s for c in chips.values() for s, _ in c["modules"])
    t1 = max(o[1] + o[2] for c in chips.values() for o in c["ops"])
    host = [h for h in tr["host"] if h[1] < t1 and h[1] + h[2] > t0]
    names = {o[0] for c in chips.values() for o in c["ops"]}
    out = {"module": mod.name, "kind": devs[0].device_kind,
           "n_windows": args.segments * n_win, "window_s": (t1 - t0) * 1e-9,
           "stacks": {k: [[_short(f) for f in st if "/repro/" in f]
                          for st in v]
                      for k, v in stacks.items() if k in names},
           "trace": {"chips": chips, "host": host}}
    with open(args.out, "w") as f:
        json.dump(out, f, separators=(",", ":"))
    print(json.dumps({"ops": sum(len(c["ops"]) for c in chips.values()),
                      "bytes": os.path.getsize(args.out)}))


if __name__ == "__main__":
    main()
