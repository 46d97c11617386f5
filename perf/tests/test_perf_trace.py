"""The trace reduction, on a trace recorded on a TPU v5e and trimmed
(``data/trace_mc021_1node.json``: the device ops of the first windows of
a traced ``mc021_1node.ground`` run, the host's annotated spans, and the
stacks of the segment program's ops), and the stack decoding on a
program compiled here.  Runs on the CPU; loads no TPU library."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
REPO = os.path.dirname(PERF)
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from perf import trace  # noqa: E402

DATA = os.path.join(HERE, "data", "trace_mc021_1node.json")


def _defs():
    out = []
    for p in sorted(os.listdir(os.path.join(PERF, "metrics"))):
        if p.endswith(".json"):
            with open(os.path.join(PERF, "metrics", p)) as f:
                out.append(json.load(f))
    return out


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(recorded):
    rules = trace.layer_rules(_defs())
    charges = trace.charge_ops(recorded["stacks"], rules)
    layers = sorted({d["layer"] for d in _defs() if d["charges"]})
    return charges, trace.reduce(recorded["trace"], charges,
                                 recorded["n_windows"], layers)


def test_no_frame_is_claimed_by_two_layers(recorded):
    rules = trace.layer_rules(_defs())
    frames = {fr for sts in recorded["stacks"].values() for st in sts
              for fr in st}
    for fr in frames:
        hits = {r[0] for r in rules if trace._frame_layer(fr, [r])}
        assert len(hits) <= 1, (fr, hits)


def test_every_op_has_one_layer(recorded, reduced):
    charges, red = reduced
    names = {op for c in recorded["trace"]["chips"].values()
             for op, _, _ in c["ops"]}
    layers = {d["layer"] for d in _defs() if d["charges"]} | {trace.OTHER}
    assert all(charges.get(op, trace.OTHER) in layers for op in names)
    # each of the recorded window's layers got device time
    assert all(red["layer_s"][k] > 0 for k in layers), red["layer_s"]


def test_layers_add_up_to_busy_time(reduced):
    _, red = reduced
    assert sum(red["layer_s"].values()) == pytest.approx(red["busy_s"],
                                                         rel=1e-12)


def test_idle_is_the_rest_of_the_window(recorded, reduced):
    _, red = reduced
    for chip in recorded["trace"]["chips"].values():
        _, busy = trace._self_times(chip["ops"])
        start = min(s for _, s, _ in chip["ops"])
        end = max(s + d for _, s, d in chip["ops"])
        gaps = sum(b0 - a1 for (_, a1), (b0, _) in zip(busy, busy[1:]))
        assert (red["busy_s"] * 1e9 + gaps) == pytest.approx(end - start)
    assert 0 < red["busy_s"] <= recorded["window_s"]


def test_self_time_charges_the_innermost_op():
    ops = [["outer", 0, 100], ["inner", 10, 20], ["late", 50, 60],
           ["alone", 200, 10]]
    self_t, busy = trace._self_times(ops)
    assert self_t == [30.0, 20.0, 60.0, 10.0]
    assert busy == [[0, 110], [200, 210]]


def test_innermost_listed_function_wins():
    rules = [("apply", "snn/simulator.py", "_apply_events"),
             ("lif", "snn/lif.py", None)]
    st = ["/x/src/repro/core/events.py:address",
          "/x/src/repro/snn/simulator.py:_apply_events",
          "/x/src/repro/snn/simulator.py:make_pipeline_fns.<locals>.body"]
    assert trace.charge([st], rules) == "apply"
    assert trace.charge([st[2:]], rules) is trace.OTHER
    assert trace.charge([["/x/src/repro/snn/lif.py:step"] + st[1:]],
                        rules) == "lif"


def _scaled_sum(x):
    return (x * 3.0).sum()


def test_stacks_come_from_the_compiled_module():
    c = jax.jit(_scaled_sum).lower(jnp.ones((8, 128))).compile()
    proto = c.runtime_executable().hlo_modules()[0] \
        .as_serialized_hlo_module_proto()
    stacks = trace.op_stacks(proto)
    named = [op for op, sts in stacks.items()
             if any(fr.endswith(":_scaled_sum") for st in sts for fr in st)]
    assert named, stacks
