"""A traffic mix names its drive: a module under ``drives/`` that gives
the program its inputs and the reference its window.

On the CPU-sized copy of the one-node cell: the background drive hands
both sides what the Table 5 drive was before drives existed; a drive the
test writes itself is run and checked like the background; a drive
whose two sides disagree comes out not correct; a name with no module
fails before the program is built or a device is touched.
"""
import json
import os
import re
import sys
import textwrap
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from perf import deploy, harness  # noqa: E402
from perf.reference import pd2014, segment  # noqa: E402
from perf.tests import tinycell  # noqa: E402

SECONDS = 0.3

# the background at twice its rate, written out as a drive of its own;
# with ``reference_factor=1.0`` the reference gets the plain background
DOUBLE = textwrap.dedent('''
    import numpy as np
    from perf.reference import pd2014, segment

    def _rates(cell, factor):
        mix = cell["traffic"]
        return factor * pd2014.background_rates(
            cell["config"]["network"]["scale"], mix["bg_rate_hz"],
            mix["bg_indegree"])

    def program_inputs(cell, part):
        return {{"bg_rates": _rates(cell, 2.0),
                "bg_weight": cell["config"]["network"]["bg_weight_pa"]}}

    def reference_window(cell, rnet):
        cfg = cell["config"]
        bg = np.pad(_rates(cell, {reference_factor}),
                    (0, rnet.n - cfg["network"]["neurons"]))
        return segment.make_window(cfg["lif"], bg,
                                   cfg["network"]["bg_weight_pa"],
                                   cfg["fabric"]["n_shards"],
                                   cfg["fabric"]["window"])
''')


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell.make(tmp_path_factory.mktemp("bench"))


def _add_cell(root, drive: str, source: str | None = None) -> str:
    """A mix naming ``drive`` (its module written from ``source`` when
    given) and the cell ``tiny1.<drive>`` under it; returns the cell."""
    if source is not None:
        with open(os.path.join(root, "drives", f"{drive}.py"), "w") as f:
            f.write(source)
    with open(os.path.join(root, "traffic", "ground.json")) as f:
        mix = json.load(f)
    mix.update(name=drive, drive=drive)
    with open(os.path.join(root, "traffic", f"{drive}.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "workloads", "tiny1.ground.json")) as f:
        wl = json.load(f)
    wl.update(name=f"tiny1.{drive}", traffic=drive)
    with open(os.path.join(root, "workloads", f"tiny1.{drive}.json"),
              "w") as f:
        json.dump(wl, f)
    return wl["name"]


def _run(root, workload, seed=7):
    res, _ = harness.run(workload, seed, SECONDS, False, root=root,
                         require_tpu=False)
    return res


def test_background_inputs_are_the_table5_rates(root):
    cell = deploy.load_cell("tiny1.ground", root)
    assert "drive" not in cell["traffic"]
    drive = harness.drive(cell)
    assert drive.__file__ == os.path.join(root, "drives", "background.py")
    got = drive.program_inputs(cell, None)
    mix, net = cell["traffic"], cell["config"]["network"]
    want = pd2014.background_rates(net["scale"], mix["bg_rate_hz"],
                                   mix["bg_indegree"])
    assert sorted(got) == ["bg_rates", "bg_weight"]
    assert got["bg_rates"].dtype == want.dtype
    assert got["bg_rates"].tobytes() == want.tobytes()
    assert got["bg_weight"] == net["bg_weight_pa"]


def test_background_window_is_make_window(root):
    cell = deploy.load_cell("tiny1.ground", root)
    cfg, mix = cell["config"], cell["traffic"]
    net, fab = cfg["network"], cfg["fabric"]
    n_shards, n = fab["n_shards"], net["neurons"] + 1
    rnet = types.SimpleNamespace(n=n)
    window_fn = harness.drive(cell).reference_window(cell, rnet)
    bg = np.pad(pd2014.background_rates(net["scale"], mix["bg_rate_hz"],
                                        mix["bg_indegree"]),
                (0, n - net["neurons"]))
    want_fn = segment.make_window(cfg["lif"], bg, net["bg_weight_pa"],
                                  n_shards, fab["window"])
    rng = np.random.default_rng(3)
    n_ring = fab["ring_len"]
    state = {"v": rng.uniform(-65.0, -50.0, n).astype(np.float32),
             "i_exc": rng.uniform(0, 300, n).astype(np.float32),
             "i_inh": rng.uniform(-300, 0, n).astype(np.float32),
             "refrac": rng.integers(0, 3, n).astype(np.int32),
             "ring_exc": rng.uniform(0, 100, (n_ring, n)).astype(np.float32),
             "ring_inh": np.zeros((n_ring, n), np.float32),
             "t": np.int32(5),
             "key": np.arange(2 * n_shards, dtype=np.uint32)
             .reshape(n_shards, 2)}
    w = (rng.random((n, n)) < 0.05).astype(np.float32) * 87.8
    arrivals = (rng.random((2, n_ring, n)) < 0.01).astype(np.float32)
    got, got_spikes = window_fn(state, w, arrivals)
    want, want_spikes = want_fn(state, w, arrivals)
    assert np.asarray(want_spikes).any()
    np.testing.assert_array_equal(np.asarray(got_spikes),
                                  np.asarray(want_spikes))
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                                      err_msg=k)


def test_own_drive_is_run_and_checked(root):
    workload = _add_cell(root, "double", DOUBLE.format(reference_factor=2.0))
    res = _run(root, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    ground = _run(root, "tiny1.ground")
    assert ground["correct"], ground["checks"]
    # on one node every spike that reaches a synapse offers one event
    per_segment = lambda r: r["attempted"] / r["segments"]
    assert per_segment(res) > 1.5 * per_segment(ground)


def test_mismatched_drive_is_not_correct(root):
    workload = _add_cell(root, "mismatch",
                         DOUBLE.format(reference_factor=1.0))
    res = _run(root, workload)
    assert not res["correct"], res["checks"]


def test_unknown_drive_fails_before_build(root, monkeypatch):
    workload = _add_cell(root, "no_such_drive")

    def touched(*a, **k):
        raise AssertionError("built or touched a device for a missing drive")

    build = harness.build
    monkeypatch.setattr(harness, "_devices", touched)
    monkeypatch.setattr(harness, "build", touched)
    monkeypatch.setattr(deploy, "partition", touched)
    path = re.escape(os.path.join(root, "drives", "no_such_drive.py"))
    with pytest.raises(FileNotFoundError, match=path):
        _run(root, workload)
    with pytest.raises(FileNotFoundError, match=path):
        build(deploy.load_cell(workload, root), [])
