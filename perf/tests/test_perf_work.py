"""The work counts behind the roofline shares: a hand count at a tiny
size, independence from the program's buffer sizes, and the table of
peaks."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
REPO = os.path.dirname(PERF)
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from perf import harness, trace  # noqa: E402


def _ctx(capacity=4096, e_max=4096):
    # 2 windows on 2 nodes
    stats = {"delivered": np.array([[3, 1], [0, 2]]),
             "offered": np.array([[4, 2], [1, 1]]),
             "sent": np.array([[4, 2], [1, 0]])}
    cfg = {"fabric": {"capacity": capacity, "e_max": e_max}}
    return {"stats": stats, "network": {"syn_per_event": np.array([10.0, 5.0])},
            "config": cfg}


def _work(name, ctx):
    return harness._load_reader(PERF, "metrics", name).work(ctx)


def test_hand_counts():
    ctx = _ctx()
    # apply: synapses = 3*10 + 1*5 + 0*10 + 2*5 = 45 over 4 chip-windows;
    # 16 B per synapse + 8 B per event (6 events)
    w = _work("apply_roofline", ctx)
    assert w["flops"] == pytest.approx(45 / 4)
    assert w["bytes"] == pytest.approx((16 * 45 + 8 * 6) / 4)
    # route + aggregate: 12 B per offered (8) + 8 B per placed (7)
    w = _work("route_aggregate_roofline", ctx)
    assert w["bytes"] == pytest.approx((12 * 8 + 8 * 7) / 4)
    # codec: 16 B per shipped (7) and per delivered (6) event
    w = _work("codec_roofline", ctx)
    assert w["bytes"] == pytest.approx(16 * 13 / 4)


@pytest.mark.parametrize("name", ["apply_roofline", "codec_roofline",
                                  "route_aggregate_roofline"])
def test_count_ignores_buffer_sizes(name):
    assert _work(name, _ctx(4096, 4096)) == _work(name, _ctx(1024, 256))


def test_synapses_per_event_by_hand():
    class Part:
        n_shards, per_shard = 2, 2
        # [target, source]: source 0 reaches node 0 twice, node 1 once;
        # source 3 reaches node 1 once; sources 1, 2 reach nothing
        weights = np.array([[1., 0, 0, 0], [2, 0, 0, 0],
                            [0, 0, 0, 5], [3, 0, 0, 0]], np.float32)

    got = harness.network_counts(Part)["syn_per_event"]
    np.testing.assert_allclose(got, [2.0, 1.0])


def test_unknown_device_kind_is_an_error():
    path = os.path.join(PERF, "peaks.json")
    assert trace.chip_peaks("TPU v5 lite", path)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace.chip_peaks("TPU v99", path)


def test_every_peak_has_its_source():
    with open(os.path.join(PERF, "peaks.json")) as f:
        for kind, p in json.load(f).items():
            assert p["source"] and p["bf16_flops_per_s"] > 0 \
                and p["hbm_bytes_per_s"] > 0, kind
