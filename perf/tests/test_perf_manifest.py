"""``BENCHMARK.json`` and the files it names: the data, not the code."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
REPO = os.path.dirname(PERF)
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from perf import deploy, trace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _man():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


def test_names_and_units():
    man = _man()
    names = [m["name"] for k in ("end_to_end", "per_layer") for m in man[k]]
    names += [c["name"] for c in man["configs"]]
    names += [w["name"] for w in man["workloads"]]
    names += [w[k] for w in man["workloads"] for k in ("config", "traffic")]
    names += [k for c in man["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for kind in ("end_to_end", "per_layer", "configs", "workloads"):
        got = [m["name"] for m in man[kind]]
        assert len(got) == len(set(got)), kind
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in man["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    texts = [w["why"] for w in man["workloads"] + man["configs"]]
    texts += [c["source"] for c in man["configs"]]
    texts += [m["layer"] for m in man["per_layer"]] + man["command"]
    assert all(0 < len(x) <= 200 and "\n" not in x and "\t" not in x
               for x in texts)


def test_every_metric_is_reported_where_it_moves():
    man = _man()
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = [w["name"] for w in man["workloads"]]
    assert "setup_s" in e2e
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for cell in cells:
        c = deploy.load_cell(cell)
        e, layer = deploy.cell_metrics(c, man)
        assert "setup_s" in [x["name"] for x in e] and len(e) >= 2
        assert layer


def test_files_agree_with_the_manifest():
    man = _man()
    for c in man["configs"]:
        assert c["file"] == f"perf/configs/{c['name']}.json"
        assert _json("configs", f"{c['name']}.json")["name"] == c["name"]
    for w in man["workloads"]:
        f = _json("workloads", f"{w['name']}.json")
        for k in ("name", "config", "traffic", "chips", "why"):
            assert f[k] == w[k], (w["name"], k)
        assert os.path.exists(os.path.join(PERF, "traffic",
                                           f"{w['traffic']}.json"))
    layers = {}
    for m in man["per_layer"]:
        d = _json("metrics", f"{m['name']}.json")
        assert (d["name"], d["layer"], d["moves"]) == \
            (m["name"], m["layer"], m["moves"])
        if d["reduce"] == "roofline_share":
            assert m["unit"] == "%" and m["name"].endswith("_roofline")
            assert os.path.exists(os.path.join(PERF, "metrics",
                                               f"{m['name']}.py"))
        layers.setdefault(d["layer"], []).append(d["charges"])
    # a layer's metrics charge the same functions; no rule is in two layers
    for layer, charges in layers.items():
        assert all(c == charges[0] for c in charges), layer
    rules = [(r[1], r[2]) for r in trace.layer_rules(
        _json("metrics", f"{m['name']}.json") for m in man["per_layer"])]
    assert len(rules) == len(set(rules))


def test_new_workload_is_found_without_code(tmp_path):
    root = tmp_path / "perf"
    for d in ("configs", "traffic", "workloads", "metrics"):
        shutil.copytree(os.path.join(PERF, d), root / d)
    man = _man()
    man["workloads"].append({"name": "mc021_1node.later", "config":
                             "mc021_1node", "traffic": "later", "chips": 1,
                             "why": "a later mix"})
    for m in man["per_layer"]:
        if m["name"] != "transport_ms":
            m["workloads"].append("mc021_1node.later")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    mix = _json("traffic", "ground.json")
    mix.update(name="later", bg_rate_hz=10.0)
    (root / "traffic" / "later.json").write_text(json.dumps(mix))
    wl = _json("workloads", "mc021_1node.ground.json")
    wl.update(name="mc021_1node.later", traffic="later")
    (root / "workloads" / "mc021_1node.later.json").write_text(json.dumps(wl))
    cell = deploy.load_cell("mc021_1node.later", str(root))
    assert cell["traffic"]["bg_rate_hz"] == 10.0
    e2e, layer = deploy.cell_metrics(cell, deploy.manifest(str(root)))
    assert {m["name"] for m in e2e} == {"rtf", "segment_p95_ms", "setup_s"}
    assert "transport_ms" not in {m["name"] for m in layer}
    assert "apply_ms" in {m["name"] for m in layer}


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(PERF, "harness.py"), "--workload",
         "mc021_1node.ground", "--seed", "3", "--seconds", "1", "--trace",
         "0"], env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
