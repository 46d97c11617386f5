"""A copy of the benchmark's files with one CPU-sized cell added:
the same deployment at scale 0.01 (767 neurons), for tests."""
from __future__ import annotations

import json
import os
import shutil

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(PERF)


def make(tmp, nodes: int = 1) -> str:
    """Write the copy under ``tmp``; return its benchmark directory.
    The cell is ``tiny<nodes>.ground``; its apply runs in float32, which
    is what an f32 einsum computes on the CPU."""
    root = os.path.join(str(tmp), "perf")
    if not os.path.exists(root):
        os.makedirs(root)
        for d in ("metrics", "traffic", "workloads", "configs",
                  "drives"):
            shutil.copytree(os.path.join(PERF, d), os.path.join(root, d))
        shutil.copy(os.path.join(PERF, "peaks.json"), root)
        shutil.copy(os.path.join(REPO, "BENCHMARK.json"), str(tmp))
    base = "mc021_1node" if nodes == 1 else "mc021_4node_torus3d"
    with open(os.path.join(root, "configs", f"{base}.json")) as f:
        cfg = json.load(f)
    cfg["name"] = f"tiny{nodes}"
    cfg["network"].update(scale=0.01, neurons=767)
    cfg["fabric"].update(e_max=256, capacity=256, residue=64)
    if cfg["fabric"]["link_credits"]:
        cfg["fabric"]["link_credits"] = 2 * 256 * 3
    cfg["apply_precision"] = "float32"
    with open(os.path.join(root, "configs", f"tiny{nodes}.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "workloads", f"{base}.ground.json")) as f:
        wl = json.load(f)
    wl.update(name=f"tiny{nodes}.ground", config=f"tiny{nodes}")
    with open(os.path.join(root, "workloads",
                           f"tiny{nodes}.ground.json"), "w") as f:
        json.dump(wl, f)
    return root
