"""The ground-state drive: an independent Poisson background onto every
neuron at in-degree x rate (Potjans & Diesmann 2014, Table 5), the mix's
``bg_rate_hz`` and ``bg_indegree``, each input weighted by the
deployment's ``bg_weight_pa``.  Each node draws its own Poisson stream
from the PRNG key in its state, so the reference reproduces the drive
from any sampled segment's start."""
from __future__ import annotations

import numpy as np

from perf.reference import pd2014, segment


def rates(cell: dict) -> np.ndarray:
    """Per-neuron Poisson background rate [Hz]."""
    mix = cell["traffic"]
    return pd2014.background_rates(cell["config"]["network"]["scale"],
                                   mix["bg_rate_hz"], mix["bg_indegree"])


def program_inputs(cell: dict, part) -> dict:
    return {"bg_rates": rates(cell),
            "bg_weight": cell["config"]["network"]["bg_weight_pa"]}


def reference_window(cell: dict, rnet: segment.Network):
    cfg = cell["config"]
    net, fab = cfg["network"], cfg["fabric"]
    bg = np.pad(rates(cell), (0, rnet.n - net["neurons"]))
    return segment.make_window(cfg["lif"], bg, net["bg_weight_pa"],
                               fab["n_shards"], fab["window"])
