"""The Potjans-Diesmann cortical microcircuit, written out plainly.

Published numbers: Potjans & Diesmann (2014), "The cell-type specific
cortical microcircuit", Cereb. Cortex 24:785, Table 5 (population sizes,
connection probabilities, background in-degrees) and the neuron model
of its Table 4 (current-based LIF with exponential synaptic currents,
NEST's ``iaf_psc_exp``).

``weights`` draws the network the deployments run: at ``scale`` every
population keeps ``max(int(size * scale), 4)`` neurons, every connection
keeps its probability, and weights are not rescaled.  The draw order is
part of the deployment's definition: one ``numpy.random.default_rng(seed)``
stream, source population outer, target population inner, skipping
pairs with probability 0; per pair one uniform block for the Bernoulli
mask and one normal block for the weights.
"""
from __future__ import annotations

import numpy as np

POPULATIONS = ("L23E", "L23I", "L4E", "L4I", "L5E", "L5I", "L6E", "L6I")
FULL_SIZES = np.array([20683, 5834, 21915, 5479, 4850, 1065, 14395, 2948])
# connection probability [target, source]
CONN_PROB = np.array([
    [0.1009, 0.1689, 0.0437, 0.0818, 0.0323, 0.0000, 0.0076, 0.0000],
    [0.1346, 0.1371, 0.0316, 0.0515, 0.0755, 0.0000, 0.0042, 0.0000],
    [0.0077, 0.0059, 0.0497, 0.1350, 0.0067, 0.0003, 0.0453, 0.0000],
    [0.0691, 0.0029, 0.0794, 0.1597, 0.0033, 0.0000, 0.1057, 0.0000],
    [0.1004, 0.0622, 0.0505, 0.0057, 0.0831, 0.3726, 0.0204, 0.0000],
    [0.0548, 0.0269, 0.0257, 0.0022, 0.0600, 0.3158, 0.0086, 0.0000],
    [0.0156, 0.0066, 0.0211, 0.0166, 0.0572, 0.0197, 0.0396, 0.2252],
    [0.0364, 0.0010, 0.0034, 0.0005, 0.0277, 0.0080, 0.0658, 0.1443],
])
BG_INDEGREE = np.array([1600, 1500, 2100, 1900, 2000, 1900, 2900, 2100])
W_EXC_PA = 87.8          # mean excitatory PSC amplitude
W_REL_SD = 0.1           # relative standard deviation of weights
G_INH = -4.0             # inhibitory / excitatory weight ratio
L4E_TO_L23E = 2.0        # the doubled L4E -> L23E projection


def sizes(scale: float) -> np.ndarray:
    return np.maximum((FULL_SIZES * scale).astype(int), 4)


def weights(scale: float, seed: int):
    """Dense (N, N) f32 weights [pA], [target, source], and the
    inhibitory-source flags (N,)."""
    n_of = sizes(scale)
    off = np.concatenate([[0], np.cumsum(n_of)])
    n = int(off[-1])
    rng = np.random.default_rng(seed)
    w = np.zeros((n, n), np.float32)
    inh = np.zeros((n,), bool)
    for j, name in enumerate(POPULATIONS):
        src_inh = name.endswith("I")
        inh[off[j]:off[j + 1]] = src_inh
        for i in range(len(POPULATIONS)):
            p = CONN_PROB[i, j]
            if p <= 0:
                continue
            shape = (n_of[i], n_of[j])
            mask = rng.random(shape) < p
            mean = W_EXC_PA * (G_INH if src_inh else 1.0)
            if (i, j) == (0, 2):
                mean *= L4E_TO_L23E
            ww = rng.normal(mean, abs(mean) * W_REL_SD, shape).astype(np.float32)
            w[off[i]:off[i + 1], off[j]:off[j + 1]] = np.where(mask, ww, 0.0)
    return w, inh


def background_rates(scale: float, rate_hz: float, indegree=BG_INDEGREE):
    """Per-neuron Poisson background rate [Hz]: in-degree x rate."""
    return np.repeat(np.asarray(indegree) * rate_hz, sizes(scale)).astype(np.float32)
