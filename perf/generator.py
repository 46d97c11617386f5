"""The one traffic generator: a mix's parameters -> the drive and the
loop a run offers the program.

A mix (``traffic/<name>.json``) states the background drive of every
population (in-degree x rate, Poisson, one stream per node), how many
windows one dispatched segment holds, and how many segments the checks
sample.  Every seed gets the same drive, the same segment length and the
same number of samples; the seed changes only the initial membrane
potentials, the Poisson streams and which segments are sampled.
"""
from __future__ import annotations

import numpy as np

from perf.reference import pd2014

# the program's init takes seed * 1000 + node into a 32-bit key
PROGRAM_SEEDS = 2_000_000


def program_seed(seed: int) -> int:
    return int(seed) % PROGRAM_SEEDS


def background(cfg: dict, traffic: dict) -> np.ndarray:
    """Per-neuron Poisson background rate [Hz]."""
    return pd2014.background_rates(cfg["network"]["scale"],
                                   traffic["bg_rate_hz"],
                                   traffic["bg_indegree"])
