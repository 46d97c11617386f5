"""The one traffic generator: a mix's parameters -> the drive and the
loop a run offers the program.

A mix (``traffic/<name>.json``) names its drive (``"drive"``, a module
under ``drives/``; ``background`` where it names none) with that drive's
parameters, and states how many windows one dispatched segment holds,
how many segments warm up, and how many the checks sample and the trace
covers.  Every seed gets the same drive, the same segment length and the
same number of samples; the seed changes only the initial membrane
potentials, the Poisson streams and which segments are sampled.
"""
from __future__ import annotations

# the program's init takes seed * 1000 + node into a 32-bit key
PROGRAM_SEEDS = 2_000_000


def program_seed(seed: int) -> int:
    return int(seed) % PROGRAM_SEEDS
