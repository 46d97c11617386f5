"""Production mesh construction.

The physical analogy is direct: a TPU v5e pod's ICI is a torus exactly like
the paper's Extoll fabric; ``("data", "model")`` maps DP/FSDP onto long
torus dimensions and TP onto the short ones, and the ``pod`` axis is the
inter-pod DCN — the BrainScaleS wafer-to-wafer hop (paper Fig. 1).

The spike fabric runs on a 1-D ``"wafer"`` axis
(:func:`make_wafer_mesh`); how a flush window crosses it is the
*transport* choice (``repro.transport``): ``"alltoall"`` treats the axis
as a crossbar (one global collective), ``"torus2d"`` / ``"torus3d"`` fold
it onto (nx, ny[, nz]) rings (:func:`wafer_torus_shape`) and ship
neighbor ``ppermute`` hops with hop-by-hop credit-based link flow
control — the same coordinates ``core.torus`` reasons about on the host
(``torus3d``'s Z rings are the wafer-stacking axis).

NOTE: functions, not module constants — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple, devices=None):
    """The one mesh constructor: the first ``prod(shape)`` devices (or
    ``devices``) on named axes of type ``Auto``.

    The axis types are spelled out because ``jax.make_mesh``'s default
    changed to ``Explicit``, under which host-side integer indexing of a
    sharded array and ``with_sharding_constraint`` both raise.
    """
    n = math.prod(shape)
    if devices is None:
        devices = jax.devices()[:n]
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_wafer_mesh(n_shards: int, axis: str = "wafer", devices=None):
    """1-D mesh for the spike-exchange fabric (one device per shard)."""
    return make_mesh((n_shards,), (axis,), devices)


def wafer_torus_shape(n_shards: int, ndim: int = 2) -> tuple:
    """The rings a torus transport folds ``n_shards`` onto.

    ``ndim=2``: most-square (nx, ny); 8 shards -> (2, 4), the paper's
    per-wafer concentrator face.  ``ndim=3``: most-cubic (nx, ny, nz);
    8 shards -> (2, 2, 2).  Wafer-stacked deployments that want the
    paper's (2, 4, n_wafers) arrangement pass the shape explicitly via
    ``torus_nx``/``ny``/``nz`` instead.
    """
    from repro.transport.torus import default_shape, default_shape3d
    if ndim == 3:
        return default_shape3d(n_shards)
    return default_shape(n_shards)


def wafer_wire_format(profile: str = "extoll"):
    """The wire protocol profile of the wafer fabric's links.

    The physical analogy again: the ICI torus is the Extoll fabric
    (``"extoll"``: 64-byte cells, ~16 B/frame tax, sub-µs cut-through
    hops), the DCN pod hop is the commodity comparison (``"ethernet"``:
    full Eth+IP+UDP stack, minimum frames, store-and-forward switches).
    Returns the :class:`repro.wire.framing.WireFormat` used by the
    transports' frame-exact ``bytes_on_wire`` accounting and the
    per-event latency model.
    """
    from repro.wire import get_profile
    return get_profile(profile)
