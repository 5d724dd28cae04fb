"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state — the 512-placeholder-device
XLA_FLAGS dance happens only inside ``dryrun.py``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(axes) -> tuple:
    # jax.make_mesh defaults to explicit-sharding axes; the model code
    # shards through NamedSharding constraints, which need Auto axes
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ("data", "model") = 256 chips.
    Multi-pod:  (2, 16, 16) ("pod", "data", "model") = 512 chips."""
    import numpy as np
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"need {need} devices, have {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            f"(dryrun.py sets this automatically)")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=_auto(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use e.g. (2, 4) on 8 host devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(axes))


# v5e-class hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
