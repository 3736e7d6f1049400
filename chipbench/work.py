"""The work one sweep has to do, from a configuration alone.

Every spin is read once and written once: ``2 x sites x itemsize`` bytes of
HBM traffic per sweep. Random numbers are not counted, since they can be
made on the chip without touching HBM. The count is the same whatever
implements the sweep, so a roofline share built on it moves only with time.
Fields that choose an implementation (``backend``, ``pipeline``) are not
read.
"""
from __future__ import annotations

import math

import numpy as np
import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)


def sites(engine: dict) -> int:
    """Global lattice sites of an engine configuration: a size^3 cube in
    3-D, a size x width torus (width 0 or absent: square) in 2-D."""
    if engine.get("dims", 2) == 3:
        return engine["size"] ** 3
    return engine["size"] * (engine.get("width") or engine["size"])


def chips(engine: dict) -> int:
    return math.prod(engine.get("mesh_shape") or (1,))


def sweep_bytes_per_chip(engine: dict) -> int:
    """HBM bytes one sweep must move on each chip of the configuration."""
    itemsize = np.dtype(engine.get("dtype", "bfloat16")).itemsize
    return 2 * sites(engine) * itemsize // chips(engine)
