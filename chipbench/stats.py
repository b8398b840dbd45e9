"""Order statistics the metrics use (numpy's linear interpolation)."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (0..100) of ``values``; None when empty."""
    v = np.asarray(values, np.float64).ravel()
    return float(np.percentile(v, q)) if v.size else None


def median(values) -> float | None:
    return percentile(values, 50.0)
