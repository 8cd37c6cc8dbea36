"""Order statistics of the benchmark.

``percentile`` is the nearest-rank percentile of ``repro.serve.metrics``,
copied here so that the yardstick cannot move with the program.
"""

from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sample."""
    if not xs:
        raise ValueError("percentile of an empty sample")
    s = sorted(xs)
    k = max(0, math.ceil(q / 100.0 * len(s)) - 1)
    return float(s[k])
