"""Arithmetic over a run's records that more than one metric reader
uses. A reader returns None where the run holds nothing to read."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np


def packing_efficiency(run: Dict) -> Optional[float]:
    """Real segment tokens over the tokens the packed steps computed
    (rows times row capacity, dummy slots included), in %."""
    real = sum(r for r, _p in run["steps"])
    packed = sum(p for _r, p in run["steps"])
    return 100.0 * real / packed if packed else None


def queue_wait_percentile(run: Dict, q: float) -> Optional[float]:
    """From when a request was due to when the engine admitted it."""
    waits = [r["admit"] - r["due"] for r in run["requests"]
             if r["admit"] is not None]
    return float(np.percentile(waits, q)) if waits else None
