"""An open loop at ``rate_per_s``: independent users. The gaps are the
midpoint quantiles of an exponential law at that rate, so every stream
of one length holds the same gaps, in the order ``order`` gives."""
import numpy as np

from chipbench.loops import OpenLoop as Loop  # noqa: F401


def count(traffic, seconds):
    return max(1, int(round(traffic["rate_per_s"] * seconds)))


def offsets(traffic, n, order):
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / float(traffic["rate_per_s"])
    order.shuffle(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
