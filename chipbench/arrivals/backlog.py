"""A queue that never empties: ``backlog_requests`` requests, all queued
before warm-up, as offline generation queues them."""
import numpy as np

from chipbench.loops import Backlog as Loop  # noqa: F401


def count(traffic, seconds):
    return int(traffic["backlog_requests"])


def offsets(traffic, n, order):
    return np.zeros(n)
