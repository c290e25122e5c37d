"""Packing efficiency of the window's packed steps, in % (engine
packing: ``serving/batcher.py``, ``core/packing.py``)."""
from chipbench import records


def read(run):
    return records.packing_efficiency(run)
