"""Packing efficiency of the sd35-medium cell's packed steps, in %: real
tokens, image and text, over the tokens of the rows they were packed into
(engine packing: ``serving/batcher.py``, ``core/packing.py``)."""
from chipbench import spec

read = spec.metric_reader("packing_eff.backlog")
