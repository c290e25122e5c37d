"""``mfu``'s reading in the sd35-medium cell, in %: the needed model work
of the window's request-steps, as ``configs/sd35_work.py`` counts it
(both streams, joint and image-only attention, CFG counted), over window
seconds times chips times the device's bf16 peak (packed step:
``pipeline/packed.py``, ``core/packing.py``)."""
from chipbench import spec

read = spec.metric_reader("mfu")
