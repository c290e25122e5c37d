"""``flash_attention_roofline``'s reading in the sd35-medium cell, in %:
the least time of the window's needed joint and image-only attention
work (``configs/sd35_work.py``: each request's [image; text] tokens, and
its image tokens alone in the dual blocks) over the summed device time of
the trace's ``flash_attention`` ops (attention kernel:
``kernels/attention/flash_attention.py``)."""
from chipbench import spec

read = spec.metric_reader("flash_attention_roofline")
