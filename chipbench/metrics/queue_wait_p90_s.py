"""90th percentile of the wait from due to admission, over the window's
requests, in s (engine admission: ``serving/scheduler.py``,
``serving/queue.py``). The due time is the harness's; the admission
stamp is the engine's ``RequestRecord.admit``."""
from chipbench import records


def read(run):
    return records.queue_wait_percentile(run, 90)
