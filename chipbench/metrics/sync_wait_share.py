"""Share of the traced window the host spent inside
``engine.materialize``, the ``block_until_ready`` wait of a dispatch on
which a request completes (``serving/scheduler.py``), in %. While the
host waits there, no request is submitted or admitted."""
from chipbench import enginetrace


def read(run):
    eng = enginetrace.engine_of(run)
    if eng is None:
        return None
    return 100.0 * eng["sync_wait_s"] / run["trace"]["window_s"]
