"""Share of the traced window in which the device idled while the host
was inside one of the engine's phases (an ``engine.*`` span,
``serving/scheduler.py``), in % (backlog cell, where the queue never
empties and every idle instant is the host's)."""
from chipbench import enginetrace


def read(run):
    return enginetrace.engine_idle_share(run)
