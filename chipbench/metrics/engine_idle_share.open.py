"""Share of the traced window in which the device idled while the host
was inside one of the engine's phases (an ``engine.*`` span,
``serving/scheduler.py``), in %: what the engine's host path, not the
wait for arrivals, leaves the device idle for (open-loop cell)."""
from chipbench import enginetrace


def read(run):
    return enginetrace.engine_idle_share(run)
