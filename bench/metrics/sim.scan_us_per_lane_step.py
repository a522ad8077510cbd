"""Device time of the ladder dispatch program per lane and per access.

The dispatch program (``run_systems``, the ``lax.scan`` over the stage
composition) as the device ran it, from the trace's ``XLA Modules``
events, divided by the lanes on that chip and the accesses of a lane.
"""
from bench import trace


def read(run):
    if not run.readable:
        return None
    per = run.per_dispatch(lambda p, d: trace.program_ns(
        run.events, p, run.program, d.start, d.end))
    if not all(per):
        return None
    mean_ns = sum(per) / len(per)
    return mean_ns / 1e3 / (run.lanes_per_chip * run.seg_accesses)
