"""Host time of unpacking one ladder dispatch's outputs into per-lane
Stats and extras: the program's ``sim.unpack`` span
(``mmu.make_systems_runner``'s ``run``), summed inside each traced
dispatch and averaged over the dispatches.  The span reaches the trace
as a profiler annotation on the dispatching thread.  Nothing to read
where the program has no such span."""

SPAN = "sim.unpack"


def read(run):
    if not run.readable:
        return None
    per = []
    for d in run.dispatches:
        inner = [e.dur for e in run.events if e.name == SPAN
                 and e.plane == d.plane and e.line == d.line
                 and d.start <= e.start and e.end <= d.end]
        if not inner:
            return None
        per.append(sum(inner))
    return sum(per) / len(per) / 1e6
