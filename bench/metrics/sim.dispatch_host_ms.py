"""Host time of one ladder dispatch: the call's wall time less the
device's busy time inside it (placing the traces, fetching and
unpacking the per-lane outputs), averaged over the traced dispatches."""
from bench import trace


def read(run):
    if not run.readable:
        return None
    host = run.per_dispatch(lambda p, d: d.dur - trace.busy_ns(
        run.events, p, d.start, d.end))
    return sum(host) / len(host) / 1e6
