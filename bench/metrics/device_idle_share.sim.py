"""Share of the traced dispatch window in which no operation ran on
the device: 1 - busy / window, in percent, averaged over the chips."""


def read(run):
    if not run.readable:
        return None
    lo, hi = run.window
    return 100.0 * (1.0 - run.busy_ns() / (hi - lo))
