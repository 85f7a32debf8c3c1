"""Engine tick: the window's time over the number of ``step()`` calls in
it, in milliseconds.  Host clock; every step ends in a host sync."""


def read(run):
    if not run.ticks:
        return None
    return 1e3 * run.window_s / len(run.ticks)
