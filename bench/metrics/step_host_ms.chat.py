"""Engine tick: mean host milliseconds of the window's engine steps, from
the program's own step account (``Request.steps``, `repro.obs.trace`):
each step's time less the time it spent blocked on device results.

The steps are those of the window's requests, each counted once, put on
the window's clock by the offset of the engine's ``time.monotonic`` from
it (the median, over accepted requests, of the engine's submit mark less
the harness's send time: both are stamped microseconds apart).  A step
counts if it lies inside the window, the rule ``run.ticks`` follows.
None where the program keeps no such account."""
import statistics


def read(run):
    recs = [r for r in run.recs if r.accepted and r.req is not None
            and getattr(r.req, "submit_mono", None) is not None]
    steps = {id(s): s for r in recs for s in getattr(r.req, "steps", ())}
    if not steps:
        return None
    origin = statistics.median(r.req.submit_mono - r.sent for r in recs)
    host = [s.host_s for s in steps.values()
            if s.t0 - origin >= 0.0 and s.t1 - origin <= run.window_s]
    if not host:
        return None
    return 1e3 * sum(host) / len(host)
