"""Scheduler: median, over requests due in the window, of due time to
the end of the step that admitted the request (its status left "queued";
its first prefill chunk runs in that step).  Host clock."""
from bench import e2e


def read(run):
    return e2e.percentile(e2e.queue_waits(run), 50)
