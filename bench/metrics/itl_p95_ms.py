"""95th percentile of every gap between consecutive output tokens of
every request, taken over all gaps that end in the window.  Host clock."""
from bench import e2e


def read(run):
    p = e2e.percentile(e2e.token_gaps(run), 95)
    return None if p is None else p * 1e3
