"""Median time to first token over every request due in the window, from
its due time.  Host clock.  At the chat cells' rate about 41 requests are
due in a window, and a sixth of the prompts take a second 512-token
prefill chunk, so the upper percentiles sit where one chunk gives way to
two and swing by a whole engine step from run to run; the median does
not."""
from bench import e2e


def read(run):
    return e2e.percentile(e2e.ttft(run), 50)
