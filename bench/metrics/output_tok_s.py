"""Output tokens emitted in the window over the window's seconds.  Host
clock."""
from bench import e2e


def read(run):
    return e2e.output_tokens(run) / run.window_s
