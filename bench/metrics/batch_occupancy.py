"""Scheduler: mean over the window's steps of the rows that gained a
token, as a share of the engine's rows, in percent."""


def read(run):
    if not run.ticks:
        return None
    gained = sum(t.decode_rows + t.first_tokens for t in run.ticks)
    return 100.0 * gained / (len(run.ticks) * run.rows)
