"""Model step: the decode work's share of the chip's peak under the
roofline, in percent.  For each step of the window, the least time of
the decode work of the rows that gained a token by decoding (the larger
of operations over peak FLOP/s and bytes over peak bytes/s, at their
real context lengths; bench/cost.py), summed and divided by the window's
step time.  Prefill work in mixed steps is not counted."""


def read(run):
    least = sum(run.cost.least_time(
        run.cost.decode_flops(t.decode_rows, t.decode_ctx),
        run.cost.decode_bytes(t.decode_rows, t.decode_ctx), run.peak)
        for t in run.ticks if t.decode_rows)
    busy = sum(t.t1 - t.t0 for t in run.ticks)
    if least == 0.0 or busy <= 0.0:
        return None
    return 100.0 * least / busy
