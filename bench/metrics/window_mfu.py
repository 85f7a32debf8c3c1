"""Model step: model operations of every prompt prefilled (its first
token came in the window) and every token decoded in the window, over
the window's seconds times the chip's peak bf16 FLOP/s, in percent
(bench/cost.py: 2 x virtual matrix parameters per token plus attention
at real context)."""


def read(run):
    c = run.cost
    flops = sum(c.decode_flops(t.decode_rows, t.decode_ctx)
                for t in run.ticks)
    flops += sum(c.prefill_flops(r.prompt_len) for r in run.recs
                 if r.tokens and run.in_window(r.tokens[0]))
    if flops == 0:
        return None
    return 100.0 * flops / (run.window_s * run.peak["bf16_flops_per_s"])
