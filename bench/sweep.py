"""Find a chat cell's knee: the same cell at several fixed arrival rates.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2,3,4,5

One process, one engine, warmed up once; for each rate the cell's traffic
is generated with that rate and served for ``seconds`` (with its warm-up
traffic first), then drained.  Each rate prints one JSON line: the tails,
the step time, the tokens per second completed, and the backlog at the
close (requests due in the window and not yet admitted).  The rate a
cell runs at is fixed in its traffic file; this script only informs that
choice and is not part of a run.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import configure  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    configure()
    from bench import e2e, harness
    from bench.layout import Layout

    cell = harness.Cell(Layout(), args.workload)
    devs = harness.device_check(cell.entry["chips"])
    params = cell.params(args.seed)
    engine = cell.engine(params)
    counter = harness.CompileCounter()
    harness.warm_up(engine, cell.config, cell.traffic, cell.vocab)
    for rate in (float(r) for r in args.rates.split(",")):
        tr = copy.deepcopy(cell.traffic)
        tr["arrival"]["rate_per_s"] = rate
        sched = harness.traffic_lib.generate(tr, args.seed, args.seconds,
                                             cell.vocab,
                                             cell.layout.bench / "traffic")
        drv, end, drain_end = harness.serve(engine, sched, args.seconds,
                                            counter)
        run = harness.make_run(cell, drv, end, drain_end, 0.0,
                               devs[0].device_kind)
        backlog = sum(1 for r in run.due_in_window
                      if r.admitted is None or r.admitted > end)
        print(json.dumps({
            "rate_per_s": rate, "due": len(run.due_in_window),
            "ttft_p50_s": e2e.percentile(e2e.ttft(run), 50),
            "ttft_p75_s": e2e.percentile(e2e.ttft(run), 75),
            "ttft_p90_s": e2e.percentile(e2e.ttft(run), 90),
            "itl_p95_ms": 1e3 * e2e.percentile(e2e.token_gaps(run), 95),
            "tick_ms": 1e3 * end / max(1, len(run.ticks)),
            "output_tok_s": e2e.output_tokens(run) / end,
            "queue_wait_p50_s": e2e.percentile(e2e.queue_waits(run), 50),
            "backlog_at_close": backlog,
            "unserved": e2e.unserved(run)}), flush=True)
        while engine.pending():
            engine.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
