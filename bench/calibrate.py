"""Readings that set a cell's correctness limit: the program's widest
logit gap, and the float8 control's, over many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--control-seeds 1,2]

For each seed, as a run of the cell does it: weights and traffic from
that seed, an engine, its warm-up, ``seconds`` of window at the cell's
own load; then, with the engine freed, the check of bench/check.py on
the served sample, and for a control seed also the float8 reference put
in the program's place on the same prompts and tokens.  One JSON line
per seed.  Not part of a run: PERF.md records the
readings and the limit set from them.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import configure  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    configure()
    from bench import check, harness
    from bench.layout import Layout

    cell = harness.Cell(Layout(), args.workload)
    devs = harness.device_check(cell.entry["chips"])
    control = {int(s) for s in args.control_seeds.split(",") if s}
    counter = harness.CompileCounter()
    length = int(cell.config["engine"]["max_len"])
    for seed in (int(s) for s in args.seeds.split(",")):
        params = cell.params(seed)
        sched = cell.schedule(seed, args.seconds)
        engine = cell.engine(params)
        harness.warm_up(engine, cell.config, cell.traffic, cell.vocab)
        drv, end, drain_end = harness.serve(engine, sched, args.seconds,
                                            counter)
        run = harness.make_run(cell, drv, end, drain_end, 0.0,
                               devs[0].device_kind)
        engine = drv.eng = None
        gc.collect()
        picked = check.sample(run.recs, seed, end)
        out = {"seed": seed, "requests": len(picked)}
        for mode in ("program", "control") if seed in control \
                else ("program",):
            widest, n = check.widest_gap(params, cell.banks, cell.config,
                                         picked, length, mode)
            out[f"{mode}_gap"] = widest
            out["tokens"] = n
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
