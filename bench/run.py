"""Benchmark entry point: one run of one cell on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, traffic
and metrics are found by name from ``BENCHMARK.json``.  The last line of
stdout is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``check``: each number compared with its limit); the same numbers close
stderr.  Without a TPU, or with fewer chips than the cell asks for, the
run exits 2 and prints no result.

JAX's persistent compilation cache lives at ``<checkout>/.bench_cache/jax``
whatever the environment says, so only a checkout's first run of a cell
compiles.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
CACHE = CHECKOUT / ".bench_cache" / "jax"


def configure() -> None:
    """Import paths and JAX's compilation cache, before JAX starts."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    configure()
    from bench import harness
    from bench.layout import Layout

    try:
        out = harness.run_cell(Layout(), args.workload, args.seed,
                               args.seconds, bool(args.trace),
                               t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, n in out["check"].items():
        print(f"check {name}: {n['value']} (limit {n['limit']})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
