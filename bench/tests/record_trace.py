"""Record the small profiler trace that bench/tests/test_bench_xplane.py
reads: the toy dense cell of bench/tests/tiny.py served for a fraction of
a second on the chip, traced from its warm-up traffic on.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Needs a TPU; the trace holds the chip's operation lines and the
harness's ``bench.*`` host spans.
"""
import pathlib
import shutil
import sys
import tempfile

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[2]),
                str(pathlib.Path(__file__).resolve().parents[2] / "src")]


def main() -> int:
    from bench import harness
    from bench.tests import tiny

    out = pathlib.Path(sys.argv[1])
    with tempfile.TemporaryDirectory() as root:
        cell = harness.Cell(tiny.write(root), "tiny.chat")
        harness.device_check(1)
        params = cell.params(5)
        sched = cell.schedule(5, 0.2)
        sched.warmup_s = 0.05
        engine = cell.engine(params)
        harness.warm_up(engine, cell.config, cell.traffic, cell.vocab)
        trace = tempfile.mkdtemp()
        harness.serve(engine, sched, 0.2, harness.CompileCounter(), trace,
                      trace_s=0.25)
        files = sorted(pathlib.Path(trace).rglob("*.xplane.pb"))
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(files[0], out)
        shutil.rmtree(trace)
    print(f"{out}: {out.stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
