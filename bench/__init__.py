"""On-chip serving benchmark of the HashedNets serving stack.

Entry point: ``python3 bench/run.py`` (see ``bench/run.py``).  The cells,
configurations and metrics are named in ``BENCHMARK.json`` at the
checkout's root; ``PERF.md`` says why each exists.
"""
