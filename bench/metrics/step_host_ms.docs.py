"""Engine tick in the docs cell: the reading of ``step_host_ms.chat``
(see there), which moves ``output_tok_s`` here."""
import pathlib

from bench.layout import load_module

read = load_module(pathlib.Path(__file__).with_name("step_host_ms.chat.py"),
                   "bench_metric_step_host_ms.chat").read
