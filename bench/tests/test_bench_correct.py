"""The comparison that decides ``correct`` has to fail what is wrong: the
float8 control in the program's place, and a run whose served tokens are
altered where they are produced.  CPU, toy widths (bench/tests/tiny.py,
whose limit, 0.08, lies between the program's widest gap, at most 0.026,
and the control's, at least 0.217, over three seeds of each toy cell).
"""
import time

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    return tiny.write(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("name", ["tiny.chat", "tinyh.docs"])
def test_program_passes_and_float8_control_fails(layout, name):
    cell = harness.Cell(layout, name)
    params = cell.params(21)
    sched = cell.schedule(21, 2.0)
    eng = cell.engine(params)
    harness.warm_up(eng, cell.config, cell.traffic, cell.vocab)
    drv, end, drain = harness.serve(eng, sched, 2.0, harness.CompileCounter())
    run = harness.make_run(cell, drv, end, drain, 0.0, "TPU v5 lite")
    numbers, ok = harness.judge(cell, params, run, 21)
    assert ok, numbers
    numbers, ok = harness.judge(cell, params, run, 21, mode="control")
    assert not ok
    assert numbers["logit_gap"]["value"] > numbers["logit_gap"]["limit"]


def test_a_token_altered_where_it_is_produced_fails(layout, monkeypatch):
    """The whole run, the harness's look for a chip skipped, with every
    sampled token moved to its neighbour inside the sampler's result."""
    from repro.serving.engine import Engine

    real = Engine._run_sampler

    def altered(self, logits, sl, kind):
        res = real(self, logits, sl, kind)
        res["token"] = (np.asarray(res["token"]) + 1) % 500
        return res

    monkeypatch.setattr(Engine, "_run_sampler", altered)
    tiny.fake_tpu(monkeypatch)
    out = harness.run_cell(layout, "tinyh.chat", 22, 2.0, False,
                           t_start=time.monotonic())
    assert out["correct"] is False
    gap = out["check"]["logit_gap"]
    assert gap["value"] > gap["limit"]
