"""The ``step_host_ms`` readers on a synthetic run whose requests carry
the engine's step records: window steps only, each counted once, on the
window's clock; nothing to read where the program keeps no account."""
import types

import pytest

from bench import e2e
from bench.layout import Layout
from repro.obs.trace import Step

ORIGIN = 5000.0        # the engine's monotonic clock at the window's 0
READERS = ["step_host_ms.chat", "step_host_ms.docs"]


def _step(i, t0, dur, wait):
    t0 += ORIGIN
    return Step(id=i, t0=t0, t1=t0 + dur, wait_s=wait, host_s=dur - wait)


def _run(steps_per_req, window=10.0, offsets=(0.0, 2e-6, 5e-6)):
    recs = []
    for i, steps in enumerate(steps_per_req):
        sent = -1.0 + i
        req = types.SimpleNamespace(
            submit_mono=ORIGIN + sent + offsets[i % len(offsets)],
            steps=steps)
        recs.append(e2e.Rec(uid=i, due=sent, prompt_len=8, greedy=True,
                            sent=sent, req=req))
    return e2e.Run(cell="x", config={}, cost=None, peak={}, rows=4,
                   setup_s=1.0, window_s=window, drain_end=window + 2.0,
                   recs=recs, ticks=[])


@pytest.mark.parametrize("name", READERS)
def test_reads_the_window_steps_once_each(name):
    warm = _step(1, -0.5, 0.2, 0.15)            # before the window
    a = _step(2, 0.1, 0.13, 0.12)               # host 10 ms
    b = _step(3, 0.3, 0.13, 0.10)               # host 30 ms
    late = _step(4, 9.95, 0.13, 0.0)            # ends past the window
    run = _run([[warm, a, b], [a, b, late], [b]])
    assert Layout().reader(name)(run) == pytest.approx(20.0)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_without_a_step_account(name):
    read = Layout().reader(name)
    # the parent program's requests have no ``steps``
    run = _run([[], []])
    for r in run.recs:
        del r.req.steps
    assert read(run) is None
    # steps only outside the window
    assert read(_run([[_step(1, -0.5, 0.2, 0.1)]])) is None
