"""The engine's step spans on the profiler's clock, its step account, and
the names of its device programs.

The tiny decoder serves a mixed greedy / sampled workload through
batched chunked prefill under ``jax.profiler.trace``; the test reads the
``.xplane.pb`` back with ``ProfileData`` and checks it against the
engine's own `Step` records and the tracer's Chrome JSON.
"""
import pathlib
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ArchConfig
from repro.core import hashed
from repro.models import build
from repro.obs.trace import ENGINE_PID, REQUEST_PID, Tracer
from repro.serving.api import SamplingParams
from repro.serving.engine import Engine, Request
from repro.serving.scheduler import SchedulerConfig

TINY = ArchConfig(
    name="tiny-spans", family="dense", arch_kind="decoder",
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256, remat=False, dtype="float32")
PHASES = {"schedule", "prefill", "decode", "sample", "commit", "cow",
          "spec_propose", "spec_verify", "wait"}


def _xplane(path: pathlib.Path):
    """(profile start ns, [(name, start ns, end ns, stats)]) of the
    host's ``engine.*`` events, start and end absolute."""
    files = sorted(path.rglob("*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        pd = ProfileData.from_file(str(files[0]))
        origin = None
        evs = []
        for plane in pd.planes:
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                origin = int(stats["profile_start_time"])
            if not plane.name.startswith("/host"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        evs.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    assert origin is not None
    return origin, [(n, origin + a, origin + b, st)
                    for n, a, b, st in evs]


def _scopes(lowered) -> set:
    """Every component of the op names in a lowered program's text."""
    txt = lowered.as_text(debug_info=True)
    return {part for name in re.findall(r'loc\("([^"]*)"', txt)
            for part in name.split("/")}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Serve six requests under the profiler, one ``step()`` at a time,
    noting after each call which requests gained a token."""
    model = build(TINY)
    params = model.init(jax.random.PRNGKey(0))
    tr = Tracer(enabled=True)
    eng = Engine(model, params, max_concurrency=3, max_len=64, eos_id=-1,
                 page_size=8, prefill_chunk=8, tracer=tr,
                 scheduler=SchedulerConfig(max_queue=8))
    rng = np.random.default_rng(0)
    reqs = []
    for uid in range(6):
        sp = SamplingParams(temperature=0.7 if uid % 2 else 0.0,
                            top_p=0.9, max_tokens=5, seed=uid)
        reqs.append(Request(uid=uid, prompt=rng.integers(
            2, TINY.vocab_size, size=5 + 4 * uid).astype(np.int32),
            sampling=sp))
    # the same work once before the trace: every program compiles there
    for r in reqs:
        assert eng.submit(Request(uid=100 + r.uid, prompt=r.prompt,
                                  sampling=r.sampling))
    eng.run()
    gained = {r.uid: set() for r in reqs}
    trace_dir = tmp_path_factory.mktemp("xplane")
    with jax.profiler.trace(str(trace_dir)):
        for r in reqs:
            assert eng.submit(r)
        while eng.pending():
            before = {r.uid: len(r.tokens) for r in reqs}
            eng.step()
            step_id = [e for e in tr.events if e["pid"] == ENGINE_PID
                       and e["name"] == "engine.step"][-1]["args"]["id"]
            for r in reqs:
                if len(r.tokens) > before[r.uid]:
                    gained[r.uid].add(step_id)
    steps = {id(s): s for r in reqs for s in r.steps}
    return eng, tr, reqs, gained, sorted(steps.values(),
                                         key=lambda s: s.id), trace_dir


def test_phase_spans_nest_inside_steps(served):
    _, _, _, _, steps, trace_dir = served
    _, evs = _xplane(trace_dir)
    xsteps = [e for e in evs if e[0] == "engine.step"]
    phases = [e for e in evs if e[0] != "engine.step"]
    assert len(xsteps) == len(steps) > 5
    assert {n[len("engine."):] for n, *_ in phases} <= PHASES
    assert {"engine.schedule", "engine.prefill", "engine.decode",
            "engine.sample", "engine.commit", "engine.wait"} \
        <= {n for n, *_ in phases}
    for name, a, b, _ in phases:
        assert any(sa <= a and b <= sb for _, sa, sb, _ in xsteps), name


def test_step_account_matches_the_profiler_and_chrome_json(served):
    _, tr, _, _, steps, trace_dir = served
    _, evs = _xplane(trace_dir)
    xsteps = sorted((e for e in evs if e[0] == "engine.step"),
                    key=lambda e: e[1])
    chrome = sorted((e for e in tr.events if e["pid"] == ENGINE_PID
                     and e["name"] == "engine.step"), key=lambda e: e["ts"])
    chrome = {e["args"]["id"]: e for e in chrome}
    assert [e[3]["step_num"] for e in xsteps] == [s.id for s in steps]
    for (_, a, b, _), st in zip(xsteps, steps):
        # the same step on both clocks
        assert abs((b - a) * 1e-9 - (st.t1 - st.t0)) < 0.5e-3
        # the Chrome JSON is on the profiler's clock
        assert abs(chrome[st.id]["ts"] - a * 1e-3) < 1e3
        assert st.host_s + st.wait_s == pytest.approx(st.t1 - st.t0,
                                                      abs=1e-9)
        assert sum(st.phase_s.values()) + st.wait_s \
            == pytest.approx(st.t1 - st.t0, abs=1e-9)
        assert st.wait_s > 0.0 and st.compiles == 0
    assert sum(s.prefill_rows for s in steps) > 0
    assert sum(s.decode_rows for s in steps) > 0


def test_request_steps_are_the_steps_that_advanced_it(served):
    _, tr, reqs, gained, _, _ = served
    chunks = {r.uid: set() for r in reqs}
    for e in tr.events:
        if e["pid"] == REQUEST_PID and e.get("name") == "prefill_chunk" \
                and e["tid"] in chunks:
            chunks[e["tid"]].add(e["args"]["step"])
    for r in reqs:
        ids = [s.id for s in r.steps]
        assert ids == sorted(set(ids))
        assert set(ids) == gained[r.uid] | chunks[r.uid]
        assert len(r.tokens) == 5 and chunks[r.uid]


def test_device_programs_and_scopes_are_named(served):
    eng = served[0]
    kv = eng.kv
    dec = eng._decode_paged.lower(
        eng.params, jnp.asarray(eng._tokens), eng.pages,
        jnp.asarray(kv.table), jnp.asarray(kv.lengths))
    assert "module @jit_decode_paged" in dec.as_text()
    assert {"attn", "kv_write", "mlp", "lm_head"} <= _scopes(dec)
    z = np.zeros((2,), np.int32)
    pre = eng._prefill_batched.lower(
        eng.params, jnp.zeros((2, 8), jnp.int32), eng.pages,
        jnp.asarray(kv.table[:2]), z, z, z).as_text()
    assert "module @jit_prefill_paged" in pre
    st = eng._sampler.state
    smp = eng._sampler._sample.lower(
        jnp.zeros((st.rows, st.vocab), jnp.float32),
        st.batch(with_masks=False), logprob_k=0, with_sampling=True,
        with_truncation=True)
    assert "module @jit_sample_tokens" in smp.as_text()
    assert "top_p" in _scopes(smp)
    spec = hashed.HashedSpec(virtual_shape=(64, 64), compression=0.125,
                             mode="block", block_shape=(16, 16))
    w = jnp.zeros(spec.real_param_shape(), jnp.float32)
    for path in ("materialize", "scan"):
        low = jax.jit(lambda x, w, path=path: hashed.matmul(
            x, w, spec, path=path)).lower(jnp.zeros((2, 64)), w)
        assert "hashed_expand" in _scopes(low), path
