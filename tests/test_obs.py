"""Observability layer: metrics registry, span tracer, and the
engine's instrumentation contract.

Host-side (no model):
- histogram bucket-edge semantics (Prometheus ``le``: a value exactly
  on an edge lands in the bucket that edge closes), percentile
  clamping, snapshot/diff arithmetic,
- `MetricView` compat surface (legacy ``stats["x"] += 1`` call sites
  publish into the registry),
- disabled-tracer guarantee (no events; the step account still kept);
  Chrome trace-event JSON round-trip.

Engine-level (tiny decoder, real jitted prefill/decode):
- span nesting stays balanced under preemption-recompute (every
  ``queued``/``request`` B has its E, preempted requests re-open
  ``queued``),
- tracing is bitwise inert: the same seeded workload emits identical
  tokens with the tracer off vs on, and with a profiler session on
  (prefix cache + chunked prefill + an oversubscribed pool — the
  busiest instrumented paths),
- ``shutdown()`` leak audit: clean engines report zero anomalies,
  corrupted bookkeeping increments ``kv.leak_anomalies`` instead of
  raising — including rows in the speculative draft pool.
"""
import json

import numpy as np
import pytest

from repro.obs.metrics import (Counter, Histogram, MetricsRegistry,
                               diff_snapshots)
from repro.obs.trace import ENGINE_PID, REQUEST_PID, Tracer


# ---------------------------------------------------------------------------
# histogram bucket semantics
# ---------------------------------------------------------------------------

def test_histogram_bucket_edges_le_semantics():
    h = Histogram("h", edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0):        # 1.0 exactly on edge -> bucket le=1.0
        h.observe(v)
    h.observe(1.5)              # (1, 2]
    h.observe(2.0)              # exactly on edge -> le=2.0 bucket
    h.observe(3.0)              # (2, 4]
    h.observe(9.0)              # overflow
    assert h.counts == [2, 2, 1, 1]
    assert h.count == 6
    assert h.total == pytest.approx(0.5 + 1.0 + 1.5 + 2.0 + 3.0 + 9.0)
    snap = h.snapshot()
    assert snap["min"] == 0.5 and snap["max"] == 9.0
    # cumulative le-buckets cover exactly the populated edges
    assert snap["buckets"] == [[1.0, 2], [2.0, 4], [4.0, 5], ["+Inf", 6]]


def test_histogram_percentiles_clamped_to_observed_range():
    h = Histogram("h", edges=(1.0, 10.0, 100.0))
    for v in (2.0, 3.0, 4.0):
        h.observe(v)
    # rank bucket is (1, 10]; upper edge 10 clamps to observed max 4
    assert h.percentile(50) == 4.0
    h.observe(500.0)                    # overflow bucket: p100 = vmax
    assert h.percentile(100) == 500.0
    assert h.mean == pytest.approx((2 + 3 + 4 + 500) / 4)
    empty = Histogram("e")
    assert empty.percentile(50) == 0.0
    assert empty.snapshot()["count"] == 0


def test_histogram_rejects_bad_edges():
    with pytest.raises(ValueError):
        Histogram("h", edges=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("h", edges=(1.0, 1.0))


# ---------------------------------------------------------------------------
# registry + dict-view compat
# ---------------------------------------------------------------------------

def test_metric_view_publishes_into_registry():
    r = MetricsRegistry()
    view = r.group("kv", keys=("pages_fresh",))
    view["pages_fresh"] += 3          # legacy += call site
    view["cow_copies"] += 1           # unknown key registers on touch
    assert r.counter("kv.pages_fresh").value == 3
    assert r.counter("kv.cow_copies").value == 1
    assert dict(view) == {"pages_fresh": 3, "cow_copies": 1}
    with pytest.raises(TypeError):
        del view["pages_fresh"]


def test_registry_name_kind_conflicts_raise():
    r = MetricsRegistry()
    r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")
    assert isinstance(r.counter("x"), Counter)   # get-or-create idempotent


def test_snapshot_diff_and_render(tmp_path):
    r = MetricsRegistry()
    r.counter("a.n").inc(5)
    r.gauge("a.g").set(7)
    h = r.histogram("a.h", edges=(1.0, 2.0))
    h.observe(0.5)
    base = r.snapshot()
    r.counter("a.n").inc(2)
    h.observe(1.5)
    d = diff_snapshots(r.snapshot(), base)
    assert d["a.n"] == 2
    assert d["a.h"]["count"] == 1
    assert d["a.h"]["mean"] == pytest.approx(1.5)
    out = tmp_path / "metrics.json"
    r.export(str(out))
    loaded = json.loads(out.read_text())["metrics"]
    assert loaded["a.n"] == 7 and loaded["a.g"] == 7
    txt = r.render()
    assert "a.n" in txt and "hist" in txt and "gauge" in txt


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    assert tr.now() == 0.0
    tr.track(0, 0, "x")
    tr.begin(0, 0, "a")
    tr.complete(0, 0, "b", 0.0)
    tr.instant(0, 0, "c")
    tr.end(0, 0, "a")
    with tr.step() as st:
        with tr.span("decode"):
            pass
    assert tr.events == [] and tr._tracks == {}
    # the step account is kept whatever ``enabled`` says
    assert st.t1 >= st.t0 and "decode" in st.phase_s


def test_trace_export_roundtrip(tmp_path):
    tr = Tracer()
    tr.track(REQUEST_PID, 3, "req 3")
    tr.begin(REQUEST_PID, 3, "request", prompt_len=4)
    t0 = tr.now()
    tr.complete(REQUEST_PID, 3, "prefill_chunk", t0, start=0, end=4)
    tr.instant(REQUEST_PID, 3, "first_token")
    tr.end(REQUEST_PID, 3, "request")
    out = tmp_path / "trace.json"
    tr.export(str(out))
    doc = json.loads(out.read_text())
    ev = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    names = {e["args"]["name"] for e in ev if e["ph"] == "M"}
    assert {"engine", "requests", "req 3"} <= names
    x = [e for e in ev if e["ph"] == "X"][0]
    assert x["dur"] >= 0 and x["args"] == {"start": 0, "end": 4}
    assert [e["ph"] for e in ev if e["ph"] in "BE"] == ["B", "E"]
    ts = [e["ts"] for e in ev if e["ph"] != "M"]
    assert ts == sorted(ts)


# ---------------------------------------------------------------------------
# engine instrumentation (tiny decoder)
# ---------------------------------------------------------------------------

import jax  # noqa: E402

from repro.configs.base import ArchConfig  # noqa: E402
from repro.models import build  # noqa: E402
from repro.serving.engine import Engine, Request  # noqa: E402
from repro.serving.scheduler import SchedulerConfig  # noqa: E402

TINY = ArchConfig(
    name="tiny-obs", family="dense", arch_kind="decoder",
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256, remat=False, dtype="float32")


@pytest.fixture(scope="module")
def tiny():
    m = build(TINY)
    return m, m.init(jax.random.PRNGKey(0))


def _run(model, params, *, tracer=None, num_pages=None, seed=0,
         n_req=6, max_new=6, prefix_cache=False, prefill_chunk=None,
         debug_leak_check=False, draft=None):
    eng = Engine(model, params, max_concurrency=2, max_len=64,
                 eos_id=-1, page_size=8, num_pages=num_pages,
                 tracer=tracer, prefix_cache=prefix_cache,
                 prefill_chunk=prefill_chunk,
                 debug_leak_check=debug_leak_check, draft=draft,
                 scheduler=SchedulerConfig(max_queue=n_req + 1))
    rng = np.random.default_rng(seed)
    shared = rng.integers(2, TINY.vocab_size, size=11).astype(np.int32)
    for uid in range(n_req):
        tail = rng.integers(2, TINY.vocab_size,
                            size=int(rng.integers(2, 9))).astype(np.int32)
        eng.submit(Request(uid=uid, prompt=np.concatenate([shared, tail]),
                           max_new_tokens=max_new))
    done = eng.run()
    return eng, {r.uid: list(r.tokens) for r in done}


def test_spans_balance_under_preemption(tiny):
    """An oversubscribed pool forces preemption-recompute; every span
    stays balanced and preempted requests re-open ``queued``."""
    model, params = tiny
    tr = Tracer()
    # 6 usable pages for 2 rows x (up to 27 feed tokens / 8 per page):
    # both rows active oversubscribe the pool
    eng, toks = _run(model, params, tracer=tr, num_pages=7,
                     n_req=5, max_new=8)
    assert eng.stats()["preemptions"] > 0
    assert eng._n_preempt == eng.stats()["preemptions"]
    per_track = {}
    for e in tr.events:
        if e["ph"] in "BE" and e["pid"] == REQUEST_PID:
            d = per_track.setdefault((e["tid"], e["name"]), [0, 0])
            d[0 if e["ph"] == "B" else 1] += 1
    for (tid, name), (b, end) in per_track.items():
        assert b == end, f"unbalanced {name} span on request {tid}"
    # at least one preempted request waited in queue more than once
    assert any(name == "queued" and b >= 2
               for (tid, name), (b, _) in per_track.items())
    preempts = [e for e in tr.events if e.get("name") == "preempt"]
    assert len(preempts) == eng.stats()["preemptions"]
    # engine-track steps recorded as X slices
    assert any(e["ph"] == "X" and e["pid"] == ENGINE_PID
               and e["name"] == "engine.step" for e in tr.events)


@pytest.mark.parametrize("arm", ["tracer", "profiler"])
@pytest.mark.parametrize("seed", [0, 3])
def test_tracing_is_bitwise_inert(tiny, seed, arm, tmp_path):
    """Same seeded workload, tracer off vs on (or under a profiler
    session): identical tokens, even through prefix-cache hits, chunked
    prefill, and preemption."""
    model, params = tiny
    kw = dict(num_pages=12, seed=seed, n_req=6, max_new=6,
              prefix_cache=True, prefill_chunk=8)
    _, toks_off = _run(model, params, tracer=Tracer(enabled=False), **kw)
    if arm == "tracer":
        eng, toks_on = _run(model, params, tracer=Tracer(enabled=True),
                            **kw)
        assert eng.tracer.events       # the traced arm actually recorded
    else:
        with jax.profiler.trace(str(tmp_path)):
            _, toks_on = _run(model, params, tracer=Tracer(enabled=False),
                              **kw)
        assert list(tmp_path.rglob("*.xplane.pb"))
    assert toks_on == toks_off


def test_engine_metrics_registry_names(tiny):
    model, params = tiny
    eng, toks = _run(model, params, n_req=4, max_new=4)
    snap = eng.metrics.snapshot()
    for name in ("engine.ticks", "engine.tokens", "engine.done",
                 "sched.submitted", "sched.queue_depth",
                 "kv.pages_in_use", "kv.pages_fresh",
                 "sampler.dispatches.decode"):
        assert name in snap, name
    assert snap["engine.done"] == 4
    assert snap["engine.tokens"] == sum(len(t) for t in toks.values())
    assert snap["engine.ttft_s"]["count"] == 4
    assert snap["engine.queue_wait_s"]["count"] == 4
    # one observation a step, from its account; the histograms that
    # timed asynchronous dispatches are gone
    assert snap["engine.step_s"]["count"] == snap["engine.ticks"]
    assert snap["engine.step_host_s"]["count"] == snap["engine.ticks"]
    assert snap["engine.step_host_s"]["sum"] <= snap["engine.step_s"]["sum"]
    for gone in ("engine.decode_tick_s", "engine.prefill_chunk_s",
                 "engine.prefill_batch_s"):
        assert gone not in snap
    # stats() is a thin view over the same registry
    s = eng.stats()
    assert s["ticks"] == snap["engine.ticks"]
    assert s["submitted"] == snap["sched.submitted"]
    assert s["sampler_dispatches"]["decode"] \
        == snap["sampler.dispatches.decode"]


def test_leak_check_clean_and_corrupted(tiny):
    model, params = tiny
    eng, _ = _run(model, params, n_req=3, max_new=4,
                  debug_leak_check=True)
    eng.shutdown()
    assert eng.last_leak_error is None
    assert eng.metrics.snapshot()["kv.leak_anomalies"] == 0
    # corrupt the bookkeeping: a page allocated but held by no row
    eng.kv.alloc.alloc(1)
    eng.shutdown()
    assert eng.last_leak_error is not None
    assert eng.metrics.snapshot()["kv.leak_anomalies"] == 1


def test_leak_audit_covers_draft_kv_rows(tiny):
    """The shutdown audit extends to the speculative draft pool: a
    clean spec engine reports zero anomalies, and a corrupted DRAFT
    row (base pool untouched) still lands in ``kv.leak_anomalies`` /
    ``last_leak_error``."""
    model, params = tiny
    from repro.serving.draft import build_draft
    _, dm, dp = build_draft(TINY, params, "1/8")
    eng, _ = _run(model, params, n_req=3, max_new=4,
                  debug_leak_check=True, draft=(dm, dp))
    eng.shutdown()
    assert eng.last_leak_error is None
    assert eng.metrics.snapshot()["kv.leak_anomalies"] == 0
    # corrupt only the draft pool's bookkeeping
    eng.spec.kv.alloc.alloc(1)
    eng.kv.leak_check()                 # base pool audits clean...
    eng.shutdown()                      # ...the draft audit catches it
    assert eng.last_leak_error is not None
    assert eng.metrics.snapshot()["kv.leak_anomalies"] == 1
