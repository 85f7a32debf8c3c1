"""Property tests for the continuous-batching serving stack.

Host-side invariants (no model, pure bookkeeping — hypothesis drives
random admit/retire/preempt sequences):
- the page allocator never double-assigns and never leaks,
- PagedKVCache row bookkeeping conserves pages across admit / grow /
  release,
- the scheduler preserves FIFO order within a priority class, bounds
  its queue (backpressure), and expires past-deadline requests.

Engine-level invariants (tiny decoder, real jitted prefill/decode):
- requests admit AND retire mid-flight (a short request completes while
  a long one is still decoding — the acceptance criterion),
- every admitted request retires with exactly max_new_tokens or an EOS,
- preemption under an oversubscribed page pool reproduces the
  fully-provisioned greedy output token-for-token,
- greedy decode through an ``.hnart`` cold start (Engine.from_artifact)
  is token-identical to the in-memory engine (determinism regression).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

SETTINGS = dict(max_examples=25, deadline=None)

from repro.serving.paged_cache import PageAllocator, PagedKVCache
from repro.serving.scheduler import Scheduler, SchedulerConfig


class _Req:
    """Stand-in request for scheduler-only tests."""

    def __init__(self, uid, priority=0):
        self.uid = uid
        self.priority = priority
        self.submit_mono = None


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(num_pages=st.integers(2, 24), seed=st.integers(0, 10 ** 6))
def test_allocator_never_double_assigns_or_leaks(num_pages, seed):
    rng = np.random.default_rng(seed)
    alloc = PageAllocator(num_pages)
    held = []
    for _ in range(40):
        if held and rng.random() < 0.4:
            i = int(rng.integers(len(held)))
            alloc.free(held.pop(i))
        else:
            n = int(rng.integers(0, num_pages))
            free_before = alloc.num_free
            got = alloc.alloc(n)
            if n > free_before:
                assert got is None, "granted more pages than were free"
                continue
            assert got is not None and len(got) == n
            held.append(got)
        flat = [p for g in held for p in g]
        assert len(flat) == len(set(flat)), "double-assigned page"
        assert 0 not in flat, "trash page handed out"
        assert alloc.num_free + alloc.num_used == num_pages - 1
        assert alloc.num_used == len(flat)
    for g in held:
        alloc.free(g)
    assert alloc.num_free == num_pages - 1


def test_allocator_rejects_bad_free():
    alloc = PageAllocator(4)
    got = alloc.alloc(1)
    alloc.free(got)
    with pytest.raises(ValueError):
        alloc.free(got)          # double free
    with pytest.raises(ValueError):
        alloc.free([0])          # trash page was never allocated


@settings(**SETTINGS)
@given(num_pages=st.integers(4, 32), page_size=st.sampled_from([4, 8, 16]),
       rows=st.integers(1, 4), seed=st.integers(0, 10 ** 6))
def test_paged_cache_random_admit_grow_release(num_pages, page_size, rows,
                                               seed):
    """No page leak across random admit / decode-grow / release."""
    maxp = 4
    kv = PagedKVCache(num_pages, page_size, rows, maxp)
    rng = np.random.default_rng(seed)
    for _ in range(60):
        op = rng.random()
        bound = [r for r in range(rows) if r in kv.row_pages]
        free_rows = [r for r in range(rows) if r not in kv.row_pages]
        if op < 0.4 and free_rows:
            tokens = int(rng.integers(1, maxp * page_size))
            if kv.pages_for(tokens) <= kv.alloc.num_free:
                assert kv.admit_row(free_rows[0], tokens)
                r = free_rows[0]
                n = kv.pages_for(tokens)
                assert list(kv.table[r, :n]) == kv.row_pages[r]
                assert (kv.table[r, n:] == 0).all()
            else:
                assert not kv.admit_row(free_rows[0], tokens)
        elif op < 0.7 and bound:
            r = bound[int(rng.integers(len(bound)))]
            st_ = kv.ensure_decode_room(r)
            if st_ == "ok":
                kv.advance(r)
        elif bound:
            kv.release_row(bound[int(rng.integers(len(bound)))])
        kv.leak_check()
    for r in list(kv.row_pages):
        kv.release_row(r)
    kv.leak_check()
    assert kv.alloc.num_free == kv.usable_pages


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(n=st.integers(1, 30), classes=st.integers(1, 3),
       seed=st.integers(0, 10 ** 6))
def test_scheduler_fifo_within_priority_class(n, classes, seed):
    """Service order within a class equals submission order, under a
    random admissibility gate (pages free / busy rows)."""
    rng = np.random.default_rng(seed)
    sched = Scheduler(SchedulerConfig(policy="priority", max_queue=n))
    reqs = [_Req(uid, priority=int(rng.integers(classes)))
            for uid in range(n)]
    for r in reqs:
        assert sched.submit(r, now=float(r.uid))
    served = []
    stall = 0
    while len(sched) and stall < 200:
        admissible = rng.random() < 0.7
        got = sched.pop_admissible(lambda r: admissible)
        if got is None:
            stall += 1
            continue
        served.append(got)
    assert len(served) == n
    for c in range(classes):
        uids = [r.uid for r in served if r.priority == c]
        assert uids == sorted(uids), f"class {c} out of FIFO order"


def test_scheduler_backpressure_bounded_queue():
    sched = Scheduler(SchedulerConfig(max_queue=2))
    assert sched.submit(_Req(0), now=0.0)
    assert sched.submit(_Req(1), now=0.0)
    assert not sched.submit(_Req(2), now=0.0)      # full: refused
    sched.pop_admissible(lambda r: True)
    assert sched.submit(_Req(3), now=1.0)          # drained: accepted


def test_scheduler_deadline_expiry():
    sched = Scheduler(SchedulerConfig(deadline_s=1.0))
    a, b = _Req(0), _Req(1)
    sched.submit(a, now=0.0)
    sched.submit(b, now=5.0)
    dead = sched.expire(now=5.5)
    assert [r.uid for r in dead] == [0] and len(sched) == 1


def test_scheduler_deadline_spares_preempted_requests():
    """The deadline bounds queue wait BEFORE first admission; a
    preempted (already-admitted, tokens served) request must not be
    expired on requeue."""
    sched = Scheduler(SchedulerConfig(deadline_s=1.0))
    r = _Req(0)
    sched.submit(r, now=0.0)
    got = sched.pop_admissible(lambda q: True)
    got.first_admit_mono = 0.1                    # engine admitted it
    sched.requeue(got)                            # preempted much later
    assert sched.expire(now=10.0) == []
    assert sched.pop_admissible(lambda q: True) is r


def test_scheduler_unpop_keeps_admitted_monotone():
    """unpop() returns a popped-but-unplaceable head to the queue; the
    ``admitted`` counter stays monotone (a ``diff_snapshots`` window
    containing an unpop must never report negative admissions — the
    regression this pins), ``unpopped`` records the bounce, and
    ``snapshot`` derives the net."""
    sched = Scheduler(SchedulerConfig())
    a, b = _Req(0), _Req(1)
    sched.submit(a, now=0.0)
    sched.submit(b, now=0.0)
    got = sched.pop_admissible(lambda r: True)
    assert got is a
    before = sched.counters["admitted"]
    sched.unpop(got)
    assert sched.counters["admitted"] == before, \
        "admitted decremented on unpop"
    assert sched.counters["unpopped"] == 1
    snap = sched.snapshot()
    assert snap["admitted_net"] == snap["admitted"] - snap["unpopped"] == 0
    # arrival order restored: a pops again before b
    assert sched.pop_admissible(lambda r: True) is a
    assert sched.snapshot()["admitted_net"] == 1


@settings(**SETTINGS)
@given(n=st.integers(1, 12), seed=st.integers(0, 10 ** 6))
def test_scheduler_counters_monotone_under_random_unpops(n, seed):
    """Every counter is non-decreasing across a random pop/unpop/
    requeue sequence, and admitted_net == pops that stuck."""
    rng = np.random.default_rng(seed)
    sched = Scheduler(SchedulerConfig(max_queue=n))
    for uid in range(n):
        assert sched.submit(_Req(uid), now=float(uid))
    prev = sched.snapshot()
    stuck = 0
    for _ in range(60):
        got = sched.pop_admissible(lambda r: True)
        if got is None:
            break
        roll = rng.random()
        if roll < 0.3:
            sched.unpop(got)
        elif roll < 0.5:
            got.first_admit_mono = 0.0
            sched.requeue(got)
            stuck += 1
        else:
            stuck += 1
        snap = sched.snapshot()
        for k in prev:
            if k not in ("queue_depth", "admitted_net"):
                assert snap[k] >= prev[k], f"counter {k} went backwards"
        prev = snap
    assert prev["admitted_net"] == stuck


def test_scheduler_requeue_restores_head():
    sched = Scheduler(SchedulerConfig())
    a, b = _Req(0), _Req(1)
    sched.submit(a, now=0.0)
    sched.submit(b, now=0.0)
    got = sched.pop_admissible(lambda r: True)
    assert got is a
    sched.requeue(got)                             # preempted
    assert sched.pop_admissible(lambda r: True) is a


def test_scheduler_priority_classes_served_in_order():
    sched = Scheduler(SchedulerConfig(policy="priority"))
    lo, hi = _Req(0, priority=5), _Req(1, priority=0)
    sched.submit(lo, now=0.0)
    sched.submit(hi, now=0.1)
    assert sched.pop_admissible(lambda r: True) is hi


# ---------------------------------------------------------------------------
# engine: continuous batching over a real (tiny) decoder
# ---------------------------------------------------------------------------

import jax  # noqa: E402

from repro.configs.base import ArchConfig  # noqa: E402
from repro.models import build  # noqa: E402
from repro.serving.engine import Engine, Request  # noqa: E402

TINY = ArchConfig(
    name="tiny-serve", family="dense", arch_kind="decoder",
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256, remat=False, dtype="float32")


@pytest.fixture(scope="module")
def tiny():
    m = build(TINY)
    return m, m.init(jax.random.PRNGKey(0))


def _prompt(rng, lo=2, hi=12):
    return rng.integers(2, TINY.vocab_size,
                        size=int(rng.integers(lo, hi))).astype(np.int32)


def test_engine_admits_and_retires_mid_flight(tiny):
    """The acceptance criterion: a short request completes while a long
    one is still decoding, and a late submit is admitted mid-flight."""
    m, params = tiny
    eng = Engine(m, params, max_concurrency=2, max_len=64, eos_id=-1,
                 page_size=8)
    rng = np.random.default_rng(0)
    long_req = Request(uid=0, prompt=_prompt(rng), max_new_tokens=24)
    short_req = Request(uid=1, prompt=_prompt(rng), max_new_tokens=3)
    assert eng.submit(long_req) and eng.submit(short_req)
    while not short_req.done:
        eng.step()
    assert long_req.status == "running" and not long_req.done, \
        "short request should retire while the long one decodes"
    late = Request(uid=2, prompt=_prompt(rng), max_new_tokens=2)
    assert eng.submit(late)
    while not late.done:
        eng.step()
    assert not long_req.done, "late arrival admitted + retired mid-flight"
    eng.run()
    assert long_req.done and len(long_req.tokens) == 24
    eng.kv.leak_check()
    assert eng.kv.alloc.num_used == 0


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n_req=st.integers(1, 5),
       eos=st.integers(-1, 255))
def test_every_admitted_request_retires_exactly(tiny, seed, n_req, eos):
    """Random admit/retire traffic: every accepted request finishes with
    exactly max_new_tokens, or earlier on EOS; no page leaks; row slots
    all free at drain."""
    m, params = tiny
    eng = Engine(m, params, max_concurrency=2, max_len=64, eos_id=eos,
                 page_size=4, num_pages=17)
    rng = np.random.default_rng(seed)
    reqs = [Request(uid=i, prompt=_prompt(rng),
                    max_new_tokens=int(rng.integers(1, 10)))
            for i in range(n_req)]
    accepted = [r for r in reqs if eng.submit(r)]
    done = eng.run()
    assert {r.uid for r in done} == {r.uid for r in accepted}
    for r in done:
        assert r.done and r.status == "done"
        if len(r.tokens) < r.max_new_tokens:
            assert r.tokens[-1] == eos, (r.tokens, eos)
        else:
            assert len(r.tokens) == r.max_new_tokens, \
                "generated past max_new_tokens"
    eng.kv.leak_check()
    assert eng.kv.alloc.num_used == 0
    assert all(r is None for r in eng.rows)


def test_preemption_reproduces_greedy_tokens(tiny):
    """Oversubscribed page pool: preempt-and-recompute must reproduce
    the fully-provisioned greedy output token-for-token."""
    m, params = tiny
    rng = np.random.default_rng(3)
    prompts = [_prompt(rng, 8, 12) for _ in range(2)]

    def run(**kw):
        eng = Engine(m, params, max_concurrency=2, max_len=64, eos_id=-1,
                     **kw)
        for i, p in enumerate(prompts):
            assert eng.submit(Request(uid=i, prompt=p, max_new_tokens=12))
        done = eng.run()
        return [r.tokens for r in sorted(done, key=lambda r: r.uid)], eng

    full, _ = run(page_size=4)
    tight, eng = run(page_size=4, num_pages=8)   # 7 usable: forces oom
    assert sum(r.preemptions for r in eng._done) > 0, \
        "pool sizing did not force a preemption"
    assert tight == full
    eng.kv.leak_check()


def test_attn_impl_default_is_the_platforms_choice(tiny, monkeypatch):
    """Unset, the decode step attends through the paged kernel on a TPU
    and through the gather oracle elsewhere; prefill keeps the oracle.
    Explicit values pass through to both."""
    from repro import runtime

    m, params = tiny

    def impls(**kw):
        eng = Engine(m, params, max_concurrency=2, max_len=64, **kw)
        return eng.decode_attn_impl, eng.prefill_attn_impl

    assert impls() == ("ref", "ref")
    for impl in ("ref", "pallas"):
        assert impls(attn_impl=impl) == (impl, impl)
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    assert impls() == ("pallas", "ref")
    for impl in ("ref", "pallas"):
        assert impls(attn_impl=impl) == (impl, impl)


def test_step_attn_pages_are_the_decoding_rows_live_pages(tiny):
    """``Step.attn_pages`` is the sum, over the rows a step decodes, of
    ceil((cached + 1) / page): the pages the decode attention reads,
    the current token's included.  The gauge is its share of the table."""
    m, params = tiny
    ps = 4
    eng = Engine(m, params, max_concurrency=3, max_len=64, eos_id=-1,
                 page_size=ps, prefill_chunk=8)
    seen = []
    decode = eng._decode_paged

    def spy(p, t, pg, tb, ln):
        seen.append(np.asarray(ln).copy())
        return decode(p, t, pg, tb, ln)

    eng._decode_paged = spy
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, prompt=_prompt(rng, 3, 20),
                    max_new_tokens=int(rng.integers(3, 9)))
            for i in range(5)]
    for r in reqs:
        assert eng.submit(r)
    steps = []
    while eng.pending():
        n = len(seen)
        eng.step()
        if len(seen) > n:
            # the step just run decoded, so it advanced some request
            st = max((s for r in reqs for s in r.steps), key=lambda s: s.id)
            ln = seen[-1]
            live = ln[ln > 0]
            assert len(live) == st.decode_rows
            want = int(sum(-(-(int(x) + 1) // ps) for x in live))
            assert st.attn_pages == want
            share = eng.metrics.gauge("decode.attn_live_page_share").value
            assert share == want / (3 * eng.kv.maxp)
            steps.append(st)
    assert len(steps) > 3 and any(s.attn_pages > s.decode_rows
                                  for s in steps)


def test_submit_rejects_never_fitting_request(tiny):
    """A request whose working set can never fit is refused at submit
    (otherwise it wedges the FIFO head forever)."""
    m, params = tiny
    eng = Engine(m, params, max_concurrency=1, max_len=32, eos_id=-1,
                 page_size=4, num_pages=4)        # 3 usable pages
    big = Request(uid=0, prompt=np.arange(10, dtype=np.int32) + 2,
                  max_new_tokens=20)              # needs 8 pages
    assert not eng.submit(big)
    assert big.status == "rejected" and eng.failed == [big]
    ok = Request(uid=1, prompt=np.arange(4, dtype=np.int32) + 2,
                 max_new_tokens=4)
    assert eng.submit(ok)
    done = eng.run()
    assert [r.uid for r in done] == [1]


def test_wall_clock_steps_do_not_skew_durations(tiny, monkeypatch):
    """NTP-style wall-clock steps (time.time jumping BACKWARDS between
    reads) must not skew queue-wait/TTFT/latency: every duration comes
    off the monotonic clock.  The old wall-clock arithmetic clamped the
    negative deltas to 0 — hiding the skew instead of being immune."""
    from repro.serving import engine as engine_mod
    m, params = tiny
    wall = {"t": 10_000.0}

    def stepping_wall():
        wall["t"] -= 97.0            # a hard backwards step every read
        return wall["t"]

    monkeypatch.setattr(engine_mod, "_now_wall", stepping_wall)
    eng = Engine(m, params, max_concurrency=2, max_len=64, eos_id=-1,
                 page_size=8)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, prompt=_prompt(rng), max_new_tokens=4)
            for i in range(3)]
    for r in reqs:
        assert eng.submit(r)
    done = eng.run()
    assert len(done) == 3
    for r in done:
        # monotonic marks stay ordered however the wall clock thrashes
        assert (r.submit_mono <= r.first_admit_mono
                <= r.first_token_mono <= r.finish_mono)
        # wall timestamps still populated — they are display-only
        assert r.submit_time is not None and r.finish_time is not None
    stats = eng.stats()
    assert stats["latency_p50_s"] >= 0.0
    assert stats["ttft_p50_s"] >= 0.0 and stats["ttft_mean_s"] >= 0.0
    snap = eng.metrics.snapshot()
    for h in ("engine.ttft_s", "engine.queue_wait_s"):
        assert snap[h]["count"] == 3 and snap[h]["min"] >= 0.0, h


def test_deadline_expiry_immune_to_wall_clock_steps(tiny, monkeypatch):
    """Queue-deadline expiry keys off the monotonic clock: a wall-clock
    step can neither spuriously expire a fresh request nor immortalize
    an overdue one.  The mono clock is driven directly; the wall clock
    is pinned to nonsense to prove it is irrelevant."""
    from repro.serving import engine as engine_mod
    mono = {"t": 0.0}
    monkeypatch.setattr(engine_mod, "_now_mono", lambda: mono["t"])
    monkeypatch.setattr(engine_mod, "_now_wall", lambda: -1e9)
    m, params = tiny
    eng = Engine(m, params, max_concurrency=1, max_len=64, eos_id=-1,
                 page_size=8,
                 scheduler=SchedulerConfig(deadline_s=5.0, max_queue=8))
    rng = np.random.default_rng(7)
    a = Request(uid=0, prompt=_prompt(rng), max_new_tokens=8)
    b = Request(uid=1, prompt=_prompt(rng), max_new_tokens=2)
    assert eng.submit(a)                     # submit_mono = 0.0
    mono["t"] = 1.0
    assert eng.submit(b)                     # queued behind a
    mono["t"] = 2.0
    eng.step()                               # b waited 1s < 5s: kept
    assert b.status == "queued"
    mono["t"] = 7.0
    eng.step()                               # b waited 6s > 5s: expired
    assert b.status == "expired" and b.finish_reason == "deadline"
    eng.run()
    assert a.done and len(a.tokens) == 8     # a unaffected throughout


def test_expired_request_stamps_finish_clocks_and_terminal_delta(
        tiny, monkeypatch):
    """The deadline-expiry path finishes a request like any other
    terminal path: ``finish_mono``/``finish_time`` are stamped at the
    expiring tick (latency math and streaming clients read them) and
    the handle drains a terminal ``deadline`` delta.  Regression: the
    expire path used to leave both clocks ``None``."""
    from repro.serving import engine as engine_mod
    mono = {"t": 0.0}
    monkeypatch.setattr(engine_mod, "_now_mono", lambda: mono["t"])
    monkeypatch.setattr(engine_mod, "_now_wall", lambda: 1234.5)
    m, params = tiny
    eng = Engine(m, params, max_concurrency=1, max_len=64, eos_id=-1,
                 page_size=8,
                 scheduler=SchedulerConfig(deadline_s=5.0, max_queue=8))
    rng = np.random.default_rng(11)
    a = Request(uid=0, prompt=_prompt(rng), max_new_tokens=6)
    b = Request(uid=1, prompt=_prompt(rng), max_new_tokens=2)
    ha = eng.submit(a)
    hb = eng.submit(b)
    assert ha and hb
    eng.step()                       # a admitted: holds the only row
    mono["t"] = 7.0                  # b's queue wait exceeds 5s
    eng.run()
    assert b.status == "expired" and b.finish_reason == "deadline"
    assert b.finish_mono == 7.0, "finish_mono not stamped on expiry"
    assert b.finish_time == 1234.5, "finish_time not stamped on expiry"
    deltas = list(hb)
    assert deltas and deltas[-1].done \
        and deltas[-1].finish_reason == "deadline"
    assert a.done and len(a.tokens) == 6


# ---------------------------------------------------------------------------
# prefix index (radix tree over KV pages)
# ---------------------------------------------------------------------------

from repro.serving.paged_cache import PageAllocator as _PA  # noqa: E402
from repro.serving.paged_cache import PrefixIndex  # noqa: E402


@settings(**SETTINGS)
@given(seed=st.integers(0, 10 ** 6), ps=st.sampled_from([2, 4]),
       n_seq=st.integers(1, 6))
def test_prefix_index_match_is_longest_indexed_prefix(seed, ps, n_seq):
    """After inserting sequences over a tiny alphabet, match() returns
    exactly the pages of the longest indexed full-page prefix, and the
    tree's refcount claims always verify against the allocator."""
    rng = np.random.default_rng(seed)
    alloc = _PA(64)
    idx = PrefixIndex(ps, alloc)
    indexed = {}                         # chunk-path tuple -> page
    for _ in range(n_seq):
        toks = rng.integers(0, 3, size=int(rng.integers(1, 5 * ps)))
        pages = alloc.alloc(-(-len(toks) // ps))
        idx.insert(toks, pages, len(toks))
        for j in range(len(toks) // ps):
            key = tuple(toks[:(j + 1) * ps])
            indexed.setdefault(key, pages[j])
        alloc.free(pages)                # tree refs keep indexed pages
    probe = rng.integers(0, 3, size=int(rng.integers(1, 6 * ps)))
    fulls, tail = idx.match(probe)
    assert len(fulls) <= len(probe) // ps
    for j, page in enumerate(fulls):
        assert indexed[tuple(probe[:(j + 1) * ps])] == page
    if len(fulls) < len(probe) // ps:          # stopped: next page unindexed
        assert tuple(probe[:(len(fulls) + 1) * ps]) not in indexed
    if tail is not None:
        page, use = tail
        assert 0 < use < ps and alloc.refcount(page) >= 1
    # every tree page is allocator-held exactly once by the tree
    for p in idx.pages():
        assert alloc.refcount(p) == 1


def test_prefix_index_evicts_lru_only_unreferenced():
    """Eviction reclaims least-recently-used tree-only pages leaf-first;
    pages a row still maps (refcount > 1) are never touched."""
    alloc = _PA(8)
    idx = PrefixIndex(2, alloc)
    old = alloc.alloc(2)
    idx.insert(np.array([0, 1, 0, 1]), old, 4)
    alloc.free(old)                      # tree-only now (LRU)
    new = alloc.alloc(2)
    idx.insert(np.array([2, 3, 2, 3]), new, 4)   # fresher, row-held
    assert idx.evictable() == 2          # only the tree-only pages
    assert idx.evict(1) == 1             # drops old's LEAF, parent stays
    fulls, _ = idx.match(np.array([2, 3, 2, 3]))
    assert fulls == new, "eviction touched a row-held entry"
    fulls, _ = idx.match(np.array([0, 1, 0, 1]))
    assert fulls == old[:1], "leaf-first LRU should keep the parent"
    assert idx.evict(5) == 1             # parent became a leaf: reclaimed
    fulls, _ = idx.match(np.array([0, 1, 0, 1]))
    assert fulls == []
    alloc.free(new)                      # row drops; tree still holds
    assert alloc.num_used == idx.num_pages == 2


@settings(**SETTINGS)
@given(num_pages=st.integers(6, 24), seed=st.integers(0, 10 ** 6))
def test_paged_cache_prefix_admit_share_release(num_pages, seed):
    """Random admit-with-sharing / publish / release sequences conserve
    refcounts (leak_check) and shared mappings never exceed what the
    tree indexed."""
    ps, rows, maxp = 4, 3, 4
    kv = PagedKVCache(num_pages, ps, rows, maxp, prefix_cache=True)
    rng = np.random.default_rng(seed)
    toks = {}
    for _ in range(60):
        op = rng.random()
        bound = sorted(kv.row_pages)
        free_rows = [r for r in range(rows) if r not in kv.row_pages]
        if op < 0.45 and free_rows:
            n = int(rng.integers(1, maxp * ps))
            ids = rng.integers(0, 2, size=n)         # collision-heavy
            if kv.admit_row(free_rows[0], n, token_ids=ids):
                r = free_rows[0]
                kv.drop_tail_ref(r)
                toks[r] = list(ids)
                assert kv.row_meta[r].hit_tokens <= max(n - 1, 0)
        elif op < 0.7 and bound:
            r = bound[int(rng.integers(len(bound)))]
            if kv.ensure_decode_room(r) == "ok":
                kv.pending_copies.clear()
                kv.advance(r)
                toks[r].append(int(rng.integers(0, 2)))
        elif op < 0.85 and bound:
            r = bound[int(rng.integers(len(bound)))]
            n = int(kv.lengths[r])
            kv.index_row(r, np.asarray(toks[r][:n]), n)
        elif bound:
            r = bound[int(rng.integers(len(bound)))]
            kv.release_row(r)
            del toks[r]
        kv.leak_check()
    for r in list(kv.row_pages):
        kv.release_row(r)
    kv.leak_check()
    assert kv.alloc.num_used == kv.prefix.num_pages


def test_engine_prefix_cache_survives_request_lifetime(tiny):
    """Sequential identical prompts: the first request's pages outlive
    it in the tree, so the second admission maps them by reference and
    still decodes token-identically."""
    m, params = tiny
    rng = np.random.default_rng(5)
    prompt = _prompt(rng, 18, 19)

    def run(prefix):
        eng = Engine(m, params, max_concurrency=1, max_len=64, eos_id=-1,
                     page_size=8, prefix_cache=prefix)
        outs = []
        for uid in range(2):             # strictly sequential lifetimes
            req = Request(uid=uid, prompt=prompt.copy(), max_new_tokens=5)
            assert eng.submit(req)
            eng.run()
            outs.append(req.tokens)
        eng.kv.leak_check()
        return outs, eng

    base, _ = run(False)
    got, eng = run(True)
    assert got == base
    stats = eng.stats()
    assert stats["hit_tokens"] > 0, "second admission missed the tree"
    assert stats["pages_shared"] >= 2
    # drained engine holds exactly the tree's retained pages
    assert eng.kv.alloc.num_used == eng.kv.prefix.num_pages > 0


def _run_sequential_pair(m, params, prompt, *, prefix, rows):
    eng = Engine(m, params, max_concurrency=rows, max_len=64, eos_id=-1,
                 page_size=8, prefix_cache=prefix)
    outs = []
    for uid in range(2):
        req = Request(uid=uid, prompt=prompt.copy(), max_new_tokens=4)
        assert eng.submit(req)
        eng.run()
        outs.append(req.tokens)
        assert req.status == "done", req.status
    eng.kv.leak_check()
    return outs, eng


def test_engine_prefix_hit_near_max_len_slide_back(tiny):
    """A prefix hit that leaves the resume chunk pressed against the
    cache edge forces the slid-back bucket (start < pos, fixed 8-grid
    shape): output must still match the prefix-off engine."""
    m, params = tiny
    rng = np.random.default_rng(13)
    prompt = _prompt(rng, 60, 61)       # 60 + 4 new tokens == max_len
    # rows=2 leaves spare pool pages, so the partial-tail pin survives
    # admission: hit 59 -> resume c=1 at pos 59, room 5 -> no menu
    # bucket fits -> the window must slide back
    base, _ = _run_sequential_pair(m, params, prompt, prefix=False,
                                   rows=2)
    got, eng = _run_sequential_pair(m, params, prompt, prefix=True,
                                    rows=2)
    assert got == base
    assert eng.stats()["hit_tokens"] >= 59


def test_engine_admission_survives_tail_pin_on_drained_pool(tiny):
    """Livelock regression: with the whole pool retained by the tree
    for this very prompt, the partial-tail pin would hold the last
    reclaimable page hostage — admission must drop the pin (trading the
    tail reuse) rather than fail forever."""
    m, params = tiny
    rng = np.random.default_rng(13)
    prompt = _prompt(rng, 60, 61)       # needs all 8 pages of the pool
    base, _ = _run_sequential_pair(m, params, prompt, prefix=False,
                                   rows=1)
    got, eng = _run_sequential_pair(m, params, prompt, prefix=True,
                                    rows=1)
    assert got == base
    stats = eng.stats()
    assert stats["hit_tokens"] >= 56    # full pages still shared
    assert stats["evictions"] > 0       # the unpinned tail was reclaimed


def test_engine_chunked_prefill_token_identical(tiny):
    """Chunked prefill (including chunk=1) reproduces monolithic greedy
    output and interleaves with decode (tick accounting shows overlap)."""
    m, params = tiny
    rng = np.random.default_rng(9)
    prompts = [_prompt(rng, 20, 40), _prompt(rng, 3, 8)]

    def run(chunk):
        eng = Engine(m, params, max_concurrency=2, max_len=64, eos_id=-1,
                     page_size=8, prefill_chunk=chunk)
        for uid, p in enumerate(prompts):
            assert eng.submit(Request(uid=uid, prompt=p.copy(),
                                      max_new_tokens=8))
        done = eng.run()
        eng.kv.leak_check()
        return ([r.tokens for r in sorted(done, key=lambda r: r.uid)],
                eng.stats())

    base, _ = run(None)
    for chunk in (1, 7, 8):
        got, stats = run(chunk)
        assert got == base, f"chunk={chunk}"
        assert stats["prefill_chunks"] >= sum(
            -(-len(p) // chunk) for p in prompts)
    _, stats = run(4)
    assert stats["interleaved_ticks"] > 0, \
        "long chunked prefill never overlapped a decode tick"


def test_determinism_artifact_vs_in_memory_engine(tiny, tmp_path):
    """Greedy decode through an ``.hnart`` cold start is token-identical
    to the in-memory engine under the continuous-batching scheduler."""
    from repro import artifact

    cfg = TINY.hashed_variant(0.25)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(1))
    path = str(tmp_path / "tiny.hnart")
    artifact.export_model(path, cfg, params)

    rng = np.random.default_rng(7)
    prompts = [_prompt(rng) for _ in range(4)]

    def drive(eng):
        for i, p in enumerate(prompts):
            assert eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
        done = eng.run()
        return [r.tokens for r in sorted(done, key=lambda r: r.uid)]

    live = drive(Engine(m, params, max_concurrency=2, max_len=64,
                        eos_id=-1, page_size=8))
    cold = drive(Engine.from_artifact(path, slots=2, max_len=64,
                                      eos_id=-1, page_size=8))
    assert cold == live
