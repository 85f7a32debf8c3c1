"""Tensor-parallel serving tier (host-simulated mesh).

Run with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; with
one device the whole module skips (CI gives this tier its own job).

What sharded serving must preserve — bitwise:

- **kernel parity**: attention is per-head independent, so a head
  shard of the paged decode kernel / ragged flash-prefill kernel over
  a KV-head-sharded page pool, concatenated across shards, equals the
  single-device output exactly (ref and pallas-interpret impls; both
  manual slicing and a real ``shard_map``),
- **engine identity**: ``Engine(mesh=...)`` is observationally
  identical to the single-device engine across the repo's existing
  differential-fuzz axes — prefix cache on/off, chunk sizes, batched
  prefill on/off, pools down to oversubscription (preemption), seeded
  sampling, both attention impls,
- **placement**: hashed banks shard over "model", dense weights
  replicate (the o-projection consumes an exact all-gather, never a
  psum), the page pool shards on the KV-head axis; head counts not
  divisible by tp degrade to full replication and still match,
- **guards**: mesh requires the paged backend and excludes the
  speculative draft; ``engine.shard.*`` metrics exist only on mesh
  engines.
"""
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

if jax.device_count() < 2:
    pytest.skip(
        "needs a multi-device mesh: run with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8",
        allow_module_level=True)

from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs.base import ArchConfig  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.models import build  # noqa: E402
from repro.serving.api import SamplingParams  # noqa: E402
from repro.serving.engine import Engine, Request  # noqa: E402
from repro.serving.scheduler import SchedulerConfig  # noqa: E402

SHARD_EXAMPLES = int(os.environ.get("SHARD_EXAMPLES", "3"))

TINY = ArchConfig(
    name="tiny-shard", family="dense", arch_kind="decoder",
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256, remat=False, dtype="float32")

PAGE = 8
MAX_LEN = 64


@pytest.fixture(scope="module")
def tiny():
    m = build(TINY)
    return m, m.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny_hashed():
    cfg = TINY.hashed_variant(0.25)
    m = build(cfg)
    return m, m.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# kernel parity: head shards concatenate to the full output, bitwise
# ---------------------------------------------------------------------------

def _rand_paged(rng, *, b=3, hq=4, hkv=2, d=16, ps=8, npages=13, maxp=4):
    q = rng.standard_normal((b, hq, d)).astype(np.float32)
    pk = rng.standard_normal((npages, ps, hkv, d)).astype(np.float32)
    pv = rng.standard_normal((npages, ps, hkv, d)).astype(np.float32)
    # distinct physical pages per row; page 0 stays the trash page
    pages = rng.permutation(np.arange(1, npages))[:b * maxp]
    table = pages.reshape(b, maxp).astype(np.int32)
    lengths = rng.integers(1, ps * maxp + 1, size=b).astype(np.int32)
    return q, pk, pv, table, lengths


def _decode_fn(impl):
    if impl == "ref":
        from repro.kernels.ref import paged_attention_ref
        return paged_attention_ref
    from repro.kernels.paged_attention import paged_decode_attention
    return paged_decode_attention


def _prefill_fn(impl):
    if impl == "ref":
        from repro.kernels.ref import paged_prefill_ref
        return paged_prefill_ref
    from repro.kernels.flash_prefill import paged_prefill_attention
    return paged_prefill_attention


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_paged_decode_manual_head_slices_concat(impl):
    """GQA head shard by hand: q heads [2s:2s+2] with kv head [s]
    produce exactly the matching slice of the full output."""
    fn = _decode_fn(impl)
    rng = np.random.default_rng(0)
    q, pk, pv, table, lengths = _rand_paged(rng)
    full = np.asarray(fn(q, pk, pv, table, lengths, 0))
    parts = [np.asarray(fn(q[:, 2 * s:2 * s + 2],
                           pk[:, :, s:s + 1], pv[:, :, s:s + 1],
                           table, lengths, 0)) for s in range(2)]
    np.testing.assert_array_equal(np.concatenate(parts, axis=1), full)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_paged_decode_shard_map_parity(impl):
    fn = _decode_fn(impl)
    rng = np.random.default_rng(1)
    q, pk, pv, table, lengths = _rand_paged(rng)
    full = np.asarray(fn(q, pk, pv, table, lengths, 0))
    mesh = make_serving_mesh(2)
    sharded = jax.shard_map(
        lambda q_, k_, v_, t_, l_, w_: fn(q_, k_, v_, t_, l_, w_),
        mesh=mesh,
        in_specs=(P(None, "model", None), P(None, None, "model", None),
                  P(None, None, "model", None), P(None, None), P(None),
                  P()),
        out_specs=P(None, "model", None), check_vma=False)
    got = np.asarray(sharded(q, pk, pv, table, lengths, jnp.int32(0)))
    np.testing.assert_array_equal(got, full)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_paged_prefill_shard_map_parity(impl):
    fn = _prefill_fn(impl)
    rng = np.random.default_rng(2)
    b, s, hq, hkv, d, ps, maxp = 3, 8, 4, 2, 16, 8, 4
    q = rng.standard_normal((b, s, hq, d)).astype(np.float32)
    pk = rng.standard_normal((13, ps, hkv, d)).astype(np.float32)
    pv = rng.standard_normal((13, ps, hkv, d)).astype(np.float32)
    table = rng.permutation(np.arange(1, 13))[:b * maxp] \
        .reshape(b, maxp).astype(np.int32)
    starts = rng.integers(0, ps, size=b).astype(np.int32)
    counts = rng.integers(1, s + 1, size=b).astype(np.int32)
    full = np.asarray(fn(q, pk, pv, table, starts, counts, 0))
    mesh = make_serving_mesh(2)
    sharded = jax.shard_map(
        lambda q_, k_, v_, t_, s_, c_, w_: fn(q_, k_, v_, t_, s_, c_, w_),
        mesh=mesh,
        in_specs=(P(None, None, "model", None),
                  P(None, None, "model", None),
                  P(None, None, "model", None),
                  P(None, None), P(None), P(None), P()),
        out_specs=P(None, None, "model", None), check_vma=False)
    got = np.asarray(sharded(q, pk, pv, table, starts, counts,
                             jnp.int32(0)))
    if impl == "ref":
        np.testing.assert_array_equal(got, full)
    else:
        # interpret mode lowers the kernel body through XLA:CPU, whose
        # within-head reduction strategy can depend on the head extent
        # (n_kv=1 per shard vs 2 unsharded) — 1-ulp drift, not a
        # sharding error.  On TPU the per-head blocks are independent.
        np.testing.assert_allclose(got, full, rtol=3e-7, atol=3e-7)


# ---------------------------------------------------------------------------
# engine differential fuzz: mesh on == mesh off, bitwise
# ---------------------------------------------------------------------------

def _workload(rng, vocab):
    n_req = int(rng.integers(2, 6))
    sys_len = int(rng.integers(0, 22))
    sys_p = rng.integers(2, vocab, size=sys_len).astype(np.int32)
    max_new = int(rng.integers(1, 7))
    prompts, prios = [], []
    for _ in range(n_req):
        r = rng.random()
        if prompts and r < 0.15:
            prompts.append(prompts[int(rng.integers(len(prompts)))].copy())
        elif sys_len and r < 0.75:
            tail = rng.integers(2, vocab, size=int(
                rng.integers(1, 9))).astype(np.int32)
            prompts.append(np.concatenate([sys_p, tail]))
        else:
            prompts.append(rng.integers(
                2, vocab, size=int(rng.integers(1, 25))).astype(np.int32))
        prios.append(int(rng.integers(0, 3)))
    return prompts, prios, max_new


def _sampling_params(rng, max_new):
    t = [0.0, 0.7, 1.3][int(rng.integers(3))]
    return SamplingParams(
        temperature=t,
        top_k=[0, 5, 40][int(rng.integers(3))],
        top_p=[1.0, 0.9][int(rng.integers(2))],
        seed=int(rng.integers(10 ** 6)),
        max_tokens=max_new)


def _run(m, params, prompts, prios, max_new, *, mesh, prefix, chunk,
         num_pages, sampling=None, batched=True, impl="ref"):
    eng = Engine(m, params, max_concurrency=3, max_len=MAX_LEN,
                 eos_id=-1, page_size=PAGE, num_pages=num_pages,
                 prefix_cache=prefix, prefill_chunk=chunk,
                 batched_prefill=batched, attn_impl=impl, mesh=mesh,
                 scheduler=SchedulerConfig(policy="priority",
                                           max_queue=64))
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new,
                    sampling=sampling[i] if sampling else None,
                    priority=prios[i]) for i, p in enumerate(prompts)]
    accepted = {r.uid for r in reqs if eng.submit(r)}
    eng.run()
    eng.kv.leak_check()
    assert accepted == set(range(len(prompts)))
    return {r.uid: list(r.tokens) for r in reqs}, eng


@settings(max_examples=SHARD_EXAMPLES, deadline=None)
@given(seed=st.integers(0, 10 ** 6))
def test_fuzz_sharded_token_identical(tiny, seed):
    """The existing differential-fuzz axes (prefix on/off, chunk sizes,
    batched prefill, pools to oversubscription, seeded sampling) with
    the mesh as one more arm: tp=2 and tp=4 (non-divisible kv heads ->
    degrades to replication) both reproduce the single-device tokens."""
    m, params = tiny
    rng = np.random.default_rng(seed)
    prompts, prios, max_new = _workload(rng, TINY.vocab_size)
    sps = [_sampling_params(rng, max_new) for _ in prompts] \
        if rng.random() < 0.5 else None
    num_pages = int(rng.integers(10, 26))
    chunk = [None, 3, PAGE][int(rng.integers(3))]
    prefix = bool(rng.integers(2))
    batched = bool(rng.integers(2))
    kw = dict(prefix=prefix, chunk=chunk, num_pages=num_pages,
              sampling=sps, batched=batched)
    base, _ = _run(m, params, prompts, prios, max_new, mesh=None, **kw)
    for tp in (2, 4):
        got, _ = _run(m, params, prompts, prios, max_new,
                      mesh=make_serving_mesh(tp), **kw)
        assert got == base, (tp, chunk, num_pages, prefix, batched)


def test_sharded_pallas_impl_token_identical(tiny):
    """The pallas (interpret-mode) kernels inside shard_map reproduce
    the single-device pallas tokens."""
    m, params = tiny
    rng = np.random.default_rng(5)
    prompts, prios, max_new = _workload(rng, TINY.vocab_size)
    kw = dict(prefix=True, chunk=PAGE, num_pages=None, impl="pallas")
    base, _ = _run(m, params, prompts, prios, max_new, mesh=None, **kw)
    got, _ = _run(m, params, prompts, prios, max_new,
                  mesh=make_serving_mesh(2), **kw)
    assert got == base


def test_sharded_hashed_banks_token_identical(tiny_hashed):
    """Hashed banks shard over "model" (materialize is a pure gather —
    exact); the compressed config matches bitwise too."""
    m, params = tiny_hashed
    rng = np.random.default_rng(6)
    prompts, prios, max_new = _workload(rng, TINY.vocab_size)
    kw = dict(prefix=True, chunk=None, num_pages=None)
    base, _ = _run(m, params, prompts, prios, max_new, mesh=None, **kw)
    got, _ = _run(m, params, prompts, prios, max_new,
                  mesh=make_serving_mesh(2), **kw)
    assert got == base


def test_sharded_preemption_token_identical(tiny):
    """Oversubscribed pool forces preemption + recompute through the
    sharded gather/copy paths: tokens still match the single-device
    tight pool AND the fully-provisioned run."""
    m, params = tiny
    rng = np.random.default_rng(11)
    short = [rng.integers(2, TINY.vocab_size, size=6).astype(np.int32)
             for _ in range(2)]
    long_p = rng.integers(2, TINY.vocab_size, size=40).astype(np.int32)
    prompts = short + [long_p]
    prios = [0] * len(prompts)
    full, _ = _run(m, params, prompts, prios, 16, mesh=None,
                   prefix=True, chunk=4, num_pages=None)
    tight, _ = _run(m, params, prompts, prios, 16, mesh=None,
                    prefix=True, chunk=4, num_pages=10)
    tight_mesh, eng = _run(m, params, prompts, prios, 16,
                           mesh=make_serving_mesh(2),
                           prefix=True, chunk=4, num_pages=10)
    assert tight == full and tight_mesh == tight
    assert eng.stats()["preemptions"] > 0, \
        "pool sizing did not force a preemption"


# ---------------------------------------------------------------------------
# placement, metrics, guards
# ---------------------------------------------------------------------------

def _flat_axes(spec):
    out = []
    for ax in tuple(spec):
        if isinstance(ax, (tuple, list)):
            out.extend(ax)
        elif ax is not None:
            out.append(ax)
    return out


def test_sharded_placement_and_metrics(tiny_hashed):
    """Pool shards on the KV-head axis, banks shard over "model",
    dense weights replicate; engine.shard.* gauges/counters exist and
    count dispatches — and only on mesh engines."""
    m, params = tiny_hashed
    mesh = make_serving_mesh(2)
    eng = Engine(m, params, max_concurrency=2, max_len=MAX_LEN,
                 eos_id=-1, page_size=PAGE, mesh=mesh)
    # page pool: axis 3 of (nl, P, ps, Hkv, hd) on "model"
    for leaf in (eng.pages["k"], eng.pages["v"]):
        s = tuple(leaf.sharding.spec)
        assert len(s) > 3 and s[3] == "model", s
    # params: banks sharded, everything else replicated
    bank_axes, dense_axes = [], []
    specs = m.pspecs()

    def collect(spec, p):
        axes = _flat_axes(p.sharding.spec)
        is_bank = any(isinstance(ax, (tuple, list)) and "tp" in ax
                      for ax in spec)
        (bank_axes if is_bank else dense_axes).append(axes)
        return p

    jax.tree.map(collect, specs, eng.params,
                 is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert bank_axes, "hashed config produced no bank leaves"
    assert all(axes == ["model"] for axes in bank_axes), bank_axes
    assert all(axes == [] for axes in dense_axes), \
        "a dense weight was sharded (o-proj psum would break identity)"

    snap = eng.metrics.snapshot()
    assert snap["engine.shard.devices"] == 2
    assert snap["engine.shard.tp"] == 2
    eng.submit(Request(uid=0, prompt=np.arange(12, dtype=np.int32) + 2,
                       max_new_tokens=4))
    eng.run()
    snap = eng.metrics.snapshot()
    assert snap["engine.shard.decode_dispatches"] > 0
    assert snap["engine.shard.prefill_dispatches"] > 0

    plain = Engine(m, params, max_concurrency=2, max_len=MAX_LEN,
                   eos_id=-1, page_size=PAGE)
    assert not any(k.startswith("engine.shard.")
                   for k in plain.metrics.snapshot())


def test_mesh_guards(tiny):
    m, params = tiny
    mesh = make_serving_mesh(2)
    with pytest.raises(ValueError, match="paged"):
        Engine(m, params, max_concurrency=2, max_len=MAX_LEN,
               eos_id=-1, paged=False, mesh=mesh)
    from repro.serving.draft import build_draft
    _, dm, dp = build_draft(TINY, params, "1/8")
    with pytest.raises(ValueError, match="speculative"):
        Engine(m, params, max_concurrency=2, max_len=MAX_LEN,
               eos_id=-1, page_size=PAGE, draft=(dm, dp), mesh=mesh)


def test_make_serving_mesh_rejects_oversized():
    with pytest.raises(ValueError, match="devices"):
        make_serving_mesh(jax.device_count() + 1)
