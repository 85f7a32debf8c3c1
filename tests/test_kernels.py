"""Pallas kernel sweep: every kernel vs the pure-jnp ref.py oracle,
across shapes, modes, dtypes, and compression factors (interpret mode).
Covers the hashed decompress-GEMM kernels and the paged-gather decode
attention kernel behind the continuous-batching engine."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HashedSpec, init
from repro.kernels import ops, ref
from repro.kernels import hashed_matmul as hk
from repro.kernels import paged_attention as PA
from repro.kernels.paged_attention import paged_decode_attention

ELEMENT_CASES = [
    # (rows, cols, compression, panel_cols, block)
    (128, 128, 0.5, 0, (32, 128, 128)),
    (256, 384, 0.125, 0, (64, 128, 128)),
    (256, 384, 0.125, 128, (64, 128, 128)),
    (512, 256, 1.0 / 64, 256, (128, 128, 128)),
    (384, 512, 0.25, 256, (16, 128, 256)),
]

BLOCK_CASES = [
    # (rows, cols, compression, block_shape)
    (256, 512, 0.125, (128, 128)),
    (384, 256, 0.25, (128, 64)),
    (256, 384, 0.3, (64, 128)),
    (512, 512, 1.0 / 16, (128, 128)),
]


def _mk(rows, seed, batch=(64,), dtype=jnp.float32):
    return jax.random.normal(
        jax.random.PRNGKey(seed), batch + (rows,)).astype(dtype)


@pytest.mark.parametrize("rows,cols,c,panel,block", ELEMENT_CASES)
def test_element_fwd(rows, cols, c, panel, block):
    spec = HashedSpec((rows, cols), c, mode="element", seed=rows + cols,
                      panel_cols=panel)
    w = init(jax.random.PRNGKey(0), spec)
    x = _mk(rows, 1)
    got = ops.hashed_matmul(x, w, spec, block=block)
    want = ref.hashed_matmul_ref(x, w, spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rows,cols,c,panel,block", ELEMENT_CASES[:3])
def test_element_grads(rows, cols, c, panel, block):
    spec = HashedSpec((rows, cols), c, mode="element", seed=3,
                      panel_cols=panel)
    w = init(jax.random.PRNGKey(0), spec)
    x = _mk(rows, 2, batch=(3, 40))

    gk = jax.grad(lambda x, w: (ops.hashed_matmul(x, w, spec, block=block)
                                ** 2).sum(), argnums=(0, 1))(x, w)
    gr = jax.grad(lambda x, w: (ref.hashed_matmul_ref(x, w, spec)
                                ** 2).sum(), argnums=(0, 1))(x, w)
    for a, b in zip(gk, gr):
        scale = max(1.0, float(np.abs(np.asarray(b)).max()))
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows,cols,c,bs", BLOCK_CASES)
def test_block_fwd(rows, cols, c, bs):
    spec = HashedSpec((rows, cols), c, mode="block", seed=rows ^ cols,
                      block_shape=bs)
    w = init(jax.random.PRNGKey(0), spec)
    x = _mk(rows, 1, batch=(2, 37))
    got = ops.hashed_matmul(x, w, spec)
    want = ref.hashed_matmul_ref(x, w, spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rows,cols,c,bs", BLOCK_CASES)
def test_block_grads(rows, cols, c, bs):
    spec = HashedSpec((rows, cols), c, mode="block", seed=17, block_shape=bs)
    w = init(jax.random.PRNGKey(0), spec)
    x = _mk(rows, 2, batch=(53,))
    gk = jax.grad(lambda x, w: (ops.hashed_matmul(x, w, spec) ** 2).sum(),
                  argnums=(0, 1))(x, w)
    gr = jax.grad(lambda x, w: (ref.hashed_matmul_ref(x, w, spec) ** 2).sum(),
                  argnums=(0, 1))(x, w)
    for a, b in zip(gk, gr):
        scale = max(1.0, float(np.abs(np.asarray(b)).max()))
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode,dtype", itertools.product(
    ["element", "block"], [jnp.float32, jnp.bfloat16]))
def test_dtypes(mode, dtype):
    if mode == "element":
        spec = HashedSpec((256, 256), 0.125, mode=mode, seed=5,
                          panel_cols=128)
    else:
        spec = HashedSpec((256, 256), 0.125, mode=mode, seed=5,
                          block_shape=(128, 128))
    w = init(jax.random.PRNGKey(0), spec, dtype=dtype)
    x = _mk(256, 1, dtype=dtype)
    got = np.asarray(ops.hashed_matmul(x, w, spec), np.float32)
    want = np.asarray(ref.hashed_matmul_ref(x, w, spec), np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 10)
    assert got.dtype == np.float32  # cast for compare; kernel out == in dtype
    assert ops.hashed_matmul(x, w, spec).dtype == dtype


def test_element_mode_refuses_compiled_path():
    """Element mode has no TPU kernel (Mosaic lowers only 2-D gathers):
    asking for the compiled path raises instead of falling back."""
    spec = HashedSpec((128, 256), 0.25, mode="element", seed=1)
    w = init(jax.random.PRNGKey(0), spec)
    with pytest.raises(NotImplementedError, match="element-mode"):
        ops.hashed_matmul(_mk(128, 0), w, spec, interpret=False)


def test_row_padding():
    """Row counts that don't divide the block are padded then sliced."""
    spec = HashedSpec((128, 256), 0.25, mode="element", seed=1)
    w = init(jax.random.PRNGKey(0), spec)
    for m in (1, 7, 100, 129):
        x = _mk(128, m, batch=(m,))
        got = ops.hashed_matmul(x, w, spec)
        want = ref.hashed_matmul_ref(x, w, spec)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_transpose_kernels_direct():
    """dx kernels (transpose-forward) vs oracle, both modes."""
    spec_e = HashedSpec((256, 384), 0.125, mode="element", seed=11,
                        panel_cols=128)
    w = init(jax.random.PRNGKey(0), spec_e)
    g = _mk(384, 4, batch=(128,))
    got = hk.element_matmul(g, w, spec_e, block=(128, 128, 128),
                            transpose=True, interpret=True)
    want = ref.hashed_matmul_t_ref(g, w, spec_e)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    spec_b = HashedSpec((256, 384), 0.125, mode="block", seed=11,
                        block_shape=(128, 128))
    wb = init(jax.random.PRNGKey(0), spec_b)
    got = hk.block_matmul(g, wb, spec_b, bm=128, transpose=True,
                          interpret=True)
    want = ref.hashed_matmul_t_ref(g, wb, spec_b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_dw_kernels_direct():
    x = _mk(256, 5, batch=(128,))
    g = _mk(384, 6, batch=(128,))
    spec_e = HashedSpec((256, 384), 0.125, mode="element", seed=23,
                        panel_cols=128)
    got = hk.element_dw(x, g, spec_e, block=(128, 128, 128), interpret=True)
    want = ref.hashed_dw_ref(x, g, spec_e)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)

    spec_b = HashedSpec((256, 384), 0.125, mode="block", seed=23,
                        block_shape=(128, 128))
    got = hk.block_dw(x, g, spec_b, bm=128, interpret=True)
    want = ref.hashed_dw_ref(x, g, spec_b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# paged-gather decode attention (serving hot path)
# ---------------------------------------------------------------------------

def _tol(dtype):
    """Shared parity tolerances: tight fp32, loose bf16 (same ladder as
    the GEMM dtype sweep above)."""
    return (2e-5, 2e-4) if dtype == jnp.float32 else (3e-2, 3e-1)


def _mk_paged(seed, *, b, ps, maxp, n_kv, g, d, dtype=jnp.float32,
              lengths=None):
    """Random page pools + per-row page tables with DISTINCT live pages
    (the allocator invariant) + ragged lengths."""
    rng = np.random.default_rng(seed)
    num_pages = 1 + b * maxp                       # page 0 = trash
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pk = jax.random.normal(ks[0], (num_pages, ps, n_kv, d)).astype(dtype)
    pv = jax.random.normal(ks[1], (num_pages, ps, n_kv, d)).astype(dtype)
    q = jax.random.normal(ks[2], (b, n_kv * g, d)).astype(dtype)
    if lengths is None:
        lengths = rng.integers(1, maxp * ps + 1, size=b)
    lengths = np.asarray(lengths, np.int32)
    table = np.zeros((b, maxp), np.int32)
    pool = list(range(1, num_pages))
    rng.shuffle(pool)
    for i in range(b):
        n = -(-int(lengths[i]) // ps)
        for j in range(n):
            table[i, j] = pool.pop()
    return q, pk, pv, jnp.asarray(table), jnp.asarray(lengths)


@pytest.mark.parametrize("ps,maxp,g,dtype", [
    (4, 3, 1, jnp.float32),
    (8, 4, 2, jnp.float32),
    (16, 2, 4, jnp.float32),
    (8, 3, 2, jnp.bfloat16),
    (16, 4, 1, jnp.bfloat16),
])
def test_paged_attention_kernel_vs_ref(ps, maxp, g, dtype):
    """Kernel (online softmax page walk) vs gather-then-attend oracle,
    across page sizes, ragged lengths, GQA groups, and dtypes."""
    q, pk, pv, table, lengths = _mk_paged(
        ps * maxp + g, b=3, ps=ps, maxp=maxp, n_kv=2, g=g, d=16,
        dtype=dtype)
    got = paged_decode_attention(q, pk, pv, table, lengths,
                                 interpret=True)
    want = ref.paged_attention_ref(q, pk, pv, table, lengths)
    assert got.dtype == q.dtype
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("window", [1, 3, 8])
def test_paged_attention_sliding_window(window):
    """Windowed masking parity (the gemma local-attention layers)."""
    q, pk, pv, table, lengths = _mk_paged(
        11 + window, b=2, ps=4, maxp=4, n_kv=2, g=2, d=8)
    got = paged_decode_attention(q, pk, pv, table, lengths,
                                 jnp.int32(window), interpret=True)
    want = ref.paged_attention_ref(q, pk, pv, table, lengths, window)
    rtol, atol = _tol(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)


def test_paged_attention_matches_dense_attend():
    """Stronger oracle: scatter a dense KV cache into pages and compare
    both paged paths against the engine's dense attention (attend with
    per-row kv_valid), which the serving parity tests trust."""
    from repro.nn import attention as ATT
    b, ps, maxp, n_kv, g, d = 2, 8, 3, 2, 2, 16
    q, pk, pv, table, lengths = _mk_paged(
        5, b=b, ps=ps, maxp=maxp, n_kv=n_kv, g=g, d=d)
    t = maxp * ps
    # gather the paged layout back to (B, T, n_kv, d) dense
    kd = jnp.take(pk, table, axis=0).reshape(b, t, n_kv, d)
    vd = jnp.take(pv, table, axis=0).reshape(b, t, n_kv, d)
    plan = ATT.AttentionPlan(d_model=n_kv * g * d, num_heads=n_kv * g,
                             num_kv_heads=n_kv, head_dim=d,
                             dtype=jnp.float32)
    q_pos = (lengths - 1)[:, None]                 # (B, 1)
    kv_valid = jnp.arange(t)[None, :] < lengths[:, None]
    want = ATT.attend(plan, q[:, None], kd, vd, q_pos, jnp.arange(t),
                      kv_valid)[:, 0]              # (B, Hq*D)
    for impl, out in [
        ("ref", ref.paged_attention_ref(q, pk, pv, table, lengths)),
        ("pallas", paged_decode_attention(q, pk, pv, table, lengths,
                                          interpret=True)),
    ]:
        np.testing.assert_allclose(
            np.asarray(out).reshape(b, -1), np.asarray(want),
            rtol=2e-5, atol=2e-4, err_msg=impl)


def test_paged_attention_idle_rows_finite():
    """length == 0 rows (idle decode slots, whole table on the trash
    page) must produce finite output — no 0/0 softmax."""
    q, pk, pv, table, lengths = _mk_paged(
        9, b=3, ps=4, maxp=2, n_kv=2, g=1, d=8, lengths=[5, 0, 3])
    table = table.at[1, :].set(0)
    for out in (ref.paged_attention_ref(q, pk, pv, table, lengths),
                paged_decode_attention(q, pk, pv, table, lengths,
                                       interpret=True)):
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_array_equal(np.asarray(out)[1], 0.0)


# (page size, table slots, pages per block the kernel derives): blocks
# of several pages (16 x 16 = 256 tokens, two a row), and of one page
# (37 slots is prime: no block of 2-36 pages divides the table)
LIVE_SHAPES = [(16, 32, 16), (8, 37, 1)]


def _edge_lengths(ps, maxp, ppb):
    """Idle, one token, one page, either side of the first block's end,
    and the whole table."""
    bk = ps * ppb
    return [0, 1, ps, bk - 1, bk, bk + 1, maxp * ps]


def _mk_live(ps, maxp, ppb, g, dtype, seed):
    n_kv, d = 2, 16
    assert PA.pages_per_block(maxp, ps, n_kv, d,
                               jnp.dtype(dtype).itemsize) == ppb
    return _mk_paged(seed, b=7, ps=ps, maxp=maxp, n_kv=n_kv, g=g, d=d,
                     dtype=dtype, lengths=_edge_lengths(ps, maxp, ppb))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("g", [1, 2])
@pytest.mark.parametrize("ps,maxp,ppb", LIVE_SHAPES)
def test_paged_attention_live_blocks_vs_ref(ps, maxp, ppb, g, dtype):
    """The block walk at the edges of a row's span: empty rows, one
    token, a page, a block boundary +- 1 and the full table, through
    scrambled pages, in blocks of one page and of several."""
    q, pk, pv, table, lengths = _mk_live(ps, maxp, ppb, g, dtype, 31 + g)
    got = paged_decode_attention(q, pk, pv, table, lengths, interpret=True)
    want = ref.paged_attention_ref(q, pk, pv, table, lengths)
    assert got.dtype == q.dtype
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=atol)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[0], 0.0)


@pytest.mark.parametrize("window", [1, 3, 8])
@pytest.mark.parametrize("ps,maxp,ppb", LIVE_SHAPES)
def test_paged_attention_live_blocks_window(ps, maxp, ppb, window):
    q, pk, pv, table, lengths = _mk_live(ps, maxp, ppb, 2, jnp.float32,
                                         41 + window)
    got = paged_decode_attention(q, pk, pv, table, lengths,
                                 jnp.int32(window), interpret=True)
    want = ref.paged_attention_ref(q, pk, pv, table, lengths, window)
    rtol, atol = _tol(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("window", [0, 3])
@pytest.mark.parametrize("ps,maxp,ppb", LIVE_SHAPES)
def test_paged_attention_reads_nothing_past_the_length(ps, maxp, ppb,
                                                       window):
    """Every pool slot no row attends (past each row's length, outside
    its window, the trash page, pages no row owns) holds NaN: the
    kernel's output stays finite and equal to the oracle's on the
    untouched pool, so nothing there was read into the result.  And
    table slots past each row's length name pages beyond the pool: the
    interpreter raises on a copy from one, so none was started."""
    q, pk, pv, table, lengths = _mk_live(ps, maxp, ppb, 2, jnp.float32, 53)
    attended = np.zeros(pk.shape[:2], bool)
    tbl = np.asarray(table).copy()
    for b, n in enumerate(np.asarray(lengths)):
        lo = max(0, n - window) if window else 0
        for t in range(lo, n):
            attended[tbl[b, t // ps], t % ps] = True
        tbl[b, -(-n // ps):] = pk.shape[0] + 7
    nan = jnp.asarray(~attended)[:, :, None, None]
    got = paged_decode_attention(q, jnp.where(nan, jnp.nan, pk),
                                 jnp.where(nan, jnp.nan, pv),
                                 jnp.asarray(tbl), lengths,
                                 jnp.int32(window), interpret=True)
    want = ref.paged_attention_ref(q, pk, pv, table, lengths, window)
    assert np.isfinite(np.asarray(got)).all()
    rtol, atol = _tol(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)


def _mk_shared_paged(seed, *, b, ps, maxp, n_kv, g, d, shared_pages,
                     dtype=jnp.float32):
    """Like _mk_paged but every row's table ALIASES the same leading
    ``shared_pages`` physical pages (prefix sharing), with private pages
    after; lengths all extend past the shared region."""
    rng = np.random.default_rng(seed)
    num_pages = 1 + shared_pages + b * (maxp - shared_pages)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pk = jax.random.normal(ks[0], (num_pages, ps, n_kv, d)).astype(dtype)
    pv = jax.random.normal(ks[1], (num_pages, ps, n_kv, d)).astype(dtype)
    q = jax.random.normal(ks[2], (b, n_kv * g, d)).astype(dtype)
    lengths = rng.integers(shared_pages * ps + 1, maxp * ps + 1, size=b)
    lengths = np.asarray(lengths, np.int32)
    table = np.zeros((b, maxp), np.int32)
    pool = list(range(1 + shared_pages, num_pages))
    rng.shuffle(pool)
    for i in range(b):
        table[i, :shared_pages] = np.arange(1, 1 + shared_pages)
        n = -(-int(lengths[i]) // ps)
        for j in range(shared_pages, n):
            table[i, j] = pool.pop()
    return q, pk, pv, jnp.asarray(table), jnp.asarray(lengths)


@pytest.mark.parametrize("shared,window", [(1, 0), (2, 0), (3, 0), (2, 5)])
def test_paged_attention_shared_prefix_tables(shared, window):
    """Prefix sharing aliases one physical page into many rows' tables;
    kernel and plain oracle must read through the aliases bitwise as if
    each row owned private copies (the shared-page-aware oracle)."""
    q, pk, pv, table, lengths = _mk_shared_paged(
        23 + shared, b=3, ps=4, maxp=4, n_kv=2, g=2, d=16,
        shared_pages=shared)
    want = ref.paged_attention_shared_ref(q, pk, pv, table, lengths,
                                          window)
    got_ref = ref.paged_attention_ref(q, pk, pv, table, lengths, window)
    np.testing.assert_array_equal(np.asarray(got_ref), np.asarray(want))
    got_pal = paged_decode_attention(q, pk, pv, table, lengths,
                                     jnp.int32(window), interpret=True)
    rtol, atol = _tol(jnp.float32)
    np.testing.assert_allclose(np.asarray(got_pal), np.asarray(want),
                               rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# chunked prefill parity (serving engine prefill path)
# ---------------------------------------------------------------------------

from repro.configs.base import ArchConfig  # noqa: E402
from repro.models import build as build_model  # noqa: E402
from repro.serving.engine import Engine, _copy_pages  # noqa: E402

_PS = 8
_CHUNK_MAX_LEN = 64


def _chunk_cfg(dtype, n_kv, window=0, global_every=0):
    return ArchConfig(
        name=f"tiny-chunk-{dtype}-{n_kv}-{window}", family="dense",
        arch_kind="decoder", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=n_kv, head_dim=16, d_ff=128, vocab_size=128,
        remat=False, dtype=dtype, sliding_window=window,
        global_every=global_every)


def _chunk_shape(pos, c, chunk):
    """The engine's own chunk-shape ladder (invoked unbound on a stub
    so the test exercises exactly the shipped compile shapes — a
    hand-copied ladder here could silently drift)."""
    import types
    stub = types.SimpleNamespace(max_len=_CHUNK_MAX_LEN,
                                 prefill_chunk=chunk,
                                 BUCKET=Engine.BUCKET,
                                 _SUB_BUCKETS=Engine._SUB_BUCKETS)
    return Engine._chunk_shape(stub, pos, c)


def _prefill_in_chunks(model, params, feed, chunk):
    """Replicate the engine's chunked prefill: bucketed pad-and-mask
    chunks against a carried scratch cache, each chunk's pages landed
    through the engine's masked page-write."""
    prefill = jax.jit(model.prefill)
    maxp = _CHUNK_MAX_LEN // _PS
    pages = model.init_paged_cache(1 + maxp, _PS)
    table = np.arange(1, 1 + maxp, dtype=np.int32)     # row owns 1..maxp
    cache = model.init_cache(1, _CHUNK_MAX_LEN)
    pos, t = 0, len(feed)
    logits = None
    while pos < t:
        c = min(chunk, t - pos)
        start, bucket, real = _chunk_shape(pos, c, chunk)
        if start != pos:                 # slid-back window
            cache = dict(cache, index=jnp.asarray(start, jnp.int32))
        prompt = np.pad(feed[start:start + real], (0, bucket - real))
        logits, cache = prefill(params, {
            "tokens": jnp.asarray(prompt[None, :]), "cache": cache,
            "length": jnp.asarray(real, jnp.int32)})
        lo, hi = pos // _PS, -(-(pos + c) // _PS)
        wpids = np.zeros((maxp,), np.int32)
        wpids[lo:hi] = table[lo:hi]
        pages = _copy_pages(pages, cache["k"], cache["v"],
                            jnp.asarray(wpids))
        pos += c
    return logits, cache, pages


@pytest.mark.parametrize("dtype,n_kv,window,ge", [
    (jnp.float32, 2, 0, 0),
    (jnp.float32, 1, 0, 0),
    (jnp.bfloat16, 2, 0, 0),
    (jnp.bfloat16, 1, 0, 0),
    (jnp.float32, 2, 6, 2),          # sliding-window + global mix
])
def test_chunked_prefill_bitwise_parity(dtype, n_kv, window, ge):
    """A prompt prefilled in chunks of {1, ps-1, ps, 3*ps} produces
    bitwise-identical KV pages and logits to monolithic prefill — every
    query attends over the same full-width cache buffer either way, so
    chunking (and therefore prefix reuse, which serves previously
    chunk-computed pages) cannot perturb greedy decoding."""
    name = "float32" if dtype == jnp.float32 else "bfloat16"
    model = build_model(_chunk_cfg(name, n_kv, window, ge))
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(7)
    t = 3 * _PS + 3                                     # partial tail page
    feed = rng.integers(2, 128, size=t).astype(np.int32)

    logits_m, cache_m, pages_m = _prefill_in_chunks(model, params, feed, t)
    for chunk in (1, _PS - 1, _PS, 3 * _PS):
        logits_c, cache_c, pages_c = _prefill_in_chunks(
            model, params, feed, chunk)
        np.testing.assert_array_equal(
            np.asarray(logits_m, np.float32),
            np.asarray(logits_c, np.float32), err_msg=f"chunk={chunk}")
        for key in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(cache_m[key], np.float32)[:, :, :t],
                np.asarray(cache_c[key], np.float32)[:, :, :t],
                err_msg=f"cache {key} chunk={chunk}")
            # pages compare on every readable position: the row's table
            # in order, flattened back to sequence layout, up to the
            # feed.  Offsets past the feed are pad garbage that lengths
            # masking keeps unreadable (and differ by bucket pattern).
            pm = np.asarray(pages_m[key], np.float32)
            pc = np.asarray(pages_c[key], np.float32)
            nl, _, ps, hkv, hd = pm.shape
            np.testing.assert_array_equal(
                pm[:, 1:].reshape(nl, -1, hkv, hd)[:, :t],
                pc[:, 1:].reshape(nl, -1, hkv, hd)[:, :t],
                err_msg=f"pages {key} chunk={chunk}")


def test_kernel_matches_core_paths():
    """pallas == scan == materialize through the core dispatcher."""
    from repro.core import matmul
    spec = HashedSpec((256, 256), 0.25, mode="element", seed=31,
                      panel_cols=128)
    w = init(jax.random.PRNGKey(0), spec)
    x = _mk(256, 7, batch=(32,))
    y_pal = matmul(x, w, spec, path="pallas")
    y_scan = matmul(x, w, spec, path="scan")
    y_mat = matmul(x, w, spec, path="materialize")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_mat),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_mat),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# batched ragged flash-prefill (serving batched prefill path)
# ---------------------------------------------------------------------------

from repro.kernels.flash_prefill import paged_prefill_attention  # noqa: E402


def _mk_ragged_prefill(seed, *, ps, maxp, n_kv, g, d, starts, counts,
                       shared_pages=0, dtype=jnp.float32):
    """Random pools + tables + a ragged (starts, counts) chunk layout.

    Row b's chunk queries sit at positions [starts[b], starts[b]+counts[b]);
    its full history [0, starts[b]+counts[b]) — old prefix AND the fresh
    chunk's K/V — is already in the pool (the engine scatters the chunk
    before attending).  With ``shared_pages`` the leading pages ALIAS one
    physical page set across rows (prefix sharing / mid-COW layout)."""
    starts = np.asarray(starts, np.int32)
    counts = np.asarray(counts, np.int32)
    b = len(starts)
    s_blk = int(counts.max()) if counts.size else 1
    rng = np.random.default_rng(seed)
    num_pages = 1 + shared_pages + b * (maxp - shared_pages)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pk = jax.random.normal(ks[0], (num_pages, ps, n_kv, d)).astype(dtype)
    pv = jax.random.normal(ks[1], (num_pages, ps, n_kv, d)).astype(dtype)
    q = jax.random.normal(ks[2], (b, s_blk, n_kv * g, d)).astype(dtype)
    table = np.zeros((b, maxp), np.int32)
    pool = list(range(1 + shared_pages, num_pages))
    rng.shuffle(pool)
    for i in range(b):
        table[i, :shared_pages] = np.arange(1, 1 + shared_pages)
        for j in range(shared_pages, maxp):
            table[i, j] = pool.pop()
    return (q, pk, pv, jnp.asarray(table), jnp.asarray(starts),
            jnp.asarray(counts))


def _dense_prefill_oracle(q, pk, pv, table, starts, counts, window=0):
    """Row-by-row dense oracle through ATT.attend (the path the serving
    parity tests trust): gather each row's pages to a dense cache and
    attend its real chunk queries with causal + length masking."""
    from repro.nn import attention as ATT
    b, s_blk, hq, d = q.shape
    _, ps, n_kv, _ = pk.shape
    t = table.shape[1] * ps
    plan = ATT.AttentionPlan(d_model=hq * d, num_heads=hq,
                             num_kv_heads=n_kv, head_dim=d,
                             dtype=q.dtype,
                             sliding_window=int(window))
    outs = np.zeros((b, s_blk, hq, d), np.float32)
    kv_pos = jnp.arange(t)
    for i in range(b):
        n = int(counts[i])
        if n == 0:
            continue
        kd = jnp.take(pk, table[i], axis=0).reshape(1, t, n_kv, d)
        vd = jnp.take(pv, table[i], axis=0).reshape(1, t, n_kv, d)
        q_pos = int(starts[i]) + jnp.arange(n)
        kv_valid = kv_pos < int(starts[i]) + n
        o = ATT.attend(plan, q[i:i + 1, :n], kd, vd, q_pos, kv_pos,
                       kv_valid)
        outs[i, :n] = np.asarray(o, np.float32).reshape(n, hq, d)
    return outs


_PS_RAGGED = 8
_RAGGED_CASES = [
    # counts sweep: 1, ps-1, ps, 3*ps, ragged mixes; starts exercise
    # page-offset rags (mid-page, boundary, zero)
    ([0, 0, 0], [1, _PS_RAGGED - 1, _PS_RAGGED]),
    ([0], [3 * _PS_RAGGED]),
    ([5, 8, 0, 13], [7, 9, 24, 1]),
    ([3, 17, 10], [1, 6, 22]),
]


@pytest.mark.parametrize("starts,counts", _RAGGED_CASES)
@pytest.mark.parametrize("g,dtype", [(1, jnp.float32), (2, jnp.float32),
                                     (4, jnp.bfloat16)])
def test_flash_prefill_kernel_vs_ref_vs_dense(starts, counts, g, dtype):
    """Ragged chunk layouts: Pallas kernel vs paged_prefill_ref (must be
    close) and ref vs the dense attend oracle, across GQA groups and
    dtypes.  Pad slots must come back zero."""
    q, pk, pv, table, st_, cn = _mk_ragged_prefill(
        11 + g + len(counts), ps=_PS_RAGGED, maxp=4, n_kv=2, g=g, d=16,
        starts=starts, counts=counts, dtype=dtype)
    want = ref.paged_prefill_ref(q, pk, pv, table, st_, cn)
    got = paged_prefill_attention(q, pk, pv, table, st_, cn,
                                  interpret=True)
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=atol)
    dense = _dense_prefill_oracle(q, pk, pv, table, st_, cn)
    b, s_blk, hq, d = q.shape
    wantf = np.asarray(want, np.float32)
    for i in range(b):
        n = int(cn[i])
        np.testing.assert_allclose(wantf[i, :n], dense[i, :n],
                                   rtol=rtol, atol=atol,
                                   err_msg=f"row {i} vs dense")
        np.testing.assert_array_equal(wantf[i, n:], 0.0,
                                      err_msg=f"row {i} pad slots")


@pytest.mark.parametrize("window", [1, 3, 8])
def test_flash_prefill_sliding_window(window):
    """Windowed masking parity on ragged chunks (gemma local layers)."""
    q, pk, pv, table, st_, cn = _mk_ragged_prefill(
        31 + window, ps=4, maxp=4, n_kv=2, g=2, d=8,
        starts=[0, 6, 9], counts=[5, 2, 7])
    want = ref.paged_prefill_ref(q, pk, pv, table, st_, cn, window)
    got = paged_prefill_attention(q, pk, pv, table, st_, cn,
                                  jnp.int32(window), interpret=True)
    rtol, atol = _tol(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)
    dense = _dense_prefill_oracle(q, pk, pv, table, st_, cn, window)
    for i in range(len(cn)):
        n = int(cn[i])
        np.testing.assert_allclose(np.asarray(want, np.float32)[i, :n],
                                   dense[i, :n], rtol=rtol, atol=atol)


def test_flash_prefill_shared_prefix_mid_cow():
    """Rows whose leading pages alias the same physical pages (prefix
    sharing; the engine resolves the boundary page via COW before the
    dispatch): reads through the aliases must match a dense gather of
    each row's table, and empty (count==0) padding rows stay zero."""
    q, pk, pv, table, st_, cn = _mk_ragged_prefill(
        43, ps=4, maxp=4, n_kv=2, g=2, d=16,
        starts=[8, 8, 11, 0], counts=[5, 3, 2, 0], shared_pages=2)
    want = ref.paged_prefill_ref(q, pk, pv, table, st_, cn)
    got = paged_prefill_attention(q, pk, pv, table, st_, cn,
                                  interpret=True)
    rtol, atol = _tol(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)
    dense = _dense_prefill_oracle(q, pk, pv, table, st_, cn)
    wantf = np.asarray(want, np.float32)
    for i in range(len(cn)):
        n = int(cn[i])
        np.testing.assert_allclose(wantf[i, :n], dense[i, :n],
                                   rtol=rtol, atol=atol)
    np.testing.assert_array_equal(wantf[3], 0.0)
    assert np.isfinite(np.asarray(got, np.float32)).all()


def test_chunk_shape_slide_back_stays_in_bounds():
    """The 8-grid slide-back must honor start + bucket <= max_len for
    prefix-hit offsets landing within 8 tokens of max_len (the regime
    where a naive pos + c - b rewind could overrun)."""
    for pos in range(_CHUNK_MAX_LEN - 8, _CHUNK_MAX_LEN):
        for c in range(1, _CHUNK_MAX_LEN - pos + 1):
            start, bucket, real = _chunk_shape(pos, c, chunk=None)
            assert start + bucket <= _CHUNK_MAX_LEN, (pos, c, start, bucket)
            assert start <= pos and start + real == pos + c, (pos, c)


# ---------------------------------------------------------------------------
# sampling filters: radix-select top-k kernel + top-p / min-p vs oracles
# ---------------------------------------------------------------------------

from repro.kernels.topk import NEG as TOPK_NEG  # noqa: E402
from repro.kernels.topk import topk_mask  # noqa: E402
from repro.serving.sampling import minp_mask, topp_mask  # noqa: E402


def _mask_of(x):
    return np.asarray(jnp.asarray(x, jnp.float32)) <= TOPK_NEG / 2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k", [1, 8, None, 0])   # None => k = V (disabled)
def test_topk_kernel_parity_uniform_k(dtype, k):
    """Pallas radix-select (interpret) AND the lax fallback vs the numpy
    sort oracle: identical surviving values, identical masks — ties at
    the threshold all survive in every implementation."""
    b, v = 4, 203                          # v % 128 != 0: pad path
    x = jnp.asarray(np.random.default_rng(k or 77).standard_normal(
        (b, v)), dtype)
    kk = np.full((b,), v if k is None else k, np.int32)
    want = ref.topk_mask_ref(x, kk, fill=TOPK_NEG)
    got_pallas = topk_mask(x, kk, use_pallas=True, interpret=True)
    got_lax = topk_mask(x, kk, use_pallas=False)
    for name, got in (("pallas", got_pallas), ("lax", got_lax)):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=f"{name} k={k} {dtype}")
    if k and k < v:
        # at least k survivors; bf16 rounding may tie at the threshold,
        # and ties all survive, so the mask can be slightly smaller
        assert _mask_of(got_pallas).sum(axis=1).max() <= v - k


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_kernel_parity_ragged_per_row_k(dtype):
    """Mixed per-row k in ONE dispatch (the fused-sampler contract):
    k=1, small, V, disabled(0), and mid — all against the oracle."""
    rng = np.random.default_rng(3)
    for v in (64, 129, 500):
        x = jnp.asarray(rng.standard_normal((5, v)) * 4, dtype)
        kk = np.asarray([1, 8, v, 0, max(v // 3, 1)], np.int32)
        want = ref.topk_mask_ref(x, kk, fill=TOPK_NEG)
        got = topk_mask(x, kk, use_pallas=True, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=f"v={v}")
        np.testing.assert_array_equal(
            np.asarray(topk_mask(x, kk, use_pallas=False), np.float32),
            np.asarray(want, np.float32), err_msg=f"lax v={v}")


def test_topk_kernel_signed_zero_threshold_parity():
    """A +-0.0 threshold: float compares treat -0.0 == +0.0 but their
    bit patterns differ — the radix kernel canonicalizes zeros so its
    mask matches the float-comparing oracle and fallback exactly."""
    row = np.asarray([1.0, 0.0, -0.0, -1.0], np.float32)
    x = jnp.asarray(np.stack([row, -row]))
    kk = np.asarray([2, 2], np.int32)       # threshold lands on +-0.0
    want = ref.topk_mask_ref(x, kk, fill=TOPK_NEG)
    got_p = topk_mask(x, kk, use_pallas=True, interpret=True)
    got_l = topk_mask(x, kk, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want))
    # both zeros tie at the threshold: all three survive in row 0
    assert (~_mask_of(got_p)[0]).sum() == 3


def test_topk_kernel_exact_with_ties():
    """Duplicated values straddling the threshold: value-threshold
    semantics keep ALL ties, in kernel, fallback, and oracle alike."""
    row = np.asarray([3.0, 3.0, 3.0, 1.0, 1.0, -2.0, 0.5, 3.0],
                     np.float32)
    x = jnp.asarray(np.stack([row, row]))
    kk = np.asarray([2, 5], np.int32)     # k=2 cuts inside the 3.0 run
    want = ref.topk_mask_ref(x, kk, fill=TOPK_NEG)
    got = topk_mask(x, kk, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (~_mask_of(got)[0]).sum() == 4    # all four 3.0s survive
    np.testing.assert_array_equal(
        np.asarray(topk_mask(x, kk, use_pallas=False)), np.asarray(want))


@pytest.mark.parametrize("p", [0.1, 0.9, 1.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topp_mask_parity(p, dtype):
    """Nucleus filter vs the numpy descending-walk oracle, including
    per-row mixed p in one call."""
    rng = np.random.default_rng(int(p * 10))
    z = jnp.asarray(rng.standard_normal((4, 157)) * 3, dtype)
    pa = np.full((4,), p, np.float32)
    got = topp_mask(jnp.asarray(z, jnp.float32), jnp.asarray(pa))
    want = ref.topp_mask_ref(jnp.asarray(z, jnp.float32), pa)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if p == 1.0:
        assert not _mask_of(got).any()       # disabled: nothing filtered
    # ragged per-row p
    pm = np.asarray([p, 1.0, 0.5, 0.05], np.float32)
    got = topp_mask(jnp.asarray(z, jnp.float32), jnp.asarray(pm))
    want = ref.topp_mask_ref(jnp.asarray(z, jnp.float32), pm)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_topp_always_keeps_top1_and_minp_parity():
    """p -> 0 still keeps the argmax (prefix-mass rule), and the min-p
    filter matches its oracle across mixed rows."""
    rng = np.random.default_rng(9)
    z = jnp.asarray(rng.standard_normal((3, 97)) * 5, jnp.float32)
    got = topp_mask(z, jnp.asarray(np.full((3,), 1e-6, np.float32)))
    kept = ~_mask_of(got)
    assert (kept.sum(axis=1) >= 1).all()
    am = np.asarray(jnp.argmax(z, -1))
    assert all(kept[i, am[i]] for i in range(3))
    mp = np.asarray([0.0, 0.2, 1.0], np.float32)
    np.testing.assert_array_equal(
        np.asarray(minp_mask(z, jnp.asarray(mp))),
        np.asarray(ref.minp_mask_ref(z, mp)))
