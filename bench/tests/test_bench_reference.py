"""The plain float32 reference against the program, on the CPU at toy
widths: the hash that defines a bank, the expansion, and the engine's
prefill-then-decode log-probabilities for the dense and the hashed
configuration."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, reference
from bench.tests import tiny

SPECS = [
    {"virtual_shape": [2048, 6144], "compression": 0.125, "mode": "block",
     "seed": 2468480155, "block_shape": [128, 128], "use_sign": True},
    {"virtual_shape": [100, 37], "compression": 0.3, "mode": "block",
     "seed": 7, "block_shape": [16, 8], "use_sign": True},
    {"virtual_shape": [64, 64], "compression": 0.5, "mode": "block",
     "seed": 0xFFFFFFFF, "block_shape": [16, 16], "use_sign": False},
]


@pytest.mark.parametrize("spec", SPECS, ids=["ffn-in", "ragged", "nosign"])
def test_tile_map_is_the_programs_hash(spec):
    from repro.core.hashed import block_indices, materialize, spec_from_dict
    hs = spec_from_dict(spec)
    idx, sgn = block_indices(hs)
    ridx, rsgn = reference.tile_map(spec)
    np.testing.assert_array_equal(np.asarray(idx), ridx)
    np.testing.assert_array_equal(np.asarray(sgn), rsgn)
    bank = np.random.default_rng(0).standard_normal(
        hs.real_param_shape()).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(materialize(jnp.asarray(bank), hs)),
        np.asarray(reference.expand(jnp.asarray(bank), spec)))


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    return tiny.write(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("name", ["tiny.chat", "tinyh.chat"])
def test_engine_logprobs_agree_with_the_reference(layout, name):
    """Greedy requests through the engine (chunked batched prefill, paged
    decode) against one full causal pass of the reference.  bfloat16
    activations round at 2**-9 relative, so logits of magnitude about 4
    carry about 0.02 of rounding and a log-probability, a difference of
    two such terms, up to about 0.05; a wrong weight or position moves
    log-probabilities by the logit spread, about 1."""
    from repro.serving.api import SamplingParams
    from repro.serving.engine import Request
    cell = harness.Cell(layout, name)
    params = cell.params(3)
    eng = cell.engine(params)
    rng = np.random.default_rng(1)
    reqs = []
    for i, n in enumerate((5, 40, 77)):       # 1, 2 and 3 prefill chunks
        r = Request(uid=i, prompt=rng.integers(0, 500, n).astype(np.int32),
                    sampling=SamplingParams(max_tokens=12))
        assert eng.submit(r)
        reqs.append(r)
    while eng.pending():
        eng.step()
    length = cell.config["engine"]["max_len"]
    for r in reqs:
        feed, pos = check._layout(r.prompt, r.tokens, length)
        tgt = np.zeros(length, np.int32)
        tgt[pos] = r.tokens
        best, at, _, lse = reference.logit_stats(
            params, cell.banks, cell.config, feed, tgt)
        ref_lp = (at - lse)[pos]
        np.testing.assert_allclose(r.token_logprobs, ref_lp, atol=0.06)
        assert np.max((best - at)[pos]) < 0.06
