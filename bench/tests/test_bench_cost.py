"""The benchmark's operation and byte counts against hand numbers and
against the program's own shapes."""
import json

import jax
import pytest

from bench import cost
from bench.layout import BENCH, Layout

DENSE = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
HASHED = json.loads((BENCH / "configs" /
                     "qwen3-1.7b-hashed8-block.json").read_text())


def test_dense_params_match_the_published_model():
    m = cost.Model.from_config(DENSE)
    # 28 x 50,331,648 layer matrices + 151,936 x 2,048 embedding + norms
    assert m.virtual_layer_params == 28 * 50_331_648
    assert m.embed_params == 311_164_928
    assert m.real_params == 1_720_574_976
    assert m.real_params == m.virtual_params


def test_dense_params_match_the_program_but_for_its_vocab_padding():
    """The program pads the vocabulary to 152,064 rows: 128 more rows of
    2,048, which the work does not need.  With them, 1,720.8 M, the
    count of the program's own arrays."""
    from bench.harness import build_model
    model, _ = build_model("qwen3-1.7b", DENSE)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    m = cost.Model.from_config(DENSE)
    assert n == m.real_params + 128 * 2048 == 1_720_837_120


def test_kv_bytes_per_token():
    assert cost.Model.from_config(DENSE).kv_bytes_per_token == 114_688


def test_hashed_params_match_the_programs_hashed_specs():
    from bench.harness import build_model
    _, banks = build_model("qwen3-1.7b-hashed8-block", HASHED)
    m = cost.Model.from_config(HASHED)
    assert len(banks) == 7
    from repro.core.hashed import spec_from_dict
    specs = [spec_from_dict(b) for b in banks.values()]
    assert m.real_layer_params == 28 * sum(s.real_param_count()
                                           for s in specs)
    assert m.virtual_layer_params == 28 * sum(s.virtual_size for s in specs)
    # 384 tiles of 128 x 128 per layer
    assert m.real_layer_params == 28 * 384 * 128 * 128 == 176_160_768
    assert m.real_params == 176_160_768 + 311_164_928 + 123_904


def test_decode_and_prefill_counts():
    m = cost.Model.from_config(DENSE)
    per_row = 2 * m.virtual_layer_params + 2 * 151_936 * 2_048
    assert m.decode_flops(1, 100) == per_row + 4 * 28 * 16 * 128 * 100
    assert m.decode_bytes(3, 100) == 2 * m.real_params + 100 * 114_688
    assert m.decode_bytes(0, 0) == 0
    assert m.prefill_flops(4) == 4 * 2 * m.virtual_layer_params \
        + 4 * 28 * 16 * 128 * 10 + 2 * 151_936 * 2_048
    h = cost.Model.from_config(HASHED)
    assert h.decode_flops(2, 50) == m.decode_flops(2, 50)
    assert h.decode_bytes(1, 0) == 2 * 487_449_600


def test_roofline_takes_the_larger_bound():
    p = cost.peaks("TPU v5 lite")
    m = cost.Model.from_config(DENSE)
    assert m.least_time(0, 819e9, p) == pytest.approx(1.0)
    assert m.least_time(197e12, 1, p) == pytest.approx(1.0)
    # one dense decode row reads 3.44 GB of weights: 4.2 ms
    t = m.least_time(m.decode_flops(1, 1), m.decode_bytes(1, 1), p)
    assert t == pytest.approx(4.2e-3, rel=0.01)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        cost.peaks("TPU v9 imaginary")


def test_every_metric_in_the_benchmark_has_a_reader():
    lay = Layout()
    for m in lay.spec["end_to_end"] + lay.spec["per_layer"]:
        assert callable(lay.reader(m["name"]))
