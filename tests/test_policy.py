"""Compression-policy subsystem tests: flat-knob compat lowering must be
byte-identical (the API redesign cannot move any weights), the equal-memory
budget solver must land on target for every registered config, per-slot
rules must steer mode/ratio/path/quant, and policies must survive JSON /
config / artifact round-trips."""
import dataclasses
import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro import artifact, policy as POL
from repro.artifact import format as afmt
from repro.artifact import report as areport
from repro.configs.reduced import reduced
from repro.core import HashedSpec
from repro.core.hashing import derive_seed
from repro.models import build
from repro.models.transformer import bank_spec_map, hash_slots, \
    slot_assignments

ALL_ARCHS = C.names()


def _legacy_spec(cfg, seed_key, vshape):
    """The pre-policy _hspec formula, verbatim — the compat contract."""
    seed = derive_seed(0xC0FFEE, zlib.crc32(seed_key.encode()) & 0x7FFFFFFF)
    return HashedSpec(
        virtual_shape=tuple(vshape),
        compression=cfg.compression,
        mode=cfg.hash_mode,
        seed=seed,
        panel_cols=(cfg.hash_panel_cols if cfg.hash_mode == "element"
                    else 0),
        block_shape=tuple(cfg.hash_block),
    )


# known seed keys per slot path (a representative per arch kind) — pins
# the seed derivation so a refactor can't silently re-key the hashes
SEED_KEYS = {
    "qwen3-1.7b": {
        ("layers", "attn", "q", "w"): "attn.q",
        ("layers", "ffn", "out", "w"): "ffn.out",
        ("embed", "emb"): "embed",
    },
    "llama3-405b": {                     # untied: has an lm_head slot
        ("lm_head", "w"): "lm_head",
    },
    "granite-moe-1b-a400m": {
        ("layers", "moe", "in"): "moe.in",
        ("layers", "moe", "out"): "moe.out",
    },
    "rwkv6-7b": {
        ("layers", "tm", "r", "w"): "rwkv.r",
        ("layers", "cm", "k", "w"): "cmix.k",
    },
    "zamba2-2.7b": {
        ("mamba_groups", "mamba", "in_proj", "w"): "mamba.in",
        ("shared", "attn", "q", "w"): "attn.q",
        ("shared", "ffn", "in", "w"): "ffn.in",
    },
    "whisper-medium": {
        ("encoder", "attn", "q", "w"): "enc.q",
        ("decoder", "self", "k", "w"): "dec.k",
        ("decoder", "cross", "v", "w"): "xattn.v",
        ("encoder", "ffn", "in", "w"): "ffn.in",
        ("decoder", "ffn", "in", "w"): "ffn.in",   # historically shared
    },
}


@pytest.mark.parametrize("arch", ALL_ARCHS)
@pytest.mark.parametrize("mode", ["element", "block"])
def test_flat_knobs_lower_byte_identical(arch, mode):
    """Legacy flat-knob configs resolved through the policy layer produce
    byte-identical HashedSpecs (same seeds, shapes, bucket counts)."""
    cfg = C.get(arch).hashed_variant(0.125, mode=mode).with_(
        hash_embeddings=True)
    slots = {s.path: s for s in hash_slots(cfg)}
    specs = bank_spec_map(cfg)
    assert specs, arch
    for path, spec in specs.items():
        want = _legacy_spec(cfg, "<seed via slot>",
                            slots[path].virtual_shape)
        want = dataclasses.replace(want, seed=slots[path].seed)
        assert spec == want, path
        # byte-identical serialization (what lands in artifact headers)
        assert json.dumps(spec.to_dict()) == json.dumps(want.to_dict())
    for path, key in SEED_KEYS.get(arch, {}).items():
        assert path in slots, (arch, path)
        assert slots[path].seed == derive_seed(
            0xC0FFEE, zlib.crc32(key.encode()) & 0x7FFFFFFF), (arch, path)


def test_flat_vs_explicit_single_rule_policy_identical_params():
    """An explicit single-rule policy equals the flat knobs: same specs,
    bit-identical params from the same key."""
    base = reduced(C.get("qwen3-1.7b")).with_(dtype="float32")
    flat = base.hashed_variant(0.25)
    pol = POL.CompressionPolicy(rules=(POL.PolicyRule(
        match="*", compression=0.25, mode="element",
        panel_cols=flat.hash_panel_cols, block_shape=flat.hash_block,
        path=flat.hash_path),))
    viapolicy = base.with_(hashed=True, hash_policy=pol)
    assert bank_spec_map(flat) == bank_spec_map(viapolicy)
    p1 = build(flat).init(jax.random.PRNGKey(0))
    p2 = build(viapolicy).init(jax.random.PRNGKey(0))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), p1, p2)


# ---------------------------------------------------------------------------
# budget solver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_budget_solver_within_one_percent(arch):
    """Acceptance: total real params within 1% of budget * total virtual
    on every registered config."""
    budget = 1 / 8
    cfg = C.get(arch).policy_variant(POL.CompressionPolicy(budget=budget))
    specs = bank_spec_map(cfg)
    total_virtual = sum(s.virtual_size for s in specs.values())
    total_real = sum(s.real_param_count() for s in specs.values())
    target = budget * total_virtual
    assert abs(total_real - target) <= 0.01 * target, \
        (arch, total_real, target)


def test_budget_with_pinned_rule_reallocates():
    """Pinning attention at 1/4 under a 1/8 total budget must push the
    free slots below 1/8 so the total still lands on target."""
    budget = 1 / 8
    pol = POL.CompressionPolicy(budget=budget, rules=(
        POL.PolicyRule(match="layers.attn.*", compression=1 / 4),))
    cfg = C.get("qwen3-1.7b").policy_variant(pol)
    asg = slot_assignments(cfg)
    attn = [a for p, a in asg.items() if p[:2] == ("layers", "attn")]
    ffn = [a for p, a in asg.items() if p[:2] == ("layers", "ffn")]
    assert all(a.spec.compression == 1 / 4 for a in attn)
    assert all(a.spec.compression < budget for a in ffn)
    specs = bank_spec_map(cfg)
    total_virtual = sum(s.virtual_size for s in specs.values())
    total_real = sum(s.real_param_count() for s in specs.values())
    target = budget * total_virtual
    assert abs(total_real - target) <= 0.01 * target


def test_budget_floor_and_cap_waterfill():
    slots = (
        POL.Slot(path=("a", "w"), virtual_shape=(1000, 100), seed=1),
        POL.Slot(path=("b", "w"), virtual_shape=(1000, 100), seed=2),
        POL.Slot(path=("c", "w"), virtual_shape=(1000, 100), seed=3),
    )
    pol = POL.CompressionPolicy(budget=0.1, rules=(
        POL.PolicyRule(match="a", floor=0.2),      # forced above target
        POL.PolicyRule(match="b", cap=0.05),       # forced below target
    ))
    asg = POL.resolve(pol, slots)
    ca = asg[("a", "w")].spec.compression
    cb = asg[("b", "w")].spec.compression
    cc = asg[("c", "w")].spec.compression
    assert ca == pytest.approx(0.2)
    assert cb == pytest.approx(0.05)
    # c absorbs the remainder: 0.3*V total = 0.2*V + 0.05*V + cc*V
    assert cc == pytest.approx(0.05)
    # solver-level exactness (before bucket rounding)
    assert ca + cb + cc == pytest.approx(3 * 0.1)


def test_budget_solver_saturates_when_infeasible():
    assign = POL.solve(10.0, [("a", 1000, 0.5, 1.0)])
    assert assign["a"] == pytest.approx(0.5)  # floor binds; no crash


def test_budget_solver_mixed_floor_cap_exact():
    """One slot capped below and one floored above the naive common
    ratio: a feasible exact allocation exists and must be found (naive
    simultaneous clamping overshot by 10% here)."""
    assign = POL.solve(100.0, [("a", 100, 0.0, 0.4),
                               ("b", 100, 0.7, 1.0)])
    assert assign["b"] == pytest.approx(0.7)
    assert assign["a"] == pytest.approx(0.3)
    assert 100 * assign["a"] + 100 * assign["b"] == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# rule matching / per-slot overrides
# ---------------------------------------------------------------------------

def test_rules_steer_mode_path_and_exclusion():
    pol = POL.CompressionPolicy(
        compression=1 / 8, mode="element", panel_cols=0, path="scan",
        rules=(
            POL.PolicyRule(match="layers.attn.*", mode="block",
                           block_shape=(16, 16), compression=1 / 4,
                           path="materialize"),
            POL.PolicyRule(match="*.ffn.out", compression=1 / 2),
            POL.PolicyRule(match="embed.*", hashed=True),
            POL.PolicyRule(match="lm_head", hashed=False),
        ))
    cfg = reduced(C.get("qwen3-1.7b")).with_(hashed=True, hash_policy=pol)
    asg = slot_assignments(cfg)
    q = asg[("layers", "attn", "q", "w")].spec
    assert (q.mode, q.block_shape, q.compression, q.exec_path) == \
        ("block", (16, 16), 1 / 4, "materialize")
    assert q.panel_cols == 0  # block mode never stratifies panels
    out = asg[("layers", "ffn", "out", "w")].spec
    assert (out.mode, out.compression, out.exec_path) == \
        ("element", 1 / 2, "scan")
    # rule turned the embedding ON without the hash_embeddings knob
    assert asg[("embed", "emb")].spec is not None
    assert asg[("embed", "emb")].spec.virtual_shape == \
        (cfg.padded_vocab, cfg.d_model)
    # ... and lm_head OFF explicitly (untied arch: qwen3 ties, so check
    # the rule against llama3's untied head)
    asg_l = slot_assignments(C.get("llama3-405b").with_(
        hashed=True, hash_embeddings=True, hash_policy=pol))
    assert asg_l[("lm_head", "w")].spec is None
    # the model actually builds and runs under the mixed policy
    m = build(cfg.with_(dtype="float32"))
    params = m.init(jax.random.PRNGKey(0))
    assert "emb" in params["embed"] and \
        params["embed"]["emb"].ndim == 1  # element-mode bank, not a table
    batch = {"tokens": jnp.asarray([[1, 2, 3, 4]]),
             "targets": jnp.asarray([[2, 3, 4, 5]])}
    loss, _ = jax.jit(m.train_loss)(params, batch)
    assert np.isfinite(float(loss))


def test_first_matching_rule_wins():
    pol = POL.CompressionPolicy(rules=(
        POL.PolicyRule(match="layers.attn.q", compression=1 / 2),
        POL.PolicyRule(match="layers.attn.*", compression=1 / 16),
    ))
    cfg = C.get("qwen3-1.7b").policy_variant(pol)
    asg = slot_assignments(cfg)
    assert asg[("layers", "attn", "q", "w")].spec.compression == 1 / 2
    assert asg[("layers", "attn", "k", "w")].spec.compression == 1 / 16
    assert asg[("layers", "attn", "q", "w")].rule == "layers.attn.q"


def test_policy_validation_rejects_garbage():
    with pytest.raises(ValueError, match="mode"):
        POL.CompressionPolicy(rules=(
            POL.PolicyRule(match="*", mode="banana"),)).validate()
    with pytest.raises(ValueError, match="floor"):
        POL.CompressionPolicy(rules=(
            POL.PolicyRule(match="*", floor=0.5, cap=0.1),)).validate()
    with pytest.raises(ValueError, match="unknown rule keys"):
        POL.rule_from_dict({"match": "*", "compresion": 0.5})
    with pytest.raises(ValueError, match="budget"):
        POL.CompressionPolicy(budget=3.0).validate()


# ---------------------------------------------------------------------------
# serialization round-trips
# ---------------------------------------------------------------------------

def _mixed_policy():
    return POL.CompressionPolicy(
        budget=1 / 8, compression=1 / 8, mode="element", panel_cols=64,
        block_shape=(32, 32), path="scan",
        rules=(
            POL.PolicyRule(match="layers.attn.*", mode="block",
                           block_shape=(16, 16), floor=1 / 32),
            POL.PolicyRule(match="*.ffn.out", compression=1 / 2,
                           quant="int8", path="materialize"),
            POL.PolicyRule(match="embed.*", hashed=False),
        ))


def test_policy_json_roundtrip(tmp_path):
    pol = _mixed_policy()
    d = POL.policy_to_dict(pol)
    json.loads(json.dumps(d))                      # JSON-safe
    assert POL.policy_from_dict(d) == pol
    f = str(tmp_path / "pol.json")
    POL.dump(pol, f)
    assert POL.load(f) == pol
    # user-facing "default" sub-object layout
    assert POL.policy_from_dict(
        {"budget": 0.125, "default": {"mode": "block"}}).mode == "block"


def test_policy_from_newer_writer_readable_non_strict():
    """Artifact read path: unknown policy/rule keys from a future writer
    are dropped, not fatal (same contract as config_from_dict); the
    strict user-file path still rejects them as typos."""
    d = POL.policy_to_dict(_mixed_policy())
    d["dither"] = True
    d["rules"][0]["sparsity"] = 0.5
    with pytest.raises(ValueError):
        POL.policy_from_dict(d)
    pol = POL.policy_from_dict(d, strict=False)
    assert pol == _mixed_policy()
    cfg_d = afmt.config_to_dict(
        reduced(C.get("qwen3-1.7b")).policy_variant(_mixed_policy()))
    cfg_d["hash_policy"]["rules"][0]["sparsity"] = 0.5
    assert afmt.config_from_dict(cfg_d).hash_policy == _mixed_policy()


def test_config_dict_roundtrip_carries_policy():
    cfg = reduced(C.get("qwen3-1.7b")).policy_variant(_mixed_policy())
    d = afmt.config_to_dict(cfg)
    json.loads(json.dumps(d))
    assert afmt.config_from_dict(d) == cfg


def test_artifact_roundtrip_policy_config_and_logits(tmp_path):
    pol = POL.CompressionPolicy(
        compression=1 / 8, panel_cols=0,
        rules=(POL.PolicyRule(match="layers.attn.*", compression=1 / 4),
               POL.PolicyRule(match="layers.ffn.*", mode="block",
                              block_shape=(16, 16), compression=1 / 2)))
    cfg = reduced(C.get("qwen3-1.7b")).with_(
        dtype="float32").policy_variant(pol)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "pol.hnart")
    artifact.export_model(path, cfg, params)
    cfg2, m2, p2 = artifact.load_model(path)
    assert cfg2 == cfg and cfg2.hash_policy == pol
    batch = {"tokens": jnp.asarray([[5, 9, 2, 7]]),
             "cache": m.init_cache(1, 32)}
    l1, _ = m.prefill(params, batch)
    l2, _ = m2.prefill(p2, {"tokens": jnp.asarray([[5, 9, 2, 7]]),
                            "cache": m2.init_cache(1, 32)})
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_per_slot_quant_override_in_artifact(tmp_path):
    pol = POL.CompressionPolicy(
        compression=1 / 4, panel_cols=0,
        rules=(POL.PolicyRule(match="layers.ffn.*", quant="int8"),))
    cfg = reduced(C.get("qwen3-1.7b")).with_(
        dtype="float32").policy_variant(pol)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "q.hnart")
    header = artifact.export_model(path, cfg, params,
                                   quant_min_size=0)
    quantized = {tuple(e["path"]) for e in header["leaves"] if e["quant"]}
    assert any(p[:2] == ("layers", "ffn") for p in quantized)
    assert not any(p[:2] == ("layers", "attn") for p in quantized)
    # still loads and serves logits (int8 error is bounded, just finite)
    _, m2, p2 = artifact.load_model(path)
    l2, _ = m2.prefill(p2, {"tokens": jnp.asarray([[5, 9, 2, 7]]),
                            "cache": m2.init_cache(1, 32)})
    assert np.isfinite(np.asarray(l2, np.float32)).all()


def test_report_groups_by_rule(tmp_path):
    pol = POL.CompressionPolicy(
        compression=1 / 8, panel_cols=0,
        rules=(POL.PolicyRule(match="layers.attn.*", compression=1 / 4),))
    cfg = reduced(C.get("qwen3-1.7b")).with_(
        dtype="float32").policy_variant(pol)
    m = build(cfg)
    path = str(tmp_path / "r.hnart")
    artifact.export_model(path, cfg, m.init(jax.random.PRNGKey(0)))
    header = afmt.read_header(path)
    rows = areport.rows_by_rule(header)
    by_name = {r["name"]: r for r in rows}
    assert "layers.attn.*" in by_name and "(defaults)" in by_name
    assert by_name["layers.attn.*"]["param_ratio"] == pytest.approx(
        1 / 4, rel=0.05)
    assert by_name["(defaults)"]["param_ratio"] == pytest.approx(
        1 / 8, rel=0.05)
    txt = areport.report(path)
    assert "by policy rule" in txt and "layers.attn.*" in txt


# ---------------------------------------------------------------------------
# satellites: variant naming, CLI ratios, mesh-derived bank sharding
# ---------------------------------------------------------------------------

def test_hashed_variant_exact_tags_and_get_roundtrip():
    base = C.get("qwen3-1.7b")
    assert base.hashed_variant(0.125).name.endswith("-hashed8")
    assert base.hashed_variant(1 / 16).name.endswith("-hashed16")
    # 0.3 is NOT "hashed3" (that would claim 1/3)
    assert base.hashed_variant(0.3).name.endswith("-hashedc0.3")
    for c in (0.125, 1 / 16, 0.3, 0.25):
        v = base.hashed_variant(c)
        got = C.get(v.name)
        assert got == v, c
    rv = reduced(base).hashed_variant(0.3)
    assert C.get(rv.name) == rv


def test_parse_ratio():
    assert POL.parse_ratio("1/8") == pytest.approx(0.125)
    assert POL.parse_ratio("0.25") == 0.25


def test_bank_pspec_derives_from_active_mesh():
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shd
    from repro.launch.mesh import single_device_mesh
    from repro.nn import layers as L
    spec = HashedSpec((60, 60), 0.5, mode="element", seed=1, panel_cols=0)
    n0 = spec.real_param_shape()[0]
    assert n0 % 256 != 0
    # no mesh: production 256-grid fallback -> replicated
    assert L.bank_pspec(spec) == P(None)
    # tiny CI mesh: 1x1 grid divides everything -> sharded spec
    mesh = single_device_mesh()
    with shd.use_mesh(mesh):
        assert L.bank_pspec(spec) == P((L.FSDP, L.TP))
