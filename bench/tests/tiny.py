"""A throwaway benchmark layout at toy widths, for the CPU tests and for
recording the small trace the reduction is tested on.

``write(root)`` writes ``BENCHMARK.json`` and a bench directory with two
configurations (dense, and hashed in 16x16 blocks), two traffic mixes
(open-loop chat, closed-loop docs), the repository's arrival kinds and
metric readers copied in, and returns the ``Layout`` that finds them.
"""
import json
import pathlib

from bench.layout import BENCH, Layout

WIDTHS = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
          "head_dim": 16, "d_ff": 128, "vocab_size": 500}

DENSE = {
    "source": "toy widths of https://huggingface.co/Qwen/Qwen3-1.7B",
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "tie_word_embeddings": True, "vocab_size": 500,
    "program": {"arch": "qwen3-1.7b", "overrides": dict(WIDTHS)},
    "engine": {"max_concurrency": 4, "max_len": 128, "page_size": 16,
               "num_pages": 33, "prefill_chunk": 32, "prefix_cache": False,
               "eos_id": -1},
    "check": {"logit_gap_limit": 0.08, "min_tokens_checked": 20},
}
HASH = {"compression": 0.125, "hash_mode": "block", "hash_block": [16, 16]}
HASHED = dict(DENSE, hashed=HASH, program={
    "arch": "qwen3-1.7b", "overrides": dict(WIDTHS, hashed=True, **HASH)})

CHAT = {"arrival": {"kind": "poisson", "rate_per_s": 8}, "warmup_s": 1,
        "prompt_len": {"median": 20, "sigma": 0.7, "min": 4, "max": 60},
        "output_len": {"median": 8, "sigma": 0.6, "min": 2, "max": 40},
        "sampling": [{"share": 0.5, "temperature": 0.0},
                     {"share": 0.5, "temperature": 0.7, "top_p": 0.95}]}
DOCS = {"arrival": {"kind": "closed", "clients": 8, "pool": 200, "block": 4},
        "warmup_s": 1,
        "prompt_len": {"median": 40, "sigma": 0.5, "min": 10, "max": 80},
        "output_len": {"median": 6, "sigma": 0.6, "min": 2, "max": 20},
        "sampling": [{"share": 1.0, "temperature": 0.0}]}

CELLS = {"tiny.chat": ("tiny", "chat"), "tinyh.chat": ("tinyh", "chat"),
         "tinyh.docs": ("tinyh", "docs")}


class FakeTPU:
    """Stands for one v5e where a test drives a whole run on the CPU."""
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 0}


def fake_tpu(monkeypatch) -> None:
    """Let ``harness.run_cell`` pass its look for a chip."""
    from bench import harness
    monkeypatch.setattr(harness, "device_check",
                        lambda chips: [FakeTPU()] * chips)


def spec() -> dict:
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    rename = {"qwen3-1.7b-hashed8-block": "tinyh", "qwen3-1.7b": "tiny"}

    def tiny(name):
        for old, new in rename.items():
            name = name.replace(old, new)
        return name

    for m in real["end_to_end"] + real["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [tiny(w) for w in m["workloads"]]
    real["configs"] = [
        {"name": n, "source": "toy", "file": f"b/configs/{n}.json",
         "reduced": [], "why": "toy widths for tests"}
        for n in ("tiny", "tinyh")]
    real["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "test"}
        for n, (c, t) in CELLS.items()]
    return real


def write(root) -> Layout:
    root = pathlib.Path(root)
    b = root / "b"
    for sub in ("configs", "traffic", "metrics"):
        (b / sub).mkdir(parents=True, exist_ok=True)
    (b / "configs" / "tiny.json").write_text(json.dumps(DENSE))
    (b / "configs" / "tinyh.json").write_text(json.dumps(HASHED))
    (b / "traffic" / "chat.json").write_text(json.dumps(CHAT))
    (b / "traffic" / "docs.json").write_text(json.dumps(DOCS))
    for sub in ("metrics", "traffic"):
        for f in (BENCH / sub).glob("*.py"):
            (b / sub / f.name).write_text(f.read_text())
    (root / "BENCHMARK.json").write_text(json.dumps(spec(), indent=1))
    return Layout(root, b)
