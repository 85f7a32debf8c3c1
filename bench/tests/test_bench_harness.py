"""The benchmark harness on the CPU at toy widths: traffic, end-to-end
arithmetic, lookup by name, and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import e2e, harness, traffic
from bench.layout import BENCH, Layout
from bench.tests import tiny

CHECKOUT = BENCH.parent
CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
DOCS = json.loads((BENCH / "traffic" / "docs.json").read_text())
BIG_SEED = 2 ** 31 + 12345


def _key(sched):
    return [(it.prompt.tolist(), it.max_tokens, it.temperature, it.top_p,
             it.seed, it.due) for it in sched.items]


@pytest.mark.parametrize("mix", [CHAT, DOCS], ids=["chat", "docs"])
def test_traffic_repeats_for_a_seed_and_changes_with_it(mix):
    a = traffic.generate(mix, BIG_SEED, 45, 151936)
    b = traffic.generate(mix, BIG_SEED, 45, 151936)
    c = traffic.generate(mix, BIG_SEED + 1, 45, 151936)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    # the same work in another order: equal multisets of lengths
    for field in ("max_tokens",):
        assert sorted(getattr(i, field) for i in a.items) \
            == sorted(getattr(i, field) for i in c.items)
    assert sorted(len(i.prompt) for i in a.items) \
        == sorted(len(i.prompt) for i in c.items)


def test_traffic_shapes_follow_the_mix():
    s = traffic.generate(CHAT, 7, 45, 151936)
    assert s.open_loop
    due = np.array([i.due for i in s.items])
    assert due.min() >= -CHAT["warmup_s"] and due.max() < 45
    assert np.all(np.diff(due) >= 0)
    rate = CHAT["arrival"]["rate_per_s"]
    for seed in (7, 8, BIG_SEED):
        d = [i.due for i in traffic.generate(CHAT, seed, 45, 151936).items]
        assert sum(0 <= t < 45 for t in d) == round(rate * 45)
    plen = [len(i.prompt) for i in s.items]
    assert min(plen) >= 16 and max(plen) <= 1024
    assert abs(np.median(plen) - 256) < 20
    greedy = sum(i.temperature == 0 for i in s.items)
    assert abs(greedy - len(s.items) / 2) <= 1
    d = traffic.generate(DOCS, 7, 45, 151936)
    assert not d.open_loop and d.clients == 64
    assert all(i.temperature == 0 for i in d.items)
    assert all(i.due is None for i in d.items)


def test_closed_loop_blocks_hold_the_same_work():
    """Every block of the docs pool is a shuffle of the same lengths, and
    a window takes many blocks."""
    size = DOCS["arrival"]["block"]
    assert size <= DOCS["arrival"]["clients"] // 4
    for seed in (7, BIG_SEED):
        d = traffic.generate(DOCS, seed, 45, 151936)
        assert len(d.items) % size == 0
        plen = np.array([len(i.prompt) for i in d.items]).reshape(-1, size)
        olen = np.array([i.max_tokens for i in d.items]).reshape(-1, size)
        for lens in (plen, olen):
            assert (np.sort(lens, axis=1) == np.sort(lens[0])).all()
    # the same exchange at any client: one request ends, the next is sent
    assert [u for u, _ in ((it.uid, t) for it, t in d.send(3.0))] \
        == list(range(64))
    assert d.send(3.5) == []
    d.ended(d.items[5], [1, 2], 4.0)
    assert [(it.uid, t) for it, t in d.send(4.0)] == [(64, 4.0)]


def test_onoff_arrivals_come_in_bursts():
    mix = dict(CHAT, arrival={"kind": "onoff", "rate_on_per_s": 10,
                              "rate_off_per_s": 0.5, "on_s": 2, "off_s": 8})
    due = np.array([i.due for i in traffic.generate(mix, 3, 40, 1000).items])
    phase = (due + mix["warmup_s"]) % 10
    periods = (40 + mix["warmup_s"]) // 10
    assert (phase < 2).sum() == periods * 20
    assert (phase >= 2).sum() == periods * 4
    assert due.max() < 40


def _run(ttfts, gaps_per_req, window=10.0):
    recs = []
    for i, first in enumerate(ttfts):
        r = e2e.Rec(uid=i, due=1.0, prompt_len=8, greedy=True)
        if first is not None:
            t = 1.0 + first
            r.tokens = [t]
            for g in gaps_per_req:
                t += g
                r.tokens.append(t)
        recs.append(r)
    return e2e.Run(cell="x", config={}, cost=None, peak={}, rows=4,
                   setup_s=1.0, window_s=window, drain_end=window + 2.0,
                   recs=recs, ticks=[])


def test_end_to_end_arithmetic_on_a_synthetic_record():
    ttfts = [0.1 * (i + 1) for i in range(9)] + [None]
    run = _run(ttfts, [0.01, 0.02, 0.03])
    samples = e2e.ttft(run)
    # the request that never started counts from its due time to the
    # end of the run, so it is the tail
    assert max(samples) == pytest.approx(run.drain_end - 1.0)
    assert e2e.percentile(samples, 90) == pytest.approx(
        np.percentile(samples, 90))
    assert e2e.unserved(run) == 1
    gaps = e2e.token_gaps(run)
    assert len(gaps) == 9 * 3
    assert e2e.percentile(gaps, 95) == pytest.approx(0.03)
    assert e2e.output_tokens(run) == 9 * 4
    read = Layout().reader
    assert read("output_tok_s")(run) == pytest.approx(36 / 10.0)
    assert read("ttft_p50_s")(run) == pytest.approx(
        np.percentile(samples, 50))
    assert read("itl_p95_ms")(run) == pytest.approx(30.0)


def test_a_gap_that_ends_outside_the_window_is_not_counted():
    run = _run([0.5], [4.0, 10.0], window=8.0)   # tokens at 1.5, 5.5, 15.5
    assert e2e.token_gaps(run) == pytest.approx([4.0])
    assert e2e.output_tokens(run) == 2


RELAY = '''"""Sessions: each client's next turn repeats its last prompt and the
answer it got, then asks more; after ``turns`` turns it starts anew."""
import numpy as np

from bench import traffic


class Relay(traffic.ClosedLoop):
    def __init__(self, items, clients, turns, vocab, rng):
        super().__init__(0.5, items, clients)
        self.turns, self.vocab, self.rng = turns, vocab, rng
        self.follow = []

    def send(self, now):
        out, self.follow = [(it, now) for it in self.follow], []
        return out + super().send(now)

    def ended(self, item, served, now):
        turn = getattr(item, "turn", 0) + 1
        if not served or turn >= self.turns:
            return super().ended(item, served, now)
        ask = self.rng.integers(0, self.vocab, 3).astype(np.int32)
        nxt = traffic.Item(uid=-1, prompt=np.concatenate(
            [item.prompt, np.asarray(served, np.int32), ask]),
            max_tokens=item.max_tokens, temperature=0.0, top_p=1.0,
            seed=item.seed)
        nxt.turn = turn
        self.follow.append(nxt)


def make(mix, rng, seconds, vocab):
    arr = mix["arrival"]
    items = traffic.stratified(mix, 32, rng, vocab)
    return Relay(items, arr["clients"], arr["turns"], vocab, rng)
'''


def test_new_config_traffic_metric_and_cell_are_found_by_name(
        tmp_path, monkeypatch):
    """A configuration, a mix of a new arrival kind that feeds back on
    what was served, a metric and a cell, each added as files and
    entries: the harness finds them all by name and runs the cell."""
    lay = tiny.write(tmp_path)
    b = lay.bench
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["engine"]["max_concurrency"] = 2
    (b / "configs" / "tiny2.json").write_text(json.dumps(cfg))
    (b / "traffic" / "relay.py").write_text(RELAY)
    mix = {"arrival": {"kind": "relay", "clients": 3, "turns": 2},
           "prompt_len": {"median": 10, "sigma": 0.5, "min": 4, "max": 20},
           "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 10},
           "sampling": [{"share": 1.0, "temperature": 0.0}]}
    (b / "traffic" / "slow.json").write_text(json.dumps(mix))
    (b / "metrics" / "requests_done.py").write_text(
        "def read(run):\n"
        "    return sum(1 for r in run.recs if r.done is not None)\n")
    (b / "metrics" / "second_turns.py").write_text(
        "def read(run):\n"
        "    return sum(1 for r in run.recs if r.done is not None\n"
        "               and getattr(r.item, 'turn', 0) == 1)\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny2", "source": "toy",
                            "file": "b/configs/tiny2.json", "reduced": [],
                            "why": "two rows"})
    spec["workloads"].append({"name": "tiny2.slow", "config": "tiny2",
                              "traffic": "slow", "chips": 1, "why": "new"})
    for name in ("requests_done", "second_turns"):
        spec["per_layer"].append({"name": name, "unit": "1",
                                  "better": "higher", "source": "host_clock",
                                  "layer": "scheduler", "moves": "ttft_p50_s",
                                  "workloads": ["tiny2.slow"]})
    for m in spec["end_to_end"]:
        if m["name"] == "ttft_p50_s":
            m["workloads"].append("tiny2.slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    lay = Layout(tmp_path, b)
    assert [m["name"] for m in lay.metrics("tiny2.slow", False)] \
        == ["setup_s", "ttft_p50_s"]
    assert [m["name"] for m in lay.metrics("tiny2.slow", True)] \
        == ["requests_done", "second_turns"]
    tiny.fake_tpu(monkeypatch)
    out = harness.run_cell(lay, "tiny2.slow", 11, 2.0, True,
                           t_start=time.monotonic())
    assert out["correct"], out["check"]
    assert out["metrics"]["requests_done"]["value"] > 0
    assert out["metrics"]["second_turns"]["value"] > 0
    assert out["device"]["kind"] == "TPU v5 lite"
    assert list(out)[-1] == "check"


def _no_result(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="1")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.chat",
         "--seed", str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)
    return p


def test_run_exits_nonzero_without_a_tpu():
    p = _no_result(CHECKOUT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _no_result(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
