"""HTTP serving front-end: the asyncio layer must be an observation-
preserving wrapper around the tick-driven engine.

- completions through the server — streaming SSE and plain JSON,
  interleaved — are bitwise token-identical to submitting the same
  requests to an identically-seeded ``Engine`` directly,
- protocol errors map deterministically: unknown model -> 404, bad
  payload / never-fits prompt -> 400, wrong method -> 405, full
  queue -> 429 (with Retry-After), queue-deadline expiry -> 504,
- graceful drain: ``begin_drain`` stops admission (503 on /healthz and
  new submissions), cancels queued requests, finishes in-flight rows,
  and the driver exits; ``http.*`` metrics land in the engine registry.

Stdlib-asyncio only (the CI image has no HTTP client/server deps);
each test drives its own event loop via ``asyncio.run``.
"""
import asyncio
import json

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.models import build
from repro.serving.api import SamplingParams
from repro.serving.engine import Engine, Request
from repro.serving.http import HTTPFrontend
from repro.serving.http import client as http_client
from repro.serving.scheduler import SchedulerConfig

TINY = ArchConfig(
    name="tiny-http", family="dense", arch_kind="decoder",
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256, remat=False, dtype="float32")

PAGE = 8
MAX_LEN = 64


@pytest.fixture(scope="module")
def tiny():
    m = build(TINY)
    return m, m.init(jax.random.PRNGKey(0))


def _engine(tiny, **kw):
    m, params = tiny
    kw.setdefault("max_concurrency", 2)
    kw.setdefault("scheduler", SchedulerConfig(max_queue=32))
    return Engine(m, params, max_len=MAX_LEN, eos_id=-1,
                  page_size=PAGE, **kw)


def _workload(n, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = [int(x) for x in rng.integers(
            2, TINY.vocab_size, size=int(rng.integers(3, 14)))]
        if i % 3 == 0:
            sp = dict(temperature=0.0)
        else:
            sp = dict(temperature=0.8, top_p=0.9, seed=100 + i)
        out.append((prompt, dict(sp, max_tokens=int(rng.integers(2, 7)))))
    return out


def test_http_token_identical_to_direct_engine(tiny):
    """Streaming and JSON completions through the server reproduce a
    direct Engine run bitwise — greedy and seeded-sampled rows."""
    work = _workload(6)

    ref = _engine(tiny)
    for uid, (prompt, kw) in enumerate(work):
        ref.submit(Request(
            uid=uid, prompt=np.array(prompt, dtype=np.int32),
            sampling=SamplingParams(
                temperature=kw["temperature"], top_p=kw.get("top_p", 1.0),
                seed=kw.get("seed"), max_tokens=kw["max_tokens"])))
    want = {r.uid: list(r.tokens) for r in ref.run()}

    async def go():
        fe = HTTPFrontend(_engine(tiny), port=0, default_model="tiny")
        await fe.start()
        tasks = []
        for uid, (prompt, kw) in enumerate(work):
            payload = dict(model="tiny", prompt=prompt, **kw)
            if uid % 2:                      # interleave SSE + JSON
                tasks.append(http_client.collect_stream(
                    fe.host, fe.port, payload))
            else:
                tasks.append(http_client.request(
                    fe.host, fe.port, "POST", "/v1/completions", payload))
        results = await asyncio.gather(*tasks)
        got = {}
        for uid, r in enumerate(results):
            if uid % 2:
                assert r["finish_reason"] == "length"
                got[uid] = r["tokens"]
            else:
                status, body = r
                assert status == 200
                got[uid] = body["choices"][0]["token_ids"]
                assert body["usage"]["completion_tokens"] == len(got[uid])
        await fe.aclose()
        return got

    assert asyncio.run(go()) == want


def test_http_error_codes(tiny):
    async def go():
        fe = HTTPFrontend(_engine(tiny), port=0, default_model="tiny")
        await fe.start()
        h, p = fe.host, fe.port
        out = {}
        out["models"] = await http_client.request(h, p, "GET", "/v1/models")
        out["404"] = await http_client.request(
            h, p, "POST", "/v1/completions",
            dict(model="nope", prompt=[2, 3], max_tokens=2))
        out["400_prompt"] = await http_client.request(
            h, p, "POST", "/v1/completions",
            dict(model="tiny", prompt="not token ids", max_tokens=2))
        out["400_fits"] = await http_client.request(
            h, p, "POST", "/v1/completions",
            dict(model="tiny", prompt=[2] * (MAX_LEN + 8), max_tokens=2))
        out["405"] = await http_client.request(h, p, "GET",
                                               "/v1/completions")
        out["health"] = await http_client.request(h, p, "GET", "/healthz")
        out["metrics"] = await http_client.request(h, p, "GET", "/metrics")
        out["lost"] = await http_client.request(h, p, "GET", "/nowhere")
        await fe.aclose()
        return out

    out = asyncio.run(go())
    assert out["models"][0] == 200
    assert [m["id"] for m in out["models"][1]["data"]] == ["tiny"]
    assert out["404"][0] == 404
    assert out["400_prompt"][0] == 400
    assert out["400_fits"][0] == 400
    assert out["405"][0] == 405
    assert out["health"][0] == 200
    assert out["metrics"][0] == 200 and "http.requests" in out["metrics"][1]
    # one observation a step from its account; no dispatch-time histograms
    assert "engine.step_s" in out["metrics"][1]
    assert "engine.step_host_s" in out["metrics"][1]
    for gone in ("engine.decode_tick_s", "engine.prefill_chunk_s",
                 "engine.prefill_batch_s"):
        assert gone not in out["metrics"][1]
    assert out["lost"][0] == 404


def test_http_backpressure_429(tiny):
    """A full bounded queue refuses with 429 + Retry-After instead of
    queueing unboundedly; accepted requests still finish."""
    async def go():
        fe = HTTPFrontend(
            _engine(tiny, max_concurrency=1,
                    scheduler=SchedulerConfig(max_queue=1)),
            port=0, default_model="tiny")
        await fe.start()
        payload = dict(model="tiny", prompt=[2, 3, 4, 5, 6],
                       max_tokens=12, temperature=0.0)
        tasks = [http_client.request(fe.host, fe.port, "POST",
                                     "/v1/completions", payload)
                 for _ in range(8)]
        results = await asyncio.gather(*tasks)
        snap = fe.metrics.snapshot()
        await fe.aclose()
        return results, snap

    results, snap = asyncio.run(go())
    codes = sorted(s for s, _ in results)
    assert 429 in codes, codes
    ok = [b for s, b in results if s == 200]
    assert ok and all(len(b["choices"][0]["token_ids"]) == 12 for b in ok)
    assert snap["http.responses.429"] == codes.count(429)


def test_http_deadline_504(tiny):
    """Queue-deadline expiry surfaces as 504 on both response paths.

    deadline_s=0 expires anything that spends a tick queued; with one
    slot most of the burst must queue.  Rather than race the admit
    path, assert the mapping on whichever requests expired."""
    async def go2():
        fe = HTTPFrontend(
            _engine(tiny, max_concurrency=1,
                    scheduler=SchedulerConfig(max_queue=16,
                                              deadline_s=0.0)),
            port=0, default_model="tiny")
        await fe.start()
        payload = dict(model="tiny", prompt=[2, 3, 4], max_tokens=6,
                       temperature=0.0)
        tasks = [http_client.request(fe.host, fe.port, "POST",
                                     "/v1/completions", payload)
                 for _ in range(4)]
        stream_task = asyncio.create_task(_stream_status(
            fe.host, fe.port, payload))
        results = await asyncio.gather(*tasks)
        s_status = await stream_task
        await fe.aclose()
        return [s for s, _ in results] + [s_status]

    codes = asyncio.run(go2())
    assert 504 in codes, codes
    assert all(c in (200, 504) for c in codes), codes


async def _stream_status(host, port, payload):
    try:
        await http_client.collect_stream(host, port, payload)
        return 200
    except http_client.HTTPStreamError as e:
        return e.status


def test_http_graceful_drain(tiny):
    """begin_drain: health flips to 503, queued requests come back
    cancelled (503), in-flight rows run to completion, driver exits."""
    async def go():
        fe = HTTPFrontend(
            _engine(tiny, max_concurrency=1,
                    scheduler=SchedulerConfig(max_queue=16)),
            port=0, default_model="tiny")
        await fe.start()
        payload = dict(model="tiny", prompt=[2, 3, 4, 5], max_tokens=16,
                       temperature=0.0)
        tasks = [asyncio.create_task(
            http_client.request(fe.host, fe.port, "POST",
                                "/v1/completions", payload))
            for _ in range(3)]
        # let the first request reach a decode row before draining
        for _ in range(200):
            await asyncio.sleep(0.005)
            if fe.metrics.snapshot().get("engine.admitted", 0) >= 1:
                break
        fe.begin_drain()
        health = await http_client.request(fe.host, fe.port, "GET",
                                           "/healthz")
        late = await http_client.request(
            fe.host, fe.port, "POST", "/v1/completions", payload)
        results = await asyncio.gather(*tasks)
        await asyncio.wait_for(fe.wait_drained(), 60)
        snap = fe.metrics.snapshot()
        await fe.aclose()
        return health, late, results, snap

    health, late, results, snap = asyncio.run(go())
    assert health[0] == 503
    assert late[0] == 503
    codes = sorted(s for s, _ in results)
    assert codes[-1] == 503 or codes[0] == 200, codes
    # whatever was admitted before the drain finished fully
    done_tokens = [b["choices"][0]["token_ids"]
                   for s, b in results if s == 200]
    assert all(len(t) == 16 for t in done_tokens)
    # queued-at-drain requests were cancelled, not dropped
    assert snap.get("engine.cancelled", 0) == codes.count(503)


def test_http_request_counters(tiny):
    """http.* metrics live in the engine's registry: request count,
    per-status responses, stream count."""
    async def go():
        fe = HTTPFrontend(_engine(tiny), port=0, default_model="tiny")
        await fe.start()
        payload = dict(model="tiny", prompt=[2, 3, 4], max_tokens=3,
                       temperature=0.0)
        await http_client.request(fe.host, fe.port, "POST",
                                  "/v1/completions", payload)
        await http_client.collect_stream(fe.host, fe.port, payload)
        await http_client.request(fe.host, fe.port, "GET", "/v1/models")
        snap = fe.metrics.snapshot()
        await fe.aclose()
        return snap

    snap = asyncio.run(go())
    assert snap["http.requests"] == 3
    assert snap["http.streams"] == 1
    assert snap["http.responses.200"] == 3
    assert snap["engine.done"] == 2


def test_http_json_body_shape(tiny):
    """The JSON completion follows the OpenAI-style envelope."""
    async def go():
        fe = HTTPFrontend(_engine(tiny), port=0, default_model="tiny")
        await fe.start()
        status, body = await http_client.request(
            fe.host, fe.port, "POST", "/v1/completions",
            dict(model="tiny", prompt=[5, 6, 7], max_tokens=4,
                 temperature=0.0))
        await fe.aclose()
        return status, body

    status, body = asyncio.run(go())
    assert status == 200
    assert body["object"] == "text_completion"
    assert body["model"] == "tiny"
    assert body["id"].startswith("cmpl-")
    ch = body["choices"][0]
    assert ch["finish_reason"] == "length"
    assert len(ch["token_ids"]) == 4
    assert body["usage"] == {"prompt_tokens": 3, "completion_tokens": 4,
                             "total_tokens": 7}
