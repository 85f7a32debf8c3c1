"""Drive one cell: build from the seed, warm up, measure, check, report.

The system under test is ``repro.serving.Engine``, driven in this process
through its public calls (the constructor, ``submit``, ``step``,
``pending``) with the configuration file's engine shape and nothing
else: no ``attn_impl``, no ``hash_path``.  The benchmark measures the
path the program serves by default.

A run:

1. set-up: weights from the seed on the device, the engine, one warm-up
   request for each prefill bucket the traffic can reach and the sampler
   variants it uses (their programs compile or load here), then
   ``warmup_s`` of the cell's own traffic so the window starts at steady
   state;
2. the window: ``seconds`` of traffic, one ``step()`` after another;
3. a drain of at most ``DRAIN_S`` past the close, without new arrivals,
   until every request due in the window has its first token;
4. the device's peak memory is read, the engine is freed, and the plain
   reference checks a sample of what the window served (bench/check.py).

With ``trace`` the profiler records the last ``TRACE_S`` of the window;
the reduction (bench/xplane.py) gives busy and idle time and the
breakdown.  Host spans: ``bench.submit``, ``bench.step``,
``bench.traffic`` (the harness's own bookkeeping) and ``bench.idle``
(waiting for the next arrival).
"""
from __future__ import annotations

import gc
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from bench import check, cost, e2e, traffic as traffic_lib, weights
from bench.e2e import Rec, Run, Tick, unserved
from bench.layout import Layout

DRAIN_S = 60.0
TRACE_S = 4.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoAccelerator(RuntimeError):
    pass


def device_check(chips: int) -> List:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"first JAX device is {devs[0].platform!r}, "
                            "not a TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"{len(devs)} chips, the cell needs {chips}")
    return devs


def _tuples(d: Dict[str, Any]) -> Dict[str, Any]:
    return {k: tuple(v) if isinstance(v, list) else v for k, v in d.items()}


def build_model(name: str, config: Dict[str, Any]):
    """The program's model for a configuration file, checked against the
    file's published sizes."""
    from repro import configs
    from repro.models import build
    from repro.models.transformer import bank_spec_map

    prog = config["program"]
    cfg = configs.get(prog["arch"]).with_(name=name,
                                          **_tuples(prog["overrides"]))
    want = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
            "num_heads": "num_attention_heads",
            "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "d_ff": "intermediate_size", "vocab_size": "vocab_size",
            "rope_theta": "rope_theta",
            "tie_embeddings": "tie_word_embeddings"}
    for ours, theirs in want.items():
        if getattr(cfg, ours) != config[theirs]:
            raise ValueError(f"{name}: program has {ours}="
                             f"{getattr(cfg, ours)}, file has {theirs}="
                             f"{config[theirs]}")
    banks = {k: v.to_dict() for k, v in bank_spec_map(cfg).items()}
    return build(cfg), banks


class CompileCounter:
    """Compilations and persistent-cache loads, from JAX's monitoring
    events: the window should see none."""

    def __init__(self):
        self.compiles = 0
        self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.loads += 1


class Driver:
    """Feeds one engine from an arrival kind and records what the client
    saw."""

    def __init__(self, engine, arrivals: traffic_lib.Arrivals,
                 origin: float):
        self.eng = engine
        self.arr = arrivals
        self.origin = origin          # monotonic time of the window's 0
        self.recs: List[Rec] = []
        self.live: List[Rec] = []
        self.ticks: List[Tick] = []
        self.open = True              # arrivals still being sent

    def now(self) -> float:
        return time.monotonic() - self.origin

    def _submit(self, item, due: float) -> None:
        from repro.serving.api import SamplingParams
        from repro.serving.engine import Request

        sp = SamplingParams(temperature=item.temperature, top_p=item.top_p,
                            max_tokens=item.max_tokens, seed=item.seed)
        req = Request(uid=len(self.recs), prompt=item.prompt, sampling=sp)
        rec = Rec(uid=req.uid, due=due, prompt_len=len(item.prompt),
                  greedy=item.temperature == 0, sent=self.now(), req=req,
                  item=item)
        with jax.profiler.TraceAnnotation("bench.submit"):
            rec.accepted = bool(self.eng.submit(req))
        self.recs.append(rec)
        if rec.accepted:
            self.live.append(rec)
        else:
            self.arr.ended(item, [], self.now())

    def arrivals(self) -> None:
        """Send every request the arrival kind has due by now."""
        if self.open:
            for item, due in self.arr.send(self.now()):
                self._submit(item, due)

    def next_due(self) -> Optional[float]:
        return self.arr.next_due() if self.open else None

    def step(self) -> Tick:
        t0 = self.now()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.eng.step()
        t1 = self.now()
        tick = Tick(t0, t1)
        with jax.profiler.TraceAnnotation("bench.traffic"):
            still = []
            for r in self.live:
                req = r.req
                n_old, n_new = len(r.tokens), len(req.tokens)
                if r.admitted is None and req.status != "queued":
                    r.admitted = t1
                for k in range(n_old, n_new):
                    r.tokens.append(t1)
                    if k == 0:
                        tick.first_tokens += 1
                    else:
                        tick.decode_rows += 1
                        tick.decode_ctx += r.prompt_len + k
                if req.done or req.status in ("expired", "cancelled"):
                    r.done = t1
                    self.arr.ended(r.item, list(req.tokens), t1)
                else:
                    still.append(r)
            self.live = still
        self.ticks.append(tick)
        return tick

    def idle_until(self, t: float) -> None:
        with jax.profiler.TraceAnnotation("bench.idle"):
            time.sleep(max(0.0, t - self.now()))


def warm_up(engine, config: Dict[str, Any], mix: Dict[str, Any],
            vocab: int) -> None:
    """Greedy requests, submitted at once, one for each prefill bucket of
    the engine's menu (8, 16 and 32 tokens, then multiples of 64 up to
    the prefill chunk) and one of a chunk and 8 more, whose second chunk
    runs on a filled cache: so every prefill program, the decode step and
    the greedy sampler run.  Then, where the mix samples, greedy and
    sampled requests together, so the mixed-batch sampler variants run
    too.  A bucket missed here would compile inside the window; the
    window logs its compiles and cache loads."""
    from repro.serving.api import SamplingParams
    from repro.serving.engine import Request

    chunk = int(config["engine"].get("prefill_chunk") or 512)
    rng = np.random.default_rng(0)

    def run(lengths, sampled):
        for i, n in enumerate(lengths):
            s = sampled[i % len(sampled)] if sampled and i % 2 else {}
            sp = SamplingParams(temperature=s.get("temperature", 0.0),
                                top_p=s.get("top_p", 1.0), max_tokens=3,
                                seed=i)
            req = Request(uid=-1 - i, prompt=rng.integers(
                0, vocab, size=n).astype(np.int32), sampling=sp)
            if not engine.submit(req):
                raise RuntimeError("warm-up request refused")
        while engine.pending():
            engine.step()

    buckets = [b for b in (8, 16, 32) if b < chunk] \
        + list(range(64, chunk + 1, 64))
    run(sorted(set(buckets + [chunk, chunk + 8])), [])
    sampled = [m for m in mix["sampling"] if m.get("temperature", 0) > 0]
    if sampled:
        run([8, 8, 16, 16], sampled)


class Cell:
    """A cell's pieces, found by name: its entry, configuration file,
    traffic mix, and the program's model and bank specs."""

    def __init__(self, layout: Layout, name: str):
        self.name = name
        self.layout = layout
        self.entry = layout.cell(name)
        self.config = layout.config(self.entry["config"])
        self.traffic = layout.traffic(self.entry["traffic"])
        self.vocab = int(self.config["vocab_size"])
        self.model, self.banks = build_model(self.entry["config"],
                                             self.config)

    def schedule(self, seed: int, seconds: float) -> traffic_lib.Arrivals:
        return traffic_lib.generate(self.traffic, seed, seconds, self.vocab,
                                    self.layout.bench / "traffic")

    def params(self, seed: int):
        return weights.make(self.model, self.vocab, self.banks, seed)

    def engine(self, params):
        from repro.serving.engine import Engine
        return Engine(self.model, params, **self.config["engine"])


def _start_trace(path: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(path, profiler_options=opts)


def serve(engine, sched: traffic_lib.Arrivals, seconds: float,
          counter: CompileCounter, trace_dir: Optional[str] = None,
          trace_s: float = TRACE_S):
    """Warm-up traffic, the window, the drain.  Returns the driver, the
    window's end and the drain's end on the window clock.  With
    ``trace_dir`` the profiler records from ``trace_s`` before the
    window's close to the end of the drain."""
    origin = time.monotonic() + sched.warmup_s
    drv = Driver(engine, sched, origin)
    tracing = False
    window_end = None
    c0 = l0 = None

    def close(t):
        drv.open = False
        return t

    while True:
        now = drv.now()
        if c0 is None and now >= 0.0:
            c0, l0 = counter.compiles, counter.loads
        if trace_dir and not tracing and window_end is None \
                and now >= seconds - trace_s:
            _start_trace(trace_dir)
            tracing = True
        with jax.profiler.TraceAnnotation("bench.traffic"):
            drv.arrivals()
        if engine.pending():
            tick = drv.step()
            if window_end is None and tick.t1 >= seconds:
                window_end = close(tick.t1)
        elif window_end is None:
            nxt = drv.next_due()
            drv.idle_until(seconds if nxt is None else min(nxt, seconds))
            if drv.now() >= seconds and not engine.pending():
                window_end = close(drv.now())
        if window_end is not None:
            # open loop: every request due in the window gets its first
            # token; a closed loop's backlog is its clients' queue
            waiting = [r for r in drv.recs if 0 <= r.due < window_end
                       and r.accepted and not r.tokens] \
                if sched.open_loop else []
            if not waiting or drv.now() > window_end + DRAIN_S \
                    or not engine.pending():
                break
    drain_end = drv.now()
    if tracing:
        jax.profiler.stop_trace()
    n_in = sum(1 for t in drv.ticks if 0.0 <= t.t0 and t.t1 <= window_end)
    log(f"window {window_end:.3f} s: {n_in} steps, "
        f"{counter.compiles - c0} compiles and {counter.loads - l0} cache "
        f"loads inside; drain to {drain_end:.3f} s")
    if sched.open_loop:
        late = max((r.sent - r.due for r in drv.recs), default=0.0)
        log(f"generator ran at most {late * 1e3:.1f} ms late")

    return drv, window_end, drain_end


def make_run(cell: Cell, drv: Driver, window_end: float, drain_end: float,
             setup_s: float, kind: str, trace_summary=None) -> Run:
    return Run(cell=cell.name, config=cell.config,
               cost=cost.Model.from_config(cell.config),
               peak=cost.peaks(kind),
               rows=int(cell.config["engine"]["max_concurrency"]),
               setup_s=setup_s, window_s=window_end, drain_end=drain_end,
               recs=drv.recs,
               ticks=[t for t in drv.ticks
                      if 0.0 <= t.t0 and t.t1 <= window_end],
               trace=trace_summary, open_loop=drv.arr.open_loop)


def run_cell(layout: Layout, cell_name: str, seed: int, seconds: float,
             trace: bool, *, t_start: float) -> Dict[str, Any]:
    """One run of one cell; returns the result line's dict."""
    devs = device_check(layout.cell(cell_name)["chips"])
    cell = Cell(layout, cell_name)
    kind = devs[0].device_kind
    cost.peaks(kind)
    sched = cell.schedule(seed, seconds)
    params = cell.params(seed)
    log(f"{cell_name}: weights made at {time.monotonic() - t_start:.1f} s")
    counter = CompileCounter()
    engine = cell.engine(params)
    warm_up(engine, cell.config, cell.traffic, cell.vocab)
    log(f"warm-up requests done at {time.monotonic() - t_start:.1f} s "
        f"({counter.compiles} compiles, {counter.loads} cache loads)")
    setup_s = time.monotonic() + sched.warmup_s - t_start
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        drv, window_end, drain_end = serve(engine, sched, seconds, counter,
                                           trace_dir)
        peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
        summary = None
        if trace_dir:
            from bench import xplane
            files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
            summary = xplane.reduce_file(files[0]) if files else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run = make_run(cell, drv, window_end, drain_end, setup_s, kind, summary)
    if run.open_loop:
        log("time to first token p50/p75/p90: " + " ".join(
            f"{e2e.percentile(e2e.ttft(run), q):.4f}" for q in (50, 75, 90)))
    metrics = {}
    for m in layout.metrics(cell_name, trace):
        v = layout.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # free the program's state before the reference runs
    engine = drv.eng = None
    gc.collect()
    numbers, correct = judge(cell, params, run, seed)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(run.due_in_window),
           "failed": numbers["unserved"]["value"], "metrics": metrics,
           "device": device}
    if trace:
        ts = summary or {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                         "idle_gaps": []}
        device["busy_s"] = ts["busy_s"]
        device["window_s"] = ts["window_s"]
        out["breakdown"] = {"device_ops": ts["device_ops"],
                            "idle_gaps": ts["idle_gaps"]}
    out["check"] = numbers
    return out


def judge(cell: Cell, params, run: Run, seed: int, mode: str = "program"):
    """The numbers compared with their limits, and whether all hold."""
    picked = check.sample(run.recs, seed, run.window_s)
    t0 = time.monotonic()
    widest, n_tok = check.widest_gap(params, cell.banks, cell.config,
                                     picked,
                                     int(cell.config["engine"]["max_len"]),
                                     mode)
    log(f"check ({mode}): {len(picked)} requests, {n_tok} tokens, widest "
        f"gap {widest:.6g}, in {time.monotonic() - t0:.1f} s")
    lim = cell.config["check"]
    n_unserved = unserved(run)
    numbers = {
        "logit_gap": {"value": widest, "limit": lim["logit_gap_limit"]},
        "tokens_checked": {"value": n_tok,
                           "limit": lim["min_tokens_checked"]},
        "unserved": {"value": n_unserved, "limit": 0},
    }
    correct = (widest <= lim["logit_gap_limit"]
               and n_tok >= lim["min_tokens_checked"] and n_unserved == 0)
    return numbers, bool(correct)
