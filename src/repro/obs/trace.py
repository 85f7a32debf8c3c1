"""Engine step spans on the profiler's clock, the step account, and
per-request tracks in Chrome trace-event JSON.

Always on, whatever ``enabled`` says:

- ``Tracer.step()`` wraps one engine step in
  ``jax.profiler.StepTraceAnnotation("engine.step", step_num=<id>)`` and
  keeps a `Step` record of it: its bounds on ``time.monotonic`` (the
  engine's duration clock), the self seconds of each phase, the seconds
  the host spent blocked on device results (``wait_s``), the rest
  (``host_s``), the rows and tokens it advanced, and the compiles and
  persistent-cache loads it ran (one process-wide ``jax.monitoring``
  listener counts them).
- ``Tracer.span(phase)`` wraps one phase of a step in
  ``jax.profiler.TraceAnnotation("engine.<phase>")`` and adds its self
  time (its duration less that of the spans nested in it) to the step's
  ``phase_s``.  The phase ``wait`` goes around every place the host
  blocks on the device, and adds to ``wait_s``.  When a profiler session
  is on, both land on the host plane of the same ``.xplane.pb`` as the
  device operations, on its clock.

With ``enabled``, the tracer also records events in the Chrome
trace-event format (the JSON Perfetto opens: https://ui.perfetto.dev):

- pid `ENGINE_PID`, tid 0: the engine track, one complete (``X``) slice
  per step and per phase span, named as the profiler names them.
- pid `REQUEST_PID`, tid = request uid: one track per request, a
  ``request`` span enclosing ``queued`` spans (the first wait and every
  wait after a preemption), ``prefill_chunk`` slices and ``preempt`` /
  ``cow_copy`` / ``first_token`` instants; each event made inside a step
  carries the step's id.

Clock: Chrome timestamps are microseconds on the clock the profiler
stamps host events with (``CLOCK_REALTIME``, ``time.time_ns``), so a
``--trace-out`` file lies over a device trace of the same run.  A
trace's ``.xplane.pb`` gives its events relative to the
``profile_start_time`` of its ``Task Environment`` plane, on that clock.

Cost with the profiler off and ``enabled`` False: per span, one TraceMe
check and two ``time.monotonic`` reads; per step, one `Step` object.
About 15 spans a step come to some tens of microseconds.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional, Tuple

import jax

ENGINE_PID = 0
REQUEST_PID = 1
WAIT = "wait"

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")
_compiles = 0
_listening = False


def _on_duration(event: str, duration: float, **_) -> None:
    global _compiles
    if event in _COMPILE_EVENTS:
        _compiles += 1


def _listen_for_compiles() -> None:
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def profiler_us() -> float:
    """Now, in microseconds on the profiler's host clock."""
    return time.time_ns() / 1e3


@dataclasses.dataclass(eq=False)
class Step:
    """The account of one engine step; times in seconds."""
    id: int
    t0: float                   # time.monotonic at the step's start
    t1: float = 0.0             # ... and at its end
    phase_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    wait_s: float = 0.0         # blocked on device results
    host_s: float = 0.0         # (t1 - t0) - wait_s
    prefill_rows: int = 0       # rows that advanced a prefill chunk
    prefill_tokens: int = 0     # prompt tokens those chunks computed
    decode_rows: int = 0        # rows that decoded
    attn_pages: int = 0         # KV pages the decode attention read:
    #                             ceil((len + 1) / page) over those rows
    compiles: int = 0           # compiles and cache loads in the step


class _Span:
    __slots__ = ("tr", "phase", "ann", "t0", "us0", "child", "dur_s")

    def __init__(self, tr: "Tracer", phase: str):
        self.tr = tr
        self.phase = phase
        self.child = 0.0

    def __enter__(self) -> "_Span":
        self.ann = jax.profiler.TraceAnnotation("engine." + self.phase)
        self.ann.__enter__()
        self.tr._stack.append(self)
        if self.tr.enabled:
            self.us0 = profiler_us()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        tr = self.tr
        self.dur_s = time.monotonic() - self.t0
        tr._stack.pop()
        if tr._stack:
            tr._stack[-1].child += self.dur_s
        st = tr.current
        if st is not None:
            if self.phase == WAIT:
                st.wait_s += self.dur_s
            else:
                st.phase_s[self.phase] = st.phase_s.get(self.phase, 0.0) \
                    + self.dur_s - self.child
        if tr.enabled:
            tr._push("X", ENGINE_PID, 0, "engine." + self.phase, self.us0,
                     {}, dur=self.dur_s * 1e6)
        self.ann.__exit__(*exc)


class _StepSpan:
    __slots__ = ("tr", "args", "ann", "us0", "c0", "step")

    def __init__(self, tr: "Tracer", args: dict):
        self.tr = tr
        self.args = args

    def __enter__(self) -> Step:
        tr = self.tr
        if tr.current is not None:
            raise RuntimeError("engine steps do not nest")
        tr._steps += 1
        self.ann = jax.profiler.StepTraceAnnotation("engine.step",
                                                    step_num=tr._steps)
        self.ann.__enter__()
        if tr.enabled:
            self.us0 = profiler_us()
        self.c0 = _compiles
        self.step = tr.current = Step(id=tr._steps, t0=time.monotonic())
        return self.step

    def __exit__(self, *exc) -> None:
        tr, st = self.tr, self.step
        st.t1 = time.monotonic()
        st.host_s = (st.t1 - st.t0) - st.wait_s
        inner = sum(st.phase_s.values()) + st.wait_s
        st.phase_s["step"] = (st.t1 - st.t0) - inner
        st.compiles = _compiles - self.c0
        tr.current = None
        if tr.enabled:
            tr._push("X", ENGINE_PID, 0, "engine.step", self.us0,
                     dict(self.args, id=st.id),
                     dur=(st.t1 - st.t0) * 1e6)
        self.ann.__exit__(*exc)


class Tracer:
    def __init__(self, enabled: bool = True):
        self.enabled = bool(enabled)
        self.events: List[dict] = []
        self._tracks: Dict[Tuple[int, int], str] = {}
        self.current: Optional[Step] = None     # the step in progress
        self._stack: List[_Span] = []
        self._steps = 0
        _listen_for_compiles()

    # ------------------------------------------------------------------
    def step(self, **args) -> _StepSpan:
        """Context manager around one engine step; yields its `Step`.
        ``args`` go on the step's Chrome event."""
        return _StepSpan(self, args)

    def span(self, phase: str) -> _Span:
        """Context manager around one phase of the step in progress (or
        an annotation alone outside a step).  After it exits, its
        ``dur_s`` holds its seconds."""
        return _Span(self, phase)

    def now(self) -> float:
        """Microseconds on the profiler's clock (0.0 when disabled —
        callers stash the value and pass it back to ``complete``)."""
        if not self.enabled:
            return 0.0
        return profiler_us()

    def track(self, pid: int, tid: int, name: str) -> None:
        """Name a (pid, tid) track; idempotent."""
        if not self.enabled or (pid, tid) in self._tracks:
            return
        self._tracks[(pid, tid)] = name

    def _push(self, ph: str, pid: int, tid: int, name: Optional[str],
              ts: float, args: dict, **extra) -> None:
        if pid == REQUEST_PID and self.current is not None:
            args = dict(args, step=self.current.id)
        ev = {"ph": ph, "pid": pid, "tid": tid, "ts": ts,
              "cat": "serving"}
        if name is not None:
            ev["name"] = name
        if args:
            ev["args"] = args
        ev.update(extra)
        self.events.append(ev)

    def begin(self, pid: int, tid: int, name: str, **args) -> None:
        if not self.enabled:
            return
        self._push("B", pid, tid, name, self.now(), args)

    def end(self, pid: int, tid: int, name: Optional[str] = None,
            **args) -> None:
        if not self.enabled:
            return
        self._push("E", pid, tid, name, self.now(), args)

    def complete(self, pid: int, tid: int, name: str, start_us: float,
                 **args) -> None:
        """A finished slice: ``start_us`` from an earlier ``now()``."""
        if not self.enabled:
            return
        self._push("X", pid, tid, name, start_us, args,
                   dur=max(self.now() - start_us, 0.0))

    def instant(self, pid: int, tid: int, name: str, **args) -> None:
        if not self.enabled:
            return
        self._push("i", pid, tid, name, self.now(), args, s="t")

    # ------------------------------------------------------------------
    def to_json(self) -> dict:
        meta: List[dict] = []
        for pid, pname in ((ENGINE_PID, "engine"),
                           (REQUEST_PID, "requests")):
            meta.append({"ph": "M", "pid": pid, "tid": 0,
                         "name": "process_name",
                         "args": {"name": pname}})
        for (pid, tid), name in sorted(self._tracks.items()):
            meta.append({"ph": "M", "pid": pid, "tid": tid,
                         "name": "thread_name", "args": {"name": name}})
        return {"traceEvents": meta + self.events,
                "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)
