"""The open loop that drives the served path on the host clock.

``InferenceEngine.run`` advances a virtual clock by a fixed step, so it
cannot time anything.  The harness drives the engine's loop itself, one
iteration as ``run`` does it, and sets ``engine.clock`` from the host clock
so that telemetry, the scheduler and the DPU sidecar see real time:

  InferenceEngine.submit -> engine._emit(QUEUE_SAMPLE)
  -> engine._admit_loop (prefill, via engine._prefill) -> engine._step
  (decode, token readback) -> engine._flush_telemetry (into the DPU
  sidecar)

These private names and ``engine.sched``, ``engine._slot_next_token``
(which ``run`` would create) and ``engine.clock`` are the whole interface
into the engine.  Each call is wrapped in a ``jax.profiler.TraceAnnotation``
named ``bench.<layer>``, so a traced run can say what the host was doing
while the device sat idle.  The loop also keeps its longest iteration,
split by phase, and the longest pause of Python's garbage collector, so
that a stall of the host loop can be put down to a phase.
"""

from __future__ import annotations

import contextlib
import gc
import time
from dataclasses import dataclass, field

import jax

from repro.core.detectors import META_DIR_INGRESS
from repro.core.events import EventKind
from repro.serving import ServeRequest

from benchmarks.chip.traffic import Request

DRAIN_LIMIT_S = 60.0        # how long past the window a due answer may take
IDLE_SLEEP_S = 2e-3         # longest nap of a loop with nothing to do


@dataclass
class Step:
    start: float
    end: float
    contexts: list[int]          # per running slot: positions attended


@dataclass
class Record:
    """What one measured window produced, in seconds after it opened."""
    requests: list[Request]
    steps: list[Step] = field(default_factory=list)
    flush_s: float = 0.0
    stop: float = 0.0            # when the loop stopped
    seconds: float = 0.0         # the window's length
    longest: dict = field(default_factory=dict)   # phase -> s, longest turn
    gc_pause_s: float = 0.0      # longest pause of the garbage collector


class _Span:
    def __init__(self, on: bool) -> None:
        self.on = on

    def __call__(self, name: str):
        if self.on:
            return jax.profiler.TraceAnnotation("bench." + name)
        return contextlib.nullcontext()


class OpenLoop:
    """Drives one engine through one window."""

    def __init__(self, engine, spans: bool = False) -> None:
        self.engine = engine
        self.span = _Span(spans)
        self.base = time.perf_counter()      # engine clock origin
        self.t0 = self.base
        self.by_id: dict[int, Request] = {}
        self.buckets: dict[int, int] = {}
        self.rec: Record | None = None
        self._gc_start = 0.0
        engine._slot_next_token = {}         # run() would reset it
        self._wrap(engine)

    # -- instrumentation of the calls into the engine ------------------

    def _wrap(self, eng) -> None:
        prefill = eng._prefill

        def timed_prefill(slot, sreq):
            r = self.by_id.get(sreq.req_id)
            if r is not None:
                r.prefill_start = time.perf_counter() - self.t0
            with self.span("prefill"):
                prefill(slot, sreq)
            self.buckets[sreq.req_id] = eng.sched.bucket_len(sreq.prompt_len)
            if r is not None:
                r.token_times.append(time.perf_counter() - self.t0)
                r.tokens.append(int(eng._slot_next_token[slot]))

        eng._prefill = timed_prefill

    def _gc_event(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self.rec is not None:
            self.rec.gc_pause_s = max(self.rec.gc_pause_s,
                                      time.perf_counter() - self._gc_start)

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def iteration(self) -> bool:
        """One turn of the engine's loop, as ``InferenceEngine.run`` takes
        it; False when the engine had nothing to do."""
        eng = self.engine
        t = [time.perf_counter()]
        eng.clock = t[0] - self.base
        eng._emit(EventKind.QUEUE_SAMPLE, depth=eng.sched.queue_depth(),
                  meta=META_DIR_INGRESS)
        busy = bool(eng.sched.queue)
        with self.span("admit"):
            eng._admit_loop()
        t.append(time.perf_counter())
        if eng.sched.running:
            busy = True
            before = dict(eng.sched.running)
            ctx = [self.buckets.get(r.req_id, 0) + r.tokens_out + 1
                   for r in before.values()]
            start = self.now()
            with self.span("decode"):
                eng._step()
            end = self.now()
            for slot, sreq in before.items():
                r = self.by_id.get(sreq.req_id)
                if r is None:
                    continue
                r.tokens.append(int(eng._slot_next_token[slot]))
                r.token_times.append(end)
                r.finished = sreq.tokens_out >= sreq.max_new_tokens
            if self.rec is not None:
                self.rec.steps.append(Step(start, end, ctx))
        t.append(time.perf_counter())
        with self.span("telemetry"):
            eng._flush_telemetry()
        t.append(time.perf_counter())
        if self.rec is not None:
            self.rec.flush_s += t[3] - t[2]
            if t[3] - t[0] > sum(self.rec.longest.values()):
                self.rec.longest = {"admit": t[1] - t[0],
                                    "decode": t[2] - t[1],
                                    "telemetry": t[3] - t[2]}
        return busy

    # -- warm-up -------------------------------------------------------

    def warm_up(self, prompt_lens: list[int]) -> None:
        """Serve one request per prompt length, one new token each, with
        telemetry held back, until all are done: every program and eager
        op the window uses is compiled or loaded."""
        eng = self.engine
        plane, eng.plane = eng.plane, None
        try:
            for j, n in enumerate(prompt_lens):
                eng.sched.submit(ServeRequest(
                    req_id=-1 - j, arrival=0.0, prompt=[1] * n,
                    max_new_tokens=1))
            while eng.sched.queue or eng.sched.running:
                self.iteration()
        finally:
            eng.plane = plane
        eng.completed.clear()
        jax.effects_barrier()

    # -- the window ----------------------------------------------------

    def _submit(self, r: Request) -> None:
        r.submitted = self.now()
        self.engine.submit(ServeRequest(
            req_id=r.rid, arrival=self.t0 - self.base + r.due,
            prompt=r.prompt, max_new_tokens=r.max_new_tokens))

    def _done(self, drain: str, seconds: float) -> bool:
        now = self.now()
        if now < seconds:
            return False
        if drain == "none" or now >= seconds + DRAIN_LIMIT_S:
            return True
        return all(r.tokens for r in self.rec.requests)

    def run(self, requests: list[Request], seconds: float,
            drain: str) -> Record:
        """Open loop: each request is submitted when due, on the host
        clock, whether or not earlier ones are done.  After the window,
        ``drain="first_token"`` keeps serving until every request due in
        it has its first token; ``"none"`` stops at once."""
        self.rec = Record(requests=requests, seconds=seconds)
        self.by_id = {r.rid: r for r in requests}
        pending = sorted(requests, key=lambda r: r.due)
        gc.callbacks.append(self._gc_event)
        self.t0 = time.perf_counter()
        try:
            with self.span("window"):
                self._loop(pending, seconds, drain)
        finally:
            gc.callbacks.remove(self._gc_event)
        self.rec.stop = self.now()
        return self.rec

    def _loop(self, pending, seconds, drain) -> None:
        k = 0
        while not self._done(drain, seconds):
            while k < len(pending) and pending[k].due <= self.now():
                with self.span("submit"):
                    self._submit(pending[k])
                k += 1
            if not self.iteration():
                nxt = pending[k].due if k < len(pending) else seconds
                with self.span("idle"):
                    time.sleep(min(max(nxt - self.now(), 0.0),
                                   IDLE_SLEEP_S))
