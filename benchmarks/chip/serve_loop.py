"""The open loop that drives the served path on the host clock.

``InferenceEngine.run`` advances a virtual clock by a fixed step, so it
cannot time anything.  The harness drives the engine's loop itself, one
iteration as ``run`` does it, and sets ``engine.clock`` from the host clock
so that telemetry, the scheduler and the DPU sidecar see real time:

  InferenceEngine.submit -> engine._emit(QUEUE_SAMPLE)
  -> engine._admit_loop (prefill, via engine._prefill) -> engine._step
  (decode, token readback) -> engine._flush_telemetry (into the DPU
  sidecar)

These private names and ``engine.sched``, ``engine.pool``,
``engine.stats``, ``engine._slot_next_token`` (which ``run`` would create)
and ``engine.clock`` are the whole interface into the engine.

One engine is driven on the calling thread: each due request is submitted
between two turns of its loop.  Several engines (one-chip replicas) sit
behind ``serving/router.py:ReplicaSet``, as behind one front end: the
calling thread routes each due request through ``ReplicaSet.submit`` and
each replica runs its own loop on a host thread of its own, so that the
devices work at once, as one serving process a replica would.  The router
sees a replica through ``_Front``: what the replica's thread published
after its last turn (queue, running slots, cache occupancy) plus what was
routed to it since; the replica's thread hands those requests to its
engine at the top of its next turn.  No engine is touched from two
threads.

Each call is wrapped in a ``jax.profiler.TraceAnnotation`` named
``bench.<layer>`` (``bench.d<device id>.<layer>`` on a replica's thread),
so a traced run can say what the host was doing while a device sat idle.
The loop also keeps its longest iteration, split by phase, and the longest
pause of Python's garbage collector, so that a stall of the host loop can
be put down to a phase.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from dataclasses import dataclass, field

import jax

from repro.core.detectors import META_DIR_INGRESS
from repro.core.events import EventKind
from repro.serving import ReplicaSet, ServeRequest

from benchmarks.chip.traffic import Request

DRAIN_LIMIT_S = 60.0        # how long past the window a due answer may take
IDLE_SLEEP_S = 2e-3         # longest nap of a loop with nothing to do


@dataclass
class Step:
    start: float
    end: float
    contexts: list[int]          # per running slot: positions attended
    replica: int = 0
    counters: dict = field(default_factory=dict)  # engine stats it moved


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
    # each engine's ``stats`` at the loop's stop less at the window's
    # start: summed over the replicas, and per replica
    counters: dict = field(default_factory=dict)
    replica_counters: list[dict] = field(default_factory=list)


class _Span:
    def __init__(self, on: bool, prefix: str = "") -> None:
        self.on, self.prefix = on, "bench." + prefix

    def __call__(self, name: str):
        if self.on:
            return jax.profiler.TraceAnnotation(self.prefix + name)
        return contextlib.nullcontext()


class _Front:
    """A replica as the router sees it (duck-typed for
    ``router.engine_snapshot``: ``sched.queue``, ``sched.running``,
    ``sched.cfg``, ``pool.occupancy()``; ``submit``)."""

    def __init__(self, engine) -> None:
        self.lock = threading.Lock()
        self.wake = threading.Event()
        self.inbox: list = []
        self.pool = self
        self._cfg = engine.sched.cfg
        self.publish(engine)

    def publish(self, engine) -> None:
        """The replica's thread, after a turn: a copy of its state."""
        queue, running = list(engine.sched.queue), dict(engine.sched.running)
        occupancy = engine.pool.occupancy()
        with self.lock:
            self.sched = _Sched(queue + self.inbox, running, self._cfg)
            self._occupancy = occupancy

    def occupancy(self) -> float:
        return self._occupancy

    def submit(self, req) -> None:
        """The router's thread: hand a request to the replica."""
        with self.lock:
            self.inbox.append(req)
            self.sched.queue.append(req)
        self.wake.set()

    def take(self) -> list:
        """The replica's thread: the requests routed to it since."""
        with self.lock:
            got, self.inbox = self.inbox, []
        return got


@dataclass
class _Sched:
    queue: list
    running: dict
    cfg: object


class _Lane:
    """One engine and what the window recorded of it."""

    def __init__(self, index: int, engine, spans: bool, prefix: str) -> None:
        self.index, self.engine = index, engine
        self.span = _Span(spans, prefix)
        self.front: _Front | None = None
        self.steps: list[Step] = []
        self.flush_s = 0.0
        self.longest: dict = {}
        self.stats0: dict = {}


def _moved(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


class OpenLoop:
    """Drives one engine, or several behind a ``ReplicaSet``, through one
    window."""

    def __init__(self, engines, spans: bool = False,
                 router: str | None = None, seed: int = 0) -> None:
        engines = list(engines) if isinstance(engines, (list, tuple)) \
            else [engines]
        self.engine = engines[0]
        self.span = _Span(spans)
        many = len(engines) > 1
        self.lanes = [_Lane(i, eng, spans,
                            f"d{eng.device.id}." if many else "")
                      for i, eng in enumerate(engines)]
        self.replicas = None
        if many:
            for lane in self.lanes:
                lane.front = _Front(lane.engine)
            kw = {"policy": router} if router else {}
            self.replicas = ReplicaSet([lane.front for lane in self.lanes],
                                       seed=seed, **kw)
        self.base = time.perf_counter()      # engine clock origin
        self.t0 = self.base
        self.by_id: dict[int, Request] = {}
        self.buckets: dict[int, int] = {}
        self.rec: Record | None = None
        self._gc_start = 0.0
        for lane in self.lanes:
            lane.engine._slot_next_token = {}    # run() would reset it
            self._wrap(lane.engine, lane.span)

    # -- instrumentation of the calls into the engine ------------------

    def _wrap(self, eng, span) -> None:
        prefill = eng._prefill

        def timed_prefill(slot, sreq):
            r = self.by_id.get(sreq.req_id)
            if r is not None:
                r.prefill_start = time.perf_counter() - self.t0
            with span("prefill"):
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

    def iteration(self, lane: _Lane | None = None) -> bool:
        """One turn of an engine's loop, as ``InferenceEngine.run`` takes
        it; False when the engine had nothing to do."""
        lane = lane or self.lanes[0]
        eng, span = lane.engine, lane.span
        t = [time.perf_counter()]
        eng.clock = t[0] - self.base
        eng._emit(EventKind.QUEUE_SAMPLE, depth=eng.sched.queue_depth(),
                  meta=META_DIR_INGRESS)
        busy = bool(eng.sched.queue)
        with span("admit"):
            eng._admit_loop()
        t.append(time.perf_counter())
        if eng.sched.running:
            busy = True
            before = dict(eng.sched.running)
            ctx = [self.buckets.get(r.req_id, 0) + r.tokens_out + 1
                   for r in before.values()]
            stats = dict(eng.stats)
            start = self.now()
            with span("decode"):
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
                lane.steps.append(Step(start, end, ctx, lane.index,
                                       _moved(stats, eng.stats)))
        t.append(time.perf_counter())
        with span("telemetry"):
            eng._flush_telemetry()
        t.append(time.perf_counter())
        if self.rec is not None:
            lane.flush_s += t[3] - t[2]
            if t[3] - t[0] > sum(lane.longest.values()):
                lane.longest = {"admit": t[1] - t[0],
                                "decode": t[2] - t[1],
                                "telemetry": t[3] - t[2]}
        return busy

    # -- warm-up -------------------------------------------------------

    def warm_up(self, prompt_lens: list[int]) -> None:
        """Serve one request per prompt length on every engine, one new
        token each, with telemetry held back, until all are done: every
        program and eager op the window uses is compiled or loaded.  The
        replicas warm up at once, each on its own thread."""
        if len(self.lanes) == 1:
            self._warm_up(self.lanes[0], prompt_lens)
        else:
            self._threads([lambda lane=lane: self._warm_up(lane, prompt_lens)
                           for lane in self.lanes])
        jax.effects_barrier()

    def _warm_up(self, lane: _Lane, prompt_lens: list[int]) -> None:
        eng = lane.engine
        plane, eng.plane = eng.plane, None
        try:
            for j, n in enumerate(prompt_lens):
                eng.sched.submit(ServeRequest(
                    req_id=-1 - j, arrival=0.0, prompt=[1] * n,
                    max_new_tokens=1))
            while eng.sched.queue or eng.sched.running:
                self.iteration(lane)
        finally:
            eng.plane = plane
        eng.completed.clear()
        if lane.front is not None:
            lane.front.publish(eng)

    @staticmethod
    def _threads(jobs: list, stop: threading.Event | None = None) -> None:
        """Runs each job on a thread of its own and waits for all; the
        first error is raised here (and sets ``stop``)."""
        errors: list[BaseException] = []

        def guard(job):
            try:
                job()
            except BaseException as e:      # noqa: BLE001 - raised below
                errors.append(e)
                if stop is not None:
                    stop.set()

        threads = [threading.Thread(target=guard, args=(job,), daemon=True)
                   for job in jobs]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errors:
            raise errors[0]

    # -- the window ----------------------------------------------------

    def _request(self, r: Request) -> ServeRequest:
        r.submitted = self.now()
        return ServeRequest(req_id=r.rid, arrival=self.t0 - self.base + r.due,
                            prompt=r.prompt, max_new_tokens=r.max_new_tokens)

    def _submit(self, r: Request) -> None:
        req = self._request(r)
        r.replica = 0
        self.engine.submit(req)

    def _route(self, r: Request) -> None:
        req = self._request(r)
        r.replica = self.replicas.submit(req, time.perf_counter() - self.base)

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
        for lane in self.lanes:
            lane.steps, lane.flush_s, lane.longest = [], 0.0, {}
            lane.stats0 = dict(lane.engine.stats)
        gc.callbacks.append(self._gc_event)
        self.t0 = time.perf_counter()
        try:
            with self.span("window"):
                if self.replicas is None:
                    self._loop(pending, seconds, drain)
                else:
                    self._replica_loop(pending, seconds, drain)
        finally:
            gc.callbacks.remove(self._gc_event)
        self.rec.stop = self.now()
        self._collect()
        return self.rec

    def _collect(self) -> None:
        rec = self.rec
        rec.steps = sorted((s for lane in self.lanes for s in lane.steps),
                           key=lambda s: s.start)
        rec.flush_s = sum(lane.flush_s for lane in self.lanes)
        rec.longest = max((lane.longest for lane in self.lanes),
                          key=lambda d: sum(d.values()))
        rec.replica_counters = [
            {k: v - lane.stats0.get(k, 0)
             for k, v in lane.engine.stats.items()} for lane in self.lanes]
        rec.counters = {k: sum(c[k] for c in rec.replica_counters)
                        for k in rec.replica_counters[0]}

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

    def _replica_loop(self, pending, seconds, drain) -> None:
        """Routes each due request on this thread while every replica
        serves on its own; the loop stops once the window (and the drain)
        is over and every replica has finished its turn."""
        stop = threading.Event()

        def route():
            k = 0
            try:
                while not stop.is_set() and not self._done(drain, seconds):
                    while k < len(pending) and pending[k].due <= self.now():
                        with self.span("submit"):
                            self._route(pending[k])
                        k += 1
                    nxt = pending[k].due if k < len(pending) else seconds
                    time.sleep(min(max(nxt - self.now(), 0.0),
                                   IDLE_SLEEP_S))
            finally:
                stop.set()
                for lane in self.lanes:
                    lane.front.wake.set()

        self._threads([route] + [lambda lane=lane: self._serve(lane, stop)
                                 for lane in self.lanes], stop)

    def _serve(self, lane: _Lane, stop: threading.Event) -> None:
        """A replica's own loop: what was routed to it, then one turn."""
        eng, front = lane.engine, lane.front
        while not stop.is_set():
            for req in front.take():
                with lane.span("submit"):
                    eng.submit(req)
            busy = self.iteration(lane)
            front.publish(eng)
            if not busy:
                with lane.span("idle"):
                    front.wake.wait(IDLE_SLEEP_S)
                front.wake.clear()
