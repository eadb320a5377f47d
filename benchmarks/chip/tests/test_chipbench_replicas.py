"""A cell's chips are that many one-chip replicas behind ``ReplicaSet``:
the open loop over several engines, at a CPU size; one engine is driven
exactly as before; the readers of the replica metrics."""

import _paths
from _paths import DATA, ROOT

import json
import os
import subprocess
import sys
import textwrap
import threading
from types import SimpleNamespace

import jax
import pytest

from benchmarks.chip import run, spec, stats, traffic
from benchmarks.chip.serve_loop import OpenLoop, Record, Step
from benchmarks.chip.traffic import Request

SECONDS = 2.0
SEED = 2**34 + 3


class _Sched:
    def __init__(self, slots):
        self.queue, self.running = [], {}
        self.cfg = SimpleNamespace(max_slots=slots)

    def submit(self, req):
        self.queue.append(req)

    def queue_depth(self):
        return len(self.queue)

    def bucket_len(self, n):
        return -(-n // 16) * 16


class FakeEngine:
    """Records every call the open loop makes into it."""

    def __init__(self, slots=2):
        self.calls = []
        self.sched = _Sched(slots)
        self.pool = SimpleNamespace(occupancy=lambda: 0.0)
        self.stats = {"steps": 0, "tokens": 0, "prefills": 0}
        self.plane, self.completed, self.clock = None, [], 0.0
        self.device = SimpleNamespace(id=0)

    def submit(self, req):
        self.calls.append(("submit", req.req_id))
        self.sched.submit(req)

    def _emit(self, kind, **kw):
        self.calls.append(("emit", kind.name, kw.get("depth")))

    def _admit_loop(self):
        self.calls.append(("admit",))
        free = [s for s in range(self.sched.cfg.max_slots)
                if s not in self.sched.running]
        while self.sched.queue and free:
            req, slot = self.sched.queue.pop(0), free.pop(0)
            self.sched.running[slot] = req
            self._prefill(slot, req)

    def _prefill(self, slot, req):
        self.calls.append(("prefill", req.req_id, slot))
        req.tokens_out = 0
        self._slot_next_token[slot] = 100 + req.req_id
        self.stats["prefills"] += 1

    def _step(self):
        self.calls.append(("step", tuple(sorted(self.sched.running))))
        self.stats["steps"] += 1
        for slot, req in list(self.sched.running.items()):
            req.tokens_out += 1
            self.stats["tokens"] += 1
            self._slot_next_token[slot] = 200 + req.req_id
            if req.tokens_out >= req.max_new_tokens:
                del self.sched.running[slot]
                self.completed.append(req)

    def _flush_telemetry(self):
        self.calls.append(("flush",))


# the calls the open loop made into one engine before it served replicas
ONE_ENGINE_CALLS = [
    ("emit", "QUEUE_SAMPLE", 2), ("admit",), ("prefill", -1, 0),
    ("prefill", -2, 1), ("step", (0, 1)), ("flush",),
    ("submit", 0), ("submit", 1), ("submit", 2), ("submit", 3),
    ("submit", 4),
    ("emit", "QUEUE_SAMPLE", 5), ("admit",), ("prefill", 0, 0),
    ("prefill", 1, 1), ("step", (0, 1)), ("flush",),
    ("emit", "QUEUE_SAMPLE", 3), ("admit",), ("prefill", 2, 0),
    ("step", (0, 1)), ("flush",),
    ("emit", "QUEUE_SAMPLE", 2), ("admit",), ("prefill", 3, 1),
    ("step", (0, 1)), ("flush",),
    ("emit", "QUEUE_SAMPLE", 1), ("admit",), ("prefill", 4, 1),
    ("step", (0, 1)), ("flush",),
]


def test_one_engine_is_driven_with_the_same_calls_in_the_same_order():
    eng = FakeEngine(slots=2)
    loop = OpenLoop(eng)
    loop.warm_up([20, 40])
    reqs = [Request(rid=i, due=0.0, prompt=[1] * (10 + i),
                    max_new_tokens=1 + i % 3) for i in range(5)]
    rec = loop.run(reqs, 0.0, "first_token")
    assert eng.calls == ONE_ENGINE_CALLS
    assert [s.contexts for s in rec.steps] == [[17, 17], [18, 17], [18, 17],
                                              [19, 17]]
    assert {r.replica for r in reqs} == {0}
    assert rec.counters == rec.replica_counters[0] == \
        {"steps": 4, "tokens": 8, "prefills": 5}
    assert [s.counters["steps"] for s in rec.steps] == [1, 1, 1, 1]


@pytest.fixture(scope="module")
def two_replicas():
    cfg = spec.load_json(DATA / "tiny.json")
    mix = dict(spec.load_json(DATA / "tiny-chat.json"), rate_per_s=10)
    dev = jax.devices()[0]
    engines = [run.build_engine(cfg, SEED, dev, node=i) for i in range(2)]
    loop = run.open_loop(engines, mix, SEED)
    loop.warm_up(sorted({engines[0].sched.bucket_len(n)
                         for n in traffic.prompt_lengths(mix, SECONDS)}))
    threads = [set() for _ in engines]
    for eng, seen in zip(engines, threads):
        for name in ("submit", "_emit", "_admit_loop", "_step",
                     "_flush_telemetry"):
            real = getattr(eng, name)

            def spy(*a, real=real, seen=seen, **kw):
                seen.add(threading.get_ident())
                return real(*a, **kw)
            setattr(eng, name, spy)
    requests = traffic.generate(mix, SEED, SECONDS, cfg["vocab_size"])
    rec = loop.run(requests, SECONDS, mix["drain"])
    return {"rec": rec, "engines": engines, "threads": threads,
            "loop": loop}


def test_two_replicas_answer_every_request_and_the_router_spreads_them(
        two_replicas):
    rec = two_replicas["rec"]
    due = [r for r in rec.requests if r.due < SECONDS]
    assert len(due) == 20 and all(r.tokens for r in due)
    routed = [sum(r.replica == i for r in rec.requests) for i in (0, 1)]
    assert min(routed) >= 4 and sum(routed) == 20
    assert {s.replica for s in rec.steps} == {0, 1}
    per = rec.replica_counters
    assert [c["prefills"] for c in per] == routed
    assert rec.counters["tokens"] == sum(c["tokens"] for c in per)
    assert rec.counters["steps"] == len(rec.steps)
    picked = run.sample(rec.requests, SEED, 3)
    assert {r.replica for r in picked} == {0, 1}


def test_no_engine_is_touched_from_two_threads(two_replicas):
    a, b = two_replicas["threads"]
    assert len(a) == len(b) == 1 and a != b


def test_the_router_sees_what_each_replica_published(two_replicas):
    """A routed request shows in its replica's queue until the replica's
    thread takes it and publishes its engine's state again."""
    loop, engines = two_replicas["loop"], two_replicas["engines"]
    for lane, eng in zip(loop.lanes, engines):
        assert lane.front.sched.queue == list(eng.sched.queue)
    req = SimpleNamespace(req_id=99, prompt_len=8, max_new_tokens=4)
    i = loop.replicas.submit(req, 0.0)
    assert [req in lane.front.sched.queue for lane in loop.lanes] == \
        [j == i for j in range(2)]
    front = loop.lanes[i].front
    assert front.take() == [req]
    front.publish(engines[i])
    assert req not in front.sched.queue


SCRIPT = textwrap.dedent("""
    import json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path[:0] = [{src!r}, {root!r}]
    import jax
    from benchmarks.chip import run, spec

    cfg = spec.load_json(spec.HERE / "tests" / "data" / "tiny.json")
    mix = dict(spec.load_json(spec.HERE / "tests" / "data" / "tiny-chat.json"),
               rate_per_s=8, router="join_shortest_queue")
    cell = {{"name": "tiny.replicas", "chips": 2}}
    bench = {{"end_to_end": [{{"name": "setup_s", "unit": "s"}},
                             {{"name": "ttft_p50_ms", "unit": "ms"}}],
              "per_layer": []}}
    logs = []

    def once(seed):
        return run.run_cell(cell, cfg, mix, seed=seed, seconds=2.0,
                            trace=False, devices=jax.devices(), bench=bench,
                            t_start=time.perf_counter(),
                            log=lambda *a: logs.append(" ".join(map(str, a))))

    out = {{"sound": once(2**35 + 1)}}
    real = run.build_engine

    def broken(cfg, seed, device, node=0):
        eng = real(cfg, seed, device, node)
        if node == 1:                   # replica 1's tokens are altered
            step = eng._decode

            def decode(params, toks, cache):
                logits, cache = step(params, toks, cache)
                return logits.at[..., 7].add(1e4), cache
            eng._decode = decode
        return eng

    run.build_engine = broken
    out["broken"] = once(2**35 + 1)
    out["logs"] = logs
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def on_two_devices():
    code = SCRIPT.format(src=str(ROOT / "src"), root=str(ROOT))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, out.stdout[-2000:] + out.stderr[-4000:]
    return json.loads(line[-1][len("RESULT "):])


def test_a_run_over_two_devices_is_correct_and_samples_both(on_two_devices):
    res = on_two_devices["sound"]
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] == 16
    assert set(res["metrics"]) == {"setup_s", "ttft_p50_ms"}
    assert res["device"]["count"] == 2
    logs = "\n".join(on_two_devices["logs"])
    assert "[replicas] devices: ['TFRT_CPU_0', 'TFRT_CPU_1']" in logs
    assert "(replicas [0, 1])" in logs
    assert "[replicas] peak memory, bytes:" in logs


def test_a_token_altered_on_one_replica_is_not_correct(on_two_devices):
    res = on_two_devices["broken"]
    assert not res["correct"]
    assert res["checks"]["widest_gap"]["value"] > \
        res["checks"]["widest_gap"]["limit"]


def _req(rid, replica, times):
    return Request(rid=rid, due=0.0, prompt=[1] * 10, max_new_tokens=4,
                   tokens=[5] * len(times), token_times=list(times),
                   replica=replica)


def test_replica_imbalance_is_the_busiest_replica_over_the_mean():
    rec = Record(requests=[], seconds=1.0, stop=1.0, replica_counters=[
        {"tokens": 90, "prefills": 10, "steps": 40},
        {"tokens": 45, "prefills": 5, "steps": 30},
        {"tokens": 27, "prefills": 3, "steps": 20},
        {"tokens": 27, "prefills": 3, "steps": 20}])
    read = spec.reader("replica_imbalance.replicas")
    run_ = stats.Run(record=rec, setup_s=1.0, config={}, peak={}, chips=4)
    assert read(run_) == pytest.approx(100 / ((100 + 50 + 30 + 30) / 4))
    rec.replica_counters = [{"tokens": 0, "prefills": 0}] * 2
    assert read(run_) is None


def test_mfu_divides_by_the_peak_of_every_chip_in_use():
    cfg = spec.config("qwen3-0.6b")
    rec = Record(requests=[_req(0, 0, [0.5, 1.0, 3.0]),
                           _req(1, 3, [0.2, 0.4])], seconds=2.0, stop=3.0)
    one = stats.Run(record=rec, setup_s=1.0, config=cfg,
                    peak=spec.peaks("TPU v5 lite"))
    four = stats.Run(record=rec, setup_s=1.0, config=cfg,
                     peak=spec.peaks("TPU v5 lite"), chips=4)
    read = spec.reader("mfu.replicas")
    arch = spec.arch("dense")
    want = (arch.request_flops(cfg, 10, 2) * 2) / (2.0 * 4 * 197e12) * 100
    assert read(four) == pytest.approx(want)
    assert read(one) == pytest.approx(4 * read(four))


def test_the_decode_roofline_is_per_chip_whatever_the_replicas():
    """Each step runs on one chip: four replicas' steps and calls read the
    same share as one replica's."""
    cfg = spec.config("qwen3-0.6b")
    peak = spec.peaks("TPU v5 lite")

    class Trace:
        def __init__(self, n):
            self.n = n

        def program(self, part):
            return 0.01 * self.n, self.n

    steps = [Step(0.0, 0.01, [500] * 16, replica=i) for i in range(4)]
    read = spec.reader("decode_roofline.replicas")
    one = stats.Run(record=Record(requests=[], steps=steps[:1], seconds=1.0,
                                  stop=1.0), setup_s=0, config=cfg,
                    peak=peak, trace=Trace(1))
    four = stats.Run(record=Record(requests=[], steps=steps, seconds=1.0,
                                   stop=1.0), setup_s=0, config=cfg,
                     peak=peak, trace=Trace(4), chips=4)
    assert read(four) == pytest.approx(read(one))
    assert 0 < read(four) < 100


def test_fronts_hand_every_routed_request_over_once_under_contention():
    """More replica threads than cores, each taking and publishing while
    the router's thread submits, the interpreter switching threads every
    microsecond: every request reaches its engine once, in order, and a
    routed request shows in the router's view until its engine holds it."""
    from benchmarks.chip.serve_loop import _Front
    n, per = (os.cpu_count() or 4) + 2, 1000
    engines = [FakeEngine() for _ in range(n)]
    fronts = [_Front(e) for e in engines]
    lost = []
    stop = threading.Event()

    def replica(front, eng):
        while not stop.is_set() or front.inbox:
            for req in front.take():
                eng.sched.queue.append(req)
            front.publish(eng)

    def router():
        for i in range(per):
            for front in fronts:
                req = SimpleNamespace(req_id=i)
                front.submit(req)
                if not any(r is req for r in reversed(front.sched.queue)):
                    lost.append(i)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=replica, args=fe, daemon=True)
                   for fe in zip(fronts, engines)]
        for th in threads:
            th.start()
        sender = threading.Thread(target=router, daemon=True)
        sender.start()
        sender.join(timeout=120)
        stop.set()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not sender.is_alive() and not any(th.is_alive() for th in threads)
    assert not lost
    for eng in engines:
        assert [r.req_id for r in eng.sched.queue] == list(range(per))
