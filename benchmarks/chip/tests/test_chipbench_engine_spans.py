"""The engine's own spans in a traced window of the open loop, at a CPU
size: every span of ``engine.SPANS``, nested as the engine nests them,
one per decode step and one per prefill."""

import _paths  # noqa: F401
from _paths import DATA

import jax
import pytest

from benchmarks.chip import phases, run, spec, traffic, xtrace
from benchmarks.chip.serve_loop import OpenLoop
from repro.serving.engine import SPANS

SECONDS = 1.5
SEED = 2**32 + 7
PARENT = {"engine.prefill": "engine.admit",
          **{n: n.rsplit(".", 1)[0] for n in SPANS if n.count(".") == 2}}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = spec.load_json(DATA / "tiny.json")
    mix = spec.load_json(DATA / "tiny-chat.json")
    eng = run.build_engine(cfg, SEED, jax.devices()[0])
    loop = OpenLoop(eng, spans=True)
    loop.warm_up(sorted({eng.sched.bucket_len(n)
                         for n in traffic.prompt_lengths(mix, SECONDS)}))
    requests = traffic.generate(mix, SEED, SECONDS, cfg["vocab_size"])
    before = dict(eng.stats)
    keep = tmp_path_factory.mktemp("trace") / "window.xplane.pb.gz"
    with xtrace.traced(str(keep)):
        rec = loop.run(requests, SECONDS, mix["drain"])
    pd = xtrace.load(str(keep))
    events = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("engine."):
                        events.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.end_ns, dict(ev.stats)))
    return {"events": events, "rec": rec, "before": before,
            "stats": dict(eng.stats), "pd": pd}


def test_every_span_appears_nested_as_listed(served):
    ev = served["events"]
    assert set(ev) == set(SPANS)
    for child, parent in PARENT.items():
        outer = ev[parent]
        for s, e, _ in ev[child]:
            assert any(ps <= s and e <= pe for ps, pe, _ in outer), child


def test_one_decode_span_per_step(served):
    first, n = served["before"]["steps"], served["stats"]["steps"]
    decode = sorted(served["events"]["engine.decode"], key=lambda x: x[0])
    assert len(decode) == n - first == len(served["rec"].steps) > 0
    assert [a["step"] for _, _, a in decode] == list(range(first, n))
    assert [a["slots"] for _, _, a in decode] == \
        [len(s.contexts) for s in served["rec"].steps]


def test_one_prefill_span_per_prefill_with_its_request(served):
    got = served["events"]["engine.prefill"]
    stats, before = served["stats"], served["before"]
    assert len(got) == stats["prefills"] - before["prefills"] > 0
    admitted = [r for r in served["rec"].requests if r.prefill_start >= 0]
    assert sorted(a["req_id"] for _, _, a in got) == \
        sorted(r.rid for r in admitted)
    assert sum(a["bucket"] for _, _, a in got) == \
        stats["prefill_positions"] - before["prefill_positions"]
    assert sum(len(r.prompt) for r in admitted) == \
        stats["prefill_tokens"] - before["prefill_tokens"]


def test_the_reduction_counts_the_same_spans(served):
    ph = phases.reduce(served["pd"])
    assert ph.spans["engine.decode"][1] == len(served["rec"].steps)
    assert set(ph.longest) == set(ph.spans) <= set(SPANS)
