"""The metric arithmetic: tails over all requests, rates over the whole
window, and the readers found by name."""

import _paths  # noqa: F401

import numpy as np
import pytest

from benchmarks.chip import spec, stats, work
from benchmarks.chip.serve_loop import Record, Step
from benchmarks.chip.traffic import Request

DENSE = spec.arch("dense")


def _req(rid, due, times):
    return Request(rid=rid, due=due, prompt=[1] * 10, max_new_tokens=4,
                   tokens=[5] * len(times), token_times=list(times),
                   prefill_start=due + 0.01 if times else -1.0)


def _run(record, **kw):
    base = dict(setup_s=12.5, config=spec.config("qwen3-0.6b"),
                peak=spec.peaks("TPU v5 lite"))
    base.update(kw)
    return stats.Run(record=record, **base)


@pytest.mark.parametrize("q", [0.0, 0.25, 0.5, 0.9, 0.95, 1.0])
def test_quantile_is_numpys_linear(q):
    xs = list(np.random.default_rng(1).exponential(size=37))
    assert stats.quantile(xs, q) == pytest.approx(np.quantile(xs, q))


def test_ttft_counts_every_due_request_and_a_missing_one_waits():
    reqs = [_req(i, i * 0.1, [i * 0.1 + 0.05 * (i + 1)]) for i in range(9)]
    reqs.append(_req(9, 0.9, []))                 # never answered
    reqs.append(_req(10, 5.0, [5.1]))             # due after the window
    rec = Record(requests=reqs, seconds=2.0, stop=3.0)
    t = stats.ttfts(rec)
    assert len(t) == 10 and t[-1] == pytest.approx(2.1)
    got = spec.reader("ttft_p90_ms")(_run(rec))
    assert got == pytest.approx(np.quantile(t, 0.9) * 1e3)


def test_median_ttft_is_over_the_same_requests_as_the_tail():
    reqs = [_req(i, i * 0.1, [i * 0.1 + 0.05 * (i + 1)]) for i in range(9)]
    reqs.append(_req(9, 0.9, []))                 # never answered
    rec = Record(requests=reqs, seconds=2.0, stop=3.0)
    got = spec.reader("ttft_p50_ms")(_run(rec))
    assert got == pytest.approx(np.median(stats.ttfts(rec)) * 1e3)
    assert spec.reader("ttft_p90_ms.chat")(_run(rec)) > got


def test_itl_is_over_every_gap_of_every_request():
    reqs = [_req(0, 0.0, [0.1, 0.2, 0.4]), _req(1, 0.0, [0.3, 1.3])]
    rec = Record(requests=reqs, seconds=2.0, stop=2.0)
    assert sorted(stats.token_gaps(rec)) == pytest.approx([0.1, 0.2, 1.0])
    got = spec.reader("itl_p95_ms")(_run(rec))
    assert got == pytest.approx(np.quantile([0.1, 0.2, 1.0], 0.95) * 1e3)
    assert spec.reader("itl_p50_ms")(_run(rec)) == pytest.approx(200.0)


def test_tokens_per_second_is_over_the_whole_window():
    reqs = [_req(0, 0.0, [0.5, 1.0, 1.5, 2.5]), _req(1, 0.0, [3.9, 4.1])]
    rec = Record(requests=reqs, seconds=4.0, stop=4.2)
    assert stats.tokens_in_window(rec) / rec.seconds == 5 / 4.0


def test_step_readers_and_silence_without_a_trace():
    steps = [Step(0.0, 0.1, [10, 20]), Step(0.1, 0.2, [30]),
             Step(0.2, 0.3, [40, 50, 60])]
    rec = Record(requests=[], steps=steps, seconds=1.0, stop=1.0,
                 flush_s=0.03)
    run = _run(rec)
    assert spec.reader("batch_occupancy.chat")(run) == 2.0
    assert spec.reader("monitor_ms_per_step.chat")(run) == \
        pytest.approx(10.0)
    for name in ("decode_step_ms.chat", "prefill_ms.chat",
                 "decode_roofline.chat", "device_idle_share.chat",
                 "loop_ms_per_step.chat"):
        assert spec.reader(name)(run) is None


def test_roofline_counts_live_work_and_stays_under_the_dense_program():
    cfg = spec.config("qwen3-0.6b")
    peak = spec.peaks("TPU v5 lite")
    live = DENSE.decode_step(cfg, Step(0.0, 0.0, [100, 2000]))
    # the program reads the whole 16 x 2048 cache whatever the lengths
    dense = DENSE.decode_step(cfg, Step(0.0, 0.0, [2048] * 16))
    assert live[1] < dense[1]
    assert work.least_time(*live, peak) == pytest.approx(
        live[1] / peak["hbm_bytes_per_s"])


@pytest.mark.parametrize("name", ["qwen3-0.6b", "h2o-danube-3-4b"])
def test_parameter_arithmetic_matches_the_programs_count(name):
    """2 x N uses the matmul parameters: every layer and the head."""
    cfg = spec.config(name)
    embed = 0 if cfg["tie_word_embeddings"] else \
        cfg["vocab_size"] * cfg["hidden_size"]
    assert DENSE.matmul_params(cfg) + embed == \
        DENSE.program_config(cfg).param_count()


def test_mfu_counts_prompt_and_generated_tokens_in_the_window():
    cfg = spec.config("qwen3-0.6b")
    reqs = [_req(0, 0.0, [0.5, 1.0, 3.0])]
    rec = Record(requests=reqs, seconds=2.0, stop=3.0)
    got = spec.reader("mfu.chat")(_run(rec))
    want = DENSE.request_flops(cfg, 10, 2) / (2.0 * 197e12) * 100
    assert got == pytest.approx(want)
