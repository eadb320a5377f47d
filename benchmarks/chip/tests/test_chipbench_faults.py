"""A whole run at a CPU size, past the harness's look for a chip: the
sound served path is correct; the same run with the timed path broken
underneath, or the fp8-weight control, is not."""

import _paths
from _paths import DATA

import time

import jax
import pytest

from benchmarks.chip import run, spec

BENCH = {"end_to_end": [{"name": "setup_s", "unit": "s"},
                        {"name": "ttft_p50_ms", "unit": "ms"},
                        {"name": "itl_p95_ms", "unit": "ms"}],
         "per_layer": [{"name": "queue_wait_ms_p90.chat", "unit": "ms"},
                       {"name": "ttft_p90_ms.chat", "unit": "ms"}]}
CELL = {"name": "tiny.chat", "chips": 1}
SECONDS = 2.0


def _run(seed, control=False, trace=False):
    return run.run_cell(CELL, spec.load_json(DATA / "tiny.json"),
                        spec.load_json(DATA / "tiny-chat.json"), seed=seed,
                        seconds=SECONDS, trace=trace,
                        devices=jax.devices(), bench=BENCH,
                        t_start=time.perf_counter(), control=control,
                        log=lambda *a: None)


def _broken_decode(monkeypatch, fault):
    from repro.serving import engine as eng_mod
    real = eng_mod.decode_fn

    def decode_fn(model):
        step = real(model)

        @jax.jit
        def broken(params, toks, cache):
            logits, new = step(params, toks, cache)
            if fault == "state_unchanged":
                return logits, cache
            # the token produced in slot 0 is altered: its logits favour 7
            return logits.at[0, ..., 7].add(1e4), new
        return broken
    monkeypatch.setattr(eng_mod, "decode_fn", decode_fn)


def test_sound_run_is_correct_and_control_is_not():
    """The control, put in the served path's place, is judged by the
    run's own rule and limit and comes out not correct."""
    res = _run(2**33 + 1, control=True)
    assert res["served"]["correct"], res
    assert not res["correct"]
    assert list(res)[-1] == "checks"
    assert res["served"]["widest_gap"] <= \
        res["checks"]["widest_gap"]["limit"] < \
        res["checks"]["widest_gap"]["value"]
    assert set(res["metrics"]) == {"setup_s", "ttft_p50_ms", "itl_p95_ms"}
    assert res["failed"] == 0 and res["attempted"] == 12


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    _broken_decode(monkeypatch, fault)
    res = _run(2**33 + 1)
    assert not res["correct"]
    assert res["checks"]["widest_gap"]["value"] > \
        res["checks"]["widest_gap"]["limit"]


def test_traced_run_reports_the_per_layer_metrics_and_a_breakdown():
    res = _run(11, trace=True)
    assert res["correct"]
    assert set(res["metrics"]) == {"queue_wait_ms_p90.chat",
                                   "ttft_p90_ms.chat"}
    assert res["device"]["window_s"] > SECONDS
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-1] == "checks"


def test_the_programs_state_is_freed_before_the_reference(monkeypatch):
    import gc
    seen = []
    compare = run.compare

    def spy(*args, **kw):
        gc.collect()
        seen.append(len(jax.live_arrays()))
        return compare(*args, **kw)
    monkeypatch.setattr(run, "compare", spy)
    assert _run(3)["correct"]
    assert seen == [0]
