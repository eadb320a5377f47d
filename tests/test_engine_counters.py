"""The engine's prefill counters: prompt tokens against the bucket
positions the prefill programs computed."""

import jax
import pytest

from repro.configs import ARCHS
from repro.models import build_model
from repro.serving import EngineConfig, InferenceEngine, ServeRequest


@pytest.fixture(scope="module")
def engine_parts():
    m = build_model(ARCHS["llama3.2-3b"].reduced())
    return m, m.init(jax.random.key(0))


def test_prefill_counters_for_prompts_of_known_lengths(engine_parts):
    m, params = engine_parts
    eng = InferenceEngine(m, params, EngineConfig(
        max_slots=4, max_seq=192, n_pages=64, page_size=16,
        telemetry=False))
    lengths = [10, 64, 65]                  # buckets 64, 64, 128
    reqs = [ServeRequest(req_id=i, arrival=0.0, prompt=[3] * n,
                         max_new_tokens=2) for i, n in enumerate(lengths)]
    rep = eng.run(reqs, max_steps=50)
    assert rep["completed"] == 3
    assert eng.stats["prefills"] == 3
    assert eng.stats["prefill_tokens"] == 139
    assert eng.stats["prefill_positions"] == 256
