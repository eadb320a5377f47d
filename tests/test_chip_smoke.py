"""chip_smoke.py's code path at a tiny size on the CPU, and its refusals.

The script itself runs only on a TPU; here its phases are driven with a
reduced bf16 config so the served-vs-reference comparison, the fp8 probe
and the replica routing are exercised on every run of the suite.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.launch.serve import build_engine

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"
SRC = str(ROOT / "src")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load()


# 8 layers: deep enough that fp8 weights clear the tolerance the way they
# do at the published 28
TINY = ARCHS["qwen3-0.6b"].reduced(dtype="bfloat16", n_layers=8)
TINY_TRAFFIC = dict(slots=4, max_seq=256, requests=4, prompt_len=(16, 120),
                    new_tokens=(9, 14))


def _run_script(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    out = _run_script(ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_fails_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run_script(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_serve_and_reference_tiny(cs, capsys):
    traffic = cs.Traffic(**TINY_TRAFFIC)
    engine, tap = cs.serve_phase(TINY, traffic, seed=0)
    assert len(engine.completed) == traffic.requests
    assert len(tap.logits) == 2
    for rid in tap.logits:
        rows = tap.rows(rid)
        req = next(r for r in engine.completed if r.req_id == rid)
        # prefill row + one row per decode step; the consumed tokens are
        # the bucketed (left-padded) prompt, then each decode input
        assert rows.shape == (req.max_new_tokens + 1, TINY.vocab)
        bucket = engine.sched.bucket_len(req.prompt_len)
        assert tap.tokens[rid][bucket - req.prompt_len:bucket] == req.prompt
        assert len(tap.tokens[rid]) == bucket + req.max_new_tokens
    cs.reference_phase(engine, tap)       # raises if either side is wrong
    out = capsys.readouterr().out
    assert "fp8-weight probe" in out


def test_reference_catches_a_wrong_logit(cs):
    want = np.random.default_rng(0).normal(size=(3, 512)).astype(np.float32)
    got = want * (1 + 1e-3)
    assert cs.within_tolerance(cs.logit_error(got, want))
    got[1, 7] += 0.5 * want[1].std()       # one logit, half a sigma off
    assert not cs.within_tolerance(cs.logit_error(got, want))


def test_replicas_on_virtual_devices():
    """--replicas 4's phase on four CPU devices (a subprocess, so the
    forced device count stays out of this process)."""
    code = textwrap.dedent(f"""
        import os, sys, importlib.util
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {str(SCRIPT)!r})
        cs = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = cs
        spec.loader.exec_module(cs)
        from repro.configs import ARCHS
        cfg = ARCHS["qwen3-0.6b"].reduced(dtype="bfloat16")
        cs.replica_phase(cfg, cs.Traffic(**{TINY_TRAFFIC!r}),
                         jax.devices()[:4], seed=0)
        print("REPLICAS_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         env={**os.environ, "PYTHONPATH": SRC,
                              "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=600)
    assert "REPLICAS_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]
    assert "bit-identical" in out.stdout
    assert "TFRT_CPU_3" in out.stdout          # four distinct devices


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set; else <checkout>/.jax_cache."""
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from pathlib import Path
        from repro.launch.serve import enable_compile_cache
        enable_compile_cache(Path({str(tmp_path / "checkout")!r}))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
    """)
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "env_cache")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    want = tmp_path / ("env_cache" if env_dir else "checkout/.jax_cache")
    assert any(p.name.startswith("jit__lambda") for p in want.iterdir())
    assert [p.name for p in tmp_path.iterdir()] == [
        "env_cache" if env_dir else "checkout"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_takes_params_as_arguments(cs, dtype):
    """The weights are arguments of the decode program's main, not
    constants folded into it."""
    engine = build_engine(ARCHS["qwen3-0.6b"].reduced(dtype=dtype), slots=2,
                          max_seq=32)
    cs.check_params_are_arguments(engine)


def test_closed_over_params_are_caught(cs):
    """The check fails for a decode step that closes over its weights."""
    engine = build_engine(ARCHS["qwen3-0.6b"].reduced(), slots=2,
                          max_seq=32)
    params, step = engine.params, engine.model.decode_step_inplace
    engine._decode = jax.jit(lambda _, tok, cache: step(params, tok, cache))
    with pytest.raises(AssertionError, match="does not take the weights"):
        cs.check_params_are_arguments(engine)

