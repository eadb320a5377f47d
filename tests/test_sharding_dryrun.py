"""Sharding-rule unit tests + a small-mesh dry-run smoke executed in a
subprocess (so XLA_FLAGS device-count forcing never leaks into this test
process, which must keep seeing 1 CPU device)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_main_process_sees_one_device():
    assert len(jax.devices()) == 1


class TestFit:
    def test_drops_nondividing_axes(self):
        from jax.sharding import PartitionSpec as P
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax
            from jax.sharding import AxisType, PartitionSpec as P
            from repro.parallel.sharding import fit
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            # batch=1 cannot shard over data
            assert fit(mesh, (1, 64), (("data",), "model")) == P(None, "model")
            # dim divisible by both axes keeps both
            assert fit(mesh, (8, 64), (("data", "model"), None)) == \\
                P(("data", "model"), None)
            # 6 divisible by 2 but not 4
            assert fit(mesh, (6, 12), ("data", "model")) == P("data", "model")
            assert fit(mesh, (6, 2), ("data", "model")) == P("data", None)
            print("FIT_OK")
        """)
        out = subprocess.run(
            [sys.executable, "-c", code],
            env={**os.environ, "PYTHONPATH": SRC},
            capture_output=True, text=True, timeout=300)
        assert "FIT_OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.slow
def test_dryrun_small_mesh_all_families():
    """Lower+compile one cell per family on an 8-device mesh (subprocess)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.configs import ARCHS
        from repro.models import build_model
        from repro.parallel.sharding import MeshRules
        from repro.models.model import ShapeSpec
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        rules = MeshRules(mesh, fsdp=True)
        for arch in ["llama3.2-3b", "qwen2-moe-a2.7b", "zamba2-7b",
                     "xlstm-125m", "seamless-m4t-large-v2",
                     "llava-next-mistral-7b"]:
            cfg = ARCHS[arch].reduced(d_model=256, n_heads=8, n_kv_heads=4,
                                      head_dim=32, vocab=1024)
            m = build_model(cfg)
            ps = jax.eval_shape(m.init, jax.random.key(0))
            psh = rules.shardings_of(rules.param_specs(ps))
            shape = ShapeSpec("t", "train", 64, 8)
            specs = m.input_specs(shape)
            bsh = rules.shardings_of(rules.batch_specs(specs["batch"]))
            def loss(p, b):
                return m.loss(p, b, shard=rules)
            with mesh:
                c = jax.jit(loss, in_shardings=(psh, bsh)).lower(
                    ps, specs["batch"]).compile()
            assert c.cost_analysis()["flops"] > 0
            # decode too
            dshape = ShapeSpec("d", "decode", 64, 8)
            dspecs = m.input_specs(dshape)
            csh = rules.shardings_of(rules.cache_specs(dspecs["cache"]))
            tsh = rules.shardings_of(rules.batch_specs(
                {"tokens": dspecs["tokens"]}))["tokens"]
            def step(p, t, c_):
                return m.decode_step(p, t, c_, shard=rules)
            with mesh:
                jax.jit(step, in_shardings=(psh, tsh, csh)).lower(
                    ps, dspecs["tokens"], dspecs["cache"]).compile()
            print("OK", arch)
        print("DRYRUN_SMALL_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "PYTHONPATH": SRC},
        capture_output=True, text=True, timeout=1200)
    assert "DRYRUN_SMALL_OK" in out.stdout, \
        out.stdout[-1500:] + out.stderr[-3000:]


@pytest.mark.slow
def test_pipeline_parallel_over_pod_axis():
    """GPipe over a 2-stage 'pod' axis matches the sequential reference."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.parallel.pipeline import pipeline_forward, bubble_fraction
        mesh = jax.make_mesh((2, 4), ("pod", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        n_stages, n_micro, mb, d = 2, 4, 2, 16
        key = jax.random.key(0)
        w = jax.random.normal(key, (n_stages, d, d)) * 0.1
        x = jax.random.normal(jax.random.key(1), (n_micro, mb, d))
        def stage_fn(params, x):
            return jnp.tanh(x @ params["w"])
        outs = pipeline_forward(stage_fn, {"w": w}, x, mesh=mesh, axis="pod")
        # sequential reference
        want = x
        for s in range(n_stages):
            want = jnp.tanh(want @ w[s])
        np.testing.assert_allclose(np.asarray(outs), np.asarray(want),
                                   atol=1e-5)
        assert abs(bubble_fraction(2, 4) - 0.2) < 1e-9
        print("PIPELINE_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "PYTHONPATH": SRC},
        capture_output=True, text=True, timeout=600)
    assert "PIPELINE_OK" in out.stdout, \
        out.stdout[-1500:] + out.stderr[-3000:]


@pytest.mark.slow
def test_elastic_remesh_preserves_values():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.parallel.sharding import MeshRules
        from repro.training.elastic import plan_remesh, remesh
        old = jax.make_mesh((4, 2), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)
        rules = MeshRules(old)
        params = {"wq": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        sh = rules.shardings_of(rules.param_specs(params))
        params = jax.tree.map(jax.device_put, params, sh)
        plan = plan_remesh(old, failed_nodes=2)
        assert plan.new_shape["data"] == 2 and plan.micro_scale == 2
        new_mesh = jax.make_mesh((2, 2), ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
        new_params, _ = remesh(params, rules, new_mesh)
        np.testing.assert_array_equal(np.asarray(new_params["wq"]),
                                      np.arange(64).reshape(8, 8))
        print("ELASTIC_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "PYTHONPATH": SRC},
        capture_output=True, text=True, timeout=600)
    assert "ELASTIC_OK" in out.stdout, \
        out.stdout[-1500:] + out.stderr[-3000:]
