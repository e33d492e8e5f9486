"""SL pipeline tests.

The pipelined-vs-monolithic equivalence needs >1 device, so it runs in a
subprocess with forced host devices (the main test process keeps 1 device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs.base import get_config
from repro.core.sl_pipeline import SLTrace, simulate_sl

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_simulate_sl_accounting():
    cfg = get_config("vit-edge")
    tr = simulate_sl(cfg, batch=8, seq=32, n_clients=4, training=True)
    assert tr.hops == 3
    act = 8 * 32 * cfg.d_model * 2          # bf16
    assert tr.smashed_bytes == act * 3
    assert tr.gradient_bytes == tr.smashed_bytes
    inf = simulate_sl(cfg, batch=8, seq=32, n_clients=4, training=False)
    assert inf.gradient_bytes == 0
    assert sum(inf.per_client_flops) < sum(tr.per_client_flops)


class TestValidation:
    """Malformed pipeline inputs raise real ValueErrors (not bare asserts
    that disappear under ``python -O``)."""

    def test_multi_group_stack_rejected(self):
        import jax.numpy as jnp
        from repro.core.sl_pipeline import split_for_stages
        cfg = get_config("vit-edge")
        params = {"backbone": {"layers": {"g0": {"w": jnp.zeros((4, 2))},
                                          "g1": {"w": jnp.zeros((4, 2))}}},
                  "adapters": {"stack": {}}}
        with pytest.raises(ValueError, match="single-group"):
            split_for_stages(params, cfg, 2)

    def test_indivisible_layers_rejected(self):
        import jax.numpy as jnp
        from repro.core.sl_pipeline import split_for_stages
        cfg = get_config("vit-edge")
        params = {"backbone": {"layers": {"g0": {"w": jnp.zeros((3, 2))}}},
                  "adapters": {"stack": {}}}
        with pytest.raises(ValueError, match="not divisible by n_stages"):
            split_for_stages(params, cfg, 2)

    def test_indivisible_microbatches_rejected(self):
        import jax
        import jax.numpy as jnp
        from repro.core.sl_pipeline import pipeline_classify
        cfg = get_config("vit-edge")
        mesh = jax.make_mesh((1,), ("stage",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        toks = jnp.zeros((5, 8), jnp.int32)     # B=5 not divisible by M=4
        with pytest.raises(ValueError, match="n_microbatches"):
            pipeline_classify({}, {}, toks, cfg, mesh, n_microbatches=4)


@pytest.mark.slow
def test_pipeline_matches_monolithic_subprocess():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config
        from repro.core.sl_pipeline import pipeline_classify, split_for_stages
        from repro.models import model as M

        cfg = get_config("vit-edge").reduced().with_(n_layers=4, dtype="float32")
        cfg = cfg.with_(peft=dataclasses.replace(cfg.peft, head_dim_out=5))
        params = M.init(cfg, jax.random.PRNGKey(0))
        mesh = jax.make_mesh((4,), ("stage",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        st = split_for_stages(params, cfg, 4)
        toks = jax.random.randint(jax.random.PRNGKey(1), (16, 24), 0,
                                  cfg.vocab_size)
        got = pipeline_classify(params, st, toks, cfg, mesh, n_microbatches=4)
        want = M.classify(params, {"tokens": toks}, cfg)
        err = float(np.abs(np.asarray(got) - np.asarray(want)).max())
        assert err < 1e-4, err
        # SL fine-tuning: grads flow through the ppermute chain
        from repro.models.layers import cross_entropy
        labels = jnp.zeros((16,), jnp.int32)
        def loss(stages, head):
            p = {"backbone": params["backbone"],
                 "adapters": {**params["adapters"], "head": head}}
            lg = pipeline_classify(p, stages, toks, cfg, mesh,
                                   n_microbatches=4)
            return cross_entropy(lg, labels)
        g_st, g_head = jax.grad(loss, argnums=(0, 1))(
            st, params["adapters"]["head"])
        gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(g_st))
        assert np.isfinite(gn) and gn > 0, gn
        print("PIPELINE_OK", err)
    """)
    r = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                       # a CPU child (forced host devices): never the chip
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=900)
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
