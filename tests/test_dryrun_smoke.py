"""Dry-run pipeline smoke test (subprocess: needs forced host devices).

Runs the REAL dryrun code path (build_lowered -> compile -> roofline walk)
on a small 4x4 mesh with reduced configs — proving the lower/compile/
roofline machinery works per family without the 512-way cost.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

CASES = [
    ("qwen2-7b", "train_4k"),
    ("granite-moe-1b-a400m", "decode_32k"),
    ("falcon-mamba-7b", "prefill_32k"),
    ("whisper-small", "train_4k"),
]


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape", CASES)
def test_dryrun_reduced_subprocess(arch, shape):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import sys; sys.path.insert(0, "src")
        from repro.launch import dryrun
        from repro.launch import mesh as mesh_lib
        from repro.launch.roofline import Roofline, analyze_hlo_text, model_flops_for
        import jax

        mesh = mesh_lib.make_test_mesh(4, 4)
        lowered, meta = dryrun.build_lowered(
            "{arch}", "{shape}", reduced=True, mesh=mesh)
        compiled = lowered.compile()
        costs = analyze_hlo_text(compiled.as_text())
        assert costs.flops > 0, "no FLOPs found in HLO"
        assert costs.bytes_accessed > 0
        roof = Roofline.from_costs(
            costs, arch=meta["arch"], shape=meta["shape"], mesh=meta["mesh"],
            chips=16, model_flops=model_flops_for(meta["cfg"], meta["shape_obj"]))
        assert roof.bottleneck in ("compute", "memory", "collective")
        print("DRYRUN_OK", roof.bottleneck, f"{{costs.flops:.2e}}")
    """)
    r = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                       # a CPU child (forced host devices): never the chip
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=900)
    assert "DRYRUN_OK" in r.stdout, (r.stdout[-2000:] + r.stderr[-3000:])


def test_skip_table():
    from repro.launch.dryrun import SKIPS
    assert ("whisper-small", "long_500k") in SKIPS


def test_variant_for_long_context():
    from repro.configs.base import get_config
    from repro.launch.dryrun import variant_for
    cfg = variant_for(get_config("qwen2-7b"), "long_500k")
    assert cfg.attn_variant == "sliding"
    cfg = variant_for(get_config("falcon-mamba-7b"), "long_500k")
    assert cfg.family == "ssm"            # untouched: natively sub-quadratic
