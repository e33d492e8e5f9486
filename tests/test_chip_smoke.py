"""chip_smoke.py off the chip: it refuses the CPU, and its phases run.

The script's own run needs a TPU. Here its phase functions run in-process
at a reduced width with the Pallas kernels in interpret mode, so a change
that breaks the smoke's drive of the engine, the HFSL round or the
integrated runtime fails tier-1 before it costs a chip run.
"""
import contextlib
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro.configs.base import get_config
from repro.kernels import ops
from repro.models import model as M

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def test_refuses_without_a_tpu():
    r = subprocess.run([sys.executable, SCRIPT], cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


@pytest.fixture(scope="module")
def smoke(monkeypatch_module):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    # reduced traffic: short prompts, 8 tokens each (same 16 x 8 slots)
    traffic = cs.traffic
    monkeypatch_module.setattr(cs, "GEN", 8)
    monkeypatch_module.setattr(
        cs, "traffic", lambda cfg, seed, n=cs.N_REQ, lo=5, hi=40:
        traffic(cfg, seed, n, lo, hi))
    return cs


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


@pytest.fixture(scope="module")
def small():
    cfg = get_config("qwen2-7b").reduced().with_(n_kv_heads=2)   # GQA
    key = jax.random.PRNGKey(0)
    return cfg, M.init(cfg, key)["backbone"], key


@pytest.fixture
def fused(smoke, tmp_path, monkeypatch):
    """Per phase, the fused programs the kernel check reads (on the chip
    each must hold a Pallas kernel, and a phase must compile at least one).
    Lowered IR is dumped under tmp_path; interpret mode has no Mosaic, so
    the kernel check itself is off."""
    monkeypatch.setattr(smoke, "IR_DIR", tmp_path)
    out: dict = {}
    real_phase = smoke.phase

    @contextlib.contextmanager
    def phase(name, clock, *, kernels=True):
        before = smoke._ir_files()
        with real_phase(name, clock, kernels=False):
            yield
        out[name] = [f.name for f in smoke._ir_files() - before
                     if any(tag in f.name for tag in smoke.FUSED)]

    monkeypatch.setattr(smoke, "phase", phase)
    prev = jax.config.values["jax_dump_ir_to"]
    jax.config.update("jax_dump_ir_to", str(tmp_path))
    yield out
    jax.config.update("jax_dump_ir_to", prev)


def test_serve_phases_interpret(smoke, small, fused):
    """The phases run, and the kernel check's name filter picks exactly the
    engine's prefill, refill and segment programs of each serve phase (a
    helper jit named like them would be checked for a kernel it lacks)."""
    cfg, backbone, key = small
    adapters = smoke.domain_adapters(cfg, jax.random.fold_in(key, 1),
                                     len(smoke.DOMAINS))
    with ops.backend("interpret"):
        smoke.serve_phases(cfg, backbone, adapters, 0, smoke.CompileClock())
    assert {k: len(v) for k, v in fused.items()} == {
        "serve dense": 3, "serve paged": 3, "serve multi-tenant": 3}


def _faulty_decode(fault):
    real = ops.flash_decode

    def decode(q, k, v, **kw):
        if fault == "no prefix-KV":
            kw.update(prefix_k=None, prefix_v=None)
        else:                                  # newest token masked out
            kw["q_pos"] = kw["q_pos"] - 1
        return real(q, k, v, **kw)
    return decode


@pytest.mark.parametrize("fault", ["no prefix-KV", "mask off by one"])
def test_served_token_check_catches_planted_faults(smoke, small, fault,
                                                   monkeypatch):
    """A decode fault the chip could hide (the dense kernel called without
    the prefix bank, or a mask that hides the newest token) fails the
    served-token check's limits."""
    cfg, backbone, key = small
    params = {"backbone": backbone, "adapters": smoke.domain_adapters(
        cfg, jax.random.fold_in(key, 1), 1)[0]}
    # a name no other test served under, so the engine's programs (cached
    # per config) are traced anew with the fault in them
    cfg = cfg.with_(name=f"{cfg.name} ({fault})")
    prompts = smoke.traffic(cfg, 0)
    with monkeypatch.context() as mp, ops.backend("interpret"):
        mp.setattr(ops, "flash_decode", _faulty_decode(fault))
        toks, _ = smoke.drain(smoke.DecodeEngine(cfg, slots=smoke.SLOTS),
                              params, prompts, smoke.GEN)
    with pytest.raises(AssertionError, match="beyond tolerance"):
        smoke.check_against_xla(fault, smoke._reference_fn(cfg, smoke.GEN),
                                params, prompts, toks)


def test_train_phase_loss_falls(smoke, small, fused):
    cfg, backbone, key = small
    adapters = smoke.domain_adapters(cfg, jax.random.fold_in(key, 1), 1)
    with ops.backend("interpret"):
        losses = smoke.train_phase(cfg, backbone, adapters[0], 0,
                                   smoke.CompileClock(), seq=32)
    assert losses[-1] < losses[0]
    assert [len(v) for v in fused.values()] == [1]


def test_cycle_phase(smoke, small, fused):
    cfg, _, _ = small
    smoke.cycle_phase(cfg, 0, smoke.CompileClock())
    assert [len(v) > 0 for v in fused.values()] == [True]


_MESH_SCRIPT = """
import os, sys, contextlib, importlib.util
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "src")
import jax
from repro.configs.base import get_config
from repro.kernels import ops
spec = importlib.util.spec_from_file_location("cs", "chip_smoke.py")
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
cs.GEN, cs.PARITY_LAYERS, cs.IR_DIR = 8, 1, cs.Path(sys.argv[1])
traffic = cs.traffic
cs.traffic = lambda cfg, seed, n=cs.N_REQ, lo=5, hi=40: traffic(
    cfg, seed, n, 5, 40)
cfg = get_config("qwen2-7b").reduced()
cs.get_config = lambda name: cfg
real_phase = cs.phase

@contextlib.contextmanager
def phase(name, clock, kernels=True):
    before = cs._ir_files()
    with real_phase(name, clock, kernels=False):
        yield
    print("FUSED", len([f for f in cs._ir_files() - before
                        if any(t in f.name for t in cs.FUSED)]))

cs.phase = phase
jax.config.update("jax_dump_ir_to", sys.argv[1])

class Args:
    seed = 0

with ops.backend("interpret"):
    cs.mesh_phases(Args, cs.CompileClock())
print("MESH_OK")
"""


def test_mesh_phases_on_host_devices(tmp_path):
    """--chips 4's phases at reduced width on four host devices: the mesh
    drain equals device 0's, the layers' bytes spread over four devices,
    the mesh drain passes the served-token check, the round's loss falls,
    and every phase compiles a fused program for the kernel check."""
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT, str(tmp_path)],
                       cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=600)
    assert "MESH_OK" in r.stdout, r.stdout[-3000:] + r.stderr[-3000:]
    counts = [int(l.split()[1]) for l in r.stdout.splitlines()
              if l.startswith("FUSED")]
    assert len(counts) == 4 and min(counts) > 0, r.stdout[-3000:]
