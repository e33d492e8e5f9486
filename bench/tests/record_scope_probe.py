"""Record the small chip trace ``data/scope-probe.xplane.pb.gz`` that the
scope tests read, and print its scope breakdown.

    python3 bench/tests/record_scope_probe.py <out.xplane.pb.gz>

On one TPU: qwen2-7b at its published widths with 2 layers, a 2-domain
adapter bank, 4 slots, 8 mixed requests through ``serve_trace`` (initial
wave, refills, decode segments), then one HFSL round (2 clusters x 1 x 256
tokens, 2 steps, FedAvg at the end, remat). Every program is compiled by
an untraced pass first; the traced pass runs with telemetry on, inside a
``bench.window`` annotation, so the engine's and the round's spans land
on the host plane beside the device ops.
"""
from __future__ import annotations

import gzip
import shutil
import statistics
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def workload(cfg, params, bank, adapters0):
    """One serving drain and one HFSL round; returns when both are done."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import hfsl
    from repro.launch.engine import DecodeEngine
    from repro.models import model as M
    from repro.optim.optimizers import adamw

    rng = np.random.default_rng(0)
    engine = DecodeEngine(cfg, slots=4, bank=bank)
    lens = [64, 200, 96, 256, 80, 128, 160, 72]
    gens = [8, 24, 12, 16, 20, 8, 12, 16]
    arrivals = [(0.0 if i < 4 else 0.05 * i,
                 rng.integers(0, cfg.vocab_size, n, dtype=np.int32), g,
                 {"domain": f"d{i % 2}"})
                for i, (n, g) in enumerate(zip(lens, gens))]
    comps, _ = engine.serve_trace(params, arrivals)
    assert len(comps) == len(arrivals)

    opt = adamw(1e-3)
    state = hfsl.init_hfsl_state(
        None, cfg, 2, opt,
        lambda c, k: {"backbone": params["backbone"], "adapters": adapters0})
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 1, 256),
                                   dtype=np.int32))
    round_fn = hfsl.make_hfsl_round(cfg, opt, M.lm_loss, steps=2,
                                    sync_every=2, remat=True)
    state, metrics = round_fn(state, {"tokens": tok, "labels": tok}, 0)
    jax.block_until_ready((state, metrics))


def main(argv) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    from bench import scopes, xplane
    from repro.configs.base import get_config
    from repro.core import telemetry
    from repro.core.adapter_bank import AdapterBank
    from repro.models import model as M

    out = Path(argv[0])
    cfg = get_config("qwen2-7b").with_depth(2)
    params = M.init(cfg, jax.random.PRNGKey(0))
    adapters = [M.init(cfg, jax.random.PRNGKey(1 + d))["adapters"]
                for d in range(2)]
    bank = AdapterBank.create({f"d{d}": a for d, a in enumerate(adapters)})
    params = bank.serving_params(params["backbone"])
    workload(cfg, params, bank, adapters[0])          # compile everything

    tmp = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # no Python call events
    try:
        tel = telemetry.enable()
        with jax.profiler.trace(str(tmp), profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench.window"):
                t_anchor = time.perf_counter()
                workload(cfg, params, bank, adapters[0])
        telemetry.disable()
        (found,) = tmp.glob("plugins/profile/*/*.xplane.pb")
        out.parent.mkdir(parents=True, exist_ok=True)
        with open(found, "rb") as f, gzip.open(out, "wb") as g:
            shutil.copyfileobj(f, g)
        ev = xplane.load_ops(found)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {out.stat().st_size} bytes gzipped, "
          f"{sum(len(d['ops']) for d in ev['devices'].values())} device ops")
    names = {n for n, _, _ in ev["host"]}
    print("host spans:", sorted(n for n in names
                                if n.startswith(("engine.", "hfsl."))))
    for prog, r in sorted(scopes.by_scope(ev).items()):
        print(f"{prog}: busy {r['busy_s']:.6f} s, unscoped "
              f"{r['unscoped_share']:.2f}%, scopes "
              + ", ".join(f"{k} {v:.6f}" for k, v in
                          sorted(r["scopes"].items())))
    # the engine's spans twice: natively, and from the telemetry record
    # shifted onto the profiler's clock by the window's start, as the
    # benchmark's idle-gap breakdown places them
    a0 = next(a for n, a, _ in ev["host"] if n == "bench.window")
    native = defaultdict(list)
    for n, a, _ in ev["host"]:
        native[n].append(a)
    off = []
    for name in ("engine.segment", "engine.refill", "engine.sync"):
        shifted = sorted(a0 + (tel._epoch + sp.t0 - t_anchor) * 1e9
                         for sp in tel.spans if sp.name == name)
        off += [(x - y) / 1e6 for x, y in zip(sorted(native[name]), shifted)]
    print(f"native minus anchor-shifted start, ms: median "
          f"{statistics.median(off):.4f}, min {min(off):.4f}, "
          f"max {max(off):.4f} ({len(off)} spans)")
    print("relayout_share.decode",
          scopes.share(ev, "decode_segment", scopes.is_relayout))
    print("lm_head_share.train",
          scopes.share(ev, "hfsl_round", scopes.is_lm_head))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
