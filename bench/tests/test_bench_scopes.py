"""The trace reader with source paths (``xplane.py``) and the reduction by
program and named scope (``scopes.py``) with its two per-layer readers,
on hand-made events and on small traces recorded on a v5e."""
import gzip
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import scopes as SC
from bench import trace_reduce as TRD
from bench import xplane as XP

DATA = Path(__file__).resolve().parent / "data"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
MS = 1_000_000


def _unzip(name, tmp_path):
    f = tmp_path / name.replace(".gz", "")
    f.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    return f


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_reader_matches_profile_data_on_recorded_trace(tmp_path):
    """Every op, program and host event of the recorded serving probe, with
    the times ``ProfileData`` gives (whole ns); and a source path on the
    ops the trace's metadata names."""
    f = _unzip("serve-probe.xplane.pb.gz", tmp_path)
    ours, ref = XP.load_ops(f), TRD.load_events(f)
    assert sorted(ours["host"]) == sorted(ref["host"])
    assert set(ours["devices"]) == set(ref["devices"])
    for d, dev in ref["devices"].items():
        mine = ours["devices"][d]
        assert mine["modules"] == dev["modules"]
        assert [o[:4] for o in mine["ops"]] == dev["ops"]
        paths = [o[4] for o in mine["ops"]]
        assert sum(1 for p in paths if p) > 0.9 * len(paths)
    kernel_paths = {o[4] for o in ours["devices"][0]["ops"]
                    if o[3] == "flash_decode"}
    assert kernel_paths and all("jit(flash_decode_pallas)" in p
                                for p in kernel_paths)


def test_paths_scopes_and_program_names():
    p = ("jit(hfsl_round)/while/body/vmap(one_cluster)/"
         "transpose(jvp(lm_head))/dot_general:")
    assert SC.parts(p)[-2:] == ["lm_head", "dot_general"]
    assert SC.top_scope(p) == "lm_head"
    assert SC.top_scope("jit(decode_segment)/while/body/layers/while/body/"
                        "layer/attn/kv_cache/scatter:") == "layers"
    assert SC.top_scope("jit(decode_segment)/steps/while/body/layers/while/"
                        "body/layer/mlp/dot:") == "layers"
    assert SC.top_scope("jit(decode_segment)/steps/while:") == "steps"
    assert SC.top_scope("jit(decode_segment)/while/body/add:") is None
    assert SC.top_scope("") is None
    assert SC.program_name("jit_decode_segment(1234)") == "decode_segment"
    assert SC.program_name("jit_refill") == "refill"


def _op(name, t0, t1, path, kernel=None):
    return (name, t0 * MS, t1 * MS, kernel, path)


def _events():
    seg = "jit(decode_segment)/steps/while/body/"
    body = seg + "layers/while/body/layer/"
    rnd = "jit(hfsl_round)/while/body/"
    ops = [
        # decode segment 0-11 ms: the step loop, what runs inside it (a
        # copy XLA inserted takes the loop's path), and an op after it
        _op("%while.1 = (s32[]) while(%t)", 0, 10,
            "jit(decode_segment)/steps/while:"),
        _op("%copy.1 = bf16[2,4] copy(%p)", 0, 1, ""),
        _op("%fusion.1 = bf16[4] fusion(%w), kind=kLoop", 1, 2,
            seg + "layers/while/body/squeeze:"),
        _op("%fusion.2 = bf16[4] fusion(%x), kind=kOutput", 2, 4,
            body + "mlp/dot_general:"),
        _op("%scatter.1 = bf16[2,8] scatter(%c)", 4, 5,
            body + "attn/kv_cache/scatter:"),
        _op("%fusion.3 = bf16[2,8] fusion(%k), kind=kLoop", 5, 6,
            body + "attn/jit(flash_decode_pallas)/kv_cache/pad:"),
        _op('%flash_decode.1 = bf16[2] custom-call(%q), '
            'custom_call_target="tpu_custom_call"', 6, 8,
            body + "attn/jit(flash_decode_pallas)/flash_decode/pallas_call:",
            "flash_decode"),
        _op("%fusion.4 = bf16[2] fusion(%o), kind=kLoop", 8, 9,
            body + "attn/jit(flash_decode_pallas)/slice:"),
        _op("%fusion.5 = s32[2] fusion(%l), kind=kLoop", 9, 10,
            seg + "sample/argmax:"),
        _op("%fusion.11 = s32[2] fusion(%r), kind=kLoop", 10, 11, ""),
        # a refill 12-14 ms, half of it outside the window's end at 13 ms
        _op("%fusion.6 = bf16[2] fusion(%x), kind=kOutput", 12, 14,
            "jit(refill)/layers/while/body/layer/mlp/dot_general:"),
    ]
    rops = [
        _op("%fusion.7 = f32[4] fusion(%h), kind=kOutput", 20, 23,
            rnd + "jvp(lm_head)/dot_general:"),
        _op("%fusion.8 = f32[4] fusion(%g), kind=kOutput", 23, 25,
            rnd + "transpose(jvp(lm_head))/dot_general:"),
        _op("%fusion.9 = f32[4] fusion(%g), kind=kOutput", 25, 29,
            rnd + "transpose(jvp(layers))/while/body/layer/mlp/dot:"),
        _op("%fusion.10 = f32[4] fusion(%a), kind=kLoop", 29, 30,
            rnd + "fedavg/reduce_sum:"),
    ]
    host = [("bench.window", 0, 13 * MS), ("bench.window2", 19 * MS,
                                           31 * MS)]
    return {"host": host,
            "devices": {0: {"ops": ops + rops,
                            "modules": [("jit_decode_segment(7)", 0, 11 * MS),
                                        ("jit_refill(8)", 12 * MS, 14 * MS),
                                        ("jit_hfsl_round(9)", 20 * MS,
                                         30 * MS)]}}}


def test_relayout_and_lm_head_shares_on_hand_made_events():
    ev = _events()
    # decode busy 11 ms; relayout: the step loop's copy, the layer scan's
    # squeeze, the cache scatter, the wrapper's pad and slice: 5 ms. The
    # kernel, the product, the sampling and the last op are not.
    assert SC.share(ev, "decode_segment", SC.is_relayout) == \
        pytest.approx(500 / 11)
    assert SC.share(ev, "hfsl_round", SC.is_lm_head) is None  # not in window
    assert SC.share(ev, "hfsl_round", SC.is_lm_head,
                    anchor="bench.window2") == pytest.approx(50.0)
    # a window with no traced programs reads nothing
    ev["host"] = []
    assert SC.share(ev, "decode_segment", SC.is_relayout) is None


def test_by_scope_sums_programs_and_the_unscoped_share():
    r = SC.by_scope(_events(), whole=True)
    seg = r["decode_segment"]
    assert seg["busy_s"] == pytest.approx(0.011)
    assert seg["ops_s"] == pytest.approx(0.011)              # no while
    assert seg["scopes"] == {"layers": pytest.approx(0.008),
                             "sample": pytest.approx(0.001),
                             "steps": pytest.approx(0.001)}  # the copy
    assert seg["unscoped_share"] == pytest.approx(100 / 11)  # the last op
    rnd = r["hfsl_round"]
    assert rnd["scopes"] == {"lm_head": pytest.approx(0.005),
                             "layers": pytest.approx(0.004),
                             "fedavg": pytest.approx(0.001)}
    clipped = SC.by_scope(_events())                        # to 13 ms
    assert clipped["refill"]["busy_s"] == pytest.approx(0.001)
    shapes = SC.layer_scan_shapes(_events(), "decode_segment", whole=True)
    assert shapes == {"bf16[4]": pytest.approx(0.001)}
    chains = SC.scope_chains(_events(), "decode_segment", whole=True)
    layer = "steps/layers/layer/"
    assert chains == {"steps": pytest.approx(0.001),
                      "steps/layers": pytest.approx(0.001),
                      layer + "mlp": pytest.approx(0.002),
                      layer + "attn/kv_cache": pytest.approx(0.002),
                      layer + "attn": pytest.approx(0.003),
                      "steps/sample": pytest.approx(0.001),
                      "": pytest.approx(0.001)}


def test_readers_read_nothing_without_a_trace():
    ctx = SimpleNamespace(trace_path=None)
    assert _reader("relayout_share.decode")(ctx) is None
    assert _reader("lm_head_share.train")(ctx) is None


def test_readers_read_nothing_from_unnamed_programs(tmp_path):
    """The serving probe predates the program names (every program is
    ``jit_impl``): neither reader finds its program, and neither raises."""
    f = _unzip("serve-probe.xplane.pb.gz", tmp_path)
    ctx = SimpleNamespace(trace_path=f)
    assert _reader("relayout_share.decode")(ctx) is None
    assert _reader("lm_head_share.train")(ctx) is None


def test_recorded_scope_probe(tmp_path):
    """A trace recorded on one v5e with the scopes in
    (``record_scope_probe.py``: qwen2-7b widths, 2 layers, a 2-domain bank,
    4 slots, 8 requests through ``serve_trace``, then one 2-step HFSL
    round): every program named, the scopes covering the decode segment
    and the round, the engine's and the round's spans on the host plane,
    both new readers reading, and the rooflines' kernels found under the
    kernels' own names."""
    f = _unzip("scope-probe.xplane.pb.gz", tmp_path)
    ev = XP.load_ops(f)
    progs = {p for p, _, _ in SC.programs(ev)}
    assert {"wave_prefill", "refill", "decode_segment", "hfsl_round"} <= progs
    assert "impl" not in progs
    r = SC.by_scope(ev)
    assert r["decode_segment"]["unscoped_share"] < 1
    assert r["hfsl_round"]["unscoped_share"] < 1
    assert {"layers", "lm_head", "steps"} <= set(r["decode_segment"]["scopes"])
    assert {"layers", "lm_head", "fedavg"} <= set(r["hfsl_round"]["scopes"])
    chains = SC.scope_chains(ev, "decode_segment")
    assert chains["steps/layers/layer/attn/kv_cache"] > 0
    host = {n for n, _, _ in ev["host"]}
    assert {"engine.schedule", "engine.prefill", "engine.refill",
            "engine.segment", "engine.dispatch", "engine.sync",
            "hfsl.round_dispatch"} <= host
    ctx = SimpleNamespace(trace_path=f)
    assert _reader("relayout_share.decode")(ctx) == pytest.approx(5.7963, 1e-3)
    assert _reader("lm_head_share.train")(ctx) == pytest.approx(45.159, 1e-3)
    k = TRD.reduce(f, anchor="bench.window", chips=1)["kernels"]
    # 2 layers: 72 decode steps of flash_decode, two lora_bgmv rows calls
    # (q, v) per step and layer; the round's 2 steps run lora_matmul's
    # backward per layer and target, and its forward three times as often
    assert k["flash_decode"][1] == 2 * 72
    assert k["lora_bgmv_rows"][1] == 2 * k["flash_decode"][1]
    assert k["lora_matmul_bwd"][1] == 2 * 2 * 2
    assert k["lora_matmul_fwd"][1] == 3 * k["lora_matmul_bwd"][1]
    assert k["flash_attention"][1] > 0 and k["lora_bgmv_seq"][1] > 0
