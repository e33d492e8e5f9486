"""The four-chip cell's readers on synthetic runs and traces: which ops
count as collectives, sums over the chips, and the kernel roofline of one
chip's share of the heads."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import trace_reduce
from bench.run import load_reader

ROOT = Path(__file__).resolve().parents[2]
CONF = json.loads((ROOT / "bench" / "configs" / "qwen2-7b-tp4.json")
                  .read_text())
PEAK = json.loads((ROOT / "bench" / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]


def _op(name, opcode, kind=None):
    tail = f", kind={kind}" if kind else ""
    return f"%{name} = bf16[128,1,3584]{{2,1,0}} {opcode}(bf16[] %p){tail}"


def _reader_module(name):
    import importlib.util
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"test_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name,want", [
    (_op("all-reduce.12", "all-reduce"), True),
    (_op("all-reduce-start.3", "all-reduce-start"), True),
    (_op("all-reduce-done.3", "all-reduce-done"), True),
    (_op("all-gather.6", "all-gather"), True),
    (_op("all-gather-start", "all-gather-start"), True),
    (_op("reduce-scatter.1", "reduce-scatter"), True),
    (_op("collective-permute-done.2", "collective-permute-done"), True),
    (_op("all-to-all.4", "all-to-all"), True),
    (_op("all-reduce-fusion.2", "fusion", "kOutput"), True),
    (_op("reduce-scatter-fusion", "fusion", "kLoop"), True),
    (_op("fusion.7", "fusion", "kOutput"), False),
    (_op("convolution_reduce_fusion", "fusion", "kOutput"), False),
    (_op("flash_decode_pallas.3", "custom-call"), False),
    (_op("copy-done.1", "copy-done"), False),
    (_op("reduce.5", "reduce"), False),
])
def test_collectives_are_classified(name, want):
    is_coll = _reader_module("collective_share.tp4").is_collective
    assert is_coll(name) is want


def _events():
    """A 10 s window on four chips: chip d is busy 1 + d s with compute
    and 0.5 s with an all-reduce; chip 0 also has one async all-gather
    pair (0.25 s) and ops outside the window."""
    s = 10**9
    host = [("bench.window", 10 * s, 20 * s)]
    devices = {}
    for d in range(4):
        ops = [(_op("fusion.1", "fusion", "kOutput"), 10 * s,
                (11 + d) * s, None),
               (_op("all-reduce.2", "all-reduce"), 15 * s, 15.5 * s, None)]
        if d == 0:
            ops += [(_op("all-gather-start.1", "all-gather-start"),
                     16 * s, 16.1 * s, None),
                    (_op("all-gather-done.1", "all-gather-done"),
                     16.1 * s, 16.25 * s, None),
                    (_op("all-reduce.9", "all-reduce"), 1 * s, 9 * s, None),
                    (_op("fusion.3", "fusion", "kLoop"), 19.5 * s, 21 * s,
                     None)]
        devices[d] = {"ops": ops, "modules": []}
    return {"host": host, "devices": devices}


def test_collective_share_sums_over_the_chips(monkeypatch):
    mod = _reader_module("collective_share.tp4")
    coll, busy = mod.shares(_events(), "bench.window", 4)
    assert coll == pytest.approx(4 * 0.5 + 0.25)
    assert busy == pytest.approx((1 + 2 + 3 + 4) + 4 * 0.5 + 0.25 + 0.5)
    monkeypatch.setattr(trace_reduce, "load_events", lambda p: _events())
    ctx = SimpleNamespace(trace_path="x.xplane.pb", chips=4)
    assert load_reader("collective_share.tp4")(ctx) == pytest.approx(
        100 * 2.25 / 12.75)
    assert mod.shares(_events(), "bench.window", 1) == pytest.approx(
        (0.75, 1 + 0.5 + 0.25 + 0.5))
    assert load_reader("collective_share.tp4")(
        SimpleNamespace(trace_path=None, chips=4)) is None


def test_idle_share_is_the_mean_over_the_chips():
    """trace_reduce averages busy time over the chips the cell asks for."""
    red = trace_reduce.reduce(None, anchor="bench.window", chips=4,
                              events=_events())
    assert red["busy_s"] == pytest.approx(12.75 / 4)
    ctx = SimpleNamespace(reduced=red)
    assert load_reader("idle_share.tp4")(ctx) == pytest.approx(
        100 * (1 - 12.75 / 40))


def _serve_ctx(kernel_s=None):
    rows = [{"prompt": np.zeros(P, np.int32), "n": n}
            for P, n in ((64, 300), (200, 1023), (256, 512))]
    red = None if kernel_s is None else {
        "kernels": {"flash_decode": (kernel_s, 100)}}
    return SimpleNamespace(conf=CONF, peak=PEAK, chips=4, reduced=red,
                           result={"requests": rows, "t0": 0.0,
                                   "window_end": 30.0})


def _quarter_least_s():
    """The least time of one chip's share: 7 query heads, 1 KV head."""
    from bench.costs import flash_decode as FD
    from bench.readers import least
    nh, hd, L = 28 // 4, 128, CONF["num_hidden_layers"]
    pairs = [FD.cost(P + j + 1 + CONF["peft"]["n_prefix"], nh, 1, hd)
             for P, n in ((64, 300), (200, 1023), (256, 512))
             for j in range(n)]
    return L * least(pairs, PEAK)


def test_flash_decode_roofline_reads_one_chips_share():
    read = load_reader("flash_decode_roofline.tp4")
    t = _quarter_least_s()
    assert read(_serve_ctx(t)) == pytest.approx(100.0)
    assert read(_serve_ctx(1.25 * t)) == pytest.approx(80.0)
    # a kernel that ran whole on every chip does all four shares
    assert read(_serve_ctx(4 * t)) == pytest.approx(25.0)
    assert read(_serve_ctx(None)) is None


def test_mfu_over_the_mesh_is_a_quarter_of_one_chips():
    ctx = _serve_ctx()
    one = load_reader("mfu.serve")(ctx)
    assert load_reader("mfu.serve.tp4")(ctx) == pytest.approx(one / 4)
    assert 0 < one / 4 <= 100
