"""trace_reduce: busy and idle time, kernel time, programs and the
breakdown, on hand-made events and on a small trace recorded on a v5e."""
from pathlib import Path

import pytest

from bench import trace_reduce as TRD

DATA = Path(__file__).resolve().parent / "data"


def _events():
    ms = 1_000_000
    ops = [("%fusion.1 = f32[2] fusion(...)", 0, 2 * ms, None),
           ("%flash_decode_pallas.3 = ...", 2 * ms, 5 * ms, "flash_decode"),
           ("%fusion.2 = f32[2] fusion(...)", 4 * ms, 6 * ms, None),
           ("%flash_attention_pallas.7 = ...", 8 * ms, 9 * ms,
            "flash_attention")]
    return {"host": [("bench.window", -1 * ms, 11 * ms),
                     ("engine.segment", 6 * ms, 8 * ms)],
            "devices": {0: {"ops": ops,
                            "modules": [("jit_impl", 0, 6 * ms),
                                        ("jit_impl", 8 * ms, 9 * ms)]}}}


def test_busy_idle_kernels_and_modules():
    spans = [("bench.window", 100.0, 100.012),
             ("engine.segment", 100.007, 100.009)]
    r = TRD.reduce(None, anchor="bench.window", chips=1, host_spans=spans,
                   events=_events())
    assert r["window_s"] == pytest.approx(0.012)
    assert r["busy_s"] == pytest.approx(0.007)           # 0-6 ms and 8-9 ms
    assert r["kernels"]["flash_decode"] == (pytest.approx(0.003), 1)
    assert r["kernels"]["flash_attention"] == (pytest.approx(0.001), 1)
    assert [m["kernels"] for m in r["modules"]] == [["flash_decode"],
                                                     ["flash_attention"]]
    assert r["modules"][0]["busy_s"] == pytest.approx(0.006)
    idle = dict(r["breakdown"]["idle_gaps"])
    # 6-8 ms lies under the engine.segment span; -1-0 and 9-11 under none
    assert idle["engine.segment"] == pytest.approx(0.002)
    assert idle["host"] == pytest.approx(0.003)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion"] == pytest.approx(0.004)
    assert TRD.op_name("%fusion.9 = f32[2] fusion(%a), kind=kOutput") == \
        "fusion:kOutput"
    assert len(r["breakdown"]["device_ops"]) <= 10


def test_no_anchor_reads_nothing():
    ev = _events()
    ev["host"] = []
    assert TRD.reduce(None, anchor="bench.window", chips=1, events=ev) is None


def test_kernel_names():
    op = ('%lora_bgmv_rows_pallas.12 = bf16[8,512] custom-call(s32[8] %a), '
          'custom_call_target="tpu_custom_call"')
    assert TRD.kernel_of(op) == "lora_bgmv_rows"
    assert TRD.kernel_of("%fusion.3 = bf16[8] fusion(%x), kind=kLoop") is None
    assert TRD.op_name("%fusion.66 = s32[2] fusion(...)") == "fusion"


def test_recorded_v5e_trace(tmp_path):
    """A trace recorded on one v5e: qwen2-7b at 14 layers, an 8-domain
    bank, 16 slots, 24 mixed requests drained through serve_trace inside
    a ``bench.window`` annotation."""
    import gzip
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(gzip.decompress((DATA / "serve-probe.xplane.pb.gz")
                                  .read_bytes()))
    r = TRD.reduce(f, anchor="bench.window", chips=1)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["window_s"] == pytest.approx(1.888633807)
    k = r["kernels"]
    # 14 layers: 51 decode steps of flash_decode, two lora_bgmv rows calls
    # (q, v) per decode step and layer; 8 prefills of flash_attention
    assert k["flash_decode"][1] == 14 * 51
    assert k["lora_bgmv_rows"][1] == 2 * k["flash_decode"][1]
    assert k["flash_attention"][1] == 14 * 8
    assert k["lora_bgmv_seq"][1] == 2 * k["flash_attention"][1]
    prefill = [m for m in r["modules"] if "flash_attention" in m["kernels"]]
    decode = [m for m in r["modules"] if "flash_decode" in m["kernels"]]
    assert len(prefill) == 8 and decode
    busy = sum(m["busy_s"] for m in r["modules"])
    assert busy == pytest.approx(r["busy_s"], rel=0.02)
    assert all(len(v) <= 10 for v in r["breakdown"].values())
