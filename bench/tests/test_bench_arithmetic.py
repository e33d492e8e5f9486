"""Rate arithmetic runs over all requests and the whole window (a planted
stall moves output tok/s), the warm-up reaches every shape, and the
kernel and model-step costs match hand counts."""
import numpy as np

from bench.costs import bound_s
from bench.costs import flash_attention as FA
from bench.costs import flash_decode as FD
from bench.costs import lora_matmul as LM
from bench.costs import model_step as MS
from bench.drivers.serve import cut_metrics, warmup_drains
from bench.model import load_config


def test_rate_is_all_tokens_over_the_whole_window():
    rec = [{"n": 100, "last": 10.0}, {"n": 300, "last": 8.0},
           {"n": 0, "last": None}]
    m, tokens, end = cut_metrics(rec, 0.0)
    assert tokens == 400 and end == 10.0 and m["output_tok_s"] == 40.0
    rec[0]["last"] = 12.0                           # a 2 s stall at the end
    m2, _, _ = cut_metrics(rec, 0.0)
    assert m2["output_tok_s"] == 400 / 12.0
    rec[0]["last"], rec[1]["last"] = 10.0, 10.0     # a stall mid-window
    m3, _, _ = cut_metrics(rec, -2.0)               # the window began 2 s early
    assert m3["output_tok_s"] == 400 / 12.0


def test_warmup_drains_reach_every_shape():
    """Simulates the engine's packing on the warm-up drains: every pow2
    prompt width gets the full wave and refills of 1..slots rows, and the
    last drain runs every pow2 segment up to the longest output."""
    pl = {"min": 64, "max": 2048}
    ol = {"min": 16, "max": 512}
    drains = warmup_drains(8, pl, ol, 4096, 1000, "d0")
    assert len(drains) == 6 + 1
    for d in drains:
        cap_row = d[-1]
        assert cap_row[3]["deadline_s"] == 0.0
        assert len(cap_row[1]) + cap_row[2] == 4096
    shapes = _simulate(drains[0][:-1], slots=8)
    assert shapes["refill_rows"] == {1, 2, 4, 8}
    seg = drains[-1][0][2]
    assert seg == 1023
    segs, rem = set(), seg
    while rem:
        s = 1 << (rem.bit_length() - 1)
        segs.add(s)
        rem -= s
    assert segs == {1 << k for k in range(10)}


def _simulate(reqs, slots):
    """The engine's slot logic (launch/engine.py DecodeEngine.run) on
    budgets alone: FIFO packing, pow2 refill rows, pow2-floor segments."""
    queue = [r[2] for r in reqs]
    rem = [0] * slots
    first, rows = True, set()
    while queue or any(rem):
        free = [i for i in range(slots) if rem[i] == 0]
        packed = 0
        for i in free:
            if not queue:
                break
            rem[i] = queue.pop(0)
            packed += 1
        if packed and not first:
            rows.add(min(1 << (packed - 1).bit_length(), slots))
        first = first and not packed
        live = [r for r in rem if r > 0]
        if not live:
            continue
        seg = 1 << ((min(live) if queue else max(live)).bit_length() - 1)
        rem = [max(0, r - seg) for r in rem]
    return {"refill_rows": rows}


def test_costs_by_hand():
    # decode: one token, 2 kv heads of 4, 8 query heads, 10 visible keys
    f, b = FD.cost(10, 8, 2, 4)
    assert f == 4 * 8 * 4 * 10 and b == 2 * 4 * (2 * 10 * 2 + 2 * 8)
    # prefill: 3 tokens, 2 prefix slots: pairs 6 + 6
    f, b = FA.cost(3, 2, 8, 2, 4)
    assert f == 4 * 8 * 4 * 12
    assert b == 2 * 4 * (2 * 3 * 8 + 2 * 5 * 2)
    f, b = LM.forward(5, 7, 11, 2)
    assert f == 2 * 5 * 7 * 11 + 2 * 5 * 2 * 18
    assert b == 2 * (35 + 77 + 55 + 36)
    f, b = LM.backward(5, 7, 11, 2)
    assert f == 4 * 5 * 2 * 18 and b == 2 * (35 + 55 + 72)
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert bound_s(1000.0, 50.0, peak) == (10.0, "flops")
    assert bound_s(100.0, 50.0, peak) == (5.0, "bytes")


def test_model_step_counts_qwen2_7b():
    conf = load_config("qwen2-7b")
    s = MS.sizes(conf)
    # 14 layers of q, k, v, o and the gated MLP: 3.26 B weights
    assert s["layer"] * s["L"] // 2 == 3_262_644_224
    f = MS.token_forward(conf, 0.0, False)
    assert f == s["L"] * (s["layer"] + s["lora"])
    assert np.isclose(MS.decode(conf, 100, 1),
                      MS.token_forward(conf, 100 + 1 + 16, True))
    assert MS.train_step(conf, 4, 512) > 3 * 4 * 512 * f / 2
