"""The control (the reference put in the program's place, in fp8) and the
readings each limit of ``correct`` was set from.

At toy widths on the CPU, through the same code the chip runs
(``bench/control.py``): the training control fails the cell's limits, and
so does a round that drops half of each batch; the serving control reads
a gap far above the program's (an fp8 error grows with depth and width,
so at toy size it stays under the chip's limit). The limits files hold
the chip's readings: each limit lies above the program's largest reading
and below the least of the control's."""
import json
from pathlib import Path

import pytest

from bench import check, control

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json")
                                       .read_text())["workloads"]]


def _readings(kind, cell, seeds):
    conf = json.loads((HERE / "tiny.json").read_text())
    traffic = json.loads((HERE / f"tiny-{kind}.json").read_text())
    return control.main(["--workload", cell, "--seeds", *map(str, seeds),
                         "--seconds", "2"], require_tpu=False, conf=conf,
                        traffic=traffic)


def _fails(reading: dict, lim: dict) -> bool:
    return any(reading[k] > lim[k]["limit"] for k in lim)


def test_training_control_and_half_batch_are_not_correct():
    lim = check.limits("qwen2-7b.hfsl-round")
    for r in _readings("train", "qwen2-7b.hfsl-round", [21, 22]):
        assert r["compiles"] == 0
        assert not _fails(r["program"], lim), r
        assert _fails(r["control"], lim), r
        assert _fails(r["half_batch"], lim), r


@pytest.mark.parametrize("kind,cell", [
    ("open", "qwen2-7b.tenants-poisson"),
    ("closed", "qwen2-7b.decode-batch")])
def test_serving_control_separates(kind, cell):
    lim = check.limits(cell)
    for r in _readings(kind, cell, [21, 22]):
        assert r["compiles"] == 0
        assert not _fails(r["program"], lim), r
        assert r["control"]["max_gap"] > 0.1 > 10 * r["program"]["max_gap"]


@pytest.mark.parametrize("cell", CELLS)
def test_limits_sit_between_their_readings(cell):
    for name, v in check.limits(cell).items():
        assert v["lower"] < v["limit"] < v["upper"], name
        assert v["upper"] >= 3 * v["lower"], name
