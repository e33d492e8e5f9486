"""Runs the harness at toy widths on the CPU (the look for a chip
skipped), optionally with the timed path broken underneath."""
import json
from pathlib import Path

from bench import run

HERE = Path(__file__).resolve().parent
CELL = {"open": "qwen2-7b.tenants-poisson", "closed": "qwen2-7b.decode-batch", "train": "qwen2-7b.hfsl-round"}


def run_tiny(kind: str, capsys, seconds: float = 2.0, seed: int = 2**31 + 3):
    conf = json.loads((HERE / "tiny.json").read_text())
    traffic = json.loads((HERE / f"tiny-{kind}.json").read_text())
    capsys.readouterr()
    rc = run.main(["--workload", CELL[kind], "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"],
                  require_tpu=False, conf=conf, traffic=traffic)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out
    return json.loads(out[-1])
