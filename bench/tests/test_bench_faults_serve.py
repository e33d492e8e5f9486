"""A sound serving run reads correct; a served token altered where the
engine produces it reads not correct."""
import numpy as np
import pytest

from faults import run_tiny


@pytest.mark.parametrize("kind", ["open", "closed"])
def test_sound_run_is_correct(kind, capsys):
    line = run_tiny(kind, capsys)
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert line["checks"]["max_gap"]["value"] < 1e-3
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["output_tok_s"]["value"] > 0


def test_altered_token_is_caught(monkeypatch, capsys):
    from repro.launch import engine as E
    real = E.DecodeEngine.run

    def altered(self, params):
        comps, stats = real(self, params)
        for c in comps:
            if len(c.tokens):
                c.tokens = np.array(c.tokens)
                c.tokens[-1] = (c.tokens[-1] + 1) % self.cfg.vocab_size
        return comps, stats

    monkeypatch.setattr(E.DecodeEngine, "run", altered)
    line = run_tiny("open", capsys)
    assert line["correct"] is False
