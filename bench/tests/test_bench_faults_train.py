"""A sound training round reads correct; a round that returns its state
unchanged, and one that leaves half of each batch out (the mean over the
rest), read not correct."""
import pytest

from faults import run_tiny


def test_sound_round_is_correct(capsys):
    line = run_tiny("train", capsys)
    assert line["correct"] is True
    assert list(line)[-1] == "checks"


def _patch_round(monkeypatch, wrap):
    from repro.core import hfsl
    real = hfsl.make_hfsl_round

    def make(*a, **k):
        return wrap(real(*a, **k))

    monkeypatch.setattr(hfsl, "make_hfsl_round", make)


def test_unchanged_state_is_caught(monkeypatch, capsys):
    def wrap(round_fn):
        def stuck(state, bank, offset=0, **kw):
            _, metrics = round_fn(state, bank, offset, **kw)
            return state, metrics
        return stuck

    _patch_round(monkeypatch, wrap)
    line = run_tiny("train", capsys)
    assert line["correct"] is False
    assert line["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_caught(monkeypatch, capsys):
    def wrap(round_fn):
        def half(state, bank, offset=0, **kw):
            b = {k: v[:, :, :v.shape[2] // 2] for k, v in bank.items()}
            return round_fn(state, b, offset, **kw)
        return half

    _patch_round(monkeypatch, wrap)
    line = run_tiny("train", capsys)
    assert line["correct"] is False
