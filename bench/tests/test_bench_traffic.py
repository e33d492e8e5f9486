"""The traffic generator repeats exactly for a seed, differs across
seeds, and gives every seed the same multiset of sizes and gaps."""
import numpy as np
import pytest

from bench import traffic as TR

MIXES = ["tenants-poisson", "decode-batch"]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    tr = TR.load(mix)
    big = 2 ** 31 + 12345
    a = TR.serve_requests(tr, big, 10.0, 152064)
    b = TR.serve_requests(tr, big, 10.0, 152064)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["t"] == y["t"] and x["gen"] == y["gen"]
        assert x["domain"] == y["domain"]
        np.testing.assert_array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_differ_in_order_not_in_work(mix):
    tr = TR.load(mix)
    a = TR.serve_requests(tr, 1, 10.0, 152064)
    b = TR.serve_requests(tr, 2, 10.0, 152064)
    key = lambda rs: [(len(r["prompt"]), r["gen"]) for r in rs]
    assert key(a) != key(b)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert sorted(r["gen"] for r in a) == sorted(r["gen"] for r in b)
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r["t"] for r in rs]),
                                      9))
    assert gaps(a) == gaps(b)
    assert sorted(str(r["domain"]) for r in a) == \
        sorted(str(r["domain"]) for r in b)


def test_open_loop_fills_the_window():
    tr = TR.load("tenants-poisson")
    rs = TR.serve_requests(tr, 3, 30.0, 1000)
    k = tr["backlog"]
    assert len(rs) == k + round(tr["rate_rps"] * 30.0)
    t = [r["t"] for r in rs]
    assert t == sorted(t) and t[:k] == [0.0] * k and 0 < t[k]
    assert abs(t[-1] - 30.0) < 1e-9


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seconds", [2.0, 30.0])
def test_extremes_always_occur(mix, seconds):
    """The cache capacity a run warms for is the longest prompt plus the
    longest output, so both occur in every run, short ones too."""
    tr = TR.load(mix)
    rs = TR.serve_requests(tr, 11, seconds, 1000)
    P = [len(r["prompt"]) for r in rs]
    G = [r["gen"] for r in rs]
    assert (min(P), max(P)) == (tr["prompt_len"]["min"], tr["prompt_len"]["max"])
    assert (min(G), max(G)) == (tr["output_len"]["min"], tr["output_len"]["max"])


def test_zipf_counts_add_up():
    c = TR.zipf_counts(8, 1.1, 240)
    assert c.sum() == 240 and list(c) == sorted(c, reverse=True)


def test_train_bank_rows_differ():
    tr = TR.load("hfsl-round")
    b = TR.train_bank(tr, 7, 152064)
    assert b["tokens"].shape == (tr["epoch_rows"], tr["clusters"],
                                 tr["batch"], tr["seq"])
    np.testing.assert_array_equal(b["tokens"][..., 1:], b["labels"][..., :-1])
    rows = b["tokens"].reshape(tr["epoch_rows"], -1)
    assert len({r.tobytes() for r in rows}) == tr["epoch_rows"]
