"""Telemetry layer: histograms, spans, no-op discipline, engine lifecycle."""
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import telemetry
from repro.core.telemetry import (Histogram, Telemetry, _NOOP_SPAN,
                                  _GROWTH)
from repro.configs.base import get_config
from repro.launch.engine import DecodeEngine
from repro.models import model as M


# -- histogram --------------------------------------------------------------
@pytest.mark.parametrize("dist,args", [
    ("uniform", (1e-3, 1.0)),
    ("lognormal", (-5.0, 2.0)),
    ("exponential", (0.05,)),
])
def test_histogram_percentiles_match_numpy(dist, args):
    """Log-bucketed percentiles track exact numpy percentiles to within
    one geometric bucket step (~±15% relative error by construction)."""
    rng = np.random.default_rng(0)
    xs = getattr(rng, dist)(*args, size=20_000)
    xs = np.abs(xs) + 1e-9
    h = Histogram()
    for x in xs:
        h.record(float(x))
    assert h.n == len(xs)
    assert h.mean == pytest.approx(float(xs.mean()), rel=1e-6)
    assert h.vmin == pytest.approx(float(xs.min()))
    assert h.vmax == pytest.approx(float(xs.max()))
    for q in (50, 90, 95, 99):
        exact = float(np.percentile(xs, q))
        got = h.percentile(q)
        # one bucket step of relative slack either side
        assert exact / _GROWTH <= got <= exact * _GROWTH, \
            f"p{q}: exact={exact:.4g} hist={got:.4g}"


def test_histogram_multiplicity_and_clamping():
    h = Histogram()
    h.record(0.5, n=10)
    assert h.n == 10 and h.total == pytest.approx(5.0)
    # a single distinct value: every percentile collapses onto it exactly
    # (bucket midpoints are clamped into the observed [min, max])
    for q in (1, 50, 99, 100):
        assert h.percentile(q) == pytest.approx(0.5)
    h2 = Histogram()
    assert h2.percentile(99) == 0.0 and h2.summary()["count"] == 0


def test_histogram_summary_keys():
    h = Histogram()
    for v in (1e-4, 1e-3, 1e-2):
        h.record(v)
    s = h.summary()
    assert set(s) == {"count", "sum", "mean", "min", "max",
                      "p50", "p95", "p99"}
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


# -- spans ------------------------------------------------------------------
def test_span_nesting_depth_and_ordering():
    tel = Telemetry(enabled=True)
    with tel.span("outer", wave=1) as outer:
        with tel.span("inner"):
            time.sleep(0.002)
        outer.set(tokens=7)
    with tel.span("after"):
        pass
    names = [s.name for s in tel.spans]
    assert names == ["inner", "outer", "after"]   # exit order
    inner, outer, after = tel.spans
    assert inner.depth == 1 and outer.depth == 0 and after.depth == 0
    # the inner interval is enclosed by the outer one
    assert outer.t0 <= inner.t0
    assert inner.t0 + inner.dur <= outer.t0 + outer.dur + 1e-9
    assert outer.args == {"wave": 1, "tokens": 7}
    assert after.t0 >= outer.t0 + outer.dur - 1e-9


def test_record_span_external_interval():
    tel = Telemetry(enabled=True)
    t0 = time.perf_counter()
    t1 = t0 + 0.25
    tel.record_span("req", t0, t1, uid=3)
    (sp,) = tel.spans
    assert sp.dur == pytest.approx(0.25)
    assert sp.args == {"uid": 3}


def test_disabled_mode_is_a_true_noop():
    tel = Telemetry(enabled=False)
    # one shared context manager: no allocation per disabled span
    assert tel.span("a") is _NOOP_SPAN
    assert tel.span("b", x=1) is tel.span("c")
    with tel.span("a") as sp:
        sp.set(tokens=1)
    tel.count("c")
    tel.observe("h", 0.1)
    tel.gauge("g", 2.0)
    assert not tel.counters and not tel.hists
    assert not tel.gauges and not tel.spans
    assert tel.hist_summary("h") is None


def test_module_singleton_enable_disable():
    tel = telemetry.get()
    assert tel is telemetry.get()
    try:
        telemetry.enable()
        assert tel.enabled
        tel.count("x")
        assert tel.counters["x"] == 1
        telemetry.enable(fresh=True)               # reset on re-enable
        assert "x" not in tel.counters
    finally:
        telemetry.disable()
    assert not tel.enabled


# -- export -----------------------------------------------------------------
def test_trace_export_round_trip(tmp_path):
    tel = Telemetry(enabled=True)
    with tel.span("engine.segment", wave=np.int32(2), live=jnp.asarray(3)):
        time.sleep(0.001)
    tel.count("engine.tokens", 42)
    path = tmp_path / "trace.json"
    n = tel.export_trace(str(path))
    assert n == 1
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    seg = [e for e in evs if e["ph"] == "X"]
    assert len(seg) == 1 and seg[0]["name"] == "engine.segment"
    assert seg[0]["dur"] >= 1000                   # >= 1ms in microseconds
    assert seg[0]["cat"] == "engine"
    # numpy / jax scalars in span args must coerce to plain JSON numbers
    assert seg[0]["args"] == {"wave": 2.0, "live": 3.0}
    cnt = [e for e in evs if e["ph"] == "C"]
    assert cnt and cnt[0]["name"] == "engine.tokens"
    assert cnt[0]["args"]["value"] == 42


def test_metrics_export_and_snapshot(tmp_path):
    tel = Telemetry(enabled=True)
    tel.count("a", 2)
    tel.gauge("g", 1.5)
    tel.observe("lat", 0.01)
    path = tmp_path / "metrics.json"
    tel.export_metrics(str(path))
    snap = json.loads(path.read_text())
    assert snap["counters"] == {"a": 2}
    assert snap["gauges"] == {"g": 1.5}
    assert snap["histograms"]["lat"]["count"] == 1
    assert "a" in tel.report() and "lat" in tel.report()


# -- engine lifecycle -------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    cfg = get_config("vit-edge").reduced().with_(dtype="float32",
                                                 vocab_size=64)
    params = M.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_lifecycle_metrics(setup):
    """A ragged drain books a coherent submit -> admit -> first-token ->
    retire lifecycle per request, and EngineStats summarizes it."""
    cfg, params = setup
    tel = Telemetry(enabled=True)
    key = jax.random.PRNGKey(3)
    engine = DecodeEngine(cfg, slots=3, tel=tel)
    short = np.asarray(jax.random.randint(key, (2, 8), 0, cfg.vocab_size))
    long = np.asarray(jax.random.randint(key, (2, 12), 0, cfg.vocab_size))
    budgets = [3, 6, 5, 2]
    for toks, g in zip([short[0], long[0], short[1], long[1]], budgets):
        engine.submit(toks, g)
    comps, stats = engine.run(params)
    assert len(comps) == 4
    for c in comps:
        assert c.queue_s >= 0
        assert c.ttft_s is not None and c.ttft_s >= c.queue_s
        assert c.latency_s >= c.ttft_s
        assert c.tok_s > 0
    # histogram summaries are always on (independent of telemetry state);
    # the queue wait is telemetry's own histogram
    assert stats.ttft_hist["count"] == 4
    assert tel.hist_summary("engine.queue_s")["count"] == 4
    assert stats.tok_latency_hist["count"] == sum(budgets)
    assert stats.ttft_hist["p50"] <= stats.ttft_hist["p99"]
    # opt-in global spans: one lifecycle span per request, segments, drain
    by_name = {}
    for sp in tel.spans:
        by_name.setdefault(sp.name, []).append(sp)
    assert len(by_name["engine.request"]) == 4
    assert "engine.prefill" in by_name and "engine.segment" in by_name
    (drain,) = by_name["engine.drain"]
    assert drain.args["tokens"] == sum(budgets)
    assert tel.counters["engine.retired"] == 4


def test_engine_deadlines_survive_wall_clock_jump(setup, monkeypatch):
    """Deadline sweeps and latency ledgers anchor on time.perf_counter();
    a wall-clock step (NTP, suspend) must not spuriously retire requests
    or corrupt latencies."""
    cfg, params = setup
    jumped = time.time() + 3600.0
    monkeypatch.setattr(time, "time", lambda: jumped)
    engine = DecodeEngine(cfg, slots=2)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (2, 8), 0, cfg.vocab_size, dtype=jnp.int32))
    for p in prompts:
        engine.submit(p, 3, deadline_s=300.0)    # generous monotonic budget
    comps, stats = engine.run(params)
    assert stats.timed_out == 0
    for c in comps:
        assert not c.timed_out
        assert c.tokens.shape == (3,)
        assert 0 <= c.latency_s < 300.0          # not an hour
        assert c.ttft_s is not None and 0 <= c.ttft_s <= c.latency_s


# -- spans on the profiler's clock ------------------------------------------
def _host_events(logdir):
    """(name, start ns, end ns, args) of every host event of a profile."""
    (path,) = logdir.glob("plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_engine_spans_land_in_the_profile(setup, tmp_path):
    """Inside jax.profiler.trace an enabled Telemetry's spans are host events
    of the profile, with their arguments: every segment holds one dispatch
    and one sync; schedule and refill spans lie between segments; segments
    and refills name the requests they serve. A disabled Telemetry adds
    no event."""
    cfg, params = setup
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (4, 8), 0, cfg.vocab_size, dtype=jnp.int32))

    def drain(tel):
        engine = DecodeEngine(cfg, slots=2, tel=tel)
        uids = [engine.submit(p, g) for p, g in zip(prompts, [2, 5, 3, 4])]
        engine.run(params)
        return uids

    drain(Telemetry(enabled=False))            # compile outside the traces
    with jax.profiler.trace(str(tmp_path / "on")):
        uids = drain(Telemetry(enabled=True))
    with jax.profiler.trace(str(tmp_path / "off")):
        drain(Telemetry(enabled=False))

    on = _host_events(tmp_path / "on")
    by = lambda n: [e for e in on if e[0] == n]
    segs = by("engine.segment")
    assert segs and by("engine.prefill") and by("engine.refill")
    for child in ("engine.dispatch", "engine.sync"):
        evs = by(child)
        assert len(evs) == len(segs)
        assert all(any(s0 <= t0 and t1 <= s1 for _, s0, s1, _ in segs)
                   for _, t0, t1, _ in evs)
    assert by("engine.schedule")
    for name in ("engine.schedule", "engine.refill"):
        for _, t0, t1, _ in by(name):
            assert not any(t0 < s1 and s0 < t1 for _, s0, s1, _ in segs)
    served = set()
    for *_, args in segs + by("engine.prefill") + by("engine.refill"):
        served |= set(json.loads(args["uids"]))
    assert served == set(uids)
    assert all("wave" in a for *_, a in by("engine.refill"))
    (drain_ev,) = by("engine.drain")
    assert all(drain_ev[1] <= t0 and t1 <= drain_ev[2]
               for _, t0, t1, _ in segs)
    off = _host_events(tmp_path / "off")
    assert not any(n.startswith("engine.") for n, *_ in off)


def test_request_lifecycle_starts_when_due(setup):
    """serve_trace keeps each arrival's due time: its engine.request span
    starts there, engine.admit_lag_s records how late the admission sweep
    took it in, and the Completion's timings stay anchored at submit."""
    cfg, params = setup
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(6), (4, 8), 0, cfg.vocab_size, dtype=jnp.int32))
    tel = Telemetry(enabled=True)
    engine = DecodeEngine(cfg, slots=2, tel=tel)
    arrivals = [(0.0, prompts[0], 3), (0.0, prompts[1], 6),
                (0.02, prompts[2], 2), (0.05, prompts[3], 2)]
    comps, _ = engine.serve_trace(params, arrivals)
    lag = tel.hists["engine.admit_lag_s"]
    assert lag.n == 4 and lag.vmin >= 0
    by_uid = {c.uid: c for c in comps}
    spans = [sp for sp in tel.spans if sp.name == "engine.request"]
    assert sorted(sp.args["uid"] for sp in spans) == sorted(by_uid)
    lags = [sp.dur - by_uid[sp.args["uid"]].latency_s for sp in spans]
    assert min(lags) >= -1e-9
    assert sum(lags) == pytest.approx(lag.total, abs=1e-6)
