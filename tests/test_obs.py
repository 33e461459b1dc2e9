"""repro.obs: virtual-time tracing + metrics layer.

The two load-bearing guarantees (ISSUE-7 satellites):

  * **Determinism** — recording the same workload twice yields
    byte-identical virtual-time traces once wall timestamps are stripped
    (``to_json(strip_wall=True)``);
  * **Zero-cost when off** — a tracing-off run leaves every netem /
    session / replay counter bit-identical to a traced run (tracing only
    *reads* the virtual clock, never mutates accounting).

Plus the tracer/metrics unit surface (interval-union attribution,
clock-scope rebasing, nearest-rank quantiles, stable snapshot schema)
and the report/bench schema checker.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.netem import WIFI, NetworkEmulator
from repro.core.recorder import compile_artifact
from repro.core.recording import Recording
from repro.core.replay_passes import PlanExecutor, plan_for
from repro.obs import (NULL, Metrics, NullTracer, SchemaError, Tracer,
                       check_workspace_report, metric_key, traced)
from repro.obs.schema import check_bench_file, check_scheduler_stats
from repro.record import CloudDryrun, RecordingSession

JOBS = 16


def _tiny():
    return (lambda x: jnp.tanh(x) * 2.0,
            (jax.ShapeDtypeStruct((8,), jnp.float32),))


@pytest.fixture(scope="module")
def artifact():
    fn, spec = _tiny()
    return compile_artifact("t", fn, spec)


def _copy(rec):
    return Recording(dict(rec.manifest), rec.payload, rec.trees)


class FakeClock:
    """Hand-cranked virtual clock for tracer unit tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ------------------------------------------------------------ tracer unit --
def test_span_nesting_and_attribution_union():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("outer", "work"):
        clk.t = 2.0
        with tr.span("inner", "work"):
            clk.t = 5.0
        clk.t = 10.0
    # inner [2,5) nests inside outer [0,10): union is 10, not 13
    assert tr.attributed_s("work") == 10.0
    spans = tr.spans("work")
    assert [s["name"] for s in spans] == ["inner", "outer"]  # close order
    assert spans[1]["ts"] == 0.0 and spans[1]["dur"] == 10.0


def test_attribution_disjoint_and_since_mark():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("a", "t"):
        clk.t = 3.0
    clk.t = 10.0
    since = tr.mark()
    with tr.span("b", "t"):
        clk.t = 14.0
    assert tr.attributed_s("t") == 7.0            # [0,3) + [10,14)
    assert tr.attributed_s("t", since=since) == 4.0
    assert tr.attributed_s("other") == 0.0


def test_clock_scope_rebases_sequentially():
    """Two components with private emulators lay out end-to-end on the
    trace timeline instead of both starting at 0."""
    tr = Tracer()                                 # base clock: constant 0
    n1 = NetworkEmulator(WIFI)
    with tr.clock_scope(n1), tr.span("first", "record"):
        n1.round_trip()
    first = tr.spans("record")[0]
    assert first["ts"] == 0.0 and first["dur"] > 0.0
    n2 = NetworkEmulator(WIFI)                    # fresh clock, also at 0
    with tr.clock_scope(n2), tr.span("second", "record"):
        n2.round_trip()
    second = tr.spans("record")[1]
    assert second["ts"] == pytest.approx(first["dur"])  # rebased past first
    # None scope is a no-op, not an error
    with tr.clock_scope(None):
        assert tr.now() == 0.0


def test_chrome_trace_export_shape():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("s", "record", site="reg0"):
        clk.t = 1.5
    tr.instant("ping", "replay")
    tr.counter("depth", 3, "replay")
    doc = tr.chrome_trace(strip_wall=True)
    assert doc["metadata"]["clock"] == "virtual"
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert [m["args"]["name"] for m in metas] == ["record", "replay"]
    span = next(e for e in evs if e["ph"] == "X")
    assert span["ts"] == 0.0 and span["dur"] == 1.5e6   # seconds -> us
    assert span["args"] == {"site": "reg0"}             # wall stripped
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t"
    ctr = next(e for e in evs if e["ph"] == "C")
    assert ctr["args"] == {"value": 3.0}
    # wall fields come back when not stripped
    wall = tr.chrome_trace(strip_wall=False)
    assert "wall_s" in next(e for e in wall["traceEvents"]
                            if e["ph"] == "X")["args"]


def test_null_tracer_is_falsy_noop():
    assert not NULL
    assert isinstance(NULL, NullTracer)
    assert NULL.mark() == 0 and NULL.now() == 0.0
    with NULL.span("x", "y"), NULL.clock_scope(None):
        pass
    NULL.instant("x")
    NULL.counter("x", 1)
    assert NULL.events == ()
    # traced() hands back a shared no-op context manager when off
    with traced(NULL, "x", "y", k=1):
        pass
    tr = Tracer(clock=FakeClock())
    with traced(tr, "x", "y"):
        pass
    assert len(tr.events) == 1


def test_span_records_args_set_in_its_body():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with traced(tr, "admit", "t", rid=3) as args:
        args["n"] = 2
    assert tr.spans("t")[0]["args"] == {"rid": 3, "n": 2}
    with traced(NULL, "admit", "t", rid=3) as args:
        args["n"] = 2                       # dropped, no error
    assert NULL.events == ()


def test_null_tracer_watch_gc_records_nothing():
    import gc
    callbacks = list(gc.callbacks)
    with NULL.watch_gc():
        gc.collect()
    assert NULL.events == () and gc.callbacks == callbacks


def test_watch_gc_records_each_collection_and_unhooks():
    import gc
    clk = FakeClock()
    tr = Tracer(clock=clk)
    callbacks = list(gc.callbacks)
    with tr.watch_gc():
        gc.collect(1)
    gc.collect()                            # outside the scope: not seen
    assert gc.callbacks == callbacks
    spans = tr.spans("host")
    assert [s["name"] for s in spans] == ["host.gc"]
    assert spans[0]["args"]["generation"] == 1
    assert spans[0]["args"]["collected"] >= 0


def test_annotate_mode_runs_on_the_host_clock():
    import time
    with pytest.raises(ValueError):
        Tracer(clock=FakeClock(), annotate=True)
    tr = Tracer(annotate=True)
    assert tr.annotate and not Tracer().annotate
    t0 = time.perf_counter()
    with tr.clock_scope(NetworkEmulator(WIFI)), tr.span("s", "t", k=1):
        time.sleep(0.002)
    tr.instant("i", "t")
    span, inst = tr.events
    assert t0 <= span["ts"] and span["dur"] >= 0.002
    assert span["ts"] + span["dur"] <= inst["ts"] <= time.perf_counter()
    assert tr.chrome_trace()["metadata"]["clock"] == "host"


def test_annotate_mode_writes_spans_into_the_profiler_trace(tmp_path):
    """Spans, instants and ``host.gc`` reach a CPU profiler trace under
    their bare names, nested, with their args as event stats."""
    import gc
    import glob
    from jax.profiler import ProfileData
    tr = Tracer(annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    with tr.watch_gc():
        with traced(tr, "executor.admit", "serve.s", rid=3) as args:
            with tr.span("prefill.dispatch", "serve.s", rid=3):
                jnp.ones(4).block_until_ready()
            tr.instant("frontier.mispredict", "serve.s", dropped=2)
            gc.collect(0)
            args["n"] = 1
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    seen, gcs = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.start_ns, e.duration_ns,
                          {k: v for k, v in e.stats})
                    if e.name == "host.gc":
                        gcs.append(ev)
                    elif e.name in ("executor.admit", "prefill.dispatch",
                                    "frontier.mispredict"):
                        seen[e.name] = ev
    assert seen["executor.admit"][2] == {"rid": 3, "n": 1}
    assert seen["prefill.dispatch"][2] == {"rid": 3}
    assert seen["frontier.mispredict"][2] == {"dropped": 2}
    a0, adur, _ = seen["executor.admit"]
    inside = lambda ev: a0 <= ev[0] and ev[0] + ev[1] <= a0 + adur
    assert inside(seen["prefill.dispatch"])
    assert inside(seen["frontier.mispredict"])
    assert seen["frontier.mispredict"][1] < 1e5        # zero-length
    # the forced collection (the library may collect on its own too)
    assert any(inside(ev) and ev[2]["generation"] == 0 for ev in gcs)
    # the in-memory events keep the same spans on the same clock
    names = [e["name"] for e in tr.events if e["name"] != "host.gc"]
    assert names == ["prefill.dispatch", "frontier.mispredict",
                     "executor.admit"]
    assert len(tr.spans("host")) == len(gcs)


def test_summary_orders_by_virtual_time():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("small", "t"):
        clk.t = 1.0
    with tr.span("big", "t"):
        clk.t = 9.0
    rows = tr.summary()
    assert [r["name"] for r in rows] == ["big", "small"]
    assert rows[0]["virtual_s"] == 8.0 and rows[0]["count"] == 1
    assert "big" in tr.format_summary(top=1)
    assert "small" not in tr.format_summary(top=1)


# ----------------------------------------------------------- metrics unit --
def test_metric_key_sorts_labels():
    assert metric_key("lat", {}) == "lat"
    assert metric_key("lat", {"b": 1, "a": "x"}) == "lat{a=x,b=1}"


def test_histogram_nearest_rank_quantiles():
    m = Metrics()
    h = m.histogram("lat", stream="s0")
    for v in range(1, 101):                       # 1..100
        h.observe(float(v))
    q = m.quantiles("lat", stream="s0")
    assert q == {"p50": 50.0, "p99": 99.0, "p999": 100.0}
    assert h.quantile(1.0) == 100.0
    with pytest.raises(ValueError):
        h.quantile(0.0)
    # single observation: every quantile is that value
    one = m.histogram("lat", stream="s1")
    one.observe(7.5)
    assert m.quantiles("lat", stream="s1") == \
        {"p50": 7.5, "p99": 7.5, "p999": 7.5}


def test_metrics_snapshot_stable_schema():
    m = Metrics()
    m.counter("hits", stream="a").inc(3)
    m.histogram("lat").observe(2.0)
    snap = m.snapshot()
    assert snap["counters"] == {"hits{stream=a}": 3}
    s = snap["histograms"]["lat"]
    assert set(s) == {"count", "sum", "min", "max", "p50", "p99", "p999"}
    # empty histogram still renders every key, zeros throughout
    empty = Metrics().histogram("never").summary()
    assert empty == {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                     "p50": 0.0, "p99": 0.0, "p999": 0.0}
    # reporting lookups never mint series
    assert m.get_histogram("absent") is None
    assert m.quantiles("absent") is None


# ---------------------------------------------------------- determinism ----
def _session_run(artifact, passes="all", tracer=None):
    s = RecordingSession.for_profile(WIFI, passes=passes,
                                     cloud=CloudDryrun(jobs=JOBS),
                                     tracer=tracer)
    rec = s.finalize(_copy(artifact))
    return s, rec.manifest["record_session"]


def _traced_session_run(artifact, passes="all"):
    tr = Tracer()
    _, rep = _session_run(artifact, passes=passes, tracer=tr)
    return tr, rep


def test_trace_determinism_byte_identical(artifact):
    """ISSUE-7 acceptance: same workload recorded twice -> byte-identical
    virtual-time traces once wall timestamps are stripped."""
    tr1, rep1 = _traced_session_run(artifact)
    tr2, rep2 = _traced_session_run(artifact)
    assert rep1 == rep2
    j1 = tr1.to_json(strip_wall=True)
    j2 = tr2.to_json(strip_wall=True)
    assert j1 == j2
    # the wall-bearing exports differ structurally only in wall args
    assert len(tr1.events) == len(tr2.events) > 0


def test_replay_trace_determinism(artifact):
    traces = []
    for _ in range(2):
        tr = Tracer()
        plan = plan_for(_copy(artifact), "all", jobs=JOBS)
        PlanExecutor(netem=NetworkEmulator(WIFI), tracer=tr).run(plan)
        traces.append(tr.to_json(strip_wall=True))
    assert traces[0] == traces[1]
    assert '"replay.dispatch"' in traces[0]
    assert '"replay.collapsed_poll"' in traces[0]


def test_tracing_off_leaves_all_counters_unchanged(artifact):
    """Zero-cost-when-off: every netem/session counter is bit-identical
    between a traced run and an untraced run of the same workload."""
    on, traced_rep = _session_run(artifact, tracer=Tracer())
    off, off_rep = _session_run(artifact)
    assert off.tracer is NULL
    assert off_rep == traced_rep
    assert off.netem.snapshot() == on.netem.snapshot()
    # replay side: traced and untraced executors bill identically
    reports = []
    for tr in (Tracer(), None):
        plan = plan_for(_copy(artifact), "all", jobs=JOBS)
        reports.append(
            PlanExecutor(netem=NetworkEmulator(WIFI), tracer=tr).run(plan))
    assert reports[0] == reports[1]


def test_wifi_record_attribution_ge_95pct(artifact):
    """>= 95% of the session's billed virtual time is covered by named
    record-track spans (the recording-ablation acceptance bar)."""
    for passes in ("none", "all"):
        tr, rep = _traced_session_run(artifact, passes=passes)
        att = tr.attributed_s("record")
        assert rep["virtual_time_s"] > 0
        assert att / rep["virtual_time_s"] >= 0.95


# ------------------------------------------------------------- schema ------
def test_workspace_report_passes_schema_check():
    from repro.api import Workspace
    ws = Workspace(registry=":memory:", key=b"obs-test-key", net="wifi",
                   trace=True)
    wl = ws.workload("cody-mnist", cache_len=32, block_k=4, batch=1, seq=8)
    rec = wl.record("prefill", jobs=8)
    wl.publish(rec)
    wl.fetch("prefill")
    wl.replay(artifact=rec, jobs=8)
    rep = ws.report()
    check_workspace_report(rep)                   # raises on any drift
    assert ws.tracer.events                       # lifecycle left a trace
    # net snapshot carries the once-dropped async/collapsed counters
    assert "async_trips" in rep["net"]
    assert "collapsed_spins" in rep["net"]
    assert rep["net"]["bytes"] == \
        rep["net"]["bytes_sent"] + rep["net"]["bytes_received"]


def test_schema_check_rejects_drift():
    from repro.api import Workspace
    ws = Workspace(registry=":memory:", key=b"obs-test-key", net="wifi")
    wl = ws.workload("cody-mnist", cache_len=32, block_k=4, batch=1, seq=8)
    wl.record("prefill", jobs=8)
    rep = ws.report()
    rep["net"].pop("async_trips")                 # the old snapshot() bug
    with pytest.raises(SchemaError):
        check_workspace_report(rep)
    rep2 = ws.report()
    del rep2["metrics"]
    with pytest.raises(SchemaError):
        check_workspace_report(rep2)


def test_scheduler_stats_schema():
    good = {"preemptions": 0, "eviction_unsupported": 0, "live_slots": 0,
            "max_live_slots": None, "stall_limit": 8,
            "streams": {"s0": {"stalled": 0, "stall_hwm": 0,
                               "unevictable": False, "evicted_requests": 0,
                               "admissions_deferred": 0}}}
    check_scheduler_stats(good)
    bad = dict(good, streams={"s0": {"stalled": 0}})
    with pytest.raises(SchemaError):
        check_scheduler_stats(bad)


def test_check_bench_file_validates_trace_artifact(tmp_path):
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("s", "t"):
        clk.t = 1.0
    p = tmp_path / "TRACE_smoke.json"
    tr.dump(str(p))
    check_bench_file(str(p))
    (tmp_path / "TRACE_empty.json").write_text('{"traceEvents": []}')
    with pytest.raises(SchemaError):
        check_bench_file(str(tmp_path / "TRACE_empty.json"))
    (tmp_path / "BENCH_unknown.json").write_text("{}")
    with pytest.raises(SchemaError):
        check_bench_file(str(tmp_path / "BENCH_unknown.json"))
