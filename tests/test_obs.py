"""repro.obs: tracing, metrics, exports, and the instrumented pipeline.

The observability acceptance criteria: a no-op default, correctly nested
spans (including under concurrent DesignEngine submissions), a metrics
registry with snapshot + Prometheus exposition, valid Chrome-trace JSON,
and the compile/pallas/serve instrumentation actually firing.
"""

import json
import threading

import jax
import numpy as np
import pytest

import repro.hls as hls
from repro import obs
from repro.core import frontend
from repro.models import braggnn
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NOOP_SPAN, Tracer


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts disabled with empty tracer/metrics state."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _conv_build(ctx):
    x = ctx.memref("input", (1, 1, 6, 6), "input")
    w = ctx.memref("w", (2, 1, 3, 3), "weight")
    b = ctx.memref("b", (2,), "weight")
    out = ctx.memref("out", (1, 2, 4, 4), "output")
    frontend.conv2d(ctx, x, w, b, out)


# ---------------------------------------------------------------------------
# disabled default: no spans, no metrics, shared no-op span
# ---------------------------------------------------------------------------


def test_disabled_is_noop():
    assert not obs.enabled()
    with obs.span("x", cat="t") as sp:
        sp.set(a=1)                       # must not raise
        assert sp is NOOP_SPAN
    obs.inc("c")
    obs.observe("h", 1.0)
    obs.gauge("g", 2.0)
    assert len(obs.tracer) == 0
    snap = obs.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}


def test_enable_disable_round_trip():
    obs.enable()
    assert obs.enabled()
    with obs.span("x", cat="t"):
        pass
    assert len(obs.tracer) == 1
    obs.disable()
    with obs.span("y", cat="t"):
        pass
    assert len(obs.tracer) == 1           # unchanged while disabled


# ---------------------------------------------------------------------------
# tracer: nesting, attributes, threads
# ---------------------------------------------------------------------------


def test_span_nesting_parent_links():
    obs.enable()
    with obs.span("outer", cat="t") as outer:
        with obs.span("inner", cat="t") as inner:
            assert inner.parent_id == outer.span_id
    spans = {s.name: s for s in obs.tracer.spans()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["outer"].t1 >= spans["inner"].t1 >= spans["inner"].t0


def test_span_attrs_and_record():
    obs.enable()
    with obs.span("s", cat="t", k=1) as sp:
        sp.set(v="x")
    s = obs.tracer.spans()[0]
    assert s.attrs == {"k": 1, "v": "x"}
    t = obs.now()
    obs.record_span("retro", t - 0.5, t, cat="t", kind="async", rid=7)
    r = [s for s in obs.tracer.spans() if s.name == "retro"][0]
    assert r.kind == "async" and r.attrs["rid"] == 7
    assert r.dur_s == pytest.approx(0.5, abs=0.05)


def test_thread_local_span_stacks():
    """Spans on different threads never parent across threads."""
    tracer = Tracer()
    barrier = threading.Barrier(4)

    def work(i):
        barrier.wait()
        with tracer.span(f"outer{i}", cat="t"):
            with tracer.span(f"inner{i}", cat="t"):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = {s.name: s for s in tracer.spans()}
    assert len(spans) == 8
    for i in range(4):
        assert spans[f"inner{i}"].parent_id == spans[f"outer{i}"].span_id
        assert spans[f"inner{i}"].thread == spans[f"outer{i}"].thread


def test_tracer_cap_drops_not_grows():
    tracer = Tracer(max_spans=3)
    for i in range(5):
        with tracer.span(f"s{i}", cat="t"):
            pass
    assert len(tracer) == 3 and tracer.dropped == 2


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_snapshot_and_kinds():
    m = MetricsRegistry()
    m.inc("reqs")
    m.inc("reqs", 2)
    m.set_gauge("depth", 4.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        m.observe("lat", v)
    snap = m.snapshot()
    assert snap["counters"]["reqs"] == 3
    assert snap["gauges"]["depth"] == 4.5
    h = snap["histograms"]["lat"]
    assert h["count"] == 4 and h["sum"] == 10.0
    assert h["min"] == 1.0 and h["max"] == 4.0
    with pytest.raises(TypeError):
        m.inc("lat")                      # kind mismatch is loud


def test_histogram_rejects_nan():
    m = MetricsRegistry()
    m.observe("h", float("nan"))
    m.observe("h", 2.0)
    assert m.snapshot()["histograms"]["h"]["count"] == 1


def test_prometheus_exposition():
    m = MetricsRegistry()
    m.inc("design_cache.hits", 3)
    m.observe("serve.queue_depth", 5.0)
    text = m.to_prometheus()
    assert "# TYPE repro_design_cache_hits counter" in text
    assert "repro_design_cache_hits 3" in text
    assert 'repro_serve_queue_depth{quantile="0.95"}' in text


# ---------------------------------------------------------------------------
# chrome trace export + __main__ summary
# ---------------------------------------------------------------------------


def test_chrome_trace_export_is_valid(tmp_path):
    obs.enable()
    with obs.span("compile", cat="compile"):
        with obs.span("compile.trace", cat="compile"):
            pass
    t = obs.now()
    obs.record_span("serve.request", t - 0.01, t, cat="serve",
                    kind="async", rid=0)
    obs.inc("design_cache.misses")
    path = obs.export_chrome_trace(tmp_path / "trace.json")
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    phases = {e["ph"] for e in events}
    assert {"X", "b", "e", "M"} <= phases
    names = {e["name"] for e in events if e["ph"] == "X"}
    assert {"compile", "compile.trace"} <= names
    for e in events:
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    assert doc["otherData"]["metrics"]["counters"]["design_cache.misses"] \
        == 1


def test_main_summarises_trace(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main
    obs.enable()
    with obs.span("compile", cat="compile"):
        pass
    obs.inc("design_cache.hits")
    path = obs.export_chrome_trace(tmp_path / "t.json")
    assert obs_main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "compile" in out and "design_cache.hits" in out


# ---------------------------------------------------------------------------
# instrumentation: compiler, pallas, serving engine
# ---------------------------------------------------------------------------


def test_compile_emits_nested_spans_and_cache_counters():
    obs.enable()
    s = hls.Session()
    s.compile(_conv_build, name="obs_conv")
    names = [sp.name for sp in obs.tracer.spans()]
    for expected in ("compile", "compile.trace", "compile.passes",
                     "compile.schedule", "passes.cse"):
        assert expected in names, (expected, names)
    by_name = {sp.name: sp for sp in obs.tracer.spans()}
    root = by_name["compile"]
    assert by_name["compile.trace"].parent_id == root.span_id
    assert by_name["compile.schedule"].parent_id == root.span_id
    assert root.attrs["ops_raw"] >= root.attrs["ops_opt"] > 0
    snap = obs.snapshot()
    assert snap["counters"]["design_cache.misses"] == 1
    s.compile(_conv_build, name="obs_conv")
    assert obs.snapshot()["counters"]["design_cache.hits"] == 1


def test_pallas_profile_spans_on_first_call():
    """Enabling obs changes no program: the first call records the
    lowering's span and counters, runs the jitted program (no host-timed
    profile), and matches the second call."""
    from repro.core import verify
    from repro.core.emit_pallas import to_pallas_fn
    obs.enable()
    design = hls.Session().compile(_conv_build, name="obs_pallas")
    feeds = verify.random_feeds(design.graph_raw, batch=2, seed=0)
    fn = to_pallas_fn(design.graph_opt)
    out1 = fn(feeds)
    names = [sp.name for sp in obs.tracer.spans()]
    assert "emit.pallas" in names
    assert "pallas.profile" not in names
    assert not any(n.startswith("pallas.segment") or n.startswith("pallas.fall")
                   for n in names), names
    counters = obs.snapshot()["counters"]
    assert counters["pallas.lowerings"] == 1
    out2 = fn(feeds)
    assert "pallas.profile" not in [s.name for s in obs.tracer.spans()]
    for k in out1:
        np.testing.assert_array_equal(np.asarray(out1[k]),
                                      np.asarray(out2[k]))


def test_engine_request_spans_and_queue_histogram():
    obs.enable()
    model = braggnn.build(1, 9)
    params = model.init_params(jax.random.key(0))
    design = hls.Session().compile(model.bind(params), name="obs_engine")
    eng = design.engine(max_batch=4)
    rng = np.random.default_rng(0)
    xs = [rng.normal(0, 0.25, (1, 1, 9, 9)).astype(np.float32)
          for _ in range(6)]
    reqs = [eng.submit(x) for x in xs]
    eng.run_until_drained()
    for r in reqs:
        r.wait(timeout=30)
    spans = obs.tracer.spans()
    req_spans = [s for s in spans if s.name == "serve.request"]
    assert len(req_spans) == 6
    assert all(s.kind == "async" for s in req_spans)
    assert {s.attrs["rid"] for s in req_spans} == {r.rid for r in reqs}
    assert any(s.name == "serve.dispatch" for s in spans)
    snap = obs.snapshot()
    assert snap["counters"]["serve.requests_completed"] == 6
    assert snap["histograms"]["serve.queue_depth"]["count"] > 0
    assert snap["histograms"]["serve.batch_occupancy"]["count"] >= 2


def test_concurrent_engine_submissions_keep_spans_consistent():
    """Satellite: span nesting stays consistent when many threads submit
    to a live threaded engine at once."""
    obs.enable()
    model = braggnn.build(1, 9)
    params = model.init_params(jax.random.key(0))
    design = hls.Session().compile(model.bind(params), name="obs_threads")
    eng = design.engine(max_batch=4, max_delay_ms=1.0)
    rng = np.random.default_rng(0)
    xs = [rng.normal(0, 0.25, (1, 1, 9, 9)).astype(np.float32)
          for _ in range(12)]
    reqs: list = []
    lock = threading.Lock()

    def submit(chunk):
        for x in chunk:
            r = eng.submit(x)
            with lock:
                reqs.append(r)

    with eng:
        threads = [threading.Thread(target=submit, args=(xs[i::3],))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for r in reqs:
            r.wait(timeout=30)
    spans = obs.tracer.spans()
    req_spans = [s for s in spans if s.name == "serve.request"]
    assert len(req_spans) == 12
    assert len({s.attrs["rid"] for s in req_spans}) == 12
    # dispatch spans all live on the engine loop thread, correctly closed
    for s in spans:
        if s.name == "serve.dispatch":
            assert s.t1 >= s.t0
    rep = eng.report()
    assert rep.completed == 12 and rep.dropped == 0


def test_design_report_mentions_obs_when_enabled():
    obs.enable()
    design = hls.Session().compile(_conv_build, name="obs_report")
    text = design.report()
    assert "obs" in text and "spans recorded" in text
    obs.disable()
    assert "spans recorded" not in design.report()
