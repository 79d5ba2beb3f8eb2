"""repro.obs's second sink: spans in the JAX profiler's trace.

Kept in one file: a process runs one profiler session at a time.  While a
capture runs, ``obs.span`` opens a ``TraceAnnotation`` under the span's
name, so the nest tier's host path (``nest.call`` around ``nest.feeds``,
``nest.weights`` when the call feeds weights, and ``nest.launch``) lands on
the profile's host plane.
"""

import glob
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.hls as hls
from repro import obs, trigger
from repro.nn import graph as nng
from repro.obs.trace import NOOP_SPAN

IMG = 8
NEST = ("nest.call", "nest.feeds", "nest.weights", "nest.launch")
#: a call on the bound weights held on the device opens no ``nest.weights``
RESIDENT = ("nest.call", "nest.feeds", "nest.launch")


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def design():
    nodes = [nng.Conv2d("c1", in_channels=1, out_channels=2, kernel=3),
             nng.ReLU(name="r1"),
             nng.Flatten(name="fl"),
             nng.Linear("fc", in_features=2 * 6 * 6, out_features=3)]
    m = nng.ModuleGraph("obs_prof", (1, 1, IMG, IMG), nodes)
    m = m.bind(m.init_params(jax.random.key(0)))
    return hls.Session().compile(m, name="obs_prof")


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _host_events(logdir, names):
    """``{name: [(start_ns, end_ns, line)]}`` of the named events on the
    profile's host planes."""
    (path,) = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    found = {n: [] for n in names}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in found:
                    found[e.name].append(
                        (e.start_ns, e.start_ns + e.duration_ns, i))
    return found


def _inside(inner, outer):
    return (inner[2] == outer[2] and outer[0] <= inner[0]
            and inner[1] <= outer[1])


def test_nest_spans_land_in_the_profile(design, tmp_path):
    run_one, _, _ = design._runner(None, None)
    x = np.random.default_rng(0).normal(
        0, 0.5, (2, 1, IMG, IMG)).astype(np.float32)
    fed = design.feeds(x)                       # the weights with the call
    jax.block_until_ready(run_one(x))           # compile outside the capture
    jax.block_until_ready(run_one(fed))
    assert not obs.enabled()
    with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
        out = jax.block_until_ready(run_one(x))
        jax.block_until_ready(run_one(fed))
    assert not obs.tracer.spans()               # recording stayed off
    ev = _host_events(tmp_path, NEST + ("nest.trace",))
    assert [len(ev[n]) for n in NEST] == [2, 2, 1, 2]
    resident, with_weights = sorted(ev["nest.call"])
    for name in RESIDENT[1:]:
        first, second = sorted(ev[name])
        assert _inside(first, resident), name
        assert _inside(second, with_weights), name
    # only the call with fed weights prepares them, after its feeds and
    # before its launch
    (weights,) = ev["nest.weights"]
    assert _inside(weights, with_weights)
    assert sorted(ev["nest.feeds"])[1][1] <= weights[0] \
        <= weights[1] <= sorted(ev["nest.launch"])[1][0]
    assert not ev["nest.trace"]                 # warm: no retrace
    (name,) = out
    np.testing.assert_array_equal(np.asarray(out[name]),
                                  np.asarray(run_one(x)[name]))


def test_enabled_spans_reach_both_sinks_and_a_retrace_shows(design,
                                                            tmp_path):
    obs.enable()
    run_one, _, _ = design._runner(None, None)
    x = np.zeros((3, 1, IMG, IMG), np.float32)  # a new batch: traced anew
    with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
        jax.block_until_ready(run_one(x))
    names = [s.name for s in obs.tracer.spans()]
    for name in RESIDENT + ("nest.trace",):
        assert names.count(name) == 1, (name, names)
    assert "nest.weights" not in names          # the weights are resident
    ev = _host_events(tmp_path, NEST + ("nest.trace",))
    assert [len(ev[n]) for n in RESIDENT + ("nest.trace",)] == [1] * 4
    assert not ev["nest.weights"]
    assert _inside(ev["nest.trace"][0], ev["nest.launch"][0])
    by_name = {s.name: s for s in obs.tracer.spans()}
    assert by_name["nest.feeds"].parent_id == by_name["nest.call"].span_id


def test_disabled_and_not_capturing_is_the_shared_noop():
    assert not obs.enabled()
    sp = obs.span("nest.call", cat="pallas", batch=4)
    assert sp is NOOP_SPAN
    with sp as inner:
        assert inner.set(x=1) is NOOP_SPAN
    assert len(obs.tracer) == 0


def test_capturing_while_disabled_records_nothing_in_memory(tmp_path):
    with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
        sp = obs.span("bench.probe")
        assert sp is not NOOP_SPAN
        with sp as inner:
            inner.set(ignored=True)
    assert len(obs.tracer) == 0
    assert obs.span("bench.probe") is NOOP_SPAN
    assert len(_host_events(tmp_path, ("bench.probe",))["bench.probe"]) == 1


def test_obs_imports_without_jax():
    code = textwrap.dedent("""
        import sys
        sys.modules["jax"] = None       # any import of jax now fails
        from repro import obs
        obs.enable()
        with obs.span("a"):
            with obs.span("b"):
                pass
        assert [s.name for s in obs.tracer.spans()] == ["b", "a"]
        obs.disable()
        assert obs.span("c") is obs.NOOP_SPAN
        assert "jax" not in [m for m in sys.modules if sys.modules[m]]
        print("ok")
    """)
    src = Path(obs.__file__).parents[2]         # the directory of repro
    env = dict(os.environ, PYTHONPATH=str(src))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_realtime_trigger_records_one_wait_per_window(design):
    obs.enable()
    loop = design.trigger(window=4)
    rep = loop.run(trigger.DetectorFeed(img=IMG, frame_rate_hz=400, seed=2),
                   30, realtime=True)
    spans = obs.tracer.spans()
    waits = [s for s in spans if s.name == "trigger.wait"]
    windows = [s for s in spans if s.name == "trigger.window"]
    assert rep.windows == len(windows) == len(waits) == 8
    # each wait ends before its window starts, and starts after the last
    for i, (w, win) in enumerate(zip(waits, windows)):
        assert w.t1 <= win.t0
        if i:
            assert windows[i - 1].t1 <= w.t0
    # waits and windows together name nearly all of the run's time
    named = sum(s.dur_s for s in waits + windows)
    assert named >= 0.9 * rep.wall_s, (named, rep.wall_s)
