"""The readers of the program's own spans, on two traces recorded on the chip.

The traces under ``data/`` named ``*.spans.trace.json.gz`` were recorded
on a TPU v5 lite by traced runs of ``braggnn-s1.trigger`` (0.3 s window,
windows of 4 frames) and ``braggnn-s1.engine`` (0.22 s window), reduced
by ``devtrace.Trace.from_profile`` and kept as JSON.  While the profiler
captured, the program's ``repro.obs`` spans were profiler annotations, so
``nest.call``, ``nest.feeds``, ``nest.weights``, ``nest.launch`` and
``trigger.wait`` sit in them beside the benchmark's ``bench.call``.  The
expected numbers are written out from those files.
"""

from pathlib import Path

import pytest

from bench import devtrace, readers, spec, spanreaders

DATA = Path(__file__).parent / "data"
NEST = ("nest.call", "nest.feeds", "nest.weights", "nest.launch")


def view(name):
    return devtrace.RunView(cell=None, cfg=None, traffic=None, model=None,
                            trace=devtrace.load_json(DATA / name),
                            records={}, setup_parts={}, peaks=None)


def read(metric, v):
    return spec.reader(metric).read(v)


def test_trigger_spans():
    v = view("braggnn-s1.trigger.spans.trace.json.gz")
    tr = v.trace
    assert tr.window_s() == pytest.approx(0.303670335)
    assert tr.busy_s() == pytest.approx(0.002260237)
    # one of each span per window of 4 frames, and one bench.call inside
    for name in NEST + ("bench.call", "trigger.wait", "trigger.window"):
        assert len(tr.spans(name)) == 62, name
    # (9,806,593 + 3,731,989 ns of feeds and weights) over 62 calls
    assert read("nest.prep_us.trigger", v) == pytest.approx(
        (9806593 + 3731989) / 62 / 1e3)
    assert read("nest.prep_us.trigger", v) == pytest.approx(218.36422580645163)
    assert read("nest.launch_us.trigger", v) == pytest.approx(
        146848117 / 62 / 1e3)
    # every device op of the window falls inside a nest.call span
    assert read("device_idle.nest.trigger", v) == pytest.approx(
        100 * (162978688e-9 - 0.002260237) / 0.303670335)
    assert read("device_idle.nest.trigger", v) == pytest.approx(
        52.925305002215644)
    assert read("trigger.wait_us", v) == pytest.approx(75993678 / 62 / 1e3)
    assert read("trigger.wait_us", v) == pytest.approx(1225.7044838709678)
    # the launch and the weights account for the bench.call host time
    assert readers.host_us_per_call(v) == pytest.approx(2423.0339032258066)
    inside = (146848117 + 3731989) / 62 / 1e3
    assert inside / readers.host_us_per_call(v) == pytest.approx(
        1.0, abs=0.01)


def test_engine_spans():
    v = view("braggnn-s1.engine.spans.trace.json.gz")
    tr = v.trace
    assert tr.window_s() == pytest.approx(0.222870416)
    assert tr.busy_s() == pytest.approx(0.004838894)
    for name in NEST + ("bench.call", "serve.dispatch"):
        assert len(tr.spans(name)) == 45, name
    assert not tr.spans("trigger.wait")
    assert read("nest.prep_us.engine", v) == pytest.approx(
        (8908581 + 2676211) / 45 / 1e3)
    assert read("nest.prep_us.engine", v) == pytest.approx(257.43982222222223)
    assert read("nest.launch_us.engine", v) == pytest.approx(
        2910.606288888889)
    assert read("device_idle.nest.engine", v) == pytest.approx(
        100 * (143476931e-9 - 0.004838894) / 0.222870416)
    assert read("device_idle.nest.engine", v) == pytest.approx(
        62.205670670978606)


@pytest.mark.parametrize("kind", ["prep_us", "launch_us"])
def test_offline_readers_read_as_the_others(kind):
    """The three cells' readers of one quantity are one function."""
    v = view("braggnn-s1.engine.spans.trace.json.gz")
    value = read(f"nest.{kind}.engine", v)
    assert read(f"nest.{kind}.offline", v) == value
    assert read(f"nest.{kind}.trigger", v) == value
    idle = read("device_idle.nest.engine", v)
    assert read("device_idle.nest.offline", v) == idle
    assert read("device_idle.nest.trigger", v) == idle


def test_span_readers_without_their_spans_return_none():
    # recorded before the program had these spans: each reader leaves its
    # metric out, as it does on a parent commit
    for name in ("braggnn-s1.trigger.trace.json.gz",
                 "braggnn-s1.offline.trace.json.gz"):
        v = view(name)
        assert v.trace.spans("bench.call")
        for metric in ("nest.prep_us.trigger", "nest.launch_us.offline",
                       "device_idle.nest.engine", "trigger.wait_us"):
            assert read(metric, v) is None, (name, metric)
    v.trace = None
    assert spanreaders.prep_us_per_call(v) is None
    assert spanreaders.mean_us(v, "nest.launch") is None
    assert spanreaders.idle_in_calls_pct(v) is None
