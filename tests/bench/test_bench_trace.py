"""The reduction from a chip trace to per-layer metrics and the breakdown.

The two traces under ``data/`` were recorded on a TPU v5 lite by traced
runs of ``braggnn-s1.trigger`` (0.3 s window, windows of 4 frames) and
``braggnn-s1.offline`` (0.3 s window, batches of 4096), reduced by
``devtrace.Trace.from_profile`` and kept as JSON.  The expected numbers
are written out from those files.
"""

import json
from pathlib import Path

import pytest

from bench import devtrace, readers, spec
from bench.models import braggnn as bm

DATA = Path(__file__).parent / "data"
CFG = json.loads((spec.BENCH / "configs" / "braggnn-s1.json").read_text())
PEAKS = spec.peaks("TPU v5 lite")


def view(name, **records):
    tr = devtrace.load_json(DATA / f"{name}.trace.json.gz")
    return devtrace.RunView(cell=None, cfg=CFG, traffic=None, model=bm,
                            trace=tr, records=records, setup_parts={},
                            peaks=PEAKS)


def test_trigger_trace():
    v = view("braggnn-s1.trigger", batch=4)
    tr = v.trace
    assert tr.window_s() == pytest.approx(0.30686528)
    assert tr.busy_s() == pytest.approx(0.002267971)
    assert readers.idle_pct(v) == pytest.approx(99.26092290401833)
    assert len(tr.spans("bench.call")) == 62
    # 62 calls x 7 conv layers, 1 softmax, 4 dense layers
    assert len(tr.kernel_events("conv2d_vmem")) == 62 * 7
    assert len(tr.kernel_events("fused_softmax")) == 62
    assert len(tr.kernel_events("smallfloat_matmul")) == 62 * 4
    assert readers.host_us_per_call(v) == pytest.approx(2578.3822580645165)
    # at batch 4: 62 calls of the conv layers' least time over 195877 ns
    least = sum((4 * ly["act_bytes"] + ly["weight_bytes"]) / 819e9
                for ly in bm.layers(CFG) if ly["kernel"] == "conv2d_vmem")
    assert readers.kernel_roofline_pct(v, "conv2d_vmem") == pytest.approx(
        100 * 62 * least / 195877e-9)
    assert readers.kernel_roofline_pct(v, "conv2d_vmem") == pytest.approx(
        7.307518735608812)
    bd = tr.breakdown()
    assert bd["device_ops"][0] == ["concatenate.4 f32[4,8,9,5,5]",
                                   pytest.approx(0.000143548)]
    assert bd["idle_gaps"][0] == ["outside the program's calls",
                                  pytest.approx(0.007963247)]
    assert len(bd["device_ops"]) == len(bd["idle_gaps"]) == 10


def test_offline_trace():
    v = view("braggnn-s1.offline", batch=4096, samples=32768, window_s=0.3)
    tr = v.trace
    assert tr.window_s() == pytest.approx(0.391639243)
    assert tr.busy_s() == pytest.approx(0.284934376)
    assert readers.idle_pct(v) == pytest.approx(27.245703515977837)
    assert readers.host_us_per_call(v) == pytest.approx(2278.32575)
    conv = sum(e[4] for e in tr.kernel_events("conv2d_vmem"))
    assert conv == 30581143.0
    # least time of the 7 conv layers at batch 4096, by hand: each is
    # bandwidth-bound (f32 input + output + weights over 819 GB/s)
    least = sum(max(2 * 4096 * ly["macs"] / 197e12,
                    (4096 * ly["act_bytes"] + ly["weight_bytes"]) / 819e9)
                for ly in bm.layers(CFG) if ly["kernel"] == "conv2d_vmem")
    assert readers.kernel_roofline_pct(v, "conv2d_vmem") == pytest.approx(
        100 * 8 * least / (conv * 1e-9))
    assert readers.kernel_roofline_pct(v, "conv2d_vmem") == pytest.approx(
        5.925873003477002)
    # the softmax reads and writes 4096 x 81 x 81 floats per call
    assert readers.kernel_roofline_pct(v, "fused_softmax") == pytest.approx(
        100 * 8 * (4096 * 4 * 2 * 81 * 81 / 819e9) / 6718405e-9)
    assert readers.mfu_pct(v) == pytest.approx(
        100 * 438256 * (32768 / 0.3) / 197e12)
    bd = tr.breakdown()
    assert bd["device_ops"][0] == ["fusion.2 f32[4096,8,5,5]",
                                   pytest.approx(0.029210381)]
    assert bd["idle_gaps"][1] == ["bench.call", pytest.approx(0.005108236)]


def test_a_reader_without_a_reading_returns_none():
    v = view("braggnn-s1.trigger")
    assert readers.kernel_roofline_pct(v, "conv2d_vmem") is None  # no batch
    assert readers.mfu_pct(v) is None
    v.trace = None
    assert readers.idle_pct(v) is None
    assert readers.host_us_per_call(v) is None
    assert spec.reader("trigger.window_us").read(v) is None
    assert spec.reader("engine.batch_fill").read(v) is None


def test_json_round_trip(tmp_path):
    tr = devtrace.load_json(DATA / "braggnn-s1.offline.trace.json.gz")
    devtrace.save_json(tr, tmp_path / "t.json.gz")
    again = devtrace.load_json(tmp_path / "t.json.gz")
    assert again.ops == tr.ops and again.window == tr.window
