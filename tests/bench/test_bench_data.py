"""The benchmark's data: every cell resolves by name, names and units keep to
the allowed characters, the copied generators repeat, and the operation and
byte counts match counts made by hand for BraggNN(s=1, img=11)."""

import json
import re

import numpy as np
import pytest

from bench import feed, spec
from bench.models import braggnn as bm

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_run_budget():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench", "tests/bench"]
    # a full check of 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_and_units():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_resolves_by_name(cell_name):
    cell = spec.cell(BENCH, cell_name)
    cfg = spec.config(BENCH, cell)
    traffic = spec.traffic(cell)
    assert spec.model(cfg["model"]).reference
    assert spec.driver(traffic["entry"]).run
    e2e = {m["name"] for m in spec.e2e_metrics(BENCH, cell_name)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.per_layer_metrics(BENCH, cell_name)
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], cell_name)
        assert callable(spec.reader(m["name"]).read)


def test_config_files_state_what_runs():
    for entry in BENCH["configs"]:
        cfg = json.loads((spec.ROOT / entry["file"]).read_text())
        assert entry["file"].startswith("bench/configs/")
        assert entry["reduced"] == cfg["reduced"] == []
        assert cfg["limits"]["out_rel_err"] is not None
        assert cfg["control"]


def test_detector_feed_repeats_and_matches_the_program():
    from repro.trigger import DetectorFeed as ProgramFeed
    a = feed.DetectorFeed(seed=2 ** 40 + 3).render(120)
    b = feed.DetectorFeed(seed=2 ** 40 + 3).render(120)
    c = feed.DetectorFeed(seed=2 ** 40 + 4).render(120)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    prog = np.stack([f.data for f in
                     ProgramFeed(seed=2 ** 40 + 3).frames(120)])
    assert np.array_equal(a, prog)


def test_frames_are_handed_out_on_the_feed_clock():
    f = feed.DetectorFeed(frame_rate_hz=2000.0, seed=5)
    f.render(16)
    frames = list(f.frames(40))
    assert [fr.frame_id for fr in frames] == list(range(40))
    assert frames[17].t_sched == pytest.approx(17 / 2000.0)
    assert np.array_equal(frames[17].data, frames[1].data)   # cycled pool


def test_bursty_schedule_repeats_and_orders_one_set_of_gaps():
    args = (600, 1000.0, 3750.0, 60, 20)
    a = feed.bursty_schedule(*args, seed=7)
    b = feed.bursty_schedule(*args, seed=7)
    c = feed.bursty_schedule(*args, seed=2 ** 33 + 1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0)
    # every seed offers the same gaps, in another order
    assert np.allclose(np.sort(np.diff(a, prepend=0.0)),
                       np.sort(np.diff(c, prepend=0.0)))
    assert a[-1] == pytest.approx(c[-1])
    # burst requests come at the burst rate, the rest at the base rate
    in_burst = (np.arange(600) % 60) < 20
    gaps = np.diff(a, prepend=0.0)
    assert gaps[in_burst].mean() == pytest.approx(1 / 3750.0, rel=0.05)
    assert gaps[~in_burst].mean() == pytest.approx(1 / 1000.0, rel=0.05)


def test_operations_and_bytes_by_hand():
    cfg = {"s": 1, "img": 11}
    by = {ly["name"]: ly for ly in bm.layers(cfg)}
    # conv1: 9x9 outputs x 16 channels x 1x3x3 taps
    assert by["conv1"]["macs"] == 81 * 16 * 9 == 11664
    assert by["conv1"]["act_bytes"] == 4 * (121 + 16 * 81) == 5668
    assert by["conv1"]["weight_bytes"] == 4 * (144 + 16) == 640
    # theta/phi/g and out: 1x1 convolutions at 9x9, 16 <-> 8 channels
    for name in ("theta", "phi", "g", "out"):
        assert by[name]["macs"] == 81 * 8 * 16 == 10368
        assert by[name]["act_bytes"] == 4 * (16 * 81 + 8 * 81) == 7776
    # attention: 81x81 scores over 8 channels, twice
    assert by["scores"]["macs"] == by["mix"]["macs"] == 81 * 81 * 8
    assert by["softmax"]["act_bytes"] == 4 * 2 * 81 * 81
    # conv2a: 7x7 outputs x 8 channels x 16x3x3; conv2b: 5x5 x 2 x 8x3x3
    assert by["conv2a"]["macs"] == 49 * 8 * 144 == 56448
    assert by["conv2b"]["macs"] == 25 * 2 * 72 == 3600
    assert [by[f"dense{i}"]["macs"] for i in range(4)] == [800, 128, 32, 8]
    assert by["dense0"]["weight_bytes"] == 4 * (50 * 16 + 16)
    assert bm.model_flops(cfg) == 2 * (11664 + 4 * 10368 + 2 * 52488
                                       + 56448 + 3600 + 968) == 438256
    kernels = [ly["kernel"] for ly in bm.layers(cfg)]
    assert kernels.count("conv2d_vmem") == 7
    assert kernels.count("smallfloat_matmul") == 4
    assert kernels.count("fused_softmax") == 1


def test_weights_fit_the_programs_model_and_repeat():
    from repro.models import braggnn
    import jax
    cfg = json.loads((spec.BENCH / "configs" / "braggnn-s1.json").read_text())
    p = bm.make_params(cfg, 2 ** 40 + 9)
    q = bm.make_params(cfg, 2 ** 40 + 9)
    want = jax.tree_util.tree_map(lambda s: s.shape, braggnn.specs(1, 11))
    got = jax.tree_util.tree_map(lambda a: a.shape, p)
    assert got == want
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(q)))
