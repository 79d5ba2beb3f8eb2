"""A whole benchmark run on the CPU, with the timed path sound, broken, or
replaced by the control; ``correct`` must come out true only when sound.

The look for a chip is skipped and the cells' traffic is cut to sizes a
test run holds (smaller pools, batches and rates); everything else is the
run the chip makes: set-up, window, reference and comparison.  The faults
are planted where the answers are produced, in the Pallas callable the
design hands to every entry:

* ``answer_altered``: the first answer of every call is moved by 0.5;
* ``half_batch``: the second half of every call's answers is left out and
  replaced by the mean of the first half;
* ``control``: the reference one precision step lower (``high`` for fp32,
  FloPoCo (5,3) for (5,4)) answers in the program's place.

An inference cell keeps no state from call to call and runs on one chip,
so the faults of a step that leaves its state unchanged and of a missing
exchange between chips do not apply.
"""

import numpy as np
import pytest

import repro.core.emit_pallas as emit_pallas
import repro.hls as hls
from bench import run as bench_run, spec
from bench.models import braggnn as bm

#: traffic cut for the CPU: the same entries and shapes of traffic, less of it
SMALL = {"trigger": {"pool_frames": 256, "frame_rate_hz": 200.0,
                     "calibration_frames": 16},
         "offline": {"batch": 128, "pool_batches": 2},
         "engine": {"pool_frames": 256, "base_rate": 150.0,
                    "burst_rate": 560.0}}


#: one seed for every run, so that one compiled design serves each config
SEED = 2 ** 36 + 11


@pytest.fixture(scope="module")
def designs():
    return {}


@pytest.fixture
def harness(monkeypatch, tmp_path, designs):
    compile_ = hls.compile

    def compile_once(module, *, name, cache):
        if name not in designs:
            designs[name] = compile_(module, name=name,
                                     cache=tmp_path / "designs")
        return designs[name]

    monkeypatch.setattr(hls, "compile", compile_once)
    monkeypatch.setattr(bench_run, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(bench_run, "enable_caches", lambda: None)
    traffic = spec.traffic
    monkeypatch.setattr(spec, "traffic", lambda cell: dict(
        traffic(cell), **SMALL[traffic(cell)["entry"]]))
    made = {}
    make_params = bm.make_params

    def keep_params(cfg, seed):
        made["params"] = make_params(cfg, seed)
        made["cfg"] = cfg
        return made["params"]

    monkeypatch.setattr(bm, "make_params", keep_params)

    def plant(kind):
        if kind is None:
            return
        to_pallas_fn = emit_pallas.to_pallas_fn

        def broken(*args, **kw):
            fn = to_pallas_fn(*args, **kw)
            in_name = kw["module"].input_name

            def call(feeds):
                ((key, val),) = fn(feeds).items()
                v = np.array(val)
                n = len(v)
                if kind == "answer_altered":
                    v[0] += 0.5
                elif kind == "half_batch":
                    h = n // 2
                    if h:
                        v[n - h:] = v[:n - h].mean(axis=0)
                elif kind == "control":
                    x = np.asarray(feeds[in_name], np.float32)
                    v = bm.reference(made["params"], x, made["cfg"],
                                     control=True).reshape(v.shape)
                return {key: v}

            call.plan = fn.plan
            return call

        monkeypatch.setattr(emit_pallas, "to_pallas_fn", broken)

    def go(cell, kind=None):
        plant(kind)
        return bench_run.run(bench_run.parse(
            ["--workload", cell, "--seed", str(SEED), "--seconds", "0.5"]),
            require_tpu=False)

    return go


@pytest.mark.parametrize("cell", ["braggnn-s1.trigger", "braggnn-s1.offline",
                                  "braggnn-s1.engine",
                                  "braggnn-s1-q54.offline"])
def test_sound_run_is_correct(harness, cell):
    out = harness(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) >= {"setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("kind", ["answer_altered", "half_batch", "control"])
@pytest.mark.parametrize("cell", ["braggnn-s1.trigger", "braggnn-s1.offline",
                                  "braggnn-s1.engine",
                                  "braggnn-s1-q54.offline"])
def test_broken_run_is_not_correct(harness, cell, kind):
    out = harness(cell, kind)
    assert not out["correct"], out["checks"]
