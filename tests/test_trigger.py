"""repro.trigger: part catalog, budget checks, and the streaming loop.

The subsystem acceptance criteria: structured pass/fail budget reports on
both sides (a feasible design vs the deployment part, a capped synthetic
part failing with *named* resources), drop-oldest ring overrun, seeded
feed determinism with pileup bursts, bit-identical accept/reject
decisions across same-seed runs, deadline accounting, and the per-window
obs spans/counters.
"""

import time

import jax
import numpy as np
import pytest

import repro.hls as hls
from repro import obs, trigger
from repro.models import braggnn
from repro.serving.common import DropOldestRing

IMG = 7


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def design():
    """One small bound BraggNN design shared by the loop tests."""
    model = braggnn.build(1, IMG)
    params = model.init_params(jax.random.key(0))
    return hls.Session().compile(model.bind(params), name="braggnn_trig")


# -- parts -------------------------------------------------------------------


def test_part_caps_speak_schedule_vocabulary():
    caps = trigger.alveo_u280.caps()
    assert caps["DSP"] == 9024
    assert caps["BRAM_ports"] == 2 * 2016          # ports, not blocks
    assert set(caps) <= {"DSP", "FF", "BRAM_ports", "LUT_units"}
    assert trigger.zcu102.caps()["DSP"] == 2520
    # synthetic parts constrain only what they name
    assert trigger.part(dsp=16).caps() == {"DSP": 16}


def test_get_part_resolves_and_rejects():
    assert trigger.get_part("alveo_u280") is trigger.alveo_u280
    assert trigger.get_part(None) is None
    p = trigger.part(dsp=4, name="tiny")
    assert trigger.get_part(p) is p
    with pytest.raises(KeyError, match="unknown part"):
        trigger.get_part("virtex_2000")


# -- budgets -----------------------------------------------------------------


def test_budget_caps_merge_and_margin_validation():
    b = trigger.TriggerBudget(part="zcu102", max_dsp=100)
    caps = b.resource_caps()
    assert caps["DSP"] == 100                      # explicit beats the part
    assert caps["FF"] == trigger.zcu102.caps()["FF"]
    with pytest.raises(ValueError, match="margin"):
        trigger.TriggerBudget(margin=1.0)
    with pytest.raises(KeyError, match="unknown part"):
        trigger.TriggerBudget(part="nope")         # typo fails eagerly
    # key() is a stable identity for tuning-context hashing
    assert b.key() == trigger.TriggerBudget(part="zcu102", max_dsp=100).key()
    assert b.key() != trigger.TriggerBudget(part="zcu102").key()


def test_check_design_both_sides(design):
    ok = design.check_budget(part="alveo_u280")
    assert ok.passed and ok.failures == []
    assert ok.check("DSP").used == design.schedule.resources()["DSP"]
    assert "PASS" in ok.summary()
    assert ok.raise_if_failed() is ok

    bad = design.check_budget(part=trigger.part(dsp=16))
    assert not bad.passed
    assert bad.failures == ["DSP"]                 # named offender
    assert "FAIL" in bad.summary() and "DSP" in bad.summary()
    with pytest.raises(trigger.BudgetError, match="DSP"):
        bad.raise_if_failed()
    j = bad.to_json()
    assert j["passed"] is False and j["failures"] == ["DSP"]


def test_budget_latency_ii_and_margin(design):
    lat = design.sample_latency_us
    tight = trigger.TriggerBudget(max_latency_us=lat / 2)
    rep = design.check_budget(tight)
    assert rep.failures == ["latency_us"]
    loose = trigger.TriggerBudget(max_latency_us=lat * 2, max_ii=10 ** 9)
    assert design.check_budget(loose).passed

    # margin shrinks resource caps: exactly-at-cap fails with headroom
    dsp = design.schedule.resources()["DSP"]
    at_cap = trigger.TriggerBudget(part=trigger.part(dsp=dsp))
    assert design.check_budget(at_cap).passed
    with_headroom = trigger.TriggerBudget(part=trigger.part(dsp=dsp),
                                          margin=0.1)
    assert design.check_budget(with_headroom).failures == ["DSP"]


def test_check_budget_requires_an_envelope(design):
    with pytest.raises(ValueError, match="TriggerBudget"):
        design.check_budget()


def test_report_budget_section_and_summary_latency(design):
    assert "us/sample" in design.summary()         # surfaced, not buried
    rep = design.report(part="alveo_u280")
    assert "budget check [PASS]" in rep
    rep2 = design.report(part=trigger.part(dsp=1))
    assert "FAIL" in rep2 and "DSP" in rep2


# -- the ring ----------------------------------------------------------------


def test_ring_drop_oldest_overrun():
    ring = DropOldestRing(3)
    assert [ring.push(i) for i in range(3)] == [None, None, None]
    assert ring.push(3) == 0                       # oldest evicted, returned
    assert ring.push(4) == 1
    assert ring.dropped == 2 and ring.pushed == 5
    assert ring.pop_many(10) == [2, 3, 4]          # survivors oldest-first
    assert ring.pop() is None
    with pytest.raises(ValueError, match="capacity"):
        DropOldestRing(0)


def test_ring_drops_count_in_obs():
    obs.enable()
    ring = DropOldestRing(1)
    ring.push("a")
    ring.push("b")
    assert obs.snapshot()["counters"]["trigger.dropped_frames"] == 1.0


# -- the feed ----------------------------------------------------------------


def test_feed_deterministic_and_pileup_bursts():
    mk = lambda: trigger.DetectorFeed(img=IMG, seed=5, event_rate=0.5,
                                      pileup_every=10, pileup_len=3,
                                      pileup_peaks=4)
    a, b = list(mk().frames(25)), list(mk().frames(25))
    assert all(np.array_equal(x.data, y.data) for x, y in zip(a, b))
    assert [f.n_peaks for f in a] == [f.n_peaks for f in b]
    # bursts: frames 0-2, 10-12, 20-22 carry pileup_peaks each
    for i in (0, 1, 2, 10, 11, 12, 20, 21, 22):
        assert a[i].n_peaks == 4
    # outside the bursts the event rate is Bernoulli 0/1
    assert set(f.n_peaks for f in a[3:10]) <= {0, 1}
    assert a[0].data.shape == (1, 1, IMG, IMG)
    assert a[0].data.dtype == np.float32
    # arrival schedule follows the configured rate
    assert a[2].t_sched == pytest.approx(2 / mk().frame_rate_hz)


# -- the loop ----------------------------------------------------------------


def test_loop_decisions_bit_identical_across_runs(design):
    def once():
        loop = design.trigger(window=4)
        loop.calibrate(trigger.DetectorFeed(img=IMG, seed=9), 32)
        rep = loop.run(trigger.DetectorFeed(img=IMG, seed=9), 50)
        return loop.threshold, rep

    th1, r1 = once()
    th2, r2 = once()
    assert th1 == th2
    assert r1.processed == r1.frames == 50
    assert r1.dropped == 0                         # deterministic mode
    assert 0 < r1.accepts < 50                     # calibrated split
    assert [(d.frame_id, d.accept, d.score) for d in r1.decisions] == \
           [(d.frame_id, d.accept, d.score) for d in r2.decisions]
    # every frame decided exactly once, in order
    assert [d.frame_id for d in r1.decisions] == list(range(50))


def test_loop_partial_window_padding(design):
    loop = design.trigger(window=8, threshold=0.0)
    rep = loop.run(trigger.DetectorFeed(img=IMG, seed=1), 10)
    assert rep.processed == 10                     # 8 + padded 2
    assert rep.windows == 2
    assert all(d.frame_id >= 0 for d in rep.decisions)


def test_loop_deadline_accounting(design):
    # an impossible deadline: every decision late, slack negative
    tight = trigger.TriggerBudget(max_latency_us=1e-3)
    rep = design.trigger(window=4, budget=tight).run(
        trigger.DetectorFeed(img=IMG, seed=2), 12)
    assert rep.deadline_misses == rep.processed == 12
    assert rep.miss_pct == 100.0
    assert all(not d.deadline_met and d.slack_us < 0 for d in rep.decisions)
    assert "missed" in rep.summary()

    # a generous one: all met, slack positive
    loose = trigger.TriggerBudget(max_latency_us=60e6)
    rep2 = design.trigger(window=4, budget=loose).run(
        trigger.DetectorFeed(img=IMG, seed=2), 12)
    assert rep2.deadline_misses == 0
    assert all(d.deadline_met and d.slack_us > 0 for d in rep2.decisions)


def test_loop_realtime_overrun_drops_oldest(design):
    # a predicate 10x slower than the feed with a tiny ring: the loop
    # must lose (old) frames, never stall the producer
    slow = trigger.threshold_predicate(0.5)

    def slow_predicate(out):
        time.sleep(0.02)
        return slow(out)

    loop = design.trigger(window=2, capacity=4,
                          predicate=slow_predicate)
    rep = loop.run(trigger.DetectorFeed(img=IMG, frame_rate_hz=2000,
                                        seed=3), 60, realtime=True)
    assert rep.realtime
    assert rep.dropped > 0
    assert rep.processed + rep.dropped == rep.frames == 60
    assert rep.drop_pct > 0
    # survivors decided in arrival order
    ids = [d.frame_id for d in rep.decisions]
    assert ids == sorted(ids)


def test_loop_realtime_sustains_modest_rate(design):
    budget = trigger.TriggerBudget(max_latency_us=2e6)
    loop = design.trigger(window=4, budget=budget)
    rep = loop.run(trigger.DetectorFeed(img=IMG, frame_rate_hz=200,
                                        seed=4), 60, realtime=True)
    assert rep.dropped == 0
    assert rep.deadline_misses == 0
    assert rep.processed == 60
    assert rep.sustained_fps > 100                 # kept pace with the feed
    assert rep.p99_us >= rep.p50_us > 0


def test_loop_window_spans_and_counters(design):
    obs.enable()
    loop = design.trigger(window=4,
                          budget=trigger.TriggerBudget(max_latency_us=1e-3))
    rep = loop.run(trigger.DetectorFeed(img=IMG, seed=6), 16)
    spans = [s for s in obs.tracer.spans() if s.name == "trigger.window"]
    assert len(spans) == rep.windows == 4
    assert all(s.attrs["frames"] == 4 for s in spans)
    assert {s.attrs["window"] for s in spans} == {0, 1, 2, 3}
    counters = obs.snapshot()["counters"]
    assert counters["trigger.windows"] == 4.0
    assert counters["trigger.deadline_misses"] == 16.0
    assert counters["trigger.accepts"] + counters["trigger.rejects"] == 16.0


def test_loop_rejects_bad_window(design):
    with pytest.raises(ValueError, match="window"):
        design.trigger(window=0)


def test_calibrate_refuses_custom_predicate(design):
    loop = design.trigger(predicate=trigger.threshold_predicate(0.1))
    with pytest.raises(ValueError, match="custom predicate"):
        loop.calibrate(trigger.DetectorFeed(img=IMG), 8)
