"""BraggNN at its original widths (s=4, img=11): the operation and byte
counts match counts made by hand, and the nest tier, through its Pallas
kernels in the interpreter, agrees with the plain reference on seeded
weights and detector frames.

The nest tier lowers from the bound module alone, so ``hls.compile`` (about
40 s at s=4) is not needed here."""

import json

import numpy as np
import pytest

from bench import check, spec
from bench.feed import DetectorFeed
from bench.models import braggnn as bm

CFG = json.loads((spec.BENCH / "configs" / "braggnn-s4.json").read_text())
SEED = 2 ** 40 + 11
BATCH = 8


def test_operations_and_bytes_by_hand():
    by = {ly["name"]: ly for ly in bm.layers(CFG)}
    # conv1: 9x9 outputs x 64 channels x 1x3x3 taps
    assert by["conv1"]["macs"] == 81 * 64 * 9 == 46656
    assert by["conv1"]["act_bytes"] == 4 * (121 + 64 * 81) == 21220
    assert by["conv1"]["weight_bytes"] == 4 * (576 + 64) == 2560
    # theta/phi/g and out: 1x1 convolutions at 9x9, 64 <-> 32 channels
    for name in ("theta", "phi", "g", "out"):
        assert by[name]["macs"] == 81 * 32 * 64 == 165888
        assert by[name]["act_bytes"] == 4 * (64 * 81 + 32 * 81) == 31104
        assert by[name]["weight_bytes"] == 4 * 32 * 64 == 8192
    # attention: the same 81x81 scores as at s=1, over 32 channels, twice
    assert by["scores"]["macs"] == by["mix"]["macs"] == 81 * 81 * 32
    assert by["scores"]["act_bytes"] == 4 * (2 * 32 * 81 + 81 * 81)
    assert by["softmax"]["act_bytes"] == 4 * 2 * 81 * 81
    # conv2a: 7x7 outputs x 32 channels x 64x3x3; conv2b: 5x5 x 8 x 32x3x3
    assert by["conv2a"]["macs"] == 49 * 32 * 576 == 903168
    assert by["conv2a"]["weight_bytes"] == 4 * (32 * 576 + 32)
    assert by["conv2b"]["macs"] == 25 * 8 * 288 == 57600
    # dense 200 -> 64 -> 32 -> 16 -> 2
    assert [by[f"dense{i}"]["macs"] for i in range(4)] == [12800, 2048,
                                                           512, 32]
    assert by["dense0"]["act_bytes"] == 4 * (200 + 64)
    assert by["dense0"]["weight_bytes"] == 4 * (200 + 1) * 64 == 51456
    assert bm.model_flops(CFG) == 2 * (46656 + 4 * 165888 + 2 * 209952
                                       + 903168 + 57600 + 15392) == 4212544
    kernels = [ly["kernel"] for ly in bm.layers(CFG)]
    assert kernels.count("conv2d_vmem") == 7
    assert kernels.count("smallfloat_matmul") == 4
    assert kernels.count("fused_softmax") == 1


@pytest.fixture(scope="module")
def case():
    params = bm.make_params(CFG, SEED)
    traffic = json.loads((spec.BENCH / "traffic" / "offline.json")
                         .read_text())
    x = DetectorFeed(img=CFG["img"], seed=SEED, **traffic["feed"]) \
        .render(BATCH)
    return params, bm.build_module(CFG, params), x


@pytest.mark.parametrize("fmt", [None, (5, 4)], ids=["fp32", "5_4"])
def test_nest_tier_matches_reference(case, fmt):
    from repro.core.emit_pallas import to_pallas_fn
    params, module, x = case
    fn = to_pallas_fn(None, module=module, mode="nests", use_pallas=True,
                      fmt=f"{fmt[0]}_{fmt[1]}" if fmt else None)
    assert fn.plan.interpret and not fn.plan.fallbacks
    assert fn.plan.blocks["dense0"].tag == "wholek"
    (out,) = fn({module.input_name: x}).values()
    got = np.asarray(out).reshape(BATCH, -1)
    ref = np.asarray(bm.forward(params, x, fmt=fmt, precision="highest",
                                taylor_order=CFG["taylor_order"]))
    assert np.abs(ref).max() > 0.5
    worst, mean = check.rel_errs(got, ref)
    if fmt is None:
        # fp32 with K up to 576: only the summation order differs from the
        # reference's (about 1e-6 of the largest output here); the
        # three-pass bfloat16 control misses by more, so the tolerance
        # still tells the precision apart
        ctl = np.asarray(bm.forward(params, x, precision="high",
                                    taylor_order=CFG["taylor_order"]))
        assert worst <= 4e-6
        assert check.rel_errs(ctl, ref)[0] > 4e-6
    else:
        # (5,4): every layer's result is rounded to 4 fraction bits, so a
        # sum that lands on another side of a rounding boundary moves an
        # output by up to 1/30 of the largest (the benchmark's (5,4)
        # limits, which such flips stay inside); the mean moves far less
        assert worst <= 0.09
        assert mean <= 1e-3
