"""The comparison that decides ``correct``.

Every answer the window produced is compared with the plain reference run
over the same input.  ``out_rel_err`` is the largest gap between a program
output and the reference's, over the largest reference output magnitude;
``out_mean_rel_err`` is the mean gap over the mean reference magnitude,
which one rounding flip moves less.  A configuration compares those of
the two it gives a limit for (``limits``), each limit set from readings
of the program and of its control.  Answers that never came are counted
in ``missing``.  A driver may add exact checks of its own (limit 0), as
``result["exact"](ref_rows)``.
"""

from __future__ import annotations

import numpy as np


def rel_errs(got: np.ndarray, ref: np.ndarray) -> tuple[float, float]:
    """(largest gap over largest magnitude, mean gap over mean magnitude)."""
    got = np.asarray(got, np.float64).reshape(len(ref), -1)
    ref = np.asarray(ref, np.float64).reshape(len(ref), -1)
    if not len(ref):
        return float("inf"), float("inf")
    gap, mag = np.abs(got - ref), np.abs(ref)
    return (float(gap.max() / max(mag.max(), 1e-30)),
            float(gap.mean() / max(mag.mean(), 1e-30)))


def judge(cfg: dict, ref_rows: np.ndarray, result: dict) -> dict:
    """``{name: {"value": v, "limit": l}}`` for every number compared: the
    relative gaps the configuration gives a limit for, answers missing,
    and the driver's exact checks."""
    got = result["outputs"]
    ref = ref_rows[result["input_index"]]
    gaps = dict(zip(("out_rel_err", "out_mean_rel_err"), rel_errs(got, ref)))
    checks = {name: {"value": gaps[name], "limit": limit}
              for name, limit in cfg["limits"].items()}
    checks["missing"] = {"value": int(result["missing"]), "limit": 0}
    for name, value in result.get("exact", lambda _: {})(ref_rows).items():
        checks[name] = {"value": int(value), "limit": 0}
    return checks


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
