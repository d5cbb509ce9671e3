"""Plain reference for the stream-apps deployment, in NumPy on the host,
independent of the program under test. Each program kind's answer is
the ``reference`` function of its file, ``chipbench/programs/<kind>.py``,
which uses NumPy alone.

The float kinds are computed in float64, so the reference's own rounding
is far below float32's; the sort is exact. ``dtype`` computes them
instead in a lower precision, one rounding per operation and a sequential
running sum, as a program that switched precision would: the control the
check must fail.
"""
from __future__ import annotations

import numpy as np

from chipbench import harness


def answer(kind: str, operands: tuple, dtype=np.float64) -> tuple:
    """The reference answer, as a tuple of arrays."""
    return harness.load_module("programs", kind).reference(operands, dtype)


def compare(kind: str, got: tuple, want: tuple) -> float:
    """The number the check holds against its limit: for the float kinds
    the largest error over the largest reference magnitude (max |got -
    want| / max |want|, over every output); for exact kinds (int32 keys)
    the count of values out of place."""
    if harness.load_module("programs", kind).KEYS:
        return float(sum(int(np.sum(np.asarray(g) != w))
                         for g, w in zip(got, want)))
    err = 0.0
    for g, w in zip(got, want):
        w = np.asarray(w, np.float64)
        d = np.max(np.abs(np.asarray(g, np.float64) - w))
        err = max(err, float(d / max(np.max(np.abs(w)), 1e-300)))
    return err
