"""Comparison of a frame the engine returned with the plain reference's.

Adapted from ``tests/compare.py`` (``_normalize`` and
``assert_frames_equal``, PR 24), not imported: that file imports the
engine, and the comparison that decides ``correct`` must not change when a
later PR edits it. What differs: rows are compared in the order given (the
statements of the mixes order their rows or return one), and the result is
numbers with limits, not an assertion: ``mismatches`` counts everything
that has to be exact (column names, row count, NULLs, strings, integers,
NaN placement) and ``max_rel_err`` is the widest relative gap of a float
cell, |got - want| / max(|want|, 1).
"""
from __future__ import annotations

import numpy as np
import pandas as pd


def _cell(v):
    if v is None or v is pd.NA or v is pd.NaT:
        return None
    if isinstance(v, (bool, np.bool_)):
        return bool(v)
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, (float, np.floating)):
        return float(v)
    return str(v)


def compare_frames(want: pd.DataFrame, got: pd.DataFrame) -> dict:
    """{"mismatches": int, "max_rel_err": float, "first": str}."""
    if list(want.columns) != list(got.columns):
        return {"mismatches": 1, "max_rel_err": 0.0,
                "first": f"columns {list(got.columns)} != "
                         f"{list(want.columns)}"}
    if len(want) != len(got):
        return {"mismatches": 1, "max_rel_err": 0.0,
                "first": f"rows {len(got)} != {len(want)}"}
    bad, worst, first = 0, 0.0, ""
    for col in want.columns:
        for i, (x, y) in enumerate(zip(want[col], got[col])):
            x, y = _cell(x), _cell(y)
            if isinstance(x, float) and isinstance(y, (float, int)) \
                    and not isinstance(y, bool):
                y = float(y)
                if np.isnan(x) or np.isnan(y) or np.isinf(x) or np.isinf(y):
                    ok = (np.isnan(x) and np.isnan(y)) or x == y
                else:
                    err = abs(y - x) / max(abs(x), 1.0)
                    worst = max(worst, err)
                    continue
            else:
                ok = type(x) is type(y) and x == y
            if not ok:
                bad += 1
                first = first or f"{col}[{i}]: got {y!r}, want {x!r}"
    return {"mismatches": bad, "max_rel_err": worst, "first": first}
