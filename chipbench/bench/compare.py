"""The comparison that decides `correct`, and its control.

Every answer is a grouped result: one integer group-key column, integer
COUNT columns and float SUM columns. Three numbers are compared over all
the answers a run checks, each against its limit in ``limits.json``:

- ``group_mismatch``: groups present on one side only (exact, limit 0);
- ``count_mismatch``: groups whose COUNT differs (exact, limit 0);
- ``sum_rel_err``: the widest relative gap of a SUM against the float64
  reference, ``|engine - reference| / max(|reference|, 1)``.

The control is the reference itself with its float columns held in
bfloat16, the step below the configuration's float32: values rounded to
bfloat16, summed with float32 accumulation, the sum stored as bfloat16,
as a bfloat16 column reduced on the chip would be.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Mapping

import ml_dtypes
import numpy as np

from .reference import Columns, accumulate64

NUMBERS = ("group_mismatch", "count_mismatch", "sum_rel_err")


def accumulate_bf16(agg: str, values: np.ndarray, group: np.ndarray,
                    n_groups: int) -> np.ndarray:
    """The control's per-group reduction: bfloat16 in and out."""
    if agg == "count" or not np.issubdtype(values.dtype, np.floating):
        return accumulate64(agg, values, group, n_groups)
    bf16 = ml_dtypes.bfloat16
    v = values.astype(bf16).astype(np.float32)
    s = np.bincount(group, weights=v, minlength=n_groups).astype(np.float32)
    return s.astype(bf16).astype(np.float64)


def _key_column(cols: Columns) -> str:
    keys = [c for c in cols if c.split("_", 1)[0] not in ("sum", "count")]
    if len(keys) != 1:
        raise ValueError(f"expected one group-key column, got {keys}")
    return keys[0]


def compare_answer(got: Columns, want: Columns) -> Dict[str, float]:
    """The three numbers for one answer (``got`` from the engine)."""
    if set(got) != set(want):
        return {"group_mismatch": float(len(want[_key_column(want)]) or 1),
                "count_mismatch": 0.0, "sum_rel_err": 0.0}
    key = _key_column(want)
    gk, wk = np.asarray(got[key]), np.asarray(want[key])
    common, gi, wi = np.intersect1d(gk, wk, return_indices=True)
    mismatch = len(gk) + len(wk) - 2 * len(common)
    if len(np.unique(gk)) != len(gk):
        mismatch += len(gk) - len(np.unique(gk))
    counts, rel = 0, 0.0
    for col in want:
        if col == key:
            continue
        g = np.asarray(got[col])[gi]
        w = np.asarray(want[col])[wi]
        if np.issubdtype(w.dtype, np.integer) and \
                np.issubdtype(g.dtype, np.integer):
            counts += int(np.sum(g.astype(np.int64) != w))
        elif len(w):
            gap = np.abs(g.astype(np.float64) - w) / np.maximum(np.abs(w), 1)
            rel = max(rel, float(np.max(gap)) if np.all(np.isfinite(gap))
                      else float("inf"))
    return {"group_mismatch": float(mismatch),
            "count_mismatch": float(counts), "sum_rel_err": rel}


def worst(readings) -> Dict[str, float]:
    """Sum the exact counts, keep the widest relative gap."""
    out = {"group_mismatch": 0.0, "count_mismatch": 0.0, "sum_rel_err": 0.0}
    for r in readings:
        out["group_mismatch"] += r["group_mismatch"]
        out["count_mismatch"] += r["count_mismatch"]
        out["sum_rel_err"] = max(out["sum_rel_err"], r["sum_rel_err"])
    return out


def load_limits(path: Path) -> Dict[str, float]:
    limits = json.loads(Path(path).read_text())["limits"]
    missing = set(NUMBERS) - set(limits)
    if missing:
        raise ValueError(f"{path}: no limit for {sorted(missing)}")
    return {k: float(limits[k]) for k in NUMBERS}


def within(numbers: Mapping[str, float], limits: Mapping[str, float]
           ) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)
