"""The comparison that decides ``correct``.

Each tile the program finished is held against the plain reference
(``references/``) computed for the same input, and reduced to three
numbers, each the worst over the compared tiles:

* ``plane_mismatch``: the largest share of pixels that differ in any
  mask or integer plane (``fg``, ``rbc``, ``fg_open``, ``nuclei``,
  ``mask_at``, ``mask``, ``markers``, ``labels > 0``, ``objects``,
  ``dist``);
* ``object_count_gap``: ``|n_objects - reference| / reference``;
* ``value_gap``: the largest ``max |program - reference|`` over a float
  output, as a share of ``max |reference|`` of that output (of each
  column, for the per-object feature tables): ``gray``, ``recon``,
  ``hema``, ``eosin`` and every ``feat_*``.

Beside them the run's own accounting: ``failed`` (tiles or requests
attempted in the window that failed or never finished) and
``host_fallbacks`` (ops an accelerator lane ran through a host
implementation, a departure from the configuration) and
``worker_errors`` (ops that raised).  The limits are
data, one file per cell under ``limits/``, each set from readings of
the program and of the control (``PERF.md`` gives them).
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

PLANES = ("fg", "rbc", "fg_open", "nuclei", "mask_at", "mask", "markers",
          "objects", "dist")
VALUES = ("gray", "recon", "hema", "eosin", "feat_pixel", "feat_gradient",
          "feat_haralick", "feat_canny", "feat_morph")
#: The numbers compared, in the order they are printed.
NUMBERS = ("plane_mismatch", "object_count_gap", "value_gap", "failed",
           "host_fallbacks", "worker_errors")


def limits(cell: str) -> dict:
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())


def tile_numbers(got: dict, ref: dict) -> dict:
    """The three output numbers of one tile.  A missing output counts
    as entirely wrong."""
    mismatch = 0.0
    for k in PLANES + ("labels",):
        if got.get(k) is None:
            mismatch = 1.0
            continue
        have, want = np.asarray(got[k]), np.asarray(ref[k])
        if k == "labels":
            have, want = have > 0, want > 0
        mismatch = max(mismatch, float(np.mean(have != want)))
    n_ref = int(ref["n_objects"])
    n_got = got.get("n_objects")
    count_gap = (1.0 if n_got is None
                 else abs(int(n_got) - n_ref) / max(n_ref, 1))
    gap = 0.0
    for k in VALUES:
        if got.get(k) is None:
            gap = max(gap, 1.0)
            continue
        want = np.asarray(ref[k], np.float64)
        have = np.asarray(got[k], np.float64)
        if have.shape != want.shape:
            gap = max(gap, 1.0)
            continue
        axis = 0 if want.ndim == 2 and k.startswith("feat_") else None
        scale = np.maximum(np.abs(want).max(axis=axis), 1e-30)
        err = np.abs(have - want).max(axis=axis) / scale
        gap = max(gap, float(np.nan_to_num(np.max(err), nan=np.inf)))
    return {"plane_mismatch": mismatch, "object_count_gap": count_gap,
            "value_gap": gap}


def worst(per_tile: list[dict]) -> dict:
    out = {"plane_mismatch": 0.0, "object_count_gap": 0.0, "value_gap": 0.0}
    for t in per_tile:
        for k in out:
            out[k] = max(out[k], t[k])
    return out


def judge(numbers: dict, lim: dict) -> bool:
    return all(numbers[k] <= lim[k] for k in NUMBERS)
