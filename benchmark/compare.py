"""The comparison that decides `correct` for a training cell.

A run's first updates are followed by the plain reference from the same seed
and batches.  Every number is a gap between the program's reading and the
reference's, as a share of the reference's:

    loss1..N     |loss_p - loss_r| / |loss_r|, one per followed update
    grad_norm1   the same for the norm of the first gradient (all leaves)
    sm3_leaf     worst leaf of the gap in the SM3 row mass after update 1:
                 the clipped first gradient as the optimizer keeps it
    change_leaf  worst leaf of the gap in the norm of the parameters' change
                 after the last followed update

A leaf's gap is the gap between the two norms (not the norm of the
difference), over the reference's norm of that leaf or of the median leaf,
whichever is larger.  Leaves whose reference gradient is under a thousandth
of the median leaf's are left out of `change_leaf`: they move by round-off
alone.  A reading that is not a number fails its limit.
"""
from __future__ import annotations

import math
import typing

import numpy as np

NULL_GRADIENT = 1e-3  # of the median leaf's gradient norm


def _gaps(prog, ref) -> np.ndarray:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return np.abs(prog - ref) / np.maximum(ref, np.median(ref))


def _leaf_gap(prog, ref, keep: typing.Optional[np.ndarray] = None,
              pick=np.max) -> float:
    gap = _gaps(prog, ref)
    if keep is not None:
        gap = gap[keep]
    return float(pick(gap)) if np.all(np.isfinite(gap)) else math.nan


def _share(prog: float, ref: float) -> float:
    return abs(prog - ref) / abs(ref)


def readings(prog: dict, ref: dict) -> typing.Dict[str, float]:
    out = {f"loss{i + 1}": _share(p, r)
           for i, (p, r) in enumerate(zip(prog["loss"], ref["loss"]))}
    out["grad_norm1"] = _share(prog["grad_norm"][0], ref["grad_norm"][0])
    moved = ref["grad_leaf"] >= NULL_GRADIENT * np.median(ref["grad_leaf"])
    for name, keep in (("sm3", None), ("change", moved)):
        for kind, pick in (("leaf", np.max), ("median", np.median)):
            out[f"{name}_{kind}"] = _leaf_gap(
                prog[name + "_leaf"], ref[name + "_leaf"], keep, pick)
    return out


def worst_leaves(prog: dict, ref: dict) -> typing.Dict[str, str]:
    """Which leaf reads the widest gap, for the run's log."""
    out = {}
    for key in ("sm3_leaf", "change_leaf"):
        i = int(np.nanargmax(_gaps(prog[key], ref[key])))
        out[key] = (f"{ref['names'][i]} program {prog[key][i]:.6g} "
                    f"reference {ref[key][i]:.6g}")
    return out


def against(got: typing.Dict[str, float], limits: typing.Dict[str, float]
            ) -> typing.List[typing.Tuple[str, float, float]]:
    """(name, reading, limit) for every number that has a limit."""
    limits = {k: v for k, v in limits.items() if k != "note"}
    missing = set(limits) - set(got)
    if missing:
        raise KeyError(f"limits name numbers that are not read: {missing}")
    return [(name, got[name], float(limits[name])) for name in limits]


def correct(rows: typing.Sequence[typing.Tuple[str, float, float]]) -> bool:
    return bool(rows) and all(value <= limit for _, value, limit in rows)
