"""The comparison that decides ``correct`` for a training cell.

Where the program's weights can be read (the one-dispatch round), three
numbers, each against the limit the cell's workload file states:

* ``loss_gap``: the largest relative gap, over the compared rounds, between
  the evaluation loss the program reported and the reference's;
* ``step_gap``: the first round's server step, w0 - w1, read from the state
  the program handed to round 2; per leaf, the gap between the program's
  norm and the reference's, over the larger of the reference's norm of that
  leaf and of the median leaf; the worst leaf;
* ``change_gap``: the same for the change of the weights over the compared
  rounds, w0 - w_R, read from the state handed to round R + 1.

A leaf whose reference step is under a thousandth of the median leaf's
moves by rounding alone and is left out of both norm gaps.

For the host-driven round, whose weights stay inside ``repro.harness.run``,
the run's history gives ``loss_gap`` as above and ``participants_gap``, the
largest difference over the compared rounds in the number of clients that
trained, which has to be 0; where its driver reads the weights too, the two
norm gaps join them.
"""
from __future__ import annotations

import numpy as np

KEEP_RULE = 1e-3


def _leaf_norms(vec: np.ndarray, sizes) -> np.ndarray:
    offs = np.cumsum([0] + [n for _, n in sizes])
    v = np.asarray(vec, np.float64)
    return np.array([np.linalg.norm(v[a:b])
                     for a, b in zip(offs[:-1], offs[1:])])


def norm_gap(prog: np.ndarray, ref: np.ndarray, sizes, keep) -> float:
    """Worst-leaf gap of norms, each over max(reference leaf norm, median
    reference leaf norm), among the leaves ``keep`` marks."""
    pn, rn = _leaf_norms(prog, sizes), _leaf_norms(ref, sizes)
    scale = np.maximum(rn, np.median(rn))
    return float(np.max(np.abs(pn - rn)[keep] / scale[keep]))


def kept_leaves(ref_step: np.ndarray, sizes) -> np.ndarray:
    rn = _leaf_norms(ref_step, sizes)
    return rn >= KEEP_RULE * np.median(rn)


def numbers(prog: dict, ref: dict, sizes) -> dict:
    """``prog`` and ``ref`` hold ``loss`` (per round) and ``w`` (flat
    weights before round 0 and after each compared round)."""
    rounds = len(ref["loss"])
    pl, rl = np.asarray(prog["loss"][:rounds]), np.asarray(ref["loss"])
    w0 = np.asarray(ref["w"][0], np.float64)
    ref_step = w0 - ref["w"][1]
    keep = kept_leaves(ref_step, sizes)
    return {
        "loss_gap": float(np.max(np.abs(pl - rl) / np.abs(rl))),
        "step_gap": norm_gap(w0 - prog["w"][1], ref_step, sizes, keep),
        "change_gap": norm_gap(w0 - prog["w"][rounds], w0 - ref["w"][rounds],
                               sizes, keep),
    }


def history_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``loss`` and ``participants`` per round."""
    rounds = len(ref["loss"])
    pl, rl = np.asarray(prog["loss"][:rounds]), np.asarray(ref["loss"])
    pp = np.asarray(prog["participants"][:rounds])
    return {
        "loss_gap": float(np.max(np.abs(pl - rl) / np.abs(rl))),
        "participants_gap": float(np.max(np.abs(
            pp - np.asarray(ref["participants"])))),
    }


def judge(nums: dict, limits: dict) -> bool:
    """Every number finite and within its limit."""
    return all(np.isfinite(nums[k]) and nums[k] <= limits[k] for k in limits)
