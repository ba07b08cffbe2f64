"""The comparison that decides ``correct``: the program's first training
steps against the plain reference's, number by number, each under a limit
of its own that the configuration file states with the readings it was
set from."""
from __future__ import annotations

import statistics

# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone: it is left out of the change
QUIET_SHARE = 1e-3


def leaf_gaps(prog, ref, skip=()):
    """leaf -> the gap between the program's norm of the leaf and the
    reference's (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger, since some leaves are all but zero."""
    med = statistics.median(ref.values())
    return {name: abs(prog[name] - r) / max(r, med, 1e-30)
            for name, r in ref.items() if name not in skip}


def worst_leaf_gap(prog, ref, skip=()):
    """(the worst leaf's gap, that leaf)."""
    gaps = leaf_gaps(prog, ref, skip)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def quiet_leaves(ref_grad):
    med = statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v < QUIET_SHARE * med}


def numbers(prog, ref):
    """name -> (value, detail) of every number that can be compared.
    ``prog`` and ``ref`` hold ``loss_rows`` (every row's loss at each of
    three steps), ``grad1``, ``change3`` and ``stats3`` (leaf -> norm).

    * ``lossN_gap``: the step's mean loss, against the first loss;
    * ``rowsN_gap``: the root mean square over the rows of the difference
      of their losses, against the first loss: rounding that the mean
      hides between rows shows here in full;
    * ``grad1_gap``, ``change3_gap``, ``stats3_gap``: the worst leaf of
      the first gradient as the optimizer gets it (``(w0 - w1) / lr``), of
      the parameters' change over the three steps (quiet leaves left
      out), and of the change of BatchNorm's running statistics.
    """
    out = {}
    first = abs(statistics.fmean(ref["loss_rows"][0]))
    for i in range(3):
        p, r = prog["loss_rows"][i], ref["loss_rows"][i]
        mp, mr = statistics.fmean(p), statistics.fmean(r)
        out["loss%d_gap" % (i + 1)] = (abs(mp - mr) / first,
                                       "%.6g against %.6g" % (mp, mr))
        out["rows%d_gap" % (i + 1)] = (
            statistics.fmean((a - b) ** 2 for a, b in zip(p, r)) ** 0.5
            / first, "%d rows" % len(r))
    out["grad1_gap"] = worst_leaf_gap(prog["grad1"], ref["grad1"])
    out["change3_gap"] = worst_leaf_gap(
        prog["change3"], ref["change3"], skip=quiet_leaves(ref["grad1"]))
    out["stats3_gap"] = worst_leaf_gap(prog["stats3"], ref["stats3"])
    return out


def judge(nums, limits):
    """(correct, rows): ``rows`` is [(name, value, limit)] of the numbers
    the configuration gives a limit; a value that is not a number under
    its limit fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = float(nums[name][0])
        rows.append((name, value, limit))
        if not value <= limit:          # NaN fails
            ok = False
    return ok, rows
