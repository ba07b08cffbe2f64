"""The numbers that decide ``correct`` for a language model trained with
Adam through a routed expert layer: the program's first three training
steps against the plain reference's, each number under a limit of its own
that the configuration file states with the readings it was set from.

Adam divides the gradient by its own size, so ``(w0 - w1) / lr`` is a sign
and says nothing, and the norm of a leaf's change is the same for any
gradient. What is compared instead is the first moment after step 1,
``(1 - beta1) g``: the gradient as the optimizer got it, and the *norm of
the difference* of the program's and the reference's change of every leaf
over three steps, not the difference of their norms.
"""
from __future__ import annotations

import statistics

import numpy as np

from harness.compare import QUIET_SHARE      # the same rule for quiet leaves

BLOCK_TOKENS = 256


def _norm(a):
    a = np.asarray(a, np.float32).ravel()
    return float(np.sqrt(np.dot(a, a)))


def leaf_differences(prog, ref, base=None, skip=()):
    """leaf -> ``||p - r|| / max(||r||, the median leaf's ||r||)``, of the
    leaves themselves or, with ``base``, of their changes from it."""
    norms, diffs = {}, {}
    for name in ref:
        if name in skip:
            continue
        r = np.asarray(ref[name], np.float32)
        p = np.asarray(prog[name], np.float32)
        if base is not None:
            b = np.asarray(base[name], np.float32)
            r, p = r - b, p - b
        norms[name] = _norm(r)
        diffs[name] = _norm(p - r)
    med = statistics.median(norms.values())
    return {k: diffs[k] / max(norms[k], med, 1e-30) for k in norms}


def quiet_leaves(ref_m1):
    norms = {k: _norm(v) for k, v in ref_m1.items()}
    med = statistics.median(norms.values())
    return {k for k, v in norms.items() if v < QUIET_SHARE * med}


def _worst(gaps):
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def block_means(rows, block=BLOCK_TOKENS):
    """The mean loss of every block of ``block`` tokens of every
    sequence."""
    rows = np.asarray(rows, np.float64)
    block = min(block, rows.shape[1])
    whole = rows.shape[1] - rows.shape[1] % block
    return rows[:, :whole].reshape(rows.shape[0], -1, block).mean(-1).ravel()


def leaf_tables(prog, ref, w0):
    """(leaf -> gap of Adam's first moment after step 1, leaf -> gap of
    the change over three steps), the quiet leaves left out of both."""
    quiet = quiet_leaves(ref["m1"])
    return (leaf_differences(prog["m1"], ref["m1"], skip=quiet),
            leaf_differences(prog["w3"], ref["w3"], base=w0, skip=quiet))


def numbers(prog, ref, w0):
    """name -> (value, detail). ``prog`` and ``ref`` hold ``loss_rows``
    (three arrays (sequences, tokens)), ``m1`` and ``w3`` (leaf -> array)
    and ``held3`` (expert layer -> the assignments the held experts got
    over the three steps: what the layer's auxiliary state counts).

    The names are those ``harness/compare.py`` gives the like numbers of a
    convolutional cell, so that one list of limits serves both kinds.

    * ``lossN_gap``: the step's mean loss, against the first loss;
    * ``seqN_gap``: the worst sequence's mean loss, against the first loss;
    * ``rowsN_gap``: the root mean square over blocks of 256 tokens (the
      rows of this comparison) of the difference of their mean losses,
      against the first loss;
    * ``grad1_gap``: the worst leaf of Adam's first moment after step 1,
      the gradient as the optimizer got it;
    * ``change3_gap``: the worst leaf of the change over three steps
      (leaves whose gradient is round-off left out of both);
    * ``stats3_gap``: the worst expert layer's count of assignments held
      over the three steps, against the reference's: tokens that chose
      other experts.
    """
    out = {}
    first = abs(float(np.mean(ref["loss_rows"][0])))
    for i in range(3):
        p = np.asarray(prog["loss_rows"][i], np.float64)
        r = np.asarray(ref["loss_rows"][i], np.float64)
        out["loss%d_gap" % (i + 1)] = (
            abs(p.mean() - r.mean()) / first,
            "%.6g against %.6g" % (p.mean(), r.mean()))
        out["seq%d_gap" % (i + 1)] = (
            float(np.max(np.abs(p.mean(1) - r.mean(1)))) / first,
            "%d sequences" % p.shape[0])
        bp, br = block_means(p), block_means(r)
        out["rows%d_gap" % (i + 1)] = (
            float(np.sqrt(np.mean((bp - br) ** 2))) / first,
            "%d blocks" % br.size)
    grad1, change3 = leaf_tables(prog, ref, w0)
    out["grad1_gap"] = _worst(grad1)
    out["change3_gap"] = _worst(change3)
    held = {l: abs(prog["held3"][l] - r) / max(r, 1.0)
            for l, r in ref["held3"].items()}
    out["stats3_gap"] = _worst(held) if held else (0.0, "no expert layer")
    return out
