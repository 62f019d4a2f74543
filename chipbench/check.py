"""The comparison that decides ``correct``.

Each sampled answer (served ``[k]`` values and ids of one request) is
held to the float64 reference. The numbers compared, each against a
limit of its own (``limits`` in the configuration's file):

``score_err``
    The widest gap between a served value and the float64 score of its
    own id, in units of ``eps32 * sum_r |u_r t_r|`` (the float32
    rounding scale of that dot product). float32 products summed in
    float32 read a few units; products made in fewer bf16 passes than
    ``HIGHEST`` read some hundred times more.
``misranked``
    Slots whose item scores below the reference's ``j``-th best by more
    than twice the ``score_err`` limit, in the same units: a better item
    was left out. Two items closer than that may swap places when each
    score is off by no more than the limit; items farther apart may not.
    The tolerance follows the error measured on the chip, not the
    worst-case float32 bound, so an approximate top-k, or one that
    skips rows, is caught even where neighbouring scores lie close.
    Limit 0.
``bad_ids``
    Slots whose id is no row of the catalogue, or repeats within one
    answer. Limit 0.
``unanswered``
    Requests of the window with no answer, an error, or a shed
    sentinel. Limit 0.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from chipbench.reference import Reference

EPS32 = float(np.finfo(np.float32).eps)


@dataclasses.dataclass
class Verdict:
    numbers: Dict[str, float]
    limits: Dict[str, float]
    #: sampled answers found wrong (each also a failed request)
    n_wrong: int

    @property
    def correct(self) -> bool:
        return all(self.numbers[n] <= self.limits[n] for n in self.numbers)

    def as_dict(self) -> Dict[str, dict]:
        return {n: {"value": self.numbers[n], "limit": self.limits[n]}
                for n in self.numbers}


def bad_id_mask(ids: np.ndarray, n_rows: int) -> np.ndarray:
    """``[S, k]``: slots whose id is no row, or repeats an earlier slot."""
    ids = np.asarray(ids, np.int64)
    bad = (ids < 0) | (ids >= n_rows)
    srt = np.sort(ids, axis=1)
    dup_sorted = np.zeros_like(bad)
    dup_sorted[:, 1:] = srt[:, 1:] == srt[:, :-1]
    # a row with any repeat is marked in its first slot
    bad[:, 0] |= dup_sorted.any(axis=1)
    return bad


def per_answer(values, ids, ref: Reference, n_rows: int, tie: float):
    """``(score_err, n_misranked, n_bad)``, each ``[S]``; ``tie`` is the
    widest gap, in units, that two items may swap across. A bad slot is
    counted in ``n_bad`` and not measured."""
    values = np.asarray(values, np.float64)
    bad = bad_id_mask(ids, n_rows)
    scale = EPS32 * ref.served_abs
    with np.errstate(invalid="ignore", divide="ignore"):
        err = np.abs(values - ref.served_true) / scale
        gap = (ref.values[:, :values.shape[1]] - ref.served_true) / scale
    return (np.nanmax(np.where(bad, 0.0, err), axis=1, initial=0.0),
            np.sum(~bad & (gap > tie), axis=1),
            bad.sum(axis=1))


def judge(values, ids, ref: Reference, n_rows: int, n_unanswered: int,
          limits: Dict[str, float]) -> Verdict:
    lim = {"score_err": float(limits["score_err"]), "misranked": 0.0,
           "bad_ids": 0.0, "unanswered": 0.0}
    score_err, misranked, n_bad = per_answer(values, ids, ref, n_rows,
                                             2.0 * lim["score_err"])
    numbers = {
        "score_err": float(np.max(score_err, initial=0.0)),
        "misranked": float(np.sum(misranked)),
        "bad_ids": float(np.sum(n_bad)),
        "unanswered": float(n_unanswered),
    }
    wrong = (score_err > lim["score_err"]) | (misranked > 0) | (n_bad > 0)
    return Verdict(numbers, lim, int(np.sum(wrong)))
