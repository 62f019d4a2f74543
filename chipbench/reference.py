"""The plain reference: float64 dense top-k over the live catalogue.

The catalogue is made again, block by block, from the seed (it takes
nothing the program made), and every block is scored in float64 on the
host: ``U @ T_block.T``, a top-k per block, merged in block order. The
arithmetic is that of ``chip_smoke.py``'s ``DenseReference.topk``, in
blocks of catalogue rows so that a 102,400 x 8,192 head fits. Blocks
are made, read back and scored by a pool of threads (NumPy's matmul and
partition release the GIL); the merge is the same as one at a time.
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import catalogue


@dataclasses.dataclass
class Reference:
    """What the reference says about each sampled answer, ``[S, k]``."""

    #: the reference's k best scores per query, descending
    values: np.ndarray
    ids: np.ndarray
    #: float64 score of each SERVED id (nan where the id is not a row)
    served_true: np.ndarray
    #: sum_r |u_r t_{id, r}| of each served id: the scale of its rounding
    served_abs: np.ndarray


def _topk(v: np.ndarray, i: np.ndarray, k: int):
    """Row-wise top ``k`` of ``v`` (descending), with the ids ``i`` of
    its columns (same shape)."""
    kk = min(k, v.shape[1])
    part = np.argpartition(-v, kk - 1, axis=1)[:, :kk]
    pv = np.take_along_axis(v, part, axis=1)
    order = np.argsort(-pv, axis=1, kind="stable")
    return (np.take_along_axis(pv, order, axis=1),
            np.take_along_axis(np.take_along_axis(i, part, axis=1), order,
                               axis=1))


def catalogue_block(seed: int, config: dict, b: int):
    """``(first_row, rows)`` of catalogue block ``b`` in float64, made
    from the seed on the device and read to the host."""
    n, r = int(config["rows"]), int(config["rank"])
    br = int(config["block_rows"])
    lo = b * br
    blk = catalogue.block(seed, catalogue.CATALOGUE, b, r,
                          config["catalogue"], br)
    return lo, np.asarray(blk, np.float64)[:n - lo]


def workers() -> int:
    """Threads that score blocks: the cores this process may use, at
    most 16 (each holds a block and its scores)."""
    return max(1, min(16, len(os.sched_getaffinity(0))))


def reference(seed: int, config: dict, U: np.ndarray, served_ids,
              k: int) -> Reference:
    """Score the sampled queries ``U`` ([S, R]) against every row."""
    U64 = np.asarray(U, np.float64)
    sid = np.asarray(served_ids, np.int64)
    s = U64.shape[0]
    vals = np.full((s, 0), -np.inf)
    ids = np.zeros((s, 0), np.int64)
    true = np.full(sid.shape, np.nan)
    absv = np.full(sid.shape, np.nan)

    def scored(b):
        """Block ``b``'s top-k per query, and the float64 score and
        rounding scale of each served id that lies in it."""
        lo, T = catalogue_block(seed, config, b)
        hi = lo + T.shape[0]
        S = U64 @ T.T
        bv, bi = _topk(S, np.broadcast_to(np.arange(lo, hi), S.shape), k)
        q, j = np.nonzero((sid >= lo) & (sid < hi))
        rows = T[sid[q, j] - lo]
        return (bv, bi, q, j, np.einsum("nr,nr->n", U64[q], rows),
                np.einsum("nr,nr->n", np.abs(U64[q]), np.abs(rows)))

    nb = catalogue.n_blocks(config["rows"], config["block_rows"])
    with ThreadPoolExecutor(workers(), thread_name_prefix="ref") as pool:
        for bv, bi, q, j, t, a in pool.map(scored, range(nb)):
            vals, ids = _topk(np.concatenate([vals, bv], axis=1),
                              np.concatenate([ids, bi], axis=1), k)
            true[q, j] = t
            absv[q, j] = a
    return Reference(vals, ids, true, absv)
