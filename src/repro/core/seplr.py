"""SEP-LR model container and adapters.

A separable linear relational model (paper Eq. 1) scores a (query, target)
couple as

    s(x, y) = u(x)^T t(y) = sum_r u_r(x) t_r(y)

The target side is a finite catalogue of M items held as a dense factor
matrix ``T`` of shape ``[M, R]``; the query side is an R-vector (or a batch
``[B, R]``).  Every model family in the paper's Section 3 reduces to this
container:

* memory-based CF (cosine):        u = x / ||x||,  T = Y / ||Y||_rows
* model-based CF (matrix factor.): u = U[i],       T = item factors
* multi-label / multivariate reg.: u = psi(x),     T = W (per-label weights)
* pairwise / Kronecker models:     u = W^T psi(x), T = phi(Y)   (folded)
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.naive import SCORE_PRECISION

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class SepLRModel:
    """A trained SEP-LR model over a finite catalogue.

    Attributes:
      targets: ``[M, R]`` dense target factors t(y) (one row per item).
      name: human-readable tag used in benchmark output.
    """

    targets: Array
    name: str = "seplr"

    @property
    def num_targets(self) -> int:
        return int(self.targets.shape[0])

    @property
    def rank(self) -> int:
        return int(self.targets.shape[1])

    def score_all(self, u: Array) -> Array:
        """Naive scoring of every target: ``[R] -> [M]`` or ``[B,R] -> [B,M]``."""
        return jnp.einsum("...r,mr->...m", u, self.targets,
                          precision=SCORE_PRECISION)

    def score(self, u: Array, ids: Array) -> Array:
        """Score a subset of targets. ``u: [R]``, ``ids: [n]`` -> ``[n]``."""
        return jnp.matmul(self.targets[ids], u,
                          precision=SCORE_PRECISION)


# ---------------------------------------------------------------------------
# Adapters (paper Section 3)
# ---------------------------------------------------------------------------


def from_cosine_similarity(item_matrix: Array, name: str = "memory_cf") -> SepLRModel:
    """Memory-based CF: rows are items, cosine similarity as the score.

    Normalising each row to unit L2 norm makes the dot product equal to the
    cosine similarity (paper Eq. 5/6). Queries must be normalised with
    :func:`normalize_query`.
    """
    norms = jnp.linalg.norm(item_matrix, axis=1, keepdims=True)
    norms = jnp.where(norms == 0, 1.0, norms)
    return SepLRModel(targets=item_matrix / norms, name=name)


def normalize_query(x: Array) -> Array:
    n = jnp.linalg.norm(x, axis=-1, keepdims=True)
    return x / jnp.where(n == 0, 1.0, n)


def from_matrix_factorization(item_factors: Array, name: str = "mf") -> SepLRModel:
    """Model-based CF: ``C ~= U T``; queries are rows of U."""
    return SepLRModel(targets=item_factors, name=name)


def from_linear_multilabel(label_weights: Array, name: str = "multilabel") -> SepLRModel:
    """Binary-relevance style linear models: ``s(x, y) = w_y^T psi(x)``.

    ``label_weights``: ``[M_labels, R_features]`` — one weight vector per label.
    """
    return SepLRModel(targets=label_weights, name=name)


def from_pairwise_kronecker(W: Array, phi_targets: Array, name: str = "kronecker") -> SepLRModel:
    """Pairwise model ``s(x,y) = psi(x)^T W phi(y)``.

    Folds ``W`` into the query side: ``u(x) = W^T psi(x)``, ``t(y) = phi(y)``.
    Returns the target-side container; use :func:`kronecker_query` for u(x).
    """
    del W  # folded at query time
    return SepLRModel(targets=phi_targets, name=name)


def kronecker_query(W: Array, psi_x: Array) -> Array:
    return psi_x @ W


# ---------------------------------------------------------------------------
# Synthetic model generators used by tests and benchmarks
# ---------------------------------------------------------------------------


def random_model(
    rng: np.random.Generator,
    num_targets: int,
    rank: int,
    distribution: str = "normal",
    sparsity: float = 0.0,
    name: Optional[str] = None,
) -> SepLRModel:
    """Random SEP-LR model with controllable factor distribution.

    ``distribution``:
      * ``normal`` — iid N(0, 1): the hardest case for TA (independent lists).
      * ``lognormal`` — heavy-tailed positive factors (implicit-feedback CF).
      * ``lowrank_spectrum`` — factors scaled by a decaying spectrum, mimicking
        PCA / PLS factors where early dimensions dominate (TA's best case).
    """
    T = rng.standard_normal((num_targets, rank)).astype(np.float32)
    if distribution == "lognormal":
        T = np.abs(rng.lognormal(0.0, 1.0, (num_targets, rank))).astype(np.float32)
    elif distribution == "lowrank_spectrum":
        spectrum = (1.0 / np.sqrt(1.0 + np.arange(rank))).astype(np.float32)
        T = T * spectrum[None, :]
    if sparsity > 0.0:
        mask = rng.random((num_targets, rank)) >= sparsity
        T = T * mask
    return SepLRModel(
        targets=jnp.asarray(T),
        name=name or f"random_{distribution}_M{num_targets}_R{rank}",
    )
