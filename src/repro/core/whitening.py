"""Activation whitening transforms S extracted from calibration Grams.

Every activation-aware method transforms the weight A into A @ S before the
SVD, where S is derived from the calibration activation matrix X (n x p):

  ASVD-0   S = diag(mean_i |x_i|)              (Yuan et al. scaling)
  ASVD-I   S = Cholesky factor of X X^T        (SVD-LLM / Wang et al.)
  ASVD-II  S = P Lambda^{1/2} from X X^T = P Lambda P^T (paper Thm 3)
  ASVD-III S = P * gamma, gamma = max sqrt(eig) (paper Thm 4, failure trial)

We never materialize X: the calibration runner accumulates the Gram
G = X X^T (n x n, fp32/fp64) and the per-channel absolute means in a
streaming fashion (see repro/calib/gram.py).  All factorizations here consume
(G, absmean) only.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import scipy.linalg

Array = np.ndarray


@dataclasses.dataclass(frozen=True)
class Whitener:
    """Holds S and its (pseudo-)inverse application.

    s:      (n, n) or (n,) diagonal — the transform applied as A @ S.
    s_inv:  matching inverse so that (A S)(S^{-1} X) == A X.
    diagonal: True when s/s_inv are stored as vectors.
    rank:   numerical rank of the Gram (n when full rank).
    """

    s: Array
    s_inv: Array
    diagonal: bool
    rank: int
    method: str

    def apply_right(self, a: Array) -> Array:
        """Compute A @ S."""
        a = np.asarray(a, dtype=self.s.dtype)
        if self.diagonal:
            return a * self.s[None, :]
        return a @ self.s

    def unapply_right(self, b: Array) -> Array:
        """Compute B @ S^{-1} (recover the weight-space factor)."""
        b = np.asarray(b, dtype=self.s_inv.dtype)
        if self.diagonal:
            return b * self.s_inv[None, :]
        return b @ self.s_inv


def _regularize(gram: Array, damp: float) -> Array:
    """Symmetrize + dampen the Gram. `damp` is relative to mean diagonal,
    mirroring GPTQ's percdamp — guards Cholesky against semi-definiteness."""
    g = np.asarray(gram, dtype=np.float64)
    g = 0.5 * (g + g.T)
    if damp > 0.0:
        mean_diag = float(np.mean(np.diag(g)))
        g = g + damp * max(mean_diag, 1e-12) * np.eye(g.shape[0])
    return g


def diag_absmean_whitener(absmean: Array, eps: float = 1e-6) -> Whitener:
    """ASVD-0: per-input-channel |mean| scaling (diagonal)."""
    d = np.asarray(absmean, dtype=np.float64)
    d = np.maximum(d, eps)
    return Whitener(s=d, s_inv=1.0 / d, diagonal=True, rank=d.shape[0], method="asvd0")


def _tri_inv(l: Array) -> Array:
    """Inverse of a lower-triangular matrix (LAPACK trtri: n^3/3 flops,
    against about 8n^3/3 for a general solve against the identity)."""
    trtri = scipy.linalg.get_lapack_funcs("trtri", (l,))
    inv, info = trtri(l, lower=1)
    if info != 0:
        raise np.linalg.LinAlgError(f"trtri failed (info={info})")
    return inv


def make_cholesky_whitener(gram: Array, damp: float = 1e-6) -> Whitener:
    """ASVD-I (SVD-LLM): S = lower Cholesky factor of XX^T."""
    g = _regularize(gram, damp)
    try:
        l = np.linalg.cholesky(g)
    except np.linalg.LinAlgError:
        # Rank-deficient even after damping: paper's stated failure mode for
        # the Cholesky path; defer to the eigen (ASVD-II) construction.
        return make_eigen_whitener(gram, damp=damp, method="asvd1_fallback")
    s_inv = _tri_inv(l)
    return Whitener(s=l, s_inv=s_inv, diagonal=False, rank=g.shape[0], method="asvd1")


def make_eigen_whitener(
    gram: Array,
    damp: float = 0.0,
    rank_rtol: float = 1e-10,
    method: str = "asvd2",
) -> Whitener:
    """ASVD-II: S = P Lambda^{1/2} from the eigendecomposition of XX^T.

    Zero eigenvalues are handled with the pseudo-inverse (paper §3: "the
    method via SVD does not require adjustments for zero eigenvalues since
    pseudo-inverses can be applied").
    """
    g = _regularize(gram, damp)
    lam, p = np.linalg.eigh(g)  # ascending
    lam = lam[::-1].copy()
    p = p[:, ::-1].copy()
    lam = np.maximum(lam, 0.0)
    if lam[0] <= 0.0:
        # Degenerate all-zero Gram: identity transform.
        n = g.shape[0]
        return Whitener(np.ones(n), np.ones(n), True, 0, method)
    cutoff = lam[0] * rank_rtol
    rank = int(np.sum(lam > cutoff))
    sqrt_lam = np.sqrt(lam)
    inv_sqrt = np.where(lam > cutoff, 1.0 / np.maximum(sqrt_lam, 1e-300), 0.0)
    s = p * sqrt_lam[None, :]  # P @ diag(sqrt(lam))
    s_inv = inv_sqrt[:, None] * p.T  # diag(pinv sqrt) @ P^T
    return Whitener(s=s, s_inv=s_inv, diagonal=False, rank=rank, method=method)


def make_gamma_whitener(gram: Array, damp: float = 0.0) -> Whitener:
    """ASVD-III (Thm 4): S = P * gamma with gamma = max(Lambda^{1/2}).

    Rotation by P followed by a *scalar* scale; the loss bound is then
    sigma_i^2 * tr(Lambda/gamma^2 v v^T) <= sigma_i^2.  Reported by the paper
    as a failure trial — kept for the ablation benchmark.
    """
    g = _regularize(gram, damp)
    lam, p = np.linalg.eigh(g)
    lam = np.maximum(lam[::-1].copy(), 0.0)
    p = p[:, ::-1].copy()
    gamma = float(np.sqrt(lam[0])) if lam[0] > 0 else 1.0
    s = p * gamma
    s_inv = p.T / gamma
    rank = int(np.sum(lam > lam[0] * 1e-10)) if lam[0] > 0 else 0
    return Whitener(s=s, s_inv=s_inv, diagonal=False, rank=rank, method="asvd3")


def make_whitener(
    method: str,
    gram: Optional[Array] = None,
    absmean: Optional[Array] = None,
    damp: float = 1e-6,
) -> Whitener:
    """Factory keyed by compressor name."""
    m = method.lower()
    if m in ("asvd0", "diag"):
        if absmean is None:
            if gram is None:
                raise ValueError("asvd0 needs absmean or gram")
            absmean = np.sqrt(np.maximum(np.diag(np.asarray(gram, np.float64)), 0.0))
        return diag_absmean_whitener(absmean)
    if gram is None:
        raise ValueError(f"{method} needs a Gram matrix")
    if m in ("asvd1", "cholesky", "svd-llm"):
        return make_cholesky_whitener(gram, damp=damp)
    if m in ("asvd2", "eigen", "svd"):
        return make_eigen_whitener(gram, damp=damp)
    if m in ("asvd3", "gamma"):
        return make_gamma_whitener(gram, damp=damp)
    raise ValueError(f"unknown whitening method {method!r}")
