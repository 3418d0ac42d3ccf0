"""The plain reference of the HPL system: HPL 2.3's scaled residual, and
the control that has to fail it.

    ||A x - b||_inf / (eps (||A||_inf ||x||_inf + ||b||_inf) N)

in float64 on the host, with eps = 2**-24, the unit roundoff of the
float32 the configuration states (HPL prints 1.11e-16, the unit roundoff
of float64, for its double-precision runs).  Written from HPL's
definition in plain numpy: it imports nothing of the program.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.linalg

EPS = 2.0 ** -24


def scaled_residual(a, b, x) -> float:
    """HPL's scaled residual of ``x`` as the answer to ``a x = b``."""
    a, b, x = (np.asarray(v, np.float64) for v in (a, b, x))
    r = np.max(np.abs(a @ x - b))
    a_norm = np.max(np.sum(np.abs(a), axis=1))
    scale = EPS * (a_norm * np.max(np.abs(x)) + np.max(np.abs(b)))
    return float(r / (scale * a.shape[0]))


def _bf16(v: np.ndarray) -> np.ndarray:
    """``v`` rounded through bfloat16, back in float32."""
    return v.astype(ml_dtypes.bfloat16).astype(np.float32)


def control_solve(a, b, nb: int) -> np.ndarray:
    """The answer to ``a x = b`` by a right-looking LU in blocks of ``nb``
    with partial pivoting, stored in float32, whose trailing updates
    multiply operands rounded through bfloat16 and sum in float32: the
    chip's default-precision product, one precision below the float32
    products the configuration states.  Panels (LAPACK ``getrf``), the
    block rows of U and the two triangular solves stay float32."""
    a = np.array(a, np.float32)
    n = a.shape[0]
    piv = np.arange(n)
    for k0 in range(0, n, nb):
        k1 = k0 + nb
        panel, swaps = scipy.linalg.lu_factor(a[k0:, k0:k1],
                                              check_finite=False)
        for i, p in enumerate(swaps):
            if p != i:
                a[[k0 + i, k0 + p]] = a[[k0 + p, k0 + i]]
        piv[k0:k1] = k0 + swaps
        a[k0:, k0:k1] = panel
        a[k0:k1, k1:] = scipy.linalg.solve_triangular(
            a[k0:k1, k0:k1], a[k0:k1, k1:], lower=True, unit_diagonal=True,
            check_finite=False)
        a[k1:, k1:] -= _bf16(a[k1:, k0:k1]) @ _bf16(a[k0:k1, k1:])
    return scipy.linalg.lu_solve((a, piv), np.asarray(b, np.float32),
                                 check_finite=False)
