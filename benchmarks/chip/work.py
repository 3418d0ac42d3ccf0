"""The work a solve needs.  For HPL, HPL's own operation count
(``hpl``).  For the Wilson-Dirac solve: operations and bytes, from the
lattice, the precisions the configuration declares and the iteration
counts the solver reports.  Never from what the program happens to stream: a program
that stores its fields wider than declared reads as a lower share, and one
that stores them as declared can reach, never pass, its roofline.

Counts are per half-lattice site (Vh = V / 2), in reals; bytes follow from
the declared bytes per real.

- Half-lattice hop (one parity block of D-slash): the standard 1320 flops
  per output site (the count in ``repro.lqcd.dirac``), and one pass over
  its operands: the source half-spinor (24 reals), the output half-spinor
  (24) and the 8 links that meet at each output site, 4 of either parity
  (8 x 18 reals: links count whole, 18 reals; counting a compressed
  link format would be a change of the benchmark).  Unlike
  the program's streaming model (8 neighbour spinors per site), each
  operand is counted once: a kernel that reuses neighbours from on-chip
  memory must not read above its roofline.
- Schur operator A = 1 - kappa^2 D_eo D_oe: two hops and one more read of
  its input for the combination (48 flops).  gamma_5 is a spin
  permutation and costs nothing.
- Inner CG iteration: one normal op A^H A (two Schur operators) and the
  vector updates, which touch x, r and p twice (read and write) and A p
  once: 7 half-spinors, 240 flops (5 complex axpy or dot passes).
- Outer round (float32): A^H r_s, the update x_e += e and r_s = rhs - A x_e.
- Per solve (float32): the Schur right-hand side (one hop), the odd-site
  back-substitution (one hop) and the true residual with the
  full-lattice operator (x, b and the links once, 1320 + 144 flops per
  site), and ||b||.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

HOP_FLOPS = 1320          # per output site (repro.lqcd.dirac's count)
SPINOR = 24               # reals per site
LINK = 18                 # reals per link, counted whole
HOP_REALS = SPINOR + SPINOR + 8 * LINK
SCHUR_REALS = 2 * HOP_REALS + SPINOR
SCHUR_FLOPS = 2 * HOP_FLOPS + 48
NORMAL_REALS, NORMAL_FLOPS = 2 * SCHUR_REALS, 2 * SCHUR_FLOPS
CG_VECTOR_REALS, CG_VECTOR_FLOPS = 7 * SPINOR, 5 * 48
OUTER_REALS = 2 * SCHUR_REALS + 3 * SPINOR + SPINOR
OUTER_FLOPS = 2 * SCHUR_FLOPS + 3 * 48
# rhs hop + back-substitution hop (+ one spinor read each), and the
# residual over the full lattice (2 Vh sites: x, b, 4 links, norms)
SOLVE_REALS = 2 * (HOP_REALS + SPINOR) + 2 * (2 * SPINOR + 4 * LINK) + 2 * SPINOR
SOLVE_FLOPS = 2 * (HOP_FLOPS + 48) + 2 * (HOP_FLOPS + 144) + 2 * 48

BYTES_PER_REAL = {"float64": 8, "float32": 4, "bfloat16": 2, "float16": 2}


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other):
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def seconds_at(self, peak_flops: float, peak_bytes: float) -> float:
        """The least time the chip could take: the larger of the two
        bounds."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes)


def half_sites(lattice: Sequence[int]) -> int:
    v = 1
    for n in lattice:
        v *= int(n)
    return v // 2


def _work(lattice, reals_per_site: int, flops_per_site: int,
          dtype: str) -> Work:
    vh = half_sites(lattice)
    return Work(float(flops_per_site * vh),
                float(reals_per_site * vh * BYTES_PER_REAL[dtype]))


def hop(lattice, dtype: str) -> Work:
    """One half-lattice hop."""
    return _work(lattice, HOP_REALS, HOP_FLOPS, dtype)


def inner_cg(lattice, iters: float, dtype: str) -> Work:
    """``iters`` inner CG iterations at the inner precision."""
    w = _work(lattice, NORMAL_REALS + CG_VECTOR_REALS,
              NORMAL_FLOPS + CG_VECTOR_FLOPS, dtype)
    return Work(w.flops * iters, w.bytes * iters)


def solve(lattice, iters: float, rounds: float, inner_dtype: str,
          outer_dtype: str) -> Work:
    """One even-odd defect-correction solve: ``iters`` inner iterations
    over ``rounds`` outer rounds."""
    outer = _work(lattice, OUTER_REALS, OUTER_FLOPS, outer_dtype)
    fixed = _work(lattice, SOLVE_REALS, SOLVE_FLOPS, outer_dtype)
    return (inner_cg(lattice, iters, inner_dtype)
            + Work(outer.flops * rounds, outer.bytes * rounds) + fixed)


def hpl(n: int) -> float:
    """HPL's operation count of one solve of order ``n``: 2/3 n^3 + 3/2 n^2
    (HPL 2.3's own, in ``HPL_pdtest``), factor and solve."""
    return 2.0 / 3.0 * n ** 3 + 1.5 * n ** 2
