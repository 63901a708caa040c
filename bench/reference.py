"""The plain reference and the numbers that decide `correct`.

The reference is NumPy's complex128 product of the operands the seed drew,
computed on the host; it imports nothing of the program.  Two numbers are
compared, each with a limit of its own (bench/limits/<cell>.json), and each
measured in units of the root-sum-square scale of the products it sums, the
scale at which rounding errors of the inputs or of the output show:

* rows_err: on rows of the output drawn from the seed, the widest error
  max_ij |C - R|_ij / sqrt(|A|^2 |B|^2)_ij, with |A|^2 the entrywise
  squared moduli;
* proj_err: over the whole output, the widest error of its product with a
  complex vector x drawn from the seed, max_i |C x - A (B x)|_i /
  sqrt(|A|^2 (|B|^2 |x|^2))_i.  An entry of C that is off shows in its
  row's projection, so no part of the output goes unchecked.

Both are run on the product that the timed loop returned last.
"""
from __future__ import annotations

import numpy as np


def draw_rows(seed: int, m: int, count: int) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    return np.sort(rng.choice(m, size=min(count, m), replace=False))


def draw_vector(seed: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    return rng.standard_normal(n) + 1j * rng.standard_normal(n)


def _sq(x):
    return np.square(np.abs(x))


def errors(c, a, b, seed: int, check_rows: int) -> dict[str, float]:
    """{number: value} of the output `c` of A @ B against the reference."""
    a = np.asarray(a, np.complex128)
    b = np.asarray(b, np.complex128)
    c = np.asarray(c, np.complex128)
    if c.shape != (a.shape[0], b.shape[1]) or not np.all(np.isfinite(c)):
        return {"rows_err": float("inf"), "proj_err": float("inf")}
    rows = draw_rows(seed, a.shape[0], check_rows)
    b2 = _sq(b)
    ref = a[rows] @ b
    scale = np.sqrt(_sq(a[rows]) @ b2)
    rows_err = float(np.max(np.abs(c[rows] - ref) / scale))
    x = draw_vector(seed, b.shape[1])
    proj = np.abs(c @ x - a @ (b @ x))
    proj_scale = np.sqrt(_sq(a) @ (b2 @ _sq(x)))
    return {"rows_err": rows_err, "proj_err": float(np.max(proj / proj_scale))}
