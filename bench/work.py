"""Operations and bytes of one emulated GEMM, stage by stage.

A frozen copy of the paper's terms (arXiv:2512.08321 SIII-C, as kept in
`repro.core.perfmodel`): fast-mode Karatsuba runs 3 int8 products of
(m, k) x (k, n) per modulus, 6 N m n k int8 operations; a real product runs
one per modulus, 2 N m n k.  Useful work is the paper's convention,
8 m n k flops for a complex product and 2 m n k for a real one.

Bytes count each stage's defined inputs and outputs at their dtypes, from
the configuration's shapes, N and formulation, not what an implementation
allocates or re-reads: so a kernel's roofline reads the same work whatever
implements it.  The residue products read the int8 residue planes of both
operands (real and imaginary for a complex product) and write the int8
residue planes of the output.
"""
from __future__ import annotations

import dataclasses

_COMPLEX = {"complex64": True, "complex128": True, "float32": False, "float64": False}


@dataclasses.dataclass(frozen=True)
class Work:
    m: int
    n: int
    k: int
    n_moduli: int
    dtype: str
    formulation: str

    @property
    def is_complex(self) -> bool:
        return _COMPLEX[self.dtype]

    @property
    def useful_flops(self) -> float:
        return (8.0 if self.is_complex else 2.0) * self.m * self.n * self.k

    def stage(self, name: str) -> tuple[float, float]:
        """(operations, bytes) of one call's stage `name`."""
        if name != "product":
            raise ValueError(f"no op and byte terms for stage {name!r}")
        m, n, k, N = self.m, self.n, self.k, self.n_moduli
        if not self.is_complex:
            return 2.0 * N * m * n * k, float(N * (m * k + k * n + m * n))
        if self.formulation != "karatsuba":
            raise ValueError(f"no terms for formulation {self.formulation!r}")
        return 6.0 * N * m * n * k, float(N * 2 * (m * k + k * n + m * n))

