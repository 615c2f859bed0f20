"""Operations, bytes and least time of the Scheme-II residue kernel
(``emugemm2_p<n>``), kept with the benchmark.

The kernel runs one exact int8 GEMM per modulus (2MNK operations each)
and reduces each product in its epilogue, so with n moduli it reads the
int8 residue stacks of A (n, M, K) and B (n, K, N) once and writes the
int8 residues of C (n, M, N) once.  Its least time is the larger of its
operations at the int8 peak and those bytes at the HBM bandwidth.
Encode and CRT, the XLA stages around it, are not counted here.
"""

from __future__ import annotations


def kernel_ops(moduli: int, m: int, k: int, n: int) -> int:
    """int8 operations (2 per multiply-add) over all moduli."""
    return moduli * 2 * m * k * n


def kernel_bytes(moduli: int, m: int, k: int, n: int) -> int:
    """int8 residues of A and B read once, of C written once."""
    return moduli * (m * k + k * n + m * n)


def kernel_least_s(moduli: int, m: int, k: int, n: int, peaks) -> float:
    return max(kernel_ops(moduli, m, k, n) / peaks.int8_ops_per_s,
               kernel_bytes(moduli, m, k, n) / peaks.hbm_bytes_per_s)
