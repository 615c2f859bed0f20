"""Double-double (compensated) arithmetic for exact CRT evaluation.

A value is represented as an unevaluated sum hi + lo with |lo| <= ulp(hi)/2,
giving ~2x the mantissa bits of the base dtype (106 bits for float64).  Used
by Scheme II to evaluate Garner's mixed-radix polynomial, whose value can be
a ~120-bit integer, and round it faithfully to the output precision.

No FMA is assumed (CPU interpret / portable): two_prod uses Dekker/Veltkamp
splitting, which is exact in IEEE arithmetic.
"""

from __future__ import annotations

import jax.numpy as jnp


def _split_constant(dtype) -> float:
    # Veltkamp split constant 2^ceil(t/2) + 1 where t = mantissa bits.
    nmant = jnp.finfo(dtype).nmant  # 52 for f64, 23 for f32
    return float(2 ** ((nmant + 2) // 2) + 1)


def two_sum(a, b):
    """Exact: a + b = s + e."""
    s = a + b
    bb = s - a
    e = (a - (s - bb)) + (b - bb)
    return s, e


def quick_two_sum(a, b):
    """Exact when |a| >= |b|: a + b = s + e."""
    s = a + b
    e = b - (s - a)
    return s, e


def _veltkamp(a):
    c = _split_constant(a.dtype) * a
    hi = c - (c - a)
    lo = a - hi
    return hi, lo


def two_prod(a, b):
    """Exact: a * b = p + e (Dekker, FMA-free)."""
    p = a * b
    ah, al = _veltkamp(a)
    bh, bl = _veltkamp(b)
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, e


def mul_scalar(hi, lo, c: int):
    """(hi, lo) * c  for a small integer constant c (e.g. a modulus).

    hi * c is formed exactly as two products: Veltkamp's split by the
    constant's own width w (factor 2^w + 1) leaves a head of t - w bits
    and a tail of at most w, each of whose products with c is exact, and
    a two-sum of those gives the same (p, e) pair as Dekker's product.
    The split scales hi by 2^w + 1 only, not by 2^ceil(t/2) + 1: where
    float64 has float32's exponent range (a TPU), a Horner value near
    2^110 times 2^27 would overflow to inf and the error term to NaN.
    """
    c = int(c)
    width = max(1, c.bit_length())
    t = hi * float(2 ** width + 1)
    head = t - (t - hi)
    c_ = jnp.asarray(float(c), dtype=hi.dtype)
    p1, p2 = two_sum(head * c_, (hi - head) * c_)
    p2 = p2 + lo * c_
    return quick_two_sum(p1, p2)


def add_scalar_array(hi, lo, x):
    """(hi, lo) + x  for an array of dtype-exact values (digits < 256)."""
    s, e = two_sum(hi, x)
    e = e + lo
    return quick_two_sum(s, e)


def add2(hi1, lo1, hi2, lo2):
    """(hi1, lo1) + (hi2, lo2), sloppy (single-branch) dd addition."""
    s, e = two_sum(hi1, hi2)
    e = e + lo1 + lo2
    return quick_two_sum(s, e)


def split_const(_: float, exact_int: int):
    """Represent a (possibly >53-bit) python integer as a dd constant."""
    hi = float(exact_int)
    lo = float(exact_int - int(hi))
    return hi, lo
