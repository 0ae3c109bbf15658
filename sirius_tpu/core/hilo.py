"""Reductions that keep more than one word of their result.

A total energy of a few hundred Ha is summed from millions of terms, and the
SCF loop asks whether it moved by 1e-5 Ha. In float32 one scalar at 250 Ha
resolves 1.5e-5 Ha, so a plain sum decides that test by its own rounding. The
reductions here return the sum as an unevaluated pair ``hi + lo`` of the
working precision (error-free transformations: Knuth's two-sum, Dekker's
product of half-words, and the Ogita-Rump-Oishi compensated dot product
arranged as a tree), so the pair carries about twice the digits of one word. The host adds the two words in float64.

In float64 the plain sum already resolves 1e-13 Ha at that size:
``dot_scaled`` then computes it as it always did, with ``lo`` zero, so the
complex128 program is the one it was.
"""

from __future__ import annotations

import struct
from functools import partial

import jax
import jax.numpy as jnp


def two_sum(a, b):
    """``s, e`` with ``s = fl(a + b)`` and ``s + e = a + b`` exactly."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _renorm(hi, lo):
    """The same value with ``|lo|`` at most half an ulp of ``hi``
    (``|hi| >= |lo|`` on entry)."""
    s = hi + lo
    return s, lo - (s - hi)


def _halves(a):
    """``a = h + l`` exactly, ``h`` the upper 12 bits of the float32
    significand: a product of two halves fits one word, so it is exact, and
    stays exact when a compiler contracts it into a fused multiply-add. Cut
    by a mask, not by Veltkamp's ``c - (c - a)``: that one the XLA CPU
    backend's contraction turns into a full-length ``h``."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    h = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFFF000), a.dtype)
    return h, a - h


def two_prod(a, b):
    """``p, e`` with ``p + e = a * b`` to 2**-33 of the product: ``p`` is the
    product of the upper halves (exact), ``e`` the three small cross terms
    (Dekker's product without the step that subtracts ``fl(a * b)``, which
    contraction would subtract unrounded)."""
    ah, al = _halves(a)
    bh, bl = _halves(b)
    return ah * bh, (ah * bl + al * bh) + al * bl


def sum_hilo(x, e=None):
    """Sum of ``x`` (and of the small corrections ``e``, one per element) as
    a pair: a tree of two-sums whose rounding errors are summed beside it."""
    x = x.ravel()
    e = jnp.zeros_like(x) if e is None else e.ravel()
    levels = max(x.shape[0] - 1, 0).bit_length()
    short = (1 << levels) - x.shape[0]
    x, e = jnp.pad(x, (0, short)), jnp.pad(e, (0, short))
    for level in reversed(range(levels)):
        n = 1 << level
        x, d = two_sum(x[:n], x[n:])
        e = e[:n] + e[n:] + d
    return _renorm(x[0], e[0])


def add_pairs(p, q):
    """Sum of two pairs as a pair."""
    s, d = two_sum(p[0], q[0])
    return _renorm(s, (p[1] + q[1]) + d)


def compensated(dtype) -> bool:
    """Whether a sum over this (real or complex) type keeps a second word."""
    return jnp.finfo(dtype).dtype == jnp.float32


def pair_eps(dtype) -> float:
    """Relative resolution of what ``dot_scaled`` returns in ``dtype``."""
    eps = float(jnp.finfo(dtype).eps)
    return eps * eps if compensated(dtype) else eps


def _float32(v: float) -> float:
    """``v`` rounded to float32, as a host float."""
    return struct.unpack("f", struct.pack("f", v))[0]


@partial(jax.jit, static_argnames=("scale",))
def dot_scaled(a, b, scale: float):
    """``scale * sum(a * b)`` of two real arrays as a pair ``(hi, lo)``.
    ``scale`` is a host number (a volume element) and is split into two words
    itself: its rounding alone would cost 6e-8 of the result."""
    if not compensated(a.dtype):
        return jnp.sum(a * b) * scale, jnp.zeros((), dtype=a.dtype)
    hi, lo = sum_hilo(*two_prod(a.ravel(), b.ravel()))
    c_hi = _float32(scale)
    c_lo = _float32(scale - c_hi)
    p, e = two_prod(hi, jnp.asarray(c_hi, dtype=hi.dtype))
    return _renorm(p, e + (hi * c_lo + lo * c_hi))


def cdot_scaled(a, b, scale: float):
    """``scale * Re sum(conj(a) * b)`` of two complex arrays as a pair."""
    if not compensated(a.dtype):
        return (jnp.real(jnp.sum(jnp.conj(a) * b)) * scale,
                jnp.zeros((), dtype=jnp.finfo(a.dtype).dtype))
    return dot_scaled(
        jnp.concatenate([jnp.real(a).ravel(), jnp.imag(a).ravel()]),
        jnp.concatenate([jnp.real(b).ravel(), jnp.imag(b).ravel()]),
        scale,
    )
