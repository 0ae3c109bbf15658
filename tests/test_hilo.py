"""core/hilo.py: sums that keep two words of their result."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.core import hilo


@pytest.fixture(scope="module")
def integrands():
    """Two float32 arrays of a fine grid's size whose product sums to a few
    hundred, one term (the G = 0 one of a density times a potential) far
    above the rest."""
    rng = np.random.default_rng(7)
    n = 144 * 144 * 72
    a = rng.normal(size=n).astype(np.float32)
    b = (0.3 * a + 0.1 * rng.normal(size=n)).astype(np.float32)
    a[0], b[0] = 216.0, -0.9
    return a, b


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_pair_carries_what_one_word_drops(integrands, jit):
    a, b = integrands
    scale = 7290.1234567 / a.size * 0.1
    exact = float(np.sum(a.astype(np.float64) * b.astype(np.float64))) * scale
    assert 100.0 < abs(exact) < 1000.0
    f = (lambda x, y: hilo.dot_scaled(x, y, scale))
    hi, lo = (jax.jit(f) if jit else f)(jnp.asarray(a), jnp.asarray(b))
    assert hi.dtype == lo.dtype == jnp.float32
    assert abs(float(lo)) <= np.spacing(np.float32(abs(float(hi))))
    assert abs(float(hi) + float(lo) - exact) <= 1e-8 * abs(exact) <= 1e-5
    # one word cannot: its spacing at this size is what the pair is for
    assert np.spacing(np.float32(abs(exact))) > 1e-5


def test_complex_dot_and_pair_sum(integrands):
    a, b = integrands
    n = 1 << 16
    x = (a[:n] + 1j * b[:n]).astype(np.complex64)
    y = (b[n:2 * n] - 0.5j * a[n:2 * n]).astype(np.complex64)
    exact = float(np.real(np.sum(np.conj(x.astype(np.complex128))
                                 * y.astype(np.complex128)))) * 3.3
    p = hilo.cdot_scaled(jnp.asarray(x), jnp.asarray(y), 3.3)
    assert abs(float(p[0]) + float(p[1]) - exact) <= 1e-9 * max(abs(exact), 1.0)
    q = hilo.add_pairs(p, p)
    assert abs(float(q[0]) + float(q[1]) - 2 * exact) <= 2e-9 * max(abs(exact), 1.0)


def test_float64_keeps_its_plain_sum(integrands):
    a, b = (jnp.asarray(v.astype(np.float64)) for v in integrands)
    hi, lo = hilo.dot_scaled(a, b, 0.5)
    assert hi.dtype == jnp.float64 and float(lo) == 0.0
    assert float(hi) == float(jnp.sum(a * b) * 0.5)
    assert not hilo.compensated(jnp.float64) and hilo.compensated(jnp.float32)
    assert hilo.pair_eps(jnp.float32) < hilo.pair_eps(jnp.float64) * 1e2


def test_two_sum_and_two_prod_are_error_free():
    rng = np.random.default_rng(3)
    a = (rng.normal(size=4096) * 10.0 ** rng.integers(-3, 3, 4096)).astype(np.float32)
    b = (rng.normal(size=4096) * 10.0 ** rng.integers(-3, 3, 4096)).astype(np.float32)
    s, e = jax.jit(hilo.two_sum)(a, b)
    got = np.asarray(s, np.float64) + np.asarray(e, np.float64)
    assert np.array_equal(got, a.astype(np.float64) + b.astype(np.float64))
    p, e = jax.jit(hilo.two_prod)(a, b)
    got = np.asarray(p, np.float64) + np.asarray(e, np.float64)
    want = a.astype(np.float64) * b.astype(np.float64)
    assert np.all(np.abs(got - want) <= 2.0 ** -32 * np.abs(want))
