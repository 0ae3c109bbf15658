"""G-vector engine tests (mirrors reference apps/unit_tests/test_gvec.cpp:
index round-trips, completeness of the sphere, shell ordering)."""

import numpy as np
import pytest

from sirius_tpu.core import Gvec, GkVec, FFTGrid
from sirius_tpu.core.gvec import reciprocal_lattice


@pytest.fixture(scope="module")
def si_lattice():
    a = 10.26
    return a / 2 * np.array([[0.0, 1, 1], [1, 0, 1], [1, 1, 0]])


def test_reciprocal_orthogonality(si_lattice):
    b = reciprocal_lattice(si_lattice)
    assert np.allclose(si_lattice @ b.T, 2 * np.pi * np.eye(3))


def test_sphere_complete_and_sorted(si_lattice):
    gv = Gvec.build(si_lattice, gmax=8.0)
    # all |G| <= gmax, sorted ascending
    glen = np.sqrt(gv.glen2)
    assert glen.max() <= 8.0 + 1e-8
    assert np.all(np.diff(glen) > -1e-8)
    # G=0 first
    assert np.all(gv.millers[0] == 0)
    # completeness: brute-force count over a larger box
    b = gv.recip
    n = 20
    rng = np.arange(-n, n + 1)
    hh, kk, ll = np.meshgrid(rng, rng, rng, indexing="ij")
    m = np.stack([hh.ravel(), kk.ravel(), ll.ravel()], axis=1)
    g2 = np.sum((m @ b) ** 2, axis=1)
    assert gv.num_gvec == int(np.sum(g2 <= 64.0 + 1e-8))
    # inversion symmetry of the set
    idx = gv.index_of_millers(-gv.millers)
    assert np.all(idx >= 0)


def test_shells(si_lattice):
    gv = Gvec.build(si_lattice, gmax=6.0)
    # shell values strictly increasing; every G maps to its shell value
    assert np.all(np.diff(gv.shell_g2) > 0)
    assert np.allclose(gv.shell_g2[gv.shell_idx], gv.glen2, atol=1e-6)


def test_fft_index_roundtrip(si_lattice):
    gv = Gvec.build(si_lattice, gmax=8.0)
    # unique indices, and decoding the linear index reproduces the Miller set
    assert len(np.unique(gv.fft_index)) == gv.num_gvec
    n1, n2, n3 = gv.fft.dims
    h = gv.fft_index // (n2 * n3)
    k = (gv.fft_index // n3) % n2
    l = gv.fft_index % n3
    dec = np.stack([h, k, l], axis=1).astype(np.int64)
    # wrap back to signed
    dims = np.array([n1, n2, n3])
    signed = (dec + dims // 2) % dims - dims // 2
    assert np.all(signed == (gv.millers + dims // 2) % dims - dims // 2)


def test_gkvec_padding(si_lattice):
    gv = Gvec.build(si_lattice, gmax=12.0)
    fft = FFTGrid.for_cutoff(si_lattice, 2 * 6.0)
    kpts = np.array([[0.0, 0, 0], [0.25, 0.25, 0.25], [0.5, 0, 0]])
    gk = GkVec.build(gv, kpts, gk_cutoff=6.0, fft=fft)
    assert gk.num_kpoints == 3
    assert gk.millers.shape[1] == gk.num_gk.max()
    for ik in range(3):
        n = gk.num_gk[ik]
        lens = np.linalg.norm(gk.gkcart[ik, :n], axis=1)
        assert lens.max() <= 6.0 + 1e-8
        assert np.all(gk.mask[ik, :n] == 1.0)
        assert np.all(gk.mask[ik, n:] == 0.0)
    # Gamma sphere is inversion symmetric
    assert gk.num_gk[0] % 2 == 1


@pytest.mark.parametrize("sign", [1.0, -1.0])
def test_phase_factors_are_the_exponential_to_the_bit(si_lattice, sign,
                                                      monkeypatch):
    """phase_factors is cos + i sin of the real angle on several threads:
    the numbers np.exp(sign 2 pi i m . x) gives, not merely close ones (two
    tier-1 tests sit within the last bits of their thresholds), whatever the
    block size and the number of rows."""
    from sirius_tpu.core import gvec as gm

    gv = Gvec.build(si_lattice, gmax=9.0)
    x = np.random.default_rng(3).random((5, 3))
    want = np.exp(sign * 2j * np.pi * (gv.millers @ x.T))
    assert np.array_equal(gm.phase_factors(gv.millers, x, sign), want)
    monkeypatch.setattr(gm, "_PHASE_ROWS", 37)  # many blocks, a ragged last
    assert np.array_equal(gm.phase_factors(gv.millers, x, sign), want)
    one = gm.phase_factors(gv.millers[:7], x[0], sign)  # one atom, as a row
    assert one.shape == (7, 1) and np.array_equal(one[:, 0], want[:7, 0])
    assert gm.phase_factors(gv.millers[:0], x, sign).shape == (0, 5)


def test_index_of_millers_is_the_dictionary_lookup(si_lattice):
    fine = Gvec.build(si_lattice, gmax=9.0)
    coarse = Gvec.build(si_lattice, gmax=5.0)
    lut = {tuple(m): i for i, m in enumerate(fine.millers)}
    n1 = fine.fft.dims[0]
    asked = np.vstack([coarse.millers, fine.millers[::-1],
                       [[n1, 0, 0], [0, -n1, 0], [n1 // 2 + 1, 1, 0]],
                       [[7, -7, 7]]])
    want = np.array([lut.get(tuple(m), -1) for m in asked])
    got = fine.index_of_millers(asked)
    assert got.dtype == np.int64 and np.array_equal(got, want)
    assert (want[-4:-1] == -1).all()  # outside the box: absent, not wrapped


def test_shells_in_one_pass_are_the_rule_value_by_value(si_lattice):
    from sirius_tpu.core import gvec as gm

    def by_rule(glen2):
        idx, first, cur = [], [], -1.0
        for g2 in glen2:
            if not first or g2 - cur > gm._SHELL_TOL * max(1.0, g2):
                cur = g2
                first.append(g2)
            idx.append(len(first) - 1)
        return np.array(idx), np.array(first)

    gv = Gvec.build(si_lattice, gmax=9.0)
    # a chain of values each within the tolerance of its neighbour but not
    # of the shell's first: the pass over neighbours would merge them
    chain = 1.0 + 0.6e-8 * np.arange(6)
    for glen2 in (gv.glen2, np.concatenate([[0.0], chain, [2.0, 2.0]])):
        idx, first = gm._shells(glen2)
        want_idx, want_first = by_rule(glen2)
        assert np.array_equal(idx, want_idx)
        assert np.array_equal(first, want_first)
    assert len(gm._shells(chain)[1]) > 1


@pytest.mark.parametrize("natoms", [1, 2, 7, 54])
def test_atom_sum_is_the_indexed_copys_sum_to_the_bit(si_lattice, natoms,
                                                     monkeypatch):
    """atom_sum adds a phase table's columns in the order numpy adds those
    of ``table[:, every atom]`` (a copy laid out atom by atom), which a sum
    along the table's own rows does not, whatever the block size."""
    from sirius_tpu.core import gvec as gm

    gv = Gvec.build(si_lattice, gmax=9.0)
    x = np.random.default_rng(5).random((natoms, 3))
    table = gm.phase_factors(gv.millers, x, -1.0)
    want = table[:, np.ones(natoms, dtype=bool)].sum(axis=1)
    assert np.array_equal(gm.atom_sum(table), want)
    monkeypatch.setattr(gm, "_SUM_ROWS", 37)  # many blocks, a ragged last
    assert np.array_equal(gm.atom_sum(table), want)
    if natoms == 54:  # the pairwise sum along a row is another number
        assert not np.array_equal(table.sum(axis=1), want)
    assert gm.atom_sum(table[:0]).shape == (0,)
