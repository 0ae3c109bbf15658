"""The band solve's trip count is dynamic (solvers/davidson.py, THE TRIP
COUNT): both loops of davidson() are while_loops bounded by num_steps that end
when no band is unconverged, by the reference's rule (a step's move of the
eigenvalue, iterative_solver.converge_by_energy) or by the residual norms,
either floored at the working precision's resolution. The solve hands back
the steps and chunks it ran; the SCF loop books its counters from them."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.config.schema import load_config
from sirius_tpu.dft.mixer import initial_res_tol
from sirius_tpu.solvers.davidson import REFRESH_EVERY, davidson, max_chunks

# the module: the package's attribute of that name is the function
dav = importlib.import_module("sirius_tpu.solvers.davidson")

N, NB, NUM_STEPS = 160, 6, 40
DTYPES = [np.complex128, np.complex64]


def _apply(h, x):
    return x @ h.T, x


def _problem(dtype, seed=0, spread=0.1, n=N):
    """A dense Hermitian H with a graded diagonal (the preconditioner's), a
    random start block; `spread` sets the gaps, so how fast it converges."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    x0 = rng.standard_normal((NB, n)) + 1j * rng.standard_normal((NB, n))
    if np.dtype(dtype).kind == "f":
        a, x0 = a.real, x0.real
    d = spread * np.arange(n)
    h = 0.05 * (a + a.conj().T) + np.diag(d)
    rdt = np.finfo(dtype).dtype
    return (jnp.asarray(h, dtype), jnp.asarray(x0.astype(dtype)),
            jnp.asarray(d, rdt), jnp.ones(n, rdt), np.linalg.eigvalsh(h)[:NB])


def _solve(p, **kw):
    h, x0, d, one, _ = p
    kw.setdefault("num_steps", NUM_STEPS)
    ev, x, rn, ran = davidson(_apply, h, x0, d, one, one, **kw)
    return np.asarray(ev), np.asarray(rn), [int(v) for v in np.asarray(ran)]


def _static(p, num_steps=NUM_STEPS):
    """The form the loop had: every step taken. With no floor under it a bar
    of zero is never met (a fresh jit: the floor is read when tracing)."""
    h, x0, d, one, _ = p
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dav, "TOL_FLOOR_EPS", 0.0)
        ev, _, rn, ran = jax.jit(
            lambda *a: dav.davidson.__wrapped__(
                _apply, *a, num_steps=num_steps, res_tol=0.0))(
                    h, x0, d, one, one)
    assert int(ran[0]) == num_steps
    return np.asarray(ev), np.asarray(rn)


@pytest.mark.parametrize("by_energy", [True, False])
@pytest.mark.parametrize("dtype", DTYPES)
def test_a_solve_that_converges_stops_early(dtype, by_energy):
    p = _problem(dtype)
    tol = 1e-5
    ev, rn, (steps, chunks) = _solve(p, res_tol=tol, by_energy=by_energy)
    ev_static, _ = _static(p)
    assert 1 <= steps < NUM_STEPS
    assert chunks == max_chunks(steps)
    # an eigenvalue is second order in the residual: within the bar either way
    assert np.abs(ev - ev_static).max() <= tol
    if not by_energy:
        # the residual rule's exit is what it says (rounding of the fresh
        # application on exit aside)
        assert rn.max() <= 2 * tol


@pytest.mark.parametrize("by_energy", [True, False])
@pytest.mark.parametrize("dtype", DTYPES)
def test_a_looser_bar_takes_fewer_steps(dtype, by_energy):
    p = _problem(dtype)
    loose = _solve(p, res_tol=1e-2, by_energy=by_energy)[2][0]
    tight = _solve(p, res_tol=1e-5, by_energy=by_energy)[2][0]
    assert loose < tight < NUM_STEPS


@pytest.mark.parametrize("num_steps", [12, 7, 3])
@pytest.mark.parametrize("dtype", DTYPES)
def test_a_solve_that_cannot_converge_runs_the_bound(dtype, num_steps):
    """num_steps is the maximum, a partial last chunk included: a slow
    problem (small gaps) under a bar it cannot meet in so few steps."""
    _, rn, (steps, chunks) = _solve(
        _problem(dtype, spread=0.02), num_steps=num_steps, res_tol=1e-7)
    assert rn.max() > 1e-4
    assert (steps, chunks) == (num_steps, -(-num_steps // REFRESH_EVERY))


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_bar_below_the_resolution_is_floored(dtype):
    """An energy bar no eigenvalue of the precision resolves gives the steps
    the floor gives: TOL_FLOOR_EPS * eps * max(1, max|e|)."""
    p = _problem(dtype)
    eps = np.finfo(dtype).eps
    floor = dav.TOL_FLOOR_EPS * eps * max(1.0, np.abs(p[4]).max())
    at_floor = _solve(p, res_tol=floor, by_energy=True)
    below = _solve(p, res_tol=1e-3 * eps, by_energy=True)
    assert below[2] == at_floor[2] and below[2][0] < NUM_STEPS
    assert np.array_equal(below[0], at_floor[0])


def _solve_set(ps, shape, **kw):
    """The problems `ps` as one set of lanes [*shape]: the stages vmapped
    over the set, the loops outside (what parallel/batched.py does)."""
    num_steps = kw.pop("num_steps", NUM_STEPS)
    res_tol = kw.pop("res_tol")
    h, x0, d, one = (
        jnp.stack([p[i] for p in ps]).reshape(shape + ps[0][i].shape)
        for i in range(4))

    def stage(name):
        def lane(h, d, one, *blocks):
            return getattr(dav.stages(_apply, h, d, one, one, res_tol, **kw),
                           name)(*blocks)

        for _ in shape:
            lane = jax.vmap(lane)
        return lambda *blocks: lane(h, d, one, *blocks)

    st = dav.Stages(*map(stage, dav.Stages._fields))
    return jax.jit(lambda x0: dav.solve(st, x0, num_steps)), x0


@pytest.mark.parametrize("shape", [(3,), (2, 2)], ids=["k", "k-spin"])
@pytest.mark.parametrize("by_energy", [True, False])
@pytest.mark.parametrize("dtype", DTYPES)
def test_over_a_set_the_count_is_the_slowest_lanes(dtype, by_energy, shape):
    """A set's solve is one pair of loops over vmapped stages: one count for
    the set, its slowest lane's. A lane that is done is held (HOLD), so its
    answer is its own solve's to rounding, whatever its neighbours need."""
    ps = [_problem(dtype, seed=s, spread=sp)
          for s, sp in ((1, 0.4), (2, 0.1), (3, 0.03), (4, 0.2))]
    ps = ps[:int(np.prod(shape))]
    kw = dict(num_steps=NUM_STEPS, res_tol=1e-4, by_energy=by_energy)
    alone = [_solve(p, **kw) for p in ps]
    steps = [a[2][0] for a in alone]
    assert len(set(steps)) > 1 and max(steps) < NUM_STEPS
    solve, x0 = _solve_set(ps, shape, **kw)
    ev, x, rn, ran = solve(x0)
    assert ev.shape == shape + (NB,) and x.shape == x0.shape
    assert rn.shape == ev.shape and ran.shape == (2,)
    assert [int(v) for v in ran] == [max(steps), max_chunks(max(steps))]
    tol = 50 * np.finfo(dtype).eps * np.abs(ps[0][4]).max()
    for lane, a in zip(np.asarray(ev).reshape(-1, NB), alone):
        assert np.abs(lane - a[0]).max() <= tol


def test_a_finished_problem_is_held_to_the_bit():
    """HOLD: stepped with every band converged, a problem keeps X, H X and
    S X as they are and its P becomes zero; with one band unconverged the
    step moves it."""
    h, x0, d, one, _ = _problem(np.complex128)
    st = dav.stages(_apply, h, d, one, one, 1e-6)
    x = st.start(x0)
    hx, sx, hp, sp = st.refresh(x, jnp.zeros_like(x))
    blocks = (x, hx, sx, jnp.zeros_like(x), hp, sp)
    held = st.step(*blocks, jnp.ones(NB, bool))
    for new, old in zip(held[:3], blocks[:3]):
        assert np.array_equal(np.asarray(new), np.asarray(old))
    assert not np.any(np.asarray(held[3])) and not np.any(np.asarray(held[4]))
    live = st.step(*blocks, jnp.ones(NB, bool).at[0].set(False))
    assert np.abs(np.asarray(live[0] - x)).max() > 1e-3


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _programs(by_energy, dtype=np.float32):
    """davidson() of one problem and a set's solve of two, with arguments."""
    p = _problem(dtype)
    h, x0, d, one, _ = p
    tol = np.finfo(dtype).dtype.type(1e-4)
    one_problem = (lambda x0: davidson(
        _apply, h, x0, d, one, one, num_steps=20, res_tol=tol,
        by_energy=by_energy)), x0
    return {"one": one_problem,
            "set": _solve_set([p, p], (2,), num_steps=20, res_tol=tol,
                              by_energy=by_energy)}


@pytest.mark.parametrize("which", ["one", "set"])
def test_the_loops_predicates_are_scalars(which):
    """What the k-set programs rely on: a set's loops are the one problem's,
    with scalar predicates, so the lowering wraps no carried block in a
    select (a vmapped while_loop gets a batched predicate and a select
    around every block, every step: the form that did not come back on the
    chip, PERF.md section 6, PR 37)."""
    solve, x0 = _programs(True, np.complex64)[which]
    assert [eqn.params["cond_jaxpr"].jaxpr.outvars[0].aval.shape
            for eqn in _eqns(jax.make_jaxpr(solve)(x0).jaxpr)
            if eqn.primitive.name == "while"] == [(), ()]


@pytest.mark.parametrize("which", ["one", "set"])
@pytest.mark.parametrize("by_energy", [True, False])
def test_the_program_holds_one_step_body_and_no_conditional(by_energy, which):
    """One loop over the chunks around one loop over the steps: the step's
    two eigenproblems and ortho's one are the program's three (a copy of the
    body a chunk was 215 MB of code on a 648-row solve), and nothing chooses
    by lax.cond (non-finite fields on the TPU once vmapped, PERF.md PR 27)."""
    solve, x0 = _programs(by_energy)[which]
    prims = [eqn.primitive.name
             for eqn in _eqns(jax.make_jaxpr(solve)(x0).jaxpr)]
    assert prims.count("while") == 2
    assert sum(p.startswith("eigh") for p in prims) == 3
    assert "cond" not in prims and "scan" not in prims
    hlo = jax.jit(solve).lower(x0).compile().as_text()
    assert " conditional(" not in hlo


def test_count_solve_books_what_ran():
    """H applications, boxes, eigenproblems and steps of one solve of a set
    from the fetched (steps, chunks): 2nb rows a chunk, nb a step and on
    exit; the steps of the longest loop."""
    from collections import defaultdict

    nb = 7
    c = defaultdict(int)
    dav.count_solve(c, np.array([[3, 1], [12, 3]]), nb, copies=2)
    rows = 2 * (2 * nb * 1 + nb * 4) + 2 * (2 * nb * 3 + nb * 13)
    assert c["num_loc_op_applied"] == rows == 2 * (
        dav.num_applies(3, 1, nb) + dav.num_applies(12, 3, nb))
    assert c["num_fft_boxes"] == 2 * rows
    assert c["num_subspace_eigh"] == 2 * (7 + 25)
    assert c["num_davidson_steps"] == 12
    # two real rows a box (the Gamma path), a list of loops (its spins)
    c = defaultdict(int)
    dav.count_solve(c, [np.array([3, 1]), np.array([4, 1])], nb,
                    rows_per_box=2)
    assert c["num_loc_op_applied"] == nb * (2 + 4) + nb * (2 + 5)
    assert c["num_fft_boxes"] == 2 * 2 * nb + 2 * 9 * 4  # ceil(14/2), ceil(7/2)
    assert c["num_davidson_steps"] == 4


@pytest.mark.parametrize("by_energy, start", [(1, 1e-2), (0, 1e-6)])
def test_the_configuration_chooses_the_rule_and_the_first_bar(by_energy,
                                                              start):
    """converge_by_energy 1 (the reference's default): the eigenvalue rule
    from energy_tolerance; 0: the residual rule from residual_tolerance."""
    from sirius_tpu.dft import band_solve
    from sirius_tpu.serve.scheduler import build_job_context

    deck = {"parameters": {"gk_cutoff": 3.0, "pw_cutoff": 7.0,
                           "ngridk": [1, 1, 1], "num_bands": 8,
                           "use_symmetry": False, "xc_functionals":
                           ["XC_LDA_X", "XC_LDA_C_PZ"]},
            "synthetic": {"ultrasoft": True}}
    assert load_config(deck).iterative_solver.converge_by_energy == 1
    deck["iterative_solver"] = {"converge_by_energy": by_energy}
    cfg = load_config(deck)
    assert initial_res_tol(cfg.iterative_solver) == start
    ctx = build_job_context(cfg, ".")
    band = band_solve.choose(
        ctx, cfg, jax.devices()[1:2], serial_bands=False, hub=None,
        paw=None, mgga=False, wf_dtype=jnp.complex128)
    assert band.by_energy is bool(by_energy)
    assert band.num_steps == cfg.iterative_solver.num_steps


def _scf(by_energy, devices, ngridk=(1, 1, 1)):
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.serve.scheduler import build_job_context

    cfg = load_config({
        "parameters": {"gk_cutoff": 3.0, "pw_cutoff": 7.0,
                       "ngridk": list(ngridk), "num_bands": 8,
                       "use_symmetry": False, "num_dft_iter": 40,
                       "density_tol": 1e-8, "energy_tol": 1e-9,
                       "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"]},
        "iterative_solver": {"converge_by_energy": by_energy},
        "control": {"verbosity": 0},
        "synthetic": {"ultrasoft": True}})
    return run_scf(cfg, ctx=build_job_context(cfg, "."), devices=devices)


@pytest.mark.parametrize("ngridk, path", [
    ((1, 1, 1), "gamma"), ((2, 2, 2), "batched+fused")])
def test_both_rules_reach_one_energy_in_fewer_steps_than_the_bound(ngridk,
                                                                   path):
    one = jax.devices()[1:2]
    e, r = _scf(1, one, ngridk), _scf(0, one, ngridk)
    for out in (e, r):
        assert out["converged"] and out["placement"]["path"] == path
        per_iter = (out["counters"]["num_davidson_steps"]
                    / out["num_scf_iterations"])
        assert 1.0 <= per_iter < 20.0
    assert abs(e["energy"]["total"] - r["energy"]["total"]) <= 1e-8
    # the eigenvalue rule starts at 1e-2 and follows the density residual;
    # the residual rule starts at 1e-6: more steps for the same answer
    assert (e["counters"]["num_davidson_steps"]
            < r["counters"]["num_davidson_steps"])
