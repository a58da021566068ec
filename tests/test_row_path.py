"""The super-tick's woken-row path against a plain Eq. 4 oracle.

The engine updates the woken rows one way: gather the rows and their
constants, sum their neighbours, apply Eq. 4, scatter the rows back.
These tests drive ``AsyncEngine.step`` with forced wake sets and hold the
rows it writes to :func:`eq4_rows_ref`, which is written from Eq. 4 and
the per-point L1 gradient clip alone, and the rows it must not write to
the bit.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AgentData, DPConfig, make_objective
from repro.core.coordinate_descent import eq4_agent_constants
from repro.core.graph import as_csr, csr_from_coo
from repro.sim import AsyncEngine, CDUpdate, DPCDUpdate

TOL = 4e-6  # f32 reduction order: the oracle's einsums vs the engine's CSR sums


def eq4_rows_ref(theta, rows, idx, w, coef, X, y, mask, clip=None):
    """Theta after one Eq. 4 step of the woken ``rows``, written back.

    theta: (n, p); rows: (B,) distinct agents; idx/w: (B, K) their padded
    neighbour tables; coef: (B, 4) per-row [alpha, deg, mu*conf, 2*lam];
    X: (B, m, p), y/mask: (B, m) their padded data. Quadratic loss with
    an optional per-point L1 gradient clip, in f32. Returns (n, p).
    """
    f32 = jnp.float32
    t32 = jnp.asarray(theta, f32)
    tr = t32[rows]  # (B, p)
    neigh = jnp.einsum("bk,bkp->bp", jnp.asarray(w, f32), t32[idx])
    X32 = jnp.asarray(X, f32)
    resid = 2.0 * (jnp.einsum("bmp,bp->bm", X32, tr) - jnp.asarray(y, f32))
    if clip is not None:
        norms = jnp.abs(resid) * jnp.sum(jnp.abs(X32), axis=-1)
        resid = resid * jnp.minimum(1.0, clip / jnp.maximum(norms, 1e-12))
    m32 = jnp.asarray(mask, f32)
    m_hat = jnp.maximum(jnp.sum(m32, axis=-1), 1.0)
    g_sum = jnp.einsum("bm,bmp->bp", resid * m32, X32)
    c32 = jnp.asarray(coef, f32)
    alpha, deg, cmu, lam2 = c32[:, 0:1], c32[:, 1:2], c32[:, 2:3], c32[:, 3:4]
    grads = g_sum / m_hat[:, None] + lam2 * tr
    new = (1.0 - alpha) * tr + alpha * (neigh / deg - cmu * grads)
    return t32.at[rows].set(new)


def _problem(n, K, m, p, rng, clip=None):
    """A quadratic objective over a weighted circulant graph on which every
    agent has degree K (offsets 1..K//2, and n/2 for odd K)."""
    offsets = list(range(1, K // 2 + 1))
    src = [np.arange(n)] * len(offsets)
    dst = [(np.arange(n) + o) % n for o in offsets]
    if K % 2:
        src.append(np.arange(n // 2))
        dst.append(np.arange(n // 2) + n // 2)
    src, dst = np.concatenate(src), np.concatenate(dst)
    graph = csr_from_coo(n, src, dst, rng.uniform(0.5, 1.5, len(src)), symmetrize=True)
    mask = (rng.random((n, m)) < 0.8).astype(np.float64)
    mask[:, 0] = 1.0
    data = AgentData(
        X=rng.normal(size=(n, m, p)) / np.sqrt(p),
        y=rng.normal(size=(n, m)),
        mask=mask,
    )
    return make_objective(graph, data, "quadratic", mu=0.5, clip=clip, mix_mode="sparse")


def _oracle(obj, theta, rows):
    """:func:`eq4_rows_ref` for ``obj``'s woken ``rows``."""
    idx, w = as_csr(obj.graph).padded_neighbors()
    c = eq4_agent_constants(obj)
    coef = np.stack([c["alpha"], c["deg"], obj.mu * c["conf"], 2.0 * c["lam"]], axis=1)
    out = eq4_rows_ref(
        theta, rows, idx[rows], w[rows], coef[rows],
        c["X"][rows], c["y"][rows], c["mask"][rows], clip=obj.clip,
    )
    return np.asarray(out)


def _wake(n, rows):
    mask = np.zeros(n, bool)
    mask[rows] = True
    return mask


@pytest.mark.parametrize("B,K,m,p,n", [(8, 4, 3, 8, 64), (17, 7, 5, 100, 128),
                                        (1, 3, 2, 128, 32), (40, 10, 4, 200, 256),
                                        (11, 4, 3, 37, 50)])
@pytest.mark.parametrize("clip", [None, 0.7])
def test_woken_rows_match_eq4_oracle(B, K, m, p, n, clip):
    rng = np.random.default_rng(B + p)
    obj = _problem(n, K, m, p, rng, clip=clip)
    assert obj.graph.max_degree() == K
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=0.5 * B, batch_size=B, seed=0)
    theta = rng.normal(size=(n, p)).astype(np.float32)
    rows = np.sort(rng.choice(n, size=B, replace=False))
    state = eng.step(eng.init_state(theta), _wake(n, rows))
    got = np.asarray(state.Theta)
    want = _oracle(obj, theta, rows)
    np.testing.assert_allclose(got[rows], want[rows], rtol=TOL, atol=TOL)
    assert not np.array_equal(got[rows], theta[rows])
    untouched = np.setdiff1d(np.arange(n), rows)
    assert np.array_equal(got[untouched], theta[untouched])
    assert int(state.applied) == B


def test_padding_and_unapplied_rows_never_write():
    """The batch's padding rows (sentinel n), the rows woken past the
    batch and the rows whose update is not applied leave Theta alone.
    Each of them reads row n-1 (``safe`` clamps them there), so a scatter
    that wrote them would write exactly that row."""
    n, B = 64, 8
    rng = np.random.default_rng(11)
    obj = _problem(n, 4, 3, 8, rng, clip=1.0)  # the DP plan needs a clip
    theta = rng.normal(size=(n, 8)).astype(np.float32)
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=4.0, batch_size=B, seed=0)

    # Five rows woken, three padding rows at the sentinel.
    rows = np.array([3, 9, 20, 41, 50])
    got = np.asarray(eng.step(eng.init_state(theta), _wake(n, rows)).Theta)
    assert np.array_equal(got[n - 1], theta[n - 1])
    np.testing.assert_allclose(got, _oracle(obj, theta, rows), rtol=TOL, atol=TOL)

    # Twelve rows woken into a batch of eight: the four largest, n-1 among
    # them, are dropped, not written.
    rows = np.array([0, 5, 7, 12, 18, 25, 33, 40, 47, 55, 60, n - 1])
    state = eng.step(eng.init_state(theta), _wake(n, rows))
    got = np.asarray(state.Theta)
    assert int(state.dropped) == 4
    assert np.array_equal(got[rows[B:]], theta[rows[B:]])
    np.testing.assert_allclose(got, _oracle(obj, theta, rows[:B]), rtol=TOL, atol=TOL)

    # An agent whose privacy budget is spent wakes and is not applied.
    upd = DPCDUpdate.plan(obj, DPConfig(eps_bar=1.0), planned_Ti=1)
    dp = AsyncEngine(upd, slot_wakes=4.0, batch_size=B, seed=0)
    rows = np.array([2, 30, n - 1])
    once = dp.step(dp.init_state(theta), _wake(n, rows))
    assert not np.array_equal(np.asarray(once.Theta)[n - 1], theta[n - 1])
    twice = dp.step(once, _wake(n, rows))
    assert int(twice.applied) == int(once.applied) == len(rows)
    assert np.array_equal(np.asarray(twice.Theta), np.asarray(once.Theta))


@pytest.mark.parametrize("extra", ["none", "seven", "double"])
def test_batch_padding_leaves_theta_bit_identical(extra):
    """One forced wake set gives the same Theta, to the bit, whatever
    static batch size the engine pads its woken rows to (here against
    the batch of every row)."""
    n = 64
    rng = np.random.default_rng(5)
    obj = _problem(n, 4, 3, 8, rng)
    theta = rng.normal(size=(n, 8)).astype(np.float32)
    rows = np.sort(rng.choice(n - 1, size=9, replace=False))
    B = {"none": 9, "seven": 9 + 7, "double": 18}[extra]

    def run(batch_size):
        eng = AsyncEngine(CDUpdate(obj), slot_wakes=4.0, batch_size=batch_size, seed=0)
        state = eng.init_state(theta)
        for _ in range(2):
            state = eng.step(state, _wake(n, rows))
        return np.asarray(state.Theta)

    assert np.array_equal(run(B), run(n))
