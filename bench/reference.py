"""The plain reference of the engine's semantics, and its lower-precision
control. It imports nothing of the program and takes nothing the program
made: it rebuilds the graph, the Eq. 4 constants and the wake sets from
the deployment and the seed, and replays every slot a run trained.

Semantics (paper Eq. 4, with the engine's documented seeded slotting):

* slot t draws its wake set from the t-th key of the chain
  ``key_{t+1}, _, _, k_wake, _, _ = split(key_t, 6)``,
  ``key_0 = PRNGKey(engine_seed)``: agent i wakes when
  ``uniform(k_wake, (n,))[i] < 1 - exp(-r_i tau)`` (f32), and the first B
  woken agents in id order are updated (the rest are dropped);
* under the sharded engine (per-shard clocks) shard s of S holds the
  agents ``placement[s]`` at its R rows (``n`` at padding) and keeps its
  own chain from ``key_0 = fold_in(PRNGKey(engine_seed), s)``: each slot
  it splits its key as above, row r wakes when
  ``uniform(k_wake, (R,))[r] < 1 - exp(-r_i tau)`` (0 at padding), and
  the first B_s woken rows in row order are updated, with B_s the batch
  of the largest shard. The woken rows of every shard are updated
  together from the start-of-slot models (the halo each shard reads is
  that snapshot), so the update is the same Eq. 4. The replay splits the
  shards over the devices it is given (each holding every table), and so
  runs on the cell's chips;
* every woken row reads the start-of-slot models:
  ``theta_i <- (1 - a_i) theta_i + a_i (sum_j W_ij theta_j / D_ii - mu c_i g_i)``
  with ``g_i = (1/m_i) sum_k clip_C(2 (x_k . theta_i - y_k) x_k) + 2 lambda_i theta_i``,
  the clip scaling each point's gradient to L1 norm at most C,
  ``lambda_i = 1/m_i``, ``c_i = m_i / max_j m_j``,
  ``a_i = 1 / (1 + mu c_i L_i)`` and ``L_i = 2 max ||x||^2 + 2 lambda_i``
  (the max over every training point of the population).

The placement is the one thing taken from the program: under per-shard
clocks it decides which agents wake in a slot, never what an update
computes, and the engine alone cuts the graph into shards. It is checked
to be a placement (every id once, S rows) before it is used.

``precision="highest"`` is the reference (f32 products at full
precision); ``"bf16_3x"`` is the control: the same contractions from
three bf16 products (hi*hi + hi*lo + lo*hi), the TPU's ``high``
precision, written out so that it computes the same on any backend.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_HIGHEST = jax.lax.Precision.HIGHEST


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def contract(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` in f32 at the stated precision."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=_HIGHEST)
    if precision != "bf16_3x":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)

    def mm(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def agent_constants(dep, cfg: dict) -> dict:
    """Degrees, confidences, lambdas and alphas of Eq. 4, in f64 on the host."""
    m = dep.mask.sum(axis=1).astype(np.float64)
    deg = np.bincount(dep_edges(dep)[0], minlength=dep.n).astype(np.float64)
    lam = 1.0 / np.maximum(m, 1.0)
    conf = np.clip(m / m.max(), 1e-3, 1.0) if m.max() > 0 else np.full_like(m, 1e-3)
    used = np.unique(dep.train_items[dep.mask > 0])
    # The largest squared norm of a training point, from f32 features.
    sq = float(np.max(np.sum(dep.V[used] ** 2, axis=1, dtype=np.float32)))
    lloc = 2.0 * sq + 2.0 * lam
    alpha = 1.0 / (1.0 + cfg["mu"] * conf * lloc)
    return {"m": m, "deg": deg, "lam": lam, "conf": conf, "alpha": alpha}


def dep_edges(dep) -> tuple[np.ndarray, np.ndarray]:
    """Directed edges of the OR-symmetrised k-NN graph, sorted by row then
    column (unit weights)."""
    n, k = dep.knn.shape
    r = np.repeat(np.arange(n, dtype=np.int64), k)
    c = dep.knn.ravel().astype(np.int64)
    rows = np.concatenate([r, c])
    cols = np.concatenate([c, r])
    keep = rows != cols
    key = np.unique(rows[keep] * n + cols[keep])
    return key // n, key % n


def neighbour_table(dep) -> tuple[np.ndarray, np.ndarray]:
    """(n, K) neighbour ids (own id at padding) and 0/1 weights."""
    rows, cols = dep_edges(dep)
    n = dep.n
    deg = np.bincount(rows, minlength=n)
    K = max(int(deg.max()), 1)
    start = np.concatenate([[0], np.cumsum(deg)[:-1]])
    slot = np.arange(rows.size) - start[rows]
    idx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, K))
    w = np.zeros((n, K), np.float32)
    idx[rows, slot] = cols
    w[rows, slot] = 1.0
    return idx, w


def wake_probability(cfg_slot_wakes: float, n: int) -> np.float32:
    """1 - exp(-r tau) at rate 1 with tau = slot_wakes / n, as f32."""
    return np.float32(-np.expm1(-1.0 * (float(cfg_slot_wakes) / float(n))))


def batch_capacity(prob: float, count: int) -> int:
    """The engine's static batch: mean + 6 sigma + 8 wakes, at most ``count``."""
    mu = float(prob) * count
    return int(min(max(int(np.ceil(mu + 6.0 * np.sqrt(mu) + 8.0)), 8), count))


def _eq4(theta, rows, tab, mu, clip, precision):
    """One slot's Eq. 4 updates of ``rows`` (padded with n) from the
    start-of-slot ``theta``."""
    return theta.at[rows].set(_eq4_rows(theta, rows, tab, mu, clip, precision), mode="drop")


def _eq4_rows(theta, rows, tab, mu, clip, precision):
    """The new models of ``rows`` (padded with n) by Eq. 4 from ``theta``."""
    n = theta.shape[0]
    safe = jnp.minimum(rows, n - 1)
    th = theta[safe]
    neigh = contract("bk,bkp->bp", tab["w"][safe], theta[tab["idx"][safe]], precision)
    X = tab["V"][tab["items"][safe]]  # (B, m, p), zero rows at padding
    r = contract("bmp,bp->bm", X, th, precision) - tab["y"][safe]
    g = 2.0 * r[..., None] * X
    l1 = jnp.sum(jnp.abs(g), axis=-1, keepdims=True)
    g = g * jnp.minimum(1.0, clip / jnp.maximum(l1, 1e-12))
    msk = tab["mask"][safe]
    grad = jnp.sum(g * msk[..., None], axis=1) / jnp.maximum(msk.sum(1), 1.0)[:, None]
    grad = grad + 2.0 * tab["lam"][safe][:, None] * th
    a = tab["alpha"][safe][:, None]
    return (1.0 - a) * th + a * (
        neigh / tab["deg"][safe][:, None] - mu * tab["conf"][safe][:, None] * grad
    )


@partial(jax.jit, static_argnames=("slots", "cap", "precision", "half"), donate_argnums=(0, 2))
def _advance(theta, key, touched, users, tab, prob, mu, clip, *, slots, cap, precision, half):
    """``slots`` slots from ``(theta, key)``; returns the new state, the
    mask of rows updated so far, and the rows of ``users``."""
    n = theta.shape[0]

    def slot(carry, _):
        theta, key, touched = carry
        key, _, _, k_wake, _, _ = jax.random.split(key, 6)
        wake = jax.random.uniform(k_wake, (n,)) < prob
        rows = jnp.nonzero(wake, size=cap, fill_value=n)[0]
        if half:  # a fault: the second half of the woken rows left out
            count = jnp.minimum(wake.sum(), cap)
            rows = jnp.where(jnp.arange(cap) < count // 2, rows, n)
        theta = _eq4(theta, rows, tab, mu, clip, precision)
        touched = touched.at[rows].set(True, mode="drop")
        return (theta, key, touched), None

    (theta, key, touched), _ = jax.lax.scan(slot, (theta, key, touched), None, length=slots)
    return theta, key, touched, theta[users]


@partial(jax.jit, static_argnames=("mesh", "slots", "cap", "precision", "half"),
         donate_argnums=(0, 2))
def _advance_sharded(theta, keys, touched, users, tab, place, probs, mu, clip, *, mesh, slots,
                     cap, precision, half):
    """``slots`` slots under per-shard clocks from ``(theta, keys)``:
    ``place`` (S, R) holds the agent at each shard row (n at padding),
    ``probs`` (S, R) its wake probability (0 at padding). The shards are
    split over ``mesh``'s devices, each holding every table and model:
    a device updates its shards' woken rows, and every device writes all
    of the slot's new rows."""
    n = theta.shape[0]
    R = place.shape[1]

    def shard_rows(key, prob, ids_s):
        key, _, _, k_wake, _, _ = jax.random.split(key, 6)
        wake = jax.random.uniform(k_wake, (R,)) < prob
        local = jnp.nonzero(wake, size=cap, fill_value=R)[0]
        if half:  # a fault: the second half of the shard's woken rows left out
            count = jnp.minimum(wake.sum(), cap)
            local = jnp.where(jnp.arange(cap) < count // 2, local, R)
        return key, ids_s[local]

    def local(theta, keys, touched, tab, place, probs, mu, clip):
        ids = jnp.concatenate([place, jnp.full((place.shape[0], 1), n, place.dtype)], axis=1)

        def slot(carry, _):
            theta, keys, touched = carry
            keys, rows = jax.vmap(shard_rows)(keys, probs, ids)
            rows = rows.ravel()
            new = _eq4_rows(theta, rows, tab, mu, clip, precision)
            rows = jax.lax.all_gather(rows, "shards", tiled=True)
            new = jax.lax.all_gather(new, "shards", tiled=True)
            theta = theta.at[rows].set(new, mode="drop")
            touched = touched.at[rows].set(True, mode="drop")
            return (theta, keys, touched), None

        (theta, keys, touched), _ = jax.lax.scan(slot, (theta, keys, touched), None,
                                                 length=slots)
        return theta, keys, touched

    split, whole = P("shards"), P()
    # Every device ends each slot with the same models (it writes the rows
    # all devices gathered), which the varying type of a gathered value
    # does not show: hence no check of it.
    theta, keys, touched = jax.shard_map(
        local, mesh=mesh, in_specs=(whole, split, whole, whole, split, split, whole, whole),
        out_specs=(whole, split, whole), check_vma=False,
    )(theta, keys, touched, tab, place, probs, mu, clip)
    return theta, keys, touched, theta[users]


def check_placement(placement, n: int, shards: int) -> np.ndarray:
    """``placement`` as an (S, R) int32 array, after checking that it is a
    placement of ``n`` agents on ``shards`` shards: every id in 0..n-1 at
    exactly one row, ``n`` at every other. Raises ValueError otherwise."""
    place = np.asarray(placement)
    if place.ndim != 2 or place.shape[0] != shards:
        raise ValueError(f"placement of shape {place.shape}: want ({shards}, rows)")
    if not np.issubdtype(place.dtype, np.integer):
        raise ValueError(f"placement of dtype {place.dtype}: want integer agent ids")
    real = place[place != n]
    if (real.size != n or real.min(initial=0) < 0 or real.max(initial=0) >= n
            or np.unique(real).size != n):
        raise ValueError(f"placement does not hold each of the {n} agents exactly once")
    return place.astype(np.int32)


def shard_batch(prob, place: np.ndarray, n: int) -> int:
    """B_s: the batch of the largest shard by :func:`batch_capacity`."""
    return batch_capacity(prob, int((place != n).sum(axis=1).max()))


def host_tables(dep, cfg: dict) -> dict:
    """The tables of the replay, on the host."""
    consts = agent_constants(dep, cfg)
    idx, w = neighbour_table(dep)
    f32 = np.float32
    return {
        "idx": idx,
        "w": w,
        "V": np.concatenate([dep.V, np.zeros((1, dep.p), f32)]),
        "items": dep.train_items,
        "y": np.asarray(dep.y, f32),
        "mask": np.asarray(dep.mask, f32),
        **{k: np.asarray(consts[k], f32) for k in ("deg", "conf", "alpha", "lam")},
    }


def tables(dep, cfg: dict) -> dict:
    """The device tables of the replay."""
    return {k: jnp.asarray(v) for k, v in host_tables(dep, cfg).items()}


def replay(dep, cfg: dict, theta0: np.ndarray, seed31: int, prob, slots: int, every: int,
           users: np.ndarray, precision: str, half: bool = False, placement=None,
           shards: int | None = None, devices=None):
    """Replay ``slots`` slots from ``theta0``.

    Returns ``(theta, touched, rows_at)``: the (n, p) f32 models after the
    last slot, the (n,) mask of agents updated at least once, and, for
    every multiple v of ``every`` up to ``slots``, ``rows_at[v]`` the
    (len(users), p) models of ``users`` after slot v (v = 0: ``theta0``).
    ``placement``: None for the single engine's slotting, else the (S, R)
    agent ids of the sharded engine's rows, with ``shards`` = S (see the
    module doc), replayed on ``devices`` (default: the first device;
    their number divides S). ``half`` is a fault for the control's
    readings.
    """
    f32 = jnp.float32
    consts = (f32(cfg["mu"]), f32(cfg["clip"]))
    if placement is None:
        tab = tables(dep, cfg)
        users = jnp.asarray(np.asarray(users, np.int32))
        theta = jnp.asarray(theta0, jnp.float32)
        touched = jnp.zeros(dep.n, bool)
        cap = batch_capacity(prob, dep.n)
        key = jax.random.PRNGKey(seed31)

        def advance(theta, key, touched, step):
            return _advance(theta, key, touched, users, tab, f32(prob), *consts,
                            slots=step, cap=cap, precision=precision, half=half)
    else:
        place = check_placement(placement, dep.n, shards)
        cap = shard_batch(prob, place, dep.n)
        mesh = Mesh(np.asarray(devices or jax.devices()[:1]), ("shards",))
        whole = NamedSharding(mesh, P())
        split = NamedSharding(mesh, P("shards"))
        tab = jax.device_put(host_tables(dep, cfg), whole)
        users, theta, touched = jax.device_put(
            (np.asarray(users, np.int32), np.asarray(theta0, np.float32),
             np.zeros(dep.n, bool)), whole)
        base = jax.random.PRNGKey(seed31)
        key = jax.device_put(
            jax.vmap(lambda s: jax.random.fold_in(base, s))(jnp.arange(place.shape[0])), split)
        probs = jax.device_put(np.where(place < dep.n, np.float32(prob), np.float32(0.0)), split)
        place = jax.device_put(place, split)
        consts = jax.device_put(consts, whole)

        def advance(theta, key, touched, step):
            return _advance_sharded(theta, key, touched, users, tab, place, probs, *consts,
                                    mesh=mesh, slots=step, cap=cap, precision=precision,
                                    half=half)
    rows_at = {0: theta[users]}
    done = 0
    while done < slots:
        step = min(every, slots - done)
        theta, key, touched, rows = advance(theta, key, touched, step)
        done += step
        if done % every == 0:
            rows_at[done] = rows
    theta, touched, rows_at = jax.device_get((theta, touched, rows_at))
    return np.asarray(theta), np.asarray(touched), rows_at


def scores(theta_rows: np.ndarray, X: np.ndarray, precision: str) -> np.ndarray:
    """(C,) scores of C items against their user's rows, both (C, p)."""
    if precision == "exact":
        return np.einsum("bp,bp->b", theta_rows.astype(np.float64), X.astype(np.float64))
    out = contract("bp,bp->b", jnp.asarray(theta_rows, jnp.float32),
                   jnp.asarray(X, jnp.float32), precision)
    return np.asarray(out, np.float64)
