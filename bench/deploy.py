"""A MovieLens-statistics deployment, generated on the device from a seed.

The same semantics as the repository's MovieLens-100K twin (the paper's
Sec. 5.2 protocol), at a population one chip holds:

* users fall into taste clusters: ``U_u = centers[c_u] + N(0, (0.6 s)^2)``
  with ``centers ~ N(0, 0.6^2)``; item factors ``V ~ N(0, 0.6^2)``; user
  bias ``N(0, 0.4^2)``; item popularity ``Dirichlet(0.3)``;
* rating counts ``clip(lognormal(4.35, 0.8), 20, 737)``, floored;
* each user's items are drawn without replacement with probability
  proportional to popularity (Gumbel top-k), rated
  ``clip(round(3 + U_u . V_j + b_u + N(0, 1.2^2)), 1, 5)``, and split
  80/20 into train and test by a uniform permutation;
* ratings are centred on the user's training mean; the features of an
  item are its generating factors (the configuration's ``assumed`` says
  so: no ALS is run);
* the graph is the 10-NN cosine graph over the users' training-rating
  vectors, OR-symmetrised with unit weights.

Everything up to the k-NN lists runs in jitted programs on the first
device, in blocks of users; only the compact per-user tables and the
directed k-NN picks come to the host.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int, stream: int) -> jax.Array:
    """A raw threefry key for ``(seed, stream)``; any non-negative seed,
    also one past 32 bits."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def engine_seed(seed: int) -> int:
    """The engine's 31-bit PRNG seed drawn from the run's seed."""
    return int(np.random.SeedSequence([int(seed), 7]).generate_state(1)[0] & 0x7FFFFFFF)


@dataclasses.dataclass
class Deployment:
    """Host tables of one generated deployment."""

    cfg: dict
    V: np.ndarray  # (n_items, p) f32 item features
    train_items: np.ndarray  # (n, m_max) int32, padding = n_items
    y: np.ndarray  # (n, m_max) f32 centred training ratings, 0 at padding
    mask: np.ndarray  # (n, m_max) f32
    test_items: np.ndarray  # (n, t_max) int32, padding = n_items
    test_count: np.ndarray  # (n,) int32
    counts: np.ndarray  # (n,) int32 ratings per user
    knn: np.ndarray  # (n, k) int32 directed k-NN picks

    @property
    def n(self) -> int:
        return self.train_items.shape[0]

    @property
    def p(self) -> int:
        return self.V.shape[1]

    def features(self, items: np.ndarray) -> np.ndarray:
        """Item features for an index table, zero rows at the padding id."""
        ext = np.concatenate([self.V, np.zeros((1, self.p), np.float32)])
        return np.take(ext, items, axis=0)

    def X(self) -> np.ndarray:
        """(n, m_max, p) f32 padded training features."""
        return self.features(self.train_items)

    def graph(self):
        """The OR-symmetrised unit-weight k-NN graph as the program's CSR."""
        from repro.core.graph import csr_from_coo

        n, k = self.knn.shape
        rows = np.repeat(np.arange(n, dtype=np.int64), k)
        return csr_from_coo(n, rows, self.knn.ravel(), np.ones(n * k), symmetrize=True)


def _population(key, n, n_items, rank, n_clusters, spread, mean, sigma, cmin, cmax):
    ks = jax.random.split(key, 6)
    centers = 0.6 * jax.random.normal(ks[0], (n_clusters, rank))
    assign = jax.random.randint(ks[1], (n,), 0, n_clusters)
    U = centers[assign] + 0.6 * spread * jax.random.normal(ks[2], (n, rank))
    V = 0.6 * jax.random.normal(ks[3], (n_items, rank))
    bias = 0.4 * jax.random.normal(ks[4], (n,))
    kp, kc = jax.random.split(ks[5])
    pop = jax.random.dirichlet(kp, jnp.full((n_items,), 0.3))
    counts = jnp.floor(jnp.clip(jnp.exp(mean + sigma * jax.random.normal(kc, (n,))), cmin, cmax))
    return U, V, bias, pop, counts.astype(jnp.int32)


def _user_block(key, U, bias, counts, V, logpop, *, cmax, m_max, t_max, noise, train_frac):
    """Items, ratings and the train/test split of one block of users."""
    b, n_items = U.shape[0], V.shape[0]
    kg, kr, ks = jax.random.split(key, 3)
    # Gumbel top-k: successive sampling without replacement, prob ~ pop.
    g = logpop[None, :] + jax.random.gumbel(kg, (b, n_items))
    _, items = jax.lax.top_k(g, cmax)  # (b, cmax)
    raw = jnp.einsum("br,bcr->bc", U, V[items], precision=_HIGHEST) + bias[:, None]
    stars = jnp.clip(jnp.round(3.0 + raw + noise * jax.random.normal(kr, (b, cmax))), 1.0, 5.0)
    pos = jnp.arange(cmax)[None, :]
    live = pos < counts[:, None]
    # A uniform permutation of each user's own items: sort uniform keys,
    # padding positions last.
    order = jnp.argsort(jnp.where(live, jax.random.uniform(ks, (b, cmax)), 2.0), axis=1)
    items = jnp.take_along_axis(items, order, axis=1)
    stars = jnp.take_along_axis(stars, order, axis=1)
    n_train = jnp.maximum(jnp.floor(train_frac * counts).astype(jnp.int32), 1)
    is_train = pos < n_train[:, None]
    is_test = (pos >= n_train[:, None]) & live
    mean = jnp.sum(jnp.where(is_train, stars, 0.0), axis=1) / n_train
    tr = is_train[:, :m_max]
    train_items = jnp.where(tr, items[:, :m_max], n_items).astype(jnp.int32)
    y = jnp.where(tr, stars[:, :m_max] - mean[:, None], 0.0)
    # Test entries start at n_train: shift each row left by n_train.
    tpos = jnp.arange(t_max)[None, :] + n_train[:, None]
    tidx = jnp.minimum(tpos, cmax - 1)
    t_ok = jnp.take_along_axis(is_test, tidx, axis=1)
    test_items = jnp.where(t_ok, jnp.take_along_axis(items, tidx, axis=1), n_items)
    # Dense training-rating vector (raw stars) for the cosine graph.
    vec = jnp.zeros((b, n_items + 1)).at[jnp.arange(b)[:, None], jnp.where(is_train, items, n_items)].set(
        jnp.where(is_train, stars, 0.0)
    )[:, :n_items]
    return (
        train_items,
        y.astype(jnp.float32),
        tr.astype(jnp.float32),
        test_items.astype(jnp.int32),
        (counts - n_train).astype(jnp.int32),
        vec,
    )


def _blocked(n: int, block: int) -> tuple[int, int]:
    nb = -(-n // block)
    return nb, nb * block


@partial(jax.jit, static_argnames=("cfg_items", "block"))
def _generate(key, cfg_items, block):
    cfg = dict(cfg_items)
    n, n_items, p = cfg["n_users"], cfg["n_items"], cfg["p"]
    kpop, kusers = jax.random.split(key)
    U, V, bias, pop, counts = _population(
        kpop, n, n_items, p, cfg["n_clusters"], cfg["cluster_spread"],
        cfg["count_lognormal_mean"], cfg["count_lognormal_sigma"],
        cfg["count_min"], cfg["count_max"],
    )
    nb, padded = _blocked(n, block)
    pad = padded - n

    def split(a):
        return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape((nb, block) + a.shape[1:])

    keys = jax.random.split(kusers, nb)
    fn = partial(
        _user_block,
        cmax=cfg["count_max"],
        m_max=cfg["m_max"],
        t_max=cfg["count_max"] - cfg["m_max"],
        noise=cfg["noise"],
        train_frac=cfg["train_frac"],
    )
    out = jax.lax.map(
        lambda a: fn(a[0], a[1], a[2], a[3], V, jnp.log(pop)),
        (keys, split(U), split(bias), split(jnp.maximum(counts, 1))),
    )
    out = [o.reshape((padded,) + o.shape[2:])[:n] for o in out]
    return V.astype(jnp.float32), counts, out


@jax.jit
def _unit_rows(vecs):
    norms = jnp.linalg.norm(vecs, axis=1, keepdims=True)
    return vecs / jnp.where(norms == 0.0, 1.0, norms)


@partial(jax.jit, static_argnames=("k", "block", "count"))
def _knn_blocks(unit, first, k: int, block: int, count: int):
    """k-NN picks of rows [first * block, (first + count) * block)."""
    n = unit.shape[0]

    def one(b):
        lo = (first + b) * block
        rows = jax.lax.dynamic_slice_in_dim(
            jnp.pad(unit, ((0, block), (0, 0))), lo, block, axis=0
        )
        sim = jnp.einsum("bi,ni->bn", rows, unit, precision=_HIGHEST)
        self_col = lo + jnp.arange(block)
        sim = jnp.where(jnp.arange(n)[None, :] == self_col[:, None], -jnp.inf, sim)
        return jax.lax.top_k(sim, k)[1]

    return jax.lax.map(one, jnp.arange(count)).reshape(count * block, k)


def knn_lists(vecs, k: int, block: int, devices=None) -> np.ndarray:
    """Directed cosine k-NN picks of every row, self excluded: (n, k).

    The same selection as the repository's ``knn_graph``: zero vectors
    keep norm 1, and the k largest similarities win. Row blocks are
    spread over ``devices`` (default: the one ``vecs`` is on).
    """
    n = vecs.shape[0]
    unit = _unit_rows(vecs)
    nb, _ = _blocked(n, block)
    devices = list(devices) if devices else [None]
    share = -(-nb // len(devices))
    parts = []
    for i, dev in enumerate(devices):
        first, count = i * share, min(share, nb - i * share)
        if count <= 0:
            break
        u = unit if dev is None else jax.device_put(unit, dev)
        parts.append(_knn_blocks(u, first, k, block, count))
    return np.concatenate([np.asarray(x) for x in parts])[:n].astype(np.int32)


def generate(cfg: dict, seed: int, block: int = 1024, devices=None) -> Deployment:
    """The deployment of ``cfg`` for ``seed``, generated on the device
    (the k-NN search spread over ``devices``).

    The population (users, ratings, graph) comes from the configuration's
    ``population_seed``; the run's ``seed`` relabels the users by a
    random permutation. So every seed gives the same sizes, degrees and
    rating counts, in another order, and runs of different seeds do the
    same amount of work.
    """
    keys = ("n_users", "n_items", "p", "n_clusters", "cluster_spread", "count_lognormal_mean",
            "count_lognormal_sigma", "count_min", "count_max", "m_max", "noise", "train_frac")
    if cfg["rank"] != cfg["p"]:
        raise ValueError("the item features are the generating factors: rank must equal p")
    items = tuple((k, cfg[k]) for k in keys)
    V, counts, (train_items, y, mask, test_items, test_count, vecs) = _generate(
        seed_key(cfg["population_seed"], 0), items, block
    )
    k = min(int(cfg["knn_k"]), int(cfg["n_users"]) - 1)
    knn = knn_lists(vecs, k, block, devices)
    del vecs
    V, train_items, y, mask, test_items, test_count, counts = jax.device_get(
        (V, train_items, y, mask, test_items, test_count, counts)
    )
    # New user j is old user perm[j]; k-NN picks name old ids.
    n = counts.size
    perm = np.random.default_rng([int(seed), 1]).permutation(n)
    inv = np.empty(n, np.int64)
    inv[perm] = np.arange(n)
    knn = inv[knn[perm]].astype(np.int32)
    return Deployment(
        cfg, V, train_items[perm], y[perm], mask[perm], test_items[perm], test_count[perm],
        counts[perm], knn,
    )
