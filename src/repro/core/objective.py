"""The paper's objective (Eq. 2) and its block structure (Eq. 3).

``Q(Theta) = 1/2 sum_{i<j} W_ij ||Theta_i - Theta_j||^2
           + mu * sum_i D_ii c_i L_i(Theta_i; S_i)``

with ``L_i(theta) = (1/m_i) sum_k loss(theta; x_k, y_k) + lambda_i ||theta||^2``.

Everything here operates on the *stacked* representation ``Theta`` of shape
``(n, p)`` and on padded per-agent datasets (``X: (n, m_max, p)``,
``y: (n, m_max)``, ``mask: (n, m_max)``) so that the whole objective and all
block gradients are jit-able and vmap-able.

The module exposes the constants driving the theory:

* block Lipschitz constants ``L_i = D_ii (1 + mu c_i L_i^loc)`` (Sec. 2.2),
* the strong-convexity lower bound ``sigma >= mu min_i D_ii c_i sigma_i^loc``,
* the contraction factor ``C = 1 - sigma / (n L_max)`` of Prop. 1 / Prop. 2.
"""

from __future__ import annotations

import dataclasses
from functools import cached_property, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import AgentGraph, CSRGraph
from repro.core.mixing import mix_op

# ---------------------------------------------------------------------------
# Loss zoo
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class Loss:
    """A pointwise convex loss ell(theta; x, y) with the constants the theory needs.

    ``lipschitz_l1(X)``: L0 s.t. ||grad ell||_1 <= L0 for all points in X
    (Thm. 1).  ``smoothness(X)``: upper bound on the largest eigenvalue of the
    pointwise Hessian over X (used for L_i^loc).
    """

    name: str
    point_loss: callable  # (theta, x, y) -> scalar
    point_grad: callable  # (theta, x, y) -> (p,)
    lipschitz_l1: callable  # (X, mask) -> float
    smoothness: callable  # (X, mask) -> float


# Full f32 precision for the x . theta products (a TPU's default rounds
# f32 matmul operands to bf16).
_HIGHEST = jax.lax.Precision.HIGHEST


def _logistic_point_loss(theta, x, y):
    margin = y * jnp.dot(x, theta, precision=_HIGHEST)
    # log(1 + exp(-m)) computed stably.
    return jnp.logaddexp(0.0, -margin)


def _logistic_point_grad(theta, x, y):
    margin = y * jnp.dot(x, theta, precision=_HIGHEST)
    return -y * jax.nn.sigmoid(-margin) * x


def _logistic_lip_l1(X, mask):
    # ||grad||_1 = sigmoid(.) * ||x||_1 <= max ||x||_1  (paper uses 1-Lipschitz
    # after normalizing features; we compute the data-dependent bound).
    norms = np.abs(np.asarray(X)).sum(axis=-1) * np.asarray(mask)
    return float(norms.max())


def _logistic_smoothness(X, mask):
    # Hessian = sigmoid'(m) x x^T with sigmoid' <= 1/4.
    sq = (np.asarray(X) ** 2).sum(axis=-1) * np.asarray(mask)
    return float(0.25 * sq.max())


def _quadratic_point_loss(theta, x, y):
    return jnp.square(jnp.dot(x, theta, precision=_HIGHEST) - y)


def _quadratic_point_grad(theta, x, y):
    return 2.0 * (jnp.dot(x, theta, precision=_HIGHEST) - y) * x


def _quadratic_lip_l1(X, mask):
    # Unbounded in general; callers should clip (paper Supp. D.2, C = 10).
    return float("inf")


def _quadratic_smoothness(X, mask):
    sq = (np.asarray(X) ** 2).sum(axis=-1) * np.asarray(mask)
    return float(2.0 * sq.max())


LOGISTIC = Loss(
    "logistic",
    _logistic_point_loss,
    _logistic_point_grad,
    _logistic_lip_l1,
    _logistic_smoothness,
)
QUADRATIC = Loss(
    "quadratic",
    _quadratic_point_loss,
    _quadratic_point_grad,
    _quadratic_lip_l1,
    _quadratic_smoothness,
)

LOSSES = {"logistic": LOGISTIC, "quadratic": QUADRATIC}


# ---------------------------------------------------------------------------
# Per-agent datasets (padded)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class AgentData:
    """Padded per-agent datasets.

    X: (n, m_max, p), y: (n, m_max), mask: (n, m_max) in {0,1}.
    """

    X: np.ndarray
    y: np.ndarray
    mask: np.ndarray

    @property
    def n(self) -> int:
        return self.X.shape[0]

    @property
    def p(self) -> int:
        return self.X.shape[2]

    @property
    def num_examples(self) -> np.ndarray:
        return self.mask.sum(axis=1)

    @staticmethod
    def from_lists(Xs, ys, p=None):
        n = len(Xs)
        m_max = max(max((len(x) for x in Xs), default=1), 1)
        p = p if p is not None else Xs[0].shape[1]
        X = np.zeros((n, m_max, p))
        y = np.zeros((n, m_max))
        mask = np.zeros((n, m_max))
        for i, (xi, yi) in enumerate(zip(Xs, ys)):
            m = len(xi)
            if m:
                X[i, :m] = xi
                y[i, :m] = yi
                mask[i, :m] = 1.0
        return AgentData(X, y, mask)


# ---------------------------------------------------------------------------
# The objective
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class Objective:
    """Q(Theta) of Eq. 2, fully specified.

    Construct via :func:`make_objective`. All jnp methods are jit-able.
    ``local_loss``/``local_grad``/``value`` pass the per-agent arrays and
    the graph's weights to their jitted programs as arguments (see
    :attr:`device_arrays`), so the program stays small at any n; the other
    methods close over them as constants.
    """

    graph: AgentGraph | CSRGraph
    data: AgentData
    loss: Loss
    mu: float
    lambdas: np.ndarray  # (n,) L2 regularization per agent
    confidences: np.ndarray  # (n,) c_i in (0, 1]
    clip: float | None = None  # per-point gradient clip (Supp. D.2); None = off
    mix_mode: str = "auto"  # neighbour-sum path: "auto" | "dense" | "sparse"

    @cached_property
    def mix(self):
        """The neighbour-sum operator sum_j W_ij Theta_j (dense or sparse)."""
        return mix_op(self.graph, mode=self.mix_mode)

    # --- constants -------------------------------------------------------
    @property
    def n(self) -> int:
        return self.graph.n

    @property
    def p(self) -> int:
        return self.data.p

    @property
    def degrees(self) -> np.ndarray:
        return self.graph.degrees

    def local_smoothness(self) -> np.ndarray:
        """L_i^loc per agent: smoothness of L_i = emp. loss + lambda_i ||.||^2."""
        base = self.loss.smoothness(self.data.X, self.data.mask)
        return base + 2.0 * self.lambdas

    def local_strong_convexity(self) -> np.ndarray:
        """sigma_i^loc >= 2 lambda_i (L2 regularizer)."""
        return 2.0 * self.lambdas

    def block_lipschitz(self) -> np.ndarray:
        """L_i = D_ii (1 + mu c_i L_i^loc)."""
        return self.degrees * (1.0 + self.mu * self.confidences * self.local_smoothness())

    def strong_convexity(self) -> float:
        """sigma >= mu min_i [D_ii c_i sigma_i^loc]."""
        return float(
            self.mu
            * np.min(self.degrees * self.confidences * self.local_strong_convexity())
        )

    def contraction(self) -> float:
        """C = 1 - sigma / (n L_max) of Prop. 1."""
        return 1.0 - self.strong_convexity() / (self.n * float(self.block_lipschitz().max()))

    def alphas(self) -> np.ndarray:
        """alpha_i = 1 / (1 + mu c_i L_i^loc) — the Eq. 4 mixing coefficient."""
        return 1.0 / (1.0 + self.mu * self.confidences * self.local_smoothness())

    def lipschitz_l1(self) -> float:
        """L0 for Thm. 1 (possibly clipped per Supp. D.2)."""
        l0 = self.loss.lipschitz_l1(self.data.X, self.data.mask)
        if self.clip is not None:
            return min(l0, float(self.clip))
        return l0

    # --- values and gradients (jit-able) ----------------------------------
    def _point_grads(self, theta_i, X_i, y_i):
        g = jax.vmap(lambda x, y: self.loss.point_grad(theta_i, x, y))(X_i, y_i)
        if self.clip is not None:
            # L1-norm clipping to C, matching the Laplace/L1 sensitivity story.
            norms = jnp.sum(jnp.abs(g), axis=-1, keepdims=True)
            g = g * jnp.minimum(1.0, self.clip / jnp.maximum(norms, 1e-12))
        return g

    @cached_property
    def device_arrays(self) -> dict:
        """The per-agent arrays and the graph's weights as device arrays
        (built eagerly even when first asked for inside a trace)."""
        with jax.ensure_compile_time_eval():
            return self._build_device_arrays()

    def _build_device_arrays(self) -> dict:
        return {
            "X": jnp.asarray(self.data.X),
            "y": jnp.asarray(self.data.y),
            "mask": jnp.asarray(self.data.mask),
            "lambdas": jnp.asarray(self.lambdas),
            "degrees": jnp.asarray(self.degrees),
            "confidences": jnp.asarray(self.confidences),
            "edges": self.mix.edge_tables(),
        }

    def local_loss(self, Theta):
        """L_i(Theta_i; S_i) for all agents: (n,) vector."""
        return _local_loss(self, self.device_arrays, Theta)

    def local_grad(self, Theta):
        """grad L_i(Theta_i; S_i) for all agents: (n, p)."""
        return _local_grad(self, self.device_arrays, Theta)

    def value(self, Theta):
        """Q(Theta) of Eq. 2."""
        return _value(self, self.device_arrays, Theta)

    @partial(jax.jit, static_argnums=0)
    def block_grad(self, Theta):
        """[grad Q]_i for all i (Eq. 3), stacked into (n, p)."""
        d = jnp.asarray(self.degrees)
        c = jnp.asarray(self.confidences)
        neigh = self.mix.all(Theta)  # (n, p): sum_j W_ij Theta_j
        return d[:, None] * (Theta + self.mu * c[:, None] * self.local_grad(Theta)) - neigh

    def grad_check(self, Theta, eps=1e-5):
        """Finite-difference check of block_grad; returns max abs error."""
        Theta = np.asarray(Theta, dtype=np.float64)
        g = np.asarray(self.block_grad(jnp.asarray(Theta)))
        err = 0.0
        rng = np.random.default_rng(0)
        for _ in range(10):
            i = rng.integers(self.n)
            k = rng.integers(self.p)
            tp = Theta.copy()
            tp[i, k] += eps
            tm = Theta.copy()
            tm[i, k] -= eps
            fd = (float(self.value(jnp.asarray(tp))) - float(self.value(jnp.asarray(tm)))) / (
                2 * eps
            )
            err = max(err, abs(fd - g[i, k]))
        return err

    def solve_exact(self) -> np.ndarray:
        """Closed-form minimizer when the loss is quadratic-in-theta.

        Only valid for QUADRATIC loss (and the model-propagation special
        case); used by tests to verify convergence to the true optimum.
        """
        if self.loss.name != "quadratic":
            raise ValueError("closed form only available for quadratic loss")
        n, p = self.n, self.p
        d = self.degrees
        c = self.confidences
        X, y, mask = self.data.X, self.data.y, self.data.mask
        m = np.maximum(mask.sum(axis=1), 1.0)
        A = np.zeros((n * p, n * p))
        b = np.zeros(n * p)
        for i in range(n):
            sl = slice(i * p, (i + 1) * p)
            Xi = X[i] * mask[i][:, None]
            H = 2.0 * Xi.T @ Xi / m[i] + 2.0 * self.lambdas[i] * np.eye(p)
            g0 = -2.0 * Xi.T @ (y[i] * mask[i]) / m[i]
            A[sl, sl] += d[i] * np.eye(p) + self.mu * d[i] * c[i] * H
            b[sl] += -self.mu * d[i] * c[i] * g0
            for j, wij in zip(*self.graph.row(i)):
                A[sl, j * p : (j + 1) * p] += -wij * np.eye(p)
        sol = np.linalg.solve(A, b)
        return sol.reshape(n, p)


def _local_loss_from(obj: Objective, a: dict, Theta):
    def one(theta_i, X_i, y_i, mask_i, lam):
        m = jnp.maximum(mask_i.sum(), 1.0)
        vals = jax.vmap(lambda x, y: obj.loss.point_loss(theta_i, x, y))(X_i, y_i)
        return jnp.sum(vals * mask_i) / m + lam * jnp.sum(theta_i**2)

    return jax.vmap(one)(Theta, a["X"], a["y"], a["mask"], a["lambdas"])


@partial(jax.jit, static_argnums=0)
def _local_loss(obj: Objective, a: dict, Theta):
    return _local_loss_from(obj, a, Theta)


@partial(jax.jit, static_argnums=0)
def _local_grad(obj: Objective, a: dict, Theta):
    def one(theta_i, X_i, y_i, mask_i, lam):
        m = jnp.maximum(mask_i.sum(), 1.0)
        g = obj._point_grads(theta_i, X_i, y_i)
        return jnp.sum(g * mask_i[:, None], axis=0) / m + 2.0 * lam * theta_i

    return jax.vmap(one)(Theta, a["X"], a["y"], a["mask"], a["lambdas"])


@partial(jax.jit, static_argnums=0)
def _value(obj: Objective, a: dict, Theta):
    smooth = obj.mix.pairwise_smoothness(Theta, a["edges"])
    local = _local_loss_from(obj, a, Theta)
    return smooth + obj.mu * jnp.sum(a["degrees"] * a["confidences"] * local)


def make_objective(
    graph: AgentGraph | CSRGraph,
    data: AgentData,
    loss: Loss | str,
    mu: float,
    lambdas=None,
    confidences=None,
    clip: float | None = None,
    mix_mode: str = "auto",
) -> Objective:
    if isinstance(loss, str):
        loss = LOSSES[loss]
    m = data.num_examples
    if lambdas is None:
        # Paper Sec. 5: lambda_i = 1 / m_i ensures overall strong convexity.
        lambdas = 1.0 / np.maximum(m, 1.0)
    if confidences is None:
        from repro.core.graph import confidences as conf_fn

        confidences = conf_fn(m)
    return Objective(
        graph=graph,
        data=data,
        loss=loss,
        mu=float(mu),
        lambdas=np.asarray(lambdas, dtype=np.float64),
        confidences=np.asarray(confidences, dtype=np.float64),
        clip=clip,
        mix_mode=mix_mode,
    )
