"""The ``LocalUpdate`` protocol: one engine, three algorithms.

The batched engine (:class:`repro.sim.AsyncEngine`) owns time, wake
sampling, scenarios, and the gather/mix/scatter plumbing; what a woken
agent *does* with its neighbour sum is delegated to a ``LocalUpdate``:

* :class:`CDUpdate` — the non-private Eq. 4 block step;
* :class:`DPCDUpdate` — the Eq. 6 private step with per-agent uniform
  budget split and accountant-style stopping (a budget-exhausted agent
  wakes but applies nothing, exactly like ``dp_cd.run_private``'s
  inactive ticks);
* :class:`PropagationUpdate` — the Eq. 16 exact block minimizer of model
  propagation (Supp. C), data-free and so compatible with the private
  warm start.

All three reduce to the same contract: given the start-of-slot snapshot,
the woken row indices (padded with the sentinel n), and their raw
neighbour sums, return replacement rows plus an ``applied`` mask. The
math lives next to its sequential twin (``eq4_theta_rows_from`` in
``coordinate_descent``, ``propagation_rows_from`` in
``model_propagation``) so the two execution paths cannot drift apart.

For the sharded engine, each update also exposes ``agent_constants`` —
the pytree of per-agent arrays (datasets, theory constants, noise
scales) its row step reads. The engine tiles those along the agent
blocks and hands the row-gathered slice back through ``apply_rows``'s
``consts`` argument, so the sharded super-tick never closes over a
replicated (n, ...) array; ``consts=None`` (the single-device path)
falls back to gathering from the replicated arrays, elementwise-equal
by construction.
"""

from __future__ import annotations

import dataclasses
from functools import cached_property
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import privacy
from repro.core.coordinate_descent import (
    eq4_agent_constants,
    eq4_theta_rows,
    eq4_theta_rows_from,
)
from repro.core.dp_cd import DPConfig, uniform_noise_plan
from repro.core.mixing import MixOp, mix_op
from repro.core.model_propagation import (
    propagation_objective,
    propagation_rows,
    propagation_rows_from,
)
from repro.core.objective import Objective


@runtime_checkable
class LocalUpdate(Protocol):
    """What the engine needs from an update rule.

    ``apply`` runs inside the jitted super-tick: ``rows`` is the (B,)
    woken index batch (padding sentinel n, which gathers clamp and the
    engine's scatter drops), ``valid`` its (B,) realness mask, ``neigh``
    the (B, p) raw neighbour sums from the (possibly delayed) snapshot.
    It returns ``(new_rows, applied, state)`` — only rows with
    ``applied[b]`` True are scattered back and charged messages.

    ``apply_rows`` is the same step for the sharded engine, which holds
    only its local Theta block: ``theta_rows`` is pre-gathered, ``rows``
    stays *global* (sentinel n), and the state pytree is this shard's
    slice, gathered and scattered at the local indices ``srows`` with
    sentinel ``ssize``. ``consts``, when given, is the row-gathered
    slice of :meth:`agent_constants` (each leaf (B, ...), row-aligned
    with ``theta_rows``) — the shard-resident replacement for indexing
    the replicated per-agent arrays with ``rows``. ``apply`` delegates
    to it with ``srows=rows, ssize=n, consts=None``, so the two
    execution paths cannot drift apart.
    """

    @property
    def n(self) -> int:
        """Number of agents."""
        ...

    @property
    def p(self) -> int:
        """Model dimension per agent."""
        ...

    @property
    def graph(self):
        """The collaboration graph (dense or CSR)."""
        ...

    @property
    def mix(self) -> MixOp:
        """The neighbour-sum operator over :attr:`graph`."""
        ...

    def init_state(self):
        """The initial update-state pytree (per-agent leaves, leading dim n)."""
        ...

    def agent_constants(self):
        """Per-agent constant arrays (leading dim n) the row step reads.

        The sharded engine tiles this pytree into (S, R, ...) blocks so
        dataset memory scales with the shard count; leaves keep their
        original dtypes (consumers cast after gathering).
        """
        ...

    def apply(self, Theta, rows, valid, neigh, key, state):
        """One batched update against the global (n, p) snapshot."""
        ...

    def apply_rows(
        self, theta_rows, rows, valid, neigh, key, state, srows=None, ssize=None, consts=None
    ):
        """One batched update from pre-gathered rows (see class docstring)."""
        ...


@dataclasses.dataclass(frozen=True, eq=False)
class CDUpdate:
    """Non-private Eq. 4 coordinate-descent block step."""

    obj: Objective

    @property
    def n(self) -> int:
        """Number of agents."""
        return self.obj.n

    @property
    def p(self) -> int:
        """Model dimension per agent."""
        return self.obj.p

    @property
    def graph(self):
        """The collaboration graph of the objective."""
        return self.obj.graph

    @property
    def mix(self) -> MixOp:
        """The objective's neighbour-sum operator."""
        return self.obj.mix

    def init_state(self):
        """Stateless: the empty pytree."""
        return ()

    def agent_constants(self):
        """Eq. 4 constants + padded per-agent datasets (see ``eq4_agent_constants``)."""
        return eq4_agent_constants(self.obj)

    def apply(self, Theta, rows, valid, neigh, key, state):
        """Gather the woken rows from the global snapshot and update them."""
        return self.apply_rows(Theta[rows], rows, valid, neigh, key, state)

    def apply_rows(
        self, theta_rows, rows, valid, neigh, key, state, srows=None, ssize=None, consts=None
    ):
        """Batched Eq. 4 step; ``consts`` selects the shard-resident path."""
        if consts is None:
            new_rows = eq4_theta_rows(self.obj, theta_rows, rows, neigh)
        else:
            new_rows = eq4_theta_rows_from(self.obj, theta_rows, neigh, consts)
        return new_rows, valid, state

    def objective(self, Theta) -> float:
        """Q(Theta) of Eq. 2 (used by ``record_every``)."""
        return float(self.obj.value(Theta))


@dataclasses.dataclass(frozen=True, eq=False)
class DPCDUpdate:
    """Eq. 6 private step with per-agent budget stopping.

    Build via :meth:`plan`. Each agent splits ``(eps_bar, delta_bar)``
    equally over ``planned_Ti`` expected wake-ups (Thm. 1 composition
    inversion, shared with ``dp_cd.uniform_noise_plan``) and freezes once
    they are spent. State is the (n,) count of applied private updates;
    :meth:`eps_spent` composes it back into per-agent spend.

    Recorded deviation: only the uniform schedule is supported — the
    Prop. 2 decreasing schedule indexes the *global sequential* tick,
    which a batched slot does not expose (use ``dp_cd.run_private``).
    """

    obj: Objective
    cfg: DPConfig
    planned_Ti: int
    eps_step: float
    scales: np.ndarray  # (n,) per-agent constant noise scale

    @classmethod
    def plan(cls, obj: Objective, cfg: DPConfig, planned_Ti: int) -> "DPCDUpdate":
        """Plan the per-agent uniform budget split for ``planned_Ti`` wake-ups."""
        if cfg.schedule != "uniform":
            raise NotImplementedError(
                "the batched engine supports the uniform budget split only; "
                "the Prop. 2 schedule needs the sequential driver dp_cd.run_private"
            )
        eps_step, scales = uniform_noise_plan(obj, cfg, planned_Ti)
        return cls(obj=obj, cfg=cfg, planned_Ti=planned_Ti, eps_step=eps_step, scales=scales)

    @property
    def n(self) -> int:
        """Number of agents."""
        return self.obj.n

    @property
    def p(self) -> int:
        """Model dimension per agent."""
        return self.obj.p

    @property
    def graph(self):
        """The collaboration graph of the objective."""
        return self.obj.graph

    @property
    def mix(self) -> MixOp:
        """The objective's neighbour-sum operator."""
        return self.obj.mix

    def init_state(self):
        """(n,) int32 count of applied private updates per agent."""
        return jnp.zeros(self.n, dtype=jnp.int32)

    def agent_constants(self):
        """Eq. 4 constants + the (n,) per-agent noise scales."""
        return {**eq4_agent_constants(self.obj), "scales": self.scales}

    def apply(self, Theta, rows, valid, neigh, key, state):
        """Gather the woken rows (sentinel-clamped) and privately update them."""
        return self.apply_rows(
            Theta[jnp.minimum(rows, self.n - 1)], rows, valid, neigh, key, state
        )

    def apply_rows(
        self, theta_rows, rows, valid, neigh, key, state, srows=None, ssize=None, consts=None
    ):
        """Batched Eq. 6 step with budget stopping; ``consts`` selects the
        shard-resident path (noise scales included in the pytree)."""
        n = self.n
        if srows is None:
            srows, ssize = rows, n
        dt = theta_rows.dtype
        counts = state[jnp.minimum(srows, ssize - 1)]
        applied = valid & (counts < self.planned_Ti)
        if self.cfg.mechanism == "gaussian":
            draws = jax.random.normal(key, shape=neigh.shape, dtype=dt)
        else:
            draws = jax.random.laplace(key, shape=neigh.shape, dtype=dt)
        if consts is None:
            scales_rows = jnp.asarray(self.scales, dt)[jnp.minimum(rows, n - 1)]
            noise = draws * scales_rows[:, None]
            new_rows = eq4_theta_rows(self.obj, theta_rows, rows, neigh, grad_noise=noise)
        else:
            noise = draws * jnp.asarray(consts["scales"], dt)[:, None]
            new_rows = eq4_theta_rows_from(self.obj, theta_rows, neigh, consts, grad_noise=noise)
        state = state.at[jnp.where(applied, srows, ssize)].add(1, mode="drop")
        return new_rows, applied, state

    def eps_spent(self, state) -> np.ndarray:
        """(n,) composed per-agent spend for the applied-update counts."""
        return privacy.compose_uniform(
            self.eps_step, np.asarray(state), self.cfg.delta_bar
        )

    def budget_stopped(self, state) -> int:
        """Agents whose planned per-agent update budget T_i is exhausted.

        The host-side ground truth the ``dp_budget_stopped`` telemetry
        gauge is tested against (``tests/test_obs.py``).
        """
        return int((np.asarray(state) >= self.planned_Ti).sum())

    def objective(self, Theta) -> float:
        """Q(Theta) of Eq. 2 (used by ``record_every``)."""
        return float(self.obj.value(Theta))


@dataclasses.dataclass(frozen=True, eq=False)
class PropagationUpdate:
    """Eq. 16 model propagation (Supp. C) as an engine update rule."""

    graph: object
    theta_loc: np.ndarray
    mu: float
    confidences: np.ndarray
    mix_mode: str = "auto"

    @cached_property
    def mix(self) -> MixOp:
        """The neighbour-sum operator over :attr:`graph` (built lazily)."""
        return mix_op(self.graph, mode=self.mix_mode)

    @property
    def n(self) -> int:
        """Number of agents."""
        return self.graph.n

    @property
    def p(self) -> int:
        """Model dimension per agent."""
        return self.theta_loc.shape[1]

    def init_state(self):
        """Stateless: the empty pytree."""
        return ()

    def agent_constants(self):
        """Degrees, confidences, and the (n, p) local models Eq. 16 reads."""
        return {"deg": self.graph.degrees, "conf": self.confidences, "loc": self.theta_loc}

    def apply(self, Theta, rows, valid, neigh, key, state):
        """Gather the woken rows from the global snapshot and update them."""
        return self.apply_rows(Theta[rows], rows, valid, neigh, key, state)

    def apply_rows(
        self, theta_rows, rows, valid, neigh, key, state, srows=None, ssize=None, consts=None
    ):
        """Batched Eq. 16 exact block minimizer; ``theta_rows`` is unused —
        the update reads only the neighbour sum and the local models."""
        if consts is None:
            new_rows = propagation_rows(
                self.graph.degrees, self.theta_loc, self.mu, self.confidences, rows, neigh
            )
        else:
            new_rows = propagation_rows_from(
                self.mu, consts["deg"], consts["conf"], consts["loc"], neigh
            )
        return new_rows, valid, state

    def objective(self, Theta) -> float:
        """Q_MP of Eq. 15 (used by ``record_every``)."""
        value, _ = propagation_objective(
            self.graph, np.asarray(self.theta_loc), self.mu, np.asarray(self.confidences)
        )
        return float(value(np.asarray(Theta)))


@dataclasses.dataclass(frozen=True)
class GraphUpdate:
    """Dada-style sparse similarity-driven edge refresh (arXiv 1901.08460).

    Zantedeschi et al. alternate model updates with a graph step that
    re-selects each node's edges from its current *model* similarity —
    their ``Node``/``set_edges`` alternation. This is that step as a
    host-side refresh the engines fire every ``every`` slots, at slot
    boundaries (the model super-ticks in between run on the frozen
    topology; see docs/DEVIATIONS.md):

    1. **Candidates** — every current edge plus ``candidates`` random
       never-self peers per node (the sparse stand-in for the dense all
       pairs similarity Dada's centralized variant uses).
    2. **Similarity** — ``w_ij = exp(-||Theta_i - Theta_j||^2 / gamma)``
       over candidate pairs only.
    3. **Selection** — per row keep the top-``k`` by similarity, always
       retaining the single best (so every degree stays >= 1: Eq. 4
       divides by D_ii) and dropping the rest below ``threshold``; then
       OR-symmetrize, exactly like the k-NN constructors.

    The refresh is deterministic in ``(seed, round_index)``, so a run is
    reproducible and the sharded engine can replay the identical graph
    sequence on every host.
    """

    every: int = 10
    k: int = 10
    candidates: int = 8
    gamma: float = 1.0
    threshold: float = 1e-4
    seed: int = 0

    def __post_init__(self):
        if self.every < 1:
            raise ValueError("every must be >= 1 slots")
        if self.k < 1:
            raise ValueError("k must be >= 1")
        if self.candidates < 0:
            raise ValueError("candidates must be >= 0")
        if self.gamma <= 0.0:
            raise ValueError("gamma must be > 0")

    def refresh(self, csr, Theta, round_index: int = 0, allowed=None):
        """One edge-update round: (current graph, models) -> new graph.

        ``csr``: the live :class:`repro.core.graph.CSRGraph`; ``Theta``:
        (n, p) current models; ``round_index``: which refresh this is
        (seeds the candidate draw). ``allowed``: optional (n,) bool mask —
        only edges between allowed agents are re-selected; existing edges
        touching a non-allowed agent pass through frozen at their current
        weight (how the engines keep not-yet-arrived agents detached and
        departed agents' caches mixed). Host-side numpy, O(n * (deg + c)).
        """
        from repro.core.graph import csr_from_coo

        Theta = np.asarray(Theta, dtype=np.float64)
        n = csr.n
        rows = csr.row_ids().astype(np.int64)
        cols = csr.indices.astype(np.int64)
        if allowed is not None:
            allowed = np.asarray(allowed, dtype=bool)
            live = allowed[rows] & allowed[cols]
            frozen = (rows[~live], cols[~live], np.asarray(csr.data, np.float64)[~live])
            rows, cols = rows[live], cols[live]
        else:
            frozen = None
        if self.candidates > 0 and n > 1:
            rng = np.random.default_rng((self.seed, round_index))
            c = min(self.candidates, n - 1)
            # i + U{1, .., n-1} mod n is never i — no self candidates.
            rand = (
                np.arange(n, dtype=np.int64)[:, None]
                + rng.integers(1, n, size=(n, c))
            ) % n
            crows = np.repeat(np.arange(n, dtype=np.int64), c)
            ccols = rand.ravel()
            if allowed is not None:
                # Draw for every row (stable rng stream), then filter.
                mask = allowed[crows] & allowed[ccols]
                crows, ccols = crows[mask], ccols[mask]
            rows = np.concatenate([rows, crows])
            cols = np.concatenate([cols, ccols])
        # Dedupe directed candidate pairs.
        key = rows * n + cols
        _, uniq = np.unique(key, return_index=True)
        rows, cols = rows[uniq], cols[uniq]
        d2 = ((Theta[rows] - Theta[cols]) ** 2).sum(axis=1)
        vals = np.exp(-d2 / self.gamma)
        # Per-row top-k: rank candidates within each row by -similarity.
        order = np.lexsort((-vals, rows))
        rows, cols, vals = rows[order], cols[order], vals[order]
        first = np.concatenate([[True], rows[1:] != rows[:-1]])
        start = np.maximum.accumulate(np.where(first, np.arange(len(rows)), 0))
        rank = np.arange(len(rows)) - start
        # The row's best candidate always survives (D_ii > 0 for Eq. 4);
        # beyond it, keep top-k entries above the negligibility floor.
        keep = (rank == 0) | ((rank < self.k) & (vals >= self.threshold))
        rows, cols, vals = rows[keep], cols[keep], vals[keep]
        if frozen is not None:
            rows = np.concatenate([rows, frozen[0]])
            cols = np.concatenate([cols, frozen[1]])
            vals = np.concatenate([vals, frozen[2]])
        return csr_from_coo(n, rows, cols, vals, symmetrize=True, dedupe="max")
