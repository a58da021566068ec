"""Versioned Theta snapshots + batched personalized inference.

The paper trains one personalized linear model per agent (row ``i`` of
Theta); this module is the read path that actually answers agent ``i``'s
prediction requests while the swarm keeps training. The trainer
publishes double-buffered, version-tagged snapshots from inside
``run(..., snapshot_every=, serve=)`` — zero-copy references to the
engine's own immutable per-shard tiles, never an ``(n, p)`` gather —
and a :class:`ServeHandle` answers batched ``predict(agent_ids, X)``
against the latest published version via one jitted per-shard
row-gather + dot, routing original agent ids through the
``GraphPartition`` ownership maps (``shard_of``/``local_of``).

Ids not yet in the swarm (scheduled-but-pending arrivals, or ids beyond
``n``) are served by a cold-start tier that synthesizes their row as the
Eq. 16 confidence-zero neighbour average — exactly the warm start
``ArrivalConfig`` applies at admission, folded into the same gather as a
K-neighbour weighted row instead of a K=1 self row.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import serve_counters_init


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """Frozen serving configuration (a real spec, never bare strings).

    ``buffers`` sets the snapshot ring depth: publication writes the
    next slot and atomically swaps the reader reference, so at least
    the last ``buffers`` published versions stay alive for readers that
    pinned one mid-request. ``neighbors`` maps a cold agent id to the
    warm ids whose Eq. 16 average synthesizes its row; per-call
    ``predict(..., neighbors=)`` entries override it.
    """

    buffers: int = 2
    neighbors: dict | None = None

    def __post_init__(self):
        """Validate at construction — a bad spec never reaches serving."""
        if int(self.buffers) < 2:
            raise ValueError(
                f"ServeSpec.buffers={self.buffers}: double-buffered publication "
                "needs at least 2 snapshot slots"
            )
        if self.neighbors is not None:
            for cold, nbrs in self.neighbors.items():
                if len(tuple(nbrs)) == 0:
                    raise ValueError(
                        f"ServeSpec.neighbors[{cold}] is empty; the Eq. 16 "
                        "cold-start average needs at least one neighbour"
                    )

    @classmethod
    def coerce(cls, value) -> "ServeSpec":
        """``None`` -> defaults, a spec passes through; anything else
        (bare strings included) is a TypeError. Mirrors the
        ``ExchangeSpec.coerce`` / ``MetricsSpec.coerce`` contract."""
        if value is None:
            return cls()
        if isinstance(value, cls):
            return value
        raise TypeError(
            f"serve spec must be a ServeSpec or None for defaults, "
            f"got {type(value).__name__}: {value!r}"
        )


class ThetaSnapshot(NamedTuple):
    """One published, immutable serving view of the swarm model.

    ``tiles`` is the engine's own ``(S, R, p)`` shard stack (a
    single-device engine publishes ``Theta[None]``, i.e. S=1, R=n) —
    jax arrays are immutable, so holding the reference *is* the
    consistent snapshot; the trainer's next super-tick allocates fresh
    buffers and never mutates a published version.
    """

    version: int  # trainer slot counter at publication
    tiles: jnp.ndarray  # (S, R, p) shard blocks; padding rows never routed to
    shard_of: np.ndarray | None  # (n,) owning shard per original id (None: S=1 identity)
    local_of: np.ndarray | None  # (n,) local row within the owning shard
    pending: frozenset  # ids scheduled but not yet admitted — served cold


class SnapshotStore:
    """Double-buffered, version-tagged snapshot ring.

    ``publish`` fills the oldest ring slot and swaps the single reader
    reference under a lock; ``latest`` is one attribute read with no
    lock, so a reader mid-``predict`` keeps its pinned snapshot while
    the trainer publishes behind it. The ring's only job is keeping the
    newest ``buffers`` versions' device buffers alive for such readers.
    """

    def __init__(self, buffers: int = 2):
        """Create an empty ring of ``buffers`` snapshot slots."""
        self._ring: list = [None] * int(buffers)
        self._idx = 0
        self._lock = threading.Lock()
        self._latest: ThetaSnapshot | None = None

    def publish(self, snap: ThetaSnapshot) -> None:
        """Install ``snap`` as the served version (atomic ref swap)."""
        with self._lock:
            self._ring[self._idx] = snap
            self._idx = (self._idx + 1) % len(self._ring)
            self._latest = snap

    @property
    def latest(self) -> ThetaSnapshot:
        """The newest published snapshot (raises before first publish)."""
        snap = self._latest
        if snap is None:
            raise RuntimeError(
                "no snapshot published yet; run the engine with "
                "run(..., snapshot_every=, serve=handle) or serve from a "
                "checkpoint via repro.serve.serve_from_checkpoint"
            )
        return snap

    @property
    def latest_version(self) -> int:
        """Version tag of the newest published snapshot."""
        return self.latest.version


class ServeResult(NamedTuple):
    """One answered batch: scores/rows plus the version that served it."""

    values: np.ndarray  # (B,) scores from predict(), (B, p) rows from rows()
    version: int  # snapshot version (trainer slot) the batch was served from
    cold: np.ndarray  # (B,) bool — True where the row was Eq. 16 synthesized


@partial(jax.jit, static_argnames=())
def _gather_rows(tiles, sids, lids, w):
    """Gather + Eq. 16 combine: ``(B, K)`` routed rows -> ``(B, p)`` f32.

    Touches exactly B*K rows of the shard tiles — the gather is the
    whole read path, so no ``(n, p)`` intermediate can exist here.
    """
    rows = tiles[sids, lids].astype(w.dtype)  # (B, K, p)
    # Full f32 precision: a TPU's default would round the rows to bf16,
    # and a warm row (weight 1) must come back exactly as published.
    return jnp.einsum("bk,bkp->bp", w, rows, precision=jax.lax.Precision.HIGHEST)


@partial(jax.jit, static_argnames=())
def _score_rows(tiles, sids, lids, w, X):
    """Fused gather + combine + per-row dot: ``(B,)`` scores."""
    theta = _gather_rows(tiles, sids, lids, w)
    return jnp.sum(theta * X.astype(theta.dtype), axis=-1)


class ServeHandle:
    """Batched personalized inference over published Theta snapshots.

    Front a *live* engine with :meth:`for_engine` +
    ``run(..., snapshot_every=, serve=handle)``, or a finished /
    crash-recovered run with :func:`repro.serve.serve_from_checkpoint`;
    the read API is identical either way. Thread-safe: ``predict`` may
    run from request threads while the training thread publishes.
    """

    def __init__(self, store: SnapshotStore, spec: ServeSpec, *, n: int, p: int):
        """Wrap ``store``; prefer :meth:`for_engine` / checkpoint serving."""
        self.spec = spec
        self.n = int(n)
        self.p = int(p)
        self._store = store
        self._engine = None
        self._lock = threading.Lock()
        self._counters = serve_counters_init()

    # -- publication -------------------------------------------------------
    @classmethod
    def for_engine(cls, engine, spec: ServeSpec | None = None) -> "ServeHandle":
        """A handle bound to a live engine, ready for ``run(serve=...)``.

        When the engine carries an arrival scenario with an explicit
        attachment map and the spec names no neighbours, the arrival
        map becomes the cold-start neighbour default — pending arrivals
        are then served with exactly the neighbours they will warm-start
        from at admission.
        """
        spec = ServeSpec.coerce(spec)
        arrival = getattr(getattr(engine, "scenario", None), "arrival", None)
        if spec.neighbors is None and arrival is not None and arrival.attach:
            spec = dataclasses.replace(
                spec,
                neighbors={int(k): tuple(v) for k, v in arrival.attach.items()},
            )
        handle = cls(SnapshotStore(spec.buffers), spec, n=engine.n, p=engine.p)
        handle._engine = engine
        return handle

    def publish(self, state) -> None:
        """Publish the engine state's Theta as the next served version.

        Zero-copy by construction: the sharded engine's ``(S, R, p)``
        tile stack (or ``Theta[None]`` single-device) is referenced as
        published, alongside the partition's ownership maps so routing
        survives dynamic-topology repartitions; only the slot counter is
        pulled to the host.
        """
        eng = self._engine
        if eng is None:
            raise RuntimeError(
                "this ServeHandle is not bound to a live engine; build it "
                "with ServeHandle.for_engine(engine) (checkpoint-served "
                "handles are read-only)"
            )
        t0 = time.perf_counter()
        part = getattr(eng, "part", None)
        if part is not None:
            snap = ThetaSnapshot(
                version=eng._ptr_of(state),
                tiles=state.Theta,
                shard_of=part.shard_of,
                local_of=part.local_of,
                pending=frozenset(eng._pending),
            )
        else:
            snap = ThetaSnapshot(
                version=eng._ptr_of(state),
                tiles=state.Theta[None],
                shard_of=None,
                local_of=None,
                pending=frozenset(eng._pending),
            )
        self._store.publish(snap)
        dt = time.perf_counter() - t0
        with self._lock:
            self._counters["serve_snapshots_published"] += 1
            self._counters["serve_publish_s_total"] += dt

    # -- the read path -----------------------------------------------------
    def snapshot(self) -> ThetaSnapshot:
        """Pin the latest published version for a multi-call consistent
        read (pass it back via ``predict(..., at=snap)``)."""
        return self._store.latest

    @property
    def version(self) -> int:
        """Version tag (trainer slot) of the latest published snapshot."""
        return self._store.latest_version

    def counters(self) -> dict:
        """A copy of the host-side ``serve_*`` counters
        (:data:`repro.obs.SERVE_COUNTERS` layout)."""
        with self._lock:
            return dict(self._counters)

    def rows(self, agent_ids, neighbors=None, at=None) -> ServeResult:
        """The served ``(B, p)`` model rows (f32) for ``agent_ids``.

        Warm ids return their snapshot row bit-exactly (bf16 tiles
        upcast exactly); cold ids return the Eq. 16 neighbour average.
        """
        ids = self._check_ids(agent_ids)
        snap = self._store.latest if at is None else at
        sids, lids, w, cold = self._route(ids, snap, neighbors)
        out = np.asarray(_gather_rows(snap.tiles, sids, lids, w))
        self._account(ids.size, int(cold.sum()), snap.version)
        return ServeResult(values=out, version=snap.version, cold=cold)

    def predict(self, agent_ids, X, neighbors=None, at=None) -> ServeResult:
        """Batched personalized predictions ``<theta_i, x_b>`` -> (B,).

        ``agent_ids`` is (B,) original ids; ``X`` is (B, p) features.
        Served from the latest published snapshot (or a pinned ``at=``
        one): a single jitted per-shard row-gather + dot over exactly
        the requested rows. Cold ids (pending arrivals, or ids >= n)
        need neighbours — from ``neighbors={id: (warm ids...)}``, the
        spec, or the engine's arrival attachment map — and are scored
        on their Eq. 16 confidence-zero average row.
        """
        ids = self._check_ids(agent_ids)
        X = np.asarray(X)
        if X.shape != (ids.size, self.p):
            raise ValueError(
                f"X must be (B, p) = ({ids.size}, {self.p}) to match "
                f"agent_ids; got {X.shape}"
            )
        snap = self._store.latest if at is None else at
        sids, lids, w, cold = self._route(ids, snap, neighbors)
        y = np.asarray(_score_rows(snap.tiles, sids, lids, w, jnp.asarray(X)))
        self._account(ids.size, int(cold.sum()), snap.version)
        return ServeResult(values=y, version=snap.version, cold=cold)

    # -- internals ---------------------------------------------------------
    def _check_ids(self, agent_ids) -> np.ndarray:
        ids = np.asarray(agent_ids, dtype=np.int64).ravel()
        if ids.size == 0:
            raise ValueError("empty agent_ids batch")
        if (ids < 0).any():
            raise ValueError(f"negative agent ids: {ids[ids < 0][:5].tolist()}")
        return ids

    def _neighbors_for(self, i: int, neighbors) -> tuple:
        if neighbors is not None and i in neighbors:
            return tuple(int(j) for j in neighbors[i])
        if self.spec.neighbors is not None and i in self.spec.neighbors:
            return tuple(int(j) for j in self.spec.neighbors[i])
        raise ValueError(
            f"agent id {i} is not in the swarm yet and has no attachment "
            f"neighbours; pass neighbors={{{i}: (warm ids...)}} (or set "
            f"ServeSpec.neighbors) so Eq. 16 can synthesize its row"
        )

    def _route(self, ids, snap, neighbors):
        """Original ids -> ``(B, K)`` (shard, local, weight) gather plan.

        Warm ids are a K=1 self-gather with weight 1 (padded slots route
        to row 0 with weight 0); cold ids spread uniform weight over
        their neighbours — the Eq. 16 average with zero confidence and
        the uniform attachment weights ``ArrivalConfig`` uses.
        """
        cold = np.fromiter(
            ((i >= self.n or i in snap.pending) for i in ids.tolist()),
            dtype=bool,
            count=ids.size,
        )
        plans = []
        for i, is_cold in zip(ids.tolist(), cold.tolist()):
            if not is_cold:
                plans.append(((i,), (1.0,)))
                continue
            nbrs = self._neighbors_for(i, neighbors)
            bad = [j for j in nbrs if j >= self.n or j < 0 or j in snap.pending]
            if bad:
                raise ValueError(
                    f"cold agent id {i}: attachment neighbours {bad} are not "
                    f"established in the swarm (pending or out of range)"
                )
            plans.append((nbrs, (1.0 / len(nbrs),) * len(nbrs)))
        K = max(len(p[0]) for p in plans)
        gids = np.zeros((ids.size, K), dtype=np.int64)
        w = np.zeros((ids.size, K), dtype=np.float32)
        for b, (g, ws) in enumerate(plans):
            gids[b, : len(g)] = g
            w[b, : len(ws)] = ws
        if snap.shard_of is None:
            sids = np.zeros_like(gids)
            lids = gids
        else:
            sids = snap.shard_of[gids]
            lids = snap.local_of[gids]
        return jnp.asarray(sids), jnp.asarray(lids), jnp.asarray(w), cold

    def _account(self, batch: int, cold: int, served_version: int) -> None:
        lag = self._store.latest_version - served_version
        with self._lock:
            c = self._counters
            c["serve_requests"] += 1
            c["serve_predictions"] += batch
            c["serve_batch_rows_max"] = max(c["serve_batch_rows_max"], batch)
            c["serve_cold_starts"] += cold
            c["serve_version_lag"] = lag
            c["serve_version_lag_max"] = max(c["serve_version_lag_max"], lag)
