"""Device-side engine telemetry: the metrics pytree and its accumulator.

The async engines run their whole super-tick inside a jit-compiled
``lax.scan``; anything worth observing (realized wake rates vs the
Poisson clocks, halo traffic, quantization error, DP budget burn-down,
churn) therefore has to be accumulated *inside* the compiled program —
a host read per slot would serialize the scan. This module provides:

* :class:`MetricsSpec` — a small frozen selector of counter groups,
  carried on :class:`repro.sim.EngineConfig` (``metrics=``; ``True``
  coerces to the default spec, ``None``/``False`` disables collection
  entirely — the default, so runs pay nothing unless asked);
* :class:`MetricsAccumulator` — built once per engine with the static
  context (row count, churn/straggler presence, DP budget limit,
  exchange-plan shape), it owns the metrics pytree: :meth:`init`
  produces the zeroed leaves that ride in ``SimState.metrics`` /
  ``ShardedSimState.metrics`` (the sharded engine stacks S copies along
  a leading shard axis), and :meth:`tick` advances them inside the
  traced slot.

Every counter is computed from values the super-tick already produces —
no extra PRNG draws, no host round-trips — so a metrics-on run is
bit-exact in Theta vs a metrics-off run (pinned by
``tests/test_obs.py``; the only cost is the counter arithmetic itself,
measured as the ``obs_overhead`` bench row).

Counter groups (leaves present only when the spec selects them and the
engine context supports them):

* ``wakes``: ``wakes_realized`` (wake mask sum before straggler/capacity
  losses), ``wakes_thinned`` (straggler drops), ``wakes_capacity_dropped``
  (static-batch overflow), ``wakes_applied`` (rows actually scattered);
  and, where the single-device engine's neighbour sums run over CSR
  edges, ``mix_edges`` (the woken rows' real edges summed) and
  ``mix_trips`` (edge blocks run; ``mix_trips`` over the slots run says
  how often a slot's edges needed a second block);
* ``churn``: cumulative ``churn_departures`` / ``churn_rejoins``
  (active-flag transitions of the churn Markov chain);
* ``privacy``: ``dp_updates_applied`` (cumulative private updates) and
  ``dp_budget_stopped`` (gauge: agents at their planned budget now);
* ``exchange`` (sharded engine only): ``border_rows_published`` plus
  ``exchange_rows`` / ``exchange_bytes`` shipped on the interconnect
  (padded rows — static shapes ship them), and per-ring-offset
  ``p2p_rows_by_offset`` / ``p2p_bytes_by_offset`` under the
  point-to-point plan. The per-slot volumes are static properties of
  the exchange plan, but they differ per shard, so they arrive as
  shard-sliced inputs (``ExchangeVolume.tiles``) rather than Python
  constants;
* ``quantization``: cumulative squared quantization error of the
  compressed halo wire (``quant_err_sq``) and the current
  error-feedback residual energy (``ef_residual_sq``, a gauge);
* ``staleness``: a log2-bucketed histogram of slots-since-last-update
  per applied wake (bucketing is approximate by construction — recorded
  in ``docs/DEVIATIONS.md``) plus the ``last_wake`` slot marker it
  needs (dropped from drains: it is state, not a counter).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

# ``mix_edges`` is kept below this on the device, its multiples in a carry.
_MIX_EDGES_WRAP = 1 << 30


@dataclasses.dataclass(frozen=True)
class MetricsSpec:
    """Selects which counter groups the engines accumulate in-jit.

    Fields toggle groups (see the module docstring for the leaves each
    one contributes); ``staleness_buckets`` sizes the staleness
    histogram (bucket b collects staleness in slots ``[2^b, 2^(b+1))``,
    the last bucket open-ended).
    """

    wakes: bool = True
    exchange: bool = True
    quantization: bool = True
    privacy: bool = True
    churn: bool = True
    staleness: bool = True
    staleness_buckets: int = 8

    def __post_init__(self):
        if self.staleness_buckets < 1:
            raise ValueError("staleness_buckets must be >= 1")

    @classmethod
    def coerce(cls, value) -> "MetricsSpec | None":
        """Accept a spec, ``True`` (defaults), or ``None``/``False`` (off)."""
        if value is None or value is False:
            return None
        if value is True:
            return cls()
        if isinstance(value, cls):
            return value
        raise TypeError(
            f"metrics must be a MetricsSpec, True, False, or None, got {type(value)!r}"
        )


@dataclasses.dataclass(frozen=True)
class ExchangeVolume:
    """Per-shard static interconnect volume of the halo exchange.

    Built once at engine build from the partition's plan; every array
    carries a leading shard axis so the stacked tiles ride through
    ``shard_map`` next to the graph tiles (per-shard border sizes
    differ, so these cannot be baked into the shared SPMD program as
    constants). Rows are padded rows, because static shapes ship them.
    """

    border_rows: np.ndarray  # (S,) real border rows published per slot
    rows_shipped: np.ndarray  # (S,) padded rows sent on the wire per slot
    bytes_shipped: np.ndarray  # (S,) rows_shipped * payload bytes per row
    p2p_rows: np.ndarray | None = None  # (S, O) padded P_d per ring offset
    p2p_bytes: np.ndarray | None = None  # (S, O)

    @property
    def num_offsets(self) -> int:
        """O: ring offsets in the point-to-point plan (0 for all_gather)."""
        return 0 if self.p2p_rows is None else int(self.p2p_rows.shape[1])

    def tiles(self) -> dict:
        """The stacked (S, ...) arrays to pass through ``shard_map``."""
        t = {
            "border_rows": jnp.asarray(self.border_rows, jnp.int32),
            "rows_shipped": jnp.asarray(self.rows_shipped, jnp.int32),
            "bytes_shipped": jnp.asarray(self.bytes_shipped, jnp.float32),
        }
        if self.p2p_rows is not None:
            t["p2p_rows"] = jnp.asarray(self.p2p_rows, jnp.int32)
            t["p2p_bytes"] = jnp.asarray(self.p2p_bytes, jnp.float32)
        return t


class MetricsAccumulator:
    """Owns the metrics pytree for one engine instance.

    ``rows`` is the scatter domain (n for the single-device engine, R
    per shard for the sharded one). Optional context enables groups:
    ``churn``/``straggler`` flags, ``dp_limit`` (the planned per-agent
    update budget ``planned_Ti``), ``exchange_offsets`` (None = no
    halo exchange; an int = the point-to-point plan's offset count,
    0 for the all_gather wire), ``quantized`` (the halo wire is lossy
    and reports error stats), ``mix_counts`` (the neighbour sums report
    their edges and trips). Groups whose context is absent contribute
    no leaves, whatever the spec says — the pytree structure is fixed
    at engine build and stable across the scan.
    """

    def __init__(
        self,
        spec: MetricsSpec,
        rows: int,
        *,
        churn: bool = False,
        straggler: bool = False,
        dp_limit: int | None = None,
        exchange_offsets: int | None = None,
        quantized: bool = False,
        mix_counts: bool = False,
    ):
        self.spec = spec
        self.rows = int(rows)
        self.churn = bool(churn) and spec.churn
        self.straggler = bool(straggler) and spec.wakes
        self.dp_limit = dp_limit if spec.privacy else None
        self.exchange_offsets = exchange_offsets if spec.exchange else None
        self.quantized = bool(quantized) and spec.quantization
        self.mix_counts = bool(mix_counts) and spec.wakes

    # -- pytree ------------------------------------------------------------
    def init(self) -> dict:
        """The zeroed metrics pytree (no leading shard axis; the sharded
        engine stacks S copies along axis 0)."""
        i32 = jnp.int32
        m: dict = {}
        if self.spec.wakes:
            m["wakes_realized"] = jnp.zeros((), i32)
            m["wakes_capacity_dropped"] = jnp.zeros((), i32)
            m["wakes_applied"] = jnp.zeros((), i32)
            if self.straggler:
                m["wakes_thinned"] = jnp.zeros((), i32)
        if self.mix_counts:
            # Edges pass int32 in minutes of a large swarm: the count is
            # kept as _MIX_EDGES_WRAP-sized carries plus a remainder,
            # joined on the host by :meth:`snapshot`.
            m["mix_edges"] = jnp.zeros((), i32)
            m["mix_edges_carry"] = jnp.zeros((), i32)
            m["mix_trips"] = jnp.zeros((), i32)
        if self.churn:
            m["churn_departures"] = jnp.zeros((), i32)
            m["churn_rejoins"] = jnp.zeros((), i32)
        if self.dp_limit is not None:
            m["dp_updates_applied"] = jnp.zeros((), i32)
            m["dp_budget_stopped"] = jnp.zeros((), i32)
        if self.exchange_offsets is not None:
            m["border_rows_published"] = jnp.zeros((), i32)
            m["exchange_rows"] = jnp.zeros((), i32)
            m["exchange_bytes"] = jnp.zeros((), jnp.float32)
            if self.exchange_offsets > 0:
                m["p2p_rows_by_offset"] = jnp.zeros((self.exchange_offsets,), i32)
                m["p2p_bytes_by_offset"] = jnp.zeros(
                    (self.exchange_offsets,), jnp.float32
                )
        if self.quantized:
            m["quant_err_sq"] = jnp.zeros((), jnp.float32)
            m["ef_residual_sq"] = jnp.zeros((), jnp.float32)
        if self.spec.staleness:
            m["staleness_hist"] = jnp.zeros((self.spec.staleness_buckets,), i32)
            m["last_wake"] = jnp.zeros((self.rows,), i32)
        return m

    def leaf_kinds(self) -> dict:
        """Classify each metrics leaf for the checkpoint layer.

        ``"per_agent"`` leaves are keyed by agent row (``last_wake``) and
        must be re-tiled through the partition on an elastic restore;
        ``"counter"`` leaves are shard-additive accumulators that can be
        summed across shards without changing any drained snapshot.
        """
        return {
            k: "per_agent" if k == "last_wake" else "counter"
            for k in self.init()
        }

    # -- in-jit update -----------------------------------------------------
    def tick(
        self,
        m: dict,
        *,
        ptr,
        wake_pre,
        wake,
        applied,
        woken,
        capacity_dropped,
        active_prev=None,
        active_new=None,
        dp_counts=None,
        exchange=None,
        quant_stats=None,
        mix=None,
    ) -> dict:
        """Advance the metrics pytree by one slot (runs inside the trace).

        ``wake_pre`` is the wake mask before straggler thinning,
        ``wake`` the realized mask, ``woken`` the (B,) scatter rows with
        sentinel ``rows``, ``applied`` their applied mask,
        ``capacity_dropped`` the static-batch overflow count,
        ``exchange`` this shard's slice of :meth:`ExchangeVolume.tiles`,
        ``quant_stats`` the halo wire's error stats dict, ``mix`` the
        neighbour sums' ``(edges, trips)`` int32 scalars. All inputs are
        values the slot already computed — the accumulator draws no
        randomness and never touches Theta.
        """
        m = dict(m)
        applied_count = applied.sum().astype(jnp.int32)
        if self.spec.wakes:
            m["wakes_realized"] = m["wakes_realized"] + wake_pre.sum().astype(jnp.int32)
            m["wakes_capacity_dropped"] = (
                m["wakes_capacity_dropped"] + capacity_dropped.astype(jnp.int32)
            )
            m["wakes_applied"] = m["wakes_applied"] + applied_count
            if self.straggler:
                thinned = (wake_pre & ~wake).sum().astype(jnp.int32)
                m["wakes_thinned"] = m["wakes_thinned"] + thinned
        if self.mix_counts and mix is not None:
            edges = m["mix_edges"] + mix[0]
            m["mix_edges_carry"] = m["mix_edges_carry"] + edges // _MIX_EDGES_WRAP
            m["mix_edges"] = edges % _MIX_EDGES_WRAP
            m["mix_trips"] = m["mix_trips"] + mix[1]
        if self.churn and active_prev is not None:
            departed = (active_prev & ~active_new).sum().astype(jnp.int32)
            rejoined = ((~active_prev) & active_new).sum().astype(jnp.int32)
            m["churn_departures"] = m["churn_departures"] + departed
            m["churn_rejoins"] = m["churn_rejoins"] + rejoined
        if self.dp_limit is not None and dp_counts is not None:
            m["dp_updates_applied"] = m["dp_updates_applied"] + applied_count
            stopped = (dp_counts >= jnp.int32(self.dp_limit)).sum().astype(jnp.int32)
            m["dp_budget_stopped"] = stopped  # gauge, not cumulative
        if self.exchange_offsets is not None and exchange is not None:
            m["border_rows_published"] = (
                m["border_rows_published"] + exchange["border_rows"]
            )
            m["exchange_rows"] = m["exchange_rows"] + exchange["rows_shipped"]
            m["exchange_bytes"] = m["exchange_bytes"] + exchange["bytes_shipped"]
            if self.exchange_offsets > 0:
                m["p2p_rows_by_offset"] = m["p2p_rows_by_offset"] + exchange["p2p_rows"]
                m["p2p_bytes_by_offset"] = (
                    m["p2p_bytes_by_offset"] + exchange["p2p_bytes"]
                )
        if self.quantized and quant_stats is not None:
            m["quant_err_sq"] = m["quant_err_sq"] + quant_stats["quant_err_sq"]
            m["ef_residual_sq"] = quant_stats["ef_residual_sq"]  # gauge
        if self.spec.staleness:
            nb = self.spec.staleness_buckets
            safe = jnp.minimum(woken, self.rows - 1)
            stale = (ptr - m["last_wake"][safe]).astype(jnp.float32)
            bucket = jnp.clip(
                jnp.floor(jnp.log2(jnp.maximum(stale, 1.0))), 0, nb - 1
            ).astype(jnp.int32)
            m["staleness_hist"] = (
                m["staleness_hist"].at[jnp.where(applied, bucket, nb)].add(1, mode="drop")
            )
            m["last_wake"] = (
                m["last_wake"].at[jnp.where(applied, woken, self.rows)]
                .set(ptr + 1, mode="drop")
            )
        return m

    # -- host drain --------------------------------------------------------
    def snapshot(self, m: dict) -> dict:
        """Device metrics -> host dict of numpy arrays (drain helper).

        Sharded callers pass the stacked (S, ...) pytree; per-shard
        leaves keep their leading shard axis so the report layer can
        show per-shard burn-down as well as totals. The internal
        ``last_wake`` marker is dropped — it is state, not a counter —
        and ``mix_edges`` is joined with its carries into one int64.
        """
        out = {
            k: np.asarray(v)
            for k, v in m.items()
            if k not in ("last_wake", "mix_edges_carry")
        }
        if "mix_edges" in out:
            carry = np.asarray(m["mix_edges_carry"], np.int64)
            out["mix_edges"] = carry * _MIX_EDGES_WRAP + out["mix_edges"].astype(np.int64)
        return out


# Host-side dynamic-topology counters. Unlike the in-jit groups above,
# topology changes happen between chunks on the host (edge refreshes,
# arrivals, partition patches), so the engines keep a plain dict and
# merge it into the ``derived`` side of ``metrics_snapshot`` with a
# ``topology_`` prefix.
TOPOLOGY_COUNTERS = (
    "edge_refreshes",  # GraphUpdate.refresh rounds fired
    "edges_added",  # undirected edges created across all topology swaps
    "edges_removed",  # undirected edges dropped across all topology swaps
    "weight_patches",  # same-structure partition rebinds (weights only)
    "structural_patches",  # GraphPartition.patch() calls (ownership frozen)
    "repartitions",  # full partition_graph rebuilds (drift over threshold)
    "arrivals",  # agents admitted mid-run
    "last_drift",  # gauge: cut-fraction drift measured at the last swap
)


def topology_log_init() -> dict:
    """A fresh host-side dynamic-topology counter dict (all zeros)."""
    return {k: (0.0 if k == "last_drift" else 0) for k in TOPOLOGY_COUNTERS}


# Host-side serving counters. Like the topology log, serving activity
# happens on the host (snapshot publication from run() events, batched
# predict() calls against the latest published version), so the
# ``repro.serve.ServeHandle`` keeps a plain dict in this layout.
SERVE_COUNTERS = (
    "serve_requests",  # predict()/rows() calls answered
    "serve_predictions",  # total rows scored across all batches
    "serve_batch_rows_max",  # gauge: largest request batch seen
    "serve_cold_starts",  # rows synthesized via the Eq. 16 neighbour average
    "serve_snapshots_published",  # publish() calls (one per snapshot_every slots)
    "serve_version_lag",  # gauge: newest published slot minus the slot just served
    "serve_version_lag_max",  # worst version lag any request observed
    "serve_publish_s_total",  # wall seconds spent publishing snapshots (float)
    "serve_publish_sync_s_total",  # of which waiting for the slot counter (float)
    "serve_route_s_total",  # wall seconds checking, routing and copying requests in (float)
    "serve_fetch_s_total",  # wall seconds waiting for answers on the host (float)
)


def serve_counters_init() -> dict:
    """A fresh host-side serving counter dict (all zeros; ``*_s_total``
    seconds are floats)."""
    return {k: (0.0 if k.endswith("_s_total") else 0) for k in SERVE_COUNTERS}


def summarize_counters(snapshot: dict) -> dict:
    """Collapse a (possibly shard-stacked) snapshot into JSON-ready totals.

    Scalar counters sum over the shard axis; per-offset / histogram
    vectors sum over shards but keep their own axis (returned as
    lists). Gauges sum too — a per-shard gauge's total is the
    fleet-wide gauge.
    """
    vector = ("staleness_hist", "p2p_rows_by_offset", "p2p_bytes_by_offset")
    out: dict = {}
    for k, v in snapshot.items():
        a = np.asarray(v)
        if k in vector:
            collapsed = a.sum(axis=0) if a.ndim > 1 else a
            cast = float if collapsed.dtype.kind == "f" else int
            out[k] = [cast(x) for x in collapsed]
        else:
            out[k] = float(a.sum()) if a.dtype.kind == "f" else int(a.sum())
    return out
