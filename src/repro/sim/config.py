"""Typed engine configuration shared by both simulation engines.

The PR-4 constructors had grown 10–14 positional-ish kwargs each, with
the sharded engine's placement knobs (partition mode, relabel, coords,
exchange method) mixed into the same flat list as the clock/scenario
knobs. :class:`EngineConfig` collapses them into one frozen dataclass
that both :class:`repro.sim.AsyncEngine` and
:class:`repro.sim.ShardedAsyncEngine` accept (``config=...``), with the
old kwargs kept working as overrides (``AsyncEngine(update,
slot_wakes=8.0)`` merges into the default config). :func:`make_engine`
is the one-call factory: shards absent/0 builds the single-device
engine, otherwise the sharded one.

Placement fields (``partition_mode``/``relabel``/``coords``/
``exchange``/``partition``/``devices``) are no-ops on the single-device
engine, so one config can drive both sides of a parity test.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

from repro.core.mixing import ExchangeSpec
from repro.sim.scenarios import Scenario


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Everything an engine run needs besides the update rule itself.

    Clock / batching / scenario (both engines):

    * ``slot_wakes``: expected wake-ups per super-tick (sets tau);
    * ``rates``: per-agent Poisson rates (None = all 1.0);
    * ``batch_size``: static woken-rows batch B (None = mean + 6 sigma);
    * ``scenario``: churn / delay / straggler bundle (None = none);
    * ``seed`` / ``dtype`` / ``steps_per_chunk``: PRNG seed, model dtype,
      super-ticks per jitted scan chunk;
    * ``metrics``: in-jit telemetry — a
      :class:`repro.obs.MetricsSpec` selecting counter groups, ``True``
      for the default spec, ``None``/``False`` (default) for no
      collection. Metrics-on runs are bit-exact in Theta vs metrics-off.

    Placement / exchange (sharded engine only; ignored at S=1):

    * ``partition_mode``: ``"degree"`` | ``"contiguous"`` block cutting;
    * ``relabel``: ``"rcm"`` | ``"sfc"`` | ``"hilbert"`` | explicit
      permutation | None;
    * ``coords``: (n, 2) agent positions for the space-filling-curve
      relabels;
    * ``exchange``: :class:`repro.core.mixing.ExchangeSpec` (None =
      defaults; deprecated bare strings still coerce);
    * ``partition``: a prebuilt ``GraphPartition`` to reuse;
    * ``devices``: explicit device list for the mesh.

    Dynamic topology (both engines; the sharded engine adds the
    repartition policy):

    * ``graph_update``: a :class:`repro.sim.updates.GraphUpdate` firing
      a Dada-style edge refresh every ``graph_update.every`` slots
      (None = static topology, the default — and the bit-exactness
      anchor: a static-topology run is byte-identical to the
      pre-dynamic engines);
    * ``drift_threshold``: sharded repartition trigger. After each
      structural topology change the engine measures
      :meth:`repro.sim.partition.GraphPartition.drift`; at or below the
      threshold it patches the existing cut
      (:meth:`GraphPartition.patch`, ownership frozen), above it it
      pays for a full ``partition_graph`` rebuild.
    """

    slot_wakes: float = 64.0
    rates: Any = None
    batch_size: int | None = None
    scenario: Scenario | None = None
    seed: int = 0
    dtype: Any = jnp.float32
    steps_per_chunk: int = 16
    metrics: Any = None  # MetricsSpec | True | False | None
    partition_mode: str = "degree"
    relabel: Any = None
    coords: Any = None
    exchange: Any = None  # ExchangeSpec | deprecated str | None
    partition: Any = None
    devices: Any = None
    graph_update: Any = None  # GraphUpdate | None (None = static topology)
    drift_threshold: float = 0.25

    def exchange_spec(self) -> ExchangeSpec:
        """The coerced exchange spec (warns on deprecated bare strings)."""
        return ExchangeSpec.coerce(self.exchange)

    def metrics_spec(self):
        """The coerced telemetry spec (None = collection off, the default)."""
        from repro.obs.metrics import MetricsSpec

        return MetricsSpec.coerce(self.metrics)

    def replace(self, **overrides) -> "EngineConfig":
        """A copy with the given fields replaced (dataclasses.replace)."""
        return dataclasses.replace(self, **overrides)


def resolve_config(config: EngineConfig | None, overrides: dict) -> EngineConfig:
    """Merge constructor ``**kwargs`` overrides into a (default) config."""
    base = config if config is not None else EngineConfig()
    if not overrides:
        return base
    try:
        return dataclasses.replace(base, **overrides)
    except TypeError as e:
        raise TypeError(f"unknown engine option(s) in {sorted(overrides)}: {e}") from None


def make_engine(update, config: EngineConfig | None = None, *, shards=None, **overrides):
    """Build the right engine for ``shards``: None/0 -> single-device
    :class:`AsyncEngine`, otherwise :class:`ShardedAsyncEngine` on that
    many mesh devices. ``overrides`` replace fields of ``config``."""
    from repro.sim.engine import AsyncEngine, ShardedAsyncEngine

    cfg = resolve_config(config, overrides)
    if not shards:
        return AsyncEngine(update, config=cfg)
    return ShardedAsyncEngine(update, num_shards=int(shards), config=cfg)
