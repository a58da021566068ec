"""Neighbour-sum operator ``sum_j W_ij Theta_j`` with dense/sparse dispatch.

Every algorithm in ``repro.core`` reduces its graph traffic to two shapes:

* ``all``: the full neighbour sum for every agent at once (synchronous
  rounds, block gradients) — (n, p) -> (n, p);
* ``row``: one agent's neighbour sum under a traced index (the Eq. 4
  asynchronous tick inside ``lax.scan``) — (n, p), i -> (p,).

:func:`mix_op` builds a :class:`MixOp` for either graph representation.
Below :func:`repro.core.graph.sparse_crossover` agents the operator
materializes the (n, n) matrix and uses the MXU matmul fast path; at or
above it the operator stays O(nnz): padded-neighbour gathers for ``row``
and a ``segment_sum`` for ``all``. On a TPU backend, ``all`` routes
through the ``graph_mix``/``sparse_mix`` Pallas kernels for f32 at
on-chip agent counts (and through plain jnp otherwise — on this CPU
container the kernels would run interpreted, so they are test/TPU-only).
Pass ``mode="dense"``/``"sparse"`` to pin a representation explicitly
(the property tests assert both paths agree).
"""

from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (
    CSRGraph,
    as_csr,
    dense_weights,
    int_env_knob,
    sparse_crossover,
)


# The Pallas mixing kernels keep the (n, bp) Theta slab VMEM-resident, so
# they only serve the on-chip regime; past this the jnp paths take over.
_KERNEL_MAX_N = 4096

# f32 products at full f32 precision: a TPU's default would round the
# operands of these contractions to bf16.
_HIGHEST = jax.lax.Precision.HIGHEST


def kernel_max_n() -> int:
    """Largest agent count the Pallas mixing kernels auto-engage at.

    The kernels keep the whole (n, bp) Theta slab VMEM-resident, so the
    ceiling tracks the chip's VMEM budget, not correctness. Override with
    the ``REPRO_KERNEL_MAX_N`` environment variable (mirrors
    ``REPRO_SPARSE_CROSSOVER``); set 0 to disable the kernel auto-path.
    """
    return int_env_knob("REPRO_KERNEL_MAX_N", _KERNEL_MAX_N)


@dataclasses.dataclass(frozen=True, eq=False)
class MixOp:
    """Dense or sparse neighbour-sum operator.

    Its methods close over the numpy arrays, which jit bakes into a
    program as constants; the batched engines instead pass
    :meth:`tables` as a jit argument to :meth:`gather_rows`.
    """

    kind: str  # "dense" | "sparse"
    n: int
    W: np.ndarray | None = None  # (n, n) — dense only
    idx: np.ndarray | None = None  # (n, K) padded neighbour indices — sparse only
    w: np.ndarray | None = None  # (n, K) padded neighbour weights — sparse only
    rows: np.ndarray | None = None  # (nnz,) COO rows, sorted — sparse only
    cols: np.ndarray | None = None  # (nnz,)
    vals: np.ndarray | None = None  # (nnz,)

    def _kernel_auto(self, Theta) -> bool:
        # Engage the Pallas kernels only where they are the right tool:
        # compiled TPU lowering, f32 (the kernels accumulate/return f32 —
        # silently downcasting the x64 theory paths is not acceptable),
        # and an on-chip agent count whose Theta slab fits VMEM.
        return (
            jax.default_backend() == "tpu"
            and Theta.dtype == jnp.float32
            and self.n <= kernel_max_n()
        )

    def all(self, Theta, use_kernel: bool | None = None):
        """sum_j W_ij Theta_j for every agent: (n, p) -> (n, p).

        ``use_kernel``: force the Pallas kernel path on (True, interpreted
        off-TPU) or off (False); None auto-selects it on TPU for f32 at
        on-chip n.
        """
        if use_kernel is None:
            use_kernel = self._kernel_auto(Theta)
        if use_kernel:
            from repro.kernels import ops

            if self.kind == "dense":
                return ops.graph_mix(jnp.asarray(self.W, jnp.float32), Theta)
            return ops.sparse_mix(
                jnp.asarray(self.idx), jnp.asarray(self.w, jnp.float32), Theta
            )
        if self.kind == "dense":
            return jnp.matmul(jnp.asarray(self.W, Theta.dtype), Theta, precision=_HIGHEST)
        contrib = jnp.asarray(self.vals, Theta.dtype)[:, None] * Theta[jnp.asarray(self.cols)]
        return jax.ops.segment_sum(
            contrib, jnp.asarray(self.rows), num_segments=self.n, indices_are_sorted=True
        )

    def row(self, Theta, i):
        """sum_j W_ij Theta_j for one (possibly traced) agent i: -> (p,)."""
        if self.kind == "dense":
            return jnp.matmul(jnp.asarray(self.W, Theta.dtype)[i], Theta, precision=_HIGHEST)
        cols_i = jnp.asarray(self.idx)[i]  # (K,)
        w_i = jnp.asarray(self.w, Theta.dtype)[i]  # (K,)
        return jnp.sum(w_i[:, None] * Theta[cols_i], axis=0)

    def tables(self, dtype) -> dict:
        """The operator's arrays as device arrays (weights in ``dtype``), for
        :meth:`gather_rows` callers that pass them through jit."""
        if self.kind == "dense":
            return {"W": jnp.asarray(self.W, dtype)}
        return {"idx": jnp.asarray(self.idx), "w": jnp.asarray(self.w, dtype)}

    def gather_rows(self, Theta, idx, use_kernel: bool | None = None, tables=None):
        """Batched neighbour sums for a row subset: (B,) indices -> (B, p).

        The super-tick path of ``repro.sim``: gather only the woken agents'
        neighbourhoods instead of computing all n sums. Indices may be
        traced and may contain the out-of-range padding sentinel n (jit
        gathers clamp it to row n-1; callers mask those entries out when
        scattering). Sparse graphs route through the ``sparse_mix`` Pallas
        machinery on TPU under the same gate as :meth:`all`. ``tables``:
        this operator's :meth:`tables`, passed in by a jitted caller (None
        reads the operator's own arrays).
        """
        if use_kernel is None:
            use_kernel = self._kernel_auto(Theta)
        if tables is None:
            tables = self.tables(Theta.dtype)
        if self.kind == "dense":
            W = jnp.asarray(tables["W"], Theta.dtype)
            return jnp.matmul(W[idx], Theta, precision=_HIGHEST)
        cols = tables["idx"][idx]  # (B, K)
        w = jnp.asarray(tables["w"], Theta.dtype)[idx]  # (B, K)
        if use_kernel:
            from repro.kernels import ops

            return ops.sparse_rows_mix(cols, w.astype(jnp.float32), Theta)
        return jnp.einsum("bk,bkp->bp", w, Theta[cols], precision=_HIGHEST)

    def edge_tables(self) -> dict:
        """The graph's weights for :meth:`pairwise_smoothness`, as device
        arrays: the (n, n) matrix, or the sorted COO triples."""
        if self.kind == "dense":
            return {"W": jnp.asarray(self.W)}
        return {
            "rows": jnp.asarray(self.rows),
            "cols": jnp.asarray(self.cols),
            "vals": jnp.asarray(self.vals),
        }

    def pairwise_smoothness(self, Theta, tables=None):
        """1/2 sum_{i<j} W_ij ||Theta_i - Theta_j||^2 (Eq. 2 first term).

        ``tables``: :meth:`edge_tables`, passed in by a jitted caller (None
        reads the operator's own arrays)."""
        t = self.edge_tables() if tables is None else tables
        if self.kind == "dense":
            W = jnp.asarray(t["W"], Theta.dtype)
            diffs = Theta[:, None, :] - Theta[None, :, :]
            return 0.25 * jnp.sum(W * jnp.sum(diffs**2, axis=-1))
        d2 = jnp.sum((Theta[t["rows"]] - Theta[t["cols"]]) ** 2, axis=-1)
        return 0.25 * jnp.sum(jnp.asarray(t["vals"], Theta.dtype) * d2)


_EXCHANGE_METHODS = ("all_gather", "p2p", "auto")
_EXCHANGE_DTYPES = ("f32", "bf16", "int8")

# The bare-string deprecation fires once per process, not once per engine:
# sweeps and parity tests construct dozens of engines from the same config
# and a warning per construction is noise that buries real warnings.
_warned_bare_exchange_string = False


@dataclasses.dataclass(frozen=True)
class ExchangeSpec:
    """Typed halo-exchange configuration for the sharded engines.

    Replaces the bare ``method`` strings: the wire format now has three
    independent axes —

    * ``method``: which collective ships the halo rows (``"all_gather"``
      replicated border pool, ``"p2p"`` per-ring-offset ``ppermute``, or
      ``"auto"`` to pick by the partition's measured cut);
    * ``dtype``: the payload element type. ``"f32"`` ships full-precision
      rows (bit-exact, the PR-4 behaviour); ``"bf16"`` halves the bytes
      per row; ``"int8"`` quarters them, shipping one f32 scale per row
      (``max|row| / 127`` symmetric quantization);
    * ``error_feedback``: carry a per-border-row residual accumulator
      (CHOCO-SGD style) in :class:`repro.sim.ShardedSimState` so the
      quantization error is re-injected into the next slot's payload
      instead of biasing the gossip fixed point.

    Old-style strings (``exchange="p2p"``) still work everywhere a spec
    is accepted, via :meth:`coerce` + ``DeprecationWarning``. The
    documented string form for CLIs is :meth:`from_string`
    (``"p2p:bf16:ef"``), which does not warn.
    """

    method: str = "auto"
    dtype: str = "f32"
    error_feedback: bool = False

    def __post_init__(self):
        if self.method not in _EXCHANGE_METHODS:
            raise ValueError(
                f"unknown exchange method {self.method!r} (use one of {_EXCHANGE_METHODS})"
            )
        if self.dtype not in _EXCHANGE_DTYPES:
            raise ValueError(
                f"unknown exchange dtype {self.dtype!r} (use one of {_EXCHANGE_DTYPES})"
            )
        if self.error_feedback and self.dtype == "f32":
            raise ValueError(
                "error_feedback has no effect on the lossless f32 wire format; "
                "pick dtype='bf16' or 'int8'"
            )

    @classmethod
    def from_string(cls, spec: str) -> "ExchangeSpec":
        """Parse the CLI form ``method[:dtype[:ef]]``, e.g. ``"p2p:bf16:ef"``."""
        parts = [s for s in str(spec).split(":") if s]
        if not parts:
            raise ValueError(f"empty exchange spec {spec!r}")
        method, rest = parts[0], parts[1:]
        ef = "ef" in rest
        dtypes = [r for r in rest if r != "ef"]
        if len(dtypes) > 1 or any(r not in _EXCHANGE_DTYPES for r in dtypes):
            raise ValueError(f"bad exchange spec {spec!r} (want method[:dtype[:ef]])")
        return cls(method=method, dtype=dtypes[0] if dtypes else "f32", error_feedback=ef)

    @classmethod
    def coerce(cls, value) -> "ExchangeSpec":
        """Accept an ExchangeSpec, None (defaults), or a deprecated string."""
        if value is None:
            return cls()
        if isinstance(value, cls):
            return value
        if isinstance(value, str):
            global _warned_bare_exchange_string
            if not _warned_bare_exchange_string:
                _warned_bare_exchange_string = True
                warnings.warn(
                    f"passing exchange={value!r} as a bare string is deprecated; "
                    f"use ExchangeSpec (e.g. ExchangeSpec.from_string({value!r}))",
                    DeprecationWarning,
                    stacklevel=3,
                )
            return cls.from_string(value)
        raise TypeError(f"exchange must be an ExchangeSpec or string, got {type(value)!r}")

    def payload_bytes_per_row(self, p: int) -> int:
        """Wire bytes per exchanged row of width p (int8 adds its f32 scale)."""
        if self.dtype == "f32":
            return 4 * p
        if self.dtype == "bf16":
            return 2 * p
        return p + 4

    def needs_error_feedback_state(self) -> bool:
        """Whether the engine must thread a (Bmax, p) accumulator per shard."""
        return self.error_feedback and self.dtype != "f32"


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedMixOp:
    """Shard-local neighbour sums with halo exchange over an agent partition.

    The multi-device counterpart of :meth:`MixOp.gather_rows`: agents are
    position-contiguous blocks on a ``shard_map`` mesh axis, each shard
    holds its own (R, p) Theta block, and cross-shard edges are served by
    a **halo exchange** with two interchangeable wire formats:

    * ``method="all_gather"`` — every shard publishes its (Bmax,) border
      rows, one ``all_gather`` replicates the (S, Bmax, p) pool, and each
      shard gathers exactly the remote rows its tiles reference. One
      static collective, but the pool is replicated: each shard receives
      (S-1) * Bmax rows however few it needs — the right trade when the
      cut is dense (high halo fraction).
    * ``method="p2p"`` — one ``ppermute`` per mesh-ring offset in the
      partition's :func:`repro.sim.partition.point_to_point_plan`: each
      shard ships only the rows its ring-offset neighbour actually reads
      (padded to the per-offset max P_d) and scatters received rows into
      its halo slots. Each shard receives sum_d P_d rows — the right
      trade once a locality relabel has shrunk the cut to a few
      neighbour shards.

    Both formats fill the identical halo slots with identical row copies,
    so everything downstream — and therefore the two methods — is
    bit-exact-interchangeable. ``method="auto"`` in
    :func:`sharded_mix_op` picks whichever ships fewer rows per
    super-tick for the measured cut.

    **Compressed payloads** (``dtype="bf16"`` / ``"int8"`` from the
    :class:`ExchangeSpec`): each shard quantizes its border rows *once*
    per slot — every reader receives the same dequantized copy, whichever
    collective ships it — and the wire carries the narrow payload (int8
    adds one f32 scale per row, ``max|row| / 127``). With
    ``error_feedback`` the shard keeps a (Bmax, p) residual accumulator
    ``e``: it quantizes ``v = border + e`` and stores ``e' = v - dq(v)``,
    so the quantization error re-enters the next slot's payload instead
    of accumulating into a fixed-point bias. The accumulator is engine
    state (:class:`repro.sim.ShardedSimState` ``ef`` leaf), threaded
    through :meth:`exchange_halo`.

    The stacked (S, ...) plan arrays (``exchange_inputs``) and tiles are
    *inputs* to the shard_map'd caller (sliced per shard by
    ``in_specs``), never closed over — a closure would replicate the
    O(nnz) tiles onto every device, which is exactly what sharding
    exists to avoid.
    """

    n: int
    num_shards: int
    idx: np.ndarray  # (S, R, K) extended-local neighbour indices
    w: np.ndarray  # (S, R, K) weights (pad entries 0)
    border: np.ndarray  # (S, Bmax) local rows each shard publishes
    halo_src: np.ndarray  # (S, Hmax) flat index into the (S * Bmax,) border pool
    method: str = "all_gather"  # "all_gather" | "p2p"
    halo_width: int = 1  # Hmax: halo slots per shard in the extended array
    p2p_offsets: tuple[int, ...] = ()  # static ring offsets, one ppermute each
    p2p_send: tuple[np.ndarray, ...] = ()  # per offset: (S, P_d) local rows to ship
    p2p_dst: tuple[np.ndarray, ...] = ()  # per offset: (S, P_d) halo slots, sentinel Hmax
    p2p_bpos: tuple[np.ndarray, ...] = ()  # per offset: (S, P_d) border-pool positions of sends
    dtype: str = "f32"  # wire format: "f32" | "bf16" | "int8"
    error_feedback: bool = False  # thread a (Bmax, p) residual accumulator
    axis: str = "shards"

    @property
    def rows_per_shard(self) -> int:
        """R: padded rows per shard."""
        return self.idx.shape[1]

    def rebound(self, partition) -> "ShardedMixOp":
        """This operator rebuilt against a patched/repartitioned partition.

        The exchange *method* is pinned to this operator's already
        resolved choice (never re-run through ``"auto"``), so a
        dynamic-topology engine keeps a stable program structure across
        :meth:`repro.sim.partition.GraphPartition.patch` rebinds — only
        the plan arrays change. Wire dtype and error-feedback threading
        carry over unchanged.
        """
        return sharded_mix_op(
            partition,
            axis=self.axis,
            exchange=ExchangeSpec(
                method=self.method,
                dtype=self.dtype,
                error_feedback=self.error_feedback,
            ),
        )

    def exchange_inputs(self):
        """The stacked (S, ...) plan arrays the chosen method consumes.

        Pass this pytree through ``shard_map`` with a leading-axis spec
        (never close over it) and hand the per-shard slice to
        :meth:`exchange_halo`.
        """
        if self.method == "p2p":
            if self.dtype != "f32":
                # Compressed p2p quantizes the border pool once, then ships
                # per-offset *slices* of it: sends are re-addressed as
                # border-pool positions and the border table rides along.
                return {"border": self.border, "bpos": self.p2p_bpos, "dst": self.p2p_dst}
            return {"send": self.p2p_send, "dst": self.p2p_dst}
        return {"border": self.border, "halo_src": self.halo_src}

    def init_error_feedback(self, p: int, dtype=jnp.float32):
        """Zero (S, Bmax, p) residual accumulator (None when not threaded)."""
        if not (self.error_feedback and self.dtype != "f32"):
            return None
        return jnp.zeros((self.num_shards, self.border.shape[1], p), dtype)

    def _quantize(self, v):
        """Quantize border rows v (Bmax, p) -> (payload dict, dequantized)."""
        if self.dtype == "bf16":
            q = v.astype(jnp.bfloat16)
            return {"q": q}, q.astype(v.dtype)
        # int8 with per-row symmetric scales: scale = max|row| / 127.
        scale = jnp.max(jnp.abs(v), axis=-1, keepdims=True) / 127.0
        scale = jnp.maximum(scale, jnp.asarray(1e-30, v.dtype))
        q = jnp.clip(jnp.round(v / scale), -127.0, 127.0).astype(jnp.int8)
        return {"q": q, "scale": scale}, q.astype(v.dtype) * scale

    def exchange_halo(self, Theta_local, ex, ef=None, *, collect_stats=False):
        """Extend this shard's (R, p) block with its halo rows.

        Runs inside ``shard_map``. ``ex`` is this shard's slice of
        :meth:`exchange_inputs` (leading S axis already consumed); ``ef``
        is this shard's (Bmax, p) error-feedback accumulator slice (None
        when not threaded). Returns ``(Theta_ext, ef_new, stats)`` where
        ``Theta_ext`` is the (R + Hmax, p) extended array the tiles index
        — halo slots past this shard's real halo size are unreferenced by
        the tiles — and ``ef_new`` is the updated accumulator
        (unchanged/None without error feedback).

        The exchange decomposes into three ``jax.named_scope`` phases:

        * ``obs.halo_publish`` — gather/quantize/pack this shard's border
          rows into the send payload;
        * ``obs.halo_collective`` — the ``ppermute``s / ``all_gather`` that
          ship it;
        * ``obs.halo_scatter`` — dequantize and place received rows into
          the halo slots.

        ``collect_stats=True`` on a compressed wire reports the
        telemetry dict ``{"quant_err_sq", "ef_residual_sq"}`` computed
        from values the exchange already produced (stats is None
        otherwise) — collection never perturbs the payload.
        """
        S = self.num_shards
        stats = None

        # -- publish: pack (and on compressed wires, quantize) the border.
        with jax.named_scope("obs.halo_publish"):
            scales = None
            ef_new = ef
            if self.dtype == "f32":
                if self.method == "p2p":
                    send = tuple(Theta_local[snd] for snd in ex["send"])  # (P_d, p) each
                else:
                    send = Theta_local[ex["border"]]  # (Bmax, p)
            else:
                # Compressed wire: quantize the border pool once per slot —
                # every reader receives the same dequantized copy — and ship
                # the narrow payload through whichever collective the plan
                # chose.
                v = Theta_local[ex["border"]]  # (Bmax, p)
                if ef is not None:
                    v = v + ef.astype(v.dtype)
                payload, dq = self._quantize(v)
                ef_new = (v - dq) if ef is not None else ef
                if collect_stats:
                    err = (v - dq).astype(jnp.float32)
                    res = err if ef is not None else jnp.zeros_like(err)
                    stats = {
                        "quant_err_sq": jnp.sum(jnp.square(err)),
                        "ef_residual_sq": jnp.sum(jnp.square(res)),
                    }
                if self.method == "p2p":
                    send = tuple(payload["q"][bpos] for bpos in ex["bpos"])
                    if "scale" in payload:
                        scales = tuple(payload["scale"][bpos] for bpos in ex["bpos"])
                else:
                    send = payload["q"]
                    scales = payload.get("scale")

        # -- collective: ship the payload.
        with jax.named_scope("obs.halo_collective"):
            if self.method == "p2p":
                recv, recv_s = [], []
                for k, off in enumerate(self.p2p_offsets):
                    perm = [(s, (s + off) % S) for s in range(S)]
                    recv.append(jax.lax.ppermute(send[k], self.axis, perm))  # (P_d, ...)
                    if scales is not None:
                        recv_s.append(jax.lax.ppermute(scales[k], self.axis, perm))
                got = (tuple(recv), tuple(recv_s) if scales is not None else None)
            else:
                pool = jax.lax.all_gather(send, self.axis)  # (S, Bmax, ...)
                pool_s = (
                    jax.lax.all_gather(scales, self.axis) if scales is not None else None
                )
                got = (pool, pool_s)

        # -- scatter: dequantize received rows into the halo slots.
        with jax.named_scope("obs.halo_scatter"):
            if self.method == "p2p":
                bufs, sbufs = got
                halo = jnp.zeros(
                    (self.halo_width,) + Theta_local.shape[1:], Theta_local.dtype
                )
                for k in range(len(self.p2p_offsets)):
                    rows = bufs[k].astype(Theta_local.dtype)
                    if sbufs is not None:
                        rows = rows * sbufs[k].astype(Theta_local.dtype)
                    # Sentinel dst Hmax drops padding rows.
                    halo = halo.at[ex["dst"][k]].set(rows, mode="drop")
            else:
                pool, pool_s = got
                flat = pool.reshape((-1,) + pool.shape[2:])[ex["halo_src"]]  # (Hmax, ...)
                halo = flat.astype(Theta_local.dtype)
                if pool_s is not None:
                    halo = halo * pool_s.reshape((-1, 1))[ex["halo_src"]].astype(
                        Theta_local.dtype
                    )
            Theta_ext = jnp.concatenate([Theta_local, halo], axis=0)
        return Theta_ext, ef_new, stats

    def gather_rows(self, Theta_ext, idx_s, w_s, rows):
        """Neighbour sums for local ``rows`` from the extended array.

        ``rows`` may be traced and may carry the out-of-range sentinel R
        (clamped here; callers mask those entries when scattering), same
        contract as :meth:`MixOp.gather_rows`.
        """
        safe = jnp.minimum(rows, idx_s.shape[0] - 1)
        cols = idx_s[safe]  # (B, K)
        ww = jnp.asarray(w_s, Theta_ext.dtype)[safe]  # (B, K)
        return jnp.einsum("bk,bkp->bp", ww, Theta_ext[cols], precision=_HIGHEST)


def sharded_mix_op(
    partition, axis: str = "shards", exchange: "ExchangeSpec | str | None" = None
) -> ShardedMixOp:
    """Build the halo-exchange operator for a :class:`GraphPartition`.

    ``exchange`` is an :class:`ExchangeSpec` (None = defaults: auto
    method, f32 wire). ``method="auto"`` goes point-to-point only when
    it ships at most 3/4 of the all_gather rows on this partition's
    measured cut (``GraphPartition.exchange_rows``): a dense cut (high
    halo fraction, e.g. unrelabeled shuffled labels) pays S-1 ppermutes
    for barely less volume, so it falls back to the single fused
    collective; a locality-relabeled cut ships a small fraction and
    wins outright. Bare strings (``"p2p"``, ``"p2p:bf16"``) are accepted
    as a deprecated shim.
    """
    spec = ExchangeSpec.coerce(exchange)
    method = spec.method
    if method == "auto":
        method = (
            "p2p"
            if 4 * partition.exchange_rows("p2p") <= 3 * partition.exchange_rows("all_gather")
            else "all_gather"
        )
    offsets, sends, dsts = partition.p2p_plan if method == "p2p" else ((), (), ())
    bpos: tuple[np.ndarray, ...] = ()
    if method == "p2p" and spec.dtype != "f32":
        # Re-address each offset's send rows as positions in the (sorted,
        # unique) border list, so compressed sends slice the
        # quantized-once border pool instead of Theta itself.
        border = np.asarray(partition.border)
        bsizes = np.asarray(partition.border_sizes)
        bpos = tuple(
            np.stack(
                [
                    # Only the valid prefix of the border row is sorted; the
                    # zero padding past border_sizes[t] would break the
                    # search. Padding send entries (row 0) may land on an
                    # arbitrary position — the receiver's sentinel dst
                    # drops them.
                    np.searchsorted(
                        border[t, : int(bsizes[t])], np.asarray(snd)[t]
                    ).astype(np.int32)
                    for t in range(partition.num_shards)
                ]
            )
            for snd in sends
        )
    return ShardedMixOp(
        n=partition.n,
        num_shards=partition.num_shards,
        idx=partition.idx,
        w=partition.w,
        border=partition.border,
        halo_src=partition.halo_src,
        method=method,
        halo_width=partition.halo.shape[1],
        p2p_offsets=offsets,
        p2p_send=sends,
        p2p_dst=dsts,
        p2p_bpos=bpos,
        dtype=spec.dtype,
        error_feedback=spec.needs_error_feedback_state(),
        axis=axis,
    )


def mix_op(graph, mode: str = "auto") -> MixOp:
    """Build the neighbour-sum operator for a dense or CSR graph.

    ``mode="auto"`` picks dense below the crossover (small graphs pay the
    O(n^2) matrix gladly for the MXU matmul) and sparse at or above it —
    regardless of which representation the caller holds.
    """
    if mode == "auto":
        mode = "sparse" if graph.n >= sparse_crossover() else "dense"
    if mode == "dense":
        return MixOp(kind="dense", n=graph.n, W=dense_weights(graph))
    if mode != "sparse":
        raise ValueError(f"unknown mix mode {mode!r}")
    csr = as_csr(graph)
    idx, w = csr.padded_neighbors()
    return MixOp(
        kind="sparse",
        n=csr.n,
        idx=idx,
        w=w,
        rows=csr.row_ids(),
        cols=csr.indices,
        vals=csr.data,
    )
