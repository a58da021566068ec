"""Engine-aware checkpointing: full resume closures for both engines.

Built on the crash-safe entry primitives of
:mod:`repro.checkpoint.checkpoint` (staged ``<entry>.tmp`` writes,
per-file sha256, atomic rename, ``keep_last`` rotation), this module
captures everything a killed run needs to resume exactly:

* **AsyncEngine** — one ``state.npz`` holding every :class:`SimState`
  leaf (Theta, delay-ring ``hist``, slot counter, churn mask, PRNG key,
  the update state — including the DP accountant's spend counts — and
  the in-jit metrics counters), plus ``topology.npz`` for dynamic runs
  (the live CSR graph, slot capacity, topology version, pending-arrival
  ids) and the host topology log.
* **ShardedAsyncEngine** — a **per-shard layout with no gather**: one
  ``shard_<s>.npz`` per shard carrying that shard's owned rows (Theta
  block, churn mask, per-agent update-state leaves, ``last_wake``)
  keyed by relabel-stable *original agent ids*, plus ``partition.npz``
  (the frozen ownership: order permutation, block bounds, tile width)
  and ``scalars.npz`` (per-shard PRNG keys, counters, the CHOCO ``ef``
  accumulator, counter-type metrics leaves). Theta never materializes
  as one (n, p) host array at save *or* load.

Restore validates a **manifest fingerprint** — graph sha256, n, p,
dtype, an :class:`repro.sim.EngineConfig` digest, topology version —
before touching engine state, and supports **shard-count-elastic**
resume: a checkpoint written at S shards restores into an engine at S'
shards by re-cutting via ``partition_graph`` and re-tiling the saved
per-shard rows through :meth:`GraphPartition.place_rows`. Same-S resume
is bit-exact; the elastic policies (per-shard keys re-derived, shard
counters collapsed into shard 0, ``ef`` re-initialized) are recorded in
``docs/DEVIATIONS.md``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import (
    CheckpointError,
    _flatten_with_paths,
    _from_numpy,
    _leaf_dtype_name,
    _load_arrays,
    _resolve_entry,
    _save_entry,
    _to_numpy,
    structure_digest,
)
from repro.core.graph import CSRGraph, TopologyState, as_csr
from repro.sim.partition import partition_from_ownership, partition_graph

_EXCLUDED_CONFIG_FIELDS = ("partition", "devices")


# ---------------------------------------------------------------------------
# Fingerprint
# ---------------------------------------------------------------------------


def _token(v) -> str:
    """Deterministic string form of a config field value (digest input)."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return repr(v)
    if isinstance(v, (np.ndarray, jnp.ndarray)):
        a = np.ascontiguousarray(np.asarray(v))
        return f"array:{a.dtype}:{a.shape}:{hashlib.sha256(a.tobytes()).hexdigest()[:16]}"
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        inner = ",".join(
            f"{f.name}={_token(getattr(v, f.name))}" for f in dataclasses.fields(v)
        )
        return f"{type(v).__name__}({inner})"
    if isinstance(v, dict):
        return "{" + ",".join(f"{k!r}:{_token(v[k])}" for k in sorted(v)) + "}"
    if isinstance(v, (list, tuple, set, frozenset)):
        items = sorted(v, key=repr) if isinstance(v, (set, frozenset)) else v
        return "[" + ",".join(_token(x) for x in items) + "]"
    try:
        return f"dtype:{jnp.dtype(v).name}"
    except TypeError:
        pass
    r = repr(v)
    # Default object reprs embed a memory address — useless as identity.
    return type(v).__name__ if " at 0x" in r else r


def config_digest(cfg) -> str:
    """sha256 identity of an :class:`EngineConfig`, placement fields
    (``partition``/``devices``) excluded — those pick *where* the run
    executes, not *what* it computes, and must not block a resume on a
    different device set."""
    parts = [
        f"{f.name}={_token(getattr(cfg, f.name))}"
        for f in dataclasses.fields(cfg)
        if f.name not in _EXCLUDED_CONFIG_FIELDS
    ]
    return hashlib.sha256("|".join(parts).encode()).hexdigest()


def _live_csr(engine) -> CSRGraph:
    """The engine's current collaboration graph (live CSR when dynamic)."""
    if getattr(engine, "_csr", None) is not None:
        return engine._csr
    return as_csr(engine.update.graph)


def engine_fingerprint(engine) -> dict:
    """The identity a checkpoint must match to restore into ``engine``."""
    is_sharded = hasattr(engine, "part")
    fp = {
        "engine": "sharded" if is_sharded else "async",
        "n": int(engine.n),
        "p": int(engine.p),
        "dtype": str(jnp.dtype(engine.dtype).name),
        "config": config_digest(engine.config),
        "metrics": engine._macc is not None,
        "dynamic": bool(engine.dynamic),
        "graph": _live_csr(engine).digest(),
        "topology_version": (
            int(np.asarray(engine.topo.version))
            if getattr(engine, "topo", None) is not None
            else 0
        ),
    }
    if is_sharded:
        fp["num_shards"] = int(engine.num_shards)
    return fp


def _check_fingerprint(entry: str, saved: dict, now: dict) -> None:
    """Reject a checkpoint/engine identity mismatch with a clear error.

    ``num_shards`` may differ (elastic restore) and ``graph`` /
    ``topology_version`` are authoritative *from the checkpoint* on
    dynamic runs (restore adopts the saved topology), so only static
    engines compare graphs.
    """
    strict = ["engine", "n", "p", "dtype", "config", "metrics", "dynamic"]
    if not saved.get("dynamic"):
        strict.append("graph")
    for key in strict:
        if saved.get(key) != now.get(key):
            raise CheckpointError(
                f"{entry}: fingerprint mismatch on {key!r}: checkpoint has "
                f"{saved.get(key)!r}, engine has {now.get(key)!r}"
            )


# ---------------------------------------------------------------------------
# Topology capture (shared)
# ---------------------------------------------------------------------------


def _topology_arrays(engine) -> dict:
    csr = engine._csr
    arrs = {
        "indptr": np.asarray(csr.indptr, np.int64),
        "indices": np.asarray(csr.indices, np.int32),
        "data": np.asarray(csr.data, np.float64),
        "pending": np.asarray(sorted(engine._pending), np.int64),
    }
    if getattr(engine, "topo", None) is not None:
        arrs["capacity"] = np.int64(engine.topo.capacity)
        arrs["version"] = np.int64(np.asarray(engine.topo.version))
    return arrs


def _topology_from_arrays(arrs) -> tuple[CSRGraph, set[int]]:
    csr = CSRGraph(
        indptr=np.asarray(arrs["indptr"], np.int64),
        indices=np.asarray(arrs["indices"], np.int32),
        data=np.asarray(arrs["data"], np.float64),
    )
    return csr, {int(i) for i in arrs["pending"]}


def _restore_topology_log(engine, manifest: dict) -> None:
    for k, v in manifest.get("topology_log", {}).items():
        engine.topology_log[k] = float(v) if k == "last_drift" else int(v)


# ---------------------------------------------------------------------------
# AsyncEngine closure
# ---------------------------------------------------------------------------


def _async_state_dict(engine, state, step: int):
    flat, _ = _flatten_with_paths(state)
    arrays = {}
    records = []
    for i, (pth, leaf) in enumerate(flat):
        arr, dt = _to_numpy(leaf)
        key = f"leaf_{i}"
        arrays[key] = arr
        records.append(
            {"key": key, "path": pth, "dtype": dt, "shape": list(arr.shape)}
        )
    files = {"state.npz": arrays}
    manifest = {
        "kind": "engine",
        "engine": "async",
        "step": int(step),
        "fingerprint": engine_fingerprint(engine),
        "leaves": records,
        "structure": structure_digest(
            (r["path"], r["dtype"], r["shape"]) for r in records
        ),
    }
    if engine.dynamic:
        files["topology.npz"] = _topology_arrays(engine)
        manifest["topology_log"] = dict(engine.topology_log)
    return files, manifest


def _restore_async(engine, entry: str, manifest: dict):
    fp = manifest["fingerprint"]
    _check_fingerprint(entry, fp, engine_fingerprint(engine))
    data = _load_arrays(entry, manifest)
    if fp.get("dynamic"):
        csr, pending = _topology_from_arrays(data)
        engine._pending = pending
        engine.topo = TopologyState.from_csr(
            csr,
            capacity=int(data["capacity"]),
            version=int(data["version"]),
        )
        engine._csr = csr
        engine._dyn = engine._dyn_tiles()
        _restore_topology_log(engine, manifest)
    like = engine.init_state(np.zeros((engine.n, engine.p)))
    like_flat, treedef = _flatten_with_paths(like)
    records = manifest["leaves"]
    saved_digest = manifest.get("structure")
    like_digest = structure_digest(
        (p, _leaf_dtype_name(ref), list(np.shape(ref))) for p, ref in like_flat
    )
    if saved_digest != like_digest:
        from repro.checkpoint.checkpoint import _check_structure

        _check_structure(entry, records, like_flat)
        raise CheckpointError(f"{entry}: engine state structure digest mismatch")
    leaves = [
        jnp.asarray(_from_numpy(data[r["key"]], r["dtype"])) for r in records
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves), int(manifest["step"])


# ---------------------------------------------------------------------------
# ShardedAsyncEngine closure (per-shard layout, no gather)
# ---------------------------------------------------------------------------


def _sharded_state_dict(engine, state, step: int):
    part, S = engine.part, engine.num_shards
    files: dict = {}
    bf16: list[str] = []

    def put(fname, arrs, key, value):
        arr, dt = _to_numpy(value)
        arrs[key] = arr
        if dt == "bfloat16":
            bf16.append(f"{fname}/{key}")

    files["partition.npz"] = {
        "order": np.asarray(part.order, np.int64),
        "bounds": np.asarray(part.bounds, np.int64),
        "sizes": np.asarray(part.sizes, np.int64),
        "tile_width": np.int64(part.tile_width),
        "batch_size": np.int64(engine.batch_size),
    }

    ustate_flat, _ = _flatten_with_paths(state.ustate)
    ustate_records = [
        {
            "path": pth,
            "dtype": _leaf_dtype_name(leaf),
            "shape_tail": list(np.shape(leaf)[2:]),
        }
        for pth, leaf in ustate_flat
    ]
    metrics = state.metrics if engine._macc is not None else None
    counter_keys = (
        []
        if metrics is None
        else [
            k for k, kind in engine._macc.leaf_kinds().items() if kind == "counter"
        ]
    )
    has_last_wake = metrics is not None and "last_wake" in metrics

    # One file per shard, owned rows only, keyed by original agent ids —
    # each block is pulled as its own (R, ...) tile; the (n, p) model
    # matrix is never assembled on the host.
    for s in range(S):
        size = int(part.sizes[s])
        fname = f"shard_{s}.npz"
        arrs: dict = {"ids": np.asarray(part.owned[s, :size], np.int64)}
        put(fname, arrs, "theta", state.Theta[s][:size])
        arrs["active"] = np.asarray(state.active[s][:size])
        for j, (_pth, leaf) in enumerate(ustate_flat):
            put(fname, arrs, f"ustate_{j}", leaf[s][:size])
        if has_last_wake:
            arrs["last_wake"] = np.asarray(metrics["last_wake"][s][:size])
        files[fname] = arrs

    sc: dict = {
        "keys": np.asarray(state.keys),
        "applied": np.asarray(state.applied),
        "dropped": np.asarray(state.dropped),
        "messages": np.asarray(state.messages),
        "ptr": np.asarray(state.ptr),
    }
    if state.ef is not None:
        put("scalars.npz", sc, "ef", state.ef)
    for k in counter_keys:
        put("scalars.npz", sc, f"metric_{k}", metrics[k])
    files["scalars.npz"] = sc

    manifest = {
        "kind": "engine",
        "engine": "sharded",
        "step": int(step),
        "fingerprint": engine_fingerprint(engine),
        "bf16": bf16,
        "theta_dtype": _leaf_dtype_name(state.Theta),
        "ustate": ustate_records,
        "metrics_keys": counter_keys,
        "has_last_wake": has_last_wake,
        "partition": {"mode": part.mode, "relabel": part.relabel},
    }
    if engine.dynamic:
        files["topology.npz"] = _topology_arrays(engine)
        manifest["topology_log"] = dict(engine.topology_log)
    return files, manifest


def _adopt_partition(engine, manifest: dict, data: dict):
    """Point the engine at the checkpoint's graph + partition.

    Same-S: the saved ownership (order/bounds/tile width) is rebuilt
    verbatim via :func:`partition_from_ownership` — the only way to
    reproduce a patch-chain partition bit-exactly. Elastic (S differs):
    static engines keep their own fresh cut of the (identical) graph;
    dynamic engines re-cut the *saved* live graph at the engine's S.
    Never routes through ``set_topology`` — its relayout path assembles
    (n, p) host arrays, which the per-shard restore contract forbids.
    """
    fp = manifest["fingerprint"]
    saved_S = int(fp["num_shards"])
    dynamic = bool(fp.get("dynamic"))
    pending_changed = False
    if dynamic:
        csr, pending = _topology_from_arrays(data)
        pending_changed = pending != engine._pending
        engine._pending = pending
        _restore_topology_log(engine, manifest)
    else:
        csr = engine._csr
    meta = manifest.get("partition", {})
    if saved_S == engine.num_shards:
        part = engine.part
        same_cut = (
            np.array_equal(np.asarray(data["order"]), np.asarray(part.order))
            and np.array_equal(np.asarray(data["bounds"]), np.asarray(part.bounds))
            and int(data["tile_width"]) == part.tile_width
        )
        same_graph = csr is engine._csr or csr.digest() == engine._csr.digest()
        engine.batch_size = int(data["batch_size"])
        if same_cut and same_graph and not pending_changed:
            return saved_S  # the engine already sits on the saved cut
        new_part = partition_from_ownership(
            csr,
            data["order"],
            data["bounds"],
            mode=meta.get("mode", engine.config.partition_mode),
            relabel=meta.get("relabel"),
            tile_width=int(data["tile_width"]),
        )
    elif dynamic or pending_changed:
        new_part = partition_graph(
            csr,
            engine.num_shards,
            mode=engine.config.partition_mode,
            relabel=engine.config.relabel,
            coords=engine.config.coords,
        )
    else:
        return saved_S  # elastic static: the engine's own fresh cut serves
    engine._csr = csr
    engine.part = new_part
    engine.smix = engine.smix.rebound(new_part)
    engine.exchange_method = engine.smix.method
    engine.batch_size = int(min(engine.batch_size, new_part.rows_per_shard))
    engine._rebuild_static()
    return saved_S


def _host_zeros(leaf) -> np.ndarray:
    return np.zeros(np.shape(leaf), np.asarray(jnp.zeros((), leaf.dtype)).dtype)


def _load_file(entry: str, name: str) -> dict:
    """One verified npz file of an entry as ``{key: array}``."""
    with np.load(os.path.join(entry, name)) as z:
        return {k: z[k] for k in z.files}


def _restore_sharded(engine, entry: str, manifest: dict):
    fp = manifest["fingerprint"]
    _check_fingerprint(entry, fp, engine_fingerprint(engine))
    pmeta = _load_file(entry, "partition.npz")
    topo = _load_file(entry, "topology.npz") if fp.get("dynamic") else {}
    saved_S = _adopt_partition(engine, manifest, {**pmeta, **topo})
    elastic = saved_S != engine.num_shards
    part, S = engine.part, engine.num_shards
    bf16 = set(manifest.get("bf16", []))

    def from_file(fname, arrs, key):
        return _from_numpy(
            arrs[key], "bfloat16" if f"{fname}/{key}" in bf16 else str(arrs[key].dtype)
        )

    blank = engine._blank_state()
    ustate_flat, ustate_def = _flatten_with_paths(blank.ustate)
    records = manifest.get("ustate", [])
    if len(records) != len(ustate_flat):
        raise CheckpointError(
            f"{entry}: update-state mismatch — checkpoint has {len(records)} "
            f"leaves, engine expects {len(ustate_flat)}"
        )
    for rec, (pth, leaf) in zip(records, ustate_flat):
        if (
            rec["path"] != pth
            or rec["dtype"] != _leaf_dtype_name(leaf)
            or tuple(rec["shape_tail"]) != tuple(np.shape(leaf)[2:])
        ):
            raise CheckpointError(
                f"{entry}: update-state leaf {pth!r} mismatch: checkpoint "
                f"({rec['path']!r}, {rec['dtype']}, {tuple(rec['shape_tail'])}) "
                f"!= engine ({pth!r}, {_leaf_dtype_name(leaf)}, "
                f"{tuple(np.shape(leaf)[2:])})"
            )
    if bool(manifest.get("has_last_wake")) and engine._macc is None:
        raise CheckpointError(f"{entry}: checkpoint carries metrics, engine has none")

    theta_t = _host_zeros(blank.Theta)
    active_t = np.zeros((S, part.rows_per_shard), bool)
    ustate_t = [_host_zeros(leaf) for _pth, leaf in ustate_flat]
    lw_t = (
        _host_zeros(blank.metrics["last_wake"])
        if manifest.get("has_last_wake")
        else None
    )
    # Re-tile each saved shard's owned rows through the live partition's
    # id maps — works unchanged whether the cut moved or S changed, and
    # only one shard file is resident on the host at a time.
    for s in range(saved_S):
        fname = f"shard_{s}.npz"
        z = _load_file(entry, fname)
        ids = z["ids"]
        part.place_rows(theta_t, ids, from_file(fname, z, "theta"))
        part.place_rows(active_t, ids, z["active"])
        for j, t in enumerate(ustate_t):
            part.place_rows(t, ids, from_file(fname, z, f"ustate_{j}"))
        if lw_t is not None:
            part.place_rows(lw_t, ids, z["last_wake"])

    sc = _load_file(entry, "scalars.npz")
    if not elastic:
        keys = jnp.asarray(sc["keys"])
        applied = jnp.asarray(sc["applied"])
        dropped = jnp.asarray(sc["dropped"])
        messages = jnp.asarray(sc["messages"])
        ptr = jnp.asarray(sc["ptr"])
        ef = blank.ef
        if (
            engine._use_ef
            and "ef" in sc
            and np.shape(sc["ef"]) == np.shape(blank.ef)
        ):
            ef = jnp.asarray(from_file("scalars.npz", sc, "ef"))
    else:
        # Elastic policies (recorded in docs/DEVIATIONS.md): per-shard
        # PRNG keys re-derive from the seed for the new S, additive
        # counters collapse into shard 0 (run totals preserved), and the
        # error-feedback accumulator restarts (its rows describe the old
        # cut's border).
        keys = blank.keys
        ptr0 = int(np.asarray(sc["ptr"])[0])
        ptr = jnp.full((S,), ptr0, jnp.int32)
        applied = jnp.zeros(S, jnp.int32).at[0].set(int(sc["applied"].sum()))
        dropped = jnp.zeros(S, jnp.int32).at[0].set(int(sc["dropped"].sum()))
        messages = (
            jnp.zeros(S, jnp.float32).at[0].set(float(sc["messages"].sum()))
        )
        ef = blank.ef

    metrics = blank.metrics
    if engine._macc is not None:
        metrics = dict(metrics)
        if lw_t is not None:
            metrics["last_wake"] = jnp.asarray(lw_t)
        for k in manifest.get("metrics_keys", []):
            if k not in metrics or f"metric_{k}" not in sc:
                continue
            saved = np.asarray(from_file("scalars.npz", sc, f"metric_{k}"))
            tmpl = metrics[k]
            if not elastic:
                if saved.shape == tuple(np.shape(tmpl)):
                    metrics[k] = jnp.asarray(saved)
            elif saved.shape[1:] == tuple(np.shape(tmpl))[1:]:
                total = saved.sum(axis=0)
                metrics[k] = (
                    jnp.zeros_like(tmpl).at[0].set(jnp.asarray(total, tmpl.dtype))
                )

    state = blank._replace(
        Theta=theta_t,
        active=active_t,
        keys=keys,
        ustate=jax.tree_util.tree_unflatten(ustate_def, list(ustate_t)),
        applied=applied,
        dropped=dropped,
        messages=messages,
        ptr=ptr,
        ef=ef,
        metrics=metrics,
    )
    return engine.place(state), int(manifest["step"])


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def engine_state_dict(engine, state, step: int | None = None):
    """The engine's complete resume closure as ``(files, manifest)``.

    ``files`` maps checkpoint file names to ``{key: numpy array}``;
    ``manifest`` is the JSON-serializable header (fingerprint included).
    This is exactly what :func:`save_engine_checkpoint` writes.
    """
    step = engine._ptr_of(state) if step is None else int(step)
    if hasattr(engine, "part"):
        return _sharded_state_dict(engine, state, step)
    return _async_state_dict(engine, state, step)


def save_engine_checkpoint(engine, state, path, *, step=None, keep_last=None):
    """Write a crash-safe engine checkpoint (see module docstring).

    ``step`` defaults to the state's slot counter. With ``keep_last=K``,
    ``path`` is a rotation root (entries ``ckpt-<step>``, newest K
    kept); otherwise it is the entry directory itself. Returns the entry
    directory written.
    """
    files, manifest = engine_state_dict(engine, state, step=step)
    return _save_entry(path, files, manifest, manifest["step"], keep_last)


def restore(engine, path):
    """Load an engine checkpoint into ``engine``; returns ``(state, step)``.

    ``path`` may be one entry or a ``keep_last`` rotation root (newest
    valid entry wins, torn entries skipped). The manifest fingerprint
    (graph hash, n, p, dtype, config digest) is validated first — any
    mismatch raises :class:`CheckpointError` naming the field. Dynamic
    runs re-adopt the saved live topology (graph, capacity, version,
    pending arrivals, host log); sharded restores re-tile per-shard
    files through the live partition, elastically when S changed.
    """
    entry, manifest = _resolve_entry(path)
    if manifest.get("kind") != "engine":
        raise CheckpointError(
            f"{entry}: not an engine checkpoint (kind={manifest.get('kind')!r}); "
            "pytree checkpoints load via repro.checkpoint.load_checkpoint"
        )
    is_sharded = hasattr(engine, "part")
    saved_engine = manifest.get("engine")
    want = "sharded" if is_sharded else "async"
    if saved_engine != want:
        raise CheckpointError(
            f"{entry}: {saved_engine} checkpoint cannot restore into a "
            f"{type(engine).__name__}"
        )
    if not is_sharded:
        return _restore_async(engine, entry, manifest)
    return _restore_sharded(engine, entry, manifest)
