"""Compressed halo exchange + config API tests.

In-process: the ExchangeSpec deprecation shim and the
EngineConfig/make_engine factory. Subprocess (8 forced host devices):
the sharded exchange matrix across S=4 x {all_gather, p2p} x {f32, bf16}
wires against the single-device engine, and the compressed fixed-point
acceptance — bf16 halos with error feedback land within 1e-4 of the
exact optimum while plain bf16 halos do not.
"""

import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

from repro.core import AgentData, knn_graph, make_objective
from repro.core.mixing import ExchangeSpec
from repro.sim import (
    AsyncEngine,
    CDUpdate,
    EngineConfig,
    ShardedAsyncEngine,
    make_engine,
)


def _quad_problem(n, p=4, m=3, seed=0):
    rng = np.random.default_rng(seed)
    graph = knn_graph(rng.normal(size=(n, 8)), k=8)
    targets = rng.normal(size=(n, p)) / np.sqrt(p)
    X = rng.normal(size=(n, m, p)) / np.sqrt(p)
    y = np.einsum("nmp,np->nm", X, targets)
    data = AgentData(X=X, y=y, mask=np.ones((n, m)))
    return make_objective(graph, data, "quadratic", mu=0.5, mix_mode="sparse")


def _forced_run(engine, n, masks):
    state = engine.init_state(np.zeros((n, engine.p)))
    for mask in masks:
        state = engine.step(state, mask)
    return state


# ---------------------------------------------------------------------------
# ExchangeSpec + deprecation shim
# ---------------------------------------------------------------------------


def test_exchange_spec_validation_and_strings():
    spec = ExchangeSpec.from_string("p2p:bf16:ef")
    assert (spec.method, spec.dtype, spec.error_feedback) == ("p2p", "bf16", True)
    assert ExchangeSpec.from_string("auto") == ExchangeSpec()
    with pytest.raises(ValueError):
        ExchangeSpec(method="ring")
    with pytest.raises(ValueError):
        ExchangeSpec(dtype="f16")
    with pytest.raises(ValueError):  # EF over a lossless wire is meaningless
        ExchangeSpec(dtype="f32", error_feedback=True)
    with pytest.raises(TypeError):
        ExchangeSpec.coerce(123)
    assert ExchangeSpec(dtype="bf16").payload_bytes_per_row(8) == 16
    assert ExchangeSpec(dtype="int8").payload_bytes_per_row(8) == 12  # q + scale
    assert ExchangeSpec().payload_bytes_per_row(8) == 32


def test_deprecated_exchange_string_warns_and_matches_spec():
    obj = _quad_problem(32, seed=4)
    n = obj.n
    rng = np.random.default_rng(2)
    masks = [rng.random(n) < 0.3 for _ in range(4)]
    with pytest.warns(DeprecationWarning, match="ExchangeSpec"):
        old = ShardedAsyncEngine(CDUpdate(obj), num_shards=1, slot_wakes=8.0,
                                 exchange="p2p")
    new = ShardedAsyncEngine(CDUpdate(obj), num_shards=1, slot_wakes=8.0,
                             exchange=ExchangeSpec(method="p2p"))
    s_old = _forced_run(old, n, masks)
    s_new = _forced_run(new, n, masks)
    assert np.array_equal(old.global_theta(s_old), new.global_theta(s_new))
    assert old.exchange_method == new.exchange_method == "p2p"


def test_exchange_spec_passes_without_warning():
    obj = _quad_problem(24, seed=6)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        ShardedAsyncEngine(
            CDUpdate(obj), num_shards=1,
            exchange=ExchangeSpec(method="all_gather", dtype="bf16"),
        )


# ---------------------------------------------------------------------------
# EngineConfig / make_engine
# ---------------------------------------------------------------------------


def test_engine_config_and_kwargs_build_identical_engines():
    obj = _quad_problem(32, seed=7)
    n = obj.n
    rng = np.random.default_rng(3)
    masks = [rng.random(n) < 0.3 for _ in range(4)]
    cfg = EngineConfig(slot_wakes=8.0, seed=1)
    a = _forced_run(AsyncEngine(CDUpdate(obj), config=cfg), n, masks)
    b = _forced_run(AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=1), n, masks)
    assert np.array_equal(np.asarray(a.Theta), np.asarray(b.Theta))
    # kwargs override config fields
    eng = AsyncEngine(CDUpdate(obj), config=cfg, slot_wakes=4.0)
    assert eng.config.slot_wakes == 4.0 and eng.config.seed == 1


def test_make_engine_dispatches_on_shards():
    obj = _quad_problem(24, seed=8)
    upd = CDUpdate(obj)
    assert isinstance(make_engine(upd, slot_wakes=8.0), AsyncEngine)
    assert isinstance(make_engine(upd, shards=0, slot_wakes=8.0), AsyncEngine)
    eng = make_engine(upd, shards=1, slot_wakes=8.0, relabel="rcm")
    assert isinstance(eng, ShardedAsyncEngine)
    assert eng.num_shards == 1


def test_engine_config_rejects_unknown_options():
    obj = _quad_problem(16, seed=9)
    with pytest.raises(TypeError, match="slot_wake"):
        AsyncEngine(CDUpdate(obj), slot_wake=8.0)  # typo'd kwarg
    # The row path has one form: there is no fused variant to select.
    gone = {"fused": "auto"}
    with pytest.raises(TypeError, match="fused"):
        EngineConfig(**gone)
    with pytest.raises(TypeError, match="fused"):
        AsyncEngine(CDUpdate(obj), **gone)


# ---------------------------------------------------------------------------
# multi-device subprocess matrices
# ---------------------------------------------------------------------------

EXCHANGE_MATRIX_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np, jax.numpy as jnp
    from repro.core import AgentData, knn_graph, make_objective
    from repro.sim import AsyncEngine, CDUpdate, ExchangeSpec, ShardedAsyncEngine

    assert len(jax.devices()) == 8

    rng = np.random.default_rng(0)
    n, p, m = 96, 4, 3
    graph = knn_graph(rng.normal(size=(n, 8)), k=8)
    targets = rng.normal(size=(n, p)) / np.sqrt(p)
    X = rng.normal(size=(n, m, p)) / np.sqrt(p)
    y = np.einsum("nmp,np->nm", X, targets)
    obj = make_objective(graph, AgentData(X=X, y=y, mask=np.ones((n, m))),
                         "quadratic", mu=0.5, mix_mode="sparse")
    upd = CDUpdate(obj)
    wrng = np.random.default_rng(7)
    masks = [wrng.random(n) < 0.15 for _ in range(4)]

    ref_eng = AsyncEngine(upd, slot_wakes=8.0)
    rs = ref_eng.init_state(np.zeros((n, p)))
    for msk in masks:
        rs = ref_eng.step(rs, msk)
    R = np.asarray(rs.Theta)

    # Exchange matrix: {all_gather, p2p} x {f32, bf16 (+EF)} at S=4. f32
    # wires must match the single-device engine; compressed wires must
    # differ from it, by no more than the wire precision allows.
    for spec in (ExchangeSpec(method="all_gather"),
                 ExchangeSpec(method="p2p"),
                 ExchangeSpec(method="all_gather", dtype="bf16"),
                 ExchangeSpec(method="p2p", dtype="bf16"),
                 ExchangeSpec(method="p2p", dtype="bf16", error_feedback=True)):
        eng = ShardedAsyncEngine(upd, num_shards=4, relabel="rcm",
                                 exchange=spec, slot_wakes=8.0)
        st = eng.init_state(np.zeros((n, p)))
        for msk in masks:
            st = eng.step(st, msk)
        err = np.abs(eng.global_theta(st) - R).max()
        if spec.dtype == "f32":
            assert err < 1e-6, (spec, err)
        else:
            assert 0 < err < 5e-2, (spec, err)
        print(f"{spec.method}:{spec.dtype}:ef={int(spec.error_feedback)} "
              f"vs_single_device={err:.2e}")
    print("EXCHANGE_MATRIX_OK")
    """
)


COMPRESSED_FIXED_POINT_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax.numpy as jnp
    from repro.core import AgentData, knn_graph, make_objective
    from repro.sim import CDUpdate, ExchangeSpec, ShardedAsyncEngine

    rng = np.random.default_rng(0)
    n, p, m = 256, 4, 3
    graph = knn_graph(rng.normal(size=(n, 8)), k=8)
    targets = rng.normal(size=(n, p)) / np.sqrt(p)
    X = rng.normal(size=(n, m, p)) / np.sqrt(p)
    y = np.einsum("nmp,np->nm", X, targets)
    obj = make_objective(graph, AgentData(X=X, y=y, mask=np.ones((n, m))),
                         "quadratic", mu=0.5, mix_mode="sparse")
    star = obj.solve_exact()
    upd = CDUpdate(obj)

    def fixed_point_err(spec):
        eng = ShardedAsyncEngine(upd, num_shards=4, relabel="rcm", exchange=spec,
                                 slot_wakes=64.0, seed=7)
        res = eng.run(np.zeros((n, p)), slots=1000)
        return float(np.abs(res.Theta - star).max())

    err_f32 = fixed_point_err(ExchangeSpec(method="p2p"))
    err_bf16 = fixed_point_err(ExchangeSpec(method="p2p", dtype="bf16"))
    err_ef = fixed_point_err(ExchangeSpec(method="p2p", dtype="bf16",
                                          error_feedback=True))
    print(f"f32={err_f32:.3e} bf16={err_bf16:.3e} bf16+ef={err_ef:.3e}")
    # Acceptance: error feedback recovers the f32 fixed point through a
    # lossy wire; the plain quantized wire demonstrably does not.
    assert err_f32 < 2e-5, err_f32
    assert err_ef <= 1e-4, err_ef
    assert err_bf16 > 1e-4, err_bf16
    assert err_ef < err_bf16 / 1.5, (err_ef, err_bf16)
    print("COMPRESSED_FIXED_POINT_OK")
    """
)


def _run_multidev(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("JAX_ENABLE_X64", None)
    return subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, text=True,
        timeout=900,
    )


def test_sharded_exchange_matrix_multidevice():
    res = _run_multidev(EXCHANGE_MATRIX_SCRIPT)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "EXCHANGE_MATRIX_OK" in res.stdout


@pytest.mark.slow
def test_compressed_halo_fixed_point_multidevice():
    """Acceptance: bf16+EF halos reach <=1e-4 of the exact optimum at S=4
    while plain bf16 halos stall above it (quantization bias)."""
    res = _run_multidev(COMPRESSED_FIXED_POINT_SCRIPT)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "COMPRESSED_FIXED_POINT_OK" in res.stdout
