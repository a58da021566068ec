"""Ahead-of-time TPU v5e compiles of the Pallas kernels on the engine path.

Interpret mode runs a kernel's program on the CPU but never asks Mosaic,
the TPU kernel compiler, whether it accepts it: tiling refusals and
VMEM/SMEM overflows only show up in a real compile. These tests compile
each kernel for one chip of a described (not attached) v5e topology at the
edges of the shapes the auto gates admit (``kernel_max_n()`` agents, the
largest slab width and data depth ``fused_row_update_fits`` accepts), and
check that the compiled program holds the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.mixing import kernel_max_n
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the installed libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    # The chip path runs in 32-bit mode; this suite turns x64 on (conftest).
    with jax.enable_x64(False):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, I32 = jnp.float32, jnp.int32


def _largest(fits, lo, hi):
    """Largest x in [lo, hi] with fits(x) (fits is monotone decreasing)."""
    assert fits(lo)
    while lo < hi:
        mid = (lo + hi + 1) // 2
        lo, hi = (mid, hi) if fits(mid) else (lo, mid - 1)
    return lo


def _fused_shapes(nt, p, m, B, K):
    return [
        ((B,), I32), ((B, K), I32), ((B, K), F32), ((B, 4), F32),
        ((B, m, p), F32), ((B, m), F32), ((B, m), F32), ((B, p), F32), ((nt, p), F32),
    ]


def _fused(nt):
    return lambda *a: ops.fused_row_update(*a, limit=nt, interpret=False)


@pytest.mark.parametrize(
    "case",
    [
        "engine_cell",  # n = kernel_max_n(), p = 20, m = 16, the smoke's shapes
        "widest_slab",  # the largest p the VMEM gate admits at that n
        "deepest_data",  # the largest m it admits at p = 20
        "every_row_woken",  # B = n, K past one lane tile
    ],
)
def test_fused_row_update_compiles_at_gate_edges(one_chip, case):
    nt = kernel_max_n()
    p, m, B, K = 20, 16, 112, 40
    if case == "widest_slab":
        p = _largest(lambda q: ops.fused_row_update_fits(nt, q, m), 1, 4096)
        assert not ops.fused_row_update_fits(nt, p + 1, m)
    elif case == "deepest_data":
        m = _largest(lambda q: ops.fused_row_update_fits(nt, p, q), 1, 1 << 14)
    elif case == "every_row_woken":
        B, K = nt, 200
    assert ops.fused_row_update_fits(nt, p, m)
    _compile(_fused(nt), one_chip, *_fused_shapes(nt, p, m, B, K))


@pytest.mark.parametrize("p", [20, 1000])
def test_sparse_mix_compiles_full_table(one_chip, p):
    """The full (n, K) neighbour table at the gate's largest n: SMEM holds one
    (block, K) tile of it at a time, so n no longer bounds the kernel."""
    n, K = kernel_max_n(), 64
    _compile(
        lambda i, w, t: ops.sparse_mix(i, w, t, interpret=False),
        one_chip, ((n, K), I32), ((n, K), F32), ((n, p), F32),
    )


def test_sparse_rows_mix_compiles_woken_batch(one_chip):
    n, B, K = kernel_max_n(), 112, 40
    _compile(
        lambda i, w, t: ops.sparse_rows_mix(i, w, t, interpret=False),
        one_chip, ((B, K), I32), ((B, K), F32), ((n, 20), F32),
    )


@pytest.mark.parametrize("n,p", [(kernel_max_n(), 1000), (kernel_max_n() - 1, 20)])
def test_graph_mix_compiles(one_chip, n, p):
    _compile(
        lambda a, t: ops.graph_mix(a, t, interpret=False),
        one_chip, ((n, n), F32), ((n, p), F32),
    )


def test_vmem_guard_keeps_scan_state_out_of_vmem(one_chip):
    """The super-tick scan's VMEM guard is a v5e compile option that the
    compiler accepts and that keeps the loop-carried slab out of VMEM
    (memory space S(1)), where the unguarded compile pins it."""
    import re

    from repro.sim.engine import _VMEM_GUARD

    def scan(theta, rows):
        def body(t, _):
            return t.at[rows].add(0.5 * t[rows]), None

        return jax.lax.scan(body, theta, None, length=8)[0]

    args = [((200_000, 20), F32), ((512,), I32)]
    pinned = {}
    for guarded in (False, True):
        opts = _VMEM_GUARD["TPU v5 lite"][0] if guarded else None
        with jax.enable_x64(False):
            shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in args]
            text = jax.jit(scan, compiler_options=opts).lower(*shapes).compile().as_text()
        loop = next(line for line in text.splitlines() if " while(" in line)
        pinned[guarded] = re.findall(r"f32\[200000,20\]\{[^}]*S\(1\)\}", loop)
    assert pinned[False] and not pinned[True]


@pytest.fixture(scope="module")
def widths_engine():
    """An engine at the MovieLens cell's widths (m = 589 padded points of
    p = 20 features an agent, a hub of degree 300 so K = 300) with
    n = 2,048 agents, built on the CPU in the chip path's 32-bit mode."""
    import numpy as np

    from repro.core import AgentData, make_objective
    from repro.core.graph import csr_from_coo
    from repro.sim import AsyncEngine, CDUpdate

    n, m, p, hub = 2048, 589, 20, 300
    rng = np.random.default_rng(0)
    rows = np.concatenate([np.arange(n - 1), np.zeros(hub, np.int64)])
    cols = np.concatenate([np.arange(1, n), np.arange(1, hub + 1)])
    graph = csr_from_coo(n, rows, cols, np.ones(len(rows)), symmetrize=True)
    data = AgentData(
        X=rng.normal(size=(n, m, p)).astype(np.float32),
        y=rng.normal(size=(n, m)).astype(np.float32),
        mask=np.ones((n, m), np.float32),
    )
    obj = make_objective(graph, data, "quadratic", mu=0.04, clip=10.0, mix_mode="sparse")
    with jax.enable_x64(False):
        return AsyncEngine(CDUpdate(obj), slot_wakes=0.01 * n, seed=0)


@pytest.mark.parametrize("tables", ["default_layout", "engine_formats"])
def test_supertick_reads_static_tables_without_relayout(one_chip, widths_engine, tables):
    """With its tables in the v5e's default layouts, the compiled scan
    chunk copies a static table at its entry (the padded X, relaid
    agent-major); with them stored as the engine stores them for the
    formats ``AsyncEngine.static_formats`` asks for, no program does: the
    chunk at its own length and at another, nor the forced slot."""
    import re

    from repro.sim.engine import _row_packing, _RowTable

    eng = widths_engine
    copy_of_static = re.compile(r"\bcopy\(%?static")

    def spec(x, shape=None):
        return jax.ShapeDtypeStruct(shape or x.shape, x.dtype, sharding=one_chip)

    with jax.enable_x64(False):
        theta = jax.ShapeDtypeStruct((eng.n, eng.p), eng.dtype)
        state = jax.tree.map(spec, jax.eval_shape(eng.init_state, theta))
        static = jax.tree.map(spec, eng._static)
        if tables == "default_layout":
            text = eng._chunk.lower(state, static, eng.steps_per_chunk).compile().as_text()
            assert copy_of_static.search(text)
            return

        def stored(x, want):
            packing = _row_packing(x.shape, want.layout)
            if packing is None:
                return x
            return _RowTable(spec(x, packing[1]), packing[0], x.shape)

        static = jax.tree.map(stored, static, eng.static_formats(state, static))
        assert static["consts"]["X"].perm == (0, 2, 1)  # agent-major, points minor
        mask = jax.ShapeDtypeStruct((eng.n,), jnp.bool_, sharding=one_chip)
        texts = [
            eng._chunk.lower(state, static, steps).compile().as_text()
            for steps in (eng.steps_per_chunk, 5)
        ]
        texts.append(eng._forced.lower(state, static, mask).compile().as_text())
    for text in texts:
        assert not copy_of_static.search(text)


def test_supertick_mix_makes_no_padded_neighbour_block(one_chip, widths_engine):
    """The compiled static chunk sums the woken rows' real CSR edges: no
    HLO instruction holds the B·K·p elements of the padded (B, K, p)
    neighbour block (K = 300, the hub's degree), while the edge block's
    (E, p) rows are there."""
    import math
    import re

    import numpy as np

    eng = widths_engine
    K = int(np.diff(eng.update.mix.indptr).max())
    assert K == 300
    shape = re.compile(r"\b(?:pred|[suf]\d+|bf16)\[([\d,]*)\]")

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    with jax.enable_x64(False):
        theta = jax.ShapeDtypeStruct((eng.n, eng.p), eng.dtype)
        state = jax.tree.map(spec, jax.eval_shape(eng.init_state, theta))
        static = jax.tree.map(spec, eng._static)
        text = eng._chunk.lower(state, static, eng.steps_per_chunk).compile().as_text()
    sizes = {
        math.prod(int(d) for d in dims.split(",")) if dims else 1
        for dims in shape.findall(text)
    }
    assert eng.mix_edge_block * eng.p in sizes
    assert eng.batch_size * K * eng.p not in sizes
