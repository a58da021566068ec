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
