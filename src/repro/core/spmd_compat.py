"""The ``shard_map`` wrapper shared by the SPMD layers.

Both the synchronous scale layer (:mod:`repro.core.spmd`) and the sharded
async engine (:mod:`repro.sim.engine`) call ``jax.shard_map`` with the
replication check off, so the call lives here once.
"""

from __future__ import annotations

import jax


def shard_map(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check disabled."""
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
