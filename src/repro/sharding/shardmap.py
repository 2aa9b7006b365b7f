"""``shard_map`` with the replication check off by default.

Every manual-collective region in this repo — the pipeline schedule, the
compressed all-reduce, and the CorrectionEngine's sharded pencil backend —
goes through this one wrapper.
"""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """``shard_map(f)`` over ``mesh`` with the replication check toggled.

    ``check=False`` (the default here) disables the static replication
    checker — the manual regions in this repo use collectives whose
    replication the checker cannot always infer.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )
