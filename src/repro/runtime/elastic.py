"""Elastic re-planning: rebuild the mesh from surviving devices.

Checkpoints store full (host-gathered) arrays, so elasticity reduces to
(1) choosing a new (pods, data, model) factorization for the surviving
device count and (2) re-entering the jitted step with the new mesh's
in_shardings — no state surgery.

Planning policy: keep TP ("model") as close to the requested degree as the
device count allows (TP degree is tied to weight-dim divisibility), give the
rest to DP; drop the pod axis when a whole pod is lost.
"""

from __future__ import annotations

from typing import Tuple

from repro.launch.mesh import make_mesh


def plan_mesh_shape(n_devices: int, preferred_model: int = 16) -> Tuple[Tuple[int, int], Tuple[str, str]]:
    """Largest model-parallel degree <= preferred that divides n_devices."""
    mp = min(preferred_model, n_devices)
    while mp > 1 and n_devices % mp != 0:
        mp -= 1
    return (n_devices // mp, mp), ("data", "model")


def replan_mesh(n_devices: int, preferred_model: int = 16):
    shape, axes = plan_mesh_shape(n_devices, preferred_model)
    return make_mesh(shape, axes)


def survivors_after_pod_loss(total: int = 512, pods: int = 2, lost_pods: int = 1) -> int:
    return total // pods * (pods - lost_pods)
