"""Elastic re-meshing: survive pod/host loss by re-sharding from checkpoint.

``plan_mesh`` picks the largest usable mesh for the devices that remain
(drop the pod axis when a pod dies; shrink the data axis for partial loss —
the model axis is preserved because TP degree is baked into layouts/Pallas
block shapes, while the batch axes are free).

``ElasticTrainer`` is the restart loop used by launch/train.py and the fault
tests: run -> (failure) -> plan_mesh over survivors -> restore checkpoint
with the *new* shardings (CheckpointManager stores unsharded arrays, so this
is one device_put per leaf) -> rescale the data loader (same global stream,
new host partition) -> continue.

``ElasticFleetSet`` is the same elasticity contract one level up, for the
region tier (``repro.region``): whole fleets join and leave a
``RegionRouter`` at runtime.  It is jax-free — the module's jax/training
imports are lazy so the serving-side membership path works in the
dependency-light smoke lane.  A departure *withdraws* the fleet's summary
from the region federation immediately (no routing-error window: in-flight
routes degrade to the least-loaded live fleet, never KeyError), and a join
re-advertises a fresh summary in the same call so the rejoiner attracts
traffic without waiting for the next periodic sync.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence


def plan_mesh(n_devices: int, *, model_parallel: int, want_pods: int = 1):
    """-> (shape tuple, axis names) for the largest mesh on n_devices.

    Keeps ``model_parallel`` fixed; gives the rest to data; re-adds the pod
    axis only if at least 2 full pods survive."""
    if n_devices % model_parallel:
        raise ValueError(f"{n_devices} devices not divisible by TP={model_parallel}")
    rest = n_devices // model_parallel
    if want_pods >= 2 and rest % want_pods == 0 and rest // want_pods >= 1:
        return (want_pods, rest // want_pods, model_parallel), ("pod", "data", "model")
    return (rest, model_parallel), ("data", "model")


def make_mesh_from_plan(shape: Sequence[int], axes: Sequence[str], devices=None):
    import jax

    from repro.launch.mesh import make_mesh

    devices = devices if devices is not None else jax.devices()
    n = 1
    for s in shape:
        n *= s
    return make_mesh(shape, axes, devices=devices[:n])


@dataclass
class ElasticTrainer:
    """Restart loop driver (see tests/test_elastic.py for the 8->4 scenario)."""

    model: object
    cfg: object
    ckpt: object          # CheckpointManager
    model_parallel: int

    def restore_on(self, devices, *, want_pods: int = 1):
        """Restore the latest checkpoint onto a mesh built from ``devices``."""
        from repro.models.sharding import use_mesh
        from repro.training.step import state_abstract, state_logical, tree_shardings

        shape, axes = plan_mesh(len(devices), model_parallel=self.model_parallel, want_pods=want_pods)
        mesh = make_mesh_from_plan(shape, axes, devices)
        step = self.ckpt.latest_step()
        if step is None:
            raise FileNotFoundError("no checkpoint to restore")
        abs_state = state_abstract(self.model, self.cfg)
        with use_mesh(mesh):
            shardings = tree_shardings(abs_state, state_logical(self.model))
            state, extra = self.ckpt.restore(step, abs_state, shardings=shardings, extra=True)
        return mesh, state, extra


@dataclass
class ElasticFleetSet:
    """Fleet membership driver for the region tier (jax-free).

    Wraps a ``repro.region.RegionRouter`` (any object with
    ``attach_fleet``/``detach_fleet``/``active_fleets``) and narrates
    membership changes through it, keeping an epoch counter and join/leave
    telemetry so tests and benches can pin the no-error-window contract:
    every ``leave`` is immediately routable-around, every ``join``
    re-advertises before returning."""

    router: object
    epoch: int = 0
    joins: int = 0
    leaves: int = 0
    log: list = field(default_factory=list)  # (epoch, "join"|"leave", fleet)

    def leave(self, fleet: int) -> None:
        """Detach ``fleet``: withdraw its federated summary and stop
        steering/shedding to it.  Sessions already admitted there finish
        normally; queued sessions homed there shed to live fleets."""
        self.router.detach_fleet(fleet)
        self.epoch += 1
        self.leaves += 1
        self.log.append((self.epoch, "leave", fleet))

    def join(self, fleet: int) -> None:
        """(Re-)attach ``fleet`` and re-advertise its summary in the same
        call — a rejoiner attracts matched traffic without a cold window."""
        self.router.attach_fleet(fleet)
        self.epoch += 1
        self.joins += 1
        self.log.append((self.epoch, "join", fleet))

    @property
    def active(self) -> list[int]:
        return [f for f, a in enumerate(self.router.active_fleets) if a]
