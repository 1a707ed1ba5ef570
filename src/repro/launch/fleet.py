"""Fleet-scale device placement for the sparse semi-async path.

PR 5 built the 'data'-axis layout rules (sharding.specs.population_pspecs
/ event_store_pspecs) but nothing consumed them: the engine ran with the
ring store and staged batches replicated. This module is the launch path
that closes that gap — it resolves the store geometry against a mesh
(sharding.planner.plan_event_store), materializes NamedShardings for

  * the arrival-slot ring store (events.init_store leaves, slot dim),
  * the population's (M,) client vectors (cohort id, delay/comm scales),
  * the engine's staged (C, K, ...) sparse batch chunks (K dim),

and hands the engine a pre-placed initial store (``state=``) plus a
``batch_put`` hook so the 6-tuple scan runs with the store sharded over
'data' instead of replicated. All specs are divisibility-guarded: a dim
that doesn't divide the axis replicates, and the scan's gather/scatter
over slot indices lowers to GSPMD collectives either way — placement is a
layout hint, never a semantics change.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import MeshConfig, SFLConfig
from repro.core import events
from repro.launch.mesh import make_mesh
from repro.obs.trace import span
from repro.core.population import ClientPopulation
from repro.sharding.planner import EventStorePlan, plan_event_store
from repro.sharding.specs import (_guard, event_store_pspecs,
                                  population_pspecs)

__all__ = ["FleetPlacement", "build_fleet_placement"]


@dataclasses.dataclass(frozen=True)
class FleetPlacement:
    """Resolved mesh + shardings for one sparse-async run."""
    mesh: jax.sharding.Mesh
    plan: EventStorePlan
    k_max: int
    axis_sizes: Dict[str, int]

    def place_store(self, store: Dict[str, jax.Array]) -> Dict[str, Any]:
        """device_put the ring store with its slot dim over 'data'."""
        with span("fleet.place_store", leaves=len(store)):
            specs = event_store_pspecs(store, slot_axis="data",
                                       axis_sizes=self.axis_sizes)
            return {k: jax.device_put(v, NamedSharding(self.mesh, specs[k]))
                    for k, v in store.items()}

    def place_vectors(self, population: ClientPopulation
                      ) -> Dict[str, jax.Array]:
        """device_put the fleet's (M,) system vectors over 'data'."""
        with span("fleet.place_vectors", clients=population.n_clients):
            vecs = population.client_vectors()
            specs = population_pspecs(vecs, axis_sizes=self.axis_sizes)
            return {k: jax.device_put(np.asarray(v),
                                      NamedSharding(self.mesh, specs[k]))
                    for k, v in vecs.items()}

    def batch_put(self, tree: Any) -> Any:
        """Place a staged (C, K, ...) sparse chunk: the scan (C) dim
        replicates, the K batch-row dim shards over 'data' when it
        divides. Engine hook: run_rounds(..., batch_put=placement
        .batch_put)."""
        def put(x):
            if np.ndim(x) < 2:
                return x
            ax = _guard(np.shape(x)[1], "data", self.axis_sizes)
            spec = P(None, ax, *((None,) * (np.ndim(x) - 2)))
            return jax.device_put(x, NamedSharding(self.mesh, spec))
        with span("fleet.batch_put"):
            return jax.tree.map(put, tree)


def build_fleet_placement(sfl: SFLConfig, *,
                          mesh: Optional[jax.sharding.Mesh] = None,
                          data_devices: int = 0) -> FleetPlacement:
    """Resolve the sparse store geometry against a mesh.

    ``mesh`` supplies an existing mesh with a 'data' axis; otherwise a
    1-D ('data',) mesh is built over ``data_devices`` devices (0 = all
    local). Raises ValueError when the resolved ring capacity or k_max
    does not divide the 'data' axis — callers that want parse-time
    validation (launch.train) call this before any device work.
    """
    if sfl.timeline != "sparse":
        raise ValueError("build_fleet_placement places the sparse ring "
                         f"store; sfl.timeline is {sfl.timeline!r}")
    if mesh is None:
        n = data_devices or len(jax.devices())
        if n > len(jax.devices()):
            raise ValueError(f"data_devices={n} exceeds the "
                             f"{len(jax.devices())} available devices")
        mesh = make_mesh((n,), ("data",))
    if "data" not in mesh.axis_names:
        raise ValueError(f"fleet placement needs a 'data' mesh axis; got "
                         f"{mesh.axis_names}")
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    k_max, capacity = events.resolve_store_geometry(sfl)
    data = axis_sizes.get("data", 1)
    if capacity % data:
        raise ValueError(
            f"ring capacity {capacity} does not divide the 'data' axis "
            f"({data} devices) — pass --ring-capacity a multiple of {data}")
    if k_max % data:
        raise ValueError(
            f"k_max {k_max} does not divide the 'data' axis ({data} "
            f"devices) — pass --k-max a multiple of {data}")
    plan = plan_event_store(
        capacity, sfl.n_clients,
        MeshConfig(shape=tuple(mesh.devices.shape),
                   axes=tuple(mesh.axis_names)),
        tau=sfl.tau, n_pert=sfl.n_perturbations)
    return FleetPlacement(mesh=mesh, plan=plan, k_max=k_max,
                          axis_sizes=axis_sizes)
