"""Device-mesh construction for federated simulation.

The reference's process topology (one MPI rank per client + one server rank,
fedml_api/distributed/fedavg/FedAvgAPI.py:13-17) maps onto a JAX device mesh:
the ``clients`` axis carries cohort/client parallelism (the FL analogue of DP),
and an optional ``silo`` axis carries intra-client data parallelism — the
analogue of the reference's intra-silo DDP (fedavg_cross_silo/
process_group_manager.py:23-27, NCCL) riding ICI instead.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

CLIENT_AXIS = "clients"
SILO_AXIS = "silo"
# model-parallel axis: tensor/FSDP sharding WITHIN one client's model
# (parallel/rules.py partition rules name it) — orthogonal to the client
# axis that carries cohort parallelism
MODEL_AXIS = "model"


def client_mesh(devices=None) -> Mesh:
    """1-D mesh: every device is a client slot."""
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.asarray(devices), (CLIENT_AXIS,))


def silo_mesh(num_silos: int, devices=None) -> Mesh:
    """2-D mesh [clients, silo]: cohort parallelism × intra-silo DP."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % num_silos:
        raise ValueError(
            f"silo_mesh(num_silos={num_silos}): {n} available devices do "
            f"not divide evenly into {num_silos} silo groups "
            f"({n} % {num_silos} = {n % num_silos})"
        )
    arr = np.asarray(devices).reshape(num_silos, n // num_silos)
    return Mesh(arr, (CLIENT_AXIS, SILO_AXIS))


def shard_mesh(mesh_shape, devices=None) -> Mesh:
    """2-D mesh [clients, model]: cohort parallelism × within-client model
    parallelism (docs/PERFORMANCE.md "Sharded client models").

    ``mesh_shape`` is ``(n_client_shards, n_model_shards)``. The product
    must divide the available device count evenly — validated here with an
    error naming both numbers, instead of the opaque numpy reshape failure
    a bad shape used to produce. When the product is a proper divisor of
    the device count (e.g. a 2x2 mesh on 8 devices), the first
    ``clients * model`` devices are used — a deterministic subset, so
    repeated constructions agree; non-divisor products are rejected
    rather than silently stranding a remainder of the mesh."""
    devices = list(devices if devices is not None else jax.devices())
    try:
        clients, model = (int(x) for x in mesh_shape)
    except (TypeError, ValueError):
        raise ValueError(
            f"mesh_shape must be a (clients, model) pair, got {mesh_shape!r}"
        ) from None
    if clients < 1 or model < 1:
        raise ValueError(
            f"mesh_shape axes must be >= 1, got {(clients, model)}"
        )
    n, want = len(devices), clients * model
    if want > n or n % want:
        raise ValueError(
            f"mesh_shape {(clients, model)} requires {want} devices "
            f"(clients x model) but {n} are available, and {want} does "
            f"not divide {n} evenly ({n} % {want} = {n % want})"
            if want <= n else
            f"mesh_shape {(clients, model)} requires {want} devices "
            f"(clients x model) but only {n} are available"
        )
    arr = np.asarray(devices[:want]).reshape(clients, model)
    return Mesh(arr, (CLIENT_AXIS, MODEL_AXIS))


def parse_mesh_shape(text: str | None):
    """CLI spelling of a (clients, model) mesh shape: ``'2x4'`` or
    ``'2,4'`` -> ``(2, 4)``; None/empty passes through (no 2-D mesh)."""
    if not text:
        return None
    parts = text.lower().replace("x", ",").split(",")
    try:
        clients, model = (int(p) for p in parts)
    except ValueError:
        raise ValueError(
            f"--mesh_shape expects 'CLIENTSxMODEL' (e.g. 2x4), got {text!r}"
        ) from None
    return (clients, model)


def current_mesh() -> Mesh | None:
    """The mesh of the innermost active ``with mesh:`` context, or ``None``.

    This is how a traced op discovers the mesh the surrounding program is
    being lowered under (``parallel/dispatch.py`` enters the mesh context
    around every pjit trace) — e.g. the head-parallel flash wrap in
    ``ops/attention.py`` decides at trace time whether to nest a per-rank
    ``shard_map`` over the model axis. jax 0.9.0 has no public reader for
    the ``with mesh:`` context (``jax.sharding.get_mesh`` only sees
    ``jax.set_mesh`` and refuses to run under ``jit``), so this reads the
    thread-local the context manager writes."""
    from jax._src import mesh as mesh_lib

    m = mesh_lib.thread_resources.env.physical_mesh
    return None if m.empty else m


def named_sharding(mesh: Mesh, spec) -> NamedSharding:
    """Build a NamedSharding from a PartitionSpec on ``mesh``, validating
    that every axis the spec names exists on the mesh — a typo'd axis name
    otherwise surfaces as a deep XLA lowering error with the spec lost."""
    unknown = [
        ax
        for entry in spec
        for ax in (entry if isinstance(entry, tuple) else (entry,))
        if ax is not None and ax not in mesh.axis_names
    ]
    if unknown:
        raise ValueError(
            f"PartitionSpec {spec} names mesh axes {unknown} not present "
            f"on this mesh (axes: {list(mesh.axis_names)})"
        )
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def client_sharded(mesh: Mesh) -> NamedSharding:
    """Shard the leading (client) axis of every leaf over the clients axis."""
    return NamedSharding(mesh, P(CLIENT_AXIS))


def cohort_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [C, S, B, ...] cohort stacks: client axis over ``clients``;
    on a 2-D mesh the within-client batch axis additionally shards over
    ``silo`` — intra-silo data parallelism, the reference's in-silo DDP
    (fedavg_cross_silo/DistWorker.py:53) as a mesh axis with XLA inserting the
    gradient all-reduce over ICI."""
    if SILO_AXIS in mesh.axis_names:
        return NamedSharding(mesh, P(CLIENT_AXIS, None, SILO_AXIS))
    return NamedSharding(mesh, P(CLIENT_AXIS))
