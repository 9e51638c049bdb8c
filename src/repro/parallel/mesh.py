"""Mesh construction and axis conventions.

Axes:
  pod   — slow DCN/ICI-bridge axis between pods. Pure data parallelism;
          crossing it is expensive (GradCompress targets exactly this axis).
  data  — intra-pod data parallelism (batch sharding) + ZeRO-1 optimizer
          state sharding. For long_500k decode it doubles as the sequence axis.
  model — tensor/expert parallelism: attention heads, FFN columns, MoE experts,
          vocab.

Production meshes (assignment): 16x16 = 256 chips single pod;
(2, 16, 16) = 512 chips across 2 pods.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# Batch axes: everything data-parallel (pod is DP too, just over slow links).
BATCH_AXES = ("pod", "data")
MODEL_AXIS = "model"


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """The one mesh constructor: every axis `AxisType.Auto`.

    The model, cache and serve code place tensors with sharding HINTS
    (`parallel.sharding.logical` / `attn_hint` / `constrain`) and leave the
    rest to GSPMD propagation.  `jax.make_mesh`'s default axis type is
    Explicit, under which those hints turn into type assertions (and an
    embedding gather whose operand and indices disagree raises a
    `ShardingTypeError`), so no mesh in the repo is built any other way.
    Uses the first prod(shape) devices, so a 2x2 mesh can sit next to a
    4x1 one on a 4-device host.  Activate one with `jax.set_mesh(mesh)`.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """A mesh over whatever devices exist (tests / single-host examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))


def parse_mesh_spec(spec: str) -> tuple[int, int]:
    """'4x1' -> (data=4, model=1). The serve-mesh CLI grammar."""
    m = spec.lower().split("x")
    if len(m) != 2:
        raise ValueError(f"mesh spec must be DATAxMODEL (e.g. 4x1), got {spec!r}")
    data, model = int(m[0]), int(m[1])
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    return data, model


def make_serve_mesh(spec: str | None) -> Mesh | None:
    """Host mesh for serving from a 'DATAxMODEL' spec; None/'' => no mesh.

    Uses the first data*model local devices (`make_mesh`), so a '2x2'
    engine can run on a 4-device host next to a '4x1' one in the same
    process (tests do exactly that under
    --xla_force_host_platform_device_count).
    """
    if not spec:
        return None
    data, model = parse_mesh_spec(spec)
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh {spec} needs {data * model} devices, have {n}")
    return make_mesh((data, model), ("data", "model"))


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def mesh_desc(mesh: Mesh | None) -> str:
    """'4x1'-style axis-size summary for logs/artifacts; 'none' without one."""
    if mesh is None:
        return "none"
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def batch_spec(mesh: Mesh) -> P:
    """PartitionSpec entry for a global-batch dimension on this mesh."""
    axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    if not axes:
        return P(None)
    return P(axes)


def dp_size(mesh: Mesh) -> int:
    n = 1
    for a in BATCH_AXES:
        n *= mesh_axis_size(mesh, a)
    return n


def shard(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
