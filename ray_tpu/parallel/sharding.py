"""Logical-axis sharding rules.

The reference delegates parameter sharding to per-worker frameworks (FSDP/
DeepSpeed configs inside the train loop — SURVEY.md §2.5); here sharding is
first-class: model code annotates arrays with *logical* axis names
("batch", "embed", "heads", …) and a rules table maps them to mesh axes.
pjit/XLA then emits the collectives. This is the t5x/flax-partitioning
idiom, which is the TPU-native replacement for wrapper classes like
RayFSDPStrategy (ref: train/lightning/_lightning_utils.py:91).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .mesh import mesh_axis_size

# (logical axis, mesh axis or tuple of mesh axes or None)
Rule = Tuple[str, Union[str, Tuple[str, ...], None]]

# Default rules for transformer training:
#  - batch splits over dp+fsdp (each fsdp rank sees different data)
#  - sequence splits over sp (ring attention axis)
#  - attention heads + mlp hidden split over tp (Megatron-style); between a
#    row-parallel matmul and the next column-parallel one the residual
#    stream's rows (its sequence, "seq_tp") lie split over tp too, so the
#    pair's all-reduce is a reduce-scatter behind the one and an
#    all-gather before the other (parallel/collective_matmul.py; the model
#    names "seq_tp" only where ring_size() says the ring runs)
#  - embed (params' fsdp shard dim) splits over fsdp: ZeRO-3-equivalent
#  - experts split over ep
#  - layer stages split over pp (for stacked-layer pipeline params)
DEFAULT_RULES: Tuple[Rule, ...] = (
    ("batch", ("dp", "fsdp")),
    ("seq", "sp"),
    ("kv_seq", "sp"),
    ("seq_tp", "tp"),
    ("embed", "fsdp"),
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("head_dim", None),
    ("mlp", "tp"),
    ("vocab", "tp"),
    ("expert", "ep"),
    ("layers", "pp"),
    ("norm", None),
)


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    rules: Sequence[Rule] = DEFAULT_RULES,
) -> PartitionSpec:
    """Map logical axis names to a PartitionSpec via the rules table. A
    mesh axis may be consumed at most once per spec (first match wins)."""
    table = dict(rules)
    used: set = set()
    out = []
    for name in logical_axes:
        mesh_axis = table.get(name) if name is not None else None
        if mesh_axis is None:
            out.append(None)
            continue
        axes = (mesh_axis,) if isinstance(mesh_axis, str) else tuple(mesh_axis)
        free = tuple(a for a in axes if a not in used)
        if not free:
            out.append(None)
            continue
        used.update(free)
        out.append(free if len(free) > 1 else free[0])
    return PartitionSpec(*out)


def named_sharding(
    mesh: Mesh,
    logical_axes: Sequence[Optional[str]],
    rules: Sequence[Rule] = DEFAULT_RULES,
) -> NamedSharding:
    return NamedSharding(mesh, prune_spec(mesh, logical_to_spec(logical_axes, rules)))


def prune_spec(mesh: Mesh, spec: PartitionSpec) -> PartitionSpec:
    """Drop mesh axes of size 1 from a spec (XLA treats them as replicated
    anyway; pruning keeps specs readable and avoids missing-axis errors on
    small meshes)."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, str):
            out.append(entry if mesh_axis_size(mesh, entry) > 1 else None)
        else:
            kept = tuple(a for a in entry if mesh_axis_size(mesh, a) > 1)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def with_logical_constraint(
    x: jax.Array,
    logical_axes: Sequence[Optional[str]],
    *,
    mesh: Optional[Mesh] = None,
    rules: Sequence[Rule] = DEFAULT_RULES,
):
    """Annotate an intermediate activation with its sharding (ref analogue
    in spirit: torch.distributed tensor placement; here it's
    jax.lax.with_sharding_constraint so XLA propagates/reshards)."""
    mesh = mesh or _current_mesh()
    if mesh is None:
        return x
    spec = prune_spec(mesh, logical_to_spec(logical_axes, rules))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _current_mesh() -> Optional[Mesh]:
    try:
        from jax._src import mesh as mesh_lib

        m = mesh_lib.thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        return None


def is_logical_axes(x) -> bool:
    """True for a leaf of a logical-axes pytree: a tuple of axis names
    (str) and Nones — e.g. ("layers", "embed", "heads", "head_dim")."""
    return isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x
    )


def tree_shardings(mesh: Mesh, logical_axes_tree, rules=DEFAULT_RULES):
    """Map a pytree of logical-axis tuples to a matching pytree of
    NamedShardings (the in/out_shardings argument shape pjit wants).
    Tuples of axis names are leaves here, not nested pytrees."""
    return jax.tree.map(
        lambda axes: named_sharding(mesh, axes, rules),
        logical_axes_tree,
        is_leaf=is_logical_axes,
    )


def constrain_pytree(tree, mesh: Mesh, logical_axes_tree,
                     rules=DEFAULT_RULES):
    """with_sharding_constraint over a whole pytree of traced values —
    the in-graph counterpart of :func:`shard_pytree` (used to pin params
    and optimizer state inside a compiled init so every buffer
    materializes with its final layout)."""
    shardings = tree_shardings(mesh, logical_axes_tree, rules)
    return jax.tree.map(
        jax.lax.with_sharding_constraint, tree, shardings
    )


def shard_pytree(tree, mesh: Mesh, logical_axes_tree, rules=DEFAULT_RULES):
    """Device-put a pytree of host arrays onto the mesh according to a
    matching pytree of logical-axis tuples."""
    def _place(x, axes):
        return jax.device_put(x, named_sharding(mesh, axes, rules))

    return jax.tree.map(_place, tree, logical_axes_tree)
