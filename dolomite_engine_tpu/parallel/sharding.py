"""Logical-axis sharding rules: the GSPMD replacement for the reference's manual parallelism.

Parity map (reference -> here):
  - FSDP/ZeRO stages (`dolomite_engine/distributed/__init__.py:118-220`): params/optimizer state
    sharded over the "fsdp" mesh axis via partition rules; stage semantics:
        stage 0 -> params + opt replicated (DDP)
        stage 1/2 -> params replicated, optimizer state sharded ("fsdp")
        stage 3 -> params AND optimizer state sharded
    XLA emits exactly the all-gather/reduce-scatter schedule FSDP implements by hand.
  - TP column/row parallel linears (`hf_models/modeling_utils_TP/linear.py:22-210`): the "tp"
    entries below; GSPMD infers the all-reduce/reduce-scatter at row-parallel boundaries.
  - Megatron-SP (`hf_models/modeling_utils_TP/TP.py:82-91` get_module_placements): activation
    sequence axis additionally sharded over "tp" between TP regions.
  - vocab/loss parallel (`gpt_dolomite_TP/main.py:96-167`): "vocab" -> "tp" +
    sharded cross-entropy in ops/loss.py.
  - EP (absent in reference, SURVEY §2.6): "experts" -> "ep".

Model code declares params with `nn.with_partitioning(init, (logical, names))` and activations
with `nn.with_logical_constraint`; these rules map logical names -> mesh axes.
"""

from __future__ import annotations

import logging

import jax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# logical axis names used across all models
#   params: "vocab", "embed", "heads", "kv_heads", "mlp", "experts", None (replicated dims)
#   activations: "act_batch", "act_seq", "act_embed", "act_heads", "act_mlp", "act_vocab"

LogicalRules = list[tuple[str, tuple[str, ...] | str | None]]


def get_logical_axis_rules(
    stage: int = 3,
    tensor_parallel_word_embeddings: bool = False,
    sequence_parallel: bool = False,
    for_optimizer: bool = False,
) -> LogicalRules:
    """Build logical->mesh rules for the given ZeRO stage / TP options.

    ``for_optimizer``: optimizer-state copies of the params; differs from param rules only for
    ZeRO stage 1/2 where opt state is sharded but params are not.
    """
    shard_params = stage >= 3 or (for_optimizer and stage >= 1)
    fsdp = "fsdp" if shard_params else None

    act_seq: tuple[str, ...] = ("sp", "tp") if sequence_parallel else ("sp",)

    rules: LogicalRules = [
        # parameter axes
        ("layers", None),  # scan_layers stacked-block axis: replicated, shard within layers
        ("vocab", "tp" if tensor_parallel_word_embeddings else fsdp),
        ("embed", fsdp),
        ("heads", "tp"),
        ("kv_heads", "tp"),
        ("mlp", "tp"),
        ("experts", "ep"),
        ("expert_mlp", "tp"),
        # Mamba-2's d_inner (in/out projections of models/nemotron_h): not sharded — tp over
        # the Mamba heads is not built, and the model raises on a mesh with tp > 1
        ("mamba_inner", None),
        # activation axes
        ("act_batch", ("dp", "fsdp", "ep")),
        ("act_seq", act_seq),
        # sequence axis INSIDE a tp region (qkv/mlp/logits tensors whose feature dim is
        # already tp-sharded): Megatron-SP's extra tp on the sequence axis only applies
        # BETWEEN tp regions — one mesh axis cannot shard two dims of the same tensor
        ("act_seq_inner", ("sp",)),
        ("act_embed", None),
        ("act_heads", "tp"),
        ("act_kv_heads", "tp"),
        ("act_mlp", "tp"),
        ("act_vocab", "tp" if tensor_parallel_word_embeddings else None),
        ("act_experts", "ep"),
    ]
    return rules


def _ambient_mesh():
    """The mesh the surrounding program activated, under either JAX API: the new
    `jax.sharding.set_mesh` (abstract mesh) or the classic `with mesh:` resource env."""
    abstract = jax.sharding.get_abstract_mesh()
    if not abstract.empty:
        return abstract
    # classic context; the private import keeps the deprecated public shim quiet. A moved
    # symbol must fail loudly: swallowed, every logical constraint below would turn into a
    # no-op and the whole program would silently replicate
    from jax._src import mesh as _mesh_lib

    physical = _mesh_lib.thread_resources.env.physical_mesh
    return None if physical.empty else physical


def logical_spec(shape: tuple[int, ...], axes) -> tuple[Mesh, PartitionSpec] | None:
    """Resolve logical axis names for an array of `shape` against the ambient rules and
    mesh: ``(mesh, PartitionSpec)``, or None when the trace has no rules or no mesh
    (meshless single-chip programs).

    Resolution follows flax: first matching rule wins; names without a rule (or mapping to
    None) leave the dimension unconstrained-as-replicated; axes absent from the mesh are
    dropped (size-1 axes are always present on MeshManager's 5-axis mesh, so this only
    triggers on hand-built test meshes). Axes that don't divide their dimension evenly
    are dropped too — the activation-side mirror of `prune_indivisible_spec`: an uneven
    constraint makes GSPMD pad-and-reshard (the "involuntary full rematerialization"
    warning) instead of erroring, which is strictly worse than replicating that dim.
    """
    rules = nn.get_logical_axis_rules()
    mesh = _ambient_mesh() if rules else None
    if mesh is None:
        return None
    table: dict[str, tuple[str, ...] | str | None] = {}
    for name, target in rules:
        table.setdefault(name, target)
    # inside a shard_map body the mesh's axes are manual: the block is already per-shard
    # there, and nothing is left to constrain or to map over
    mesh_sizes = {a: n for a, n in mesh.shape.items() if a not in mesh.manual_axes}
    entries = []
    used: set[str] = set()  # a mesh axis may shard at most one dim; first dim wins
    for dim, a in enumerate(axes):
        target = table.get(a) if a is not None else None
        if target is None:
            entries.append(None)
            continue
        kept: list[str] = []
        size = 1
        for t in target if isinstance(target, tuple) else (target,):
            if t not in mesh_sizes or t in used:
                continue
            if shape[dim] % (size * mesh_sizes[t]) != 0:
                continue
            kept.append(t)
            size *= mesh_sizes[t]
        used.update(kept)
        entries.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return mesh, PartitionSpec(*entries)


def logical_constraint(x, axes):
    """`nn.with_logical_constraint` that binds under the classic ``with mesh:`` context.

    flax's version only engages when `jax.sharding.set_mesh` is active (its
    `global_mesh_defined` check ignores the resource-env mesh) — and `set_mesh` cannot be
    entered inside `jit`, where our model code runs. So resolve the ambient logical-axis
    rules (set by `ModelWrapper.apply_scope`) here (:func:`logical_spec`) and emit a
    bare-PartitionSpec `with_sharding_constraint`, which jit resolves against whichever
    mesh context is live. No rules or no mesh -> no-op, so meshless single-chip programs
    are untouched.
    """
    resolved = logical_spec(x.shape, axes)
    if resolved is None:
        return x
    return jax.lax.with_sharding_constraint(x, resolved[1])


def kernel_sharding(operands: tuple, results: tuple) -> tuple | None:
    """Resolve a kernel's operands and results, each a ``(shape, logical axes)`` pair, to
    ``(mesh, in_specs, out_specs)`` for :func:`shard_kernel`, or None when the trace has
    no multi-device mesh. The result is hashable and must be taken where the model is
    traced (inside `apply_scope` and the mesh context) and handed on as a static
    argument: a `custom_vjp`'s rules are traced later — in the backward pass, or when a
    remat replays the forward — when neither context is live any more."""
    first = logical_spec(*operands[0])
    if first is None:
        return None
    mesh = first[0]
    if all(n == 1 or a in mesh.manual_axes for a, n in mesh.shape.items()):
        return None  # one device, or already inside a shard_map body
    in_specs, out_specs = (
        tuple(logical_spec(shape, axes)[1] for shape, axes in arrays)
        for arrays in (operands, results)
    )
    return mesh, in_specs, out_specs


def shard_kernel(kernel, sharding: tuple | None):
    """``kernel``, run once per shard of the mesh in `sharding` (:func:`kernel_sharding`).

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"), so every `pallas_call` that can
    trace under a multi-device mesh — any sharded training or serving program — goes
    through here. `kernel` returns a tuple, sees per-shard blocks, and may ask
    `jax.lax.axis_index` where a shard's position matters. With `sharding` None it is
    returned as it is."""
    if sharding is None:
        return kernel
    mesh, in_specs, out_specs = sharding
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def logical_to_mesh_sharding(logical_spec_tree, mesh: Mesh, rules: LogicalRules):
    """Convert a pytree of logical PartitionSpecs (from `nn.get_partition_spec`) to
    NamedShardings on `mesh`."""
    return nn.logical_to_mesh_sharding(logical_spec_tree, mesh, rules)


def prune_indivisible_spec(spec: PartitionSpec, shape: tuple[int, ...], mesh: Mesh) -> PartitionSpec:
    """Drop mesh axes from a PartitionSpec entry when they don't divide the dimension evenly.

    Makes every mesh size work (odd device counts, dims smaller than the axis): an indivisible
    axis falls back to replication for that tensor instead of a GSPMD divisibility error. The
    reference has the same escape hatch implicitly — FSDP pads, DTensor requires divisibility
    and simply can't run those shapes."""
    entries = []
    dropped: list[str] = []
    for dim, entry in enumerate(spec):
        if entry is None:
            entries.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept: list[str] = []
        size = 1
        for ax in axes:
            ax_size = mesh.shape[ax]
            if shape[dim] % (size * ax_size) == 0:
                kept.append(ax)
                size *= ax_size
            else:
                dropped.append(f"{ax}({ax_size})!|dim{dim}={shape[dim]}")
        entries.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    if dropped:
        # replication instead of sharding can be a large memory regression at scale — say so
        from ..utils.logger import log_rank_0

        log_rank_0(
            logging.WARNING,
            f"sharding fallback: replicating tensor of shape {tuple(shape)} on mesh axes "
            f"{dropped} (indivisible dimension)",
        )
    return PartitionSpec(*entries)


def prune_indivisible_shardings(abstract_tree, sharding_tree, mesh: Mesh):
    """Apply `prune_indivisible_spec` leaf-wise over (ShapeDtypeStruct tree, NamedSharding tree)."""
    return jax.tree.map(
        lambda leaf, sh: (
            NamedSharding(
                mesh,
                prune_indivisible_spec(sh.spec, leaf.shape, mesh),
                memory_kind=sh.memory_kind,  # preserve host offload placement
            )
            if isinstance(sh, NamedSharding)
            else sh
        ),
        abstract_tree,
        sharding_tree,
    )


def get_abstract_state_shardings(abstract_tree, logical_spec_tree, mesh: Mesh, rules: LogicalRules):
    """Pair an eval_shape tree with shardings derived from its logical specs."""
    shardings = logical_to_mesh_sharding(logical_spec_tree, mesh, rules)
    shardings = prune_indivisible_shardings(abstract_tree, shardings, mesh)
    return jax.tree.map(
        lambda shape, sharding: jax.ShapeDtypeStruct(shape.shape, shape.dtype, sharding=sharding),
        abstract_tree,
        shardings,
    )


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
