"""Logical-axis → mesh-axis rules and sharding computation.

Model code annotates parameters and activations with *logical* names
(``vocab``/``embed``/``heads``/``mlp`` for params, ``batch``/``length``/
``act_*`` for activations — see models/gpt.py). This module maps them onto
the physical mesh axes (data/fsdp/tensor/sequence/pipeline/expert):

* pure data parallel: every param rule lands on a size-1 axis → replicated
  params, batch sharded over (data, fsdp). Gradient sync is the psum XLA
  inserts for the replicated-param gradient — the moral equivalent of DDP's
  all-reduce hook (reference trainer.py:88-91), but fused into the step.
* FSDP: param ``embed`` axes shard over ``fsdp``; XLA all-gathers just-in-time.
* Tensor parallel: ``heads``/``mlp``/``vocab`` shard over ``tensor`` —
  Megatron-style column/row splits fall out of the einsum shardings.
* Sequence parallel: activation ``length`` shards over ``sequence``
  (ring attention in ops/ring_attention.py extends this to attention itself).
"""

from __future__ import annotations

import math
from typing import Any

import jax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# fmt: off
DEFAULT_LOGICAL_AXIS_RULES = (
    # activations
    ("batch", ("data", "fsdp", "expert")),
    ("length", "sequence"),
    ("act_embed", None),
    ("act_mlp", "tensor"),
    ("act_heads", "tensor"),
    ("act_kv", None),
    ("act_vocab", "tensor"),
    # MoE dispatch layout (models/moe.py): the leading expert dim shards
    # over the mesh `expert` axis while the token-group dim keeps the
    # remaining batch axes — the reshard between the two IS the all-to-all.
    ("act_expert", "expert"),
    ("act_expert_group", ("data", "fsdp")),
    # params
    ("vocab", "tensor"),
    ("embed", "fsdp"),
    ("mlp", "tensor"),
    ("heads", "tensor"),
    ("kv", None),
    ("qkv", None),
    ("position", None),
    # Norm scales (models/llama.py RMSNorm): replicated — a (D,) vector
    # gains nothing from fsdp and an embed→fsdp mapping forces an
    # inefficient embed-wise grad reshard for the dscale reduction.
    ("norm", None),
    ("expert", "expert"),
    # Stacked-layer params (models/gpt_pipeline.py): the leading layer dim
    # shards over pipeline stages; the per-layer dims reuse the standard
    # names above (heads/mlp -> tensor), so DP x PP x TP composes.
    ("layers", "pipeline"),
)
# fmt: on


def ambient_mesh() -> Mesh | None:
    """The mesh from an enclosing ``with mesh:`` block, if any.

    Single home for the private-API access (jax._src churns; one site to
    fix) — used by ring attention and the pipeline model to decide whether
    a parallel axis is available at trace time.
    """
    from jax._src import mesh as mesh_lib

    physical = mesh_lib.thread_resources.env.physical_mesh
    return None if physical.empty else physical


# Mesh axes the activation ``batch`` dim shards over (the "batch" rule
# above) — also what token-wise Pallas kernels shard their rows over.
BATCH_AXES = ("data", "fsdp", "expert")


def kernel_mesh() -> Mesh | None:
    """The ambient mesh a Pallas call must be ``shard_map``-ped over, or
    None when the bare call is right.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so inside a jitted step on a mesh of
    more than one device every kernel call site wraps itself in
    ``jax.shard_map`` over the axes its operands are sharded on and each
    chip runs the kernel on its own shard only. None on a one-device
    mesh, outside any ``with mesh:``, and inside an enclosing shard_map
    (pipeline stages, ring/ulysses bodies), where operands already are
    per-shard.
    """
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return None
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return mesh


def shard_axes(mesh: Mesh, axes: tuple[str, ...], *dims: int):
    """PartitionSpec entry sharding a dim over those of ``axes`` the mesh
    actually splits (size > 1) — or None (replicated) when there are none
    or any of ``dims`` does not divide by their product. The (1, T)
    param-init probe batch is the designed replicated case."""
    used = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
    n = math.prod(mesh.shape[a] for a in used)
    if not used or any(d % n for d in dims):
        return None
    return used if len(used) > 1 else used[0]


def data_parallel_degree(mesh: Mesh) -> int:
    """Number of batch shards = product of the axes 'batch' maps onto.

    The ``expert`` axis carries batch shards too: dense params replicate
    over it while MoE expert weights shard over it (GShard layout), so
    non-MoE compute is never duplicated across expert devices.
    """
    return mesh.shape["data"] * mesh.shape["fsdp"] * mesh.shape.get("expert", 1)


def batch_sharding(mesh: Mesh, *, with_accum_dim: bool = False) -> NamedSharding:
    """Sharding for (accum, B, T) or (B, T) token batches."""
    if with_accum_dim:
        return NamedSharding(mesh, P(None, BATCH_AXES, "sequence"))
    return NamedSharding(mesh, P(BATCH_AXES, "sequence"))


# Leaves whose unsatisfiable sharding spec was already repaired (and warned
# about) once this process — keyed by (tree path, shape, spec) so distinct
# leaves each warn exactly once and re-derivations stay silent.
_REPAIR_WARNED: set[tuple] = set()


def _spec_fits(mesh: Mesh, spec, shape: tuple) -> bool:
    """Every sharded dim of ``shape`` is divisible by its mapped axis product."""
    for dim, axes in zip(shape, spec):
        if axes is None:
            continue
        names = (axes,) if isinstance(axes, str) else axes
        shards = 1
        for name in names:
            shards *= mesh.shape[name]
        if dim % shards != 0:
            return False
    return True


def state_shardings(mesh: Mesh, abstract_tree: Any, rules=DEFAULT_LOGICAL_AXIS_RULES):
    """NamedShardings for a pytree whose leaves may carry logical metadata.

    Leaves without metadata (e.g. the dummy model, optimizer scalars) get
    fully-replicated shardings. So do leaves that inherited a param's
    logical names but not its shape — optimizers that reduce over param
    dims (optax.adafactor's factored ``v_row``/``v_col``, rank reduced by
    one, and its shape-(1,) placeholders) carry the full spec through the
    flax boxes, and applying it to the reduced array is a pjit error.
    Repairs: spec longer than the rank, and any leaf whose spec the mesh
    cannot satisfy (a sharded dim not divisible by the mapped axis
    product — which previously surfaced as an opaque pjit error at jit
    time) fall back to replicated, the latter with a one-time warning
    NAMING the leaf so a silently-unsharded giant embedding is visible.
    """
    logical_spec = nn.get_partition_spec(abstract_tree)
    shardings = nn.logical_to_mesh_sharding(logical_spec, mesh, list(rules))

    def finalize(path, sharding: Any, leaf: Any) -> Any:
        value = nn.meta.unbox(leaf)
        shape = getattr(value, "shape", None)
        if shape is None or not isinstance(sharding, NamedSharding):
            return sharding
        if len(sharding.spec) > len(shape):
            return replicated(mesh)
        if not _spec_fits(mesh, sharding.spec, tuple(shape)):
            if tuple(shape) != (1,):
                # (1,) placeholders (adafactor) are structural noise; a
                # full-rank leaf losing its sharding is worth one warning.
                key = (jax.tree_util.keystr(path), tuple(shape), str(sharding.spec))
                if key not in _REPAIR_WARNED:
                    _REPAIR_WARNED.add(key)
                    from ..utils.logging import get_logger

                    get_logger().warning(
                        "sharding spec %s does not divide leaf %s with shape "
                        "%s on mesh %s; storing this leaf REPLICATED (pick "
                        "dims divisible by the mapped axis sizes to shard it)",
                        sharding.spec,
                        jax.tree_util.keystr(path),
                        tuple(shape),
                        dict(mesh.shape),
                    )
            return replicated(mesh)
        return sharding

    return jax.tree_util.tree_map_with_path(
        finalize,
        shardings,
        abstract_tree,
        is_leaf=lambda s: isinstance(s, NamedSharding),
    )


# Axes whose product is the data-parallel degree — the replicas that hold
# redundant optimizer-state copies, i.e. the ZeRO partitioning dimension.
ZERO_PARTITION_AXES = BATCH_AXES


def opt_state_shardings(
    mesh: Mesh,
    abstract_state: Any,
    rules=DEFAULT_LOGICAL_AXIS_RULES,
    *,
    subject: str = "optimizer-state",
):
    """ZeRO-style shardings: partition every optimizer-state leaf across
    the combined data-parallel axes (``data``/``fsdp``/``expert``).

    The weight-update sharding of Xu et al. (arXiv:2004.13336): replicas
    that hold redundant copies of the AdamW moments each keep only a
    1/N_dp shard instead. Per-leaf derivation starts from the param-
    inherited spec (:func:`state_shardings` — the moments carry the flax
    ``Partitioned`` metadata through optax's init) and then APPENDS the
    data-parallel axes the spec does not already use to the first dim
    that can absorb them: the dim's size must be divisible by its
    existing shard product times the free-axis product. Leaves with no
    such dim (scalars like Adam's ``count``, indivisible shapes,
    adafactor's ``(1,)`` placeholders) keep their base spec — replicated
    across the dp axes — with a one-time warning for non-trivial leaves,
    so the fallback is visible instead of silently eating the memory win.

    Applying the same derivation to the abstract PARAM tree yields the
    gradient layout of ZeRO stage 2 (reduce-scattered grads) — the
    train step's ``grad_shardings`` constraint (training/train_step.py).
    """
    base = state_shardings(mesh, abstract_state, rules)
    free_template = [a for a in ZERO_PARTITION_AXES if mesh.shape.get(a, 1) > 1]
    if not free_template:
        return base

    def extend(path, sharding: Any, leaf: Any) -> Any:
        value = nn.meta.unbox(leaf)
        shape = getattr(value, "shape", None)
        if shape is None or not shape or not isinstance(sharding, NamedSharding):
            return sharding  # scalars / non-array leaves stay replicated
        spec = list(sharding.spec) + [None] * (len(shape) - len(sharding.spec))
        used: set[str] = set()
        for axes in spec:
            if axes is None:
                continue
            used.update((axes,) if isinstance(axes, str) else axes)
        free = [a for a in free_template if a not in used]
        if not free:
            return sharding
        free_product = 1
        for a in free:
            free_product *= mesh.shape[a]
        for i, dim in enumerate(shape):
            axes = spec[i]
            names = () if axes is None else (
                (axes,) if isinstance(axes, str) else tuple(axes)
            )
            current = 1
            for name in names:
                current *= mesh.shape[name]
            if dim % (current * free_product) == 0:
                spec[i] = tuple(names) + tuple(free)
                return NamedSharding(mesh, P(*spec))
        if _leaf_size(shape) > 1:
            key = ("zero", subject, jax.tree_util.keystr(path), tuple(shape))
            if key not in _REPAIR_WARNED:
                _REPAIR_WARNED.add(key)
                from ..utils.logging import get_logger

                get_logger().warning(
                    "ZeRO: %s leaf %s with shape %s has no dim "
                    "divisible by the data-parallel product %d; this leaf "
                    "stays REPLICATED across the %s axes",
                    subject,
                    jax.tree_util.keystr(path),
                    tuple(shape),
                    free_product,
                    "/".join(free),
                )
        return sharding

    return jax.tree_util.tree_map_with_path(
        extend,
        base,
        abstract_state,
        is_leaf=lambda s: isinstance(s, NamedSharding),
    )


def _leaf_size(shape: tuple) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def host_memory_kind(mesh: Mesh) -> str | None:
    """``"pinned_host"`` when the mesh devices expose a host memory space
    jit shardings can target (TPU backends with the memories API), else
    None — callers fall back to an explicit host round-trip.
    ``unpinned_host`` alone does not count: on the CPU backend it IS device
    memory, so offloading to it would be a no-op pretending otherwise (the
    installed jax 0.9.0 gives the CPU backend a ``pinned_host`` space too)."""
    try:
        device = mesh.devices.flat[0]
        kinds = {m.kind for m in device.addressable_memories()}
    except Exception:  # noqa: BLE001 — memories API is backend-optional
        return None
    return "pinned_host" if "pinned_host" in kinds else None


def with_memory_kind(shardings: Any, kind: str) -> Any:
    """Re-target every NamedSharding leaf of a sharding tree at ``kind``."""
    return jax.tree.map(
        lambda s: s.with_memory_kind(kind) if isinstance(s, NamedSharding) else s,
        shardings,
        is_leaf=lambda s: isinstance(s, NamedSharding),
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    """``{axis: size}`` for every named mesh axis — the topology record a
    checkpoint manifest carries (resilience/elastic.py validates a resume
    against it)."""
    return {name: int(size) for name, size in mesh.shape.items()}


def reshard_state(tree: Any, shardings: Any) -> Any:
    """Lay a (restored) state pytree out onto the current mesh's shardings.

    This is the elastic-resume entry point: a checkpoint holds FULL host
    arrays, so landing them on a mesh with a different data-parallel/fsdp
    degree is purely a placement decision against the sharding tree
    computed for the NEW mesh. Implemented as a jit'd identity with
    ``out_shardings`` — NOT ``jax.device_put`` — because on the CPU
    backend device_put can alias the host numpy buffers zero-copy, and
    the first train step then DONATES those buffers (donate_argnums);
    XLA writing into memory numpy still owns corrupts the heap (segfault
    reproduced by the chaos harness on jax 0.4.37). The jit identity's
    outputs are XLA-owned copies, which makes them safely donatable."""
    return jax.jit(lambda s: s, out_shardings=shardings)(tree)
