"""Declarative mesh planning: one validated object per parallelism layout.

Before this module, the rules that decide whether a mesh layout can run
were scattered: wildcard resolution in ``distributed/__init__.py``,
pipeline capability in the Trainer, GQA/tensor divisibility in
``models/gpt.py:validate_mesh``, microbatch/pipeline coupling in
``models/gpt_pipeline.py``, expert-axis wiring in ``models/moe.py`` +
``parallel/sharding.py``, and the resume topology matrix in
``resilience/elastic.py``.  A layout that passed one layer could still
die in the next as an opaque pjit/XLA sharding error deep inside trainer
setup.  :class:`MeshPlan` pulls every rule into one validated object:

* axis sizes (``data``/``fsdp``/``tensor``/``sequence``/``pipeline``/
  ``expert``, incl. the ``-1`` wildcard) resolved against the device
  count — :func:`resolve_axis_sizes` is now the single owner of that
  math (``distributed.resolve_mesh_axes`` delegates here);
* model capability flags (``supports_pipeline``, attention kind vs the
  ``sequence`` axis, MoE expert count vs the ``expert`` axis) and
  divisibility rules (heads/KV-heads over ``tensor``, microbatch over
  ``pipeline_microbatches``, context over ``sequence``);
* the same topology matrix elastic resume enforces:
  :meth:`MeshPlan.describe_topology` emits exactly the manifest block
  ``resilience/elastic.py`` validates, so a plan is checkpoint/manifest
  -legal by construction (``mesh_axis_sizes`` round-trips).

Every violation raises :class:`MeshPlanError` — a *named* error mapped to
exit code 2 (config error) by ``resilience/exit_codes.py``, because
retrying the same layout replays the same mismatch.

Deliberately dependency-free (dict math only, like elastic.py): the CLI
``plan`` path, the search enumerator, and the tests import it without
dragging in jax.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Mapping

from ..config.activation_tiers import canonical_tier_spec, parse_activation_tiers
from ..resilience.elastic import ELASTIC_AXES, MODEL_AXES, describe_topology

# Canonical axis order — must match distributed.MESH_AXES (physical
# iteration order: data outermost so replicas span hosts, tensor/sequence
# shards ride ICI). distributed/__init__.py asserts the two stay in sync.
MESH_AXES = ("data", "fsdp", "tensor", "sequence", "pipeline", "expert")

# Axes whose product is the data-parallel degree (parallel/sharding.py
# data_parallel_degree: batch shards over all three).
assert set(ELASTIC_AXES) | set(MODEL_AXES) == set(MESH_AXES)


class MeshPlanError(ValueError):
    """A parallelism layout that cannot run: axis sizes don't divide the
    device count, the global micro-batch, or a model dimension, or the
    model lacks a capability the layout requires.  Deterministic config
    problem — ``resilience/exit_codes.py`` maps it to exit code 2, and the
    message names the axis and the rule instead of surfacing later as an
    opaque pjit/XLA sharding error."""


def resolve_axis_sizes(
    sizes: Mapping[str, int], device_count: int
) -> dict[str, int]:
    """Materialize axis sizes against ``device_count``, expanding one
    ``-1`` wildcard.  Single owner of the wildcard/divisibility math —
    ``distributed.resolve_mesh_axes`` delegates here.

    Raises :class:`MeshPlanError` when more than one axis is a wildcard,
    when the fixed axes don't divide the device count, or when the
    resolved product mismatches it.
    """
    out = {axis: int(sizes.get(axis, 1)) for axis in MESH_AXES}
    for axis, v in out.items():
        if v == 0 or v < -1:
            raise MeshPlanError(
                f"mesh axis {axis!r} must be a positive int or -1 (got {v})"
            )
    wildcards = [axis for axis, v in out.items() if v == -1]
    if len(wildcards) > 1:
        raise MeshPlanError(
            f"at most one mesh axis may be -1 (wildcard); got {wildcards}"
        )
    fixed = math.prod(v for v in out.values() if v != -1)
    if wildcards:
        if device_count % fixed != 0:
            raise MeshPlanError(
                f"device count {device_count} not divisible by fixed mesh "
                f"axes product {fixed} (axes {dict(out)}) — the "
                f"{wildcards[0]!r} wildcard cannot be filled"
            )
        out[wildcards[0]] = device_count // fixed
        fixed *= out[wildcards[0]]
    if fixed != device_count:
        raise MeshPlanError(
            f"mesh axes {dict(out)} multiply to {fixed} but {device_count} "
            "devices are available — axis sizes must exactly tile the "
            "device count"
        )
    return out


@dataclass(frozen=True)
class ModelCaps:
    """Capability flags + divisibility inputs a plan validates against.

    Built from a config (and optionally the registered adapter class) by
    :func:`caps_from_config`; constructed directly in pure unit tests.
    """

    n_heads: int
    block_size: int
    supports_pipeline: bool = False
    attention: str = "dense"
    n_kv_heads: int = 0
    n_experts: int = 0
    pipeline_microbatches: int = 4
    # Layer count, consumed by activation-tier spec validation; 0 =
    # unknown (pure unit tests constructing ModelCaps directly) — tier
    # specs then pass through unvalidated.
    n_layers: int = 0
    # Resolved cross-entropy implementation (config_loss_impl): fused_ce
    # contracts hidden states against the WHOLE [V, d] lm-head per token
    # shard, so it cannot ride a vocab-sharding tensor axis.
    loss_impl: str = "dense"


def caps_from_config(cfg: Any, adapter: Any | None = None) -> ModelCaps:
    """Derive :class:`ModelCaps` from a ``RunConfig`` (+ optional adapter
    class/instance for the ``supports_pipeline`` flag — registry lookup is
    the caller's job so this module stays import-light)."""
    extra = dict(cfg.model.extra or {})
    # Only adapters that read the knob can resolve to a streamed CE; the
    # auto-selection must not charge e.g. dummy_gpt with fused_ce.
    known = getattr(adapter, "known_extra_keys", None)
    reads_loss_impl = "loss_impl" in extra or (
        known is not None and "loss_impl" in known
    )
    return ModelCaps(
        n_heads=int(cfg.model.n_heads),
        block_size=int(cfg.model.block_size),
        supports_pipeline=bool(getattr(adapter, "supports_pipeline", False)),
        attention=str(cfg.model.attention),
        n_kv_heads=int(extra.get("n_kv_heads", 0) or 0),
        n_experts=int(extra.get("n_experts", 0) or 0),
        pipeline_microbatches=int(extra.get("pipeline_microbatches", 4) or 4),
        n_layers=int(cfg.model.n_layers),
        loss_impl=config_loss_impl(cfg)[0] if reads_loss_impl else "dense",
    )


@dataclass(frozen=True)
class MeshPlan:
    """One fully-resolved, validated parallelism layout.

    Construct via :func:`resolve_plan` (which validates) — a directly-
    instantiated MeshPlan carries no legality guarantee.  ``axes`` always
    holds all six concrete sizes (no wildcard survives resolution).
    """

    axes: dict[str, int]
    device_count: int
    micro_batch_size: int
    grad_accum_steps: int
    remat: bool = False
    zero_stage: int = 0  # 0 = ZeRO off; 1/2 per trainer.zero.stage
    attention: str = "dense"
    model_name: str = ""
    # Canonical per-layer activation-tier spec (config/activation_tiers.py),
    # "" = unset (the legacy remat flag above describes the layout).
    activation_tiers: str = ""

    @property
    def data_parallel(self) -> int:
        """Combined batch-sharding degree (parallel/sharding.py
        data_parallel_degree: data x fsdp x expert)."""
        return math.prod(self.axes[a] for a in ELASTIC_AXES)

    @property
    def model_parallel(self) -> int:
        return math.prod(self.axes[a] for a in MODEL_AXES)

    @property
    def global_micro_batch(self) -> int:
        return self.micro_batch_size * self.data_parallel

    def mesh_axis_sizes(self) -> dict[str, int]:
        """Round-trips with ``parallel.sharding.mesh_axis_sizes(mesh)`` of
        the built mesh — the exact dict checkpoint manifests record."""
        return {axis: int(self.axes[axis]) for axis in MESH_AXES}

    def describe_topology(self, *, num_processes: int = 1) -> dict[str, Any]:
        """The manifest topology block (resilience/elastic.py) this plan
        produces — a plan is checkpoint-legal by construction because
        resume validation consumes exactly this dict."""
        return describe_topology(
            self.mesh_axis_sizes(),
            data_parallel=self.data_parallel,
            global_micro_batch=self.global_micro_batch,
            micro_batch_size=self.micro_batch_size,
            grad_accum_steps=self.grad_accum_steps,
            num_processes=num_processes,
        )

    def key(self) -> str:
        """Compact stable identity, e.g. ``d2.f2.t1.s1.p1.e2|mb4|remat0|zero1``
        (``|act=<spec>`` appended only when a tier ladder is set, so every
        pre-tier key string is unchanged)."""
        mesh = ".".join(f"{a[0]}{self.axes[a]}" for a in MESH_AXES)
        base = f"{mesh}|mb{self.micro_batch_size}|remat{int(self.remat)}|zero{self.zero_stage}"
        if self.activation_tiers:
            return f"{base}|act={self.activation_tiers}"
        return base

    def config_overrides(self) -> dict[str, Any]:
        """The config fields this plan pins, as a nested dict that deep-
        merges into a ``RunConfig.model_dump()`` — the emitted tuned YAML
        and the probe configs are both built through this, so what the
        tuner measured is exactly what ``llmtrain train`` later runs."""
        overrides: dict[str, Any] = {
            "distributed": {"mesh": self.mesh_axis_sizes()},
            "trainer": {
                "micro_batch_size": self.micro_batch_size,
                "zero": {
                    "enabled": self.zero_stage > 0,
                    "stage": self.zero_stage if self.zero_stage > 0 else 1,
                },
            },
            "model": {"remat": self.remat},
        }
        if self.activation_tiers:
            # Tiers subsume remat; pin remat off so the merged config
            # passes the schema's mutual-exclusion check.
            overrides["model"] = {
                "remat": False,
                "extra": {"activation_tiers": self.activation_tiers},
            }
        return overrides


def resolve_plan(
    *,
    mesh_sizes: Mapping[str, int],
    device_count: int,
    caps: ModelCaps,
    micro_batch_size: int,
    grad_accum_steps: int = 1,
    remat: bool = False,
    zero_stage: int = 0,
    attention: str | None = None,
    model_name: str = "",
    activation_tiers: str = "",
) -> MeshPlan:
    """Resolve + validate one layout into a :class:`MeshPlan`.

    Every rule that used to fail later (or not at all until pjit) lives
    here, each with a named :class:`MeshPlanError`:

    * axis sizes tile the device count (wildcard included);
    * ``pipeline > 1`` needs ``supports_pipeline`` and
      ``micro_batch_size % pipeline_microbatches == 0`` (the global
      micro-batch must divide by dp x microbatches — gpt_pipeline);
    * ``sequence > 1`` with the ring/ulysses kernels needs
      ``block_size % sequence == 0``; ulysses additionally shards heads,
      so ``n_heads % sequence == 0`` (dense attention on a sequence axis
      is legal as-is — GSPMD inserts the comms);
    * ``tensor > 1`` needs ``n_heads % tensor == 0`` (and
      ``n_kv_heads % tensor`` for GQA — models/gpt.py validate_mesh);
    * ``expert > 1`` on a MoE model needs ``n_experts % expert == 0``
      (models/moe.py layout); on a dense model the axis only carries
      batch shards and is always legal;
    * ``tensor > 1`` with the resolved ``loss_impl: fused_ce`` is
      rejected: the Pallas kernel needs the whole ``[V, d]`` lm-head per
      token shard and ``tensor`` shards its vocab (ops/fused_ce.py);
    * ``zero_stage`` in {0, 1, 2} (trainer.zero.stage).
    """
    axes = resolve_axis_sizes(mesh_sizes, device_count)
    att = caps.attention if attention is None else attention
    if micro_batch_size < 1:
        raise MeshPlanError(
            f"micro_batch_size must be >= 1 (got {micro_batch_size})"
        )
    if zero_stage not in (0, 1, 2):
        raise MeshPlanError(
            f"zero_stage must be 0 (off), 1 or 2 (got {zero_stage})"
        )

    pp = axes["pipeline"]
    if pp > 1:
        if not caps.supports_pipeline:
            raise MeshPlanError(
                f"mesh axis 'pipeline' is {pp} but model "
                f"{model_name or '?'!r} does not stack its layers for "
                "pipeline stages; use a pipeline-capable model (e.g. "
                "'gpt_pipeline') or set pipeline to 1"
            )
        m = max(caps.pipeline_microbatches, 1)
        if micro_batch_size % m != 0:
            raise MeshPlanError(
                f"trainer.micro_batch_size ({micro_batch_size}) must be "
                f"divisible by model.extra.pipeline_microbatches ({m}) on "
                "a pipeline mesh — otherwise the global micro-batch "
                "cannot split into pipeline microbatches"
            )

    # A sequence axis is legal with ANY attention (dense just lets GSPMD
    # insert the comms — tests/test_distributed.py pins that the layouts
    # agree); the ring/ulysses kernels additionally need exact shards.
    sp = axes["sequence"]
    if sp > 1 and att in ("ring", "ulysses"):
        if caps.block_size % sp != 0:
            raise MeshPlanError(
                f"model.block_size ({caps.block_size}) must be divisible "
                f"by the mesh sequence axis ({sp}) — each {att} shard "
                "holds an equal context slice"
            )
        if att == "ulysses" and caps.n_heads % sp != 0:
            raise MeshPlanError(
                f"model.n_heads ({caps.n_heads}) must be divisible by the "
                f"mesh sequence axis ({sp}) — ulysses all-to-alls between "
                "sequence shards and head shards"
            )

    tp = axes["tensor"]
    if tp > 1:
        if caps.n_heads % tp != 0:
            raise MeshPlanError(
                f"model.n_heads ({caps.n_heads}) must be divisible by the "
                "mesh tensor axis "
                f"({tp}) — attention heads shard over tensor parallelism"
            )
        if caps.n_kv_heads and caps.n_kv_heads % tp != 0:
            raise MeshPlanError(
                f"model.extra.n_kv_heads ({caps.n_kv_heads}) must be "
                f"divisible by the mesh tensor axis ({tp}) — K/V heads "
                "shard over tensor parallelism like query heads do"
            )
        if caps.loss_impl == "fused_ce":
            raise MeshPlanError(
                "model.extra.loss_impl resolves to 'fused_ce' but the mesh "
                f"tensor axis is {tp}: the fused lm-head + CE kernel "
                "contracts each token shard against the whole [V, d] "
                "matrix, which tensor parallelism shards over vocab — set "
                "model.extra.loss_impl: chunked_ce (or dense), or tensor: 1"
            )

    # `expert` with a dense model is legal — the axis then only carries
    # batch shards (it is one of the ELASTIC data-parallel axes,
    # parallel/sharding.py). Only a MoE model adds the divisibility rule.
    ep = axes["expert"]
    if ep > 1 and caps.n_experts > 0 and caps.n_experts % ep != 0:
        raise MeshPlanError(
            f"model.extra.n_experts ({caps.n_experts}) must be "
            f"divisible by the mesh expert axis ({ep}) — each shard "
            "holds an equal expert slice"
        )

    tiers_spec = str(activation_tiers or "")
    if tiers_spec:
        if remat:
            raise MeshPlanError(
                "model.remat: true conflicts with activation_tiers; tiers "
                "subsume the remat flag"
            )
        if caps.n_layers > 0:
            try:
                tiers_spec = canonical_tier_spec(
                    parse_activation_tiers(tiers_spec, caps.n_layers)
                )
            except ValueError as exc:
                raise MeshPlanError(f"activation_tiers: {exc}") from exc

    return MeshPlan(
        axes=axes,
        device_count=device_count,
        micro_batch_size=int(micro_batch_size),
        grad_accum_steps=int(grad_accum_steps),
        remat=bool(remat),
        zero_stage=int(zero_stage),
        attention=att,
        model_name=model_name,
        activation_tiers=tiers_spec,
    )


def plan_from_config(
    cfg: Any, device_count: int, *, adapter: Any | None = None
) -> MeshPlan:
    """The plan the *current* config resolves to on ``device_count``
    devices — the identity/baseline candidate of every tune, and the
    object ``llmtrain plan`` prints."""
    caps = caps_from_config(cfg, adapter)
    zero = cfg.trainer.zero
    return resolve_plan(
        mesh_sizes=cfg.distributed.mesh.axis_sizes(),
        device_count=device_count,
        caps=caps,
        micro_batch_size=cfg.trainer.micro_batch_size,
        grad_accum_steps=cfg.trainer.grad_accum_steps,
        remat=cfg.model.remat,
        zero_stage=int(zero.stage) if zero.enabled else 0,
        attention=cfg.model.attention,
        model_name=cfg.model.name,
        activation_tiers=str(
            (cfg.model.extra or {}).get("activation_tiers", "") or ""
        ),
    )


# --------------------------------------------------------------------------
# Analytic memory model (per-device HBM prediction)
# --------------------------------------------------------------------------

# Device-resident activation copies of [tokens, d_model] per layer by
# tier. none=14 / full=2 are the pre-tier all-or-nothing model (the exact
# values the old `2.0 if remat else 14.0` used); selective keeps the ~6
# matmul outputs dots_saveable pins; offload keeps ~1 (the in-flight
# staging buffer) and parks the block boundary on the host instead.
TIER_ACT_COPIES: dict[str, float] = {
    "none": 14.0,
    "selective": 6.0,
    "full": 2.0,
    "offload": 1.0,
}

# Host-RAM copies of [tokens, d_model] per offload layer: the block-input
# residual, double-buffered so the D2H of layer i overlaps layer i+1.
OFFLOAD_HOST_COPIES = 2.0


def plan_layer_tiers(plan: MeshPlan, n_layers: int) -> tuple[str, ...]:
    """The per-layer tier list a plan implies: the parsed spec when set,
    else the legacy remat flag mapped to all-``full``/all-``none``."""
    if plan.activation_tiers:
        return parse_activation_tiers(plan.activation_tiers, n_layers)
    return ("full",) * n_layers if plan.remat else ("none",) * n_layers


def estimate_param_count(
    *,
    d_model: int,
    n_layers: int,
    d_ff: int,
    vocab_size: int,
    block_size: int,
    tie_embeddings: bool = True,
    n_experts: int = 0,
) -> int:
    """Analytic transformer parameter count (GPT-shaped: QKVO + MLP +
    norms + embeddings).  An estimate for *relative* feasibility ranking,
    not an exact census — MoE multiplies the MLP block by ``n_experts``
    (plus the router), LoRA/quant variants are close enough."""
    attn = 4 * d_model * d_model + 4 * d_model  # QKVO kernels + biases
    mlp = 2 * d_model * d_ff + d_model + d_ff  # up/down kernels + biases
    if n_experts > 0:
        mlp = mlp * n_experts + d_model * n_experts  # experts + router
    norms = 4 * d_model  # 2 LayerNorms (scale+bias) per block
    per_layer = attn + mlp + norms
    embed = vocab_size * d_model + block_size * d_model + 2 * d_model
    head = 0 if tie_embeddings else vocab_size * d_model
    return int(n_layers * per_layer + embed + head)


def config_loss_impl(cfg) -> tuple[str, int]:
    """``(loss_impl, ce_chunk)`` the planner should assume for ``cfg`` —
    resolved by the SAME selection authority the GPT adapter family runs
    at build time (ops/fused_ce.py:resolve_loss_impl), so an `llmtrain
    plan` verdict charges the logits buffer the run will actually pay.
    An invalid explicit value resolves to "dense" here: config validation
    owns that error, and a feasibility estimate must not mask it."""
    extra = dict(getattr(cfg.model, "extra", {}) or {})
    from ..ops.fused_ce import resolve_loss_impl

    try:
        impl = resolve_loss_impl(
            extra.get("loss_impl"),
            vocab_size=int(cfg.model.vocab_size or 50257),
            ce_auto_vocab=int(extra.get("ce_auto_vocab", 32768) or 32768),
            interpret=bool(extra.get("pallas_interpret", False)),
        )
    except ValueError:
        impl = "dense"
    return impl, int(extra.get("ce_chunk", 8192) or 8192)


def predict_hbm_bytes(
    plan: MeshPlan,
    *,
    n_params: int,
    d_model: int,
    n_layers: int,
    vocab_size: int,
    block_size: int,
    dtype_bytes: int = 4,
    param_dtype_bytes: int = 4,
    loss_impl: str = "dense",
    ce_chunk: int = 8192,
) -> dict[str, float]:
    """Predicted per-device HBM footprint of a training step under this
    plan — the feasibility half of the analytical pruning pass.

    The model (documented in docs/perf.md "Mesh planning"): parameters
    and gradients shard over the model-parallel axes x fsdp; AdamW keeps
    two moments, sharded further over the full data-parallel degree when
    ZeRO is on; activations scale with the per-device token count
    (batch / dp, context / sequence) and drop to the sqrt-ish remat
    checkpoint footprint with ``remat``; the logits buffer
    ``mb x T x V`` is counted separately because it dominates small
    models and is what the streamed/fused CE paths shrink: ``loss_impl``
    (resolve via :func:`config_loss_impl`) charges the full buffer under
    "dense", a ``tokens x min(ce_chunk, V)`` block under "chunked_ce",
    and nothing under "fused_ce" — the Pallas kernel keeps every logits
    tile in VMEM (ops/fused_ce.py).
    """
    model_shard = plan.axes["tensor"] * plan.axes["pipeline"] * plan.axes["fsdp"]
    if plan.axes["expert"] > 1:
        model_shard *= plan.axes["expert"]  # MoE: experts shard the MLP
    params_b = n_params * param_dtype_bytes / max(model_shard, 1)
    grads_b = n_params * dtype_bytes / max(model_shard, 1)
    # Opt state mirrors the param sharding; ZeRO additionally partitions
    # it over the data-parallel degree, so the combined shard factor is
    # the whole device count (parallel/sharding.py opt_state_shardings).
    opt_shard = plan.device_count if plan.zero_stage > 0 else max(model_shard, 1)
    opt_b = 2 * n_params * 4.0 / max(opt_shard, 1)  # AdamW m+v, f32
    # Per-device activation tokens: batch shards over dp, context over
    # sequence. Device-resident copies of [tokens, d_model] per layer come
    # from the layer's activation tier (TIER_ACT_COPIES — none=14 dense,
    # full=2 block boundaries, offload additionally parks the boundary in
    # host RAM, tracked separately since it spends no HBM).
    tokens = (
        plan.micro_batch_size
        * (block_size / max(plan.axes["sequence"], 1))
    )
    try:
        tiers = plan_layer_tiers(plan, n_layers)
    except ValueError as exc:
        raise MeshPlanError(f"activation_tiers: {exc}") from exc
    per_copy = tokens * d_model * dtype_bytes
    by_tier: dict[str, float] = {}
    host_b = 0.0
    for tier in tiers:
        by_tier[tier] = by_tier.get(tier, 0.0) + per_copy * TIER_ACT_COPIES[tier]
        if tier == "offload":
            host_b += per_copy * OFFLOAD_HOST_COPIES
    acts_b = sum(by_tier.values())
    if loss_impl == "fused_ce":
        logits_b = 0.0
    elif loss_impl == "chunked_ce":
        logits_b = tokens * min(ce_chunk, vocab_size) * 4.0  # CE runs f32
    else:
        logits_b = tokens * vocab_size * 4.0  # CE runs f32
    total = params_b + grads_b + opt_b + acts_b + logits_b
    return {
        "loss_impl": loss_impl,
        "params_bytes": round(params_b),
        "grads_bytes": round(grads_b),
        "opt_state_bytes": round(opt_b),
        "activation_bytes": round(acts_b),
        "activation_bytes_by_tier": {t: round(v) for t, v in by_tier.items()},
        "activation_host_bytes": round(host_b),
        "logits_bytes": round(logits_b),
        "total_bytes": round(total),
    }


__all__ = [
    "MESH_AXES",
    "MeshPlan",
    "MeshPlanError",
    "ModelCaps",
    "OFFLOAD_HOST_COPIES",
    "TIER_ACT_COPIES",
    "caps_from_config",
    "config_loss_impl",
    "estimate_param_count",
    "plan_from_config",
    "plan_layer_tiers",
    "predict_hbm_bytes",
    "resolve_axis_sizes",
    "resolve_plan",
]
