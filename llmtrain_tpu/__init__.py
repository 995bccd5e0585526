"""llmtrain_tpu — a TPU-native (JAX/XLA/pjit/Pallas) LLM training framework.

Brand-new framework with the capabilities of the reference ``llmtrain``
(LeGabriel/local-llm-training-k8s): strict YAML→Pydantic configs, a plugin
registry of model adapters and data modules, a step-based trainer whose entire
optimizer step (grad accumulation + clipping + AdamW + LR schedule + gradient
sync) is one jit-compiled XLA program over a ``jax.sharding.Mesh``,
checkpoint/resume with exact loss parity, rank-0 MLflow tracking, and
Kubernetes IndexedJob orchestration (incl. a GKE TPU pod-slice variant).

The compute path is JAX/Flax/Pallas; parallelism is expressed as shardings
over a named device mesh (data/fsdp/tensor/sequence axes) with XLA
collectives over ICI/DCN — not a DDP wrapper.
"""

import time as _time

# Start-up's first stamp (telemetry/timeline.py). Nothing else belongs up here.
_T_IMPORT = _time.perf_counter()

__version__ = "0.1.0"
