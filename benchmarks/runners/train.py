"""Runner ``train``: optimizer steps of the program's own trainer in a window.

Set-up builds ONE object — ``llmtrain_tpu.training.Trainer`` with its
compiled step and state — gives it weights made from ``--seed``, drives it
through its first ``check_steps`` steps with the window's own feed
(``BatchPrefetcher`` over ``Trainer._global_batch``) and the window's own
call (``Trainer._train_step_fn``), and hands that same object to the
window. The reference follows those first steps after the window closes
and the program's state is freed.

Private names of the program this leans on (a refactor that renames them
breaks the benchmark; listed in PERF.md): ``Trainer._state``,
``_state_shardings``, ``_train_step_fn``, ``_global_batch``,
``_global_micro``, ``_data_module``, ``_mesh``, ``_rules``;
``data.prefetch.BatchPrefetcher``; ``data.sampler.DeterministicSampler``.
``Trainer.fit()`` is not used: it cannot end without an eval and a
checkpoint, and it cannot be stopped by the clock.
"""

from __future__ import annotations

import gc
import statistics
import time
from typing import Any

import numpy as np

B1 = 0.9  # AdamW's first-moment decay in the program (training/optimizer.py)


def seq_len(ctx) -> int:
    return int(ctx.traffic["seq_len"]) if not ctx.rehearse else ctx.reference.context_length(ctx.config)


def build_run_config(ctx) -> dict:
    """The YAML a user would write for this cell: widths, attention, bf16,
    mesh and batch from the data files; the program's defaults otherwise."""
    traffic = ctx.traffic
    program_model = ctx.reference.program_model(ctx.config, seq_len(ctx))
    extra = program_model["extra"]
    extra.update({"tokenizer": "byte", "assume_packed": True})
    if ctx.control and "model_extra" in traffic["control"]:
        extra.update(traffic["control"]["model_extra"])  # the program's own lower-precision path
    if ctx.rehearse:
        program_model["attention"] = "dense"
        extra["loss_impl"] = "dense"
    return {
        "schema_version": 1,
        "run": {
            "name": ctx.workload.replace(".", "_"),
            "seed": int(ctx.seed % (2**31 - 1)),
            "device": "cpu" if ctx.rehearse else "tpu",
            "deterministic": False,  # shuffled sampler, seeded from --seed
        },
        "model": program_model,
        "data": {
            "name": "local_text",
            "cache_dir": str(ctx.work_dir / "datasets"),
            "extra": {
                "globs": [str(ctx.root / g) for g in traffic["data_globs"]],
                "val_fraction": 0.01,
            },
        },
        "trainer": {
            "max_steps": int(traffic["optimizer"]["max_steps"]),
            "micro_batch_size": int(traffic["micro_batch_size"]) if not ctx.rehearse else 2,
            "grad_accum_steps": int(traffic["grad_accum_steps"]) if not ctx.rehearse else 2,
            "lr": float(traffic["optimizer"]["lr"]),
            "weight_decay": float(traffic["optimizer"]["weight_decay"]),
            "warmup_steps": int(traffic["optimizer"]["warmup_steps"]),
            "max_grad_norm": float(traffic["optimizer"]["max_grad_norm"]),
            "log_every_steps": int(traffic["log_every_steps"]),
            "eval_every_steps": 10**9,
            "save_every_steps": 10**9,
        },
        "distributed": {"mesh": dict(traffic["mesh"]) if not ctx.rehearse else {"data": 1}},
        "mlflow": {"enabled": False},
        "logging": {"level": "WARNING", "json_output": False, "log_to_file": False},
        "output": {"root_dir": str(ctx.work_dir / "runs")},
    }


def _find_adam(node: Any) -> Any:
    if hasattr(node, "mu") and hasattr(node, "nu"):
        return node
    if isinstance(node, (tuple, list)):
        for child in node:
            hit = _find_adam(child)
            if hit is not None:
                return hit
    return None


def _flat_norms(tree: Any) -> dict[str, float]:
    import jax
    from flax.linen import meta as nn_meta

    flat, _ = jax.tree_util.tree_flatten_with_path(nn_meta.unbox(tree))
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v) for path, v in flat}


def leaf_gaps(got: dict[str, float], ref: dict[str, float]) -> dict[str, float]:
    """Per leaf |got - ref| over max(ref of that leaf, median ref leaf): the
    gap between the two NORMS, not the norm of a difference, against a
    floor because some gradients are all but zero."""
    floor = statistics.median(ref.values())
    return {key: abs(got[key] - r) / max(r, floor, 1e-30) for key, r in ref.items()}


def worst_leaf_gap(got: dict[str, float], ref: dict[str, float]) -> tuple[float, str]:
    gaps = leaf_gaps(got, ref)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def rms_leaf_gap(got: dict[str, float], ref: dict[str, float]) -> float:
    """Root mean square over the leaves of the same gaps: steadier from
    seed to seed than the worst leaf, which one noisy leaf decides."""
    gaps = list(leaf_gaps(got, ref).values())
    return (sum(g * g for g in gaps) / len(gaps)) ** 0.5


class Program:
    """The one object set-up builds and the window drives."""

    def __init__(self, ctx) -> None:
        import jax
        import jax.numpy as jnp
        from flax import linen as nn

        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.data.prefetch import BatchPrefetcher
        from llmtrain_tpu.data.sampler import DeterministicSampler
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.tracking.base import NullTracker
        from llmtrain_tpu.training import Trainer

        self.ctx = ctx
        self.spans: list[tuple[str, float, float]] = []
        initialize_registries()
        self.run_cfg = RunConfig.model_validate(build_run_config(ctx))
        self.trainer = trainer = Trainer(self.run_cfg, None, NullTracker())
        self.mesh_ctx = lambda: (trainer._mesh, nn.logical_axis_rules(trainer._rules))

        # Weights from --seed, made on the device in one jitted call, laid
        # out as the program shards them; the optimizer state stays zero.
        ref, model_cfg = ctx.reference, ctx.config
        boxed = trainer._state.params
        structure = jax.tree.structure(boxed)
        want = [leaf.shape for leaf in jax.tree.leaves(boxed)]

        def make(key):
            tree = ref.program_tree(ref.make_weights(model_cfg, key, jnp.float32), model_cfg)
            leaves = jax.tree.leaves(tree)
            if [leaf.shape for leaf in leaves] != want:
                raise ValueError("reference and program parameter trees differ in shape")
            return jax.tree.unflatten(structure, leaves)

        params = jax.jit(make, out_shardings=trainer._state_shardings.params)(
            ref.seed_key(ctx.seed, 1)
        )
        trainer._state = trainer._state.replace(params=params)

        self._norms = jax.jit(
            lambda t: jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t)
        )
        self._delta_norms = jax.jit(
            lambda a, b: jax.tree.map(
                lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y))), a, b
            )
        )
        self._copy = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(jnp.float32) + 0.0, t))

        train_ds = trainer._data_module.train_dataset()
        sampler = DeterministicSampler(
            num_examples=len(train_ds),
            batch_size=trainer._global_micro,
            seed=self.run_cfg.run.seed,
            shuffle=True,
        )
        self.prefetcher = BatchPrefetcher(
            lambda s: trainer._global_batch(sampler, train_ds, s),
            depth=self.run_cfg.trainer.prefetch_depth,
            start_step=1,
        )
        self.run_key = jax.random.key(self.run_cfg.run.seed)
        self.step = 0
        accum = self.run_cfg.trainer.grad_accum_steps
        self.tokens_per_step = accum * trainer._global_micro * self.run_cfg.model.block_size
        self.last_loss = None

    def one_step(self):
        """The window's own call and feed: one optimizer step, dispatched."""
        trainer = self.trainer
        self.step += 1
        t0 = time.perf_counter()
        batch = self.prefetcher.get(self.step)
        t1 = time.perf_counter()
        trainer._state, metrics = trainer._train_step_fn(trainer._state, batch, self.run_key)
        t2 = time.perf_counter()
        self.spans.append(("data_wait", t0, t1))
        self.spans.append(("host_dispatch", t1, t2))
        self.last_loss = metrics["loss"]
        return batch, metrics

    def sync(self) -> float:
        import jax

        t0 = time.perf_counter()
        loss = float(jax.device_get(self.last_loss))
        self.spans.append(("boundary_sync", t0, time.perf_counter()))
        return loss

    def first_steps(self, n: int) -> dict[str, Any]:
        """Steps 1..n with everything the comparison needs read on the way."""
        trainer = self.trainer
        p0 = self._copy(trainer._state.params)
        losses, batches, grad_norms = [], [], None
        for i in range(n):
            batch, metrics = self.one_step()
            batches.append({k: np.asarray(v) for k, v in batch.items()})
            if i == 0:
                mu = _find_adam(trainer._state.opt_state).mu
                grad_norms = {k: v / (1.0 - B1) for k, v in _flat_norms(self._norms(mu)).items()}
            losses.append(metrics["loss"])
        delta = _flat_norms(self._delta_norms(trainer._state.params, p0))
        del p0
        losses = [float(x) for x in losses]
        for batch in batches:
            mask = batch.get("attention_mask")
            if mask is None:
                batch["attention_mask"] = np.ones_like(batch["input_ids"])
            elif not (mask == 1).all():
                raise ValueError("the reference handles unmasked, unsegmented rows only")
            rows = batch["input_ids"].reshape(-1, batch["input_ids"].shape[-1])
            if len({row.tobytes() for row in rows}) != len(rows):
                raise ValueError("rows of a checked batch repeat")
        return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta, "batches": batches}

    def close(self) -> None:
        self.prefetcher.close()


def compare(got: dict, ref: dict, limits: dict) -> list[dict]:
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"]))
    grad_gap, grad_leaf = worst_leaf_gap(got["grad_norms"], ref["grad_norms"])
    delta_gap, delta_leaf = worst_leaf_gap(got["delta_norms"], ref["delta_norms"])
    return [
        {"name": "loss_rel_gap", "value": loss_gap, "limit": limits["loss_rel_gap"],
         "detail": f"program {got['losses']} reference {ref['losses']}"},
        {"name": "first_grad_norm_gap", "value": grad_gap, "limit": limits["first_grad_norm_gap"],
         "detail": grad_leaf},
        {"name": "param_delta_norm_gap", "value": delta_gap, "limit": limits["param_delta_norm_gap"],
         "detail": delta_leaf},
        {"name": "first_grad_norm_rms_gap", "value": rms_leaf_gap(got["grad_norms"], ref["grad_norms"]),
         "limit": limits["first_grad_norm_rms_gap"], "detail": "rms over leaves"},
    ]


def run_reference(ctx, first: dict, precision: str = "f32") -> dict:
    hyper = dict(ctx.traffic["optimizer"])
    n_ref = int(ctx.traffic["reference_steps"])
    rows = int(ctx.traffic["reference_rows_per_block"])
    return ctx.reference.train_reference(
        ctx.config, ctx.seed, first["batches"][:n_ref], hyper, rows_per_block=rows, precision=precision
    )


def check_only(ctx) -> dict:
    """Set-up and the first steps only: the readings a limit is set from."""
    program = Program(ctx)
    try:
        mesh, rules = program.mesh_ctx()
        with mesh, rules:
            first = program.first_steps(int(ctx.traffic["check_steps"]))
    finally:
        program.close()
    del program
    gc.collect()
    ref = run_reference(ctx, first)
    first["losses"] = first["losses"][: len(ref["losses"])]
    checks = compare(first, ref, ctx.limits)
    precision = ctx.traffic["control"].get("reference_precision")
    if ctx.control and precision:
        # The control: the reference in the program's place, one precision
        # below the configuration's, on the same batches.
        low = run_reference(ctx, first, precision)
        checks += [dict(c, name="control:" + c["name"]) for c in compare(low, ref, ctx.limits)]
    return {"checks": checks}


def run(ctx) -> dict:
    import jax

    from llmtrain_tpu.distributed import configure_compilation_cache

    configure_compilation_cache()
    traffic = ctx.traffic
    log_every = int(traffic["log_every_steps"]) if not ctx.rehearse else 2
    program = Program(ctx)
    mesh, rules = program.mesh_ctx()
    trace_info = None
    try:
        with mesh, rules:
            first = program.first_steps(int(traffic["check_steps"]))
            for _ in range(int(traffic["warm_steps"])):
                program.one_step()
            program.sync()

            # ------------------------------------------------ the window
            compiles = ctx.compile_counter()
            t_start = time.perf_counter()
            ctx.mark_window_start(t_start)
            boundaries = [t_start]
            steps_at = [program.step]
            traced_interval = None
            while time.perf_counter() - t_start < ctx.seconds:
                if ctx.trace and len(boundaries) == 2 and trace_info is None:
                    trace_info = ctx.start_trace()
                    for _ in range(int(traffic["trace_steps"])):
                        program.one_step()
                    program.sync()
                    ctx.stop_trace(trace_info)
                    traced_interval = len(boundaries) - 1
                else:
                    for _ in range(log_every):
                        program.one_step()
                    program.sync()
                boundaries.append(time.perf_counter())
                steps_at.append(program.step)
            t_end = boundaries[-1]
            compiled_in_window = compiles.stop()
        peak = ctx.memory_peak_bytes()
    finally:
        program.close()

    steps = steps_at[-1] - steps_at[0]
    window = t_end - t_start
    per_step = [
        (b1 - b0) / (s1 - s0)
        for i, (b0, b1, s0, s1) in enumerate(zip(boundaries, boundaries[1:], steps_at, steps_at[1:]))
        if i != traced_interval
    ]
    spans = [s for s in program.spans if s[1] >= t_start]
    tokens_per_step = program.tokens_per_step
    loss_impl = getattr(program.trainer._model, "loss_impl", None)
    ctx.log(
        f"train: {steps} steps of {tokens_per_step} tokens in {window:.3f}s; "
        f"loss_impl={loss_impl}; compiles in window={compiled_in_window}"
    )

    # The program's state goes before the reference comes.
    del program
    gc.collect()
    jax.clear_caches()
    ref = run_reference(ctx, first)
    first["losses"] = first["losses"][: len(ref["losses"])]
    checks = compare(first, ref, ctx.limits)

    return {
        "attempted": steps,
        "failed": 0 if compiled_in_window == 0 else steps,
        "compiles_in_window": compiled_in_window,
        "checks": checks,
        "memory_peak_bytes": peak,
        "end_to_end": {"train_tokens_per_s": steps * tokens_per_step / window},
        "records": {
            "spans": spans,
            "window": (t_start, t_end),
            "step_seconds": per_step,
            "steps": steps,
            "tokens_per_step": tokens_per_step,
            "seq_len": seq_len(ctx),
            "trace": trace_info,
        },
    }
