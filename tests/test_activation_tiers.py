"""Per-layer activation policy tiers (model.extra.activation_tiers).

The tier ladder replaces the global ``model.remat`` boolean: every
transformer block gets one of ``none | selective | full | offload``
(docs/perf.md "Activation tiers and host offload"). Covered here:

* the spec grammar — parse tables, canonicalization round-trips, and the
  full rejection catalogue (unknown tier, overlap, inversion, range);
* jaxpr evidence that the ladder pins remat boundaries per layer (N
  ``remat`` equations for N rematerialized layers, zero for all-none);
* bitwise forward parity — tiers change what is recomputed, never the
  math;
* the ``model.remat: true`` deprecation shim and the remat/tiers
  conflict, at both the schema and the adapter layer;
* the planner's per-tier HBM model: monotone none > full > offload
  ladders, host-offload bytes tracked outside the device total, and the
  fits/doesn't-fit ordering of a cap between two ladders' predictions;
* candidate enumeration producing tier-ladder candidates with the
  ``|act=`` key suffix (and pre-tier keys byte-identical to before);
* ``@pytest.mark.slow``: real Trainer fits under a ladder (CPU
  pinned_host fallback warning, mem/activation_bytes gauges) and the
  checkpoint/elastic-resume contract with tiers CHANGED between save
  and resume (tiers are resume-mutable, like loss_impl).
  ``make verify-offload`` runs everything including the slow fits.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmtrain_tpu.config import RunConfig
from llmtrain_tpu.config.activation_tiers import (
    TIERS,
    canonical_tier_spec,
    parse_activation_tiers,
)
from llmtrain_tpu.models.gpt import GPT
from llmtrain_tpu.registry import initialize_registries
from llmtrain_tpu.tracking import NullTracker


@pytest.fixture(autouse=True)
def _registries():
    initialize_registries()


VOCAB = 64
BLOCK = 16


def _tiny_gpt(**overrides):
    kwargs = dict(
        vocab_size=VOCAB,
        block_size=BLOCK,
        d_model=32,
        n_layers=2,
        n_heads=4,
        d_ff=64,
        dropout=0.0,
    )
    kwargs.update(overrides)
    return GPT(**kwargs)


def _run_cfg(n_layers=2, model_extra=None, remat=False, **sections):
    base = {
        "run": {"name": "tiers", "seed": 3, "device": "cpu"},
        "model": {
            "name": "gpt",
            "block_size": 8,
            "vocab_size": 32,
            "dropout": 0.0,
            "d_model": 32,
            "n_heads": 4,
            "d_ff": 64,
            "n_layers": n_layers,
            "remat": remat,
            "extra": {**(model_extra or {})},
        },
        "data": {"name": "dummy_text"},
        "trainer": {
            "max_steps": 6,
            "micro_batch_size": 2,
            "grad_accum_steps": 1,
            "lr": 3e-3,
            "warmup_steps": 0,
            "log_every_steps": 3,
            "eval_every_steps": 100,
            "save_every_steps": 100,
        },
        "mlflow": {"enabled": False},
    }
    for section, values in sections.items():
        base[section] = {**base.get(section, {}), **values}
    return RunConfig.model_validate(base)


# --------------------------------------------------------------------------
# Spec grammar
# --------------------------------------------------------------------------


class TestParseTable:
    @pytest.mark.parametrize(
        ("spec", "n_layers", "expected"),
        [
            ("none:*", 3, ("none", "none", "none")),
            ("full:*", 2, ("full", "full")),
            ("offload:*", 1, ("offload",)),
            ("selective:1", 3, ("none", "selective", "none")),
            ("full:0-1", 4, ("full", "full", "none", "none")),
            (
                "offload:0-1,full:2-3",
                4,
                ("offload", "offload", "full", "full"),
            ),
            # Out-of-order entries and single-layer ranges are fine.
            ("full:3,offload:0-2", 4, ("offload", "offload", "offload", "full")),
            # Unassigned layers default to none (cheapest tier).
            ("full:1", 3, ("none", "full", "none")),
        ],
    )
    def test_parse(self, spec, n_layers, expected):
        assert parse_activation_tiers(spec, n_layers) == expected

    @pytest.mark.parametrize(
        "spec",
        [
            "",  # empty
            "turbo:*",  # unknown tier
            "full",  # missing range
            "full:",  # empty range
            "full:a-b",  # non-numeric
            "full:3-1",  # inverted
            "full:0-9",  # out of range for n_layers=2
            "full:2",  # out of range (0-based)
            "full:0,none:0",  # overlap
            "full:0-1,offload:1",  # overlap via range
            "full:*,none:0",  # * must be the sole entry
            "full:-1",  # negative
        ],
    )
    def test_rejects(self, spec):
        with pytest.raises(ValueError):
            parse_activation_tiers(spec, 2)

    def test_canonical_round_trip(self):
        for spec, n_layers in [
            ("none:*", 4),
            ("full:*", 4),
            ("offload:0-1,full:2-3", 4),
            ("selective:1,full:2-3", 4),
        ]:
            tiers = parse_activation_tiers(spec, n_layers)
            canon = canonical_tier_spec(tiers)
            assert parse_activation_tiers(canon, n_layers) == tiers

    def test_canonical_compresses_runs(self):
        assert canonical_tier_spec(("full", "full", "full")) == "full:*"
        assert (
            canonical_tier_spec(("offload", "full", "full", "none"))
            == "offload:0,full:1-2,none:3"
        )

    def test_tier_names_are_stable(self):
        # The config surface: renaming a tier is a breaking change.
        assert TIERS == ("none", "selective", "full", "offload")


# --------------------------------------------------------------------------
# Remat boundaries in the jaxpr + forward parity
# --------------------------------------------------------------------------


def _remat_eqn_count(model, params, tokens) -> int:
    def loss(p):
        logits = model.apply({"params": p}, tokens, deterministic=True)
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    return sum(
        1 for eqn in jaxpr.jaxpr.eqns if "remat" in eqn.primitive.name
    )


class TestJaxprBoundaries:
    """The ladder must be visible in the lowered program: one remat scope
    per rematerialized layer, none for ``none`` layers."""

    def _params(self, model):
        from flax.linen import meta as nn_meta

        ids = jnp.zeros((1, BLOCK), jnp.int32)
        return nn_meta.unbox(
            model.init(jax.random.key(0), ids, deterministic=True)
        )["params"]

    def test_counts_per_ladder(self):
        base = _tiny_gpt()
        params = self._params(base)
        tokens = jnp.asarray(
            np.random.default_rng(5).integers(0, VOCAB, (2, BLOCK)), jnp.int32
        )
        cases = {
            ("none", "none"): 0,
            ("full", "full"): 2,
            ("full", "none"): 1,
            ("selective", "selective"): 2,
        }
        for tiers, expected in cases.items():
            model = _tiny_gpt(activation_tiers=tiers)
            assert _remat_eqn_count(model, params, tokens) == expected, tiers

    def test_offload_ladder_traces_and_pins_boundaries(self):
        """On this CPU container offload degrades to full remat (no
        pinned_host memory space) BEFORE reaching the model, so exercise
        the resolver path end to end via the adapter."""
        from llmtrain_tpu.models.gpt import resolve_config_activation_tiers

        cfg = _run_cfg(model_extra={"activation_tiers": "offload:0,full:1"})
        tiers = resolve_config_activation_tiers(cfg)
        assert tiers is not None and len(tiers) == 2
        assert all(t in ("full", "offload") for t in tiers)
        model = _tiny_gpt(activation_tiers=tiers)
        params = self._params(_tiny_gpt())
        tokens = jnp.zeros((1, BLOCK), jnp.int32)
        assert _remat_eqn_count(model, params, tokens) == 2

    def test_forward_bitwise_parity_across_ladders(self):
        """Tiers only change what the BACKWARD pass recomputes; forward
        logits must be bit-identical across every ladder."""
        base = _tiny_gpt()
        params = self._params(base)
        tokens = jnp.asarray(
            np.random.default_rng(9).integers(0, VOCAB, (2, BLOCK)), jnp.int32
        )
        ref = np.asarray(base.apply({"params": params}, tokens, deterministic=True))
        for tiers in [
            ("full", "full"),
            ("selective", "none"),
            ("full", "selective"),
        ]:
            got = np.asarray(
                _tiny_gpt(activation_tiers=tiers).apply(
                    {"params": params}, tokens, deterministic=True
                )
            )
            assert (ref == got).all(), tiers

    def test_grads_flow_and_are_close(self):
        """Gradients under any ladder stay finite and match the no-remat
        baseline to fp noise (remat may reassociate reductions, so this is
        allclose, not bitwise — only the forward is held bitwise)."""
        base = _tiny_gpt()
        params = self._params(base)
        tokens = jnp.asarray(
            np.random.default_rng(2).integers(0, VOCAB, (2, BLOCK)), jnp.int32
        )

        def grads_of(model):
            def loss(p):
                logits = model.apply({"params": p}, tokens, deterministic=True)
                return jnp.mean(logits.astype(jnp.float32) ** 2)

            return jax.grad(loss)(params)

        g_ref = grads_of(base)
        g_tiered = grads_of(_tiny_gpt(activation_tiers=("full", "selective")))
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_tiered)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# --------------------------------------------------------------------------
# Config surface: shim, conflicts, strict validation
# --------------------------------------------------------------------------


class TestConfigResolution:
    def test_tiers_spec_wins(self):
        from llmtrain_tpu.models.gpt import resolve_config_activation_tiers

        cfg = _run_cfg(n_layers=4, model_extra={"activation_tiers": "full:0-1"})
        assert resolve_config_activation_tiers(cfg) == (
            "full",
            "full",
            "none",
            "none",
        )

    def test_no_remat_no_tiers_is_none(self):
        from llmtrain_tpu.models.gpt import resolve_config_activation_tiers

        assert resolve_config_activation_tiers(_run_cfg()) is None

    def test_remat_true_migrates_to_full_star(self, caplog):
        """Deprecation shim: model.remat true (default policy) maps to
        ``full:*`` with a one-time INFO."""
        import llmtrain_tpu.models.gpt as gpt_mod

        gpt_mod._TIER_MIGRATION_LOGGED = False
        cfg = _run_cfg(remat=True)
        with caplog.at_level(logging.INFO):
            assert gpt_mod.resolve_config_activation_tiers(cfg) == ("full", "full")
            gpt_mod.resolve_config_activation_tiers(cfg)
        msgs = [r for r in caplog.records if "deprecated" in r.getMessage()]
        assert len(msgs) == 1  # once per process, not per call

    def test_remat_dots_migrates_to_selective(self):
        import llmtrain_tpu.models.gpt as gpt_mod

        cfg = _run_cfg(remat=True, model_extra={"remat_policy": "dots"})
        assert gpt_mod.resolve_config_activation_tiers(cfg) == (
            "selective",
            "selective",
        )

    def test_remat_dots_no_batch_stays_legacy(self):
        """dots_no_batch has no tier equivalent; the legacy remat path
        keeps handling it (returns None -> model uses remat/remat_policy)."""
        from llmtrain_tpu.models.gpt import resolve_config_activation_tiers

        cfg = _run_cfg(remat=True, model_extra={"remat_policy": "dots_no_batch"})
        assert resolve_config_activation_tiers(cfg) is None

    def test_schema_rejects_bad_spec(self):
        with pytest.raises(ValueError, match="activation_tiers"):
            _run_cfg(model_extra={"activation_tiers": "turbo:*"})

    def test_schema_rejects_out_of_range(self):
        with pytest.raises(ValueError, match="activation_tiers"):
            _run_cfg(n_layers=2, model_extra={"activation_tiers": "full:0-7"})

    def test_schema_rejects_remat_conflict(self):
        with pytest.raises(ValueError, match="conflict"):
            _run_cfg(remat=True, model_extra={"activation_tiers": "full:*"})

    def test_offload_spec_is_not_a_config_error_without_pinned_host(self):
        """Missing pinned_host is a RUNTIME downgrade (offload -> full with
        a warning), never a config validation failure — the same YAML must
        validate on a laptop and run offloaded on a TPU host."""
        cfg = _run_cfg(model_extra={"activation_tiers": "offload:*"})
        assert cfg.model.extra["activation_tiers"] == "offload:*"

    def test_runtime_fallback_warns_once(self, caplog):
        from llmtrain_tpu.models import activation_policy

        activation_policy._FALLBACK_WARNED.clear()
        with caplog.at_level(logging.WARNING):
            out1 = activation_policy.resolve_activation_tiers(("offload", "full"))
            out2 = activation_policy.resolve_activation_tiers(("offload", "none"))
        if activation_policy.offload_supported():  # pragma: no cover - TPU host
            assert out1 == ("offload", "full")
            return
        assert out1 == ("full", "full")
        assert out2 == ("full", "none")
        warned = [r for r in caplog.records if "pinned_host" in r.getMessage()]
        assert len(warned) == 1  # once per process, not per resolve

    def test_adapter_builds_tiered_model(self):
        from llmtrain_tpu.models.gpt import GPTAdapter

        cfg = _run_cfg(n_layers=2, model_extra={"activation_tiers": "full:0"})
        model = GPTAdapter().build_model(cfg)
        assert model.activation_tiers == ("full", "none")


# --------------------------------------------------------------------------
# Planner HBM model + candidate enumeration
# --------------------------------------------------------------------------


class TestHbmModel:
    def _hbm(self, cfg, devices=4):
        from llmtrain_tpu.autotune.plan import plan_from_config, predict_hbm_bytes
        from llmtrain_tpu.models.gpt import GPTAdapter

        plan = plan_from_config(cfg, devices, adapter=GPTAdapter())
        return predict_hbm_bytes(
            plan,
            n_params=1_000_000,
            d_model=cfg.model.d_model,
            n_layers=cfg.model.n_layers,
            vocab_size=int(cfg.model.vocab_size),
            block_size=cfg.model.block_size,
        )

    def test_ladder_monotonicity(self):
        """The reason tiers exist: none > selective > full >= offload
        ladder in device-resident activation bytes; offload alone parks
        bytes in host RAM."""
        n = {"activation_tiers": "none:*"}
        s = {"activation_tiers": "selective:*"}
        f = {"activation_tiers": "full:*"}
        o = {"activation_tiers": "offload:0,full:1"}
        h_n = self._hbm(_run_cfg(model_extra=n))
        h_s = self._hbm(_run_cfg(model_extra=s))
        h_f = self._hbm(_run_cfg(model_extra=f))
        h_o = self._hbm(_run_cfg(model_extra=o))
        assert h_n["activation_bytes"] > h_s["activation_bytes"]
        assert h_s["activation_bytes"] > h_f["activation_bytes"]
        assert h_o["activation_bytes"] < h_f["activation_bytes"]
        assert h_n["total_bytes"] > h_f["total_bytes"] > h_o["total_bytes"]
        # Host bytes appear ONLY under offload, and never in the total.
        assert h_n["activation_host_bytes"] == 0
        assert h_f["activation_host_bytes"] == 0
        assert h_o["activation_host_bytes"] > 0
        parts = (
            h_o["params_bytes"]
            + h_o["grads_bytes"]
            + h_o["opt_state_bytes"]
            + h_o["activation_bytes"]
            + h_o["logits_bytes"]
        )
        assert h_o["total_bytes"] == pytest.approx(parts, abs=2)

    def test_per_tier_breakdown_keys(self):
        hbm = self._hbm(
            _run_cfg(n_layers=4, model_extra={"activation_tiers": "offload:0-1,full:2-3"})
        )
        assert set(hbm["activation_bytes_by_tier"]) == {"offload", "full"}
        assert sum(hbm["activation_bytes_by_tier"].values()) == pytest.approx(
            hbm["activation_bytes"], abs=2
        )

    def test_cap_ordering_matches_bench_scenario(self):
        """A cap at the midpoint of the two predictions: the tiered ladder
        fits where all-`none` does not, which is the ordering `llmtrain
        plan` reports against a device's HBM limit."""
        h_none = self._hbm(_run_cfg(model_extra={"activation_tiers": "none:*"}))
        h_tier = self._hbm(
            _run_cfg(model_extra={"activation_tiers": "offload:0,full:1"})
        )
        cap = (h_none["total_bytes"] + h_tier["total_bytes"]) // 2
        assert not h_none["total_bytes"] <= cap  # all-none does NOT fit
        assert h_tier["total_bytes"] <= cap  # the ladder fits

    def test_plan_cli_fits_verdict_for_both_configs(self, tmp_path, capsys):
        """`llmtrain plan` itself (not just the HBM model it wraps) must
        call fits/doesn't-fit correctly under a cap between the all-none
        and tiered predictions: exit 2 + feasible=false for all-none,
        exit 0 + feasible=true for the ladder."""
        import argparse
        import json

        import yaml

        from llmtrain_tpu.cli import _handle_plan

        def plan_rc(extra, cap, tag):
            cfg = _run_cfg(model_extra=extra)
            data = cfg.model_dump(mode="json", exclude_none=True)
            if cap is not None:
                data.setdefault("tune", {})["hbm_limit_bytes"] = float(cap)
            path = tmp_path / f"{tag}.yaml"
            path.write_text(yaml.safe_dump(data, sort_keys=False))
            rc = _handle_plan(
                argparse.Namespace(config=str(path), devices=1, json=True)
            )
            payload = json.loads(capsys.readouterr().out)
            return rc, payload

        _, none_free = plan_rc({"activation_tiers": "none:*"}, None, "n0")
        _, tier_free = plan_rc(
            {"activation_tiers": "offload:0,full:1"}, None, "t0"
        )
        cap = (
            none_free["predicted_hbm"]["total_bytes"]
            + tier_free["predicted_hbm"]["total_bytes"]
        ) / 2
        rc_none, p_none = plan_rc({"activation_tiers": "none:*"}, cap, "n1")
        rc_tier, p_tier = plan_rc(
            {"activation_tiers": "offload:0,full:1"}, cap, "t1"
        )
        assert rc_none == 2 and p_none["feasible"] is False
        assert rc_tier == 0 and p_tier["feasible"] is True

    def test_bad_spec_raises_mesh_plan_error(self):
        from llmtrain_tpu.autotune.plan import (
            MeshPlanError,
            ModelCaps,
            resolve_plan,
        )

        with pytest.raises(MeshPlanError, match="activation_tiers"):
            resolve_plan(
                mesh_sizes={"data": 4},
                device_count=4,
                micro_batch_size=2,
                caps=ModelCaps(n_heads=4, block_size=8, n_layers=2),
                activation_tiers="full:0-7",
            )

    def test_remat_conflict_raises(self):
        from llmtrain_tpu.autotune.plan import (
            MeshPlanError,
            ModelCaps,
            resolve_plan,
        )

        with pytest.raises(MeshPlanError, match="remat"):
            resolve_plan(
                mesh_sizes={"data": 4},
                device_count=4,
                micro_batch_size=2,
                caps=ModelCaps(n_heads=4, block_size=8, n_layers=2),
                remat=True,
                activation_tiers="full:*",
            )

    def test_key_suffix_only_when_tiers_set(self):
        from llmtrain_tpu.autotune.plan import plan_from_config
        from llmtrain_tpu.models.gpt import GPTAdapter

        plain = plan_from_config(_run_cfg(), 4, adapter=GPTAdapter())
        assert "act=" not in plain.key()  # pre-tier keys stay byte-stable
        tiered = plan_from_config(
            _run_cfg(model_extra={"activation_tiers": "offload:0,full:1"}),
            4,
            adapter=GPTAdapter(),
        )
        assert tiered.key().endswith("|act=offload:0,full:1")


class TestSearchLadders:
    def test_enumeration_includes_offload_ladder(self):
        from llmtrain_tpu.autotune.search import enumerate_candidates

        cands = enumerate_candidates(
            _run_cfg(n_layers=4),
            8,
            seed=0,
            microbatch_candidates=[2],
            search_mesh=False,
            search_remat=True,
            search_zero=False,
        )
        specs = {c.activation_tiers for c in cands}
        assert "" in specs  # the legacy remat on/off axis is still there
        assert any("offload:" in s for s in specs)
        ladder = next(s for s in specs if "offload:" in s)
        keyed = [c for c in cands if c.activation_tiers == ladder]
        assert all(c.key().endswith(f"|act={ladder}") for c in keyed)

    def test_base_spec_carried_through_all_candidates(self):
        """When the base config already runs a ladder, every enumerated
        candidate carries an EXPLICIT spec — a tier-less override merged
        over the base would silently inherit the base ladder under a
        misleading key."""
        from llmtrain_tpu.autotune.search import enumerate_candidates

        cfg = _run_cfg(n_layers=4, model_extra={"activation_tiers": "full:0-1"})
        cands = enumerate_candidates(
            cfg,
            8,
            seed=0,
            microbatch_candidates=[2],
            search_mesh=False,
            search_remat=True,
            search_zero=False,
        )
        assert all(c.activation_tiers for c in cands)
        assert any(c.activation_tiers == "full:0-1,none:2-3" for c in cands)

    def test_plan_overrides_round_trip(self):
        """config_overrides() of a tiered plan re-validates and resolves to
        the same ladder (the tune emit path)."""
        from llmtrain_tpu.autotune.plan import plan_from_config
        from llmtrain_tpu.models.gpt import GPTAdapter
        from llmtrain_tpu.resilience.harness import deep_merge

        cfg = _run_cfg(model_extra={"activation_tiers": "offload:0,full:1"})
        plan = plan_from_config(cfg, 4, adapter=GPTAdapter())
        merged = deep_merge(
            cfg.model_dump(exclude_none=True), plan.config_overrides()
        )
        cfg2 = RunConfig.model_validate(merged)
        assert cfg2.model.extra["activation_tiers"] == "offload:0,full:1"
        assert cfg2.model.remat is False


# --------------------------------------------------------------------------
# Slow: real fits under a ladder + resume with tiers changed
# --------------------------------------------------------------------------


@pytest.mark.slow
class TestTieredFits:
    def test_offload_ladder_fits_and_publishes_gauges(self, caplog):
        """End-to-end: a Trainer fit under an offload-bottom ladder on this
        CPU container (a) downgrades offload -> full with the one-time
        warning, (b) trains to a finite decreasing loss, (c) publishes the
        mem/activation_bytes{,_offloaded} gauges into the memory block."""
        from llmtrain_tpu.models import activation_policy
        from llmtrain_tpu.training import Trainer

        activation_policy._FALLBACK_WARNED.clear()
        cfg = _run_cfg(model_extra={"activation_tiers": "offload:0,full:1"})
        with caplog.at_level(logging.WARNING):
            trainer = Trainer(cfg, None, NullTracker(), None)
            res = trainer.fit()
        if not activation_policy.offload_supported():
            assert any("pinned_host" in r.getMessage() for r in caplog.records)
        assert np.isfinite(res.final_loss)
        assert res.final_loss < res.first_step_loss
        latest = trainer._telemetry.metrics.latest()
        assert latest["mem/activation_bytes"][0] > 0
        assert latest["mem/activation_bytes_offloaded"][0] > 0
        monitor = trainer._telemetry.memory
        assert monitor is not None
        peaks = monitor.peaks()
        assert peaks["activation_bytes"] == latest["mem/activation_bytes"][0]

    def test_loss_bitwise_parity_tiered_vs_none_first_step(self):
        """The bench offload scenario's bitwise claim, pinned as a test:
        step-1 loss (pure forward on identical init) is bit-identical
        between all-none and the ladder."""
        from llmtrain_tpu.training import Trainer

        runs = {}
        for name, extra in [
            ("none", {"activation_tiers": "none:*"}),
            ("ladder", {"activation_tiers": "offload:0,full:1"}),
        ]:
            cfg = _run_cfg(model_extra=extra, trainer={"max_steps": 2})
            runs[name] = Trainer(cfg, None, NullTracker(), None).fit()
        assert runs["none"].first_step_loss == runs["ladder"].first_step_loss

    def test_resume_with_tiers_changed(self, tmp_path):
        """Tiers are resume-mutable (like loss_impl): params/opt_state are
        tier-independent, so a checkpoint saved under ``full:*`` resumes
        under ``none:*`` (and vice versa) with only the config-mismatch
        warning."""
        from llmtrain_tpu.training import Trainer

        cfg_a = _run_cfg(
            model_extra={"activation_tiers": "full:*"},
            trainer={"max_steps": 6, "save_every_steps": 3},
        )
        run_a = tmp_path / "save"
        run_a.mkdir()
        Trainer(cfg_a, run_a, NullTracker(), None).fit(max_steps_override=3)

        cfg_b = _run_cfg(
            model_extra={"activation_tiers": "none:*"},
            trainer={"max_steps": 6, "save_every_steps": 3},
        )
        res = Trainer(cfg_b, None, NullTracker(), None).fit(
            resume_from=str(run_a / "checkpoints" / "step_000003.ckpt")
        )
        assert res.resumed_from_step == 3
        assert res.final_step == 6
        assert np.isfinite(res.final_loss)

    def test_elastic_resume_with_tiers_changed(self, tmp_path):
        """Elastic world-size change AND a tier-ladder change in the same
        resume: save on an emulated 2-device data mesh under ``full:*``,
        resume on 1 device (global micro-batch preserved, 2x2 -> 4x1)
        under the offload ladder."""
        import jax as _jax

        from llmtrain_tpu.training import Trainer

        all_cpu = _jax.devices("cpu")
        if len(all_cpu) < 2:
            pytest.skip("needs >= 2 emulated devices")

        # Topology-independent dataset (test_elastic.py corpus pattern:
        # local_text sizes itself from the file, dummy_text from the
        # batch topology).
        corpus = tmp_path / "corpus.txt"
        corpus.write_text("the quick brown fox jumps over the lazy dog. " * 200)

        def cfg_for(tiers, micro, mesh):
            return _run_cfg(
                model_extra={"activation_tiers": tiers, "tokenizer": "byte"},
                model={"vocab_size": 256},
                data={
                    "name": "local_text",
                    "cache_dir": str(tmp_path / "cache"),
                    "extra": {"globs": [str(corpus)], "val_fraction": 0.1},
                },
                trainer={"max_steps": 6, "save_every_steps": 3,
                         "micro_batch_size": micro},
                distributed={"mesh": mesh},
            )

        real = _jax.devices
        _jax.devices = lambda *a, **k: all_cpu[:2]
        try:
            run_a = tmp_path / "ws2"
            run_a.mkdir()
            Trainer(
                cfg_for("full:*", 2, {"data": 2}), run_a, NullTracker(), None
            ).fit(max_steps_override=3)
        finally:
            _jax.devices = real

        _jax.devices = lambda *a, **k: all_cpu[:1]
        try:
            res = Trainer(
                cfg_for("offload:0,full:1", 4, {"data": 1}),
                None,
                NullTracker(),
                None,
            ).fit(resume_from=str(run_a / "checkpoints" / "step_000003.ckpt"))
        finally:
            _jax.devices = real
        assert res.resumed_from_step == 3
        assert np.isfinite(res.final_loss)
