"""Pipelined G/D dispatch (ISSUE 7): GDPipeline fill/drain lifecycle units,
the drain-before-restore rollback hook, the stage-program warmup plan, and
the trainer-level contracts — fused-mode parity (the default dispatch
stream and event values are untouched by the pipeline code), state-tree
invariance across modes (a checkpoint from either mode restores in the
other), and the flight recorder's pipeline phase tag."""

import json
import os

import jax
import pytest

from dcgan_tpu.config import ModelConfig, TrainConfig
from dcgan_tpu.train.gd_pipeline import GDPipeline
from dcgan_tpu.train.rollback import RollbackManager


class _Buf:
    """Stands in for a device-resident fake stack; records its release."""

    def __init__(self, tag):
        self.tag = tag
        self.deleted = False

    def delete(self):
        self.deleted = True


class StubPT:
    """Records the stage-dispatch stream the buffer manager drives."""

    def __init__(self, name="pt"):
        self.name = name
        self.calls = []
        self._n = 0

    def gen_fakes(self, state, key):
        self._n += 1
        buf = _Buf(f"{self.name}-fill{self._n}")
        self.calls.append(("gen_fakes", buf.tag))
        return buf

    def d_update(self, state, images, fakes, key):
        self.calls.append(("d_update", fakes.tag))
        return state, {"d_loss": 0.5}

    def g_update(self, state, key):
        self._n += 1
        buf = _Buf(f"{self.name}-g{self._n}")
        self.calls.append(("g_update", buf.tag))
        return state, buf, {"g_loss": 0.25}


def _key():
    return jax.random.key(0)


class TestGDPipelineLifecycle:
    def test_first_step_fills_then_steady_state_consumes(self):
        """Run start: step 1 dispatches the gen_fakes fill; every later
        step's d_update consumes exactly the stack the PREVIOUS g_update
        produced (staleness 1), with no further fills."""
        pipe, pt = GDPipeline(), StubPT()
        state = {}
        for _ in range(3):
            state, metrics = pipe.step(pt, state, None, _key())
        assert metrics == {"d_loss": 0.5, "g_loss": 0.25}
        assert pipe.fills == 1 and pipe.steps == 3
        consumed = [tag for op, tag in pt.calls if op == "d_update"]
        # step 1 eats the fill; steps 2-3 eat g_update's previous output
        assert consumed == ["pt-fill1", "pt-g2", "pt-g3"]

    def test_checkpoint_boundary_keeps_buffer(self):
        """The buffer lives OUTSIDE the checkpoint pytree: an in-run save
        touches nothing here, so steps around a boundary keep the
        staleness-1 chain with zero extra fills."""
        pipe, pt = GDPipeline(), StubPT()
        state = {}
        state, _ = pipe.step(pt, state, None, _key())
        # <- a periodic checkpoint save happens here: no pipeline API call
        state, _ = pipe.step(pt, state, None, _key())
        assert pipe.fills == 1 and pipe.drains == 0
        assert pipe.primed  # the in-flight stack survived the boundary

    def test_drain_releases_buffer_and_next_step_refills(self):
        """Rollback invalidation: drain drops AND releases the in-flight
        stack; the next step fills again from the (restored) state."""
        pipe, pt = GDPipeline(), StubPT()
        state, _ = pipe.step(pt, {}, None, _key())
        held = next(tag for op, tag in pt.calls if op == "g_update")
        assert pipe.drain("rollback") is True
        assert not pipe.primed and pipe.drains == 1
        assert pipe.last_phase == "drain"
        assert pipe.last_drain_reason == "rollback"
        state, _ = pipe.step(pt, state, None, _key())
        assert pipe.fills == 2
        assert pipe.last_phase == "fill"
        consumed = [tag for op, tag in pt.calls if op == "d_update"]
        refill = [tag for op, tag in pt.calls if op == "gen_fakes"][-1]
        assert consumed[-1] == refill       # never the drained stack
        assert consumed[-1] != held

    def test_drain_calls_device_release(self):
        pipe, pt = GDPipeline(), StubPT()
        pipe.step(pt, {}, None, _key())
        buf = pipe._buf
        pipe.drain("coordinated-stop")
        assert buf.deleted, "drain must release the device buffer"

    def test_drain_on_empty_buffer_is_noop(self):
        """A rollback before the first fill (or a double drain) is free."""
        pipe = GDPipeline()
        assert pipe.drain("rollback") is False
        assert pipe.drains == 0
        pt = StubPT()
        pipe.step(pt, {}, None, _key())
        assert pipe.drain("stop") is True
        assert pipe.drain("stop") is False
        assert pipe.drains == 1

    def test_phase_tags_follow_the_lifecycle(self):
        pipe, pt = GDPipeline(), StubPT()
        assert pipe.last_phase == ""
        pipe.step(pt, {}, None, _key())
        assert pipe.last_phase == "fill"
        pipe.step(pt, {}, None, _key())
        assert pipe.last_phase == "steady"
        pipe.drain("x")
        assert pipe.last_phase == "drain"

    def test_refill_uses_the_current_surface(self):
        """The LR-backoff rollback swaps ParallelTrain surfaces; the
        refill after the swap must dispatch the NEW surface's programs —
        pt binds per call, not at construction."""
        pipe, old, new = GDPipeline(), StubPT("old"), StubPT("new")
        pipe.step(old, {}, None, _key())
        pipe.drain("rollback")
        pipe.step(new, {}, None, _key())
        assert ("gen_fakes", "new-fill1") in new.calls
        consumed = [tag for op, tag in new.calls if op == "d_update"]
        assert consumed == ["new-fill1"]


class TestRollbackDrainHook:
    def _armed(self):
        m = RollbackManager(every=1, max_rollbacks=1)
        m.snapshot(2, {"w": jax.numpy.ones((2,))})
        return m

    def test_on_restore_fires_once_per_consumed_rollback(self):
        m = self._armed()
        drained = []
        m.on_restore = lambda: drained.append(True)
        state, step = m.restore(FloatingPointError("nan at step 3"))
        assert step == 2 and drained == [True]

    def test_on_restore_skipped_when_budget_exhausted(self):
        """An exhausted budget aborts — nothing restores, so the drain
        hook must NOT fire (ordering: after the budget check)."""
        from dcgan_tpu.train.rollback import RollbackExhausted

        m = RollbackManager(every=1, max_rollbacks=0)
        m.snapshot(2, {"w": jax.numpy.ones((2,))})
        drained = []
        m.on_restore = lambda: drained.append(True)
        with pytest.raises(RollbackExhausted):
            m.restore(FloatingPointError("nan"))
        assert drained == []


class TestConfigValidation:
    def _cfg(self, **kw):
        return TrainConfig(model=ModelConfig(output_size=16, gf_dim=8,
                                             df_dim=8), batch_size=16, **kw)

    def test_requires_sequential_update_mode(self):
        with pytest.raises(ValueError, match="sequential"):
            self._cfg(pipeline_gd=True, update_mode="fused")

    def test_rejects_conditional_models(self):
        with pytest.raises(ValueError, match="unconditional"):
            TrainConfig(model=ModelConfig(output_size=16, gf_dim=8,
                                          df_dim=8, num_classes=10),
                        batch_size=16, pipeline_gd=True)

    def test_rejects_multi_step_dispatch(self):
        with pytest.raises(ValueError, match="steps_per_call"):
            self._cfg(pipeline_gd=True, steps_per_call=4)


class TestWarmupPlanStages:
    """--aot_warmup must pre-build exactly what the pipelined loop
    dispatches: the three stage programs instead of the fused step, and
    the LR-backoff prebuild must cover the LR-dependent stages."""

    def _plan_names(self, **kw):
        from dcgan_tpu.parallel import make_mesh, make_parallel_train
        from dcgan_tpu.train import warmup

        cfg = TrainConfig(model=ModelConfig(output_size=16, gf_dim=8,
                                            df_dim=8,
                                            compute_dtype="float32"),
                          batch_size=16, **kw)
        pt = make_parallel_train(cfg, make_mesh(cfg.mesh))
        state = pt.init(jax.random.key(0))
        plan, pt_backoff = warmup.build_warmup_plan(
            cfg, pt, state,
            make_backoff_pt=lambda c: make_parallel_train(c, pt.mesh))
        return [name for name, _, _ in plan], pt_backoff

    def test_pipelined_plan_covers_the_stage_programs(self):
        names, _ = self._plan_names(pipeline_gd=True)
        assert {"gen_fakes", "d_update", "g_update"} <= set(names)
        # the loop never dispatches the fused program under --pipeline_gd
        assert "train_step" not in names

    def test_fused_plan_unchanged(self):
        names, _ = self._plan_names()
        assert "train_step" in names
        assert not any(n.startswith(("gen_fakes", "d_update", "g_update"))
                       for n in names)

    def test_backoff_prebuild_covers_lr_dependent_stages(self):
        names, pt_backoff = self._plan_names(
            pipeline_gd=True, nan_policy="rollback",
            rollback_snapshot_steps=2, rollback_lr_backoff=0.5)
        assert pt_backoff is not None
        assert "d_update@lr_backoff" in names
        assert "g_update@lr_backoff" in names
        # gen_fakes is LR-independent (no optimizer constants): identical
        # HLO to the base program, so it is deliberately NOT re-planned
        assert "gen_fakes@lr_backoff" not in names


class TestShardMapStagesTrace:
    """The shard_map backend's pipelined stage programs must trace: the
    critic-scan metric carry is cast data-axis-varying
    (steps.py::_zero_metric, `lax.pcast`) to match the loop body's
    per-device outputs. Once a latent crash (ISSUE 11 triage) — the tier-1
    suite never lowered these programs on this backend, and the semantic
    analyzer's first enumeration could not even complete."""

    def test_shard_map_pipeline_stages_trace(self):
        import jax.numpy as jnp

        from dcgan_tpu.parallel import make_mesh, make_parallel_train
        from dcgan_tpu.train import warmup

        cfg = TrainConfig(model=ModelConfig(output_size=16, gf_dim=8,
                                            df_dim=8,
                                            compute_dtype="float32"),
                          batch_size=8, backend="shard_map",
                          pipeline_gd=True)
        pt = make_parallel_train(cfg, make_mesh(cfg.mesh))
        state = warmup.state_example(pt)
        img = jax.ShapeDtypeStruct(
            (8, 16, 16, cfg.model.c_dim), jnp.float32)
        fakes = jax.ShapeDtypeStruct(
            (cfg.n_critic, 8, 16, 16, cfg.model.c_dim), jnp.float32)
        key = jax.random.key(0)
        # tracing is the regression surface: pcast raised AttributeError
        # inside the d_update critic scan before any compile
        d = pt.d_update.trace(state, img, fakes, key)
        g = pt.g_update.trace(state, key)
        assert d.jaxpr is not None and g.jaxpr is not None


@pytest.mark.slow
class TestTrainerPipelineContracts:
    """Trainer-level contracts on the real loop (CPU): fused parity,
    state-tree invariance across modes, and the flight recorder tag."""

    def _cfg(self, tmp_path, **kw):
        base = dict(
            model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                              compute_dtype="float32"),
            batch_size=16,
            checkpoint_dir=str(tmp_path / "ckpt"),
            sample_dir=str(tmp_path / "samples"),
            sample_every_steps=0,
            save_summaries_secs=0.0,
            save_model_secs=1e9,
            log_every_steps=0)
        base.update(kw)
        return TrainConfig(**base)

    def _events(self, tmp_path):
        with open(tmp_path / "ckpt" / "events.jsonl") as f:
            return [json.loads(line) for line in f]

    def test_pipelined_scalar_keys_match_fused(self, tmp_path):
        """The pipelined metric row is the fused row's exact key set —
        d_update's discriminator half merged with g_update's g_loss; no
        keys lost, none invented. (Values legitimately differ: staleness-1
        fakes are a different training trajectory.)"""
        from dcgan_tpu.train.trainer import train

        def keys(sub, pipeline):
            cfg = self._cfg(tmp_path / sub, pipeline_gd=pipeline)
            train(cfg, synthetic_data=True, max_steps=4)
            loss_rows = [
                set(e["values"])
                for e in self._events(tmp_path / sub)
                if e["kind"] == "scalars" and "d_loss" in e["values"]]
            assert loss_rows
            return set().union(*loss_rows)

        fused = {k for k in keys("fused", False)
                 if not k.startswith("perf/")}
        pipelined = {k for k in keys("pipelined", True)
                     if not k.startswith("perf/")}
        assert pipelined == fused

    def test_fused_stream_identical_with_pipeline_code_present(self,
                                                               tmp_path):
        """--pipeline_gd off (the default) is reference parity: two
        identical fused runs produce byte-identical event values — the
        pipeline integration added no nondeterminism, no new keys, and no
        dispatch-stream perturbation to the default path."""
        from dcgan_tpu.train.trainer import train

        def run(sub):
            cfg = self._cfg(tmp_path / sub, pipeline_gd=False)
            train(cfg, synthetic_data=True, max_steps=5)
            cleaned = []
            for e in self._events(tmp_path / sub):
                e.pop("time", None)
                if e["kind"] == "scalars":
                    e["values"] = {k: v for k, v in e["values"].items()
                                   if not k.startswith("perf/")}
                cleaned.append(e)
            return cleaned

        a, b = run("a"), run("b")
        assert a == b
        assert not any("pipeline" in k for e in a if e["kind"] == "scalars"
                       for k in e["values"])

    def test_checkpoint_restores_across_modes(self, tmp_path):
        """State-tree invariance: the fake buffer lives OUTSIDE the
        checkpoint pytree, so a fused-mode checkpoint restores under
        --pipeline_gd (and the run refills and completes), and the final
        trees are structurally identical."""
        from dcgan_tpu.train.trainer import train

        cfg_a = self._cfg(tmp_path, pipeline_gd=False)
        state_a = train(cfg_a, synthetic_data=True, max_steps=4)
        assert os.path.isdir(tmp_path / "ckpt" / "4")
        cfg_b = self._cfg(tmp_path, pipeline_gd=True)
        state_b = train(cfg_b, synthetic_data=True, max_steps=6)
        assert int(jax.device_get(state_b["step"])) == 6
        assert (jax.tree_util.tree_structure(state_a)
                == jax.tree_util.tree_structure(state_b))

    def test_flight_recorder_pipeline_tag(self, tmp_path):
        """--pipeline_gd per-step flight records carry the pipeline phase
        tag (a crash dump from a mid-fill hang must say so); fused-mode
        records must NOT gain the key."""
        from dcgan_tpu.train.flight_recorder import read_dump
        from dcgan_tpu.train.trainer import train

        def crash(sub, pipeline):
            cfg = self._cfg(tmp_path / sub, pipeline_gd=pipeline,
                            learning_rate=float("nan"), nan_check_steps=1)
            with pytest.raises(FloatingPointError):
                train(cfg, synthetic_data=True, max_steps=4)
            _, records = read_dump(
                str(tmp_path / sub / "ckpt" / "flight_recorder.jsonl"))
            assert records
            return records

        piped = crash("piped", True)
        assert all(r.get("pipeline") in ("fill", "steady") for r in piped)
        assert piped[0]["pipeline"] == "fill"     # step 1 filled
        fused = crash("fused", False)
        assert all("pipeline" not in r for r in fused)
