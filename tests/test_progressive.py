"""Progressive-resolution training plane (ISSUE 15).

Covers the schedule table (parse/validate/phase arithmetic), the
cross-phase state carry (bit-exact carried leaves on both backends and
under ZeRO residency), warmup-plan completeness + the zero-compile
switch contract (CompileCacheMonitor-pinned on the headline 64->128->256
ladder), loader re-bucketing with quarantine carry-over, mid-schedule
checkpoint resume (and the sidecar phase-tag cross-check), the fade
blend, and the single-phase parity A/B (a one-phase schedule IS the
existing trainer, byte-identical events modulo wall-clock).
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from dcgan_tpu.config import MeshConfig, ModelConfig, TrainConfig
from dcgan_tpu.progressive import (
    PhaseRuntime,
    Rebucketer,
    carry_path,
    carry_state,
    parse_schedule,
    phase_data_cfg,
)


def _model(size=16, **kw):
    kw.setdefault("gf_dim", 8)
    kw.setdefault("df_dim", 8)
    kw.setdefault("compute_dtype", "float32")
    return ModelConfig(output_size=size, **kw)


def _cfg(tmp_path, size=16, spec="8:2,16:*", **kw):
    kw.setdefault("model", _model(size))
    kw.setdefault("batch_size", 8)
    kw.setdefault("tensorboard", False)
    kw.setdefault("sample_every_steps", 0)
    kw.setdefault("activation_summary_steps", 0)
    kw.setdefault("nan_check_steps", 0)
    kw.setdefault("save_summaries_secs", 0.0)
    kw.setdefault("save_model_secs", 1e9)
    kw.setdefault("log_every_steps", 1)
    kw.setdefault("checkpoint_dir", str(tmp_path / "ckpt"))
    kw.setdefault("sample_dir", str(tmp_path / "samples"))
    return TrainConfig(progressive=spec, **kw)


def _parse(spec, *, model=None, batch=8, max_steps=1000, **kw):
    return parse_schedule(spec, model=model or _model(),
                          batch_size=batch, max_steps=max_steps, **kw)


def _events(ckpt_dir):
    path = os.path.join(ckpt_dir, "events.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f]


# ---------------------------------------------------------------------------
# schedule parsing / validation / arithmetic
# ---------------------------------------------------------------------------

class TestSchedule:
    def test_parse_basic(self):
        s = _parse("8:4,16:*")
        assert [(p.resolution, p.steps, p.batch_size) for p in s.phases] \
            == [(8, 4, 8), (16, None, 8)]

    def test_per_phase_batch_override(self):
        s = _parse("8:4:16,16:*:4")
        assert [p.batch_size for p in s.phases] == [16, 4]

    def test_last_phase_must_be_star(self):
        with pytest.raises(ValueError, match="last progressive phase"):
            _parse("8:4,16:4")

    def test_star_only_on_last(self):
        with pytest.raises(ValueError, match="only valid on the last"):
            _parse("8:*,16:*")

    def test_resolutions_strictly_ascending(self):
        with pytest.raises(ValueError, match="strictly ascending"):
            _parse("16:4,16:*", model=_model(16))

    def test_resolution_must_be_stack_site(self):
        with pytest.raises(ValueError, match="model-stack site"):
            _parse("12:4,16:*")

    def test_last_resolution_must_match_model(self):
        with pytest.raises(ValueError, match="output_size"):
            _parse("8:4,32:*", model=_model(16))

    def test_steps_respect_steps_per_call(self):
        with pytest.raises(ValueError, match="steps_per_call"):
            _parse("8:3,16:*", steps_per_call=2)
        _parse("8:4,16:*", steps_per_call=2)  # aligned: fine

    def test_fixed_phases_must_leave_room(self):
        with pytest.raises(ValueError, match="never run"):
            _parse("8:1000,16:*", max_steps=1000)

    def test_fade_requires_room_and_per_step_dispatch(self):
        with pytest.raises(ValueError, match="steps_per_call=1"):
            _parse("8:4,16:4,32:*", model=_model(32), steps_per_call=2,
                   fade_steps=2)
        with pytest.raises(ValueError, match="exceeds phase"):
            _parse("8:4,16:4,32:*", model=_model(32), fade_steps=8)

    def test_phase_arithmetic_and_boundary_semantics(self):
        s = _parse("8:2,16:2,32:*", model=_model(32))
        assert s.starts(10) == [0, 2, 4]
        assert [s.index_for_dispatch(t, 10) for t in (0, 1, 2, 3, 4, 9)] \
            == [0, 0, 1, 1, 2, 2]
        # a state at completed-step 2 was PRODUCED by phase 0 (the switch
        # runs before the first new-phase dispatch)
        assert s.index_for_state(2, 10) == 0
        assert s.index_for_state(3, 10) == 1
        assert s.index_for_state(0, 10) == 0

    def test_alpha_ramp(self):
        s = _parse("8:2,16:*", fade_steps=4)
        assert s.alpha_at(0, 10) == 1.0   # first phase never fades
        assert s.alpha_at(2, 10) == pytest.approx(0.25)
        assert s.alpha_at(3, 10) == pytest.approx(0.5)
        assert s.alpha_at(5, 10) == pytest.approx(1.0)
        assert s.alpha_at(9, 10) == 1.0

    def test_validate_mesh_granule(self):
        s = _parse("8:2:6,16:*", model=_model(16))
        with pytest.raises(ValueError, match="does not divide"):
            s.validate_mesh({"data": 4, "model": 1}, spatial=False)

    def test_config_for_is_single_shape(self):
        cfg = _cfg_for_schedule()
        s = _parse("8:2,16:*")
        phase0 = s.config_for(cfg, 0)
        assert phase0.model.output_size == 8
        assert phase0.progressive == ""

    def test_config_validation_wires_the_parser(self, tmp_path):
        with pytest.raises(ValueError, match="last progressive phase"):
            _cfg(tmp_path, spec="8:4,16:4")
        with pytest.raises(ValueError, match="attn_res"):
            _cfg(tmp_path, size=32, spec="16:4,32:*",
                 model=_model(32, attn_res=16))
        with pytest.raises(ValueError, match="rollback_lr_backoff"):
            _cfg(tmp_path, nan_policy="rollback", nan_check_steps=1,
                 rollback_lr_backoff=0.5)
        with pytest.raises(ValueError, match="silent no-op"):
            _cfg(tmp_path, spec="", progressive_fade_steps=2)


def _cfg_for_schedule():
    return TrainConfig(model=_model(16), batch_size=8,
                       progressive="8:2,16:*", tensorboard=False)


# ---------------------------------------------------------------------------
# cross-phase state carry
# ---------------------------------------------------------------------------

class TestCarry:
    def test_dcgan_gen_stage_shift(self):
        # growing by one stage: old deconv{i} -> new deconv{i+1}; the
        # z-side top (proj/bn0) has no home; SN state shifts with its layer
        assert carry_path("params/gen/deconv1/w", arch="dcgan", shift=1) \
            == "params/gen/deconv2/w"
        assert carry_path("bn/gen/bn1/mean", arch="dcgan", shift=1) \
            == "bn/gen/bn2/mean"
        assert carry_path("opt/gen/0/0/mu/deconv2/w", arch="dcgan",
                          shift=1) == "opt/gen/0/0/mu/deconv3/w"
        assert carry_path("ema_gen/deconv1/b", arch="dcgan", shift=1) \
            == "ema_gen/deconv2/b"
        assert carry_path("bn/gen/sn_deconv1/u", arch="dcgan", shift=1) \
            == "bn/gen/sn_deconv2/u"
        assert carry_path("params/gen/proj/w", arch="dcgan", shift=1) \
            is None
        assert carry_path("params/gen/bn0/scale", arch="dcgan", shift=1) \
            is None

    def test_disc_and_scalars_identity(self):
        assert carry_path("params/disc/conv0/w", arch="dcgan", shift=1) \
            == "params/disc/conv0/w"
        assert carry_path("step", arch="dcgan", shift=1) == "step"
        assert carry_path("opt/disc/0/0/count", arch="dcgan", shift=1) \
            == "opt/disc/0/0/count"

    def test_non_dcgan_is_name_matched(self):
        assert carry_path("params/gen/deconv1/w", arch="resnet", shift=1) \
            == "params/gen/deconv1/w"

    @pytest.mark.parametrize("backend,zero", [("gspmd", 1),
                                              ("shard_map", 1),
                                              ("shard_map", 3)])
    def test_carried_leaves_bit_exact(self, tmp_path, backend, zero):
        """The issue's carry contract on live trees: carried leaves
        transfer bit-exactly (ZeRO-3 resident shards included — same
        path + shape + mesh => same spec, so the buffers carry verbatim),
        new-at-phase leaves keep their fresh init."""
        from dcgan_tpu.parallel import make_mesh

        cfg = _cfg(tmp_path, size=16, spec="8:2,16:*", backend=backend,
                   mesh=MeshConfig(data=2, zero_stage=zero))
        mesh = make_mesh(cfg.mesh, jax.devices()[:2])
        rt = PhaseRuntime(
            cfg, mesh,
            _parse("8:2,16:*", model=cfg.model, batch=cfg.batch_size),
            total_steps=10)
        st0 = rt.pt.init(jax.random.key(0))
        old = {p: np.asarray(jax.device_get(leaf)) for p, leaf in
               _flat(st0).items()}
        st1 = rt.advance(st0)
        assert rt.index == 1 and rt.last_carried > 0
        new = _flat(st1)
        hits = 0
        for path, arr in old.items():
            home = carry_path(path, arch="dcgan", shift=1)
            if home is None or home not in new:
                continue
            tgt = np.asarray(jax.device_get(new[home]))
            if tgt.shape != arr.shape:
                continue  # shape-guarded: fresh by design (head etc.)
            np.testing.assert_array_equal(tgt, arr, err_msg=home)
            hits += 1
        assert hits == rt.last_carried
        # a genuinely new leaf exists and is NOT the old one
        assert "params/gen/proj/w" in new

    def test_carry_state_shape_guard(self):
        # same name, different shape (the disc head) -> fresh init wins
        old = {"params": {"disc": {"head": {"w": np.ones((4, 1),
                                                         np.float32)}}}}
        fresh = {"params": {"disc": {"head": {"w": np.zeros((8, 1),
                                                            np.float32)}}}}
        merged, carried = carry_state(old, fresh, arch="dcgan", shift=1)
        assert carried == 0
        assert merged["params"]["disc"]["head"]["w"].shape == (8, 1)


def _flat(tree):
    from dcgan_tpu.elastic.rules import path_str

    return {path_str(p): leaf for p, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


# ---------------------------------------------------------------------------
# warmup completeness + the zero-compile switch (the acceptance pin)
# ---------------------------------------------------------------------------

class TestWarmup:
    def test_plan_enumerates_every_phase(self, tmp_path):
        from dcgan_tpu.parallel import make_mesh
        from dcgan_tpu.train import warmup

        cfg = _cfg(tmp_path, size=32, spec="8:2,16:2,32:*",
                   sample_every_steps=100, activation_summary_steps=100,
                   progressive_fade_steps=2)
        mesh = make_mesh(cfg.mesh)
        rt = PhaseRuntime(
            cfg, mesh,
            _parse("8:2,16:2,32:*", model=cfg.model, fade_steps=2),
            total_steps=10)
        z = jax.random.uniform(jax.random.key(1), (8, cfg.model.z_dim))
        plan = rt.build_warmup_plan(warmup.state_example(rt.pt),
                                    sample_z=z)
        names = {n for n, _, _ in plan}
        # current phase rows keep their plain names (perf/compile_ms and
        # the coverage pins read unchanged); later phases suffix @r<res>
        assert {"init", "train_step", "state_copy", "sampler",
                "eval_losses", "summarize"} <= names
        for res in (16, 32):
            assert {f"init@r{res}", f"train_step@r{res}",
                    f"state_copy@r{res}", f"sampler@r{res}",
                    f"fade@r{res}"} <= names

    def test_headline_ladder_switches_with_zero_compile_requests(
            self, tmp_path, capsys):
        """THE acceptance criterion: a 64->128->256 smoke schedule on CPU
        completes with compile-request delta == 0 after AOT warmup across
        BOTH switches (CompileCacheMonitor-pinned via the trainer's
        per-switch printed delta — priming makes the zero literal, the
        PR 9 mechanism)."""
        from dcgan_tpu.train.trainer import train

        cfg = _cfg(tmp_path, size=256, spec="64:2,128:2,256:*",
                   batch_size=8, save_summaries_secs=1e9,
                   compile_cache_dir=str(tmp_path / "cache"),
                   aot_warmup=True)
        state = train(cfg, synthetic_data=True, max_steps=6)
        assert int(jax.device_get(state["step"])) == 6
        out = capsys.readouterr().out
        switches = [l for l in out.splitlines()
                    if "progressive phase" in l and "->" in l]
        assert len(switches) == 2, out[-2000:]
        for line in switches:
            assert "compile_requests_delta=0" in line, line

    def test_pipelined_progressive_warmup_primes_and_switches(
            self, tmp_path, capsys):
        """--pipeline_gd composes: prime() dispatches the stage programs
        (regression: the g_update metrics carry g_loss only — the prime
        sync must not assume d_loss) and the switch still reports zero
        compile requests."""
        from dcgan_tpu.train.trainer import train

        cfg = _cfg(tmp_path, size=16, spec="8:2,16:*", pipeline_gd=True,
                   save_summaries_secs=1e9,
                   compile_cache_dir=str(tmp_path / "cache"),
                   aot_warmup=True)
        state = train(cfg, synthetic_data=True, max_steps=4)
        assert int(jax.device_get(state["step"])) == 4
        out = capsys.readouterr().out
        assert "progressive warmup primed" in out
        switch = [l for l in out.splitlines()
                  if "progressive phase 1" in l]
        assert switch and "compile_requests_delta=0" in switch[0]


# ---------------------------------------------------------------------------
# loader re-bucketing + quarantine carry
# ---------------------------------------------------------------------------

class TestRebucket:
    def test_phase_data_cfg_substitutes_res(self, tmp_path):
        cfg = _cfg(tmp_path, data_dir="train_{res}",
                   sample_image_dir="held_{res}")
        p0 = phase_data_cfg(_parse("8:2,16:*").config_for(cfg, 0))
        assert p0.data_dir == "train_8" and p0.sample_image_dir == "held_8"
        plain = _cfg(tmp_path)
        assert phase_data_cfg(plain) is plain

    def test_reopen_closes_old_and_carries_tally(self):
        from dcgan_tpu.data import quarantine

        class FakeIt:
            def __init__(self):
                self.closed = False

            def close(self):
                self.closed = True

        opened = []

        def open_fn(cfg):
            it = FakeIt()
            opened.append(it)
            return it, None

        rb = Rebucketer(open_fn)
        cfg = _cfg_for_schedule()
        rb.open(cfg)
        base = quarantine.count()
        quarantine.record("shard-0", 7, "test corruption", budget=10_000)
        rb.reopen(cfg)
        assert opened[0].closed and not opened[1].closed
        # the process-global tally rode across the re-open
        assert rb.last_tally == base + 1
        assert rb.reopens == 1
        rb.close()
        assert opened[1].closed

    def test_real_data_rebucket_with_quarantine_budget(self, tmp_path):
        """End-to-end: per-resolution TFRecord dirs (the {res} pattern),
        one corrupt record in EACH, a budget spanning the run — the
        switch re-opens the loader at the new decode size and the
        quarantine counter accumulates across phases instead of
        resetting."""
        from dcgan_tpu.data.synthetic import write_image_tfrecords
        from dcgan_tpu.testing.chaos import corrupt_tfrecord_payload
        from dcgan_tpu.train.trainer import train

        for res in (8, 16):
            paths = write_image_tfrecords(
                str(tmp_path / f"train_{res}"), num_examples=32,
                image_size=res, num_shards=1)
            corrupt_tfrecord_payload(paths[0], record_index=1)
        cfg = _cfg(tmp_path, size=16, spec="8:3,16:*",
                   data_dir=str(tmp_path / "train_{res}"),
                   max_corrupt_records=100, shuffle_buffer=8,
                   num_loader_threads=1)
        state = train(cfg, synthetic_data=False, max_steps=6)
        assert int(jax.device_get(state["step"])) == 6
        counts = [e["values"]["data/corrupt_records"]
                  for e in _events(cfg.checkpoint_dir)
                  if e["kind"] == "scalars"
                  and "data/corrupt_records" in e["values"]]
        assert counts and max(counts) >= 2, counts  # both dirs' corruption


# ---------------------------------------------------------------------------
# checkpoint resume across the schedule
# ---------------------------------------------------------------------------

class TestResume:
    def test_mid_schedule_resume_lands_in_right_phase(self, tmp_path,
                                                      capsys):
        from dcgan_tpu.elastic import sidecar
        from dcgan_tpu.train.trainer import train

        cfg = _cfg(tmp_path, size=32, spec="8:2,16:2,32:*")
        train(cfg, synthetic_data=True, max_steps=3)   # stops inside r16
        payload = sidecar.read(cfg.checkpoint_dir, 3)
        assert payload["progressive"] == {"phase": 1, "resolution": 16}
        state = train(cfg, synthetic_data=True, max_steps=6)
        out = capsys.readouterr().out
        assert "starting in phase 1 (r16" in out
        assert "r16 -> r32" in out
        assert int(jax.device_get(state["step"])) == 6
        assert sidecar.read(cfg.checkpoint_dir, 6)["progressive"] \
            == {"phase": 2, "resolution": 32}

    def test_boundary_checkpoint_carries_pre_switch_tree(self, tmp_path,
                                                         capsys):
        """A save at exactly a phase boundary holds the OLD phase's tree
        (the switch runs before the first new-phase dispatch); the resume
        must template-match it, then switch immediately."""
        from dcgan_tpu.elastic import sidecar
        from dcgan_tpu.train.trainer import train

        cfg = _cfg(tmp_path, size=16, spec="8:2,16:*")
        train(cfg, synthetic_data=True, max_steps=2)
        assert sidecar.read(cfg.checkpoint_dir, 2)["progressive"] \
            == {"phase": 0, "resolution": 8}
        state = train(cfg, synthetic_data=True, max_steps=4)
        out = capsys.readouterr().out
        assert "starting in phase 0 (r8" in out
        assert "r8 -> r16" in out
        assert int(jax.device_get(state["step"])) == 4

    def test_consumers_resolve_mid_schedule_checkpoints(self, tmp_path):
        """generate/evals build their restore template through
        resolve_model_config: a checkpoint stopped mid-schedule holds an
        earlier phase's SHALLOWER tree, and the sidecar phase tag — not
        config.json's final architecture — must decide the model."""
        from dcgan_tpu.config import resolve_model_config
        from dcgan_tpu.train.trainer import train

        cfg = _cfg(tmp_path, size=32, spec="8:2,16:2,32:*")
        train(cfg, synthetic_data=True, max_steps=3)   # stopped inside r16
        resolved = resolve_model_config(cfg.checkpoint_dir)
        assert resolved.output_size == 16
        # an explicit flag still wins (the documented precedence)
        assert resolve_model_config(
            cfg.checkpoint_dir,
            overrides={"output_size": 32}).output_size == 32

    def test_schedule_change_between_runs_fails_loudly(self, tmp_path):
        from dcgan_tpu.train.trainer import train

        cfg = _cfg(tmp_path, size=16, spec="8:2,16:*")
        train(cfg, synthetic_data=True, max_steps=3)   # saved in phase 1
        moved = dataclasses.replace(cfg, progressive="8:4,16:*")
        with pytest.raises(ValueError, match="spec changed"):
            train(moved, synthetic_data=True, max_steps=6)


# ---------------------------------------------------------------------------
# fade
# ---------------------------------------------------------------------------

class TestFade:
    def test_fade_blend_semantics(self, tmp_path):
        from dcgan_tpu.parallel import make_mesh

        cfg = _cfg(tmp_path, progressive_fade_steps=2)
        mesh = make_mesh(cfg.mesh)
        rt = PhaseRuntime(cfg, mesh,
                          _parse("8:2,16:*", fade_steps=2), total_steps=10)
        rt.index = 1
        fade = rt.fade_program()
        x = jax.random.uniform(jax.random.key(0), (8, 16, 16, 3))
        np.testing.assert_allclose(np.asarray(fade(x, np.float32(1.0))),
                                   np.asarray(x), rtol=1e-6)
        low = np.asarray(fade(x, np.float32(0.0)))
        # alpha=0 is pure previous-resolution content: 2x2 blocks constant
        np.testing.assert_allclose(low[:, 0::2, 0::2], low[:, 1::2, 1::2],
                                   rtol=1e-5)

    def test_fade_run_completes_and_logs_alpha(self, tmp_path):
        from dcgan_tpu.train.trainer import train

        cfg = _cfg(tmp_path, size=16, spec="8:2,16:*",
                   progressive_fade_steps=2)
        state = train(cfg, synthetic_data=True, max_steps=6)
        assert int(jax.device_get(state["step"])) == 6
        alphas = [e["values"]["progressive/alpha"]
                  for e in _events(cfg.checkpoint_dir)
                  if e["kind"] == "scalars"
                  and "progressive/alpha" in e["values"]]
        assert alphas and all(0 < a < 1 for a in alphas)


# ---------------------------------------------------------------------------
# parity: a single-phase schedule IS the existing trainer
# ---------------------------------------------------------------------------

class TestParity:
    def test_single_phase_schedule_byte_identical_events(self, tmp_path):
        from dcgan_tpu.train.trainer import train

        def run(sub, spec):
            cfg = _cfg(tmp_path / sub, size=16, spec=spec,
                       nan_check_steps=2)
            train(cfg, synthetic_data=True, max_steps=6)
            lines = []
            for e in _events(cfg.checkpoint_dir):
                # wall-clock fields differ across ANY two runs (the same
                # convention as the async-vs-inline parity A/B); every
                # deterministic byte — kinds, steps, losses, histograms,
                # and crucially the KEY SET — must match exactly
                e.pop("time", None)
                if e["kind"] == "scalars":
                    e["values"] = {k: v for k, v in e["values"].items()
                                   if not k.startswith("perf/")}
                lines.append(json.dumps(e, sort_keys=True))
            return lines

        assert run("plain", "") == run("prog", "16:*")

    def test_progressive_keys_present_in_multi_phase_runs(self, tmp_path):
        from dcgan_tpu.train.event_keys import EVENT_KEYS
        from dcgan_tpu.train.trainer import train

        cfg = _cfg(tmp_path, size=16, spec="8:2,16:*")
        train(cfg, synthetic_data=True, max_steps=4)
        keys = set()
        for e in _events(cfg.checkpoint_dir):
            if e["kind"] == "scalars":
                keys |= {k for k in e["values"]
                         if k.startswith("progressive/")}
        assert {"progressive/phase", "progressive/resolution",
                "progressive/switch_ms"} <= keys
        for k in keys:   # every emitted key is inventory-declared
            assert k in EVENT_KEYS, k

    def test_counter_snapshot_has_phase_field(self):
        from dcgan_tpu.utils.metrics import CounterSnapshot

        assert CounterSnapshot().as_dict()["progressive_phase"] == 0
