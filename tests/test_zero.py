"""ZeRO-2/3 state-sharded training (ISSUE 13, arXiv:2004.13336).

The `--zero_stage {1,2,3}` knob extends ZeRO-1 (`shard_opt`) to gradient
and parameter sharding over the data axis on BOTH backends: stage 2
reduce-scatters gradients into rule-engine shards, runs Adam shard-local
against the already-sharded moments, and rebuilds replicated params with
one fused all-gather per update; stage 3 additionally keeps params and
the EMA mirror resident sharded between steps with a just-in-time
all-gather inside each forward.

Stage-1 parity (the `--zero_stage 1` default must be byte-identical to
pre-PR behavior) is pinned MECHANICALLY, not by an A/B of the binary
against itself: every stage-1 program's jaxpr fingerprint in the
committed `analysis/programs.lock.jsonl` is unchanged from the pre-ZeRO
manifest (the semantic smoke pin, tests/test_tools_analysis_all*.py,
recomputes and byte-compares it), and the rule engine's stage-1
resolution still matches the retired hand-built oracle
spec-object-for-spec-object (tests/test_elastic.py). What THIS file pins:

- stage 1/2/3 loss parity on the canonical 2-device CPU mesh for all
  three model families, both backends, with per-chip resident state
  strictly decreasing 1 -> 2 -> 3;
- the donation-aliasing contract for every sharded-grad program (both
  backends, both LR-backoff variants) via the committed manifest;
- warmup-plan completeness for every stage variant;
- the zero_stage config validation (stage >= 2 needs a data axis of
  size > 1; an unshardable targeted leaf fails loudly, named);
- device-resident rollback snapshots of ZeRO-sharded state.

The end-to-end NaN-rollback drill (zero_stage=3 vs a stage-1 control,
bit-exact loss replay) lives in tools/chaos_drill.py::zero-rollback,
pinned by tests/test_tools_elastic.py; the cross-stage cross-mesh checkpoint
restore lives in tests/test_elastic.py.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dcgan_tpu.config import MeshConfig, ModelConfig, TrainConfig
from dcgan_tpu.elastic import rules
from dcgan_tpu.parallel import make_parallel_train
from dcgan_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

TINY = dict(output_size=16, gf_dim=8, df_dim=8, compute_dtype="float32")

#: the three trainable families at the tiny preset; resnet/stylegan pair
#: with the hinge loss (their BN-free critic recipe)
FAMILIES = {
    "dcgan": dict(model=ModelConfig(**TINY), loss="gan"),
    "resnet": dict(model=ModelConfig(arch="resnet", **TINY), loss="hinge"),
    "stylegan": dict(model=ModelConfig(arch="stylegan", spectral_norm="d",
                                       **TINY), loss="hinge"),
}


def _mesh2():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:2]).reshape(2, 1),
                (DATA_AXIS, MODEL_AXIS))


def _batch():
    rng = np.random.default_rng(0)
    return jnp.asarray(np.tanh(rng.normal(size=(8, 16, 16, 3)))
                       .astype(np.float32))


def _state_mib_per_chip(state) -> float:
    """THE derivation bench.py's peak_state_mib ships (one shared
    definition — the test pins the real metric, not a copy)."""
    from dcgan_tpu.parallel.sharding import state_bytes_per_chip

    return state_bytes_per_chip(state) / 2**20


def _run(backend: str, family: str, stage: int, steps: int = 3):
    cfg = TrainConfig(batch_size=8, backend=backend,
                      mesh=MeshConfig(data=2, zero_stage=stage),
                      **FAMILIES[family])
    pt = make_parallel_train(cfg, _mesh2())
    state = pt.init(jax.random.key(0))
    mib = _state_mib_per_chip(state)
    xs = _batch()
    rows = []
    for i in range(steps):
        state, m = pt.step(state, xs,
                           jax.random.fold_in(jax.random.key(1), i))
        rows.append([float(v) for _, v in sorted(m.items())])
    return np.asarray(rows), mib, state


class TestLossParity:
    """Stages 2/3 must train the stage-1 trajectory: the sharding is a
    LAYOUT of the same computation (reduce-scatter + shard-local Adam +
    all-gather == all-reduce + replicated Adam), so losses track stage 1
    to f32 reduction-order noise — and the per-chip resident state
    strictly decreases 1 -> 2 -> 3, which is the point of the ladder."""

    # one smoke cell per backend; the full family matrix is slow-tier
    # (every cell is two fresh multi-device compiles)
    @pytest.mark.parametrize("backend,family", [
        pytest.param("gspmd", "dcgan", id="gspmd-dcgan"),
        pytest.param("shard_map", "dcgan", id="shard_map-dcgan"),
        pytest.param("gspmd", "resnet", id="gspmd-resnet",
                     marks=pytest.mark.slow),
        pytest.param("shard_map", "resnet", id="shard_map-resnet",
                     marks=pytest.mark.slow),
        pytest.param("gspmd", "stylegan", id="gspmd-stylegan",
                     marks=pytest.mark.slow),
        pytest.param("shard_map", "stylegan", id="shard_map-stylegan",
                     marks=pytest.mark.slow),
    ])
    def test_stage_ladder_loss_parity_and_memory(self, backend, family):
        rows1, mib1, _ = _run(backend, family, 1)
        rows2, mib2, _ = _run(backend, family, 2)
        rows3, mib3, _ = _run(backend, family, 3)
        np.testing.assert_allclose(rows2, rows1, rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(rows3, rows1, rtol=1e-3, atol=1e-3)
        assert mib1 > mib2 > mib3, (mib1, mib2, mib3)

    def test_stage3_residency(self):
        """Stage 3's memory model, asserted on the physical shards: Adam
        moments AND params AND the EMA mirror each hold 1/2 per device on
        the 2-way data axis; stage 2 shards only the moments."""
        _, _, s2 = _run("gspmd", "dcgan", 2, steps=1)
        _, _, s3 = _run("gspmd", "dcgan", 3, steps=1)
        for state, param_sharded in ((s2, False), (s3, True)):
            mu = state["opt"]["disc"][1][0].mu["conv1"]["w"]
            assert {int(np.prod(sh.data.shape))
                    for sh in mu.addressable_shards} \
                == {mu.size // 2}
            for leaf in (state["params"]["disc"]["conv1"]["w"],
                         state["ema_gen"]["deconv1"]["w"]):
                frac = {int(np.prod(sh.data.shape))
                        for sh in leaf.addressable_shards}
                assert frac == {leaf.size // (2 if param_sharded else 1)}


class TestDonationAudit:
    """DCG007's answer for the sharded-grad programs, read from the
    committed manifest (the semantic smoke pin recomputes it live): every
    donated data-SHARDED state leaf is realized as an input_output_alias
    pair — in BOTH backends, at BOTH stages, including the LR-backoff
    rebuild variants. A donated-but-unaliased sharded leaf would be a
    silent full-shard copy per step, exactly the overhead ZeRO exists to
    remove."""

    def _zero_rows(self):
        from dcgan_tpu.analysis import manifest as mlib

        recs = mlib.load_path(mlib.default_manifest_path())
        return [r for r in recs if "@zero" in r.name]

    def test_every_stage_variant_is_in_the_manifest(self):
        names = {r.name for r in self._zero_rows()}
        for backend in ("gspmd", "shard_map"):
            for stage in (2, 3):
                for prog in ("train_step", "multi_step@k2", "d_update",
                             "g_update", "gen_fakes"):
                    assert f"{backend}::{prog}@zero{stage}" in names
                for prog in ("train_step", "multi_step@k2", "d_update",
                             "g_update"):
                    assert (f"{backend}::{prog}@lr_backoff@zero{stage}"
                            in names)

    def test_donated_sharded_leaves_all_alias(self):
        donating = [r for r in self._zero_rows() if r.donation is not None]
        # 4 programs x 2 backoffs x 2 stages x 2 backends = 32, plus the
        # collective-overlap variants (ISSUE 20): 4 donated programs x
        # 2 backoffs x 3 arms (zero2@overlap, zero3@overlap,
        # zero3@prefetch) = 24
        assert len(donating) == 56
        for r in donating:
            assert r.donation["unaliased"] == [], r.name
            assert r.donation["aliased"] == r.donation["donated"] > 0, \
                r.name

    def test_shard_map_census_shows_the_zero_collectives(self):
        """The explicit-collective backend's rows carry the ZeRO wire
        pattern: reduce-scatter gradients at both stages, strictly MORE
        all-gathers at stage 3 (the just-in-time param gathers)."""
        rows = {r.name: r for r in self._zero_rows()}
        for stage in (2, 3):
            c = rows[f"shard_map::train_step@zero{stage}"].collectives
            assert c.get("reduce_scatter", 0) > 0
            assert c.get("all_gather", 0) > 0
        assert (rows["shard_map::train_step@zero3"].collectives[
                    "all_gather"]
                > rows["shard_map::train_step@zero2"].collectives[
                    "all_gather"])
        # stage 3's fill program gathers the sharded G params; stage 2's
        # reads them replicated
        assert rows["shard_map::gen_fakes@zero3"].collectives.get(
            "all_gather", 0) > 0
        assert rows["shard_map::gen_fakes@zero2"].collectives.get(
            "all_gather", 0) == 0


class TestWarmupPlanCompleteness:
    """Every stage variant's warmup plan must enumerate what its loop
    dispatches (DESIGN §6d: the first live dispatch of an unplanned
    program would compile under an armed watchdog deadline)."""

    def _cfg(self, backend, stage, pipeline=False):
        return TrainConfig(
            model=ModelConfig(**TINY), batch_size=8, backend=backend,
            mesh=MeshConfig(data=2, zero_stage=stage),
            steps_per_call=1 if pipeline else 2, pipeline_gd=pipeline,
            sample_every_steps=100, activation_summary_steps=100,
            nan_check_steps=100, nan_policy="rollback",
            rollback_snapshot_steps=100, rollback_lr_backoff=0.5,
            tensorboard=False)

    @pytest.mark.parametrize("backend", ["gspmd", "shard_map"])
    @pytest.mark.parametrize("stage", [2, 3])
    def test_plan_covers_the_stage_variants(self, backend, stage):
        from dcgan_tpu.train import warmup

        mesh = _mesh2()
        cfg = self._cfg(backend, stage)
        pt = make_parallel_train(cfg, mesh)
        state = warmup.state_example(pt)
        z = jax.ShapeDtypeStruct((8, cfg.model.z_dim), jnp.float32)
        plan, pt_backoff = warmup.build_warmup_plan(
            cfg, pt, state, sample_z=z, eval_z=z,
            make_backoff_pt=lambda c: make_parallel_train(c, mesh))
        names = [n for n, _, _ in plan]
        for want in ("train_step", "multi_step@k2", "sampler",
                     "eval_losses", "summarize", "state_copy",
                     "train_step@lr_backoff", "multi_step@k2@lr_backoff"):
            assert want in names, (backend, stage, names)
        assert pt_backoff is not None

        cfg_p = self._cfg(backend, stage, pipeline=True)
        pt_p = make_parallel_train(cfg_p, mesh)
        plan_p, _ = warmup.build_warmup_plan(
            cfg_p, pt_p, warmup.state_example(pt_p),
            make_backoff_pt=lambda c: make_parallel_train(c, mesh))
        names_p = [n for n, _, _ in plan_p]
        for want in ("gen_fakes", "d_update", "g_update",
                     "d_update@lr_backoff", "g_update@lr_backoff"):
            assert want in names_p, (backend, stage, names_p)


class TestConfigValidation:
    def test_stage_out_of_range(self):
        with pytest.raises(ValueError, match="zero_stage"):
            MeshConfig(zero_stage=0)
        with pytest.raises(ValueError, match="zero_stage"):
            MeshConfig(zero_stage=4)

    def test_stage_rejects_spatial(self):
        with pytest.raises(ValueError, match="spatial"):
            MeshConfig(model=2, spatial=True, zero_stage=2)

    def test_shard_map_rejects_grad_clip_under_zero(self):
        with pytest.raises(ValueError, match="global norm"):
            TrainConfig(model=ModelConfig(**TINY), backend="shard_map",
                        grad_clip=1.0, mesh=MeshConfig(zero_stage=2))

    @pytest.mark.parametrize("backend", ["gspmd", "shard_map"])
    def test_stage2_requires_data_axis_gt_1(self, backend):
        from jax.sharding import Mesh

        mesh1 = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                     (DATA_AXIS, MODEL_AXIS))
        cfg = TrainConfig(model=ModelConfig(**TINY), batch_size=8,
                          backend=backend,
                          mesh=MeshConfig(data=1, zero_stage=2))
        with pytest.raises(ValueError, match="data axis"):
            make_parallel_train(cfg, mesh1)

    def test_divisibility_error_names_the_offending_leaf(self):
        """A targeted leaf with >= 2x the data axis's elements but no dim
        the axis divides must fail loudly, NAMING the leaf — not silently
        degrade the stage's memory model."""
        shapes = {"opt": {"g": {"proj": {
            "w": jax.ShapeDtypeStruct((5, 5), jnp.float32)}}}}
        with pytest.raises(ValueError, match=r"opt/g/proj/w"):
            rules.validate_zero_state(shapes, {"data": 2, "model": 1},
                                      zero_stage=2)
        # the same leaf is fine at stage 1 (nothing targets it) and when
        # a dim divides
        rules.validate_zero_state(shapes, {"data": 2, "model": 1},
                                  zero_stage=1)
        ok = {"opt": {"g": {"proj": {
            "w": jax.ShapeDtypeStruct((5, 6), jnp.float32)}}}}
        rules.validate_zero_state(ok, {"data": 2, "model": 1},
                                  zero_stage=2)

    def test_shard_map_now_accepts_zero_stages(self):
        # the pre-ISSUE-13 blanket rejection narrowed to shard_opt only
        cfg = TrainConfig(model=ModelConfig(**TINY), backend="shard_map",
                          mesh=MeshConfig(zero_stage=3))
        assert cfg.mesh.zero_stage == 3


class TestGradSpecDerivation:
    """`rules.grad_shardings` / `zero_scatter_dims`: the gradient specs
    derive from the SAME rule table as mu/nu (the ISSUE's contract — the
    reduce-scattered gradient is the shard-local update's input with zero
    re-layout)."""

    def _param_shapes(self):
        from dcgan_tpu.train.steps import init_train_state

        cfg = TrainConfig(model=ModelConfig(**TINY), batch_size=8)
        return jax.eval_shape(lambda k: init_train_state(k, cfg),
                              jax.random.key(0))

    def test_grad_specs_match_moment_specs(self):
        shapes = self._param_shapes()
        mesh_shape = {"data": 2, "model": 1}
        sharded = 0
        for net in ("gen", "disc"):
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    shapes["params"][net])[0]:
                tail = rules.path_str(path)
                shape = tuple(leaf.shape)
                gspec = rules.resolve_spec(
                    rules.logical_spec(tail, len(shape)), shape,
                    mesh_shape, zero=True)
                mspec = rules.resolve_spec(
                    rules.logical_spec(f"opt/{net}/1/0/mu/{tail}",
                                       len(shape)),
                    shape, mesh_shape, zero=True)
                assert gspec == mspec, (net, tail)
                if any(a == DATA_AXIS
                       or (isinstance(a, tuple) and DATA_AXIS in a)
                       for a in gspec):
                    sharded += 1
        assert sharded >= 10  # the policy really shards the heavy leaves

    def test_scatter_dims_match_shardings(self):
        """The shard_map backend's explicit collective dims agree with
        the NamedSharding derivation: the dim carrying the data axis in
        the resolved spec IS the psum_scatter/all_gather dim, and the
        dims tree maps one-to-one onto the param tree (what the backend's
        tree_map against gradient trees rides on)."""
        shapes = self._param_shapes()
        mesh_shape = {"data": 2, "model": 1}
        for net in ("gen", "disc"):
            dims = rules.zero_scatter_dims(shapes["params"][net],
                                           mesh_shape)
            assert jax.tree_util.tree_structure(dims) == \
                jax.tree_util.tree_structure(
                    jax.tree_util.tree_map(lambda _: 0,
                                           shapes["params"][net]))
            for (path, leaf), d in zip(
                    jax.tree_util.tree_flatten_with_path(
                        shapes["params"][net])[0],
                    jax.tree_util.tree_leaves(dims)):
                tail = rules.path_str(path)
                shape = tuple(leaf.shape)
                spec = rules.resolve_spec(
                    rules.logical_spec(tail, len(shape)), shape,
                    mesh_shape, zero=True)
                data_dims = [i for i, a in enumerate(spec)
                             if a == DATA_AXIS
                             or (isinstance(a, tuple) and DATA_AXIS in a)]
                assert data_dims == ([] if d < 0 else [d]), (net, tail)


class TestPipelineZeroCompose:
    """--pipeline_gd x --zero_stage: the stage programs carry the same
    hooks (manifest rows d_update@zeroN / g_update@zeroN), so the
    pipelined dispatch loop trains the same trajectory sharded as
    replicated — bit-exact on the shard_map backend, whose explicit
    collectives reproduce the pmean arithmetic."""

    @pytest.mark.slow
    def test_pipelined_stage3_matches_pipelined_stage1(self):
        from dcgan_tpu.train.gd_pipeline import GDPipeline

        rows = {}
        for stage in (1, 3):
            cfg = TrainConfig(model=ModelConfig(**TINY), batch_size=8,
                              backend="shard_map", pipeline_gd=True,
                              mesh=MeshConfig(data=2, zero_stage=stage))
            pt = make_parallel_train(cfg, _mesh2())
            state = pt.init(jax.random.key(0))
            pipe = GDPipeline()
            xs = _batch()
            out = []
            for i in range(3):
                state, m = pipe.step(
                    pt, state, xs,
                    jax.random.fold_in(jax.random.key(1), i))
                out.append(sorted((k, float(v)) for k, v in m.items()))
            pipe.drain("test-end")
            rows[stage] = out
        assert rows[1] == rows[3]


class TestRollbackWithShardedState:
    """train/rollback.py under ZeRO-3 residency: both snapshot modes
    round-trip the data-sharded state with shardings AND values intact
    (the device-resident mode is what multi-host rollback dispatches; the
    host mode is the single-process drill's path)."""

    @pytest.mark.parametrize("device_resident", [True, False],
                             ids=["device-resident", "host"])
    def test_snapshot_restore_roundtrip(self, device_resident):
        from dcgan_tpu.train.rollback import RollbackManager

        cfg = TrainConfig(model=ModelConfig(**TINY), batch_size=8,
                          mesh=MeshConfig(data=2, zero_stage=3))
        pt = make_parallel_train(cfg, _mesh2())
        state = pt.init(jax.random.key(0))
        mgr = RollbackManager(every=1, max_rollbacks=1,
                              device_resident=device_resident)
        mgr.snapshot(0, state)
        restored, step = mgr.restore(FloatingPointError("test"))
        assert step == 0
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(state),
                jax.tree_util.tree_leaves(restored)):
            # placement equivalence, not spec-object equality: the jit
            # identity copy canonicalizes away size-1 mesh axes
            # (P(..., 'data', 'model') -> P(..., 'data') on a model=1
            # mesh) without moving a byte
            assert a.sharding.is_equivalent_to(b.sharding, a.ndim), \
                jax.tree_util.keystr(path)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
