"""Elastic topology (ISSUE 12): the sharding-rule engine, the checkpoint
sharding sidecar, and the cross-mesh resharding restore.

The engine must reproduce the retired hand-built derivation bit-for-bit
(the committed semantic manifest's program fingerprints ride on the spec
objects); the sidecar must record the saving topology for every sharded
save; `restore_latest` must reshard across mesh/process changes while the
same-topology path stays byte-identical in behavior (sidecar present,
reshard not taken, no elastic/* keys). The full cross-process drill lives
in tools/chaos_drill.py (elastic-shrink / elastic-grow, the shrink smoke
pinned by tests/test_tools_elastic.py)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dcgan_tpu.config import MeshConfig, ModelConfig, TrainConfig
from dcgan_tpu.elastic import rules, sidecar
from dcgan_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
from dcgan_tpu.train.steps import init_train_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh_of(n: int) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n, 1),
                (DATA_AXIS, MODEL_AXIS))


# ONE variant list for the whole elastic surface: DCG011's coverage audit
# (analysis/semantic.py) and the engine-vs-oracle equivalence below must
# cover the same structural union of trainable families, so the list is
# defined once, there.
def _variants():
    from dcgan_tpu.analysis.semantic import spec_coverage_variants

    return dict(spec_coverage_variants())


def _state_shapes(variant: str):
    cfg = _variants()[variant]
    return jax.eval_shape(lambda k: init_train_state(k, cfg),
                          jax.random.key(0))


# -- the retired hand-built derivation, kept verbatim as the equivalence
# -- oracle: the engine must match it spec-object-for-spec-object ---------

def _oracle_spec_for_leaf(path, leaf, model_size):
    names = [p.key for p in path if hasattr(p, "key")]
    shape = getattr(leaf, "shape", ())
    if not names or len(shape) == 0:
        return P()

    def ok(dim):
        return shape[dim] % model_size == 0

    is_weight = names[-1] == "w"
    if is_weight and len(shape) == 4 and ok(3):
        return P(None, None, None, MODEL_AXIS)
    if is_weight and len(shape) == 2:
        if "proj" in names and ok(1):
            return P(None, MODEL_AXIS)
        if "head" in names and ok(0):
            return P(MODEL_AXIS, None)
    return P()


def _oracle_insert_data_axis(spec, shape, data_size):
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for d, (axis, size) in enumerate(zip(parts, shape)):
        if axis is None and size >= data_size and size % data_size == 0:
            parts[d] = DATA_AXIS
            return P(*parts)
    return spec


def _oracle_state_shardings(state_shapes, mesh, *, spatial=False,
                            shard_opt=False):
    model_size = mesh.shape[MODEL_AXIS]
    data_size = mesh.shape[DATA_AXIS]

    def to_sharding(path, leaf):
        spec = P() if spatial else _oracle_spec_for_leaf(path, leaf,
                                                         model_size)
        if shard_opt and path and getattr(path[0], "key", None) == "opt":
            spec = _oracle_insert_data_axis(spec,
                                            getattr(leaf, "shape", ()),
                                            data_size)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(to_sharding, state_shapes)


class TestRuleEngineEquivalence:
    """The regex table resolved against a mesh == the retired hand-built
    walk, spec OBJECT for spec object (not just placement-equivalent:
    P() vs P(None) would move every committed program fingerprint)."""

    @pytest.mark.parametrize("variant", sorted(_variants()))
    @pytest.mark.parametrize("mesh_cfg,spatial", [
        (MeshConfig(), False),
        (MeshConfig(model=2), False),
        (MeshConfig(model=4), False),
        (MeshConfig(model=2, spatial=True), True),
    ], ids=["dp8", "dp4xtp2", "dp2xtp4", "dp4xsp2"])
    @pytest.mark.parametrize("shard_opt", [False, True],
                             ids=["plain", "zero1"])
    def test_specs_match_oracle(self, variant, mesh_cfg, spatial,
                                shard_opt):
        from dcgan_tpu.parallel.sharding import state_shardings

        shapes = _state_shapes(variant)
        mesh = make_mesh(mesh_cfg)
        want = _oracle_state_shardings(shapes, mesh, spatial=spatial,
                                       shard_opt=shard_opt)
        got = state_shardings(shapes, mesh, spatial=spatial,
                              shard_opt=shard_opt)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                                jax.tree_util.tree_leaves(got)):
            assert a.spec == b.spec, (
                f"{jax.tree_util.keystr(path)}: oracle {a.spec} != "
                f"engine {b.spec}")


class TestRuleTable:
    def test_exact_one_coverage_every_family(self):
        """DCG011's contract, asserted directly: every leaf of every model
        family's full train state matches exactly one rule row."""
        from dcgan_tpu.analysis.semantic import check_spec_coverage

        assert check_spec_coverage() == []

    def test_unmatched_leaf_raises(self):
        with pytest.raises(ValueError, match="no sharding rule matches"):
            rules.logical_spec("params/gen/mystery_layer/q", 2)

    def test_rank_gates_sharded_rows(self):
        """A sharded row applies only at its own rank: a hypothetical
        rank-3 'proj/w' must not silently take the rank-2 projection
        rule."""
        assert rules.matching_rules("params/gen/proj/w", 2)
        assert not rules.matching_rules("params/gen/proj/w", 3)

    def test_ambiguity_detected(self):
        table = ((r"/w$", (None, MODEL_AXIS)),
                 (r"proj/w$", (None, MODEL_AXIS)))
        assert len(rules.matching_rules("a/proj/w", 2, table)) == 2

    def test_opt_and_ema_paths_hit_param_rules(self):
        spec = rules.logical_spec("opt/gen/1/0/mu/proj/w", 2)
        assert tuple(spec) == (None, MODEL_AXIS)
        spec = rules.logical_spec("ema_gen/deconv1/w", 4)
        assert tuple(spec) == (None, None, None, MODEL_AXIS)

    def test_resolution_policies(self):
        mesh_shape = {DATA_AXIS: 4, MODEL_AXIS: 2}
        conv = rules.logical_spec("params/gen/deconv1/w", 4)
        # divisible out-channels shard; a non-divisible dim collapses the
        # WHOLE spec (the old single ok(dim) gate)
        assert rules.resolve_spec(conv, (5, 5, 16, 8), mesh_shape) == \
            (None, None, None, MODEL_AXIS)
        assert rules.resolve_spec(conv, (5, 5, 8, 3), mesh_shape) == ()
        # size-1 model axis keeps the axis name (spec-object parity with
        # the old derivation on data-parallel meshes)
        assert rules.resolve_spec(conv, (5, 5, 8, 3),
                                  {DATA_AXIS: 8, MODEL_AXIS: 1}) == \
            (None, None, None, MODEL_AXIS)
        # an axis the current mesh does not carry replicates
        assert rules.resolve_spec(conv, (5, 5, 16, 8),
                                  {DATA_AXIS: 4}) == ()
        # spatial replicates everything
        assert rules.resolve_spec(conv, (5, 5, 16, 8), mesh_shape,
                                  spatial=True) == ()
        # ZeRO-1 inserts the data axis on the first dividing dim of
        # optimizer-state leaves only
        bias = rules.logical_spec("opt/gen/1/0/mu/proj/b", 1)
        assert rules.resolve_spec(bias, (256,), mesh_shape,
                                  shard_opt=True, is_opt=True) == \
            (DATA_AXIS,)
        assert rules.resolve_spec(bias, (256,), mesh_shape,
                                  shard_opt=True, is_opt=False) == ()

    def test_sidecar_specs_round_trip_through_engine(self):
        """state_partition_specs (what a sidecar would resolve on a target
        mesh) agrees with the NamedSharding tree the backends build."""
        from dcgan_tpu.parallel.sharding import state_shardings

        shapes = _state_shapes("dcgan")
        mesh = make_mesh(MeshConfig(model=2))
        table = rules.state_partition_specs(shapes, dict(mesh.shape))
        sh = state_shardings(shapes, mesh)
        for path, leaf in jax.tree_util.tree_leaves_with_path(sh):
            p = rules.path_str(path)
            assert P(*table[p]) == leaf.spec, p


def _small_tree(mesh: Mesh):
    sh = NamedSharding(mesh, P(DATA_AXIS))
    rep = NamedSharding(mesh, P())
    return {"params": {"gen": {"proj": {
                "w": jax.device_put(
                    jnp.arange(32, dtype=jnp.float32).reshape(8, 4), sh)}}},
            "step": jax.device_put(jnp.asarray(3, jnp.int32), rep)}


class TestSidecar:
    def test_payload_records_topology_and_specs(self):
        payload = sidecar.build_payload(_small_tree(_mesh_of(2)))
        assert payload["version"] == sidecar.VERSION
        assert payload["process_count"] == 1
        assert payload["mesh"] == {"axes": ["data", "model"],
                                   "sizes": [2, 1]}
        assert payload["specs"]["params/gen/proj/w"] == ["data", None]
        assert payload["specs"]["step"] == []

    def test_host_tree_yields_no_payload(self):
        assert sidecar.build_payload({"a": np.zeros(3)}) is None

    def test_mismatch_detection(self):
        tree = _small_tree(_mesh_of(2))
        payload = sidecar.build_payload(tree)
        assert sidecar.topology_mismatch(payload, tree) is None
        assert "8" in sidecar.topology_mismatch(
            payload, _small_tree(_mesh_of(8)))
        bumped = dict(payload, process_count=2)
        assert "processes 2 -> 1" in sidecar.topology_mismatch(bumped, tree)
        # a host tree can state no topology: never a mismatch
        assert sidecar.topology_mismatch(payload,
                                         {"a": np.zeros(3)}) is None


class TestCheckpointerReshard:
    def _ckpt(self, tmp_path):
        from dcgan_tpu.utils.checkpoint import Checkpointer

        return Checkpointer(str(tmp_path / "ck"), async_save=False)

    def test_sidecar_written_beside_manifest(self, tmp_path):
        ck = self._ckpt(tmp_path)
        ck.save(3, _small_tree(_mesh_of(2)))
        ck.wait()
        path = sidecar.sidecar_path(ck.directory, 3)
        assert os.path.exists(path)
        assert os.path.exists(os.path.join(ck.directory, "integrity",
                                           "3.json"))
        ck.close()

    def test_device_path_reshard(self, tmp_path):
        """Same process count, different mesh: the restore read is
        directed at the new NamedShardings; values and target shardings
        both exact."""
        ck = self._ckpt(tmp_path)
        ck.save(3, _small_tree(_mesh_of(2)))
        ck.wait()
        target = _small_tree(_mesh_of(8))
        restored = ck.restore_latest(target)
        assert restored is not None
        assert ck.last_reshard is not None
        assert ck.last_reshard["host_stage"] == 0.0
        assert ck.last_reshard["saved_devices"] == 2.0
        w = restored["params"]["gen"]["proj"]["w"]
        assert w.sharding == target["params"]["gen"]["proj"]["w"].sharding
        np.testing.assert_array_equal(
            np.asarray(w), np.arange(32, dtype=np.float32).reshape(8, 4))
        ck.close()

    def test_host_path_reshard(self, tmp_path):
        """A process-count change (simulated by editing the sidecar — one
        process cannot BE two) takes the host-staged path: numpy restore +
        per-shard upload, same values/shardings."""
        ck = self._ckpt(tmp_path)
        ck.save(3, _small_tree(_mesh_of(2)))
        ck.wait()
        path = sidecar.sidecar_path(ck.directory, 3)
        payload = json.load(open(path))
        payload["process_count"] = 2
        json.dump(payload, open(path, "w"))
        target = _small_tree(_mesh_of(8))
        restored = ck.restore_latest(target)
        assert ck.last_reshard is not None
        assert ck.last_reshard["host_stage"] == 1.0
        w = restored["params"]["gen"]["proj"]["w"]
        assert w.sharding == target["params"]["gen"]["proj"]["w"].sharding
        np.testing.assert_array_equal(
            np.asarray(w), np.arange(32, dtype=np.float32).reshape(8, 4))
        ck.close()

    def test_same_topology_takes_default_path(self, tmp_path):
        ck = self._ckpt(tmp_path)
        ck.save(3, _small_tree(_mesh_of(2)))
        ck.wait()
        restored = ck.restore_latest(_small_tree(_mesh_of(2)))
        assert restored is not None
        assert ck.last_reshard is None  # sidecar present, path untaken
        ck.close()

    def test_reshard_preserves_quarantine_fallback(self, tmp_path):
        """A corrupt newest step still quarantines and falls back on the
        reshard path — the verified-restore contract is topology-blind."""
        from dcgan_tpu.testing.chaos import truncate_file

        ck = self._ckpt(tmp_path)
        tree = _small_tree(_mesh_of(2))
        ck.save(3, tree)
        ck.wait()
        t2 = {"params": {"gen": {"proj": {"w": tree["params"]["gen"][
            "proj"]["w"] * 2}}}, "step": tree["step"]}
        ck.save(4, t2)
        ck.wait()
        files = []
        for root, _, names in os.walk(os.path.join(ck.directory, "4")):
            files += [os.path.join(root, n) for n in names]
        truncate_file(max(files, key=os.path.getsize))
        restored = ck.restore_latest(_small_tree(_mesh_of(8)))
        assert restored is not None
        assert os.path.isdir(os.path.join(ck.directory, "4.corrupt"))
        assert ck.last_reshard is not None  # step 3 resharded
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["gen"]["proj"]["w"]),
            np.arange(32, dtype=np.float32).reshape(8, 4))
        ck.close()

    def test_delete_steps_after_removes_sidecar(self, tmp_path):
        ck = self._ckpt(tmp_path)
        ck.save(3, _small_tree(_mesh_of(2)))
        ck.wait()
        ck.save(5, _small_tree(_mesh_of(2)), force=True)
        ck.wait()
        assert os.path.exists(sidecar.sidecar_path(ck.directory, 5))
        dropped = ck.delete_steps_after(3)
        assert dropped == [5]
        assert not os.path.exists(sidecar.sidecar_path(ck.directory, 5))
        assert os.path.exists(sidecar.sidecar_path(ck.directory, 3))
        ck.close()


class TestZeroCrossStageRestore:
    """ISSUE 13 satellite: a zero_stage=3 checkpoint — params, EMA, and
    both Adam moments resident data-SHARDED over 2 devices — restores at
    zero_stage=1 on 1 device (and vice versa) through the PR 11 reshard
    path, every leaf bit-exact. The sidecar's per-leaf specs already
    carry the information; the ZeRO layout is a placement, not a format.
    Slow: four multi-device ParallelTrain compiles."""

    def _pt(self, stage, ndev):
        from dcgan_tpu.parallel import make_parallel_train

        cfg = TrainConfig(
            model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                              compute_dtype="float32"),
            batch_size=8, mesh=MeshConfig(data=ndev, zero_stage=stage))
        return make_parallel_train(cfg, _mesh_of(ndev))

    @pytest.mark.slow
    @pytest.mark.parametrize("direction", ["zero3to1", "zero1to3"])
    def test_cross_stage_cross_mesh_restore_bit_exact(self, tmp_path,
                                                      direction):
        from dcgan_tpu.utils.checkpoint import Checkpointer

        src_stage, src_dev, dst_stage, dst_dev = \
            (3, 2, 1, 1) if direction == "zero3to1" else (1, 1, 3, 2)
        pt = self._pt(src_stage, src_dev)
        state = pt.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        xs = jnp.asarray(np.tanh(rng.normal(size=(8, 16, 16, 3)))
                         .astype(np.float32))
        for i in range(2):
            state, _ = pt.step(state, xs,
                               jax.random.fold_in(jax.random.key(1), i))
        host_src = jax.device_get(state)
        ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
        ck.save(2, state)
        ck.wait()
        # the sidecar recorded the ZeRO residency as per-leaf specs
        payload = sidecar.read(ck.directory, 2)
        assert payload is not None
        mu_spec = payload["specs"]["opt/disc/1/0/mu/conv1/w"]
        w_spec = payload["specs"]["params/disc/conv1/w"]
        if src_stage >= 3:
            assert any(a == DATA_AXIS or (isinstance(a, list)
                                          and DATA_AXIS in a)
                       for a in mu_spec if a)
            assert any(a == DATA_AXIS or (isinstance(a, list)
                                          and DATA_AXIS in a)
                       for a in w_spec if a)

        pt2 = self._pt(dst_stage, dst_dev)
        target = pt2.init(jax.random.key(7))
        restored = ck.restore_latest(target)
        assert restored is not None
        # the mesh changed (2 <-> 1 devices), so the reshard path ran
        assert ck.last_reshard is not None
        assert ck.last_reshard["saved_devices"] == float(src_dev)
        # restored leaves carry the TARGET stage's shardings...
        mu_t = target["opt"]["disc"][1][0].mu["conv1"]["w"]
        mu_r = restored["opt"]["disc"][1][0].mu["conv1"]["w"]
        assert mu_r.sharding == mu_t.sharding
        # ...and every leaf's VALUES moved bit-exactly
        host_dst = jax.device_get(restored)
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(host_src),
                jax.tree_util.tree_leaves(host_dst)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=jax.tree_util.keystr(path))
        ck.close()


class TestSameTopologyParity:
    """The parity contract (ISSUE 12 satellite): on a SAME-topology
    save/resume, the sidecar machinery must be invisible — the resume's
    event stream is identical whether the sidecar exists or was deleted,
    and elastic/* keys never appear."""

    def _cfg(self, root, **kw):
        return TrainConfig(
            model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                              compute_dtype="float32"),
            batch_size=8, tensorboard=False, sample_every_steps=0,
            activation_summary_steps=0, save_summaries_secs=0.0,
            log_every_steps=1, save_model_secs=1e9,
            checkpoint_dir=str(root / "ckpt"),
            sample_dir=str(root / "samples"), **kw)

    def _events(self, root):
        cleaned = []
        with open(root / "ckpt" / "events.jsonl") as f:
            for line in f:
                e = json.loads(line)
                e.pop("time", None)
                if e["kind"] == "scalars":
                    e["values"] = {k: v for k, v in e["values"].items()
                                   if not k.startswith("perf/")}
                cleaned.append(e)
        return cleaned

    def test_resume_stream_identical_with_and_without_sidecar(
            self, tmp_path):
        from dcgan_tpu.train.trainer import train

        def run(sub, drop_sidecar):
            root = tmp_path / sub
            train(self._cfg(root), synthetic_data=True, max_steps=2)
            if drop_sidecar:
                removed = 0
                int_dir = root / "ckpt" / "integrity"
                for name in os.listdir(int_dir):
                    if name.endswith(".sharding.json"):
                        os.remove(int_dir / name)
                        removed += 1
                assert removed  # the save really produced sidecars
            train(self._cfg(root), synthetic_data=True, max_steps=4)
            return self._events(root)

        with_sidecar = run("with", drop_sidecar=False)
        without = run("without", drop_sidecar=True)
        assert with_sidecar == without
        for e in with_sidecar:
            if e["kind"] == "scalars":
                assert not any(k.startswith("elastic/")
                               for k in e["values"])


@pytest.mark.slow
class TestServeCrossTopology:
    """ISSUE 12 satellite: CheckpointSource cold-starts from a checkpoint
    saved on a DIFFERENT topology (a 2-device subprocess save served on
    the 8-device test mesh), restores through the sidecar reshard, and
    serves samples BIT-equal to the same weights placed directly on the
    serving mesh — the reshard moved bytes, not values."""

    _SAVER = """
import jax; jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
import numpy as np
from dcgan_tpu.config import ModelConfig, TrainConfig
from dcgan_tpu.elastic.rules import path_str
from dcgan_tpu.train.trainer import train
cfg = TrainConfig(model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                                    compute_dtype="float32"),
                  batch_size=8, tensorboard=False, sample_every_steps=0,
                  save_summaries_secs=0.0, log_every_steps=1,
                  save_model_secs=1e9, checkpoint_dir=r"{ck}",
                  sample_dir=r"{sm}")
state = train(cfg, synthetic_data=True, max_steps=1)
flat = {{path_str(p): np.asarray(jax.device_get(v)) for p, v in
        jax.tree_util.tree_flatten_with_path(state)[0]}}
np.savez(r"{npz}", **flat)
print("SAVED", len(flat))
"""

    def test_cross_topology_cold_start_bit_equal(self, tmp_path):
        from dcgan_tpu.serve.buckets import BucketLadder, compile_buckets
        from dcgan_tpu.serve.sources import CheckpointSource

        ck = str(tmp_path / "ck")
        npz = str(tmp_path / "state.npz")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        res = subprocess.run(
            [sys.executable, "-c", self._SAVER.format(
                ck=ck, sm=str(tmp_path / "sm"), npz=npz)],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
        assert res.returncode == 0, (res.stdout[-800:], res.stderr[-800:])

        src = CheckpointSource(ck, max_batch=8)
        meta = src.prepare()
        assert "resharded" in meta, meta
        assert meta["resharded"]["saved_devices"] == 2
        # the resharded state's bytes == the saver's host dump
        host = np.load(npz)
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                src._state)[0]:
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(leaf)),
                host[rules.path_str(path)])
        ladder = BucketLadder([8], granule=src.granule)
        compiled, _ = compile_buckets(src.bucket_plan(ladder))
        src.bind(compiled)
        z = np.random.default_rng(7).uniform(
            -1, 1, (8, 100)).astype(np.float32)
        got = src.sample(8, z)

        # same-topology reference: identical weights placed directly on
        # the serving mesh (no checkpoint, no reshard), same program
        ref_src = CheckpointSource(ck, max_batch=8)
        ref_src.prepare()
        unflat = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(ref_src._state),
            [host[rules.path_str(p)] for p, _ in
             jax.tree_util.tree_flatten_with_path(ref_src._state)[0]])
        ref_src._state = jax.tree_util.tree_map(
            lambda a, like: jax.device_put(a, like.sharding),
            unflat, ref_src._state)
        ref_compiled, _ = compile_buckets(ref_src.bucket_plan(ladder))
        ref_src.bind(ref_compiled)
        ref = ref_src.sample(8, z)
        np.testing.assert_array_equal(got, ref)
