"""Collective overlap plane (ISSUE 20, DESIGN §6n).

The contract this file pins: `--comm_overlap {bucket,prefetch}` is a
WIRE-PLAN change, never a math change. The bucketed reduce-scatter /
all-gather and the layer-ahead staged param gather must produce
BIT-identical training trajectories to the per-leaf `off` plan — full
params trees compared with np.array_equal after 8 real steps, at every
ZeRO stage, for both the fused step and the pipelined G/D stages. On
top of that: the pack/unpack round trip is exact leaf-for-leaf (mixed
dtypes, leaves larger than the bucket cap), the bucket plan groups by
dtype and respects the cap, the config validation rejects the
impossible arms (prefetch without ZeRO-3, a non-positive cap), the
XLA flag helper never fires on non-TPU hosts, the warmup plan still
covers every program an overlap run can dispatch (rollback drill with
zero compile-cache misses), and the bench A/B row rides before the
headline row with per-arm collective-op censuses.

The census-shrink half of the acceptance (one collective per bucket
instead of one per leaf) is pinned by the committed manifest's
`@overlap` rows, checked in tests/test_zero.py and the analyzer lock
byte-compare in tests/test_tools_analysis_all*.py.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dcgan_tpu.config import MeshConfig, ModelConfig, TrainConfig
from dcgan_tpu.elastic import rules
from dcgan_tpu.parallel import comm, make_parallel_train
from dcgan_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(output_size=16, gf_dim=8, df_dim=8, compute_dtype="float32")


def _mesh2():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:2]).reshape(2, 1),
                (DATA_AXIS, MODEL_AXIS))


def _batch():
    rng = np.random.default_rng(0)
    return jnp.asarray(np.tanh(rng.normal(size=(8, 16, 16, 3)))
                       .astype(np.float32))


# -- pack/unpack round trip (pure data movement, no mesh) -------------------

def _mixed_leaves():
    """Leaves exercising every packing regime: different ranks, different
    scatter dims, a dtype split, and one leaf big enough to overflow a
    tiny cap on its own."""
    rng = np.random.default_rng(7)
    leaves = [
        jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(2, 6, 3)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32)),  # dim 1
        jnp.asarray(rng.integers(0, 9, size=(6, 2)).astype(np.int32)),
        jnp.asarray(rng.normal(size=(512, 8)).astype(np.float32)),  # big
    ]
    dims = [0, 1, 1, 0, 0]
    return leaves, dims


def _dtype_groups(leaves):
    """Index groups per dtype, insertion-ordered — the dtype-purity the
    real bucket plan guarantees (mixed packs would promote)."""
    groups = {}
    for i, x in enumerate(leaves):
        groups.setdefault(str(x.dtype), []).append(i)
    return list(groups.values())


class TestPackUnpackRoundTrip:
    N = 2

    def test_scatter_pack_rows_are_per_shard_blocks(self):
        """Row k of the packed buffer must be exactly the flat of the
        block the per-leaf psum_scatter would hand shard k — that
        equivalence is the whole bit-exactness argument."""
        leaves, dims = _mixed_leaves()
        idxs = [0, 1, 2]
        buf, segs = comm.pack_scatter(leaves, dims, idxs, self.N)
        total = sum(w for _, w, _ in segs)
        view = np.asarray(buf).reshape(self.N, total)
        for k in range(self.N):
            o = 0
            for i, width, moved_shape in segs:
                row = view[k, o:o + width]
                o += width
                moved = np.moveaxis(np.asarray(leaves[i]), dims[i], 0)
                blk = moved.reshape(self.N, -1)[k]
                assert np.array_equal(row, blk), f"leaf {i} shard {k}"

    def test_scatter_unpack_reassembles_leaves_exactly(self):
        """Emulate the collective host-side: shard k keeps row k of the
        packed buffer; unpacking every shard and concatenating the local
        blocks along each leaf's scatter dim must reproduce the input
        bit-for-bit."""
        leaves, dims = _mixed_leaves()
        shards = [[None] * len(leaves) for _ in range(self.N)]
        # one pack per dtype group, exactly like the bucket plan (mixed
        # dtypes in one buffer would force a promoting concatenate)
        for idxs in _dtype_groups(leaves):
            buf, segs = comm.pack_scatter(leaves, dims, idxs, self.N)
            total = sum(w for _, w, _ in segs)
            view = jnp.reshape(buf, (self.N, total))
            for k in range(self.N):
                comm.unpack_scatter(view[k], segs, self.N, dims,
                                    shards[k])
        for i, d in enumerate(dims):
            full = jnp.concatenate([shards[k][i] for k in range(self.N)],
                                   axis=d)
            assert np.array_equal(np.asarray(full),
                                  np.asarray(leaves[i])), f"leaf {i}"
            assert full.dtype == leaves[i].dtype

    def test_gather_round_trip_reassembles_leaves_exactly(self):
        """Split each leaf into its shard-local blocks, pack each
        shard's blocks, emulate the tiled all_gather by concatenating
        the segments, and unpack — every FULL leaf must come back
        bit-identical."""
        leaves, dims = _mixed_leaves()
        out = [None] * len(leaves)
        for idxs in _dtype_groups(leaves):
            segments, segs = [], None
            for k in range(self.N):
                local = [jnp.moveaxis(jnp.split(jnp.moveaxis(x, d, 0),
                                                self.N, axis=0)[k], 0, d)
                         for x, d in zip(leaves, dims)]
                seg, segs = comm.pack_gather(local, dims, idxs)
                segments.append(seg)
            gathered = jnp.concatenate(segments)
            comm.unpack_gather(gathered, segs, self.N, dims, out)
        for i in range(len(leaves)):
            assert np.array_equal(np.asarray(out[i]),
                                  np.asarray(leaves[i])), f"leaf {i}"
            assert out[i].dtype == leaves[i].dtype


class TestBucketPlan:
    MESH = {"data": 2, "model": 1}

    def _shapes(self):
        cfg = TrainConfig(batch_size=8, backend="shard_map",
                          mesh=MeshConfig(data=2, zero_stage=2),
                          model=ModelConfig(**TINY))
        mesh = _mesh2()
        pt = make_parallel_train(cfg, mesh)
        state = jax.eval_shape(lambda: pt.init(jax.random.key(0)))
        return state["params"]["gen"], dict(mesh.shape)

    def test_covers_every_scatter_leaf_exactly_once(self):
        shapes, mesh_shape = self._shapes()
        dims = jax.tree_util.tree_leaves(
            rules.zero_scatter_dims(shapes, mesh_shape))
        plan = rules.zero_bucket_plan(shapes, mesh_shape, bucket_mb=4)
        flat = [i for b in plan for i in b]
        assert len(flat) == len(set(flat))  # no index twice
        scatter = {i for i, d in enumerate(dims) if d >= 0}
        assert set(flat) == scatter  # replicated leaves stay outside

    def test_buckets_are_dtype_pure_and_capped(self):
        shapes, mesh_shape = self._shapes()
        leaves = jax.tree_util.tree_leaves(shapes)
        cap_mb = 1
        plan = rules.zero_bucket_plan(shapes, mesh_shape,
                                      bucket_mb=cap_mb)
        for b in plan:
            dts = {str(np.dtype(leaves[i].dtype)) for i in b}
            assert len(dts) == 1, b  # a cast would break bit-exactness
            nbytes = sum(int(np.prod(leaves[i].shape))
                         * np.dtype(leaves[i].dtype).itemsize for i in b)
            if len(b) > 1:  # single oversized leaves own their bucket
                assert nbytes <= cap_mb * (1 << 20), b

    def test_oversized_leaf_gets_its_own_bucket(self):
        """A leaf bigger than the cap must never merge with neighbors —
        inflate one real scatter-targeted leaf past a 1-MiB cap (scaling
        its scatter dim keeps the rule resolution divisible) and check
        it rides alone."""
        shapes, mesh_shape = self._shapes()
        dims = jax.tree_util.tree_leaves(
            rules.zero_scatter_dims(shapes, mesh_shape))
        leaves, treedef = jax.tree_util.tree_flatten(shapes)
        target = next(i for i, d in enumerate(dims) if d >= 0)
        big = leaves[target]
        shape = list(big.shape)
        itemsize = np.dtype(big.dtype).itemsize
        while int(np.prod(shape)) * itemsize <= (1 << 20):
            shape[dims[target]] *= 2
        leaves[target] = jax.ShapeDtypeStruct(tuple(shape), big.dtype)
        shapes2 = jax.tree_util.tree_unflatten(treedef, leaves)
        plan = rules.zero_bucket_plan(shapes2, mesh_shape, bucket_mb=1)
        bucket = next(b for b in plan if target in b)
        assert bucket == (target,)
        # deterministic for a given (tree, mesh, cap): cache-stable
        assert plan == rules.zero_bucket_plan(shapes2, mesh_shape,
                                              bucket_mb=1)

    def test_nonpositive_cap_raises(self):
        shapes, mesh_shape = self._shapes()
        with pytest.raises(ValueError, match="bucket_mb"):
            rules.zero_bucket_plan(shapes, mesh_shape, bucket_mb=0)


# -- config validation ------------------------------------------------------

class TestConfigValidation:
    def test_prefetch_requires_zero3(self):
        with pytest.raises(ValueError, match="zero_stage=3"):
            TrainConfig(model=ModelConfig(**TINY), batch_size=8,
                        backend="shard_map", comm_overlap="prefetch",
                        mesh=MeshConfig(data=2, zero_stage=2))

    def test_prefetch_at_zero3_is_valid(self):
        cfg = TrainConfig(model=ModelConfig(**TINY), batch_size=8,
                          backend="shard_map", comm_overlap="prefetch",
                          mesh=MeshConfig(data=2, zero_stage=3))
        assert cfg.comm_overlap == "prefetch"

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="comm_overlap"):
            TrainConfig(model=ModelConfig(**TINY), batch_size=8,
                        comm_overlap="aggressive")

    def test_nonpositive_bucket_mb_rejected(self):
        with pytest.raises(ValueError, match="comm_bucket_mb"):
            TrainConfig(model=ModelConfig(**TINY), batch_size=8,
                        comm_overlap="bucket", comm_bucket_mb=0,
                        mesh=MeshConfig(data=2, zero_stage=2))


# -- XLA flag helper --------------------------------------------------------

class TestXlaOverlapFlags:
    def test_noop_without_tpu_runtime(self):
        """On a host without libtpu nothing would read the flags — the
        helper must add NOTHING (and so report nothing armed)."""
        import importlib.util

        if importlib.util.find_spec("libtpu") is not None:
            pytest.skip("host has libtpu; the guard cannot be observed")
        env = {}
        assert comm.maybe_apply_xla_overlap_flags(env) == ()
        assert env == {}

    def test_explicit_non_tpu_platform_suppresses(self):
        """Libtpu presence alone is the wrong gate: a `--platform cpu`
        debug run on a TPU-equipped host never starts libtpu. An
        explicit non-TPU request — platform arg or JAX_PLATFORMS — must
        win over the libtpu probe, so this holds on EVERY host."""
        env = {}
        assert comm.maybe_apply_xla_overlap_flags(env, platform="cpu") == ()
        assert env == {}
        env = {"JAX_PLATFORMS": "cpu"}
        assert comm.maybe_apply_xla_overlap_flags(env) == ()
        assert comm.LIBTPU_FLAGS_VAR not in env
        # the explicit platform arg outranks the env var
        env = {"JAX_PLATFORMS": "tpu"}
        assert comm.maybe_apply_xla_overlap_flags(env, platform="cpu") == ()

    def test_force_appends_all_flags_once(self):
        env = {}
        added = comm.maybe_apply_xla_overlap_flags(env, force=True)
        assert added == comm.XLA_OVERLAP_FLAGS
        # libtpu's own variable: jaxlib aborts on these in XLA_FLAGS,
        # on the chip too
        assert "XLA_FLAGS" not in env
        for f in comm.XLA_OVERLAP_FLAGS:
            assert f in env["LIBTPU_INIT_ARGS"]
        # idempotent: a second call finds every key present
        assert comm.maybe_apply_xla_overlap_flags(env, force=True) == ()

    def test_user_set_keys_are_respected(self):
        key = comm.XLA_OVERLAP_FLAGS[0].split("=", 1)[0]
        env = {comm.LIBTPU_FLAGS_VAR: f"{key}=false"}
        added = comm.maybe_apply_xla_overlap_flags(env, force=True)
        assert comm.XLA_OVERLAP_FLAGS[0] not in added
        assert f"{key}=false" in env[comm.LIBTPU_FLAGS_VAR]
        assert f"{key}=true" not in env[comm.LIBTPU_FLAGS_VAR]


# -- bit-exact training arms ------------------------------------------------

def _run_arm(stage, mode, *, pipeline=False, steps=8):
    cfg = TrainConfig(batch_size=8, backend="shard_map",
                      comm_overlap=mode, comm_bucket_mb=1,
                      pipeline_gd=pipeline,
                      mesh=MeshConfig(data=2, zero_stage=stage),
                      model=ModelConfig(**TINY))
    pt = make_parallel_train(cfg, _mesh2())
    state = pt.init(jax.random.key(0))
    xs = _batch()
    metrics = []
    for i in range(steps):
        state, m = pt.step(state, xs,
                           jax.random.fold_in(jax.random.key(1), i))
        metrics.append({k: float(v) for k, v in m.items()})
    return jax.device_get(state), metrics


def _assert_bit_exact(a, b):
    la, ta = jax.tree_util.tree_flatten_with_path(a["params"])
    lb, _ = jax.tree_util.tree_flatten_with_path(b["params"])
    for (pa, xa), (_, xb) in zip(la, lb):
        assert np.array_equal(np.asarray(xa), np.asarray(xb)), \
            jax.tree_util.keystr(pa)


def _assert_same_gradients_one_ulp_update(a, b):
    """What "the same program in a different wire layout" still pins at
    ZeRO-3 under the installed XLA:CPU, after ONE step: the Adam moments
    — every gradient bit the collectives moved — are identical, and the
    params (with their EMA mirror) agree to one ulp. The stage-3 update
    runs shard-local on whatever layout the arm keeps the shard in, and
    XLA:CPU's fusion emitters round the scalar tail of that elementwise
    loop differently from its vector body (`--xla_cpu_use_fusion_emitters
    =false` restores bit-equality): 3 of deconv2.w's 600 elements move by
    one ulp in the first step. Over 8 steps Adam turns an ulp on a
    near-zero gradient into a sign flip worth lr, so there is no 8-step
    tolerance to state; one step, where the claim is sharp, is compared
    instead."""
    for xa, xb in zip(jax.tree_util.tree_leaves(a["opt"]),
                      jax.tree_util.tree_leaves(b["opt"])):
        assert np.array_equal(np.asarray(xa), np.asarray(xb))
    for key in ("params", "ema_gen"):
        for xa, xb in zip(jax.tree_util.tree_leaves(a[key]),
                          jax.tree_util.tree_leaves(b[key])):
            np.testing.assert_array_max_ulp(np.asarray(xa), np.asarray(xb),
                                            maxulp=1)


class TestBitExactArms:
    """THE acceptance criterion: every overlap arm is the SAME program
    in a different wire layout. 8 real optimizer steps, full params
    trees compared to the last bit against `--comm_overlap off` (at
    stage 3: one step, gradients to the bit and the update to one ulp —
    `_assert_same_gradients_one_ulp_update` says why). The fast tier
    keeps one fused cell per mode; the full stage x mode x dispatch
    matrix is slow (every cell is two fresh 2-device compiles)."""

    @pytest.mark.parametrize("stage,mode,pipeline", [
        pytest.param(2, "bucket", False, id="fused-zero2-bucket"),
        pytest.param(3, "prefetch", False, id="fused-zero3-prefetch"),
        pytest.param(1, "bucket", False, id="fused-zero1-bucket",
                     marks=pytest.mark.slow),
        pytest.param(3, "bucket", False, id="fused-zero3-bucket",
                     marks=pytest.mark.slow),
        pytest.param(1, "bucket", True, id="pipeline-zero1-bucket",
                     marks=pytest.mark.slow),
        pytest.param(2, "bucket", True, id="pipeline-zero2-bucket",
                     marks=pytest.mark.slow),
        pytest.param(3, "bucket", True, id="pipeline-zero3-bucket",
                     marks=pytest.mark.slow),
        pytest.param(3, "prefetch", True, id="pipeline-zero3-prefetch",
                     marks=pytest.mark.slow),
    ])
    def test_arm_bit_exact_vs_off(self, stage, mode, pipeline):
        steps = 1 if stage == 3 else 8
        base, m_off = _run_arm(stage, "off", pipeline=pipeline, steps=steps)
        arm, m_arm = _run_arm(stage, mode, pipeline=pipeline, steps=steps)
        if stage == 3:
            _assert_same_gradients_one_ulp_update(base, arm)
        else:
            _assert_bit_exact(base, arm)
        for a, b in zip(m_off, m_arm):
            assert a == b  # loss stream identical too, step for step

    def test_ema_mirror_bit_exact_at_zero3(self):
        """Stage 3 shards the EMA mirror with the gen plan — the
        bucketed gather must reassemble it identically."""
        base, _ = _run_arm(3, "off")
        arm, _ = _run_arm(3, "bucket")
        for key in ("ema", "opt_g", "opt_d"):
            if key not in base:
                continue
            fa = jax.tree_util.tree_leaves(base[key])
            fb = jax.tree_util.tree_leaves(arm[key])
            for xa, xb in zip(fa, fb):
                assert np.array_equal(np.asarray(xa), np.asarray(xb))


# -- warmup-plan completeness + zero-recompile drill ------------------------

class TestWarmupAndRecompile:
    def _cfg(self, stage, mode, **kw):
        base = dict(batch_size=8, backend="shard_map", comm_overlap=mode,
                    comm_bucket_mb=1,
                    mesh=MeshConfig(data=2, zero_stage=stage),
                    model=ModelConfig(**TINY))
        base.update(kw)
        return TrainConfig(**base)

    @pytest.mark.parametrize("stage,mode", [(2, "bucket"),
                                            (3, "prefetch")])
    def test_plan_covers_overlap_variants(self, stage, mode):
        """build_warmup_plan under an overlap arm must enumerate the
        same program set as `off` — the overlap plane swaps hook bodies
        inside programs, it never adds dispatch surface."""
        from dcgan_tpu.train import warmup

        cfg = self._cfg(stage, mode, steps_per_call=2,
                        nan_policy="rollback", rollback_snapshot_steps=2,
                        rollback_lr_backoff=0.5)
        pt = make_parallel_train(cfg, _mesh2())
        state = pt.init(jax.random.key(0))
        plan, pt_backoff = warmup.build_warmup_plan(
            cfg, pt, state,
            make_backoff_pt=lambda c: make_parallel_train(c, _mesh2()))
        names = [n for n, _, _ in plan]
        assert "train_step" in names
        assert "multi_step@k2" in names
        assert "train_step@lr_backoff" in names
        assert pt_backoff is not None
        assert pt_backoff.cfg.comm_overlap == mode  # backoff keeps arm
        timings = warmup.aot_compile(plan)
        assert set(timings) == set(names)

    @pytest.mark.slow
    def test_rollback_drill_zero_recompiles_under_bucket(self, tmp_path):
        """The zero-recompile contract survives the overlap plane: a
        primed cache + AOT warmup under `--comm_overlap bucket`, then a
        live NaN rollback with LR backoff — the whole drill records
        compile_requests_delta == 0 misses."""
        from dcgan_tpu.testing import chaos
        from dcgan_tpu.train import warmup
        from dcgan_tpu.train.trainer import train

        chaos.reset()
        try:
            # the trainer's mesh must cover the whole device set (8
            # virtual devices under tests/conftest.py), unlike the
            # direct-make_parallel_train tests' 2-device submesh
            kw = dict(batch_size=8, backend="shard_map",
                      comm_overlap="bucket", comm_bucket_mb=1,
                      mesh=MeshConfig(zero_stage=2),
                      model=ModelConfig(**TINY),
                      compile_cache_dir=str(tmp_path / "cache"),
                      aot_warmup=True, nan_policy="rollback",
                      nan_check_steps=1, rollback_snapshot_steps=2,
                      max_rollbacks=2, rollback_lr_backoff=0.5,
                      sample_every_steps=0, save_summaries_secs=0.0,
                      save_model_secs=1e9, log_every_steps=0,
                      tensorboard=False, activation_summary_steps=0)
            train(TrainConfig(checkpoint_dir=str(tmp_path / "p"), **kw),
                  synthetic_data=True, max_steps=3)  # prime, no fault
            mon = warmup.CompileCacheMonitor()
            before = mon.counters()
            chaos.set_plan(chaos.FaultPlan(nan_at_step=3))
            state = train(
                TrainConfig(checkpoint_dir=str(tmp_path / "d"), **kw),
                synthetic_data=True, max_steps=6)
            delta = mon.delta(mon.counters(), before)
            mon.close()
            assert int(jax.device_get(state["step"])) == 6
            assert delta["misses"] == 0, delta
        finally:
            chaos.reset()


# -- bench contract ---------------------------------------------------------

@pytest.mark.slow
class TestBenchCommOverlapAB:
    """ISSUE 20's bench contract: `COMM_OVERLAP=1 ZERO_STAGE=3 python
    bench.py` prints the overlap A/B row BEFORE the headline row (the
    driver parses the last line) with per-arm ms_per_step AND the
    collective-op census — the bucketed arms must issue strictly fewer
    collectives than `off`. Slow tier: several multi-device step
    compiles in a subprocess."""

    def test_overlap_ab_row_before_headline_with_op_counts(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_PLATFORM="cpu",
                   BENCH_BATCH="8", BENCH_STEPS="4", BENCH_WINDOWS="1",
                   BENCH_OVERLAP_STEPS="3", BENCH_DEVSTEP="0",
                   BENCH_SIZE="16", COMM_OVERLAP="1", ZERO_STAGE="3",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        res = subprocess.run([sys.executable, "bench.py"], cwd=REPO,
                             env=env, capture_output=True, text=True,
                             timeout=600)
        assert res.returncode == 0, (res.stdout[-800:], res.stderr[-800:])
        rows = [json.loads(l) for l in res.stdout.splitlines()
                if l.startswith("{")]
        ab = next(r for r in rows if "collective overlap" in r["metric"])
        # precedes the headline (last-line parse contract)
        assert rows.index(ab) < len(rows) - 1
        assert rows[-1]["metric"].endswith("(batch 8/chip, bf16)")
        for arm in ("off", "bucket", "prefetch"):
            assert ab[arm]["ms_per_step"] > 0, arm
            assert ab[arm]["collective_ops_total"] > 0, arm
        # THE census shrink, as numbers in the bench output
        assert (ab["bucket"]["collective_ops_total"]
                < ab["off"]["collective_ops_total"])
        assert (ab["bucket"]["collective_ops"]["reduce_scatter"]
                < ab["off"]["collective_ops"]["reduce_scatter"])
