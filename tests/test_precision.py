"""Reduced-precision ladder (ISSUE 17): the `--precision {f32,bf16}`
policy knob and everything downstream of it — config normalization, f32
master-moment layout, the refusal of the retired fp8 rung and kernel
fields (PR 29), the int8 post-training-quantization serving rung, telemetry surfacing, and the bf16 FID-parity
gate. The structural parity gate runs in the smoke tier (the ISSUE's
acceptance requires it in tier-1); the full FID run rides the slow tier."""

import dataclasses

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from dcgan_tpu.config import ModelConfig, TrainConfig, config_from_dict, \
    config_to_dict


def _cfg(precision="", **kw):
    return TrainConfig(
        model=ModelConfig(output_size=16, base_size=4, gf_dim=8, df_dim=8,
                          z_dim=8),
        batch_size=8, precision=precision, max_steps=100, **kw)


class TestPolicyConfig:
    """precision is ONE knob normalized into the model dtype fields at
    construction, so checkpoints and config_from_dict reproduce the same
    model."""

    def test_bf16_policy(self):
        cfg = _cfg("bf16")
        assert cfg.model.compute_dtype == "bfloat16"
        assert cfg.model.param_dtype == "bfloat16"

    def test_f32_policy_overrides_model_default(self):
        # the model's default compute dtype is bfloat16 — precision="f32"
        # must override it (one knob, one meaning), giving a true-f32 arm
        cfg = _cfg("f32")
        assert cfg.model.compute_dtype == "float32"
        assert cfg.model.param_dtype == "float32"

    def test_unset_leaves_model_alone(self):
        cfg = _cfg("")
        assert cfg.model.compute_dtype == "bfloat16"
        assert cfg.model.param_dtype == "float32"

    @pytest.mark.parametrize("precision", ["f32", "bf16"])
    def test_dict_roundtrip_idempotent(self, precision):
        cfg = _cfg(precision)
        cfg2 = config_from_dict(config_to_dict(cfg))
        assert cfg2.precision == precision
        assert cfg2.model == cfg.model

    def test_invalid_precision_raises(self):
        with pytest.raises(ValueError, match="precision"):
            _cfg("fp16")



class TestRetiredNames:
    """PR 29 deleted the Pallas BN kernels, the fused conv blocks and the
    fp8 rung. Asking for any of them is refused, never read as something
    else: the two ModelConfig fields that old files still carry refuse a
    truthy value, and the names that are gone are gone everywhere."""

    @pytest.mark.parametrize("build,exc,match", [
        (lambda: ModelConfig(use_pallas=True, bn_pallas=True),
         ValueError, "removed in PR 29"),
        (lambda: ModelConfig(use_pallas=True, pallas_fused=True),
         ValueError, "removed in PR 29"),
        (lambda: _cfg("fp8"), ValueError, "precision must be one of"),
        (lambda: ModelConfig(quant="fp8"), TypeError, "quant"),
    ], ids=["bn_pallas", "pallas_fused", "precision_fp8", "model_quant"])
    def test_dataclass_refuses(self, build, exc, match):
        with pytest.raises(exc, match=match):
            build()

    @pytest.mark.parametrize("argv", [["--precision", "fp8"],
                                      ["--pallas_fused"]],
                             ids=["precision_fp8", "pallas_fused"])
    def test_cli_refuses(self, argv, capsys):
        from dcgan_tpu.train.cli import build_parser

        with pytest.raises(SystemExit):
            build_parser().parse_args(argv)
        assert argv[-1].lstrip("-") in capsys.readouterr().err

    def test_falsy_values_normalize_to_the_defaults(self):
        # the spellings benchmark/configs/*.json carry
        m = ModelConfig(use_pallas=True, bn_pallas=False, pallas_fused=False)
        assert m == ModelConfig(use_pallas=True)
        assert (m.bn_pallas, m.pallas_fused) == (None, False)

    def test_nothing_reads_the_retired_fields(self):
        """The two fields are names only: no module of the program mentions
        them outside config.py."""
        import os

        import dcgan_tpu

        root = os.path.dirname(dcgan_tpu.__file__)
        hits = []
        for d, _, files in os.walk(root):
            for f in files:
                path = os.path.join(d, f)
                if f.endswith(".py") and path != os.path.join(root,
                                                              "config.py"):
                    text = open(path).read()
                    hits += [(os.path.relpath(path, root), w)
                             for w in ("bn_pallas", "pallas_fused", "fp8",
                                       "float8") if w in text]
        assert not hits


class TestMasterWeights:
    """bf16 keeps an f32 master copy of the Adam FIRST moment
    (mu_dtype=f32); params and the sqrt-bound second moment stay in the
    param dtype. Verified structurally (eval_shape — no compute)."""

    def _state_shapes(self, precision):
        from dcgan_tpu.train.steps import make_train_step

        cfg = _cfg(precision)
        fns = make_train_step(cfg)
        return cfg, fns, jax.eval_shape(fns.init, jax.random.key(0))

    def _leaf_dtypes(self, state, match):
        return [(jtu.keystr(p), l.dtype)
                for p, l in jtu.tree_flatten_with_path(state)[0]
                if match in jtu.keystr(p)]

    def test_bf16_layout(self):
        _, _, state = self._state_shapes("bf16")
        params = self._leaf_dtypes(state["params"], "")
        assert params and all(d == jnp.bfloat16 for _, d in params)
        mu = self._leaf_dtypes(state["opt"], "mu")
        assert mu and all(d == jnp.float32 for _, d in mu)
        nu = self._leaf_dtypes(state["opt"], "nu")
        assert nu and all(d == jnp.bfloat16 for _, d in nu)

    def test_f32_has_no_split_layout(self):
        _, _, state = self._state_shapes("f32")
        for leaves in (self._leaf_dtypes(state["opt"], "mu"),
                       self._leaf_dtypes(state["opt"], "nu")):
            assert leaves and all(d == jnp.float32 for _, d in leaves)

    def test_master_leaf_census(self):
        from dcgan_tpu.elastic.rules import count_master_f32_leaves

        _, _, state = self._state_shapes("bf16")
        n_params = len(jtu.tree_leaves(state["params"]))
        assert count_master_f32_leaves(state) == n_params
        _, _, state_f = self._state_shapes("f32")
        assert count_master_f32_leaves(state_f) == 0
        _, _, state_d = self._state_shapes("")
        assert count_master_f32_leaves(state_d) == 0

    @pytest.mark.parametrize("precision", ["", "f32", "bf16"])
    def test_train_step_dtype_invariance(self, precision):
        # regression for the f32-cotangent bug: a single leaf changing
        # dtype across the step breaks lax.scan carries and donation
        # aliasing. The step must be a dtype-preserving state map under
        # EVERY policy.
        cfg, fns, state = self._state_shapes(precision)
        img = jax.ShapeDtypeStruct((8, 16, 16, 3), jnp.float32)
        out, _ = jax.eval_shape(fns.train_step, state, img,
                                jax.random.key(1))
        ins = {jtu.keystr(p): l for p, l in
               jtu.tree_flatten_with_path(state)[0]}
        bad = [jtu.keystr(p) for p, l in jtu.tree_flatten_with_path(out)[0]
               if ins[jtu.keystr(p)].dtype != l.dtype]
        assert not bad, f"dtype drift across train_step: {bad}"


class TestInt8Serving:
    """Post-training int8 rung (serve/quantize.py): symmetric per-output-
    channel round-trip of the weight kernels; biases/BN leaves exact."""

    def _params(self):
        from dcgan_tpu.models import gan_init

        mcfg = ModelConfig(output_size=16, base_size=4, gf_dim=8, df_dim=8,
                           z_dim=8)
        params, _ = gan_init(jax.random.key(0), mcfg)
        return params

    def test_report_and_error_bound(self):
        from dcgan_tpu.serve.quantize import quantize_dequantize_int8

        params = self._params()
        qp, report = quantize_dequantize_int8(params)
        assert report["scheme"] == "int8-sym-per-channel"
        assert report["quantized_leaves"] > 0
        assert 0 < report["max_rel_error"] < 0.02
        assert report["int8_bytes"] < report["orig_bytes"]
        assert report["worst_leaf"].endswith("/w")

    def test_only_weight_kernels_touched(self):
        from dcgan_tpu.serve.quantize import quantize_dequantize_int8

        params = self._params()
        qp, _ = quantize_dequantize_int8(params)
        for (path, a), (_, b) in zip(
                jtu.tree_flatten_with_path(params)[0],
                jtu.tree_flatten_with_path(qp)[0]):
            p = jtu.keystr(path)
            if p.endswith("['w']"):
                assert not bool(jnp.array_equal(a, b)), p
            else:
                np.testing.assert_array_equal(a, b, err_msg=p)


class TestTelemetry:
    def test_event_keys_registered(self):
        from dcgan_tpu.train.event_keys import EVENT_KEYS

        assert EVENT_KEYS["perf/precision/policy"] == "precision"
        assert EVENT_KEYS["perf/precision/master_f32_leaves"] == "precision"

    def test_counter_snapshot_field(self):
        from dcgan_tpu.utils.metrics import CounterSnapshot

        assert CounterSnapshot().master_f32_leaves == 0

    def test_flight_context_names_policy(self):
        from dcgan_tpu.train.flight_recorder import FlightRecorder
        from dcgan_tpu.train.trainer import _flight_context
        from dcgan_tpu.utils.profiling import StartupProfile

        fl = FlightRecorder("", capacity=0)
        ctx = _flight_context(_cfg("bf16"), StartupProfile(), fl)
        assert ctx["precision"] == "bf16"
        # the default policy must emit NOTHING — crash dumps under the
        # parity-pinned configuration stay byte-stable
        assert "precision" not in _flight_context(_cfg(""), StartupProfile(),
                                                  fl)


# ---------------------------------------------------------------------------
# FID-parity gate: the bf16 arm must land where the f32 arm lands
# ---------------------------------------------------------------------------

def _images(seed, n, size):
    return jnp.tanh(jax.random.normal(jax.random.key(seed), (n, size, size,
                                                             3)))


def _train_arm(precision, steps):
    from dcgan_tpu.train.steps import make_train_step

    fns = make_train_step(_cfg(precision))
    state = jax.jit(fns.init)(jax.random.key(0))
    step = jax.jit(fns.train_step)
    metrics = None
    for i in range(steps):
        state, metrics = step(state, _images(i, 8, 16),
                              jax.random.key(1000 + i))
    return fns, state, metrics


class TestFidParityGate:
    def test_bf16_structural_parity(self):
        """Smoke-tier gate: identical seeds/data, 4 steps per arm — the
        bf16 arm's samples and losses must track the f32 arm closely
        (measured drift ~2e-3 per pixel; bounds carry ~20x margin)."""
        fns_f, state_f, m_f = _train_arm("f32", 4)
        fns_b, state_b, m_b = _train_arm("bf16", 4)
        assert abs(float(m_f["d_loss"]) - float(m_b["d_loss"])) < 0.3
        assert abs(float(m_f["g_loss"]) - float(m_b["g_loss"])) < 0.3
        z = jax.random.uniform(jax.random.key(7), (64, 8),
                               minval=-1.0, maxval=1.0)
        a = np.asarray(fns_f.sample(state_f, z), np.float32)
        b = np.asarray(fns_b.sample(state_b, z), np.float32)
        assert b.dtype == np.float32 and a.shape == b.shape
        assert np.abs(a - b).mean() < 0.05
        assert abs(a.mean() - b.mean()) < 0.02
        assert abs(a.std() - b.std()) < 0.02

    @pytest.mark.slow
    def test_bf16_fid_parity(self):
        """Full gate: FID of each arm against the same synthetic real
        stream — the bf16 arm must score within 15% of f32 (measured gap
        ~0.15%; the bound covers seed-to-seed FID estimator noise)."""
        from dcgan_tpu.evals.job import compute_fid

        def _stream(seed, nb, n, size):
            for i in range(nb):
                yield np.asarray(_images(seed * 100 + i, n, size))

        fids = {}
        for prec in ("f32", "bf16"):
            fns, state, _ = _train_arm(prec, 4)
            r = compute_fid(lambda z: fns.sample(state, z),
                            _stream(9, 4, 64, 16), image_size=16,
                            z_dim=8, num_samples=256, batch_size=64)
            assert np.isfinite(r["fid"]) and r["fid"] > 0
            fids[prec] = r["fid"]
        assert abs(fids["bf16"] - fids["f32"]) <= 0.15 * fids["f32"]
