"""Cross-seed surrogate-FID rank-stability experiment (VERDICT r3 #3).

Every surrogate-validity trajectory (tools/fid_trajectory.py) uses the one fixed
feature seed (42, evals/features.py). The objection that leaves open:
"your FID is one lucky random projection." This tool kills it with CPU
minutes: train ONE run, snapshot the state at an increasing step ladder,
then score the SAME snapshots under a grid of feature seeds x feature
dims, and report

- per-config Spearman(step, FID): does training order survive every
  random projection, not just seed 42's?
- inter-config rank agreement: pairwise Spearman between the checkpoint
  orderings two feature configs induce — 1.0 means every projection ranks
  the ladder identically.

Prints one JSON line per (seed, dim) config with its scores, then a
summary line {"label": "fid-seed-stability", ...} for capture_all.

    python tools/fid_seed_stability.py --platform cpu \
        --snapshots 0,100,300,600,1000 --num_samples 1024

Workload anchor: the eval duty being replaced, image_train.py:179-192.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.fid_trajectory import _spearman  # noqa: E402


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="fid_seed_stability")
    p.add_argument("--arch", choices=["dcgan", "resnet", "stylegan"],
                   default="dcgan")
    p.add_argument("--snapshots", default="0,100,300,600,1000")
    p.add_argument("--num_samples", type=int, default=1024)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--feature_seeds", default="42,7,123",
                   help="comma-joined embedder seeds (>=3 for the claim)")
    p.add_argument("--feature_dims", default="512,256",
                   help="comma-joined embedder output dims")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", default=None)
    args = p.parse_args(argv)

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from dcgan_tpu.config import ModelConfig, TrainConfig
    from dcgan_tpu.data import synthetic_batches
    from dcgan_tpu.evals.features import make_random_feature_fn
    from dcgan_tpu.evals.job import compute_fid
    from dcgan_tpu.parallel import make_mesh, make_parallel_train
    from dcgan_tpu.train.trainer import train

    snapshots = sorted(int(s) for s in args.snapshots.split(","))
    seeds = [int(s) for s in args.feature_seeds.split(",")]
    dims = [int(d) for d in args.feature_dims.split(",")]
    root = tempfile.mkdtemp(prefix="fid_seed_")

    # the tiny CPU validity config (matches fid_trajectory.py's)
    cfg = TrainConfig(
        model=ModelConfig(arch=args.arch, output_size=16, gf_dim=8,
                          df_dim=8, compute_dtype="float32"),
        batch_size=args.batch_size, seed=args.seed,
        checkpoint_dir=f"{root}/ckpt", sample_dir=f"{root}/samples",
        sample_every_steps=0, save_summaries_secs=1e18,
        save_model_secs=1e18, log_every_steps=0, nan_check_steps=0)
    mcfg = cfg.model
    mesh = make_mesh(cfg.mesh)
    pt = make_parallel_train(cfg, mesh)

    # one growing run; hold a frozen state copy at each rung of the ladder
    states = []
    for target in snapshots:
        if target > 0:
            state = train(cfg, synthetic_data=True, max_steps=target)
        else:
            state = pt.init(jax.random.key(cfg.seed))
        states.append((target, state))
        print(f"snapshot {target} captured", file=sys.stderr)

    # score the whole ladder under every (seed, dim) feature config
    per_config = []
    for fseed, fdim in itertools.product(seeds, dims):
        feature_fn, _ = make_random_feature_fn(
            mcfg.output_size, mcfg.c_dim, feature_dim=fdim, seed=fseed)
        fids = []
        for target, state in states:
            def sample_fn(z, labels=None, _s=state):
                return pt.sample(_s, z, labels) if labels is not None \
                    else pt.sample(_s, z)

            data = synthetic_batches(args.batch_size, mcfg.output_size,
                                     mcfg.c_dim, seed=args.seed + 1, pool=0)
            result = compute_fid(
                sample_fn, data, image_size=mcfg.output_size,
                c_dim=mcfg.c_dim, z_dim=mcfg.z_dim,
                num_samples=args.num_samples, batch_size=args.batch_size,
                seed=args.seed, feature_fn=feature_fn, feature_dim=fdim)
            fids.append(result["fid"])
        sp = _spearman(snapshots, fids)
        row = {"feature_seed": fseed, "feature_dim": fdim,
               "fids": [round(f, 6) for f in fids],
               "spearman_steps_vs_fid": round(sp, 4)}
        per_config.append(row)
        print(json.dumps(row), flush=True)

    # inter-config rank agreement of the checkpoint orderings
    pair_sp = [
        _spearman(a["fids"], b["fids"])
        for a, b in itertools.combinations(per_config, 2)]
    spearmans = [r["spearman_steps_vs_fid"] for r in per_config]
    print(json.dumps({
        "label": "fid-seed-stability",
        "arch": args.arch,
        "snapshots": snapshots,
        "configs": len(per_config),
        "per_config_spearman_min": round(min(spearmans), 4),
        "per_config_spearman_max": round(max(spearmans), 4),
        "inter_config_spearman_min": round(min(pair_sp), 4),
        "inter_config_spearman_mean": round(
            sum(pair_sp) / len(pair_sp), 4),
    }), flush=True)


if __name__ == "__main__":
    main()
