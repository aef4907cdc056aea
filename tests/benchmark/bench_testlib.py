"""Shared by the benchmark's tests: a temporary root that holds a copy of
`benchmark/` plus tiny cells ADDED as new files and new entries, the way a
later PR adds a cell (no file that is there is edited)."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TIGHT = {"stat_err": 1e-3, "grad_err": 1e-3, "grad_err_worst": 1e-3, "loss_gap": 1e-3,
         "grad_gap": 1e-3, "delta_gap": 1e-3}


def tiny_config(base: str, attn_res: int) -> dict:
    """A shipped configuration cut to 16 px and 8 channels, float32 compute,
    so that program and reference agree to rounding on the CPU."""
    with open(os.path.join(REPO, "benchmark", "configs", base + ".json")) as f:
        conf = json.load(f)
    conf["model"].update(output_size=16, gf_dim=8, df_dim=8,
                         attn_res=attn_res, compute_dtype="float32")
    return conf


def tiny_mix(feed: str, chips: int = 1, per_chip_batch: int = 8) -> dict:
    mix = {"kind": "train", "feed": feed, "per_chip_batch": per_chip_batch,
           "chips": chips, "mesh": {"data": chips, "model": 1},
           "backend": "gspmd", "resident_batches": 4, "in_flight": 2}
    if feed == "records":
        mix.update(shuffle_buffer=16, loader_threads=2,
                   records={"count": 64, "shards": 2, "dtype": "uint8",
                            "seed": 0})
    return mix


def make_root(tmp: str) -> str:
    root = os.path.join(tmp, "root")
    os.makedirs(root)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {
        "tiny_sagan.resident": ("tiny_sagan", "tiny-resident",
                                tiny_mix("resident"), 1),
        "tiny_dcgan.fed": ("tiny_dcgan", "tiny-fed", tiny_mix("records"), 1),
        "tiny_dcgan.resident": ("tiny_dcgan", "tiny-resident",
                                tiny_mix("resident"), 1),
        "tiny_dcgan.dp4": ("tiny_dcgan", "tiny-dp4",
                           tiny_mix("resident", chips=4, per_chip_batch=4), 4),
    }
    configs = {"tiny_sagan": tiny_config("sagan128", 8),
               "tiny_dcgan": tiny_config("dcgan128", 0)}
    for name, conf in configs.items():
        path = f"benchmark/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(conf, f)
        bench["configs"].append({"name": name, "source": "test", "file": path,
                                 "reduced": [], "why": "test"})
    for cell, (config, mix_name, mix, chips) in cells.items():
        with open(os.path.join(root, "benchmark", "traffic",
                               mix_name + ".json"), "w") as f:
            json.dump(mix, f)
        limits = dict(TIGHT, **({"feed_gap": 1e-5} if mix["feed"] == "records"
                                else {}),
                      **({"replica_gap": 0.0} if chips > 1 else {}))
        with open(os.path.join(root, "benchmark", "limits", cell + ".json"),
                  "w") as f:
            json.dump(limits, f)
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": mix_name, "chips": chips,
                                   "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
