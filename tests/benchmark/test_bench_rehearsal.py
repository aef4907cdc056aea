"""CPU rehearsal of whole runs at a tiny size (16 px, 8 channels): one
resident and one fed cell through the driver, the last line's keys, and the
refusal to report a device metric from a CPU backend."""

import json
import os
import sys
import time

import pytest

from bench_testlib import REPO, make_root

sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench")))


def rehearse(root, name, tmp, seed=3_000_000_019, trace=False):
    import jax

    cell = manifest.cell(root, name)
    return manifest.driver(root, cell.traffic["kind"]).run(
        cell, seed=seed, seconds=0.3, trace=trace,
        t_start=time.time(), devices=jax.devices(),
        cache_root=os.path.join(str(tmp), "cache"), device_metrics=False)


@pytest.mark.parametrize("name, extra", [
    ("tiny_sagan.resident", ()),         # attention, spectral norm, hinge
    ("tiny_dcgan.fed", ("feed_gap",)),   # records through the native loader
    ("tiny_dcgan.dp4", ("replica_gap",)),   # the readings over a sharded state
])
def test_rehearsal_last_line(root, tmp_path, name, extra):
    line = json.loads(json.dumps(rehearse(root, name, tmp_path)))
    assert list(line)[-1] == "check"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == manifest.cell(root, name).chips
    assert "memory_peak_bytes" in line["device"]
    # a CPU run reports no number under the name of a device metric
    assert line["metrics"] == {}
    assert "train_images_per_s" in line["not_measured"]
    assert line["run"]["compiles_in_window"] == 0
    for number in ("grad_err", "grad_gap", "delta_gap") + extra:
        c = line["check"][number]
        assert c["limit"] is not None and 0 <= c["value"] <= c["limit"]
    # only numbers with a limit take part in a run
    assert set(line["check"]) == set(manifest.cell(root, name).limits)


def test_the_same_seed_gives_the_same_inputs(root):
    import jax
    import numpy as np

    from benchmark import traffic, weights

    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    a, b, c = (traffic.resident_batches(weights.seed_key(s, 1), 2,
                                        (4, 8, 8, 3), sh)
               for s in (2 ** 31 + 11, 2 ** 31 + 11, 2 ** 31 + 12))
    assert np.array_equal(a[0], b[0]) and not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], a[1])
    rows = np.asarray(a[0]).reshape(4, -1)
    assert len({r.tobytes() for r in rows}) == 4        # rows all differ


def test_cli_refuses_a_cpu_backend(monkeypatch, capsys):
    """`run.py` finds no accelerator of the table of peaks here: it exits
    non-zero and prints no result line."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_run_cli", os.path.join(REPO, "benchmark", "run.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    rc = cli.main(["--workload", "dcgan128.resident-b512", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no accelerator" in out.err
    assert cli.main(["--workload", "no_such.cell"]) != 0
    assert capsys.readouterr().out == ""
