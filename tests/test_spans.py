"""Names and spans inside the program (ISSUE 24): the `span` primitive of
utils/profiling.py, the `feed/*` spans of the DevicePrefetcher, the
`train/*` spans of the trainer loop, and the kernel names and model scopes
the lowered step carries."""

import json
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.utils import profiling
from dcgan_tpu.utils.profiling import span, spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "dcgan_tpu")
TRAIN_SPANS = ("train/next", "train/dispatch", "train/consume",
               "train/services")
PALLAS_NAMES = {
    # the windowed call's names (`flash_fwd_win`, `flash_dq_dkv_win`) are
    # these two call sites' under `window`: tests/test_flash_window.py
    "ops/pallas_attention.py": ["flash_fwd", "flash_dq_dkv"],
    "ops/pallas_scan.py": ["ssm_scan_fwd", "ssm_scan_bwd"],
}


def since(t0, name):
    """This test's records: the ring is the process's, shared by tests."""
    return [r for r in spans(name) if r.start >= t0]


class TestSpan:
    def test_records_start_duration_and_count(self):
        t0 = time.perf_counter()
        with span("t/outer") as outer:
            with span("t/inner", count=3):
                pass
            with span("t/inner"):
                pass
        inner = since(t0, "t/inner")
        assert [r.count for r in inner] == [3, None]
        (rec,) = since(t0, "t/outer")
        assert rec.count is None
        assert rec.duration == outer.duration >= sum(r.duration
                                                     for r in inner)
        assert rec.start <= inner[0].start
        assert inner[1].start >= inner[0].start + inner[0].duration
        # all names together come out by start time
        mine = [r.name for r in spans() if r.start >= t0
                and r.name.startswith("t/")]
        assert mine == ["t/outer", "t/inner", "t/inner"]

    def test_ring_is_bounded_per_name(self):
        for i in range(profiling.SPAN_RING + 10):
            with span("t/many", count=i):
                pass
        kept = spans("t/many")
        assert len(kept) == profiling.SPAN_RING
        assert kept[-1].count == profiling.SPAN_RING + 9   # oldest fell off
        with span("t/rare"):
            pass
        assert spans("t/rare")   # a busy name does not push a rare one out

    def test_two_threads_lose_no_record_and_keep_their_order(self):
        t0 = time.perf_counter()
        n, go = 400, threading.Barrier(2)

        def work(tag, base):
            go.wait()
            for i in range(n):
                with span(f"t/{tag}", count=i):
                    with span("t/shared", count=base + i):
                        pass

        threads = [threading.Thread(target=work, args=a)
                   for a in (("a", 0), ("b", n))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        shared = [r.count for r in since(t0, "t/shared")]
        assert len(shared) == 2 * n
        for tag, base in (("a", 0), ("b", n)):
            assert [r.count for r in since(t0, f"t/{tag}")] == list(range(n))
            # each thread's order is kept in the ring the two share
            assert [c for c in shared if base <= c < base + n] == \
                list(range(base, base + n))

    def test_reading_while_another_thread_records(self):
        """No lock guards the rings: a reader gets a whole, ordered copy
        while the recording thread goes on."""
        stop = threading.Event()

        def record():
            i = 0
            while not stop.is_set():
                with span("t/busy", count=i):
                    i += 1

        writer = threading.Thread(target=record)
        writer.start()
        while not spans("t/busy"):
            time.sleep(0.001)
        try:
            for _ in range(200):
                for recs in (spans("t/busy"),
                             [r for r in spans() if r.name == "t/busy"]):
                    counts = [r.count for r in recs]
                    assert counts == list(range(counts[0], counts[0]
                                                + len(counts)))
        finally:
            stop.set()
            writer.join()

    def test_a_block_left_by_an_exception_leaves_no_record(self):
        t0 = time.perf_counter()
        with pytest.raises(KeyError):
            with span("t/raises"):
                raise KeyError("x")
        assert not since(t0, "t/raises")
        with span("t/raises"):   # the next one of the name is recorded
            pass
        assert len(since(t0, "t/raises")) == 1

    def test_span_lies_on_the_profilers_clock(self, tmp_path):
        """In a capture the span is a TraceAnnotation on the host line."""
        jax.profiler.start_trace(str(tmp_path))
        with span("t/in_capture"):
            jnp.ones((8,)).block_until_ready()
        jax.profiler.stop_trace()
        from jax.profiler import ProfileData

        (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
                   for f in fs if f.endswith(".xplane.pb")]
        names = {e.name for p in ProfileData.from_file(path).planes
                 for line in p.lines for e in line.events}
        assert "t/in_capture" in names


class TestFeedSpans:
    @staticmethod
    def _prefetcher(n, depth=2, delay=0.0):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dcgan_tpu.data.pipeline import DevicePrefetcher
        from dcgan_tpu.parallel import make_mesh

        rng = np.random.default_rng(0)

        def host_iter():
            for _ in range(n):
                time.sleep(delay)
                yield rng.uniform(-1, 1, (16, 8, 8, 3)).astype(np.float32)

        sh = NamedSharding(make_mesh(), P("data", None, None, None))
        return DevicePrefetcher(host_iter(), sh, depth=depth)

    def test_one_wait_per_delivered_and_load_h2d_per_produced(self):
        t0 = time.perf_counter()
        pf = self._prefetcher(6)
        out = list(pf)
        pf.close()
        assert len(out) == 6
        wait, load, h2d = (since(t0, n) for n in
                           ("feed/wait", "feed/load", "feed/h2d"))
        assert len(wait) == len(load) == len(h2d) == 6
        assert all(0 <= r.count <= 2 for r in wait)     # depth on entry
        assert all(r.count is None for r in load + h2d)
        # one load and one transfer after the other, on the producer thread
        for a, b in zip(load, h2d):
            assert a.start + a.duration <= b.start

    def test_a_stalled_consumer_shows_as_feed_full(self):
        t0 = time.perf_counter()
        pf = self._prefetcher(5, depth=1)
        deadline = time.time() + 5.0
        while len(since(t0, "feed/h2d")) < 2 and time.time() < deadline:
            time.sleep(0.01)   # the producer runs ahead and parks
        time.sleep(0.05)
        assert len(list(pf)) == 5
        pf.close()
        full = since(t0, "feed/full")
        assert full and max(r.duration for r in full) >= 0.04

    def test_a_slow_loader_shows_as_wait_at_depth_zero(self):
        t0 = time.perf_counter()
        pf = self._prefetcher(4, delay=0.05)
        assert len(list(pf)) == 4
        pf.close()
        wait = since(t0, "feed/wait")
        assert min(r.count for r in wait) == 0
        assert sum(r.duration for r in wait) >= 0.1
        assert not since(t0, "feed/full")

    def test_an_empty_feed_leaves_no_wait_record(self):
        t0 = time.perf_counter()
        pf = self._prefetcher(0)
        assert list(pf) == []
        pf.close()
        assert not since(t0, "feed/wait")   # nothing was delivered


class TestTrainerSpans:
    def test_four_spans_a_step_and_host_ms_is_what_they_sum_to(self,
                                                              tmp_path):
        from dcgan_tpu.config import ModelConfig, TrainConfig
        from dcgan_tpu.train.trainer import train

        steps = 6
        cfg = TrainConfig(
            model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                              compute_dtype="float32"),
            batch_size=8,
            checkpoint_dir=str(tmp_path / "ckpt"),
            sample_dir=str(tmp_path / "samples"),
            sample_every_steps=0, save_summaries_secs=0.0,
            save_model_secs=1e9, log_every_steps=1)
        t0 = time.perf_counter()
        train(cfg, synthetic_data=True, max_steps=steps)

        by_name = {n: since(t0, n) for n in TRAIN_SPANS}
        for name, recs in by_name.items():
            assert len(recs) == steps, name
            assert all(r.count is None for r in recs), name
        # the order of one iteration
        for i in range(steps):
            starts = [by_name[n][i].start for n in TRAIN_SPANS]
            assert starts == sorted(starts)

        # perf/host_ms_mean of the row written for step p is the mean, over
        # ticks 2..p, of (services of the iteration before + consume of this
        # one): StepTimer.note_host is fed from these spans and nothing else
        events = [json.loads(line) for line in
                  open(tmp_path / "ckpt" / "events.jsonl")]
        rows = [e for e in events if e["kind"] == "scalars"
                and "perf/host_ms_mean" in e["values"]]
        assert rows
        consume = by_name["train/consume"]
        services = by_name["train/services"]
        for e in rows:
            p = e["step"]
            host = [services[j - 2].duration + consume[j - 1].duration
                    for j in range(2, p + 1)]
            assert e["values"]["perf/host_ms_mean"] == pytest.approx(
                1e3 * sum(host) / len(host), rel=1e-9), p


def lowered_at_16px(preset):
    """A preset's train step cut to 16 px (attention at 8 x 8) and batch 4,
    lowered with its locations."""
    import dataclasses

    from dcgan_tpu.presets import get_preset
    from dcgan_tpu.train import make_train_step

    cfg = get_preset(preset)
    cfg = dataclasses.replace(
        cfg, batch_size=4, model=dataclasses.replace(
            cfg.model, output_size=16, gf_dim=8, df_dim=8, attn_res=8))
    fns = make_train_step(cfg)
    state = jax.eval_shape(fns.init, jax.random.key(0))
    images = jax.ShapeDtypeStruct((4, 16, 16, 3), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.key(1))
    text = jax.jit(fns.train_step).lower(state, images, key).as_text(
        debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.fixture(scope="module")
def lowered_sagan16():
    return lowered_at_16px("sagan128")


class TestKernelPolicy:
    """The one kernel decision of the image models (PR 29): `use_pallas`
    puts attention on the flash kernels and changes nothing else, so every
    Pallas call of a step is one of the two names `flash_attn_roofline`,
    `flash_fwd_ms` and `flash_bwd_ms` read."""

    @pytest.mark.parametrize("preset", ["sagan64", "sagan128",
                                        "sagan256-lc"])
    def test_a_flash_preset_runs_the_flash_kernels_and_no_other(self,
                                                                preset):
        from dcgan_tpu.presets import get_preset

        assert get_preset(preset).model.use_pallas
        locs = lowered_at_16px(preset)
        kernels = {loc.split("/")[-2] for loc in locs
                   if loc.endswith("/pallas_call")}
        assert kernels == set(PALLAS_NAMES["ops/pallas_attention.py"])


class TestNamesInTheLoweredStep:
    @pytest.mark.parametrize("kernel", PALLAS_NAMES["ops/pallas_attention.py"])
    def test_flash_kernels_carry_their_names(self, lowered_sagan16, kernel):
        hits = [loc for loc in lowered_sagan16
                if loc.endswith(f"/attn/{kernel}/pallas_call")]
        assert hits, kernel
        # forward kernels sit under jvp(net), backward ones under transpose
        assert all(("transpose(" in h) == (kernel != "flash_fwd")
                   for h in hits)

    @pytest.mark.parametrize("scope", [
        "d_step/loss/jvp(disc)/conv1/bn", "g_step/loss/jvp(gen)/deconv1",
        "g_step/loss/jvp(gen)/proj/sn", "d_step/loss/jvp(disc)/head",
        "d_step/adam", "g_step/loss/jvp(gen)/attn", "g_step/adam", "ema"])
    def test_scopes_are_in_the_locations(self, lowered_sagan16, scope):
        assert any(f"jit(train_step)/{scope}/" in loc
                   for loc in lowered_sagan16), scope

    def test_four_forward_and_four_backward_attention_passes(
            self, lowered_sagan16):
        """G's attention forward runs once, in the G half, whose forward
        the D half takes its fake batch from (PR 34; before, it ran in
        the D half too)."""
        def sites(kernel):
            return sorted(loc.split("/attn/")[0].replace(
                "jit(train_step)/", "") for loc in lowered_sagan16
                if loc.endswith(f"/attn/{kernel}/pallas_call"))

        assert sites("flash_fwd") == [
            "d_step/loss/jvp(disc)", "g_step/loss/jvp(disc)",
            "g_step/loss/jvp(gen)"]
        # one location per site; D's real and fake pass share theirs
        assert len(sites("flash_dq_dkv")) == 3


class TestNothingIsLeftUnnamed:
    @pytest.mark.parametrize("path", sorted(PALLAS_NAMES))
    def test_every_pallas_call_has_its_name(self, path):
        src = open(os.path.join(PACKAGE, path)).read()
        calls = re.findall(r"pl\.pallas_call\((.*?)\n    \)\(", src, re.S)
        names = [re.search(r'\bname="(\w+)"', c) for c in calls]
        assert all(names), f"a pallas_call of {path} has no name="
        assert [m.group(1) for m in names] == PALLAS_NAMES[path]

    def test_no_pallas_call_outside_those_files(self):
        found = {}
        for d, _, files in os.walk(PACKAGE):
            for f in files:
                if f.endswith(".py"):
                    n = open(os.path.join(d, f)).read().count(
                        "pl.pallas_call(")
                    if n:
                        found[os.path.relpath(os.path.join(d, f),
                                              PACKAGE)] = n
        assert found == {p: len(n) for p, n in PALLAS_NAMES.items()}

    def test_the_trainer_times_its_host_work_through_spans_only(self):
        src = open(os.path.join(PACKAGE, "train", "trainer.py")).read()
        assert "host_t0" not in src
        for name in TRAIN_SPANS:
            assert f'span("{name}"' in src
        # note_host is fed from the two spans' durations and nothing else
        assert re.findall(r"timer\.note_host\((.*?)\)", src) == \
            ["consume.duration", "services.duration"]
