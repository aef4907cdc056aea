"""Checkpoint / resume (Orbax-backed) with integrity verification.

The reference's story (SURVEY.md §3.3, §5): a tf.train.Saver over all
variables (image_train.py:103), Supervisor-driven periodic save every 600 s on
the chief only (image_train.py:123-129), and restore-latest on startup
(image_train.py:141-146,233-245). Same contract here over the train-state
pytree — params, BN running stats, both Adam states, step — with Orbax doing
sharded, async-capable array IO (each host writes its shards; no PS process
holds "the" copy).

Integrity layer (ISSUE 3): Orbax's tmp+rename protocol guarantees a step
directory is COMPLETE, not that its bytes stay GOOD — a post-rename partial
flush on power loss, a filesystem that silently truncates, or plain bit rot
all leave an integer-named dir whose restore dies mid-run with an opaque
array error, and the seed had no fallback. Here every finalized step gets a
checksum manifest (`<dir>/integrity/<step>.json`, size + crc32 per file,
written atomically via tmp+rename, chief-only); `restore_latest` verifies
the newest step against its manifest first, renames a failing step to
`<step>.corrupt` (kept for forensics, invisible to the step scanner), and
falls back to the next-newest intact checkpoint. Steps without a manifest
(legacy dirs, or a crash before the manifest landed) are trusted as before —
verification only ever ADDS protection. Manifest IO runs under
utils/retry.retry_io, so one transient host-IO error does not fail a save.

Single-pass verified restore (ISSUE 5): the seed's restore read every
checkpoint byte TWICE — a sequential checksum pass over the whole step,
then Orbax's leaf payload read of the same files. Restarts are this
trainer's normal fault response (PRs 3-4), so that double full read sat on
the critical path of every recovery. Now `restore_latest` fuses the two:
a size pre-check from stat metadata (zero payload bytes; catches
truncation, the dominant real-world corruption, before anything is
dispatched), a pre-parse checksum of the SMALL structural files (so the
native parser never consumes unverified metadata), then the checksum pass
over the bulk array chunks runs THREAD-POOLED in manifest (tree) order on
background threads while the calling thread runs the Orbax leaf
payload read right behind it — the verifier streams each file into the
page cache and the payload read is served from memory, so the step's bytes
come off storage once and restore wall-clock is max(verify, restore)
instead of their sum. The verification CONTRACT is unchanged: the restore
result is returned only after a clean checksum verdict; a failing verdict
discards it, quarantines the step, and falls back (a restore exception on
a step whose checksums FAIL is corruption evidence; on a step whose
checksums pass it propagates as before). A per-process fingerprint cache
(path, size, mtime_ns -> crc32) shares save-time manifest hashes with
restore-time verification, so a file the process itself just checksummed
is never read again.
"""

from __future__ import annotations

import json
import os
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax

Pytree = Any

INTEGRITY_DIRNAME = "integrity"

# fingerprint -> crc32 cache shared by the manifest writer and the restore
# verifier: (abspath, size, mtime_ns) identifies a file's bytes for the
# atomic-rename files Orbax and the manifest writer produce, so a file this
# process already checksummed (at save time, or an earlier verify) is not
# read again. Process-local, bounded; a changed file changes its
# fingerprint, so stale entries can never match.
_CRC_CACHE: Dict[Tuple[str, int, int], int] = {}
_CRC_CACHE_MAX = 8192

# Files at or under this size are CRC-verified BEFORE the Orbax restore is
# dispatched; only larger files fuse their verification with the payload
# read. The small files are the format's structural metadata (OCDBT
# manifests, _METADATA, sharding records) — feeding corrupt structure to
# the native parser concurrently would trade the old verify-first ordering
# for wall-clock on bytes that are cheap to verify anyway; the array chunk
# files that dominate restore IO stay fused.
_PREPARSE_VERIFY_MAX_BYTES = 1 << 20


def _file_checksum(path: str, chunk: int = 1 << 20) -> Tuple[int, int]:
    """(size, crc32) of one file, streamed."""
    size = 0
    crc = 0
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                break
            size += len(block)
            crc = zlib.crc32(block, crc)
    return size, crc & 0xFFFFFFFF


def _file_checksum_cached(path: str) -> Tuple[int, int, bool]:
    """(size, crc32, served_from_cache) — one disk read per file per
    fingerprint per process."""
    apath = os.path.abspath(path)
    st = os.stat(apath)
    key = (apath, st.st_size, st.st_mtime_ns)
    crc = _CRC_CACHE.get(key)
    if crc is not None:
        return st.st_size, crc, True
    size, crc = _file_checksum(apath)
    if len(_CRC_CACHE) >= _CRC_CACHE_MAX:
        _CRC_CACHE.clear()
    # fingerprint with the POST-read stat only if unchanged mid-read
    st2 = os.stat(apath)
    if (st2.st_size, st2.st_mtime_ns) == (st.st_size, st.st_mtime_ns):
        _CRC_CACHE[key] = crc
    return size, crc, False


def _dir_checksums(step_dir: str) -> Dict[str, Dict[str, int]]:
    """{relative path: {size, crc32}} over every regular file under
    `step_dir` (hashes land in the fingerprint cache, so a same-process
    restore verifies them without re-reading)."""
    out: Dict[str, Dict[str, int]] = {}
    for root, _, files in os.walk(step_dir):
        for name in sorted(files):
            path = os.path.join(root, name)
            rel = os.path.relpath(path, step_dir)
            size, crc, _ = _file_checksum_cached(path)
            out[rel] = {"size": size, "crc32": crc}
    return out


def has_restorable_checkpoint(directory: str) -> bool:
    """True iff `directory` holds at least one completed Orbax step dir.

    Cheap filesystem check — no CheckpointManager construction (which
    would spin up async machinery and create the directory as a side
    effect). Completed Orbax steps are integer-named subdirectories;
    in-flight temp dirs carry an `.orbax-checkpoint-tmp` suffix and fail
    the digit test. Gates config.json adoption in the CLI: a stale config
    from a run that died before its first save must not claim the
    directory (mirror of the trainer's `latest_step() is not None` gate
    on the arch-mismatch check).
    """
    import os

    try:
        entries = os.listdir(directory)
    except OSError:
        return False
    return any(name.isdigit() and os.path.isdir(os.path.join(directory, name))
               for name in entries)


class Checkpointer:
    """save / maybe_save (time-throttled) / restore_latest over a state pytree.

    Only the chief process drives the save cadence (is_chief gating lives in
    the trainer, matching the reference's chief-only Supervisor saver), but
    all processes must enter save() together for multi-host array gather.
    """

    def __init__(self, directory: str, *, save_interval_secs: float = 600.0,
                 save_interval_steps: int = 1000, max_to_keep: int = 5,
                 async_save: bool = True):
        import os

        import orbax.checkpoint as ocp

        self._ocp = ocp
        self.directory = os.path.abspath(directory)
        self._mgr_options = dict(max_to_keep=max_to_keep,
                                 enable_async_checkpointing=async_save)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(**self._mgr_options))
        self.save_interval_secs = save_interval_secs
        self.save_interval_steps = save_interval_steps
        self._next_save = time.time() + save_interval_secs
        # reshard accounting of the last restore (ISSUE 12): None when the
        # same-topology path ran (the default; sidecar present but not
        # needed), else {"reshard_ms", "host_stage", "saved_processes",
        # "saved_devices", "leaves"} — the trainer's elastic/* row and
        # tools/bench_startup.py's cross-topology arm read it
        self.last_reshard: Optional[Dict[str, float]] = None
        # sharding sidecars captured at save() time, written (chief-only)
        # once their step finalizes — see _stash_sidecar
        self._pending_sidecars: Dict[int, Dict] = {}
        # progressive-schedule phase tag (ISSUE 15): the trainer sets this
        # dict ({"phase": i, "resolution": r}) at start and on every phase
        # switch; saves fold it into the sharding sidecar so a resume can
        # cross-check which phase's tree a checkpoint carries. None (the
        # default) leaves the sidecar schema untouched — parity.
        self.progressive_tag: Optional[Dict[str, int]] = None
        # checksum-pass parallelism for the fused verified restore; the
        # env override exists for hosts whose storage saturates earlier
        self.verify_threads = max(1, int(os.environ.get(
            "DCGAN_CKPT_VERIFY_THREADS", "4")))
        # {"files","bytes_read","bytes_cached","verify_ms","restore_ms"}
        # of the last successful VERIFIED restore (None when the restore
        # was unverified or never happened) — the trainer's startup report
        # and tools/bench_startup.py read it
        self.last_restore_stats: Optional[Dict[str, float]] = None

    def save(self, step: int, state: Pytree, *, force: bool = False) -> None:
        self._mgr.save(int(step),
                       args=self._ocp.args.StandardSave(state),
                       force=force)
        # the sharding sidecar (ISSUE 12) is derived from the live tree's
        # NamedShardings NOW (the arrays may be donated away by the next
        # step program) and written once the step FINALIZES, beside its
        # integrity manifest — an in-flight async step has no dir yet and
        # the stale-pruner must keep treating dirless files as garbage
        self._stash_sidecar(step, state)
        # manifest any step finalized by now (with async saves that is the
        # PREVIOUS save — this step's manifest lands on the next call/wait)
        self._write_pending_manifests()

    # -- sharding sidecar (ISSUE 12) -----------------------------------------

    def _stash_sidecar(self, step: int, state: Pytree) -> None:
        """Capture the saving topology for `step`: logical per-leaf specs
        + mesh axis names/sizes + process count (elastic/sidecar.py
        schema). Chief-only like the manifests; host/np trees (no
        NamedShardings) simply get none — absence restores exactly as
        before, same-topology."""
        if jax.process_index() != 0:
            return
        from dcgan_tpu.elastic import sidecar as _sidecar

        payload = _sidecar.build_payload(state)
        if payload is not None:
            tag = getattr(self, "progressive_tag", None)
            if tag:
                # which progressive phase's tree this step carries
                # (ISSUE 15); key absent in fixed-resolution runs
                payload["progressive"] = dict(tag)
            self._pending_sidecars[int(step)] = payload

    def _write_sidecar(self, step: int, payload: Dict) -> None:
        from dcgan_tpu.elastic import sidecar as _sidecar
        from dcgan_tpu.utils.retry import retry_io

        path = _sidecar.sidecar_path(self.directory, step)

        def _write():
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, path)

        retry_io(_write, tag="ckpt-sidecar")

    # -- integrity manifests -------------------------------------------------

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self.directory, INTEGRITY_DIRNAME,
                            f"{int(step)}.json")

    def _finalized_steps(self) -> list:
        """Integer-named step dirs on disk, newest first. Orbax's tmp+rename
        finalize means an integer-named dir is complete; in-flight temp dirs
        carry a suffix and fail the digit test (same contract as
        has_restorable_checkpoint)."""
        try:
            entries = os.listdir(self.directory)
        except OSError:
            return []
        return sorted(
            (int(n) for n in entries if n.isdigit()
             and os.path.isdir(os.path.join(self.directory, n))),
            reverse=True)

    def _write_pending_manifests(self) -> None:
        """Write the checksum manifest for every finalized step that lacks
        one. Chief-only (one writer per shared filesystem); manifest IO —
        not the Orbax array writes — retries transient OSErrors with
        jittered backoff (utils/retry)."""
        if jax.process_index() != 0:
            return
        from dcgan_tpu.utils.retry import retry_io

        # prune manifests AND sharding sidecars whose step Orbax retention
        # already deleted (keep both beside a .corrupt dir — forensics)
        int_dir = os.path.join(self.directory, INTEGRITY_DIRNAME)

        def _stem(name: str) -> str:
            if name.endswith(".sharding.json"):
                return name[:-len(".sharding.json")]
            return name[:-5] if name.endswith(".json") else ""

        try:
            stale = [n for n in os.listdir(int_dir)
                     if _stem(n).isdigit()
                     and not os.path.exists(
                         os.path.join(self.directory, _stem(n)))
                     and not os.path.exists(
                         os.path.join(self.directory,
                                      _stem(n) + ".corrupt"))]
        except OSError:
            stale = []
        for name in stale:
            try:
                os.remove(os.path.join(int_dir, name))
            except OSError:
                pass

        for step in self._finalized_steps():
            # the step's stashed sharding sidecar lands with (before) its
            # manifest — both describe a now-durable step
            payload = self._pending_sidecars.pop(step, None)
            if payload is not None:
                self._write_sidecar(step, payload)
            path = self._manifest_path(step)
            if os.path.exists(path):
                continue
            step_dir = os.path.join(self.directory, str(step))

            def _write(step=step, path=path, step_dir=step_dir):
                manifest = {"step": step, "files": _dir_checksums(step_dir)}
                os.makedirs(os.path.dirname(path), exist_ok=True)
                tmp = f"{path}.{os.getpid()}.tmp"
                with open(tmp, "w") as f:
                    json.dump(manifest, f, indent=1, sort_keys=True)
                os.replace(tmp, path)

            retry_io(_write, tag="ckpt-manifest")

    def _manifest_files(self, step: int
                        ) -> Tuple[Optional[Dict[str, Dict[str, int]]], str]:
        """The step's manifest file table, or (None, why) when the step
        restores UNVERIFIED (no manifest: legacy dirs and crash-before-
        manifest saves keep the seed's restore semantics; an unreadable
        manifest is a manifest-side problem, not evidence against the
        arrays). Manifest IO runs under retry_io — a verification failure
        permanently condemns a step, so transient blips get their bounded
        retries before any verdict."""
        from dcgan_tpu.utils.retry import retry_io

        path = self._manifest_path(step)
        if not os.path.exists(path):
            return None, "no integrity manifest (unverified)"

        def _read_manifest():
            with open(path) as f:
                return json.load(f)

        try:
            return retry_io(_read_manifest, tag="ckpt-verify")["files"], \
                "manifest"
        except (OSError, ValueError, KeyError) as e:
            return None, f"unreadable integrity manifest ({e})"

    def _stat_precheck(self, step: int,
                       files: Dict[str, Dict[str, int]]) -> Optional[str]:
        """Metadata-only screen, tree order: a manifest-listed file that is
        missing or the wrong SIZE is deterministic corruption (truncation /
        deletion — the dominant real-world classes), caught from stat calls
        before a single payload byte is read or any restore collective is
        dispatched. Returns the failure reason or None.

        Retry semantics mirror PR 4's verify fix: a missing file condemns
        immediately (deterministic), but any other stat OSError — an NFS
        hiccup, a momentary EIO — gets retry_io's bounded retries before
        the verdict, because a failing screen permanently quarantines the
        step."""
        from dcgan_tpu.utils.retry import retry_io

        step_dir = os.path.join(self.directory, str(step))
        for rel, rec in files.items():
            fpath = os.path.join(step_dir, rel)
            try:
                size = os.stat(fpath).st_size
            except FileNotFoundError:
                return f"missing file {rel!r}"
            except OSError:
                try:
                    size = retry_io(lambda p=fpath: os.stat(p).st_size,
                                    tag="ckpt-verify")
                except OSError as e:
                    return f"unreadable file {rel!r} ({e})"
            if size != rec["size"]:
                return (f"size mismatch on {rel!r} "
                        f"({size} != {rec['size']})")
        return None

    def _crc_pass(self, step: int, files: Dict[str, Dict[str, int]]
                  ) -> Tuple[bool, str, Dict[str, float]]:
        """Thread-pooled checksum pass over the manifest's files in tree
        (sorted-path) order: (ok, why, stats). Reads stream through the
        fingerprint cache, so bytes this process already hashed (the save-
        time manifest write, an earlier verify) are not re-read; fresh
        reads run under retry_io so only an error that survives the bounded
        retries counts as evidence against the bytes. The verdict reports
        the FIRST failing file in tree order — deterministic across the
        pool's scheduling."""
        from concurrent.futures import ThreadPoolExecutor

        from dcgan_tpu.utils.retry import retry_io

        step_dir = os.path.join(self.directory, str(step))
        t0 = time.perf_counter()

        def _one(item):
            rel, rec = item
            fpath = os.path.join(step_dir, rel)
            try:
                size, crc, cached = retry_io(
                    lambda p=fpath: _file_checksum_cached(p),
                    tag="ckpt-verify")
            except FileNotFoundError:
                return f"missing file {rel!r}", 0, 0
            except OSError as e:
                return f"unreadable file {rel!r} ({e})", 0, 0
            if size != rec["size"]:
                return (f"size mismatch on {rel!r} "
                        f"({size} != {rec['size']})"), 0, 0
            if crc != rec["crc32"]:
                return f"crc32 mismatch on {rel!r}", 0, 0
            return None, (0 if cached else size), (size if cached else 0)

        items = list(files.items())
        n = min(self.verify_threads, max(1, len(items)))
        if n > 1:
            with ThreadPoolExecutor(max_workers=n,
                                    thread_name_prefix="ckpt-crc") as pool:
                results = list(pool.map(_one, items))
        else:
            results = [_one(i) for i in items]
        stats = {
            "files": float(len(items)),
            "bytes_read": float(sum(r[1] for r in results)),
            "bytes_cached": float(sum(r[2] for r in results)),
            "verify_ms": (time.perf_counter() - t0) * 1e3,
        }
        for why, _, _ in results:
            if why is not None:
                return False, why, stats
        return True, "verified", stats

    def _verify_step(self, step: int) -> Tuple[bool, str]:
        """Check a finalized step dir against its manifest: metadata screen
        first (missing/truncated files condemn with zero payload reads),
        then the thread-pooled checksum pass. No manifest = trusted —
        verification only ever adds protection."""
        files, why = self._manifest_files(step)
        if files is None:
            return True, why
        bad = self._stat_precheck(step, files)
        if bad is not None:
            return False, bad
        ok, why, _ = self._crc_pass(step, files)
        return ok, why

    def _mark_corrupt(self, step: int, why: str) -> None:
        """Rename a failing step dir to `<step>.corrupt` (chief-only): the
        step scanner and Orbax both ignore non-integer names, the bytes stay
        on disk for forensics, and the manifest stays beside it."""
        from dcgan_tpu.utils.retry import retry_io

        src = os.path.join(self.directory, str(step))
        dst = f"{src}.corrupt"
        print(f"[dcgan_tpu] checkpoint step {step} failed integrity check "
              f"({why}) — marking {dst} and falling back to the newest "
              f"intact checkpoint", flush=True)
        if jax.process_index() == 0 and os.path.isdir(src):
            # retried (DCG006): a transient rename failure here would
            # abort the very fallback that exists to survive bad bytes
            retry_io(lambda: os.replace(src, dst), tag="ckpt-corrupt-mark")
        try:
            self._mgr.reload()  # drop the manager's cached step metadata
        except Exception:  # older orbax without reload(): rebuild instead
            self._mgr.close()
            self._mgr = self._ocp.CheckpointManager(
                self.directory,
                options=self._ocp.CheckpointManagerOptions(
                    **self._mgr_options))

    def maybe_save(self, step: int, state: Pytree) -> bool:
        """Throttled save — the Supervisor's save_model_secs=600 cadence
        (image_train.py:129).

        Single-process: wall-clock throttle. Multi-host: save() is a
        collective, so the decision must be identical on every process —
        per-process clocks are not, so the cadence switches to the
        deterministic step interval.
        """
        if jax.process_count() > 1:
            if step % self.save_interval_steps != 0:
                return False
        else:
            now = time.time()
            if now < self._next_save:
                return False
            self._next_save = now + self.save_interval_secs
        self.save(step, state)
        return True

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def delete_steps_after(self, step: int) -> list:
        """Remove checkpoints NEWER than `step`; returns the steps dropped.

        Rollback support (train/rollback.py): a save taken between the
        last-good snapshot and the gate trip may embed the divergence the
        gate only caught later (the gate runs every nan_check_steps, not
        every step), and a replayed save at the same step number would
        collide with the stale dir.

        Multi-host (ISSUE 4): every process calls this at the same
        consensus-agreed rollback, but only the chief touches the shared
        filesystem (one deleter, like the manifest writer); the others
        wait at a named barrier so no process can dispatch a replayed save
        into a directory the chief is still deleting, then every manager
        drops its cached step metadata. The in-flight-save wait runs FIRST
        and the barrier is unconditional: the disk listing below is only
        symmetric across processes after every process has finished (and
        Orbax has committed) its async save work — a `dropped`-gated
        barrier could be entered by the process that listed after the
        commit rename and skipped by the one that listed before it."""
        multi = jax.process_count() > 1
        self._mgr.wait_until_finished()  # never race an in-flight save
        dropped = [s for s in self._finalized_steps() if s > step]
        delete_err = None
        if dropped and jax.process_index() == 0:
            import shutil

            from dcgan_tpu.utils.retry import retry_io

            for s in dropped:
                if multi:
                    # raw removal: CheckpointManager.delete is not a
                    # collective contract across orbax versions, and the
                    # reload below resyncs every manager anyway. A FAILED
                    # removal must be loud (matching mgr.delete's raise on
                    # the single-process path): a surviving poisoned-window
                    # dir is exactly the stale-collision / unverified-
                    # restore hazard this method exists to prevent. The
                    # failure is RECORDED, not raised here — the chief must
                    # still reach the verdict allgather below, or the
                    # non-chief processes deadlock in it.
                    try:
                        retry_io(lambda p=os.path.join(
                            self.directory, str(s)): shutil.rmtree(p),
                            tag="ckpt-delete")
                    except OSError as e:
                        delete_err = e
                        break
                else:
                    self._mgr.delete(s)
                # the manifest must die with the step: a REPLAYED save at
                # this step number writes different bytes, and verifying
                # them against the stale manifest would falsely mark the
                # good checkpoint .corrupt at the next restore (the
                # sharding sidecar likewise — a replayed save re-records
                # its topology fresh)
                from dcgan_tpu.elastic import sidecar as _sidecar

                for stale_path in (self._manifest_path(s),
                                   _sidecar.sidecar_path(self.directory,
                                                         s)):
                    try:
                        os.remove(stale_path)
                    except OSError:
                        pass
        if multi:
            import numpy as np
            from jax.experimental import multihost_utils

            # one allgather doubles as the barrier (no process passes this
            # point until all have entered) AND carries the chief's
            # deletion verdict, so success/failure is decided identically
            # on every process — an asymmetric raise above a collective is
            # a deadlock generator
            failed = np.asarray(multihost_utils.process_allgather(
                np.asarray(1 if delete_err is not None else 0,
                           np.int32))).reshape(-1)
            try:
                self._mgr.reload()
            except Exception:  # older orbax: rebuild instead
                self._mgr.close()
                self._mgr = self._ocp.CheckpointManager(
                    self.directory,
                    options=self._ocp.CheckpointManagerOptions(
                        **self._mgr_options))
            if failed.any():
                raise RuntimeError(
                    f"rollback checkpoint cleanup failed on the chief "
                    f"(steps {dropped}): aborting on every process rather "
                    f"than replaying into a stale step dir"
                ) from delete_err
        return dropped

    def restore_latest(self, target_state: Pytree) -> Optional[Pytree]:
        """Restore the newest INTACT checkpoint into the shape/sharding of
        `target_state` (pass the freshly-initialized state); None if no
        checkpoint exists — the reference's load() boolean contract
        (image_train.py:233-245).

        Candidates are tried newest-first: a step whose integrity manifest
        disagrees with the bytes on disk is renamed `<step>.corrupt` and the
        next-newest step is tried — a truncated latest checkpoint costs the
        run its most recent save interval, not the whole run. Steps without
        a manifest restore exactly as before (unverified), and restore-time
        exceptions still propagate — only MANIFEST-proven corruption
        quarantines a step, so a tree/shape mismatch can never silently
        retire good checkpoints.

        SINGLE-PASS (ISSUE 5): bulk verification is fused with the restore
        instead of preceding it. The stat pre-check screens out truncation
        with zero payload reads; small files (the format's structural
        metadata) CRC-verify before the native parser sees them; then the
        thread-pooled checksum pass over the bulk array chunks runs on
        background threads while THIS thread (the one that must own the
        multi-host restore collective) runs Orbax's leaf payload read of
        the same files — bytes come off storage once (the verifier's read
        warms the page cache the payload read is served from) and restore
        wall-clock is max(verify, restore) instead of their sum. The
        restored tree is RETURNED only after a clean checksum verdict; a
        failing verdict discards it and falls back, and a restore
        exception is re-raised only when the checksums PASSED (on a step
        whose checksums fail, the exception is just corruption showing up
        twice). Verdicts stay deterministic across processes — every
        process hashes the same shared-filesystem bytes — so the
        quarantine/fallback branch is taken symmetrically, like before.

        ELASTIC (ISSUE 12): each candidate step's sharding sidecar
        (written at save time beside the integrity manifest) names the
        SAVING topology; when it differs from the target tree's — a
        preempted 32-chip job resuming as 16, a 2-process save resumed by
        1 — the restore RESHARDS instead of failing deep inside the array
        reader. Same process count: the read itself is directed at the
        current NamedShardings (each process pulls exactly its new
        shards). Different process count: the arrays restore host-side
        (numpy, full arrays, no device staging copy) and
        `make_array_from_callback` uploads each device's shard
        (elastic/reshard.py). Verification and quarantine fallback are
        IDENTICAL on both paths; a missing or
        unreadable sidecar — or a matching topology — takes the exact
        pre-elastic path, so same-topology restores are byte-identical in
        behavior (the parity contract). `last_reshard` records the event.
        """
        from dcgan_tpu.elastic import reshard as _reshard
        from dcgan_tpu.elastic import sidecar as _sidecar

        self.last_reshard = None
        abstract = _reshard.device_abstract(target_state)
        for step in self._finalized_steps():
            # topology decision first: zero payload bytes move before the
            # reshard-vs-direct choice is made. The choice itself is
            # elastic/sidecar.restore_decision — shared with the protocol
            # simulator (ISSUE 14), which replays it under a virtual
            # process census and lockstep-audits the branch
            payload = _sidecar.read(self.directory, step)
            path_kind, mismatch = _sidecar.restore_decision(payload,
                                                            target_state)
            step_abstract, assemble, reshard_info = abstract, None, None
            if mismatch is not None:
                saved_procs = int(payload.get("process_count", 1))
                saved_devices = 1
                for s in payload["mesh"]["sizes"]:
                    saved_devices *= int(s)
                host_stage = path_kind == "host"
                if host_stage:
                    step_abstract = _reshard.host_abstract(target_state)
                    assemble = lambda t: _reshard.put_host_tree(
                        t, target_state)
                print(f"[dcgan_tpu] cross-topology restore of step {step}: "
                      f"{mismatch} — resharding via the sharding sidecar "
                      f"({'host-staged' if host_stage else 'device-read'} "
                      f"path)", flush=True)
                reshard_info = {
                    "host_stage": 1.0 if host_stage else 0.0,
                    "saved_processes": float(saved_procs),
                    "saved_devices": float(saved_devices),
                    "leaves": float(len(jax.tree_util.tree_leaves(
                        target_state))),
                }
            files, why = self._manifest_files(step)
            if files is None:
                # unverified restore (legacy/unreadable-manifest step):
                # exactly the seed's semantics, exceptions propagate
                t0 = time.perf_counter()
                restored = self._mgr.restore(
                    step,
                    args=self._ocp.args.StandardRestore(step_abstract))
                if assemble is not None:
                    restored = assemble(restored)
                if reshard_info is not None:
                    reshard_info["reshard_ms"] = \
                        (time.perf_counter() - t0) * 1e3
                    self.last_reshard = reshard_info
                return restored
            bad = self._stat_precheck(step, files)
            if bad is not None:
                self._mark_corrupt(step, bad)
                continue
            # structural metadata (small files: OCDBT manifests, _METADATA,
            # sharding records) verifies BEFORE the native parser ever sees
            # it — only the bulk array chunks, which dominate restore IO,
            # fuse their verification with the payload read
            small = {r: rec for r, rec in files.items()
                     if rec["size"] <= _PREPARSE_VERIFY_MAX_BYTES}
            large = {r: rec for r, rec in files.items()
                     if rec["size"] > _PREPARSE_VERIFY_MAX_BYTES}
            ok, vwhy, stats = self._crc_pass(step, small)
            if not ok:
                self._mark_corrupt(step, vwhy)
                continue
            verdict: List = []
            verifier = None
            if large:
                verifier = threading.Thread(
                    target=lambda: verdict.extend(
                        self._crc_pass(step, large)),
                    name="ckpt-verify", daemon=True)
            t0 = time.perf_counter()
            if verifier is not None:
                verifier.start()
            restored, restore_err = None, None
            try:
                restored = self._mgr.restore(
                    step,
                    args=self._ocp.args.StandardRestore(step_abstract))
                if assemble is not None:
                    # host-staged reshard: upload each device's shard of
                    # the target sharding from the numpy staging tree —
                    # part of the restore wall-clock it replaces
                    restored = assemble(restored)
            except Exception as e:  # verdict decides if this is corruption
                restore_err = e
            restore_ms = (time.perf_counter() - t0) * 1e3
            if verifier is not None:
                verifier.join()
                if not verdict:  # verifier died before producing a verdict
                    if restore_err is not None:
                        raise restore_err
                    raise RuntimeError(
                        f"checkpoint verifier died without a verdict on "
                        f"step {step}")
                ok, vwhy, big_stats = verdict
                for k in ("files", "bytes_read", "bytes_cached",
                          "verify_ms"):
                    stats[k] += big_stats[k]
                if not ok:
                    restored = None  # corrupt bytes — never hand them out
                    self._mark_corrupt(step, vwhy)
                    continue
            if restore_err is not None:
                raise restore_err
            stats["restore_ms"] = restore_ms
            self.last_restore_stats = stats
            if reshard_info is not None:
                reshard_info["reshard_ms"] = restore_ms
                self.last_reshard = reshard_info
            return restored
        return None

    def wait(self) -> None:
        """Block until async saves are durable (and manifest them)."""
        self._mgr.wait_until_finished()
        self._write_pending_manifests()

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._write_pending_manifests()
        self._mgr.close()
