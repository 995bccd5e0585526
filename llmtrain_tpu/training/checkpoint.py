"""Checkpoint save/load/prune/resume-resolution.

Parity target: reference ``src/llmtrain/training/checkpoint.py`` —
``step_{step:06d}`` file naming (:70-71), keep-last-k pruning (default 3,
override via ``trainer.extra.keep_last_k``), payload key validation (:88-92),
``latest_checkpoint`` by parsed step number (:96-103) — and the resume-spec
resolution from reference trainer.py:215-241 (file | dir→latest |
run-id→root/run_id/checkpoints→latest).

TPU design: the payload is a msgpack file of host numpy arrays via
``flax.serialization`` — step, params, opt_state, and the resolved config
(for the mismatch warning, reference trainer.py:315-318). There are NO RNG
states in the payload: dropout keys and data order are pure functions of
(seed, step) in this framework, so restoring ``step`` alone reproduces the
exact stream — this is what makes resume exact under any process count,
where the reference's skip-ahead replay was single-process-only
(reference trainer.py:336-347).

Atomic commit protocol (docs/robustness.md "Crash consistency"): a
checkpoint step is a SET of files (payload + sha-256 sidecar, historically
growing), and a kill can land between any two of their writes. Every save
therefore stages its files (tmp write + fsync + rename) and then publishes
one ``step_N.manifest.json`` — file list with sizes and sha-256 digests,
plus the saving run's mesh/topology and sampler progress — via atomic
rename. The manifest IS the commit: selection (``latest_valid_checkpoint``,
and through it ``resolve_resume_path``) only ever returns manifested steps
whose listed files verify, so a partially committed step is invisible no
matter where the kill landed. ``_prune`` garbage-collects orphaned stages
(torn tmp files, non-verifying unmanifested payloads) and ADOPTS complete
unmanifested payloads by synthesizing their manifest — which is also the
backward-compat path for pre-manifest checkpoint dirs.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
from concurrent.futures import TimeoutError as FuturesTimeoutError
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np
import yaml
from flax import serialization
from flax.linen import meta as nn_meta

CHECKPOINT_VERSION = 1
MANIFEST_VERSION = 1
_STEP_RE = re.compile(r"^step_(\d{6,})\.ckpt$")
_MANIFEST_RE = re.compile(r"^step_(\d{6,})\.manifest\.json$")
_REQUIRED_KEYS = {"checkpoint_version", "step", "params", "opt_state", "config_yaml"}


def sidecar_path(ckpt: Path) -> Path:
    """``step_NNNNNN.ckpt`` → its ``step_NNNNNN.ckpt.sha256`` sidecar."""
    return ckpt.with_name(ckpt.name + ".sha256")


def manifest_path(ckpt: Path) -> Path:
    """``step_NNNNNN.ckpt`` → its ``step_NNNNNN.manifest.json`` commit record."""
    return ckpt.with_name(ckpt.name[: -len(".ckpt")] + ".manifest.json")


def read_manifest(ckpt: Path) -> dict[str, Any] | None:
    """The parsed commit manifest next to ``ckpt``, or None when absent or
    unparseable (pre-manifest checkpoints; a torn manifest tmp never gets
    the final name, so a parse failure here means external damage)."""
    try:
        raw = manifest_path(Path(ckpt)).read_text(encoding="utf-8")
        data = json.loads(raw)
    except (OSError, ValueError):
        return None
    return data if isinstance(data, dict) else None


def _fsync_file(path: Path) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: Path) -> None:
    """Durably record renames in the directory itself. Best-effort: some
    filesystems (and platforms) refuse O_RDONLY fsync on directories."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _read_sidecar_digest(ckpt: Path) -> str | None:
    """Hex digest recorded for ``ckpt``, or None when no sidecar exists.

    Sidecar format is ``sha256sum`` output (``<hex>  <name>``) so integrity
    is also checkable by hand: ``cd checkpoints && sha256sum -c *.sha256``.
    """
    side = sidecar_path(ckpt)
    try:
        first = side.read_text(encoding="utf-8").split()
    except OSError:
        return None
    return first[0].lower() if first else None


def owned_host_copy(x: Any) -> np.ndarray:
    """``np.asarray`` that always OWNS its bytes.

    On the CPU backend ``np.asarray`` of a jax.Array is a zero-copy VIEW
    of the device buffer — the aliasing trap behind both the async
    checkpoint-vs-donation race (see :func:`_to_host`) and the ZeRO
    host-offload round-trip (trainer._opt_state_to_host). One home for
    the copy-when-foreign rule so the two stay in sync."""
    arr = np.asarray(x)
    if arr.base is not None:
        arr = arr.copy()
    return arr


def host_fetch(x: Any) -> np.ndarray:
    """Owned host materialization of ONE leaf: multi-host sharded arrays
    (shards on other processes) gather via ``process_allgather`` — a
    collective, so every process must reach this together — and
    everything else takes the :func:`owned_host_copy` path."""
    if isinstance(x, jax.Array) and not (
        x.is_fully_addressable or x.is_fully_replicated
    ):
        from jax.experimental import multihost_utils

        return owned_host_copy(multihost_utils.process_allgather(x, tiled=True))
    return owned_host_copy(x)


def start_host_transfers(tree: Any) -> None:
    """Kick off every addressable leaf's device→host DMA so subsequent
    ``np.asarray`` materializations pipeline instead of serializing
    leaf-by-leaf (see :func:`_to_host`)."""
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array) and (
            x.is_fully_addressable or x.is_fully_replicated
        ):
            x.copy_to_host_async()


def _to_host(tree: Any) -> Any:
    """Unbox metadata and materialize every leaf as host numpy.

    Multi-host sharded leaves (FSDP/TP params whose shards live on other
    processes) are gathered with ``process_allgather`` — a collective, so
    EVERY process must call this; only the main process then writes (see
    Trainer.fit's save path).
    """
    unboxed = nn_meta.unbox(tree)

    # Phase 1: start every addressable leaf's device→host DMA up front so
    # the transfers pipeline instead of serializing leaf-by-leaf inside
    # np.asarray.
    start_host_transfers(unboxed)
    # The snapshot must OWN its bytes (host_fetch/owned_host_copy): the
    # next train step DONATES the state buffers (donate_argnums=(0,)) and
    # XLA writes the new state into them in place — while the async
    # checkpoint writer may still be serializing a zero-copy view.
    # Result: a checkpoint whose step field says N but whose params are
    # from a later step (caught by the prefetch determinism suite, which
    # removes the host-assembly slack that usually hid the race).
    return jax.tree.map(host_fetch, unboxed)


def state_to_host(state: Any) -> dict[str, Any]:
    """Collective-safe host materialization of a TrainState's saved fields.

    One ``_to_host`` call over both subtrees so ALL leaves' DMAs start
    before any materialization blocks (two calls would serialize opt_state
    behind params — and Adam's opt_state is ~2x the params bytes).

    Gather-on-save is what keeps manifests topology-portable: ZeRO-sharded
    optimizer state (trainer.zero) arrives here as per-replica shards and
    leaves as FULL host arrays — ``np.asarray`` assembles locally-
    addressable shards, ``process_allgather`` covers multi-host ones — so
    a checkpoint restores onto any dp size and any zero on/off setting
    (tests/test_zero.py pins both round-trips).
    """
    host = _to_host({"params": state.params, "opt_state": state.opt_state})
    return {
        "step": int(state.step),
        "params": serialization.to_state_dict(host["params"]),
        "opt_state": serialization.to_state_dict(host["opt_state"]),
    }


class CheckpointError(Exception):
    """Raised for malformed or missing checkpoints."""


class CheckpointManager:
    def __init__(
        self,
        directory: str | Path,
        *,
        keep_last_k: int = 3,
        on_commit: Callable[[int, Path], None] | None = None,
    ) -> None:
        self._dir = Path(directory)
        self._keep_last_k = max(1, keep_last_k)
        self._pending: Any = None  # in-flight async write (Future)
        # Commit observer: called (step, manifest_path) right after the
        # manifest rename lands — from the WRITER thread on async saves, so
        # consumers must be thread-safe (the telemetry registry is). Drives
        # the llmtrain_checkpoint_commits_total counter.
        self.on_commit = on_commit
        # Verification results keyed by (path, size, mtime_ns): pruning and
        # rollback re-verify the same unchanged files every save; hashing a
        # multi-GB checkpoint repeatedly would be pure waste.
        self._verify_cache: dict[tuple[str, int, int], bool] = {}

    @property
    def directory(self) -> Path:
        return self._dir

    def save(self, step: int, state: Any, resolved_config: dict[str, Any]) -> Path:
        """Serialize (step, params, opt_state, config) to ``step_{step:06d}.ckpt``.

        Single-host convenience wrapper; multi-host callers run
        ``state_to_host`` on every process and pass the result to
        ``save_host`` on the main process only.
        """
        host_state = state_to_host(state)
        return self.save_host(step, host_state, resolved_config)

    def save_host(
        self,
        step: int,
        host_state: dict[str, Any],
        resolved_config: dict[str, Any],
        *,
        resilience: dict[str, Any] | None = None,
        manifest_extra: dict[str, Any] | None = None,
        inject_kill: bool = False,
    ) -> Path:
        """Stage + atomically commit one checkpoint step.

        Order of operations (each stage is tmp-write → fsync → rename):
        payload, then sidecar, then the ``step_N.manifest.json`` publish —
        the manifest rename IS the commit point. A kill anywhere before it
        leaves an uncommitted stage that selection never sees and the next
        save's :meth:`_prune` cleans up (or adopts, when the payload is in
        fact complete). ``manifest_extra`` (topology/sampler metadata from
        the trainer) rides in the manifest, not the payload, so resume can
        validate a topology change without deserializing gigabytes.

        ``inject_kill`` is the ``faults.kill_during_checkpoint`` hook: a
        REAL ``SIGKILL`` fired between the staged files and the manifest
        publish, i.e. inside the exact crash window the protocol exists to
        make survivable (resilience/chaos.py drives it).
        """
        self._dir.mkdir(parents=True, exist_ok=True)
        payload = {
            "checkpoint_version": CHECKPOINT_VERSION,
            "step": np.int64(step),
            "params": host_state["params"],
            "opt_state": host_state["opt_state"],
            "config_yaml": yaml.safe_dump(resolved_config, sort_keys=False),
        }
        if resilience:
            # Optional small scalar dict (guard skip counter, rollback
            # bookkeeping, spike-detector EWMA) — not in _REQUIRED_KEYS, so
            # checkpoints stay readable both ways across versions.
            payload["resilience"] = {k: np.asarray(v) for k, v in resilience.items()}
        target = self._dir / f"step_{step:06d}.ckpt"
        blob = serialization.msgpack_serialize(payload)
        digest = hashlib.sha256(blob).hexdigest()
        # Re-saving a step (rollback replay): withdraw the old step before
        # staging the new bytes — a crash mid-rewrite must leave the step
        # unselectable (previous commit restores), never pair stale files
        # with new ones. PAYLOAD FIRST: with the payload gone the step can
        # neither verify against its (momentarily surviving) manifest nor
        # be adopted by the orphan sweep as a pre-rollback snapshot with
        # stale data_offset/rollback bookkeeping — whereas manifest-first
        # would open exactly that window between the two unlinks. A
        # briefly-dangling manifest fails verification closed and is
        # garbage-collected by the next prune.
        target.unlink(missing_ok=True)
        sidecar_path(target).unlink(missing_ok=True)
        manifest_path(target).unlink(missing_ok=True)
        tmp = target.with_suffix(".ckpt.tmp")
        tmp.write_bytes(blob)
        _fsync_file(tmp)
        tmp.replace(target)
        side = sidecar_path(target)
        side_body = f"{digest}  {target.name}\n"
        side_tmp = side.with_name(side.name + ".tmp")
        side_tmp.write_text(side_body, encoding="utf-8")
        _fsync_file(side_tmp)
        side_tmp.replace(side)
        if inject_kill:
            from ..utils.logging import get_logger

            get_logger().warning(
                "fault injection: SIGKILL inside the checkpoint write at "
                "step %d (staged files present, manifest NOT published)",
                step,
            )
            import signal

            os.kill(os.getpid(), signal.SIGKILL)
        self._publish_manifest(
            target,
            [(target.name, len(blob), digest), _file_entry(side)],
            manifest_extra,
        )
        stat = target.stat()
        self._verify_cache[(str(target), stat.st_size, stat.st_mtime_ns)] = True
        # Seed the manifest-keyed cache too (verify_manifest keys on the
        # manifest path + payload stat): the first selection scan after a
        # save — e.g. the rollback restore-point search — must not re-read
        # and re-hash the multi-GB payload it just wrote.
        self._verify_cache[
            (str(manifest_path(target)), stat.st_size, stat.st_mtime_ns)
        ] = True
        if self.on_commit is not None:
            try:
                self.on_commit(step, manifest_path(target))
            except Exception:  # noqa: BLE001 — observer must not fail the save
                pass
        self._prune()
        return target

    def _publish_manifest(
        self,
        target: Path,
        files: list[tuple[str, int, str]],
        manifest_extra: dict[str, Any] | None,
        *,
        synthesized: bool = False,
    ) -> Path:
        """Atomic-rename publish of the commit record for ``target``."""
        step = int(_STEP_RE.match(target.name).group(1))
        manifest = {
            "manifest_version": MANIFEST_VERSION,
            "step": step,
            "files": [
                {"name": name, "bytes": size, "sha256": digest}
                for name, size, digest in files
            ],
        }
        if synthesized:
            # Pre-manifest checkpoint adopted on first scan/prune: no
            # topology metadata exists, so elastic validation treats the
            # saved topology as unknown (resume proceeds, no reshard check).
            manifest["synthesized"] = True
        if manifest_extra:
            manifest.update(manifest_extra)
        mpath = manifest_path(target)
        mtmp = mpath.with_name(mpath.name + ".tmp")
        mtmp.write_text(json.dumps(manifest, indent=1, sort_keys=False), encoding="utf-8")
        _fsync_file(mtmp)
        mtmp.replace(mpath)
        _fsync_dir(self._dir)
        return mpath

    def save_host_async(
        self,
        step: int,
        host_state: dict[str, Any],
        resolved_config: dict[str, Any],
        *,
        resilience: dict[str, Any] | None = None,
        manifest_extra: dict[str, Any] | None = None,
        inject_kill: bool = False,
    ) -> None:
        """Queue ``save_host`` on a background thread (one write in flight).

        The device→host gather has already happened in ``state_to_host``, so
        the remaining msgpack serialization + disk IO can overlap the next
        training steps — the reference's ``torch.save`` blocks the step loop
        (reference trainer.py:402-413). At most one write runs at a time;
        queueing a new one first drains (and re-raises errors from) the
        previous. Call ``wait_pending`` before reading checkpoints back.

        A plain DAEMON thread + Future, deliberately not ThreadPoolExecutor:
        executor workers are non-daemon and joined by an atexit hook, so a
        write wedged on dead storage would deadlock interpreter exit even
        after ``close(timeout)`` "abandoned" it — the abort-path contract
        (docs/robustness.md) requires the process to actually get out.
        """
        import threading
        from concurrent.futures import Future

        self.wait_pending()
        future: Future = Future()

        def work() -> None:
            # False = wait_pending cancelled the write before we started.
            if not future.set_running_or_notify_cancel():
                return
            try:
                future.set_result(
                    self.save_host(
                        step,
                        host_state,
                        resolved_config,
                        resilience=resilience,
                        manifest_extra=manifest_extra,
                        inject_kill=inject_kill,
                    )
                )
            except BaseException as exc:  # noqa: BLE001 — delivered via result()
                future.set_exception(exc)

        threading.Thread(target=work, name="ckpt-write", daemon=True).start()
        self._pending = future

    def poll(self) -> None:
        """Non-blocking failure check: if the in-flight async write has
        already finished with an error, re-raise it now. Called by the
        trainer each log interval so a failed write surfaces within one
        interval instead of at the next save or at close()."""
        pending = self._pending
        if pending is not None and pending.done():
            self._pending = None
            pending.result()

    def wait_pending(self, timeout: float | None = None) -> bool:
        """Block until the in-flight async write (if any) finishes; re-raise
        its error. With a ``timeout``, give up after that many seconds and
        return False, leaving the write in flight — abort/watchdog exit
        paths must never deadlock behind a write wedged on dead storage.
        Returns True when nothing is (any longer) pending."""
        pending = self._pending
        if pending is None:
            return True
        if timeout is not None and not pending.done():
            # A queued-but-unstarted write can simply be withdrawn — but
            # loudly, same as the timeout path: a checkpoint that silently
            # never lands makes the next resume inexplicable.
            if pending.cancel():
                from ..utils.logging import get_logger

                get_logger().error(
                    "queued async checkpoint write cancelled before it "
                    "started (bounded drain); the newest on-disk checkpoint "
                    "may be one save behind"
                )
                self._pending = None
                return True
        self._pending = None
        try:
            pending.result(timeout)
        except FuturesTimeoutError:
            # Still running: put it back so a later unbounded drain (or a
            # repeat bounded attempt) can still observe its outcome.
            self._pending = pending
            return False
        return True

    def close(self, timeout: float | None = None) -> None:
        """Drain the pending write. A ``timeout`` bounds the drain: on
        expiry the write is ABANDONED (logged as an error; the daemon
        writer thread cannot block process exit) instead of deadlocking —
        the abort-path contract (docs/robustness.md)."""
        try:
            drained = self.wait_pending(timeout)
            if not drained:
                from ..utils.logging import get_logger

                get_logger().error(
                    "async checkpoint write still in flight after %.1fs; "
                    "abandoning it (the newest on-disk checkpoint may be one "
                    "save behind)",
                    timeout,
                )
        finally:
            self._pending = None

    def _prune(self) -> None:
        """Keep the last k checkpoints by step — but NEVER delete the newest
        VERIFIED one. Retention keyed on file count alone would, with a
        corrupt newest file, delete the only restorable checkpoint and leave
        the run with nothing but garbage to resume from.

        Also garbage-collects orphaned commit stages: leftover ``*.tmp``
        files and unmanifested payloads whose write was cut before the
        manifest publish. An unmanifested payload that VERIFIES (the kill
        landed after its fsync'd rename) is a complete snapshot of the same
        deterministic trajectory — it is adopted via a synthesized manifest
        instead of deleted, which is also how pre-manifest checkpoint dirs
        migrate in place."""
        self._collect_orphans()
        ckpts = self.all_checkpoints()
        doomed = ckpts[: -self._keep_last_k]
        if not doomed:
            return
        newest_valid = next(
            (p for p in reversed(ckpts) if self.verify(p)), None
        )
        for path in doomed:
            if path == newest_valid:
                continue
            path.unlink(missing_ok=True)
            sidecar_path(path).unlink(missing_ok=True)
            manifest_path(path).unlink(missing_ok=True)

    def _collect_orphans(self) -> None:
        """Sweep uncommitted stage leftovers (see :meth:`_prune`). Only
        called between writes of THIS manager — writes are serialized (one
        async write in flight, drained before the next queues), so any tmp
        file or unmanifested payload found here is a dead stage, not an
        in-flight one."""
        if not self._dir.is_dir():
            return
        from ..utils.logging import get_logger

        manifested = {
            int(_MANIFEST_RE.match(p.name).group(1))
            for p in self._dir.iterdir()
            if _MANIFEST_RE.match(p.name)
        }
        if not manifested:
            # Pre-manifest directory: nothing to reconcile against; legacy
            # selection (and synthesis on scan) handles it.
            return
        for path in list(self._dir.iterdir()):
            if path.name.endswith(".tmp"):
                path.unlink(missing_ok=True)
                continue
            mm = _MANIFEST_RE.match(path.name)
            if mm and not (
                self._dir / f"step_{int(mm.group(1)):06d}.ckpt"
            ).is_file():
                # Manifest whose payload vanished (external deletion):
                # a dangling commit record must not shadow older steps.
                path.unlink(missing_ok=True)
                continue
            m = _STEP_RE.match(path.name)
            if not m or int(m.group(1)) in manifested:
                continue
            if self.verify(path):
                try:
                    self.synthesize_manifest(path)
                    get_logger().warning(
                        "adopted unmanifested checkpoint %s (complete payload "
                        "whose commit was interrupted): synthesized its manifest",
                        path.name,
                    )
                except OSError:
                    pass
            else:
                get_logger().warning(
                    "garbage-collecting torn uncommitted checkpoint stage %s",
                    path.name,
                )
                path.unlink(missing_ok=True)
                sidecar_path(path).unlink(missing_ok=True)

    def synthesize_manifest(self, ckpt: str | Path) -> Path:
        """Write a commit manifest for an existing (verifying) payload —
        the backward-compat path for pre-manifest checkpoints, and the
        adoption path for complete-but-uncommitted stages."""
        ckpt = Path(ckpt)
        files = [_file_entry(ckpt)]
        side = sidecar_path(ckpt)
        if side.is_file():
            files.append(_file_entry(side))
        return self._publish_manifest(ckpt, files, None, synthesized=True)

    def verify(self, path: str | Path) -> bool:
        """True when ``path`` is a restorable checkpoint.

        With a sha-256 sidecar present the file digest must match; without
        one (pre-integrity checkpoints, or a crash between payload and
        sidecar rename) fall back to a deep parse — msgpack restore plus the
        required-key check. Results are cached by (path, size, mtime).
        """
        path = Path(path)
        try:
            stat = path.stat()
        except OSError:
            return False
        key = (str(path), stat.st_size, stat.st_mtime_ns)
        cached = self._verify_cache.get(key)
        if cached is not None:
            return cached
        ok = _verify_uncached(path)
        self._verify_cache[key] = ok
        return ok

    def verify_manifest(self, ckpt: str | Path) -> bool:
        """True when ``ckpt``'s commit manifest exists and every listed
        file is present with the recorded size and (for the payload) the
        recorded sha-256. Results are cached by the payload's
        (path, size, mtime) alongside the sidecar-based cache."""
        ckpt = Path(ckpt)
        manifest = read_manifest(ckpt)
        if manifest is None:
            return False
        try:
            stat = ckpt.stat()
        except OSError:
            return False
        key = (str(manifest_path(ckpt)), stat.st_size, stat.st_mtime_ns)
        cached = self._verify_cache.get(key)
        if cached is not None:
            return cached
        ok = _manifest_files_ok(self._dir, manifest)
        self._verify_cache[key] = ok
        return ok

    def all_manifests(self) -> list[Path]:
        """Committed steps' payload paths (manifest present), sorted by
        step, oldest first. The payload file itself may be missing or
        damaged — :meth:`verify_manifest` decides restorability."""
        if not self._dir.is_dir():
            return []
        found = []
        for path in self._dir.iterdir():
            m = _MANIFEST_RE.match(path.name)
            if m:
                step = int(m.group(1))
                found.append((step, self._dir / f"step_{step:06d}.ckpt"))
        return [p for _, p in sorted(found)]

    def latest_valid_checkpoint(self, *, before_step: int | None = None) -> Path | None:
        """Newest COMMITTED checkpoint whose manifest verifies, scanning
        backward past damaged steps (each skip logs a warning).

        Selection is manifest-driven: in a directory with commit manifests,
        a payload without one is an uncommitted stage — invisible here no
        matter how intact its bytes look, which is what makes the multi-file
        commit atomic. Directories with NO manifests at all are pre-manifest
        layouts: they fall back to per-file verification (sidecar digest or
        deep parse) and every file that verifies gets a manifest synthesized
        in place, so the dir is migrated by its first scan.

        ``before_step`` restricts the scan to checkpoints saved strictly
        before that step — the loss-spike rollback uses it so a periodic
        save that landed inside the spiking window (valid by integrity,
        poisoned by value) cannot become the restore point; with the
        restriction active, no fallback applies and None means "nothing
        restorable".

        Unrestricted scans where NO file verifies fall back to the plain
        newest so legacy layouts and hand-assembled dirs still resolve — a
        genuinely broken file then fails at ``load`` with a precise error.
        """

        def step_of(p: Path) -> int:
            return int(_STEP_RE.match(p.name).group(1))

        from ..utils.logging import get_logger

        manifests = self.all_manifests()
        if manifests:
            candidates = manifests
            if before_step is not None:
                candidates = [p for p in candidates if step_of(p) < before_step]
            for path in reversed(candidates):
                if self.verify_manifest(path):
                    return path
                get_logger().warning(
                    "checkpoint %s failed integrity verification against its "
                    "commit manifest; falling back to the previous one",
                    path,
                )
            if before_step is not None:
                return None
            # Every committed step is damaged: degrade to the legacy
            # per-file scan below rather than returning nothing for a dir
            # that may still hold a restorable unmanifested payload.
        ckpts = self.all_checkpoints()
        if before_step is not None:
            ckpts = [p for p in ckpts if step_of(p) < before_step]
        for path in reversed(ckpts):
            if self.verify(path):
                if read_manifest(path) is None:
                    # Backward compat: adopt the pre-manifest checkpoint so
                    # later scans (and the atomic-commit invariants) see a
                    # committed step. Best-effort — a read-only snapshot
                    # dir still resolves, it just stays unmigrated.
                    try:
                        self.synthesize_manifest(path)
                    except OSError:
                        pass
                return path
            get_logger().warning(
                "checkpoint %s failed integrity verification; "
                "falling back to the previous one",
                path,
            )
        if before_step is not None:
            return None
        return ckpts[-1] if ckpts else None

    def all_checkpoints(self) -> list[Path]:
        """Checkpoints sorted by parsed step number, oldest first."""
        if not self._dir.is_dir():
            return []
        found = []
        for path in self._dir.iterdir():
            m = _STEP_RE.match(path.name)
            if m:
                found.append((int(m.group(1)), path))
        return [p for _, p in sorted(found)]

    def latest_checkpoint(self) -> Path | None:
        ckpts = self.all_checkpoints()
        return ckpts[-1] if ckpts else None

    @staticmethod
    def load(path: str | Path) -> dict[str, Any]:
        """Read and validate a checkpoint payload (host numpy trees).

        When a sha-256 sidecar exists the file content is verified against
        it first, so a truncated or bit-flipped checkpoint fails with a
        precise integrity error instead of a deep msgpack traceback (or —
        worse — silently restoring garbage arrays).
        """
        path = Path(path)
        if not path.is_file():
            raise CheckpointError(f"Checkpoint file not found: {path}")
        blob = path.read_bytes()
        expected = _read_sidecar_digest(path)
        if expected is not None:
            actual = hashlib.sha256(blob).hexdigest()
            if actual != expected:
                raise CheckpointError(
                    f"Checkpoint {path} failed sha-256 integrity verification "
                    f"(expected {expected[:12]}…, got {actual[:12]}…): the file "
                    "is truncated or corrupt"
                )
        try:
            payload = serialization.msgpack_restore(blob)
        except Exception as exc:
            raise CheckpointError(
                f"Checkpoint {path} is not a parseable msgpack payload "
                f"(truncated or corrupt): {exc}"
            ) from exc
        if not isinstance(payload, dict):
            raise CheckpointError(
                f"Checkpoint {path} does not hold a payload mapping"
            )
        missing = _REQUIRED_KEYS - set(payload)
        if missing:
            raise CheckpointError(
                f"Checkpoint {path} is missing required keys: {sorted(missing)}"
            )
        return payload


def _file_entry(path: Path) -> tuple[str, int, str]:
    """(name, size, sha256) manifest entry for an existing file."""
    blob = path.read_bytes()
    return (path.name, len(blob), hashlib.sha256(blob).hexdigest())


def _manifest_files_ok(directory: Path, manifest: dict[str, Any]) -> bool:
    """Every file the manifest lists exists with the recorded size and
    digest. Malformed manifests (wrong shapes, non-numeric sizes, junk
    digest values) fail CLOSED — the backward scan must fall back to the
    previous step, never crash mid-resolution."""
    try:
        files = manifest.get("files")
        if not isinstance(files, list) or not files:
            return False
        for entry in files:
            if not isinstance(entry, dict):
                return False
            name = entry.get("name")
            if not isinstance(name, str) or "/" in name or name.startswith("."):
                return False
            path = directory / name
            try:
                blob = path.read_bytes()
            except OSError:
                return False
            size = entry.get("bytes")
            if size is not None and len(blob) != int(size):
                return False
            digest = entry.get("sha256")
            if (
                digest is not None
                and hashlib.sha256(blob).hexdigest() != str(digest).lower()
            ):
                return False
    except (TypeError, ValueError):
        return False
    return True


def _verify_uncached(path: Path) -> bool:
    """One verification pass: sidecar digest when present, deep parse
    (msgpack restore + required keys) otherwise."""
    try:
        blob = path.read_bytes()
    except OSError:
        return False
    expected = _read_sidecar_digest(path)
    if expected is not None:
        return hashlib.sha256(blob).hexdigest() == expected
    try:
        payload = serialization.msgpack_restore(blob)
    except Exception:
        return False
    return isinstance(payload, dict) and not (_REQUIRED_KEYS - set(payload))


def load_inference_params(
    path: str | Path,
    abstract_params: Any,
    *,
    expected_config_yaml: str | None = None,
    device: bool = True,
) -> tuple[Any, int]:
    """Restore just the model params (no optimizer state) from a checkpoint.

    ``abstract_params`` is an unboxed ``jax.eval_shape`` tree of the model's
    parameters; it supplies the pytree structure that the flat state dict is
    mapped back onto. Returns ``(params_on_device, step)`` — the inference
    path for the ``generate`` CLI, which the reference only offers as eager
    notebook cells (reference notebooks/trained_vs_random_completion.ipynb).
    ``device=False`` keeps host numpy (host-side consumers like
    checkpoint averaging skip a full device round-trip per input).

    When ``expected_config_yaml`` is given and differs from the config stored
    in the checkpoint, a warning is logged — the same warn-and-continue
    contract as the resume path (reference trainer.py:315-318).
    """
    import jax.numpy as jnp

    payload = CheckpointManager.load(path)
    if expected_config_yaml is not None:
        warn_on_config_mismatch(payload, expected_config_yaml, path)
    host_params = serialization.from_state_dict(abstract_params, payload["params"])
    if not device:
        return host_params, int(payload["step"])
    params = jax.tree.map(jnp.asarray, host_params)
    return params, int(payload["step"])


def ema_from_payload(payload: dict[str, Any], abstract_target: Any) -> Any:
    """Dig the EMA shadow out of an already-loaded checkpoint payload and
    map it onto ``abstract_target`` (the params tree the shadow mirrors —
    the full model tree, or the factor subtree for LoRA runs). The
    shadow is stored in float32 (training/optimizer.py); extraction
    casts back to each target leaf's dtype. Raises ``ValueError`` when
    the payload holds no EMA state."""
    import jax.numpy as jnp

    from .optimizer import find_ema_tree

    raw = find_ema_tree(payload["opt_state"])
    if raw is None:
        raise ValueError(
            "checkpoint holds no EMA state — train with "
            "trainer.extra.ema_decay to track shadow weights"
        )
    # from_state_dict maps values onto the target STRUCTURE (dtypes come
    # from the stored f32 arrays); cast each leaf back to the dtype the
    # consumer's tree expects.
    host = serialization.from_state_dict(abstract_target, raw)
    return jax.tree.map(
        lambda t, v: jnp.asarray(v, t.dtype), abstract_target, host
    )


def load_ema_params(
    path: str | Path,
    abstract_target: Any,
    *,
    expected_config_yaml: str | None = None,
) -> tuple[Any, int]:
    """Path-based wrapper over :func:`ema_from_payload` — restore the
    Polyak shadow tracked by ``trainer.extra.ema_decay`` from a
    checkpoint file."""
    payload = CheckpointManager.load(path)
    if expected_config_yaml is not None:
        warn_on_config_mismatch(payload, expected_config_yaml, path)
    return ema_from_payload(payload, abstract_target), int(payload["step"])


def warn_on_config_mismatch(
    payload: dict[str, Any], current_config_yaml: str, path: str | Path
) -> None:
    """Warn-and-continue when a checkpoint's stored config differs from the
    current one (reference trainer.py:315-318) — shared by resume and the
    ``generate`` inference loader."""
    if payload["config_yaml"] != current_config_yaml:
        from ..utils.logging import get_logger

        get_logger().warning(
            "checkpoint config differs from current config; "
            "continuing with the CURRENT config (checkpoint: %s)",
            path,
        )


def resolve_resume_path(resume_spec: str, output_root: str | Path) -> Path:
    """Resolve a ``--resume`` spec (reference trainer.py:215-241).

    file → itself; dir → newest VALID inside (falling back to the dir's
    ``checkpoints/`` subdir, so a run DIRECTORY path works like its run
    id); bare ``*.ckpt``/``*.pt`` string → FileNotFoundError; anything
    else → treated as a run id under ``{output_root}/{run_id}/checkpoints``.

    Directory and run-id resolution go through ``latest_valid_checkpoint``:
    a run whose newest checkpoint was truncated by a mid-write eviction
    warns and resumes from the previous verified one instead of dying
    mid-restore — the auto-resume loop must never wedge on its own save.
    """
    candidate = Path(resume_spec)
    if candidate.is_file():
        return candidate
    if candidate.is_dir():
        latest = CheckpointManager(candidate).latest_valid_checkpoint()
        if latest is None and (candidate / "checkpoints").is_dir():
            # A run DIRECTORY (not just a run id): descend into its
            # checkpoints/ subdir, same shape as the run-id branch below.
            latest = CheckpointManager(
                candidate / "checkpoints"
            ).latest_valid_checkpoint()
        if latest is None:
            raise FileNotFoundError(f"No checkpoints found in directory: {candidate}")
        return latest
    if resume_spec.endswith((".ckpt", ".pt")):
        raise FileNotFoundError(f"Checkpoint file does not exist: {resume_spec}")
    run_ckpt_dir = Path(output_root) / resume_spec / "checkpoints"
    if not run_ckpt_dir.is_dir():
        raise FileNotFoundError(
            f"Resume spec {resume_spec!r} is neither a file, a directory, "
            f"nor a run id with checkpoints under {run_ckpt_dir}"
        )
    latest = CheckpointManager(run_ckpt_dir).latest_valid_checkpoint()
    if latest is None:
        raise FileNotFoundError(f"No checkpoints found for run id {resume_spec!r}")
    return latest
