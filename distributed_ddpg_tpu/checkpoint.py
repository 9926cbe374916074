"""Checkpoint / resume via orbax (SURVEY.md §3.5, §5 'Checkpoint / resume').

The reference checkpoints only the parameter-server variables through
`tf.train.Saver`; replay contents are lost on restart (SURVEY.md §3.5).
Here a checkpoint is the COMPLETE learner-side state:
  - TrainState (params, targets, both Adam states, step counter),
  - the host replay buffer (via its state_dict — uniform or PER, including
    priorities), so a restored run resumes the same data distribution,
  - the config (for a mismatch warning on restore).

Saves go through a throwaway directory + atomic rename via orbax's own
finalization, and happen off the hot loop (call cadence is
config.checkpoint_every).

Robustness (docs/RESILIENCE.md): every successful save also writes
`manifest_<step>.json` — per-file sizes + a cheap head/tail crc32 — so
restore can verify a checkpoint BEFORE handing it to orbax. Writes retry
with exponential backoff on OSError (`retries=`, wired from
config.ckpt_write_retries; injectable via a faults.FaultSite). Restore
with no explicit step walks the retained checkpoints newest-first and
falls back past any that fail verification or fail to load — a corrupt or
half-written latest checkpoint costs one cadence of progress, not the run.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
import re
import shutil
import sys
import threading
import time
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.types import TrainState


# Fields that must match between a checkpoint and the run restoring it —
# shapes/semantics of the restored state depend on them. (orbax restores the
# CHECKPOINT's shapes regardless of the template, so a silent mismatch here
# would surface as a crash or corruption far from the root cause.)
COMPAT_FIELDS = (
    "env_id",
    "actor_hidden",
    "critic_hidden",
    "action_insert_layer",
    "distributional",
    "twin_critic",  # rank-3 ensemble critic leaves vs rank-2 plain ones
    "sac",  # double-width Gaussian head + twin leaves + log_alpha node
    "sac_autotune",  # alpha_opt presence changes the TrainState tree
    "crossq",  # no target nodes; batch-norm leaves in every layer
    "simba",  # residual nets: blocks, LayerNorm and input-statistics leaves
    "pixels",  # DrQ-v2's trees: an encoder in the critic's, no target actor
    "mpo",  # LayerNormMLP nets, a [mean | scale] head, the dual variables' tree
    "encoder_channels",
    "feature_dim",
    "recurrent",  # recurrent nets: dicts of embedders, an LSTM, a shortcut and heads
    "seq_len",  # the ring's row is a window of seq_len steps
    "rnn_hidden",
    "obs_embed",
    "action_embed",
    "reward_embed",
    "num_atoms",
    "v_min",
    "v_max",
    "prioritized",
    "replay_capacity",
    "n_step",
)


def _snapshot(
    step: int, state: TrainState, replay, env_steps: int,
    v_bounds=None,
) -> Dict[str, Any]:
    """Materialize everything host-side. This is the only part that touches
    device memory; once it returns, the learner is free to mutate/donate
    its state — the write can proceed on any thread."""
    ckpt: Dict[str, Any] = {
        "state": jax.device_get(state),
        "meta": {"env_steps": np.asarray(env_steps, np.int64)},
    }
    if v_bounds is not None:
        # Auto-sized C51 support (config.v_support_auto): the RESOLVED
        # bounds must ride the checkpoint — mean_q-driven expansions are
        # unrecoverable from reward statistics, and restoring the critic's
        # logits over re-derived (smaller) atom values would silently
        # reinterpret every probability as a wrong Q.
        ckpt["meta"]["v_bounds"] = np.asarray(v_bounds, np.float64)
    if replay is not None:
        ckpt["replay"] = replay.state_dict()
    return ckpt


class _OrbaxImport:
    """`orbax.checkpoint`, imported once and only by a process that
    checkpoints. The import is the slowest thing a run does before its
    first step that is not the compiler (`google.api_core`, pulled in by
    orbax's cloud logger, walks the metadata of every installed
    distribution: 39-47 s on a chip host), so importing this module does
    not pay it. One thread owns the import: `warm()`'s daemon thread, or
    the first caller of `get()` when nobody warmed; every other caller
    waits for that one."""

    def __init__(self, load=lambda: importlib.import_module("orbax.checkpoint")):
        self._load = load
        self._lock = threading.Lock()  # the claim, and waited_s
        self._claimed = False
        self._done = threading.Event()
        self._module = None
        self._error: Optional[BaseException] = None
        self.import_s = 0.0  # 0.0: never imported
        self.waited_s = 0.0  # callers' seconds blocked on another thread's import
        self.thread = ""  # name of the thread the import ran on

    def _claim(self) -> bool:
        with self._lock:
            first, self._claimed = not self._claimed, True
        return first

    def _import(self) -> None:
        t0 = time.perf_counter()
        try:
            with trace.span("ckpt_import"):
                self._module = self._load()
        except BaseException as e:  # re-raised in every caller of get()
            self._error = e
        finally:
            self.import_s = time.perf_counter() - t0
            self.thread = threading.current_thread().name
            self._done.set()

    def warm(self) -> None:
        if self._claim():
            threading.Thread(
                target=self._import, name="ckpt-import", daemon=True
            ).start()

    def get(self):
        if not self._done.is_set():
            if self._claim():
                self._import()
            else:
                t0 = time.perf_counter()
                self._done.wait()
                with self._lock:
                    self.waited_s += time.perf_counter() - t0
        if self._error is not None:
            raise self._error
        return self._module


_ORBAX = _OrbaxImport()


def warm() -> None:
    """Start importing orbax on a daemon thread, once (idempotent). A run
    with a checkpoint directory calls this as its imports end, so the
    import rides beside the backend's start and the first compile (both
    release the GIL) instead of in front of them, and is not left for the
    first cadence save's writer thread or, worse, the SIGTERM handler's
    emergency save, whose grace period is shorter than the import.
    Whatever needs orbax first joins it through `_orbax()`."""
    _ORBAX.warm()


def _orbax():
    """The `orbax.checkpoint` module: waits for `warm()`'s import, or
    imports here when nobody warmed."""
    return _ORBAX.get()


def import_fields() -> Dict[str, float]:
    """Whether this process paid for orbax, and whether anyone waited:
    `ckpt_import_s` is the import's seconds on the thread that ran it (0.0:
    orbax was never loaded), `ckpt_import_waited_s` what callers spent
    blocked on a background import (0.0: it had finished before anyone
    needed it, or the caller imported inline)."""
    return {
        "ckpt_import_s": round(_ORBAX.import_s, 3),
        "ckpt_import_waited_s": round(_ORBAX.waited_s, 3),
    }


def _checkpointer() -> "ocp.StandardCheckpointer":
    """A StandardCheckpointer whose cross-process barriers are scoped to
    THIS process only. The repo's checkpoint discipline is single-writer
    (train.py: process 0 writes the replicated state; pod aborts add
    per-process emergency dirs — docs/RESILIENCE.md pod rows), so
    orbax's default all-process barrier is wrong twice over: a lone
    writer's `sync_global_devices` is a COLLECTIVE the other processes
    never join, which both wedges the save and interleaves a mismatched
    op into the training pod's lockstep gloo streams (observed as
    `gloo EnforceNotMet op.preamble.length <= op.nbytes` corruption on
    the 3-process chaos harness); and at pod-abort time an all-process
    barrier can never complete — the dead peer is exactly why we are
    checkpointing. Subset barriers (active_processes = {this process})
    keep orbax's atomic-rename machinery intact with zero cross-process
    traffic. Single-process runs keep stock options (every barrier is
    already skipped)."""
    ocp = _orbax()
    if jax.process_count() == 1:
        return ocp.StandardCheckpointer()
    me = jax.process_index()
    mp = ocp.options.MultiprocessingOptions(
        primary_host=me,
        active_processes={me},
        barrier_sync_key_prefix=f"proc{me}",
    )
    # use_ocdbt=False: OCDBT's per-process write + merge machinery also
    # assumes an all-process save (the merge validated a partial world
    # and rejected single-writer saves with "params missing"); the
    # classic per-param layout has no cross-process step at all.
    return ocp.Checkpointer(
        ocp.PyTreeCheckpointHandler(
            use_ocdbt=False, multiprocessing_options=mp
        ),
        multiprocessing_options=mp,
    )


# Checkpoints RETAINED after each successful write (latest N). A
# checkpoint with a full 1M-row replay is ~3 GB; without retention a
# 2M-step Humanoid run at checkpoint_every=10k writes ~200 of them
# (~hundreds of GB) and fills the disk mid-run — observed round 5 at
# 6.4 GB by 340k steps. 3 matches the spirit of the reference family's
# tf.train.Saver default (keep a few, not all): latest for resume, two
# back in case the newest write raced a crash.
KEEP_CHECKPOINTS = 3


def _steps(directory: str):
    """All step numbers present in a checkpoint directory — THE parser for
    the step_N naming scheme, shared by pruning and latest_step so the two
    can never disagree about what exists."""
    return sorted(
        int(name.split("_", 1)[1])
        for name in os.listdir(directory)
        if name.startswith("step_") and name.split("_", 1)[1].isdigit()
    )


def _prune(directory: str, keep: int, current: int) -> None:
    """Delete old step_*/config_*/manifest_* triples, retaining `current`
    (the checkpoint that just landed) plus the newest `keep`-1 steps BELOW
    it. Steps ABOVE current are stale by definition — leftovers of a
    previous run sharing the directory (the --resume=false reuse workflow
    check_config_compatible suggests) or of a diverged timeline a
    guardrail rollback rewound past — and are pruned too, loudly: left in
    place they would permanently occupy the retention slots (every save
    would delete the run's OWN previous checkpoint, losing the keep-1
    crash redundancy) and keep latest_step()/resume pointing at state this
    run never produced. Runs on the writer thread after a successful save;
    best-effort (a failed unlink must not fail the save that just
    landed)."""
    if keep <= 0:
        return
    steps = _steps(directory)
    stale_above = [s for s in steps if s > current]
    below = [s for s in steps if s < current]
    if stale_above:
        print(
            f"[checkpoint] pruning stale checkpoint(s) above the current "
            f"save step_{current}: "
            + ", ".join(f"step_{s}" for s in stale_above)
            + " (previous-run or pre-rollback leftovers — resume must "
            "track THIS run's latest state)",
            file=sys.stderr, flush=True,
        )
    doomed = stale_above + (below[: -(keep - 1)] if keep > 1 else below)
    # Elastic-pod protection: never delete the newest step whose replay
    # slice set is complete (latest_complete_slice_step) — on a pod whose
    # membership shrank, that set is the ONLY recoverable copy of the dead
    # peer's shard, and survivors keep checkpointing learner state past it
    # (slice sets at newer steps stay incomplete until the peer returns).
    protected = latest_complete_slice_step(directory)
    if protected is not None and protected in doomed:
        doomed = [s for s in doomed if s != protected]
    for old in doomed:
        try:
            shutil.rmtree(os.path.join(directory, f"step_{old}"),
                          ignore_errors=True)
            shutil.rmtree(_slice_step_dir(directory, old),
                          ignore_errors=True)
            for side in (f"config_{old}.json", f"manifest_{old}.json"):
                side_path = os.path.join(directory, side)
                if os.path.exists(side_path):
                    os.unlink(side_path)
        except OSError:
            pass


# --- integrity manifest (restore-time verification) -----------------------

# Digest window per file: crc32 over the first and last MiB + the size.
# A full-stream hash of a ~3 GB replay checkpoint would add seconds to
# every save; head+tail+size catches the real-world corruptions (truncated
# write, zeroed header, wrong-length file) at microsecond cost.
_DIGEST_CAP = 1 << 20


def _digest_file(path: str) -> Tuple[int, int]:
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        crc = zlib.crc32(f.read(_DIGEST_CAP))
        if size > _DIGEST_CAP:
            f.seek(max(size - _DIGEST_CAP, _DIGEST_CAP))
            crc = zlib.crc32(f.read(_DIGEST_CAP), crc)
    return size, crc


def _write_manifest(directory: str, step: int) -> None:
    """Record every file under step_<step> with size + head/tail crc32.
    Written AFTER orbax finalizes (the atomic rename), so a manifest's
    existence certifies 'this checkpoint finished writing'; its contents
    let restore detect post-finalize corruption."""
    root = os.path.join(directory, f"step_{step}")
    files: Dict[str, Any] = {}
    for dirpath, _, names in os.walk(root):
        for name in sorted(names):
            full = os.path.join(dirpath, name)
            rel = os.path.relpath(full, root)
            size, crc = _digest_file(full)
            files[rel] = [size, crc]
    path = os.path.join(directory, f"manifest_{step}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"step": step, "files": files}, f)
    os.replace(tmp, path)


def verify_checkpoint(directory: str, step: int) -> Tuple[bool, str]:
    """Cheap integrity check of one retained checkpoint against its
    manifest. Returns (ok, why). A checkpoint written before manifests
    existed verifies as ok ('no manifest') — the orbax restore itself is
    the backstop for those; restore()'s fallback chain catches its
    failure too."""
    directory = os.path.abspath(directory)
    root = os.path.join(directory, f"step_{step}")
    if not os.path.isdir(root):
        return False, "missing checkpoint directory"
    mpath = os.path.join(directory, f"manifest_{step}.json")
    if not os.path.exists(mpath):
        return True, "no manifest (pre-manifest checkpoint)"
    try:
        with open(mpath) as f:
            manifest = json.load(f)
        entries = manifest["files"]
    except (OSError, json.JSONDecodeError, KeyError, TypeError) as e:
        return False, f"unreadable manifest: {e!r}"
    for rel, (size, crc) in entries.items():
        full = os.path.join(root, rel)
        try:
            got_size, got_crc = _digest_file(full)
        except OSError:
            return False, f"missing/unreadable file {rel}"
        if got_size != size:
            return False, f"size mismatch {rel}: {got_size} != {size}"
        if got_crc != crc:
            return False, f"digest mismatch {rel}"
    # Per-slice digests (elastic pod): a torn replay-slice write
    # quarantines ONLY that slice — the learner-state step above already
    # verified, and slice adoption has its own fallback chain
    # (latest_complete_slice_step), so a bad slice must never cost the
    # whole step.
    verify_replay_slices(directory, step, quarantine=True)
    return True, "ok"


def _quarantine_corrupt(directory: str, step: int) -> None:
    """Move a verification-failed checkpoint out of the step_N namespace
    (-> corrupt_step_N) so a resumed run that re-reaches step N can write
    a fresh checkpoint there — orbax refuses to overwrite an existing
    destination, and without this the corrupt leftovers would fail every
    later save at that step. Renamed, not deleted: the payload stays on
    disk for forensics. Best-effort (fallback must proceed regardless)."""
    directory = os.path.abspath(directory)
    src = os.path.join(directory, f"step_{step}")
    dst = os.path.join(directory, f"corrupt_step_{step}")
    try:
        if os.path.isdir(dst):
            shutil.rmtree(dst, ignore_errors=True)
        os.rename(src, dst)
        for side in (f"manifest_{step}.json", f"config_{step}.json"):
            side_path = os.path.join(directory, side)
            if os.path.exists(side_path):
                os.unlink(side_path)
        print(
            f"[checkpoint] quarantined corrupt step_{step} -> "
            f"corrupt_step_{step}",
            file=sys.stderr, flush=True,
        )
    except OSError:
        pass


def _write_once(directory: str, step: int, ckpt: Dict[str, Any],
                config: Optional[DDPGConfig],
                keep: int = KEEP_CHECKPOINTS,
                devactor_state: Optional[Dict[str, Any]] = None) -> str:
    path = os.path.join(os.path.abspath(directory), f"step_{step}")
    # A leftover directory at this step (a corrupt checkpoint restore
    # skipped, or a prior attempt whose sidecar write failed) would make
    # orbax refuse the save; this writer is the single authority for the
    # step, so clear it.
    if os.path.isdir(path):
        shutil.rmtree(path, ignore_errors=True)
    with _checkpointer() as ckptr:
        ckptr.save(path, ckpt)
    if devactor_state:
        # Device-actor rollout carry (actors/device_pool.carry_state_dict;
        # docs/DEVICE_ACTORS.md): a flat-leaf npz INSIDE the step dir —
        # written after orbax finalizes and before the manifest walk, so
        # the manifest's size+crc verification covers it like every orbax
        # payload file. A sidecar, not an orbax subtree: the carry's tree
        # shape is env/config-dependent, and restore() must be able to
        # read it back BEFORE the pool (hence the template) exists.
        with open(os.path.join(path, "devactor_carry.npz"), "wb") as f:
            np.savez(f, **devactor_state)
    if config is not None:
        # nan (the v_min/v_max auto sentinel) would serialize as the
        # non-RFC bare `NaN` token — unreadable by jq and strict parsers.
        # null keeps the file valid JSON; _compat_eq maps it back.
        fields = {
            k: (None if isinstance(v, float) and math.isnan(v) else v)
            for k, v in dataclasses.asdict(config).items()
        }
        with open(os.path.join(os.path.dirname(path), f"config_{step}.json"), "w") as f:
            json.dump(fields, f, indent=2, default=list)
    _write_manifest(os.path.dirname(path), step)
    _prune(os.path.dirname(path), keep, step)
    return path


def _write(directory: str, step: int, ckpt: Dict[str, Any],
           config: Optional[DDPGConfig], keep: int = KEEP_CHECKPOINTS,
           retries: int = 0, backoff_s: float = 0.5,
           fault=None,
           devactor_state: Optional[Dict[str, Any]] = None) -> Tuple[str, int]:
    """Write with bounded retry + exponential backoff on OSError (full
    disk blips, NFS hiccups, injected ckpt:write:ioerror faults). Returns
    (path, retries_used). `fault` is a faults.FaultSite ticked once per
    ATTEMPT — retries advance the ordinal, so 'ioerror@2' scripts 'the
    second attempt overall fails'."""
    for attempt in range(retries + 1):
        try:
            if fault is not None:
                fault.tick()
            return _write_once(
                directory, step, ckpt, config, keep=keep,
                devactor_state=devactor_state,
            ), attempt
        except OSError as e:
            # A failed attempt may leave a partially-finalized step dir
            # (or a completed dir whose sidecar write failed) — clear it
            # so the retry's orbax save starts clean.
            shutil.rmtree(
                os.path.join(os.path.abspath(directory), f"step_{step}"),
                ignore_errors=True,
            )
            if attempt >= retries:
                raise
            delay = backoff_s * (2.0 ** attempt)
            trace.instant("ckpt_write_retry", step=step,
                          attempt=attempt + 1)
            print(
                f"[checkpoint] write of step_{step} failed ({e!r}); "
                f"retry {attempt + 1}/{retries} in {delay:.2f}s",
                file=sys.stderr, flush=True,
            )
            time.sleep(delay)
    raise AssertionError("unreachable")


def save(
    directory: str,
    step: int,
    state: TrainState,
    replay=None,
    config: Optional[DDPGConfig] = None,
    env_steps: int = 0,
    v_bounds=None,
    keep: int = KEEP_CHECKPOINTS,
    retries: int = 0,
    backoff_s: float = 0.5,
    fault=None,
    devactor_state=None,
) -> str:
    """Write checkpoint `directory/step_N` synchronously. Returns the path.
    `retries`/`backoff_s` bound the OSError retry loop (_write); `fault`
    is an optional faults.FaultSite for the chaos harness.
    `devactor_state` (actors/device_pool.carry_state_dict) rides as the
    devactor_carry.npz sidecar inside the step dir."""
    path, _ = _write(
        directory, step,
        _snapshot(step, state, replay, env_steps, v_bounds=v_bounds),
        config,
        keep=keep,
        retries=retries,
        backoff_s=backoff_s,
        fault=fault,
        devactor_state=devactor_state,
    )
    return path


class AsyncSaver:
    """Checkpointing off the hot loop (SURVEY.md §5 'async save off the hot
    loop'; VERDICT.md round-1 Weak #6). save_async snapshots device state on
    the caller's thread — one HBM->host copy, fast at memory bandwidth —
    and hands serialization + the multi-hundred-MB disk write to a single
    background writer. If the writer is still busy when the next cadence
    fires, that save is SKIPPED (coalesced): a fresher checkpoint is always
    coming, and queueing would grow host memory by a full replay copy per
    backlog entry."""

    def __init__(self):
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self.skipped = 0
        self.errors: list = []
        # Cumulative OSError retries consumed by background writes — the
        # `ckpt_write_retries` recovery counter train.py logs.
        self.write_retries = 0

    @property
    def busy(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def save_async(
        self,
        directory: str,
        step: int,
        state: TrainState,
        replay=None,
        config: Optional[DDPGConfig] = None,
        env_steps: int = 0,
        v_bounds=None,
        keep: int = KEEP_CHECKPOINTS,
        retries: int = 0,
        backoff_s: float = 0.5,
        fault=None,
        devactor_state=None,
    ) -> bool:
        """Snapshot now, write in the background. Returns False (and skips)
        if the previous write is still in flight. `devactor_state` must
        already be host-side numpy (device_pool.carry_state_dict pulls it
        on the caller's thread, same discipline as the state snapshot)."""
        with self._lock:
            if self.busy:
                self.skipped += 1
                return False
            ckpt = _snapshot(step, state, replay, env_steps, v_bounds=v_bounds)

            def _run():
                try:
                    with trace.span("ckpt_write", step=step):
                        _, used = _write(
                            directory, step, ckpt, config, keep=keep,
                            retries=retries, backoff_s=backoff_s,
                            fault=fault, devactor_state=devactor_state,
                        )
                    self.write_retries += used
                except Exception as e:  # surfaced via .errors / wait()
                    self.errors.append(e)

            self._thread = threading.Thread(
                target=_run, name=f"ckpt-writer-{step}", daemon=True
            )
            self._thread.start()
            return True

    def wait(self) -> None:
        """Block until the in-flight write (if any) lands; re-raise its
        error if it failed. Call before reading back a checkpoint or at
        shutdown."""
        t = self._thread
        if t is not None:
            t.join()
        if self.errors:
            raise self.errors[-1]


def check_config_compatible(directory: str, step: int, config: DDPGConfig) -> None:
    """Raise ValueError if the checkpoint was written under a config whose
    COMPAT_FIELDS differ from the current run's."""
    path = os.path.join(os.path.abspath(directory), f"config_{step}.json")
    if not os.path.exists(path):
        return
    with open(path) as f:
        # a checkpoint from before the field existed was not a crossq run's
        saved = {"crossq": False, "simba": False, "pixels": False, "mpo": False, "recurrent": False, **json.load(f)}
    current = dataclasses.asdict(config)
    mismatches = [
        f"{k}: checkpoint={saved[k]!r} run={_listify(current[k])!r}"
        for k in COMPAT_FIELDS
        if k in saved and not _compat_eq(saved[k], _listify(current[k]))
    ]
    if mismatches:
        raise ValueError(
            f"checkpoint {directory}/step_{step} is incompatible with this "
            "run's config (pass --resume=false or a fresh --checkpoint_dir):\n  "
            + "\n  ".join(mismatches)
        )


def _listify(v):
    return list(v) if isinstance(v, tuple) else v


def _compat_eq(a, b) -> bool:
    # nan == nan for compat purposes: v_min/v_max use nan as the 'auto'
    # sentinel (config.py), and two auto runs ARE compatible — IEEE
    # inequality would reject every auto-support resume. The saved side
    # serializes the sentinel as null (_write), so None matches nan too.
    def _is_auto(v) -> bool:
        return v is None or (isinstance(v, float) and math.isnan(v))

    if _is_auto(a) and _is_auto(b):
        return True
    return a == b


def discard_above(directory: str, step: int) -> list:
    """Quarantine every retained checkpoint NEWER than `step` out of the
    step_N namespace (-> diverged_step_N; sidecars removed so
    latest_step()/valid_steps() stop seeing them, payload kept for
    forensics — the _quarantine_corrupt discipline). The guardrail
    rollback (train.py) calls this right after restoring `step`:
    checkpoints written after the divergence began are poisoned by
    assumption, and a crash landing before the next clean save must
    resume from `step`, not from them. Returns the steps discarded."""
    directory = os.path.abspath(directory)
    if not os.path.isdir(directory):
        return []
    discarded = []
    for s in _steps(directory):
        if s <= step:
            continue
        src = os.path.join(directory, f"step_{s}")
        dst = os.path.join(directory, f"diverged_step_{s}")
        try:
            if os.path.isdir(dst):
                shutil.rmtree(dst, ignore_errors=True)
            os.rename(src, dst)
            for side in (f"manifest_{s}.json", f"config_{s}.json"):
                side_path = os.path.join(directory, side)
                if os.path.exists(side_path):
                    os.unlink(side_path)
            discarded.append(s)
        except OSError:
            pass
    if discarded:
        print(
            "[checkpoint] rollback quarantined diverged checkpoint(s): "
            + ", ".join(f"step_{s} -> diverged_step_{s}" for s in discarded),
            file=sys.stderr, flush=True,
        )
    return discarded


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = _steps(directory)
    return max(steps) if steps else None


def valid_steps(directory: str, limit: Optional[int] = None):
    """Manifest-valid retained steps, ascending (verify_checkpoint passes
    — pre-manifest checkpoints count as valid, matching restore()'s
    fallback semantics). The input to the pod resume-step election
    (parallel/multihost.elect_resume_step): a pod restarting after a
    clean abort restores the greatest step valid on EVERY process, so
    per-process step lists must be cheap and honest. `limit` keeps only
    the newest N."""
    if not directory or not os.path.isdir(directory):
        return []
    out = [s for s in _steps(directory) if verify_checkpoint(directory, s)[0]]
    return out[-limit:] if limit else out


# --- all-writer replay slices (elastic pod; docs/REPLAY_SHARDING.md) ------
#
# Multi-host SHARDED replay spans processes, so no single writer can put
# its contents inside the orbax tree (state_dict raises there by design).
# Instead EVERY process writes its own slice — the logical ring positions
# it owns plus the packed rows (and PER priorities) at those positions —
# into a shared sibling namespace:
#
#   directory/replay_slices/step_<N>/slice_<k>_of_<n>.npz   (payload)
#   directory/replay_slices/step_<N>/slice_<k>_of_<n>.json  (digest sidecar)
#
# Filenames are per-writer, so the single-writer-per-file discipline holds
# on a shared filesystem with zero cross-process coordination; the digest
# sidecar (size + head/tail crc32, written AFTER the payload's atomic
# rename) certifies "this slice finished writing". The slice format is
# position-indexed, so a restore can merge any complete set and re-scatter
# to a DIFFERENT process count (replay/device.py merge_slice_states +
# the reshard program) — the wire format is placement-portable like the
# logical-order state_dict it slices.

SLICE_DIRNAME = "replay_slices"
_SLICE_RE = re.compile(r"^slice_(\d+)_of_(\d+)\.npz$")


def _slice_step_dir(directory: str, step: int) -> str:
    return os.path.join(
        os.path.abspath(directory), SLICE_DIRNAME, f"step_{step}"
    )


def _slice_steps(directory: str):
    """Step numbers with any slice directory present, ascending."""
    root = os.path.join(os.path.abspath(directory), SLICE_DIRNAME)
    if not os.path.isdir(root):
        return []
    return sorted(
        int(name.split("_", 1)[1])
        for name in os.listdir(root)
        if name.startswith("step_") and name.split("_", 1)[1].isdigit()
    )


def write_replay_slice(
    directory: str, step: int, proc: int, nprocs: int,
    slice_state: Dict[str, Any], fault=None,
) -> str:
    """Write this process's replay slice for `step` (atomic tmp+rename),
    then its digest sidecar. `slice_state` is replay/device.py
    slice_state_dict() output (positions + rows + ring scalars, PER adds
    priorities). `fault` is a faults.FaultSite for the chaos harness: a
    `kill` kind fires before any byte lands (peer lost DURING checkpoint
    — the slice simply never exists), `ioerror` raises to the caller,
    and `corrupt` tears the payload AFTER the digest sidecar was
    computed — the torn-shard-write case restore-time verification must
    quarantine without failing the step."""
    torn = False
    if fault is not None:
        from distributed_ddpg_tpu.faults import InjectedCorruption

        try:
            fault.tick()
        except InjectedCorruption:
            torn = True
    root = _slice_step_dir(directory, step)
    os.makedirs(root, exist_ok=True)
    path = os.path.join(root, f"slice_{proc}_of_{nprocs}.npz")
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **slice_state)
    os.replace(tmp, path)
    size, crc = _digest_file(path)
    jpath = os.path.join(root, f"slice_{proc}_of_{nprocs}.json")
    jtmp = jpath + ".tmp"
    with open(jtmp, "w") as f:
        json.dump(
            {"step": step, "proc": proc, "nprocs": nprocs,
             "digest": [size, crc]},
            f,
        )
    os.replace(jtmp, jpath)
    if torn:
        # Injected torn write: the digest above covered the intact file,
        # the payload on disk is now shorter — exactly what a crash
        # mid-flush past the rename window leaves behind.
        with open(path, "r+b") as f:
            f.truncate(max(size // 2, 1))
    return path


def _verify_slice(root: str, proc: int, nprocs: int) -> Tuple[bool, str]:
    path = os.path.join(root, f"slice_{proc}_of_{nprocs}.npz")
    jpath = os.path.join(root, f"slice_{proc}_of_{nprocs}.json")
    if not os.path.exists(path):
        return False, "missing slice"
    if not os.path.exists(jpath):
        return False, "no digest sidecar (write did not finish)"
    try:
        with open(jpath) as f:
            size, crc = json.load(f)["digest"]
    except (OSError, json.JSONDecodeError, KeyError, TypeError, ValueError) as e:
        return False, f"unreadable digest sidecar: {e!r}"
    try:
        got_size, got_crc = _digest_file(path)
    except OSError:
        return False, "unreadable slice"
    if got_size != size:
        return False, f"size mismatch: {got_size} != {size}"
    if got_crc != crc:
        return False, "digest mismatch"
    return True, "ok"


def slice_status(directory: str, step: int):
    """-> (complete, nprocs, {proc: (ok, why)}). A step's slice set is
    COMPLETE when some world size n has all n slices present and
    digest-valid. `nprocs` is that n (or the largest world size seen when
    incomplete; None when no slices exist at all)."""
    root = _slice_step_dir(directory, step)
    if not os.path.isdir(root):
        return False, None, {}
    by_n: Dict[int, set] = {}
    for name in os.listdir(root):
        m = _SLICE_RE.match(name)
        if m:
            by_n.setdefault(int(m.group(2)), set()).add(int(m.group(1)))
    if not by_n:
        return False, None, {}
    # Prefer a world size whose file set is full; verify digests for it.
    for n in sorted(by_n, reverse=True):
        if by_n[n] == set(range(n)):
            status = {k: _verify_slice(root, k, n) for k in range(n)}
            complete = all(ok for ok, _ in status.values())
            return complete, n, status
    n = max(by_n)
    status = {k: _verify_slice(root, k, n) for k in sorted(by_n[n])}
    return False, n, status


def verify_replay_slices(directory: str, step: int,
                         quarantine: bool = True) -> Tuple[bool, int]:
    """Verify the step's slice set; with `quarantine`, move each
    digest-failed slice out of the slice namespace (-> .corrupt, the
    _quarantine_corrupt discipline: payload kept for forensics, the set
    reads as incomplete afterwards). Returns (complete, nprocs or 0).
    A torn slice quarantines ONLY itself — the learner-state step stays
    valid (verify_checkpoint), and adoption falls back to the newest
    OLDER complete set (latest_complete_slice_step)."""
    complete, n, status = slice_status(directory, step)
    if quarantine:
        root = _slice_step_dir(directory, step)
        for proc, (ok, why) in status.items():
            if ok or why == "missing slice":
                continue
            src = os.path.join(root, f"slice_{proc}_of_{n}.npz")
            if not os.path.exists(src):
                continue
            try:
                os.replace(src, src + ".corrupt")
                print(
                    f"[checkpoint] quarantined corrupt replay slice "
                    f"{proc}/{n} at step_{step} ({why}) -> .corrupt; the "
                    "step's learner state stays valid",
                    file=sys.stderr, flush=True,
                )
            except OSError:
                pass
    return complete, (n or 0)


def latest_complete_slice_step(
    directory: str, at_or_below: Optional[int] = None,
) -> Optional[int]:
    """Newest step (optionally <= `at_or_below`) whose replay slice set is
    complete and digest-valid — the adoption input for an elastic
    restart (train.py): the dead peer's slice comes from its last
    verified write, so replay may be a few cadences staler than the
    elected learner step. Returns None when no step qualifies (the
    exit-76 fallback branch)."""
    if not directory:
        return None
    for s in sorted(_slice_steps(directory), reverse=True):
        if at_or_below is not None and s > at_or_below:
            continue
        complete, _, _ = slice_status(directory, s)
        if complete:
            return s
    return None


def load_replay_slices(directory: str, step: int):
    """Read back the complete slice set at `step` as a list of dicts of
    host arrays (one per writer, any order — merge is position-driven)."""
    complete, n, status = slice_status(directory, step)
    if not complete:
        bad = {k: why for k, (ok, why) in status.items() if not ok}
        raise RuntimeError(
            f"replay slice set at step_{step} is incomplete "
            f"(world={n}, failures={bad})"
        )
    root = _slice_step_dir(directory, step)
    out = []
    for k in range(n):
        with np.load(os.path.join(root, f"slice_{k}_of_{n}.npz")) as z:
            out.append({key: z[key] for key in z.files})
    return out


def restore(
    directory: str,
    state_template: TrainState,
    replay=None,
    step: Optional[int] = None,
    config: Optional[DDPGConfig] = None,
    meta_out: Optional[Dict[str, Any]] = None,
) -> Tuple[TrainState, int, int]:
    """Restore (TrainState, step, env_steps). If `replay` is given its
    contents are restored in place. `state_template` supplies the tree
    structure/shapes (orbax restores into abstract targets). When `config`
    is given, the checkpoint's saved config is validated against it first.
    `meta_out`, when given, is filled with the checkpoint's extra metadata
    (currently: "v_bounds" — the resolved auto-support bounds, present only
    on checkpoints from auto-support runs).

    With `step=None` the retained checkpoints are walked NEWEST-FIRST and
    any that fails manifest verification (verify_checkpoint) or fails to
    load is skipped with a loud stderr note — a corrupt or half-written
    latest checkpoint costs one cadence of progress, not the run. An
    explicit `step` restores exactly that step (no fallback); a config
    incompatibility always raises (it is a contract violation, not
    corruption)."""
    if step is None:
        candidates = (
            _steps(os.path.abspath(directory))
            if os.path.isdir(directory) else []
        )
        if not candidates:
            raise FileNotFoundError(f"no checkpoints under {directory}")
        failures = []
        for s in sorted(candidates, reverse=True):
            ok, why = verify_checkpoint(directory, s)
            if not ok:
                print(
                    f"[checkpoint] step_{s} failed verification ({why}); "
                    "falling back to the previous retained checkpoint",
                    file=sys.stderr, flush=True,
                )
                failures.append(f"step_{s}: {why}")
                _quarantine_corrupt(directory, s)
                continue
            # Config compatibility is checked HERE, outside the load
            # try/except, so its ValueError raises through (a contract
            # violation, not corruption) while a ValueError from orbax's
            # own load (tree mismatch on a subtly-corrupt checkpoint that
            # passed the crc spot-check) still falls back.
            if config is not None:
                check_config_compatible(directory, s, config)
            try:
                return restore(
                    directory, state_template, replay=replay, step=s,
                    config=None, meta_out=meta_out,
                )
            except Exception as e:
                print(
                    f"[checkpoint] step_{s} failed to load ({e!r}); "
                    "falling back to the previous retained checkpoint",
                    file=sys.stderr, flush=True,
                )
                failures.append(f"step_{s}: load error: {e!r}")
        raise RuntimeError(
            f"no restorable checkpoint under {directory}; tried newest-"
            "first: " + "; ".join(failures)
        )
    if config is not None:
        check_config_compatible(directory, step, config)
    path = os.path.join(os.path.abspath(directory), f"step_{step}")
    template: Dict[str, Any] = {
        "state": jax.device_get(state_template),
        "meta": {"env_steps": np.zeros((), np.int64)},
    }
    if replay is not None:
        template["replay"] = replay.state_dict()
    with _checkpointer() as ckptr:
        # Checkpoints written before the 'meta' entry existed lack that
        # subtree, and orbax requires the template to match the on-disk tree
        # exactly. Probe the saved structure rather than catching ValueError,
        # so genuine template mismatches keep their original diagnostic.
        has_bounds = False
        has_replay = replay is not None
        try:
            on_disk = ckptr.metadata(path)
            # The saved tree's location varies by orbax version: current
            # StandardCheckpointer returns StepMetadata with the tree under
            # .item_metadata.tree; older versions exposed .tree or the raw
            # tree itself.
            tree = getattr(on_disk, "tree", None)
            if tree is None:
                tree = getattr(
                    getattr(on_disk, "item_metadata", None), "tree", None
                )
            if tree is None:
                tree = on_disk
            has_meta = "meta" in tree
            has_bounds = has_meta and "v_bounds" in tree["meta"]
            has_replay = "replay" in tree
        except Exception:
            has_meta = True  # metadata unreadable: let restore() report it
        if not has_meta:
            template.pop("meta")  # env_steps then resumes as 0
        elif has_bounds:
            template["meta"]["v_bounds"] = np.zeros(2, np.float64)
        if not has_replay and replay is not None:
            # Checkpoints from multi-host SHARDED runs omit replay
            # contents from the orbax tree (no single-writer snapshot
            # spans the shards — replay/device.py state_dict,
            # docs/REPLAY_SHARDING.md): the buffer resumes empty here;
            # the caller may adopt the all-writer slice set afterwards
            # (latest_complete_slice_step + load_replay_slices).
            template.pop("replay", None)
            print(
                f"[checkpoint] step_{step} carries no replay contents "
                "(multi-host sharded writer); the buffer resumes empty "
                "unless a verified slice set is adopted",
                file=sys.stderr, flush=True,
            )
        elif has_replay and replay is None:
            # A replay-carrying checkpoint restored without a buffer to
            # land it in (e.g. a replicated-mode checkpoint resumed by a
            # multi-host sharded run): orbax needs the template to cover
            # the on-disk tree, and silently dropping GBs of experience
            # would mask a placement-mode switch — surface it instead.
            raise RuntimeError(
                f"checkpoint step_{step} carries replay contents but this "
                "run cannot restore them (multi-host sharded replay has "
                "no single-writer snapshot; docs/REPLAY_SHARDING.md) — "
                "resume with the original replay placement, or start a "
                "fresh checkpoint_dir"
            )
        restored = ckptr.restore(path, template)
    if replay is not None and "replay" in restored:
        replay.load_state_dict(restored["replay"])
    state = jax.tree.map(np.asarray, restored["state"])
    meta = restored.get("meta", {})
    env_steps = int(meta.get("env_steps", 0))
    if meta_out is not None:
        # Whether the checkpoint's orbax tree carried replay contents —
        # the slice-adoption gate (train.py adopts the all-writer slice
        # set only when the tree did NOT restore the buffer).
        meta_out["ckpt_has_replay"] = bool(has_replay)
        if "v_bounds" in meta:
            vb = np.asarray(meta["v_bounds"], np.float64)
            meta_out["v_bounds"] = (float(vb[0]), float(vb[1]))
        carry_path = os.path.join(path, "devactor_carry.npz")
        if os.path.exists(carry_path):
            # Device-actor rollout carry sidecar (save's devactor_state):
            # handed back as host arrays — the pool that consumes it is
            # built AFTER restore (its warmup budget needs env_steps), so
            # it cannot contribute a template here.
            try:
                with np.load(carry_path) as z:
                    meta_out["devactor_carry"] = {k: z[k] for k in z.files}
            except (OSError, ValueError) as e:
                print(
                    f"[checkpoint] devactor_carry.npz unreadable ({e!r}); "
                    "rollout state starts fresh",
                    file=sys.stderr, flush=True,
                )
    return state, step, env_steps
