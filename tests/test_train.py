"""End-to-end driver tests: the native CLI path, the full async jax path
(actors + prefetch + sharded learner) on the fake 8-device mesh, and
checkpoint save/restore including replay (SURVEY.md §4 'Integration' and
'Fault/elastic' rows)."""

import os

import numpy as np
import pytest

from distributed_ddpg_tpu import checkpoint as ckpt_lib
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import init_train_state
from distributed_ddpg_tpu.replay import PrioritizedReplay
from distributed_ddpg_tpu.train import train_jax, train_native


def test_train_native_runs_and_reports_rate():
    cfg = DDPGConfig(
        backend="native",
        actor_hidden=(32, 32),
        critic_hidden=(32, 32),
        total_env_steps=1500,
        replay_min_size=200,
        replay_capacity=10_000,
        eval_every=1000,
    )
    out = train_native(cfg)
    assert out["learner_steps"] == 1500 - 200 + 1
    assert out["learner_steps_per_sec"] > 10


def test_jax_backends_refuse_a_cpu_nobody_asked_for():
    """The jax backends run on the CPU only when the CPU was ASKED for
    (jax_platforms leads with 'cpu' — conftest.py and the tier-1 command
    both do). With nothing asked, or the chip asked, a resolved CPU means
    no usable TPU was found: train() raises before any work instead of
    carrying on silently."""
    import jax

    from distributed_ddpg_tpu.train import require_platform, train

    assert require_platform() == "cpu"  # asked-for CPU runs
    try:
        for asked in ("", "tpu,cpu"):
            jax.config.update("jax_platforms", asked)
            with pytest.raises(RuntimeError, match="did not ask for it"):
                train(DDPGConfig(backend="jax_tpu"))
    finally:
        jax.config.update("jax_platforms", "cpu")


def test_chip_smoke_refuses_without_the_chip():
    """`python chip_smoke.py` with no TPU (here: the CPU asked for) exits
    non-zero in seconds, before any training, and prints no result."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2, (proc.stdout, proc.stderr)
    assert proc.stdout == ""
    assert "needs the chip" in proc.stderr


def test_chip_smoke_verdict_line_is_exactly_ok_and_device():
    """The chip check parses the last stdout line and refuses any key
    beyond `ok` and `device` {platform, kind, count}; the run's facts go on
    the line before it."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        import chip_smoke
    finally:
        sys.path.remove(root)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    for ok in (True, False):
        line = chip_smoke.verdict_line(ok, device)
        assert "\n" not in line
        assert json.loads(line) == {"ok": ok, "device": device}


def test_entry_modules_import_without_jax():
    """ActorPool spawns its workers and a spawned worker re-imports the
    parent's main module: whatever can be that module (train.py under
    `python -m`, chip_smoke.py) and whatever the worker imports itself must
    not import JAX — one process per chip, and N workers must not each pay
    the jax+orbax import."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys, chip_smoke, distributed_ddpg_tpu.train, "
        "distributed_ddpg_tpu.actors.worker, distributed_ddpg_tpu.ops.noise; "
        "bad = [m for m in ('jax', 'orbax') if m in sys.modules]; "
        "assert not bad, bad"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr


# What loading orbax drags in: itself and, through its cloud logger,
# google.api_core, whose start-up walk of every installed distribution's
# metadata is most of the 39-47 s the import costs on a chip host.
_ORBAX_MODULES = ("orbax", "google.api_core")

# One child process per run: the CLI's main() on tiny Pendulum nets, with
# what the case asks for steered from here (a SIGTERM once a record of a
# given kind is in the log; an orbax import held open until after it),
# then one JSON line of what the process ended as.
_ORBAX_CHILD = r"""
import json, os, signal, sys, threading, time
case = json.loads(sys.argv[1])
import jax
from distributed_ddpg_tpu import checkpoint as ckpt_lib

gate = threading.Event()
if case["hold_import"]:
    load = ckpt_lib._ORBAX._load
    ckpt_lib._ORBAX._load = lambda: (gate.wait(120), load())[1]

def preempt():
    needle = '"kind": "%s"' % case["sigterm_at"]
    while not (os.path.exists(case["log_path"])
               and needle in open(case["log_path"]).read()):
        time.sleep(0.05)
    os.kill(os.getpid(), signal.SIGTERM)
    time.sleep(1.0)  # the emergency save is under way, the import is not done
    gate.set()

code = 0
if case["flags"] is not None:
    from distributed_ddpg_tpu import train
    if case["sigterm_at"]:
        threading.Thread(target=preempt, daemon=True).start()
    try:
        train.main([
            "--env_id=Pendulum-v1", "--actor_hidden=16,16",
            "--critic_hidden=16,16", "--num_actors=1",
            "--replay_min_size=256", "--replay_capacity=20000",
            "--eval_every=0", "--log_path=" + case["log_path"],
            *case["flags"],
        ])
    except SystemExit as e:
        code = e.code
print(json.dumps({
    "exit": code,
    "loaded": [m for m in case["modules"] if m in sys.modules],
    "import_thread": ckpt_lib._ORBAX.thread,
}))
"""


def _orbax_child(tmp_path, name, flags=None, sigterm_at="", hold_import=False):
    """Run `_ORBAX_CHILD`; returns (its last line, the run's final record,
    its stdout)."""
    import json
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    log_path = tmp_path / f"{name}.jsonl"
    case = {
        "flags": flags, "sigterm_at": sigterm_at, "hold_import": hold_import,
        "log_path": str(log_path), "modules": _ORBAX_MODULES,
    }
    proc = subprocess.run(
        [sys.executable, "-c", _ORBAX_CHILD, json.dumps(case)], cwd=root,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    facts = json.loads(proc.stdout.splitlines()[-1])
    final = None
    if flags is not None:
        final = json.loads(log_path.read_text().splitlines()[-1])
        assert final["kind"] == "final"
    return facts, final, proc.stdout


@pytest.mark.parametrize(
    "case",
    ["import_only", "no_dir_to_end", "no_dir_sigterm", "dir_save_then_resume",
     "dir_sigterm_before_any_save"],
)
def test_a_run_pays_for_orbax_only_if_it_checkpoints(tmp_path, case):
    """ISSUE 37: `import orbax.checkpoint` is off the path to the first step.
    A process that never writes or reads a checkpoint never loads it (not at
    import, not at SIGTERM, not at exit: `ckpt_import_s` 0.0); one with a
    checkpoint directory imports it on the `ckpt-import` thread beside its
    start-up, and the first save, a resume's restore and the SIGTERM
    emergency save join that import rather than start one of their own."""
    from distributed_ddpg_tpu.train import EXIT_PREEMPTED

    ckpt_dir = str(tmp_path / "ckpt")
    paced = ["--max_learn_ratio=1.0", "--max_ingest_ratio=1.0"]
    forever = ["--total_env_steps=2000000"]
    if case == "import_only":
        facts, _, _ = _orbax_child(tmp_path, "a")
        assert facts == {"exit": 0, "loaded": [], "import_thread": ""}
    elif case in ("no_dir_to_end", "no_dir_sigterm"):
        sigterm = case == "no_dir_sigterm"
        facts, final, _ = _orbax_child(
            tmp_path, "a",
            flags=forever if sigterm else ["--total_env_steps=1500"],
            sigterm_at="train" if sigterm else "",
        )
        assert facts == {
            "exit": EXIT_PREEMPTED if sigterm else 0,
            "loaded": [], "import_thread": "",
        }
        assert final["ckpt_import_s"] == 0.0
        assert final["ckpt_import_waited_s"] == 0.0
        assert final["learner_steps"] > 0
    elif case == "dir_save_then_resume":
        dir_flags = [f"--checkpoint_dir={ckpt_dir}", "--checkpoint_every=100"]
        facts, final, _ = _orbax_child(
            tmp_path, "a", flags=["--total_env_steps=1200", *paced, *dir_flags]
        )
        assert facts["exit"] == 0 and facts["import_thread"] == "ckpt-import"
        assert final["ckpt_import_s"] > 0
        saved = ckpt_lib.latest_step(ckpt_dir)
        assert saved is not None and saved >= 100
        assert os.path.exists(os.path.join(ckpt_dir, f"manifest_{saved}.json"))
        assert ckpt_lib.verify_checkpoint(ckpt_dir, saved) == (True, "ok")
        # A second process resumes: restore() needs orbax before the first
        # chunk and gets it from the warm import, not one of its own.
        facts, final, stdout = _orbax_child(
            tmp_path, "b", flags=["--total_env_steps=1600", *paced, *dir_flags]
        )
        assert f"resumed from {ckpt_dir} at learner step {saved}" in stdout
        assert facts["exit"] == 0 and facts["import_thread"] == "ckpt-import"
        assert final["ckpt_import_s"] > 0
        assert final["learner_steps"] > saved
    else:
        # The cadence never fires and the import is held open until a
        # second after the SIGTERM: the emergency save is the first to need
        # orbax and finds the import still running.
        facts, final, _ = _orbax_child(
            tmp_path, "a",
            flags=[*forever, f"--checkpoint_dir={ckpt_dir}",
                   "--checkpoint_every=1000000000"],
            sigterm_at="train", hold_import=True,
        )
        assert facts["exit"] == EXIT_PREEMPTED
        assert facts["import_thread"] == "ckpt-import"
        assert final["ckpt_import_s"] > 0 and final["ckpt_import_waited_s"] > 0
        assert final["emergency_ckpt"] == 1
        saved = ckpt_lib.latest_step(ckpt_dir)
        assert saved == final["learner_steps"]
        assert ckpt_lib.verify_checkpoint(ckpt_dir, saved) == (True, "ok")


def test_orbax_import_runs_once_whoever_asks():
    """Two callers racing the accessor, with or without a `warm()` before
    them, import once: one thread owns the import, every other caller waits
    for it and is counted as waiting; a failed import fails every caller."""
    import threading
    import time

    for warmed in (False, True):
        calls = []

        def slow_load():
            calls.append(threading.current_thread().name)
            time.sleep(0.3)
            return object()

        imp = ckpt_lib._OrbaxImport(load=slow_load)
        if warmed:
            imp.warm()
            imp.warm()  # idempotent
        got = []
        callers = [
            threading.Thread(target=lambda: got.append(imp.get()), name=f"caller-{i}")
            for i in range(2)
        ]
        for t in callers:
            t.start()
        for t in callers:
            t.join(timeout=10)
            assert not t.is_alive()
        assert len(calls) == 1 and len(got) == 2 and got[0] is got[1]
        assert imp.thread == calls[0]
        assert (imp.thread == "ckpt-import") == warmed
        assert imp.import_s >= 0.3
        # Without a warm import one caller imports inline and one waits; with
        # one, both wait.
        assert imp.waited_s > (0.3 if warmed else 0.0)
        assert imp.get() is got[0]  # and no wait is counted once it is in
        waited = imp.waited_s
        imp.get()
        assert imp.waited_s == waited

    def failing_load():
        raise ImportError("no orbax here")

    imp = ckpt_lib._OrbaxImport(load=failing_load)
    imp.warm()
    for _ in range(2):
        with pytest.raises(ImportError, match="no orbax here"):
            imp.get()


def test_learner_chunk_resolution():
    """config.learner_chunk: explicit value wins; 0 = auto (8 on the CPU
    test platform, 800 only on kernel-native TPU backends)."""
    from distributed_ddpg_tpu.parallel.learner import resolve_learner_chunk

    assert resolve_learner_chunk(DDPGConfig(learner_chunk=4)) == 4
    assert resolve_learner_chunk(DDPGConfig()) == 8  # conftest pins cpu
    import distributed_ddpg_tpu.ops.fused_chunk as fc

    orig = fc.runs_native
    fc.runs_native = lambda: True
    try:
        assert resolve_learner_chunk(DDPGConfig()) == 800
    finally:
        fc.runs_native = orig
    with pytest.raises(ValueError, match="learner_chunk"):
        DDPGConfig(learner_chunk=-1)
    # The two rate caps point at each other: with ratio product < 1 each
    # allowance waits on the other forever (livelock); product >= 1 is the
    # equal-return gate's both-sides pin and must be accepted.
    with pytest.raises(ValueError, match="livelock"):
        DDPGConfig(max_learn_ratio=0.5, max_ingest_ratio=0.5)
    DDPGConfig(max_learn_ratio=1.0, max_ingest_ratio=1.0)
    DDPGConfig(max_learn_ratio=1.0, max_ingest_ratio=50.0)
    # Staleness-sweep experiment knob (worker-side env-production brake).
    DDPGConfig(actor_throttle_s=0.25)
    with pytest.raises(ValueError, match="actor_throttle_s"):
        DDPGConfig(actor_throttle_s=-0.1)


def test_backend_jax_ondevice_is_refused_with_where_it_went():
    with pytest.raises(ValueError, match="backend must be") as refused:
        DDPGConfig(backend="jax_ondevice")
    said = str(refused.value)
    assert "'jax_ondevice'" in said
    assert "backend='jax_tpu' with actor_backend='device'" in said
    assert "fused_beat" in said


def test_every_config_field_is_read_outside_config():
    # A knob whose last reader was deleted must not linger in DDPGConfig.
    import dataclasses
    import pathlib
    import re

    import distributed_ddpg_tpu

    package = pathlib.Path(distributed_ddpg_tpu.__file__).parent
    words = set()
    for path in package.rglob("*.py"):
        if path != package / "config.py":
            words.update(re.findall(r"\w+", path.read_text(encoding="utf-8")))
    fields = [f.name for f in dataclasses.fields(DDPGConfig)]
    assert [name for name in fields if name not in words] == []


@pytest.mark.slow
def test_train_jax_max_learn_ratio_caps_learner(tmp_path):
    """max_learn_ratio: the learner may not run ahead of
    replay_min_size + ratio * env_steps (the equal-return gate's knob —
    free-running async would do orders of magnitude more grad steps per
    env step than the reference's sync semantics)."""
    cfg = DDPGConfig(
        backend="jax_tpu",
        env_id="Pendulum-v1",
        actor_hidden=(32, 32),
        critic_hidden=(32, 32),
        num_actors=2,
        total_env_steps=3_000,
        replay_min_size=500,
        replay_capacity=20_000,
        max_learn_ratio=1.0,
        eval_every=0,
        log_path=str(tmp_path / "metrics.jsonl"),
    )
    out = train_jax(cfg)
    # Overshoot is bounded by one chunk past the cap at the final env-step
    # count (env steps keep arriving while the last chunks dispatch, so use
    # the generous bound: budget + one chunk).
    from distributed_ddpg_tpu.parallel.learner import resolve_learner_chunk

    chunk = resolve_learner_chunk(cfg)
    assert out["learner_steps"] > 0
    assert out["learner_steps"] <= cfg.replay_min_size + cfg.total_env_steps * 1.1 + chunk


def test_train_jax_tiny_budget_takes_at_least_one_chunk(tmp_path):
    """Regression: with free ingest (max_ingest_ratio=0) a fast actor can
    deliver the entire env-step budget during warmup. The budget break must
    not fire before the first learner dispatch — a run that met
    replay_min_size and reports success must have learner_steps > 0.
    Budget == replay_min_size makes the overfill deterministic: warmup
    necessarily consumes the whole budget."""
    cfg = DDPGConfig(
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        num_actors=1,
        total_env_steps=128,
        replay_min_size=128,
        replay_capacity=5_000,
        eval_every=0,
        log_path=str(tmp_path / "metrics.jsonl"),
    )
    out = train_jax(cfg)
    assert out["learner_steps"] > 0


@pytest.mark.slow
def test_train_jax_auto_support_resolves_and_reports(tmp_path):
    """train_jax with --v_min=auto --v_max=auto: the warmup sizing must
    resolve concrete bounds before the first dispatch, and the running
    expansion check (incl. the round-5 data-corroboration closure over
    replay.reward_sample) must execute without error and report
    v_min/v_max/support_refusals in the metrics stream."""
    import json

    path = tmp_path / "metrics.jsonl"
    cfg = DDPGConfig(
        distributional=True,
        num_atoms=11,
        v_min=float("nan"),  # the 'auto' sentinel (config.from_flags)
        v_max=float("nan"),
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        num_actors=1,
        total_env_steps=1_200,
        replay_min_size=256,
        replay_capacity=5_000,
        eval_every=0,
        # Lockstep + a tiny pinned chunk: the support metrics ride the
        # 50*chunk cadence, which a free-running tiny budget never reaches
        # (the whole env budget can drain during the first compile).
        max_learn_ratio=1.0,
        max_ingest_ratio=1.0,
        learner_chunk=4,
        log_path=str(path),
    )
    out = train_jax(cfg)
    assert out["learner_steps"] > 0
    rows = [json.loads(l) for l in path.read_text().splitlines()]
    sup = [r for r in rows if "v_min" in r and "v_max" in r]
    assert sup, "no support metrics reported"
    assert all(np.isfinite(r["v_min"]) and np.isfinite(r["v_max"])
               for r in sup)
    assert all(r["v_min"] < r["v_max"] for r in sup)
    assert "support_refusals" in sup[-1]


@pytest.mark.slow
def test_train_jax_async_pipeline(tmp_path):
    cfg = DDPGConfig(
        backend="jax_tpu",
        env_id="Pendulum-v1",
        actor_hidden=(32, 32),
        critic_hidden=(32, 32),
        num_actors=2,
        total_env_steps=4_000,
        replay_min_size=500,
        replay_capacity=50_000,
        prioritized=True,
        n_step=3,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_every=40,
        log_path=str(tmp_path / "metrics.jsonl"),
        # Rate-limit ingest so the 4000-step budget guarantees >= ~70
        # learner steps regardless of how fast the actors produce (the shm
        # transport buffers far more than the old queue did).
        max_ingest_ratio=50.0,
    )
    out = train_jax(cfg)
    assert out["learner_steps"] >= 40
    assert np.isfinite(out["final_return"])
    # JSONL metrics were written.
    lines = open(cfg.log_path).read().strip().splitlines()
    assert len(lines) >= 1
    # A checkpoint landed.
    assert ckpt_lib.latest_step(cfg.checkpoint_dir) is not None


def test_checkpoint_roundtrip_with_replay(tmp_path):
    cfg = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16), prioritized=True)
    state = init_train_state(cfg, 4, 2, seed=0)
    replay = PrioritizedReplay(64, 4, 2, seed=0)
    rng = np.random.default_rng(0)
    for i in range(20):
        replay.add(
            rng.standard_normal(4).astype(np.float32),
            rng.standard_normal(2).astype(np.float32),
            float(i), 0.99,
            rng.standard_normal(4).astype(np.float32),
        )
    replay.update_priorities(np.arange(20), np.linspace(0.1, 2.0, 20))

    path = ckpt_lib.save(str(tmp_path), 42, state, replay, cfg)
    assert os.path.exists(path)

    fresh_replay = PrioritizedReplay(64, 4, 2, seed=1)
    template = init_train_state(cfg, 4, 2, seed=99)
    restored, step, env_steps = ckpt_lib.restore(str(tmp_path), template, fresh_replay)
    assert step == 42
    assert len(fresh_replay) == 20
    np.testing.assert_array_equal(fresh_replay.reward[:20], replay.reward[:20])
    np.testing.assert_allclose(
        fresh_replay._tree.get(np.arange(20)), replay._tree.get(np.arange(20))
    )
    import jax

    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(jax.device_get(state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_auto_support_host_replay_multiprocess_rejected(monkeypatch):
    """Host replay is process-local; auto-support bounds derived from it
    would differ per replica and fork the compiled programs — train_jax
    must refuse the combination loudly."""
    import jax as jax_mod

    monkeypatch.setattr(jax_mod, "process_count", lambda: 2)
    cfg = DDPGConfig(
        distributional=True,
        num_atoms=11,
        v_min=float("nan"),
        v_max=float("nan"),
        host_replay=True,
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        total_env_steps=256,
        replay_min_size=128,
    )
    with pytest.raises(ValueError, match="host_replay.*multi-process"):
        train_jax(cfg)


def test_checkpoint_retention_prunes_old_steps(tmp_path):
    """Latest-N retention (round-5 disk incident: a full-replay checkpoint
    is ~3 GB and the saver kept every cadence point — a 2M-step run would
    fill the disk). Old step_*/config_* pairs must go; keep=0 keeps all;
    restore must still find the latest."""
    cfg = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16))
    state = init_train_state(cfg, 4, 2, seed=0)
    for step in (10, 20, 30, 40, 50):
        ckpt_lib.save(str(tmp_path), step, state, None, cfg, keep=3)
    kept = sorted(p for p in os.listdir(tmp_path) if p.startswith("step_"))
    assert kept == ["step_30", "step_40", "step_50"]
    cfgs = sorted(p for p in os.listdir(tmp_path) if p.startswith("config_"))
    assert cfgs == ["config_30.json", "config_40.json", "config_50.json"]
    assert ckpt_lib.latest_step(str(tmp_path)) == 50
    # keep=0 disables pruning entirely.
    ckpt_lib.save(str(tmp_path), 60, state, None, cfg, keep=0)
    assert len([p for p in os.listdir(tmp_path) if p.startswith("step_")]) == 4


def test_checkpoint_retention_protects_fresh_save_from_stale_dirs(tmp_path):
    """A fresh run reusing a directory with HIGHER-numbered stale
    checkpoints (the --resume=false reuse workflow) must never prune the
    checkpoint it just wrote — numeric sorting alone would. And the stale
    higher-numbered dirs themselves must GO (loudly): left in place they
    would permanently occupy the keep-N retention slots (every later save
    deleting the run's own previous checkpoint) and keep
    latest_step()/resume pointing at another run's state."""
    cfg = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16))
    state = init_train_state(cfg, 4, 2, seed=0)
    for stale in (100_000, 110_000, 120_000):
        ckpt_lib.save(str(tmp_path), stale, state, None, cfg, keep=0)
    ckpt_lib.save(str(tmp_path), 10_000, state, None, cfg, keep=3)
    kept = {p for p in os.listdir(tmp_path) if p.startswith("step_")}
    assert kept == {"step_10000"}, (
        f"stale higher-numbered checkpoints must be pruned: {kept}"
    )
    # Resume now finds THIS run's state, and the next saves rebuild the
    # keep-N redundancy below it.
    assert ckpt_lib.latest_step(str(tmp_path)) == 10_000
    ckpt_lib.save(str(tmp_path), 20_000, state, None, cfg, keep=3)
    ckpt_lib.save(str(tmp_path), 30_000, state, None, cfg, keep=3)
    kept = sorted(p for p in os.listdir(tmp_path) if p.startswith("step_"))
    assert kept == ["step_10000", "step_20000", "step_30000"]


@pytest.mark.slow
def test_train_jax_device_replay_path(tmp_path):
    """Uniform replay -> device-resident buffer with fused on-device
    sampling (the zero-h2d steady-state path); periodic eval runs in the
    background thread and still lands its JSONL records."""
    cfg = DDPGConfig(
        backend="jax_tpu",
        env_id="Pendulum-v1",
        actor_hidden=(32, 32),
        critic_hidden=(32, 32),
        num_actors=2,
        total_env_steps=3_000,
        replay_min_size=300,
        replay_capacity=20_000,
        prioritized=False,
        eval_every=1_000,
        eval_episodes=1,
        log_path=str(tmp_path / "metrics.jsonl"),
    )
    out = train_jax(cfg)
    assert out["learner_steps"] > 0
    assert np.isfinite(out["final_return"])
    import json

    kinds = [json.loads(l)["kind"] for l in open(cfg.log_path)]
    assert "eval" in kinds, f"no background-eval record in {kinds}"
    # Per-phase timing breakdown (SURVEY.md §5) rides in the train/final
    # records (train cadence is 50 chunks; short runs still get the final).
    recs = [json.loads(l) for l in open(cfg.log_path)]
    assert any("t_dispatch_ms" in r for r in recs), recs


def test_async_saver_snapshot_isolation(tmp_path):
    """save_async must snapshot at call time: mutations made to the replay
    AFTER save_async returns (but possibly before the background write
    finishes) must not leak into the checkpoint. Also: while the writer is
    busy, further saves coalesce (skip) instead of queueing."""
    from distributed_ddpg_tpu.replay import UniformReplay

    cfg = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16))
    state = init_train_state(cfg, 4, 2, seed=0)
    replay = UniformReplay(50_000, 4, 2, seed=0)
    rng = np.random.default_rng(0)
    obs = rng.standard_normal((40_000, 4)).astype(np.float32)
    replay.add_batch(
        obs,
        rng.standard_normal((40_000, 2)).astype(np.float32),
        np.arange(40_000, dtype=np.float32),
        np.full(40_000, 0.99, np.float32),
        obs,
    )
    saver = ckpt_lib.AsyncSaver()
    assert saver.save_async(str(tmp_path), 3, state, replay, cfg) is True
    # Mutate immediately — the background write must not see this.
    replay.reward[:40_000] = -1.0
    saver.wait()
    fresh = UniformReplay(50_000, 4, 2, seed=1)
    _, step, _ = ckpt_lib.restore(str(tmp_path), init_train_state(cfg, 4, 2, seed=2), fresh)
    assert step == 3 and len(fresh) == 40_000
    np.testing.assert_array_equal(
        fresh.reward[:40_000], np.arange(40_000, dtype=np.float32)
    )


def test_checkpoint_roundtrip_device_replay(tmp_path):
    """Restore must work into a fresh (empty) DeviceReplay template — the
    resume path in train_jax."""
    from distributed_ddpg_tpu.parallel.mesh import make_mesh
    from distributed_ddpg_tpu.replay.device import DeviceReplay
    from distributed_ddpg_tpu.types import pack_batch_np

    cfg = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16))
    state = init_train_state(cfg, 4, 2, seed=0)
    mesh = make_mesh(-1, 1)
    rep = DeviceReplay(128, 4, 2, mesh=mesh, block_size=32)
    rng = np.random.default_rng(0)
    rep.add_packed(
        pack_batch_np(
            {
                "obs": rng.standard_normal((64, 4)).astype(np.float32),
                "action": rng.standard_normal((64, 2)).astype(np.float32),
                "reward": rng.standard_normal(64).astype(np.float32),
                "discount": np.full(64, 0.99, np.float32),
                "next_obs": rng.standard_normal((64, 4)).astype(np.float32),
            }
        )
    )
    ckpt_lib.save(str(tmp_path), 7, state, rep, cfg)

    fresh = DeviceReplay(128, 4, 2, mesh=mesh, block_size=32)
    template = init_train_state(cfg, 4, 2, seed=9)
    restored, step, env_steps = ckpt_lib.restore(str(tmp_path), template, fresh)
    assert step == 7 and len(fresh) == 64
    import jax

    np.testing.assert_allclose(
        np.asarray(jax.device_get(fresh.storage))[:64],
        np.asarray(jax.device_get(rep.storage))[:64],
    )


def test_restore_rejects_incompatible_config(tmp_path):
    cfg = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16))
    state = init_train_state(cfg, 4, 2, seed=0)
    ckpt_lib.save(str(tmp_path), 5, state, None, cfg, env_steps=1234)
    # Same config restores fine and carries env_steps.
    _, step, env_steps = ckpt_lib.restore(
        str(tmp_path), init_train_state(cfg, 4, 2, seed=1), config=cfg
    )
    assert step == 5 and env_steps == 1234
    # Changed architecture must be rejected with a named mismatch.
    bad = DDPGConfig(actor_hidden=(32, 32), critic_hidden=(16, 16))
    with pytest.raises(ValueError, match="actor_hidden"):
        ckpt_lib.restore(
            str(tmp_path), init_train_state(bad, 4, 2, seed=1), config=bad
        )


@pytest.mark.parametrize("leg,tiles", [("on", 15), ("off", None)])
def test_train_reports_kernel_state_tiles_on_the_kernel_leg_only(tmp_path, leg, tiles):
    """The run fact that shows the lane-major output layers engaged
    (ops/fused_chunk.state_tiles): on the header, the final record and the
    summary of a kernel-leg run through train() (the interpreter here, on
    the data-only mesh's fused-mesh leg), null on the scan leg's. Pendulum
    at 16-16: 15 tiles, 17 with [F, out] heads."""
    import json

    cfg = DDPGConfig(
        backend="jax_tpu",
        env_id="Pendulum-v1",
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        batch_size=16,
        num_actors=1,
        total_env_steps=600,
        replay_min_size=200,
        replay_capacity=4_096,
        learner_chunk=2,
        max_learn_ratio=0.05,
        eval_every=0,
        fused_chunk=leg,
        log_path=str(tmp_path / "metrics.jsonl"),
    )
    out = train_jax(cfg)
    assert out["learner_steps"] > 0
    assert out["fused_chunk_active"] is (leg == "on")
    records = [json.loads(line) for line in open(cfg.log_path)]
    header, final = records[0], records[-1]
    assert header["kind"] == "header" and final["kind"] == "final"
    for rec in (header, final, out):
        assert "kernel_state_tiles" in rec and rec["kernel_state_tiles"] == tiles
    # The actors were handed [F, out] throughout: the run learned and ended clean.
    assert np.isfinite(final["critic_loss"])


def test_train_returns_setup_spans_and_counts_finished_updates(tmp_path):
    """What a job waits through before its first useful step, measured from
    inside (ISSUE 25): five disjoint stages in order, no longer together
    than the call; the launch queue's fields on every train record; and a
    summary that says where its records went."""
    import json
    import time

    from distributed_ddpg_tpu import train as train_mod

    log_path = tmp_path / "metrics.jsonl"
    cfg = DDPGConfig(
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        num_actors=1,
        total_env_steps=4_000,
        replay_min_size=1_500,
        replay_capacity=16_384,
        max_ingest_ratio=6.0,  # paces ingest past the 50-chunk record cadence (test_trace.py)
        eval_every=0,
        trace_dir=str(tmp_path),
        log_path=str(log_path),
    )
    t0 = time.perf_counter()
    out = train_mod.train(cfg)
    wall_s = time.perf_counter() - t0

    spans = out["setup_spans"]
    assert list(spans) == [
        "setup_import", "setup_backend", "setup_build", "setup_fill", "setup_first_chunk",
    ]
    assert all(v >= 0 for v in spans.values())
    assert spans["setup_first_chunk"] == pytest.approx(out["first_chunk_s"], abs=1e-3)
    # The module's own import was paid before the call; the rest lies inside it.
    assert sum(spans.values()) - train_mod._IMPORT_S <= wall_s
    assert out["setup_programs_compiled"] >= 1 and out["setup_compile_s"] > 0
    assert out["log_path"] == str(log_path)

    # The stages as the flight recorder saw them: disjoint, in order, on one thread.
    events = json.loads((tmp_path / "trace.json").read_text())["traceEvents"]
    stages = sorted(
        (e for e in events if e.get("ph") == "X" and e["name"].startswith("setup_")),
        key=lambda e: e["ts"],
    )
    assert [e["name"] for e in stages] == [
        "setup_import", "setup_backend", "setup_build", "setup_fill", "setup_first_chunk",
    ]
    assert len({e["tid"] for e in stages}) == 1
    for a, b in zip(stages, stages[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1.0  # microseconds
    # dispatch spans carry the launch's index; refresh its learner step
    chunks = [e["args"]["chunk"] for e in events if e.get("name") == "dispatch"]
    assert chunks == list(range(len(chunks))) and len(chunks) == out["learner_steps"] // 8
    assert all("learner_step" in e["args"] for e in events if e.get("name") == "refresh")

    records = [json.loads(line) for line in log_path.read_text().splitlines()]
    train_recs = [r for r in records if r["kind"] == "train"]
    assert train_recs
    for r in train_recs:
        assert 1 <= r["launches_in_flight_mean"] <= r["launches_in_flight_max"]
        assert 0 <= r["n_dispatch_starved"] <= r["n_dispatch"]
    final = records[-1]
    assert final["setup_spans"] == spans and final["kind"] == "final"
    # After a finished run every update dispatched is an update done.
    assert final["learner_steps_per_sec"] > 0
