"""The learner's chunk call on a seeded synthetic ring, under the harness's
own check, and the check's control: the reference itself, its products'
operands rounded to the next precision below the configuration's
(`check.control_operands`), put in the program's place. The tests run both
at a small size on the CPU (test_runs.py); on the chip, at a cell's own size
and on the ring the actors fill, calibrate.py reads the same numbers.
"""

import importlib
import os
import sys
import threading

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class FakeRing:
    """What `run_sample_chunk` needs of a DeviceReplay: the lock and the
    (storage, size) pair. Rows are seeded, all different, in the packed
    layout, with a few terminal rows (discount 0)."""

    def __init__(self, seed, rows, env):
        import jax
        import jax.numpy as jnp

        o, a = env["obs_dim"], env["act_dim"]
        k = jax.random.split(jax.random.PRNGKey(seed ^ 0x51D), 5)
        obs = jax.random.normal(k[0], (rows, o))
        act = env["action_offset"] + env["action_scale"] * jax.random.uniform(k[1], (rows, a), minval=-1.0, maxval=1.0)
        rew = jax.random.normal(k[2], (rows, 1))
        disc = 0.99 * (jax.random.uniform(k[3], (rows, 1)) > 0.01)
        nobs = obs + 0.1 * jax.random.normal(k[4], (rows, o))
        self.storage = jnp.concatenate([obs, act, rew, disc, nobs, jnp.ones((rows, 1))], axis=1).astype(jnp.float32)
        self.size = jnp.asarray(rows, jnp.int32)
        self.dispatch_lock = threading.RLock()

    def device_state(self):
        return self.storage, self.size


def break_learner(learner):
    """The fault `change_gap` is there to catch: a chunk step that hands its
    state back unchanged (it still returns losses and TD errors)."""
    real = learner._sample_chunk_step

    def unchanged(state, key, storage, size):
        import jax
        import jax.numpy as jnp

        out, key = real(jax.tree.map(jnp.copy, state), key, storage, size)
        return out._replace(state=state), key

    learner._sample_chunk_step = unchanged


def run_once(config, seed, extra_flags=(), chunk=None, rows=2000, break_step=False):
    """One first-chunk check of a learner built as the trainer builds it."""
    for p in (ROOT, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner, resolve_learner_chunk
    from harness.check import ChunkCheck

    env = config["env"]
    cfg = DDPGConfig.from_flags(list(config["flags"]) + [f"--seed={seed}"] + list(extra_flags))
    learner = ShardedLearner(
        cfg, env["obs_dim"], env["act_dim"], env["action_scale"], env["action_offset"],
        chunk_size=chunk or resolve_learner_chunk(cfg), replay_sharding=cfg.replay_sharding,
    )
    if break_step:
        break_learner(learner)
    reference = importlib.import_module("reference." + config["reference"]["module"])
    check = ChunkCheck(
        reference, seed, env, config["reference"]["hp"], config["check"]["limits"],
        config["precision"]["products"],
    )
    check.install(ShardedLearner)
    try:
        learner.run_sample_chunk(FakeRing(seed, rows, env))
    finally:
        check.uninstall()
    return dict(check.result, fused_chunk_active=bool(learner.fused_chunk_active), seconds=check.seconds)


def control_verdict(drawn, operand_dtype, ref, limits):
    """The control's verdict: the reference with its operands rounded to
    `operand_dtype`, put in the program's place on the rows `drawn` names,
    against `ref` (`check.reference_side` of the same rows). Its per-update
    TD errors ride along under "td"."""
    import jax.numpy as jnp

    from harness import check as check_lib

    ctl0, ctl1, ctl = check_lib.follow(*drawn, operand_dtype=operand_dtype)
    means = {k: float(jnp.mean(v)) for k, v in ctl.items() if v.ndim == 1}
    verdict = check_lib.judge(
        *check_lib.compare(ctl0, ctl1, ref[0], ref[1], ctl["td"], means, *ref[2:]), limits
    )
    return dict(verdict, td=ctl["td"])


DUMP_UPDATES = 32  # TD errors of updates 0..32 go into a dump


def control_check(control_operands, *args, dump=None):
    """The harness's check that also judges the control on the rows of the
    program's first call (`result["control"]`), and with `dump` (a path)
    saves the first updates' TD errors of program, reference and control and
    the seeded state's on the same rows. The benchmark's own runs never run
    the control; calibrate.py does, on the chip."""
    from harness import check as check_lib

    class ControlCheck(check_lib.ChunkCheck):
        def verdict(self, drawn, prog, ref):
            import numpy as np

            result = super().verdict(drawn, prog, ref)
            control = control_verdict(drawn, control_operands, ref, self.limits)
            ctl_td = control.pop("td")
            result["control"] = control
            if dump:
                n = min(DUMP_UPDATES, drawn[-2] - 1)
                np.savez(
                    dump, prog_td=np.asarray(prog[2][: n + 1]), ref_td=np.asarray(ref[2]["td"][: n + 1]),
                    ctl_td=np.asarray(ctl_td[: n + 1]), stated_td0=np.asarray(ref[3]),
                    seeded_td=np.asarray(check_lib.seeded_td_on(*drawn, updates=n)),
                )
            return result

    return ControlCheck(*args)


def control_once(config, seed, chunk, rows=2000):
    """The control's numbers: the reference with rounded operands against
    the reference, on the rows a learner seeded alike would draw."""
    for p in (ROOT, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from harness import check as check_lib

    env, hp = config["env"], config["reference"]["hp"]
    reference = importlib.import_module("reference." + config["reference"]["module"])
    ring = FakeRing(seed, rows, env)
    drawn = (reference, seed, env, hp, jax.random.PRNGKey(seed), ring.storage, ring.size, chunk, hp["batch_size"])
    ref = check_lib.reference_side(drawn, config["precision"]["products"])
    verdict = control_verdict(drawn, config["check"]["control_operands"], ref, config["check"]["limits"])
    del verdict["td"]
    return verdict
