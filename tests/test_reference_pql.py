"""PQL's learner against its plain reference (benchmarks/reference/pql.py), at a
small size on the CPU (obs 11, act 3, nets 32-16-8, batch 64): the single
step, the scan chunk and a 2-device data mesh follow the reference over
2 * 2 + 1 updates from a step off the delay's phase; five bent references do
not; the configuration stays outside the kernel's VMEM budget.

The reference is loaded from its one file under benchmarks/, by path, so
there is no second copy to drift.
"""

import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import delayed_updates, init_train_state, make_learner_step
from distributed_ddpg_tpu.ops import fused_chunk
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.types import unpack_batch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

ENV = {"obs_dim": 11, "act_dim": 3, "action_scale": 1.0, "action_offset": 0.0}
HP = {"hidden": [32, 16, 8], "gamma": 0.99, "tau": 0.05, "actor_lr": 5e-4, "critic_lr": 5e-4,
      "batch_size": 64, "policy_delay": 2}
# An odd first step: the first update skips the actor, and the delay's phase
# (state.step % 2) is carried into the launch, not restarted.
UPDATES, STEP0, SEED = 2 * 2 + 1, 3, 11


@pytest.fixture(scope="module")
def pql():
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module("reference.pql")
    finally:
        sys.path.remove(BENCH)


def config(**kw):
    return DDPGConfig(
        twin_critic=True, policy_delay=HP["policy_delay"], target_noise=0.0, n_step=3,
        actor_hidden=tuple(HP["hidden"]), critic_hidden=tuple(HP["hidden"]), action_insert_layer=0,
        batch_size=HP["batch_size"], actor_lr=HP["actor_lr"], critic_lr=HP["critic_lr"], tau=HP["tau"],
        seed=SEED, **kw,
    )


def rows(seed, n):
    """Packed 3-step rows [obs | action | R3 | d3 | next_obs | w]: d3 is
    gamma^3 on most, gamma^2 and gamma on rows an episode's end cut short,
    and 0 on a few that terminated."""
    o, a = ENV["obs_dim"], ENV["act_dim"]
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    obs = jax.random.normal(k[0], (n, o))
    steps = jnp.where(jax.random.uniform(k[3], (n, 1)) > 0.1, 3, jax.random.randint(k[5], (n, 1), 1, 3))
    disc = HP["gamma"] ** steps * (jax.random.uniform(k[4], (n, 1)) > 0.05)
    return jnp.concatenate(
        [obs, jax.random.uniform(k[1], (n, a), minval=-1.0, maxval=1.0), jax.random.normal(k[2], (n, 1)), disc,
         obs + 0.1 * jax.random.normal(k[4], (n, o)), jnp.ones((n, 1))], axis=1,
    ).astype(jnp.float32)


def at_step(state, step0):
    """`state` as a run that has made `step0` updates would carry its
    counters (the moments stay zero: both sides start from the same)."""
    return state._replace(
        step=jnp.asarray(step0, jnp.int32),
        actor_opt=state.actor_opt._replace(count=jnp.asarray(delayed_updates(step0, HP["policy_delay"]), jnp.int32)),
        critic_opt=state.critic_opt._replace(count=jnp.asarray(step0, jnp.int32)),
    )


def view(state):
    return {"actor": state.actor_params, "critic": state.critic_params,
            "target_actor": state.target_actor_params, "target_critic": state.target_critic_params}


def grown(critic):
    """The critics' last layers at 300 times their seeded size (the paper's
    +-3e-3): Q values of size 1, as a trained critic's, so that what enters a
    target through Q' shows in the TD errors."""
    return (*critic[:-1], jax.tree.map(lambda x: 300.0 * x, critic[-1]))


def seeded(pql, hp=HP):
    s0 = at_step(init_train_state(config(), ENV["obs_dim"], ENV["act_dim"], SEED), STEP0)
    s0 = s0._replace(critic_params=grown(s0.critic_params), target_critic_params=grown(s0.target_critic_params))
    ref0 = pql.init(SEED, ENV, hp)
    ref0["critic"] = ref0["target_critic"] = grown(ref0["critic"])
    ref0["step"] = s0.step
    ref0["actor_opt"]["count"], ref0["critic_opt"]["count"] = s0.actor_opt.count, s0.critic_opt.count
    return s0, ref0


def program(leg, s0, batches):
    """(state after, td [K, B]) from the program's own updates on `batches`
    [K, B, width] from `s0`: update by update through the jitted step, in one
    scan, or through ShardedLearner's chunk program on a 2-device data mesh."""
    cfg = config()
    step = make_learner_step(cfg, ENV["action_scale"], action_offset=ENV["action_offset"])

    def one(s, packed):
        out = step(s, unpack_batch(packed, ENV["obs_dim"], ENV["act_dim"]))
        return out.state, out.td_errors

    if leg == "step":
        tds, s = [], s0
        for packed in batches:
            s, td = jax.jit(one)(s, packed)
            tds.append(td)
        return s, jnp.stack(tds)
    if leg == "scan":
        return jax.jit(lambda s, b: jax.lax.scan(one, s, b))(s0, batches)
    mesh = mesh_lib.make_mesh(data_axis=2, model_axis=1, devices=jax.devices()[:2])
    learner = ShardedLearner(
        cfg, ENV["obs_dim"], ENV["act_dim"], ENV["action_scale"], ENV["action_offset"],
        mesh=mesh, chunk_size=batches.shape[0],
    )
    assert not learner.fused_chunk_active
    # a copy: the chunk program donates its state, and `s0` is compared later
    learner.state = jax.device_put(jax.tree.map(jnp.copy, s0), learner._state_sharding)
    out = learner.run_chunk({k: np.asarray(v) for k, v in unpack_batch(batches, ENV["obs_dim"], ENV["act_dim"])._asdict().items()})
    return jax.device_get(out.state), jax.device_get(out.td_errors)


def gaps(pql, hp, leg="scan", transform=lambda b: b):
    """(largest |td - reference td| over the chunk, largest leaf gap of any
    net's change over the chunk as a share of the leaf's own change or the
    net's median leaf's) between the program and a reference built from `hp`
    that sees `transform(batches)`."""
    batches = rows(3, UPDATES * HP["batch_size"]).reshape(UPDATES, HP["batch_size"], -1)
    s0, ref0 = seeded(pql, hp)
    s1, td = program(leg, s0, batches)
    ref1, ref = jax.jit(lambda s, b: jax.lax.scan(pql.make_step(SEED, ENV, hp), s, b))(ref0, transform(batches))
    worst = 0.0
    for k in view(s1):
        d_ref = [np.asarray(b1 - b0) for b1, b0 in zip(jax.tree.leaves(ref1[k]), jax.tree.leaves(ref0[k]))]
        d_prog = [np.asarray(a1 - a0) for a1, a0 in zip(jax.tree.leaves(view(s1)[k]), jax.tree.leaves(view(s0)[k]))]
        floor = np.median([np.linalg.norm(d) for d in d_ref])
        for dr, dp in zip(d_ref, d_prog):
            worst = max(worst, float(np.linalg.norm(dp - dr) / max(np.linalg.norm(dr), floor, 1e-30)))
    return float(np.max(np.abs(np.asarray(td) - np.asarray(ref["td"])))), worst, (s0, s1, ref0, ref1)


@pytest.mark.parametrize("leg", ["step", "scan", "mesh"])
def test_program_follows_the_reference_from_a_step_off_the_delays_phase(pql, leg):
    td_gap, change_gap, (s0, s1, ref0, ref1) = gaps(pql, HP, leg)
    for k in view(s0):  # the seeded weights: the same keys, the same draws
        for a, b in zip(jax.tree.leaves(view(s0)[k]), jax.tree.leaves(ref0[k])):
            np.testing.assert_array_equal(a, b)
    # returns of size 1 and five Adam steps of 5e-4: 1e-4 absolute holds the
    # order of rounding (XLA:CPU's default dot against Precision.HIGHEST) and
    # nothing else; each bent reference below moves it in the second digit
    assert td_gap < 1e-4
    assert change_gap < 0.01
    assert int(s1.step) == STEP0 + UPDATES == int(ref1["step"])
    # updates at steps 3..7: the actor moved on 4 and 6
    assert int(s1.actor_opt.count) - int(s0.actor_opt.count) == 2 == int(ref1["actor_opt"]["count"]) - int(ref0["actor_opt"]["count"])


def _gamma_for_d3(b):
    d = ENV["obs_dim"] + ENV["act_dim"] + 1
    return b.at[..., d].set(jnp.where(b[..., d] > 0, HP["gamma"], 0.0))


@pytest.mark.parametrize("bent", ["gamma_for_d3", "one_critic_in_the_target", "policy_on_every_update",
                                  "smoothing_noise_on", "tau_0.005"])
def test_a_bent_reference_is_not_followed(pql, bent, monkeypatch):
    """Each departure from the three steps of the issue, put into the
    reference: the program no longer follows it, by the TD errors (a target
    built otherwise) or by the nets' change over the chunk (a beat or a rate
    taken otherwise)."""
    hp, transform = dict(HP), (lambda b: b)
    if bent == "gamma_for_d3":
        transform = _gamma_for_d3  # d3 recomputed as one step's discount, not read from the row
    elif bent == "one_critic_in_the_target":
        class FirstForMin:  # jax.numpy for the reference alone, its `min` bent
            min = staticmethod(lambda x, axis: x[0])
            __getattr__ = staticmethod(lambda name: getattr(jnp, name))

        monkeypatch.setattr(pql, "jnp", FirstForMin())
    elif bent == "policy_on_every_update":
        hp["policy_delay"] = 1
    elif bent == "smoothing_noise_on":
        clean = pql.c.mlp_body

        def noisy_policy_head(mm, params, x):
            out = clean(mm, params, x)
            # the actor's head alone is act_dim wide: TD3's smoothing, 0.2 clipped at 0.5
            if out.shape[-1] == ENV["act_dim"]:
                out = out + jnp.clip(0.2 * jax.random.normal(jax.random.PRNGKey(5), out.shape), -0.5, 0.5)
            return out

        monkeypatch.setattr(pql.c, "mlp_body", noisy_policy_head)
    else:
        hp["tau"] = 0.005
    td_gap, change_gap, _ = gaps(pql, hp, "scan", transform)
    assert td_gap > 1e-3 or change_gap > 0.1, (bent, td_gap, change_gap)


def test_the_configuration_is_outside_the_kernels_budget_and_picks_the_scan_leg():
    """PQL's resident state (parameters, targets and both Adam moments of
    three 512-256-128 nets, 10.9 MB) is over VMEM_STATE_BUDGET, so
    `fits_vmem` is false at the cell's own flags and the learner picks the
    scan leg by itself; the action joining at the input is outside the
    kernel's envelope too."""
    cell = json.load(open(os.path.join(BENCH, "configs", "pql-isaac-humanoid.json")))
    traffic = json.load(open(os.path.join(BENCH, "traffic", "devactors.json")))
    cfg = DDPGConfig.from_flags(cell["flags"] + traffic["flags"])
    assert (cfg.n_step, cfg.batch_size, cfg.device_actor_envs, cfg.replay_capacity) == (3, 8192, 4096, 5_000_000)
    assert cfg.learner_chunk == 8 * cfg.device_actor_chunk  # one rollout program a launch
    assert cfg.max_ingest_ratio * cfg.learner_chunk == cfg.device_actor_envs * cfg.device_actor_chunk
    assert not fused_chunk.fits_vmem(cfg, cell["env"]["obs_dim"], cell["env"]["act_dim"])
    assert not fused_chunk.fits_vmem(cfg.replace(action_insert_layer=1), cell["env"]["obs_dim"], cell["env"]["act_dim"])
    assert cell["expects"] == {"fused_chunk_active": False}
    small = config(fused_chunk="auto")
    learner = ShardedLearner(small, ENV["obs_dim"], ENV["act_dim"], 1.0, 0.0, chunk_size=4)
    assert not learner.fused_chunk_active and learner.kernel_state_tiles is None


def test_work_counts_what_the_algorithm_needs_once(pql):
    cell = json.load(open(os.path.join(BENCH, "configs", "pql-isaac-humanoid.json")))
    w = pql.work(cell["env"], cell["reference"]["hp"])
    # every update 31.6 GFLOP (targets 11.2, the twin critics' forward and
    # backward 20.4), the policy's half 16.6 on one update in two
    assert 39.5e9 < w["flops"] < 40.5e9
    assert w["row_bytes"] == 4.0 * 8192 * 240
    values = 221_824 + 917 + 2 * (230_016 + 897)
    assert w["state_bytes"] == 2.0 * 4 * 4 * values
    eager = pql.work(cell["env"], {**cell["reference"]["hp"], "policy_delay": 1})
    assert eager["flops"] - w["flops"] == pytest.approx(8.31e9, rel=0.01)
