"""The fused learner step — the metric-defining hot loop (SURVEY.md §3.3).

One pure function performs, in a single traced XLA program:
  1. critic TD update (or D4PG categorical update),
  2. DPG actor update (against the pre-update critic, matching the
     reference's semantics where both gradients are computed from the same
     forward values before either apply),
  3. Adam for both nets,
  4. Polyak target updates (SURVEY.md §3.4).

The reference crosses the worker<->parameter-server gRPC boundary three times
per step (params pull, grads push, target assign — SURVEY.md §3.3). Here the
step compiles to one device program: zero host crossings; the only transfers
are the incoming minibatch (double-buffered via train.py's ChunkPrefetcher)
and the
outgoing per-sample TD errors for PER priority updates.

`axis_name` threads an explicit `jax.lax.psum` gradient AllReduce for the
shard_map/ICI path (parallel/learner.py); under plain jit+sharding the same
collective is inserted by XLA from the sharding annotations, and psum is a
no-op (axis_name=None).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.ops import losses
from distributed_ddpg_tpu.ops.optim import adam_update
from distributed_ddpg_tpu.ops.polyak import polyak_update, target_update
from distributed_ddpg_tpu.trace import device_scope
from distributed_ddpg_tpu.types import Batch, ObsSpec, OptState, TrainState, Windows
from distributed_ddpg_tpu.models import pixels as pixnet
from distributed_ddpg_tpu.models import recurrent as recnet
from distributed_ddpg_tpu.models.mlp import (
    actor_init, critic_init, gaussian_apply, lnmlp_init, norm_moved,
    rs_merged, rs_written, simba_init,
)
from distributed_ddpg_tpu.ops import pixels as pix


class StepOutput(NamedTuple):
    state: TrainState
    td_errors: jnp.ndarray   # f32[B] — for PER priority updates
    metrics: dict


# The exact keys of StepOutput.metrics (the dict built in make_learner_step).
# Sharded wrappers build out-sharding/out-spec pytrees from this, so it must
# stay in lockstep with the metrics dict below — which is why it lives here.
METRIC_KEYS = (
    "critic_loss",
    "actor_loss",
    "mean_q",
    "td_abs_mean",
    "critic_grad_norm",
    "actor_grad_norm",
)


def metric_keys(config: DDPGConfig) -> tuple:
    """The exact keys of StepOutput.metrics for `config`'s family, in the
    order the megakernel stacks them. The categorical (D4PG) branch reports
    `c51_edge_mass` besides: the share of the projected target's mass on the
    support's two end atoms, the number a user sets v_min / v_max by. The
    twin-critic (TD3) branch reports `td3_twin_gap` besides: the batch mean
    of |Q'_1 - Q'_2| at the smoothed target action, how much the clipped
    minimum bites. An ensemble (REDQ) run, config.redq, reports
    `redq_q_spread` besides: the batch mean of the standard deviation over
    the N online Q_i(s, a), how far the critics lie apart where the
    in-target minimum acts. A CrossQ run, config.crossq, reports
    `bn_stat_gap` besides: the mean over the critics' normalised features of
    |batch mean - running mean| / running standard deviation, how far the
    evaluation-mode critics the actor climbs are from the training-mode
    critics the loss fits. A residual (SimBa) run, config.simba, reports
    `resid_share` (the mean over the critics' blocks and the batch of
    |f(LN(x))| / |x + f(LN(x))|: how much of the stream each block
    rewrites), `rsnorm_count` (the rows the input normaliser has seen) and
    `rsnorm_drift` (the mean over the features of |batch mean - running
    mean| / running standard deviation). A chunk reports its last update's
    of each (chunk_metrics). A pixel (DrQ-v2) run, config.pixels, reports
    beside the twin gap `encoder_grad_norm` (the norm of the critic loss's
    gradient on the encoder alone, the chunk's mean), `explore_sigma` (the
    scheduled noise scale of the chunk's last update) and `aug_offset_mean`
    (the mean of the launch's crop offsets: `aug_pad` in expectation, a
    counter that says the draw is alive). An MPO run, config.mpo, reports
    beside the categorical critic's edge mass `mpo_weight_ess` (the batch
    mean of 1 / sum_j w_j^2 over the E-step's value weights: how many of the
    mpo_samples actions a state the improved policy is fitted to),
    `mpo_kl_mean_ratio` (the mean over the action's dimensions of KL_mean /
    mpo_epsilon_mean: above 1 the mean's bound is broken and its multiplier
    grows) and `mpo_temperature` (eta out of log space). A recurrent run,
    config.recurrent, reports beside the twin gap `seq_valid_frac`: the
    share of the batch's B x L window steps that are real (mask 1), the
    chunk's mean. Only those branches
    have the keys, so every other family's programs and records are what
    they were."""
    if config.recurrent:
        return METRIC_KEYS + ("td3_twin_gap",) + RECURRENT_KEYS
    if config.mpo:
        return METRIC_KEYS + ("c51_edge_mass",) + MPO_KEYS
    if config.distributional:  # config.py: never with twin_critic or sac
        return METRIC_KEYS + ("c51_edge_mass",)
    if config.pixels:
        return METRIC_KEYS + ("td3_twin_gap",) + PIXEL_KEYS
    if config.twin_critic:
        return METRIC_KEYS + ("td3_twin_gap",)
    if config.redq:
        return METRIC_KEYS + ("redq_q_spread",)
    if config.crossq:
        return METRIC_KEYS + ("bn_stat_gap",)
    if config.simba:
        return METRIC_KEYS + SIMBA_KEYS
    return METRIC_KEYS


SIMBA_KEYS = ("resid_share", "rsnorm_count", "rsnorm_drift")
PIXEL_KEYS = ("encoder_grad_norm", "explore_sigma", "aug_offset_mean")
MPO_KEYS = ("mpo_weight_ess", "mpo_kl_mean_ratio", "mpo_temperature")
RECURRENT_KEYS = ("seq_valid_frac",)
# Metrics a chunk reports for its LAST update, not as a mean over the K.
LAST_UPDATE_KEYS = (
    "c51_edge_mass", "td3_twin_gap", "redq_q_spread", "bn_stat_gap", *SIMBA_KEYS,
    "explore_sigma", *MPO_KEYS,
)


def chunk_metrics(ms: dict) -> dict:
    """[K]-stacked per-update metrics of a scan chunk -> the chunk's: each
    key's mean over the K updates, except LAST_UPDATE_KEYS, which are the
    chunk's last update's, as the megakernel computes them on its last grid
    step only. For every other family this is the tree.map it replaces."""
    with device_scope("metrics"):
        out = jax.tree.map(lambda x: jnp.mean(x), ms)
        for k in LAST_UPDATE_KEYS:
            if k in ms:
                out[k] = ms[k][-1]
    return out


def delayed_updates(steps, delay: int):
    """How many of the learner steps 0 .. steps-1 move the TD3 actor and the
    targets (under sac: the actor and the temperature): the multiples of
    `delay` below `steps`. The one rule behind the scan steps' cond
    (state.step % delay == 0), the kernel's schedule and actor Adam count,
    and the records' `td3_actor_updates` / `redq_policy_updates` /
    `crossq_policy_updates`. Works on
    ints and on traced scalars."""
    return (steps + delay - 1) // delay


def _maybe_psum_mean(tree, axis_name: Optional[str]):
    if axis_name is None:
        return tree
    # lint: ok(collective-discipline): only called from inside the jitted
    # learner step — axis_name exists only when the pmap/shard_map builder
    # (parallel/) threads it, so this traces under a mesh, never eagerly
    return jax.lax.pmean(tree, axis_name)


# --- the learner's noise streams ---
# TD3's target-smoothing noise and SAC's sampling noise are keyed by
# fold_in(seed-derived base, state.step): the stream is deterministic and
# replayable, and every data-parallel replica derives the identical key from
# the replicated state.step, so replicas cannot fork. The base
# (`noise_base_key`) never changes during a run and is no part of the
# TrainState; a chunk program takes it as an ARGUMENT (ShardedLearner holds
# it replicated on the mesh beside its sampling key), so the program's text
# holds nothing derived from config.seed and one compiled chunk serves every
# seed of a configuration. One definition of each stream: a single step draws
# its own (`step_noise`), and every chunk program, on the scan leg and the
# kernel leg alike, pre-draws its K steps' worth in front of its loop
# (`chunk_noise`) — the same bits, because the draw does not depend on the
# parameters (SAC: u = mean + std * eps). REDQ's in-target subset is a third
# member of SAC's pair, from the same step key BEFORE any per-device fold: the
# critics' gradient is averaged across replicas against one y per row, so
# every replica must draw the same critics.

_SUBSET_FOLD = 0x5B5E7


def draws_subset(config: DDPGConfig) -> bool:
    """Whether each update's target takes a drawn subset of the ensemble
    (REDQ's M < N) and not all of it."""
    return bool(config.sac and config.target_subset < config.critic_ensemble)


def draws_noise(config: DDPGConfig) -> bool:
    """Whether `config`'s learner step draws noise at all."""
    return bool(
        config.sac or config.pixels or config.mpo
        or (config.twin_critic and config.target_noise > 0.0)
    )


def noise_base_key(config: DDPGConfig):
    """The base key of `config`'s one noise stream (None, an empty pytree:
    it has none). The only value the learner derives from config.seed
    besides its initial state and its sampling key, and like them handed to
    the chunk programs, not traced into them."""
    if not draws_noise(config):
        return None
    return jax.random.PRNGKey(
        config.seed
        ^ (
            0x5AC0 if config.sac else 0xD2C if config.pixels
            else 0x3B0 if config.mpo else 0x7D3AF
        )
    )


def step_noise(config: DDPGConfig, base, step, batch: int, act_dim: int,
               device_fold=None):
    """The noise of learner step `step`, what make_learner_step's step takes
    as its third argument. SAC: the standard normals (eps_next, eps_cur),
    each [B, act], the critic-target draw at s' first, then the actor draw
    at s; with target_subset < critic_ensemble a third member, the update's
    in-target critics, int32[M], distinct and uniform over the subsets, the
    same on every device. TD3: the target-smoothing noise [B, act], scaled
    and clipped. Pixels (DrQ-v2): the triple (crop offsets int32[B, 4] =
    (dy, dx) of `obs` then of `next_obs`, uniform in 0..2*aug_pad; the
    target action's noise; the actor loss's), both noises sigma * N(0, I)
    clipped at target_noise_clip with sigma the schedule at this step
    (ops/pixels.sigma_at). MPO: the standard normals of the E-step's draws,
    f32[B, mpo_samples, act], the rows first so that a data mesh shards them
    like the batch. Recurrent (config.recurrent): TD3's smoothing noise for
    every step of every window, f32[B, seq_len, act]. None
    where the algorithm draws none (DDPG, D4PG, TD3 without smoothing).
    `device_fold` (lax.axis_index under shard_map) folds a per-device term
    AFTER the step fold, so that each shard of a global batch draws its own
    rows: without it every shard would draw the identical matrix and a
    global batch of B*D rows would get only B unique perturbations."""
    if not draws_noise(config):
        return None
    step_key = key = jax.random.fold_in(base, step)
    if device_fold is not None:
        key = jax.random.fold_in(key, device_fold)
    if config.sac:
        k_next, k_cur = jax.random.split(key)
        eps = (
            jax.random.normal(k_next, (batch, act_dim)),
            jax.random.normal(k_cur, (batch, act_dim)),
        )
        if not draws_subset(config):
            return eps
        subset = jax.random.choice(
            jax.random.fold_in(step_key, _SUBSET_FOLD),
            config.critic_ensemble, (config.target_subset,), replace=False,
        )
        return (*eps, subset.astype(jnp.int32))
    if config.mpo:
        return jax.random.normal(key, (batch, config.mpo_samples, act_dim))
    if config.pixels:
        k_off, k_next, k_cur = jax.random.split(key, 3)
        sigma = pix.sigma_at(config.sigma_schedule, step)
        clip = config.target_noise_clip
        return (
            jax.random.randint(k_off, (batch, 4), 0, 2 * config.aug_pad + 1),
            *(
                jnp.clip(sigma * jax.random.normal(k, (batch, act_dim)), -clip, clip)
                for k in (k_next, k_cur)
            ),
        )
    shape = (
        (batch, config.seq_len, act_dim) if config.recurrent
        else (batch, act_dim)
    )
    return jnp.clip(
        config.target_noise * jax.random.normal(key, shape),
        -config.target_noise_clip,
        config.target_noise_clip,
    )


def noise_per_row(config: DDPGConfig):
    """step_noise's structure, True where a member has the batch's rows as
    its first axis (a data mesh shards it like the batch) and False where it
    is the same on every replica (REDQ's subset)."""
    if not draws_noise(config):
        return None
    if config.pixels:
        return (True, True, True)
    if not config.sac:
        return True  # TD3's smoothing noise, MPO's draws
    return (True, True, False) if draws_subset(config) else (True, True)


def chunk_noise(config: DDPGConfig, base, step0, chunk: int, batch: int,
                act_dim: int, device_fold=None):
    """step_noise for the K steps from `step0`, stacked [K, ...]: drawn once
    a launch, in front of the loop that scans over it. `base` is the
    stream's base key (noise_base_key), a traced argument of the chunk
    program; None where the algorithm draws none."""
    if not draws_noise(config):
        return None
    with device_scope("noise"):
        return jax.vmap(
            lambda s: step_noise(config, base, s, batch, act_dim, device_fold)
        )(step0 + jnp.arange(chunk))


def _opt_init(params) -> OptState:
    """Adam's state before any step: zero moments shaped like `params`."""
    return OptState(
        mu=jax.tree.map(jnp.zeros_like, params),
        nu=jax.tree.map(jnp.zeros_like, params),
        count=jnp.zeros((), jnp.int32),
    )


def init_pixel_state(config: DDPGConfig, obs: ObsSpec, act_dim: int, seed: int) -> TrainState:
    """DrQ-v2's state (models/pixels.py): the encoder in the critic's tree
    under the critic's Adam, a target for the critic's trunk and heads
    alone, and no target actor (the slot None, as CrossQ's are)."""
    critic = pixnet.critic_init(
        seed, obs.shape, config.encoder_channels, config.feature_dim,
        tuple(config.critic_hidden), act_dim,
    )
    actor = pixnet.actor_init(
        seed, critic["trunk"]["w"].shape[0], config.feature_dim,
        tuple(config.actor_hidden), act_dim,
    )
    return TrainState(
        actor_params=actor,
        critic_params=critic,
        target_actor_params=None,
        target_critic_params=jax.tree.map(
            jnp.copy, pixnet.trained_with_target(critic)
        ),
        actor_opt=_opt_init(actor),
        critic_opt=_opt_init(critic),
        step=jnp.zeros((), jnp.int32),
    )


def init_mpo_state(config: DDPGConfig, obs_dim: int, act_dim: int, k_actor, k_critic) -> TrainState:
    """DMPO's state (models/mlp.lnmlp_init): a LayerNormMLP policy with a
    [mean | scale] head, a LayerNormMLP categorical critic on [obs |
    action], both targets, and the four dual variables as `log_alpha`'s
    small tree (ops/losses.MPO_DUALS: two temperatures, f32[1] as the
    source holds them, so that their arithmetic rides the vector unit's
    fusions and not the scalar core (PERF.md §6, PR 46), and the mean's and
    the scale's KL multipliers, one a dimension of the action) with their
    own Adam's moments in `alpha_opt`."""
    actor = lnmlp_init(k_actor, obs_dim, 2 * act_dim, tuple(config.actor_hidden))
    critic = lnmlp_init(
        k_critic, obs_dim + act_dim, config.num_atoms, tuple(config.critic_hidden)
    )
    duals = {
        "log_temperature": jnp.full(
            (1,), config.mpo_init_log_temperature, jnp.float32
        ),
        "log_penalty_temperature": jnp.full(
            (1,), config.mpo_init_log_temperature, jnp.float32
        ),
        "log_alpha_mean": jnp.full(
            (act_dim,), config.mpo_init_log_alpha_mean, jnp.float32
        ),
        "log_alpha_stddev": jnp.full(
            (act_dim,), config.mpo_init_log_alpha_stddev, jnp.float32
        ),
    }
    return TrainState(
        actor_params=actor,
        critic_params=critic,
        target_actor_params=jax.tree.map(jnp.copy, actor),
        target_critic_params=jax.tree.map(jnp.copy, critic),
        actor_opt=_opt_init(actor),
        critic_opt=_opt_init(critic),
        step=jnp.zeros((), jnp.int32),
        log_alpha=duals,
        alpha_opt=_opt_init(duals),
    )


def _with_targets(actor, critic) -> TrainState:
    """The state of two nets with a copied target and a fresh Adam each."""
    return TrainState(
        actor_params=actor,
        critic_params=critic,
        target_actor_params=jax.tree.map(jnp.copy, actor),
        target_critic_params=jax.tree.map(jnp.copy, critic),
        actor_opt=_opt_init(actor),
        critic_opt=_opt_init(critic),
        step=jnp.zeros((), jnp.int32),
    )


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _recurrent_state(k_actor, k_critic, obs_dim, act_dim, widths, actor_hidden, critic_hidden):
    """A recurrent configuration's seeded state (models/recurrent.py), as
    ONE program: a recurrent net has seventeen leaves, and drawn one eager
    operation at a time (two splits and two uniforms a layer) a cold compile
    cache pays for some two hundred tiny programs, 96 s of `setup.build_s`
    on the chip (PERF.md §6, PR 53). The bits are the eager draws' (threefry
    and an affine map)."""
    return _with_targets(
        recnet.actor_init(k_actor, obs_dim, act_dim, widths, actor_hidden),
        recnet.critic_init(k_critic, obs_dim, act_dim, widths, critic_hidden),
    )


def init_train_state(config: DDPGConfig, obs_dim, act_dim: int, seed: int) -> TrainState:
    """Build initial params + hard-copied targets (SURVEY.md §3.4) + Adam
    state. CrossQ (config.crossq): batch-normalised nets and no targets,
    the two slots None (empty pytree nodes, as log_alpha is outside sac).
    SimBa (config.simba): residual nets (models/mlp.simba_init). `obs_dim`
    is the observation's float count or its types.ObsSpec; a pixel
    configuration (config.pixels) needs the spec and takes init_pixel_state."""
    if config.pixels:
        return init_pixel_state(config, ObsSpec.of(obs_dim), act_dim, seed)
    obs_dim = ObsSpec.of(obs_dim).words
    key = jax.random.PRNGKey(seed)
    k_actor, k_critic = jax.random.split(key)
    num_outputs = config.num_atoms if config.distributional else 1
    # SAC's stochastic head emits [mean | log_std] — double-width output
    # (actor_head_dim is the single source of the width rule; the actor
    # pool sizes its shared-memory layout with the same helper).
    from distributed_ddpg_tpu.actors.policy import actor_head_dim

    if config.mpo:
        return init_mpo_state(config, obs_dim, act_dim, k_actor, k_critic)
    if config.recurrent:
        return _recurrent_state(
            k_actor, k_critic, obs_dim, act_dim, recnet.widths_of(config),
            tuple(config.actor_hidden), tuple(config.critic_hidden),
        )
    if config.simba:
        actor_params = simba_init(
            k_actor, obs_dim, obs_dim, actor_head_dim(act_dim, True),
            tuple(config.actor_hidden),
        )
    else:
        actor_params = actor_init(
            k_actor,
            obs_dim,
            actor_head_dim(act_dim, config.sac),
            tuple(config.actor_hidden),
            norm=config.crossq,
        )
    if config.twin_critic or config.sac:
        # TD3 / SAC ensemble: independently-initialized critics stacked on a
        # leading axis (two, or config.critic_ensemble under sac) — the
        # TrainState SHAPE is unchanged (same tree, each critic leaf just
        # gains a [N, ...] dim), so checkpointing, Adam, Polyak, and the
        # mesh pspec trees all compose without new cases.
        critic_params = jax.tree.map(
            lambda *members: jnp.stack(members),
            *(
                simba_init(
                    k, obs_dim, obs_dim + act_dim, 1, tuple(config.critic_hidden)
                )
                if config.simba
                else critic_init(
                    k, obs_dim, act_dim, tuple(config.critic_hidden),
                    config.action_insert_layer, num_outputs,
                    norm=config.crossq,
                )
                for k in jax.random.split(k_critic, config.critic_ensemble)
            ),
        )
    else:
        critic_params = critic_init(
            k_critic,
            obs_dim,
            act_dim,
            tuple(config.critic_hidden),
            config.action_insert_layer,
            num_outputs,
        )
    return TrainState(
        actor_params=actor_params,
        critic_params=critic_params,
        target_actor_params=(
            None if config.crossq else jax.tree.map(jnp.copy, actor_params)
        ),
        target_critic_params=(
            None if config.crossq else jax.tree.map(jnp.copy, critic_params)
        ),
        actor_opt=_opt_init(actor_params),
        critic_opt=_opt_init(critic_params),
        step=jnp.zeros((), jnp.int32),
        # SAC entropy temperature: learned log(alpha) scalar + its own Adam
        # state (None = empty pytree nodes for every other family).
        log_alpha=(
            jnp.asarray(jnp.log(config.sac_alpha), jnp.float32)
            if config.sac
            else None
        ),
        alpha_opt=(
            OptState(
                mu=jnp.zeros((), jnp.float32),
                nu=jnp.zeros((), jnp.float32),
                count=jnp.zeros((), jnp.int32),
            )
            if (config.sac and config.sac_autotune)
            else None
        ),
    )


def make_learner_step(
    config: DDPGConfig,
    action_scale,
    axis_name: Optional[str] = None,
    action_offset=0.0,
    obs: Optional[ObsSpec] = None,
    mesh=None,
):
    """Returns the pure (state, batch, noise=None) -> StepOutput function.
    Not jitted here: callers wrap it in jit-with-shardings, shard_map, or call
    it under interpretation for tests (parallel/learner.py owns device
    placement). `noise` is the step's own slice of chunk_noise (SAC: the pair
    (eps_next, eps_cur); TD3: the scaled and clipped smoothing noise), which
    every chunk program draws once before its scan; a single step passes
    none and draws the same bits itself. A pixel configuration's step needs
    `obs`, the images' types.ObsSpec (its batches hold words, which carry no
    shape), and under a jit over a `mesh` of several devices that mesh."""
    if config.pixels and obs is None:
        raise ValueError(
            "a pixel configuration's step takes its images as ring words, "
            "which carry no shape: pass obs=ObsSpec((C, H, W), 'uint8')"
        )
    ail = config.action_insert_layer
    scale = jnp.asarray(action_scale, jnp.float32)
    offset = jnp.asarray(action_offset, jnp.float32)
    # Mixed precision: bf16 matmuls (MXU native rate) with f32 accumulation
    # and f32 master params/opt state. Default f32 keeps the native-backend
    # bit-comparability oracle exact (BASELINE.json:5).
    mm = jnp.bfloat16 if config.compute_dtype == "bfloat16" else None
    keys = metric_keys(config)
    support = (
        losses.categorical_support(config.v_min, config.v_max, config.num_atoms)
        if config.distributional
        else None
    )
    # Only a step handed no noise draws for itself, and holds the base key
    # as a constant of its program: the single-step programs (agent.py,
    # ShardedLearner.step). Every chunk program pre-draws from
    # the base it takes as an argument (chunk_noise) and passes `noise`.
    base_key = noise_base_key(config)

    def own_noise(state: TrainState, batch: Batch):
        """What a step handed no noise draws for itself; under shard_map
        (explicit mode) each shard for its OWN batch slice."""
        return step_noise(
            config, base_key, state.step, batch.action.shape[0],
            batch.action.shape[-1],
            None if axis_name is None else jax.lax.axis_index(axis_name),
        )

    def moved_target(online, target, step):
        """The target net after the update of count `step`: Polyak's
        average, or under config.target_update_period the whole copy."""
        return target_update(
            online, target, config.tau, step, config.target_update_period
        )

    def sac_step(state: TrainState, batch: Batch, noise=None) -> StepOutput:
        """SAC: entropy-regularized twin-critic TD + reparameterized actor
        + (optionally) the learned temperature. Kept as its own body — the
        actor loss carries an aux (mean log-prob -> alpha update) that the
        shared branch structure below has no slot for. REDQ (config.redq)
        is this step with N critics, a drawn in-target subset and the
        policy's half under a cond; CrossQ (config.crossq) this step with
        the joint batch-normalised critic pass, the same cond, and no
        target: no polyak_update is traced; SimBa (config.simba) this step
        on residual nets, with AdamW's decay and the input normaliser's
        statistics moved by the batch's `obs` rows: every net of this
        update reads them as they stood when it began."""
        eps_next, eps_cur, *subset = (
            own_noise(state, batch) if noise is None else noise
        )
        subset = subset[0] if subset else None
        alpha = jnp.exp(state.log_alpha)
        crossq, b1 = config.crossq, config.adam_b1
        simba, decay = config.simba, config.weight_decay

        def critic_loss_fn(cp):
            if crossq:
                return losses.crossq_critic_loss(
                    cp, state.actor_params, batch, scale, eps_next, alpha,
                    config.sac_log_std_min, config.sac_log_std_max,
                    ail, config.critic_l2, offset, mm, axis_name,
                )
            return losses.sac_critic_loss(
                cp, state.actor_params, state.target_critic_params, batch,
                scale, eps_next, alpha,
                config.sac_log_std_min, config.sac_log_std_max,
                ail, config.critic_l2, offset, mm,
                subset=subset, ensemble_stats=config.redq, resid_share=simba,
            )

        with device_scope("critic"):
            (closs, td), cgrads = jax.value_and_grad(
                critic_loss_fn, has_aux=True
            )(state.critic_params)
            cgrads = _maybe_psum_mean(cgrads, axis_name)
        if crossq:
            td, joint_mean_q, stat_gap, critic_moments = td
        if simba:
            td, resid_share = td
            with device_scope("critic"):
                # one merge for the state: every net is handed its result
                rs_stats, rs_drift = rs_merged(
                    state.actor_params[0], batch.obs, axis_name
                )

        def critic_adam():
            new, opt = adam_update(
                state.critic_params, cgrads, state.critic_opt,
                config.critic_lr, b1, decay,
            )
            if simba:
                with device_scope("critic"):
                    new = rs_written(new, rs_stats)
            if crossq:
                # Adam left the running statistics where they were (their
                # gradient is zero): the joint pass's moments move them.
                with device_scope("critic"):
                    new = norm_moved(new, critic_moments)
            return new, opt

        # Actor gradient against the pre-update critic (file convention):
        # its ensemble's mean where the target draws a subset (REDQ,
        # Algorithm 1), the minimum otherwise.
        def actor_loss_fn(ap):
            return losses.sac_actor_loss(
                ap, state.critic_params, batch, scale, eps_cur, alpha,
                config.sac_log_std_min, config.sac_log_std_max,
                ail, offset, mm,
                reduce=jnp.min if subset is None else jnp.mean,
                train_norm=crossq, axis_name=axis_name,
            )

        def actor_grads():
            """(loss, mean log-prob, gradient, the actor's batch-norm
            moments: None outside CrossQ)."""
            with device_scope("actor"):
                (aloss, mean_lp), agrads = jax.value_and_grad(
                    actor_loss_fn, has_aux=True
                )(state.actor_params)
                mean_lp, moments = mean_lp if crossq else (mean_lp, None)
                agrads = _maybe_psum_mean(agrads, axis_name)
                # Global mean log-prob so every shard's alpha update sees the
                # same scalar (replicas must not fork on log_alpha).
                return aloss, _maybe_psum_mean(mean_lp, axis_name), agrads, moments

        def actor_adam(agrads, moments):
            new, opt = adam_update(
                state.actor_params, agrads, state.actor_opt, config.actor_lr,
                b1, decay,
            )
            if crossq:
                with device_scope("actor"):
                    new = norm_moved(new, moments)
            if simba:
                with device_scope("actor"):
                    new = rs_written(new, rs_stats)
            return new, opt

        def temperature_adam(mean_lp):
            if not config.sac_autotune:
                return state.log_alpha, state.alpha_opt
            # J(log_alpha) = -log_alpha * (E[log pi] + target_H);
            # d/dlog_alpha = -(E[log pi] + target_H), exact — no autodiff
            # needed for a scalar with a linear objective. The target
            # resolution (explicit value vs the env-unit-shifted -act_dim
            # heuristic) lives in losses.sac_target_entropy, shared with
            # the fused kernel wrapper. act_dim is static under jit from
            # the batch's action shape.
            tgt_h = losses.sac_target_entropy(
                config.target_entropy, batch.action.shape[-1], action_scale,
                config.target_entropy_scale,
            )
            alpha_grad = -(jax.lax.stop_gradient(mean_lp) + tgt_h)
            return adam_update(
                state.log_alpha, alpha_grad, state.alpha_opt, config.critic_lr, b1
            )

        if config.policy_delay > 1:
            # REDQ's one policy step in G: the critics and their targets step
            # on every update, the actor and the temperature on the updates
            # whose (pre-increment, replicated) step count is 0, G, 2G, ...,
            # TD3's rule (delayed_updates), so every replica takes the same
            # branch. The actor's backward through all N critics and its
            # pmean live inside the taken branch. A skipped update runs no
            # pass for the record either (N critic forwards are a third of
            # an update at N = 10): actor_loss and actor_grad_norm read 0 on
            # it, as TD3's actor_grad_norm does.
            new_critic, critic_opt = critic_adam()

            def policy_update():
                aloss, mean_lp, agrads, moments = actor_grads()
                return (
                    *actor_adam(agrads, moments), *temperature_adam(mean_lp),
                    aloss, optree_norm(agrads),
                )

            zero = jnp.zeros((), jnp.float32)
            (
                new_actor, actor_opt, new_log_alpha, alpha_opt,
                aloss, actor_grad_norm,
            ) = jax.lax.cond(
                state.step % config.policy_delay == 0,
                policy_update,
                lambda: (
                    state.actor_params, state.actor_opt, state.log_alpha,
                    state.alpha_opt, zero, zero,
                ),
            )
        else:
            # Plain SAC's sequence, op for op as it was before the delay
            # existed (actor gradient, both Adams, both Polyaks, then the
            # temperature, the actor's gradient norm last, in the metrics):
            # its lowered text is held equal to the parent's.
            aloss, mean_lp, agrads, moments = actor_grads()
            new_critic, critic_opt = critic_adam()
            new_actor, actor_opt = actor_adam(agrads, moments)
        if crossq:
            # No target exists: the slots stay None and no Polyak pass runs.
            new_target_critic = new_target_actor = None
        else:
            new_target_critic = moved_target(
                new_critic, state.target_critic_params, state.step
            )
            # SAC's math has no target actor; the slot still trails the actor
            # via the same polyak so the TrainState invariants (targets trail
            # params) and checkpoint shape stay uniform across families.
            new_target_actor = moved_target(
                new_actor, state.target_actor_params, state.step
            )
        if config.policy_delay == 1:
            new_log_alpha, alpha_opt = temperature_adam(mean_lp)

        if config.redq:
            # mean_q from the critic loss's own q, there on every update: the
            # ensemble's mean Q(s, a) on the replay rows.
            td, q_spread, mean_q = td
            branch_metrics = (q_spread,)
        elif crossq:
            # likewise, and the joint pass's statistics gap
            mean_q, branch_metrics = joint_mean_q, (stat_gap,)
        else:
            # mean_q recovered exactly: aloss = E[alpha*lp - minQ]
            # => E[minQ] = alpha * mean_lp - aloss.
            mean_q = alpha * mean_lp - aloss
            branch_metrics = (
                (resid_share, rs_stats[2], rs_drift) if simba else ()
            )
        metrics = dict(
            zip(
                keys,
                (
                    closs,
                    aloss,
                    mean_q,
                    jnp.mean(jnp.abs(td)),
                    optree_norm(cgrads),
                    (
                        actor_grad_norm if config.policy_delay > 1
                        else optree_norm(agrads)
                    ),
                    *branch_metrics,
                ),
            )
        )
        metrics = _maybe_psum_mean(metrics, axis_name)
        new_state = TrainState(
            actor_params=new_actor,
            critic_params=new_critic,
            target_actor_params=new_target_actor,
            target_critic_params=new_target_critic,
            actor_opt=actor_opt,
            critic_opt=critic_opt,
            step=state.step + 1,
            log_alpha=new_log_alpha,
            alpha_opt=alpha_opt,
        )
        return StepOutput(state=new_state, td_errors=td, metrics=metrics)

    if config.sac:
        return sac_step

    def mpo_step(state: TrainState, batch: Batch, noise=None) -> StepOutput:
        """DMPO (config.mpo; ops/losses.py's MPO section): no gradient
        passes through the critic to the policy. E-step, no gradient:
        mpo_samples actions a row from the TARGET policy at s', the TARGET
        critic on all B x N (s', clipped action) rows, each distribution's
        expectation, the samples' mixture, and the softmax weights at the
        learned temperature (with the out-of-box penalty's beside them).
        Critic: cross-entropy against the projected mixture at (s, a), the
        ring's action mapped onto the canonical box. M-step: one forward of
        the online policy at s' (jax.vjp), the decoupled weighted
        likelihood with both KLs under their multipliers on its (mean,
        scale), pulled back to the parameters. Duals: the four variables
        (state.log_alpha's tree, floored at MPO_MIN_LOG_DUAL) on their own
        loss under their own Adam (state.alpha_opt, config.dual_lr). Then
        both nets' Adam and the targets (moved_target)."""
        eps = own_noise(state, batch) if noise is None else noise
        duals = jax.tree.map(
            lambda x: jnp.maximum(x, losses.MPO_MIN_LOG_DUAL), state.log_alpha
        )
        next_obs = batch.next_obs
        with device_scope("estep"):
            actions, (mean_t, scale_t), probs, q = losses.mpo_estep(
                state.target_actor_params, state.target_critic_params,
                next_obs, eps, support, mm,
            )
            mixture = jnp.mean(probs, axis=1)
            cost = losses.mpo_out_of_box_cost(actions)
            dual_values = losses.mpo_dual_values(duals)
            value_weights = losses.mpo_weights(q, dual_values["log_temperature"])
            weights = value_weights + losses.mpo_weights(
                cost, dual_values["log_penalty_temperature"]
            )

        with device_scope("critic"):
            (closs, (td, edge_mass)), cgrads = jax.value_and_grad(
                lambda cp: losses.mpo_critic_loss(
                    cp, batch, (batch.action - offset) / scale, mixture,
                    support, mm,
                ),
                has_aux=True,
            )(state.critic_params)
            cgrads = _maybe_psum_mean(cgrads, axis_name)

        with device_scope("actor"):
            (mean, std), to_params = jax.vjp(
                lambda ap: gaussian_apply(ap, next_obs, mm), state.actor_params
            )
            (aloss, kls), head_grads = jax.value_and_grad(
                lambda m, sd: losses.mpo_policy_loss(
                    m, sd, mean_t, scale_t, actions, weights,
                    dual_values["log_alpha_mean"], dual_values["log_alpha_stddev"],
                ),
                argnums=(0, 1), has_aux=True,
            )(mean, std)
            (agrads,) = to_params(head_grads)
            agrads = _maybe_psum_mean(agrads, axis_name)
            # every shard's multipliers must see the global batch's KLs
            kl_mean, kl_std = _maybe_psum_mean(kls, axis_name)

        with device_scope("duals"):
            dgrads = jax.grad(losses.mpo_dual_loss)(
                duals, q, cost, kl_mean, kl_std,
                config.mpo_epsilon, config.mpo_epsilon_penalty,
                config.mpo_epsilon_mean, config.mpo_epsilon_stddev,
            )
            dgrads = _maybe_psum_mean(dgrads, axis_name)
            new_duals, dual_opt = adam_update(
                duals, dgrads, state.alpha_opt, config.dual_lr, config.adam_b1
            )
        new_critic, critic_opt = adam_update(
            state.critic_params, cgrads, state.critic_opt, config.critic_lr,
            config.adam_b1, config.weight_decay,
        )
        new_actor, actor_opt = adam_update(
            state.actor_params, agrads, state.actor_opt, config.actor_lr,
            config.adam_b1, config.weight_decay,
        )
        metrics = dict(zip(keys, (
            closs, aloss, jnp.mean(q), jnp.mean(jnp.abs(td)),
            optree_norm(cgrads), optree_norm(agrads), edge_mass,
            jnp.mean(1.0 / jnp.sum(jnp.square(value_weights), axis=1)),
            jnp.mean(kl_mean) / config.mpo_epsilon_mean,
            dual_values["log_temperature"][0],
        )))
        metrics = _maybe_psum_mean(metrics, axis_name)
        new_state = TrainState(
            actor_params=new_actor,
            critic_params=new_critic,
            target_actor_params=moved_target(
                new_actor, state.target_actor_params, state.step
            ),
            target_critic_params=moved_target(
                new_critic, state.target_critic_params, state.step
            ),
            actor_opt=actor_opt,
            critic_opt=critic_opt,
            step=state.step + 1,
            log_alpha=new_duals,
            alpha_opt=dual_opt,
        )
        return StepOutput(state=new_state, td_errors=td, metrics=metrics)

    if config.mpo:
        return mpo_step

    def shift(words, offsets):
        return pix.random_shift(words, offsets, config.aug_pad, obs)

    if config.pixels and mesh is not None and mesh.size > 1:
        # The crop is a Pallas kernel, which no partitioner splits: under a
        # jit over a mesh each data shard runs it on its own rows.
        shift = jax.shard_map(
            shift, mesh=mesh, in_specs=(P(None, "data"), P("data")),
            out_specs=P("data"), check_vma=False,
        )

    def trunk_rows(state: TrainState, move) -> TrainState:
        """`state` with the rows of every stored copy of a trunk's `w`
        passed through `move` (pixnet.block_rows or stored_rows): the three
        trunks' and Adam's two moments of the two that train. Adam, the
        decay and Polyak are elementwise, so an update on moved rows is the
        update on stored rows, moved."""
        def on(tree):
            return pixnet.with_trunk_rows(tree, move, config.encoder_channels)

        def on_opt(opt):
            return opt._replace(mu=on(opt.mu), nu=on(opt.nu))

        return state._replace(
            actor_params=on(state.actor_params),
            critic_params=on(state.critic_params),
            target_critic_params=on(state.target_critic_params),
            actor_opt=on_opt(state.actor_opt),
            critic_opt=on_opt(state.critic_opt),
        )

    def pixel_update(state: TrainState, batch: Batch, noise=None) -> StepOutput:
        """DrQ-v2 (config.pixels; models/pixels.py, ops/pixels.py): the
        deterministic twin-critic update on augmented byte images, on a
        state whose trunks' rows are in the encoder's block's order
        (trunk_rows with pixnet.block_rows; pixel_step below takes a stored
        state). `batch.obs`
        and `batch.next_obs` are the ring's WORDS, batch-minor as
        ops/pixels.cut_pixels lays a launch, f32[obs.words, B], four pixels
        each and nothing a float operation may read: the bytes come
        out here, on this update's own rows, inside the random shift
        (ops/pixels.random_shift: a reinterpretation at the word's own width
        and integer shifts; a bitcast to bytes the TPU's compiler expands 32
        bits a pixel, ops/pixels.py), which hands the encoder its float32
        input f32[B, C, H, W]. One pass of the ONLINE
        encoder on each (the second without gradient), clipped double Q on
        the TARGET trunk and heads with the online actor's noisy action, the
        critic's loss (the SUM of the two heads' weighted squared errors, as
        the source writes it) moving encoder, trunk and heads under one Adam,
        the actor's loss on the DETACHED features through the critic as it
        stood before this update (file convention), Polyak on trunk and
        heads. The features are the last convolution's block f32[B, C, S, S]
        wherever they go: `feat`, `feat_next`, the detached `feat` and the
        block's gradient `gfeat`; the five trunk products an update contract
        it in place (pixnet.trunk_apply). The encoder's passes read under
        `update/encoder`, outside the critic's bracket, so their device time
        can be told apart."""
        offsets, noise_next, noise_cur = (
            own_noise(state, batch) if noise is None else noise
        )
        critic, lo, hi = state.critic_params, offset - scale, offset + scale
        with device_scope("augment"):
            obs_in = shift(batch.obs, offsets[:, :2])
            next_obs_in = shift(batch.next_obs, offsets[:, 2:])
        with device_scope("encoder"):
            feat, encoder_vjp = jax.vjp(
                lambda enc: pixnet.encoder_apply(enc, obs_in), critic["encoder"]
            )
            feat_next = pixnet.encoder_apply(critic["encoder"], next_obs_in)

        def critic_loss_fn(heads, feat):
            next_action = pix.clipped_action(
                pixnet.actor_apply(state.actor_params, feat_next, scale, offset),
                noise_next, lo, hi,
            )
            next_q = pixnet.critic_apply(
                state.target_critic_params, feat_next, next_action
            )
            y = jax.lax.stop_gradient(
                losses.td_targets(batch, jnp.min(next_q, axis=0))
            )
            td = y[None, :] - pixnet.critic_apply(heads, feat, batch.action)
            loss = jnp.sum(jnp.mean(batch.weight[None, :] * jnp.square(td), axis=1))
            twin_gap = jnp.mean(jnp.abs(next_q[0] - next_q[1]))
            return loss, (jnp.mean(td, axis=0), twin_gap)

        with device_scope("critic"):
            (closs, (td, twin_gap)), (hgrads, gfeat) = jax.value_and_grad(
                critic_loss_fn, argnums=(0, 1), has_aux=True
            )(pixnet.trained_with_target(critic), feat)
        with device_scope("encoder"):
            (egrads,) = encoder_vjp(gfeat)
        cgrads = _maybe_psum_mean({"encoder": egrads, **hgrads}, axis_name)

        def actor_loss_fn(ap):
            detached = jax.lax.stop_gradient(feat)
            action = pix.clipped_action(
                pixnet.actor_apply(ap, detached, scale, offset), noise_cur, lo, hi
            )
            return -jnp.mean(
                jnp.min(pixnet.critic_apply(critic, detached, action), axis=0)
            )

        with device_scope("actor"):
            aloss, agrads = jax.value_and_grad(actor_loss_fn)(state.actor_params)
            agrads = _maybe_psum_mean(agrads, axis_name)
        new_critic, critic_opt = adam_update(
            critic, cgrads, state.critic_opt, config.critic_lr,
            config.adam_b1, config.weight_decay,
        )
        new_actor, actor_opt = adam_update(
            state.actor_params, agrads, state.actor_opt, config.actor_lr,
            config.adam_b1, config.weight_decay,
        )
        new_target = polyak_update(
            pixnet.trained_with_target(new_critic), state.target_critic_params,
            config.tau,
        )
        metrics = dict(zip(keys, (
            closs, aloss, -aloss, jnp.mean(jnp.abs(td)),
            optree_norm(cgrads), optree_norm(agrads), twin_gap,
            optree_norm(cgrads["encoder"]),
            pix.sigma_at(config.sigma_schedule, state.step),
            jnp.mean(offsets.astype(jnp.float32)),
        )))
        metrics = _maybe_psum_mean(metrics, axis_name)
        new_state = TrainState(
            actor_params=new_actor,
            critic_params=new_critic,
            target_actor_params=None,
            target_critic_params=new_target,
            actor_opt=actor_opt,
            critic_opt=critic_opt,
            step=state.step + 1,
        )
        return StepOutput(state=new_state, td_errors=td, metrics=metrics)

    def pixel_step(state: TrainState, batch: Batch, noise=None) -> StepOutput:
        """pixel_update on a stored state: the trunks' rows moved to the
        block's order in front of it and back behind it, three weights and
        four moments each way. A launch of K updates moves them once for all
        K (`launch`, which parallel/learner.scan_chunk reads): a trunk's `w`
        is as many bytes as a feature block, so an update that moved weights
        would pay what moving the blocks did (every form that moved them an
        update ran slower on the chip: PERF.md §6, PR 50)."""
        out = pixel_update(enter(state), batch, noise)
        return out._replace(state=leave(out.state))

    if config.pixels:
        enter = functools.partial(trunk_rows, move=pixnet.block_rows)
        leave = functools.partial(trunk_rows, move=pixnet.stored_rows)
        pixel_step.launch = (enter, pixel_update, leave)
        return pixel_step

    def recurrent_update(state: TrainState, batch: Windows, noise=None) -> StepOutput:
        """Recurrent TD3 (config.recurrent; models/recurrent.py) on a batch
        of WINDOWS (types.Windows): obs f32[B, L + 1, o], action, reward,
        terminated (the steps' flags d) and mask f32[B, L].
        Every net's memory is scanned over the window's L + 1 observations
        from a zero state, with a_{-1} = r_{-1} = 0: the two targets' without
        gradient (`target/recur`), the critic's forward and back through
        time under its loss (`critic/recur`), the actor's under its own
        (`actor/recur`). Targets y_t = r_t + gamma (1 - d_t) min_k
        Q'_k(t + 1, clip(pi'(t + 1) + noise_t)); the critic's loss the SUM of
        the two heads' masked squared errors over the real steps, the
        actor's -min_k Q_k(t, pi(t)) over them, through the critic's shortcut
        alone (the critic's memory h^Q_t is a function of the ring's
        actions, not of pi; it is the one the critic's loss computed, on the
        critic as it stood before this update: file convention). Adam on
        both, Polyak on every target leaf, the memories' among them.
        td_errors are per step, f32[B, L]: the mean over the heads of
        y - Q_k, 0 on a padded step."""
        if noise is None:
            noise = own_noise(state, batch)
        obs, mask = batch.obs, batch.mask
        lo, hi = offset - scale, offset + scale
        zero_a, zero_r = jnp.zeros_like(batch.action[:, :1]), jnp.zeros_like(batch.reward[:, :1])
        prev_action = jnp.concatenate([zero_a, batch.action], axis=1)
        prev_reward = jnp.concatenate([zero_r, batch.reward], axis=1)
        steps = jnp.maximum(jnp.sum(mask), 1.0)

        with device_scope("target"):
            h_ta = recnet.memory(state.target_actor_params, obs, prev_action, prev_reward)
            h_tc = recnet.memory(state.target_critic_params, obs, prev_action, prev_reward)
            next_action = jnp.clip(
                recnet.actor_head(
                    state.target_actor_params, h_ta[:, 1:], obs[:, 1:], scale, offset
                ) + noise,
                lo, hi,
            )
            next_q = recnet.critic_heads(
                state.target_critic_params, h_tc[:, 1:], obs[:, 1:], next_action
            )
            y = batch.reward + config.gamma * (1.0 - batch.terminated) * jnp.min(next_q, axis=0)
            twin_gap = jnp.sum(jnp.abs(next_q[0] - next_q[1]) * mask) / steps

        def critic_loss_fn(cp):
            h = recnet.memory(cp, obs, prev_action, prev_reward)
            td = (y[None] - recnet.critic_heads(cp, h[:, :-1], obs[:, :-1], batch.action)) * mask[None]
            return jnp.sum(jnp.square(td)) / steps, (
                jnp.mean(td, axis=0), jax.lax.stop_gradient(h)
            )

        with device_scope("critic"):
            (closs, (td, h_critic)), cgrads = jax.value_and_grad(
                critic_loss_fn, has_aux=True
            )(state.critic_params)
            cgrads = _maybe_psum_mean(cgrads, axis_name)

        def actor_loss_fn(ap):
            h = recnet.memory(ap, obs, prev_action, prev_reward)
            action = recnet.actor_head(ap, h[:, :-1], obs[:, :-1], scale, offset)
            q = recnet.critic_heads(
                state.critic_params, h_critic[:, :-1], obs[:, :-1], action
            )
            return -jnp.sum(jnp.min(q, axis=0) * mask) / steps

        with device_scope("actor"):
            aloss, agrads = jax.value_and_grad(actor_loss_fn)(state.actor_params)
            agrads = _maybe_psum_mean(agrads, axis_name)
        new_critic, critic_opt = adam_update(
            state.critic_params, cgrads, state.critic_opt, config.critic_lr,
            config.adam_b1,
        )
        new_actor, actor_opt = adam_update(
            state.actor_params, agrads, state.actor_opt, config.actor_lr,
            config.adam_b1,
        )
        metrics = dict(zip(keys, (
            closs, aloss, -aloss, jnp.sum(jnp.abs(td)) / steps,
            optree_norm(cgrads), optree_norm(agrads), twin_gap,
            jnp.mean(mask),
        )))
        metrics = _maybe_psum_mean(metrics, axis_name)
        new_state = TrainState(
            actor_params=new_actor,
            critic_params=new_critic,
            target_actor_params=polyak_update(
                new_actor, state.target_actor_params, config.tau
            ),
            target_critic_params=polyak_update(
                new_critic, state.target_critic_params, config.tau
            ),
            actor_opt=actor_opt,
            critic_opt=critic_opt,
            step=state.step + 1,
        )
        return StepOutput(state=new_state, td_errors=td, metrics=metrics)

    if config.recurrent:
        return recurrent_update

    def step(state: TrainState, batch: Batch, noise=None) -> StepOutput:
        # --- critic update ---
        if config.twin_critic:
            if noise is None:
                noise = own_noise(state, batch)

            def critic_loss_fn(cp):
                return losses.td3_critic_loss(
                    cp,
                    state.target_actor_params,
                    state.target_critic_params,
                    batch,
                    scale,
                    noise,
                    ail,
                    config.critic_l2,
                    offset,
                    mm,
                )
        elif config.distributional:
            def critic_loss_fn(cp):
                return losses.distributional_critic_loss(
                    cp,
                    state.target_actor_params,
                    state.target_critic_params,
                    batch,
                    scale,
                    support,
                    ail,
                    offset,
                    mm,
                )
        else:
            def critic_loss_fn(cp):
                return losses.critic_loss(
                    cp,
                    state.target_actor_params,
                    state.target_critic_params,
                    batch,
                    scale,
                    ail,
                    config.critic_l2,
                    offset,
                    mm,
                )

        with device_scope("critic"):
            (closs, td), cgrads = jax.value_and_grad(
                critic_loss_fn, has_aux=True
            )(state.critic_params)
            cgrads = _maybe_psum_mean(cgrads, axis_name)
        branch_metrics = ()
        if config.distributional or config.twin_critic:
            td, *branch_metrics = td  # (td, edge_mass) / (td, twin_gap)

        # --- actor update (pre-update critic: both grads from the same state) ---
        if config.twin_critic:
            def actor_loss_fn(ap):
                return losses.td3_actor_loss(
                    ap, state.critic_params, batch, scale, ail, offset, mm
                )
        elif config.distributional:
            def actor_loss_fn(ap):
                return losses.distributional_actor_loss(
                    ap, state.critic_params, batch, scale, support, ail, offset, mm
                )
        else:
            def actor_loss_fn(ap):
                return losses.actor_loss(
                    ap, state.critic_params, batch, scale, ail, offset, mm
                )

        if config.twin_critic and config.policy_delay > 1:
            # TD3 delayed updates: the critic steps every call; the actor
            # AND both target nets step once per policy_delay critic steps
            # (lax.cond — both branches return the same pytree structure,
            # so the step stays a single traced program). The actor
            # BACKWARD (and its gradient pmean) lives inside the update
            # branch so skipped steps pay only the cheap forward for the
            # aloss metric — not (d-1)/d of wasted bwd FLOPs per chunk.
            # The cond predicate is the replicated state.step, so every
            # replica takes the same branch and the collective schedule
            # stays aligned. actor_opt.count only advances on real
            # updates, keeping Adam bias correction honest; updates land
            # on critic steps 0, d, 2d, ... (pre-increment step).
            with device_scope("actor"):
                aloss = actor_loss_fn(state.actor_params)
            new_critic, critic_opt = adam_update(
                state.critic_params, cgrads, state.critic_opt, config.critic_lr,
                config.adam_b1, config.weight_decay,
            )

            def _delayed_update(_):
                with device_scope("actor"):
                    agrads = jax.grad(actor_loss_fn)(state.actor_params)
                    agrads = _maybe_psum_mean(agrads, axis_name)
                na, aopt = adam_update(
                    state.actor_params, agrads, state.actor_opt, config.actor_lr,
                    config.adam_b1, config.weight_decay,
                )
                return (
                    na,
                    aopt,
                    polyak_update(na, state.target_actor_params, config.tau),
                    polyak_update(
                        new_critic, state.target_critic_params, config.tau
                    ),
                    optree_norm(agrads),
                )

            def _skip_update(_):
                # actor_grad_norm reads 0 on skip steps (no grad computed).
                return (
                    state.actor_params,
                    state.actor_opt,
                    state.target_actor_params,
                    state.target_critic_params,
                    jnp.zeros((), jnp.float32),
                )

            (
                new_actor, actor_opt, new_target_actor, new_target_critic,
                actor_grad_norm,
            ) = jax.lax.cond(
                state.step % config.policy_delay == 0,
                _delayed_update,
                _skip_update,
                operand=None,
            )
        else:
            with device_scope("actor"):
                aloss, agrads = jax.value_and_grad(actor_loss_fn)(
                    state.actor_params
                )
                agrads = _maybe_psum_mean(agrads, axis_name)
            actor_grad_norm = optree_norm(agrads)
            new_critic, critic_opt = adam_update(
                state.critic_params, cgrads, state.critic_opt, config.critic_lr,
                config.adam_b1, config.weight_decay,
            )
            new_actor, actor_opt = adam_update(
                state.actor_params, agrads, state.actor_opt, config.actor_lr,
                config.adam_b1, config.weight_decay,
            )

            # --- Polyak target updates, fused in (SURVEY.md §3.4) ---
            new_target_actor = moved_target(new_actor, state.target_actor_params, state.step)
            new_target_critic = moved_target(new_critic, state.target_critic_params, state.step)

        metrics = dict(
            zip(
                keys,
                (
                    closs,
                    aloss,
                    -aloss,
                    jnp.mean(jnp.abs(td)),
                    optree_norm(cgrads),
                    actor_grad_norm,
                    *branch_metrics,
                ),
            )
        )
        # Under shard_map each shard sees only its batch slice; average the
        # scalar diagnostics so every shard reports the global value.
        metrics = _maybe_psum_mean(metrics, axis_name)
        new_state = TrainState(
            actor_params=new_actor,
            critic_params=new_critic,
            target_actor_params=new_target_actor,
            target_critic_params=new_target_critic,
            actor_opt=actor_opt,
            critic_opt=critic_opt,
            step=state.step + 1,
        )
        return StepOutput(state=new_state, td_errors=td, metrics=metrics)

    return step


def optree_norm(tree) -> jnp.ndarray:
    """The global L2 norm of a gradient tree, with ONE scalar leaving the
    vector unit: each leaf's squares are summed down to its last axis (a row
    of lanes), the rows added across the leaves (a narrower one padded with
    zeros), and one sum of that row is rooted. A sum a leaf, added up as
    scalars, is the same number to rounding and cost the scan leg 5.8 us an
    update on the chip: a scalar that a fusion hands to the scalar core, or
    the core to a fusion, costs 0.35-0.7 us each way, the arithmetic on it
    nothing (PERF.md §6, PR 46); a SAC update had twelve such sums."""
    rows = [
        jnp.sum(jnp.square(x), axis=tuple(range(x.ndim - 1)))
        for x in map(jnp.atleast_1d, jax.tree.leaves(tree))
    ]
    width = max(row.shape[0] for row in rows)
    lanes = functools.reduce(
        jnp.add, (jnp.pad(row, (0, width - row.shape[0])) for row in rows)
    )
    return jnp.sqrt(jnp.sum(lanes))


def jit_learner_step(config: DDPGConfig, action_scale, donate: bool = True, action_offset=0.0):
    """Single-device jitted step with donated TrainState (no HBM copy of the
    params between steps)."""
    step = make_learner_step(config, action_scale, action_offset=action_offset)
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_act_fn(config: DDPGConfig, action_scale, action_offset=0.0):
    """Jitted deterministic policy for evaluation/acting on device.
    SAC evaluates on the distribution mode: tanh(mean) onto the box."""
    from distributed_ddpg_tpu.models.mlp import actor_apply, actor_gaussian_apply

    scale = jnp.asarray(action_scale, jnp.float32)
    offset = jnp.asarray(action_offset, jnp.float32)

    if config.pixels:
        # `actor_params` is models/pixels.policy_params (encoder + actor),
        # `obs` byte frames uint8[B, C, H, W]: the learner's own apply.
        return jax.jit(
            lambda policy, obs: pixnet.policy_apply(policy, obs, scale, offset)
        )

    if config.recurrent:
        # One step of the policy's memory: (params, obs f32[B, o], Memory)
        # -> (action, (h, c)); the caller carries the Memory on
        # (models/recurrent.actor_step: the rollout's own step).
        return jax.jit(
            lambda actor_params, obs, memory: recnet.actor_step(
                actor_params, obs, memory, scale, offset
            )
        )

    if config.mpo:
        # the Gaussian's mean, clipped to the canonical box and mapped on
        return jax.jit(
            lambda actor_params, obs: jnp.clip(
                gaussian_apply(actor_params, obs)[0], -1.0, 1.0
            ) * scale + offset
        )

    if config.sac:

        @jax.jit
        def act(actor_params, obs):
            mean, _ = actor_gaussian_apply(
                actor_params, obs, config.sac_log_std_min, config.sac_log_std_max
            )
            return jnp.tanh(mean) * scale + offset

        return act

    @jax.jit
    def act(actor_params, obs):
        return actor_apply(actor_params, obs, scale, offset)

    return act


def make_sample_fn(config: DDPGConfig, action_scale, action_offset=0.0):
    """Jitted stochastic policy (exploration): a ~ pi(.|s), SAC's squashed
    Gaussian or MPO's plain one clipped to the box."""
    from distributed_ddpg_tpu.models.mlp import actor_gaussian_apply
    from distributed_ddpg_tpu.ops import losses as losses_lib

    scale = jnp.asarray(action_scale, jnp.float32)
    offset = jnp.asarray(action_offset, jnp.float32)

    if config.mpo:

        @jax.jit
        def sample_clipped(actor_params, obs, key):
            mean, std = gaussian_apply(actor_params, obs)
            draw = mean + std * jax.random.normal(key, mean.shape)
            return jnp.clip(draw, -1.0, 1.0) * scale + offset

        return sample_clipped

    @jax.jit
    def sample(actor_params, obs, key):
        mean, log_std = actor_gaussian_apply(
            actor_params, obs, config.sac_log_std_min, config.sac_log_std_max
        )
        action, _ = losses_lib.sac_sample(
            mean, log_std, jax.random.normal(key, mean.shape), scale, offset
        )
        return action

    return sample
