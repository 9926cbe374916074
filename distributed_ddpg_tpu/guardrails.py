"""Numerical-health guardrails: on-device divergence detection and
bad-batch quarantine for the learner step (docs/RESILIENCE.md 'numerical
health'; the math-side counterpart of the process-resilience layers from
PRs 4-6).

A NaN gradient, an exploding critic, or a poisoned replay row silently
corrupts the params — and then every checkpoint written afterwards — long
before any host-visible symptom. D4PG-scale runs (PAPERS.md,
arXiv 1804.08617) and always-on Podracer fleets (arXiv 2104.06272) assume
weeks unattended, and this repo has already logged one real divergence
incident (the seed-1 C51 support runaway, ops/support_auto.py docstring).
So the learner itself carries a cheap jitted health probe:

  - **finite checks** on the step's TD errors, grad norms/losses, and the
    UPDATED float params — a non-finite anywhere marks the step bad;
  - **EWMA z-score anomaly detection** on critic loss and critic grad
    norm — a finite-but-absurd step (loss spike, grad explosion) marks
    the step bad once the EWMA has warmed up;
  - **bad-batch quarantine**: a bad step's update is DROPPED on device
    (params/opt state/targets keep their pre-step values; only the step
    counter advances, so the deterministic noise streams never re-draw),
    its TD errors are zeroed (a NaN TD must not poison PER priorities),
    and its metrics are zeroed out of the chunk mean;
  - **bad-row capture**: rows of the sampled minibatch that are
    themselves non-finite are counted and their replay indices recorded
    (first GUARD_BAD_IDX per chunk) so the host can attribute them to an
    ingest source and quarantine repeat offenders through the actor-pool
    machinery (train.py).

Everything lives in a small replicated `GuardState` pytree threaded
through the chunk scan (parallel/learner.py); the host reads ONE tiny
health vector per chunk (HEALTH_KEYS — a handful of int32 counters, one
d2h) and never pulls params or grads. All decisions are computed from
replicated inputs, so every data-parallel replica takes the identical
skip/keep branch and a mesh can never fork on a guardrail.

Deterministic chaos (faults.py `numeric:*` grammar): `numeric:grad:nan@K`
and `numeric:loss:spike@K` poison the K-th guarded step's minibatch
inside the program, keyed on `GuardState.total` — a MONOTONIC step clock
that rollback deliberately does not rewind (a step-keyed fault that
re-fired after every rollback would trap the run in its own repair).

With `config.guardrails=False` none of this exists: the chunk programs
are built exactly as before this module existed (the parity test pins
bit-identical outputs), and the wrapper is never constructed.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

# EWMA decay for the running loss/grad-norm statistics. ~1/ALPHA steps of
# memory: long enough to smooth minibatch noise, short enough to track the
# (nonstationary) loss scale of early training.
EWMA_ALPHA = 0.05

# Bad replay indices captured per chunk (fixed-size jit output; -1 pads).
GUARD_BAD_IDX = 32

# Reward scale applied by the numeric:loss:spike injection — finite but
# far outside any EWMA band, so it must trip the z-score detector and
# ONLY that detector (everything stays representable in f32).
SPIKE_SCALE = 1.0e6


class GuardState(NamedTuple):
    """Replicated device-resident probe state, threaded through the scan.

    `total` is the monotonic guarded-step clock (never rewound — numeric
    fault ordinals and the host's cumulative-counter deltas key on it).
    The four EWMA fields reset on rollback (the restored params have the
    OLD loss scale; statistics accumulated on the diverged trajectory
    would mis-score the first post-rollback steps); the counters are
    CUMULATIVE across rollbacks so the host's delta accounting never sees
    a counter move backwards."""

    loss_mean: jnp.ndarray   # f32: EWMA of critic_loss
    loss_var: jnp.ndarray    # f32: EW variance of critic_loss
    gnorm_mean: jnp.ndarray  # f32: EWMA of critic_grad_norm
    gnorm_var: jnp.ndarray   # f32: EW variance of critic_grad_norm
    warm: jnp.ndarray        # i32: clean observations absorbed by the EWMA
    total: jnp.ndarray       # i32: guarded steps processed (monotonic)
    nonfinite: jnp.ndarray   # i32: steps skipped for a non-finite value
    spikes: jnp.ndarray      # i32: steps skipped for a z-score anomaly
    skipped: jnp.ndarray     # i32: total updates dropped (>= the two above)
    bad_rows: jnp.ndarray    # i32: non-finite sampled replay rows seen


# Order of the per-chunk health vector (int32[len(HEALTH_KEYS)]) — the one
# word the host reads each chunk. Counters are cumulative; train.py
# differences consecutive reads.
HEALTH_KEYS = ("total", "nonfinite", "spikes", "skipped", "bad_rows")


def init_guard_state(
    total: int = 0,
    nonfinite: int = 0,
    spikes: int = 0,
    skipped: int = 0,
    bad_rows: int = 0,
) -> GuardState:
    """Fresh probe state. Rollback passes the preserved counter values so
    the cumulative contract survives the EWMA reset."""
    f = lambda v: jnp.asarray(v, jnp.float32)
    i = lambda v: jnp.asarray(v, jnp.int32)
    return GuardState(
        loss_mean=f(0.0), loss_var=f(0.0),
        gnorm_mean=f(0.0), gnorm_var=f(0.0),
        warm=i(0), total=i(total),
        nonfinite=i(nonfinite), spikes=i(spikes),
        skipped=i(skipped), bad_rows=i(bad_rows),
    )


def health_vector(g: GuardState) -> jnp.ndarray:
    """Pack the cumulative counters into the per-chunk health word."""
    return jnp.stack(
        [g.total, g.nonfinite, g.spikes, g.skipped, g.bad_rows]
    ).astype(jnp.int32)


def _tree_all_finite(tree) -> jnp.ndarray:
    """True iff every float leaf of `tree` is fully finite (int leaves —
    step counters, Adam counts — are finite by construction and skipped)."""
    ok = jnp.asarray(True)
    for leaf in jax.tree.leaves(tree):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(leaf)))
    return ok


def batch_row_health(packed: jnp.ndarray, idx: Optional[jnp.ndarray]):
    """Pre-step screen of the raw sampled rows.

    packed: f32[K, B, D] gathered minibatch rows; idx: i32[K, B] replay
    indices (None on the host-fed path, where the sampler owns indices).
    Returns (pre_bad f32-free bool[K], bad_count i32, bad_idx i32[GUARD_BAD_IDX])
    — per-step "this step's batch contains a non-finite row" flags, the
    total bad-row count, and the first GUARD_BAD_IDX offending replay
    indices (-1 padded) for the host's source attribution."""
    row_bad = jnp.logical_not(jnp.all(jnp.isfinite(packed), axis=-1))  # [K,B]
    pre_bad = jnp.any(row_bad, axis=-1)                                # [K]
    bad_count = jnp.sum(row_bad).astype(jnp.int32)
    if idx is None:
        return pre_bad, bad_count, jnp.full((GUARD_BAD_IDX,), -1, jnp.int32)
    flat_bad = row_bad.reshape(-1)
    flat_idx = idx.reshape(-1).astype(jnp.int32)
    # First-K bad positions via top_k over the bad mask (deterministic,
    # O(n log k)); non-bad slots mask to -1.
    k = min(GUARD_BAD_IDX, flat_bad.shape[0])
    vals, pos = jax.lax.top_k(flat_bad.astype(jnp.float32), k)
    got = jnp.where(vals > 0, flat_idx[pos], -1)
    if k < GUARD_BAD_IDX:
        got = jnp.concatenate(
            [got, jnp.full((GUARD_BAD_IDX - k,), -1, jnp.int32)]
        )
    return pre_bad, bad_count, got


def make_guarded_step(
    step_fn,
    zmax: float,
    warmup: int,
    inject: Optional[Dict[str, Tuple[int, ...]]] = None,
):
    """Wrap a pure learner step (state, batch, noise) -> StepOutput with
    the health probe. Returns

        guarded(state, gstate, batch, pre_bad, noise=None) ->
            (new_state, new_gstate, td_errors, metrics)

    where `pre_bad` is this step's raw-row screen from batch_row_health
    (a scalar bool; pass False when rows were screened elsewhere). The
    update is dropped when the step is bad; the TrainState step counter
    still advances so the fold_in(seed, step) noise streams never
    re-draw (and a chunk's pre-drawn `noise`, handed on to the step, stays
    aligned). `inject` maps 'grad'/'loss' to guarded-step ordinals
    (faults.numeric_steps) and is baked into the traced program — absent
    (the production case) the injection code does not exist."""
    inject = inject or {}
    zmax = float(zmax)
    warmup = int(warmup)

    def _fires(ordinal, ats):
        fire = jnp.asarray(False)
        for at in ats:
            fire = jnp.logical_or(fire, ordinal == jnp.int32(at))
        return fire

    def guarded(state, g: GuardState, batch, pre_bad, noise=None):
        ordinal = g.total + 1
        if inject.get("grad"):
            fire = _fires(ordinal, inject["grad"])
            batch = batch._replace(
                obs=batch.obs + jnp.where(fire, jnp.nan, 0.0)
            )
        if inject.get("loss"):
            fire = _fires(ordinal, inject["loss"])
            batch = batch._replace(
                reward=batch.reward * jnp.where(fire, SPIKE_SCALE, 1.0)
            )

        out = step_fn(state, batch, noise)
        m = out.metrics
        closs = m["critic_loss"]
        gnorm = m["critic_grad_norm"]
        finite_ok = jnp.logical_and(
            jnp.all(jnp.isfinite(out.td_errors)),
            jnp.logical_and(
                _tree_all_finite(
                    (closs, m["actor_loss"], gnorm, m["actor_grad_norm"])
                ),
                jnp.logical_and(
                    _tree_all_finite(out.state.actor_params),
                    _tree_all_finite(out.state.critic_params),
                ),
            ),
        )
        # One-sided z-scores (divergence is always UP): armed only after
        # `warmup` clean observations, and never on a non-finite step
        # (NaN z-scores must not double-count).
        armed = jnp.logical_and(g.warm >= warmup, finite_ok)
        z_loss = (closs - g.loss_mean) * jax.lax.rsqrt(g.loss_var + 1e-12)
        z_g = (gnorm - g.gnorm_mean) * jax.lax.rsqrt(g.gnorm_var + 1e-12)
        spike = jnp.logical_and(
            armed, jnp.logical_or(z_loss > zmax, z_g > zmax)
        )
        bad = jnp.logical_or(
            pre_bad, jnp.logical_or(jnp.logical_not(finite_ok), spike)
        )

        # Drop the update on a bad step: every leaf keeps its pre-step
        # value except the step counter (deterministic noise streams key
        # on it and must not re-draw the exact draw that just failed).
        kept = jax.tree.map(
            lambda old, new: jnp.where(bad, old, new), state, out.state
        )
        kept = kept._replace(step=out.state.step)
        td = jnp.where(bad, 0.0, out.td_errors)
        metrics = {k: jnp.where(bad, 0.0, v) for k, v in m.items()}

        # EWMA absorbs only clean, finite steps — a spike that updated its
        # own baseline would mask the follow-on steps of a divergence.
        upd = jnp.logical_not(bad)

        def ewma(mean, var, x):
            diff = x - mean
            incr = EWMA_ALPHA * diff
            new_mean = jnp.where(upd, mean + incr, mean)
            new_var = jnp.where(
                upd, (1.0 - EWMA_ALPHA) * (var + diff * incr), var
            )
            return new_mean, new_var

        loss_mean, loss_var = ewma(g.loss_mean, g.loss_var, closs)
        gnorm_mean, gnorm_var = ewma(g.gnorm_mean, g.gnorm_var, gnorm)
        new_g = GuardState(
            loss_mean=loss_mean, loss_var=loss_var,
            gnorm_mean=gnorm_mean, gnorm_var=gnorm_var,
            warm=g.warm + upd.astype(jnp.int32),
            total=ordinal,
            nonfinite=g.nonfinite
            + jnp.logical_not(finite_ok).astype(jnp.int32),
            spikes=g.spikes + spike.astype(jnp.int32),
            skipped=g.skipped + bad.astype(jnp.int32),
            bad_rows=g.bad_rows,
        )
        return kept, new_g, td, metrics

    return guarded
