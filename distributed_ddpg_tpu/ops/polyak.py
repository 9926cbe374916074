"""Polyak (soft) target-network update: target <- tau*online + (1-tau)*target.

In the reference this is a set of TF assign ops executed against
parameter-server variables every train step — a network round trip
(SURVEY.md §3.4). Here it is a pure pytree lerp fused into the jitted
learner step: zero boundary crossings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_ddpg_tpu.trace import device_scope


def _is_statistic(path) -> bool:
    """A running input statistic of a residual net (models/mlp.RS_STATS)."""
    return bool(path) and str(getattr(path[-1], "key", "")).startswith("rs_")


def polyak_update(online, target, tau):
    """The averaged target. A residual net's input statistics are COPIED
    from the online net, not averaged: a state has one normaliser, and its
    targets read what the online nets read."""
    with device_scope("polyak"):
        return jax.tree_util.tree_map_with_path(
            lambda path, o, t: (
                o if _is_statistic(path) else tau * o + (1.0 - tau) * t
            ),
            online, target,
        )


def target_update(online, target, tau, step, period: int = 0):
    """The target after the update whose pre-increment count is `step`:
    `polyak_update` with `tau` where `period` is 0 (config's default: the
    same program, op for op), else `online` copied whole when that update
    ends a period ((step + 1) % period == 0: after updates period - 1,
    2 * period - 1, ...) and `target` as it was otherwise. A period of 1
    is tau = 1. A select, not a branch: the scan body stays one straight
    line, and every replica reads the same replicated count."""
    if not period:
        return polyak_update(online, target, tau)
    with device_scope("polyak"):
        copy = jax.lax.rem(step + 1, jnp.asarray(period, step.dtype)) == 0  # both positive
        return jax.tree.map(lambda o, t: jnp.where(copy, o, t), online, target)


def target_copies(steps, period: int):
    """How many of the learner steps 0 .. steps-1 ended with the targets
    copied whole (target_update's rule): the records' `target_copies`."""
    return steps // period
