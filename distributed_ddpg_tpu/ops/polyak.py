"""Polyak (soft) target-network update: target <- tau*online + (1-tau)*target.

In the reference this is a set of TF assign ops executed against
parameter-server variables every train step — a network round trip
(SURVEY.md §3.4). Here it is a pure pytree lerp fused into the jitted
learner step: zero boundary crossings.
"""

from __future__ import annotations

import jax

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
