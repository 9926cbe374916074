"""Polyak (soft) target-network update: target <- tau*online + (1-tau)*target.

In the reference this is a set of TF assign ops executed against
parameter-server variables every train step — a network round trip
(SURVEY.md §3.4). Here it is a pure pytree lerp fused into the jitted
learner step: zero boundary crossings.
"""

from __future__ import annotations

import jax

from distributed_ddpg_tpu.trace import device_scope


def polyak_update(online, target, tau):
    with device_scope("polyak"):
        return jax.tree.map(
            lambda o, t: tau * o + (1.0 - tau) * t, online, target
        )
