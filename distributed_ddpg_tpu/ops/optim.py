"""Explicit Adam, tree-level.

Written out (rather than hidden behind an optimizer-library object) for two
reasons tied to this framework's contract:
1. the numpy `native` backend must produce bit-comparable updates
   (BASELINE.json:5) — same formulas, same order of operations;
2. the whole update lives inside the one jitted learner step — there is no
   optimizer.apply_gradients host round trip like the reference's
   parameter-server path (SURVEY.md §3.3).

Formulation matches optax.adam defaults (b1=0.9, b2=0.999, eps=1e-8,
eps_root=0): bias-corrected moments, eps added outside the sqrt.

The two bias corrections are computed inside every call, on scalars (two
`power`s, two subtracts, a convert, the count's add): in the scan chunk's
loop on the TPU that is 19 unfused instructions an update under SAC's three
Adams, and they cost nothing measurable there. Computed once a launch in
front of the scan and handed in (tried on the chip, PR 46: PERF.md §6), the
loop's own time stood (5.87 -> 5.94 ms a launch of 800 updates) and every
fusion that holds an Adam took 0.7 us longer to read the pair out of an
array than it takes from the scalar core (the launch 39.5 -> 46.1 ms). The
scalar core runs such a chain beside the vector unit's fusions; they stay
where they are.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_ddpg_tpu.trace import device_scope
from distributed_ddpg_tpu.types import OptState

B1 = 0.9
B2 = 0.999
EPS = 1e-8


def adam_update(params, grads, opt: OptState, lr, b1: float = B1, weight_decay: float = 0.0):
    """One Adam step with beta_1 `b1` (config.adam_b1); with `weight_decay`
    (config.weight_decay, a Python float) AdamW's decoupled decay on every
    leaf, p <- p - lr * (adam's step + weight_decay * p). 0 traces plain
    Adam, op for op. Returns (new_params, new_opt)."""
    with device_scope("optim"):
        count = opt.count + 1
        c = count.astype(jnp.float32)
        bc1 = 1.0 - b1 ** c
        bc2 = 1.0 - B2 ** c
        mu = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * g, opt.mu, grads)
        nu = jax.tree.map(
            lambda v, g: B2 * v + (1.0 - B2) * (g * g), opt.nu, grads
        )
        if weight_decay:
            step = lambda p, m, v: p - lr * (
                (m / bc1) / (jnp.sqrt(v / bc2) + EPS) + weight_decay * p
            )
        else:
            step = lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + EPS)
        new_params = jax.tree.map(step, params, mu, nu)
    return new_params, OptState(mu=mu, nu=nu, count=count)
