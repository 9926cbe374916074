"""Pallas TPU kernel: fused Adam + Polyak parameter update.

The optimizer update is HBM-bandwidth-bound: per leaf it reads params, both
Adam moments, grads, and the Polyak target, and writes four of them. Done as
separate ops that is 9 HBM round trips over the parameter footprint; fused
into one VPU pass it is 5 reads + 4 writes with every intermediate kept in
VMEM — and the Polyak lerp (SURVEY.md §3.4) rides along for free.

The whole param tree is raveled to one flat f32 vector (a no-op layout
change under XLA), padded to the f32 (8, 128) tile, processed by a single
grid of row blocks, and unraveled. Scalars that change per step (lr, the
two Adam bias corrections, tau) enter through SMEM.

`fused_adam_polyak` is numerically identical to ops.optim.adam_update +
ops.polyak.polyak_update (same formulas, same order); tests/test_fused.py
enforces equivalence (bit-exact on real TPU too). On non-TPU backends the
kernel runs in pallas interpret mode, so the feature degrades in speed,
never in availability.

When to enable: only for LARGE parameter trees. Measured on v5e-1 at the
default DDPG scale (2x256 MLPs, ~200KB params) the ravel/pad/unravel around
the kernel outweighs the HBM-round-trip savings — 17.3k steps/s fused vs
28.1k unfused at chunk=200 — which is why config.fused_update defaults to
False. The crossover favors the kernel once the parameter footprint is
MB-scale (where the 9->1 HBM pass reduction dominates).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_ddpg_tpu.ops.fused_chunk import runs_native
from distributed_ddpg_tpu.ops.optim import B1, B2, EPS
from distributed_ddpg_tpu.types import OptState

_LANES = 128
_SUBLANES = 8
_BLOCK_ROWS = 256  # rows of 128 lanes per grid step (128KB/operand in VMEM)


def _kernel(scal_ref, p_ref, m_ref, v_ref, g_ref, t_ref,
            p_out, m_out, v_out, t_out):
    lr = scal_ref[0]
    bc1 = scal_ref[1]
    bc2 = scal_ref[2]
    tau = scal_ref[3]
    g = g_ref[:]
    m = B1 * m_ref[:] + (1.0 - B1) * g
    v = B2 * v_ref[:] + (1.0 - B2) * (g * g)
    p = p_ref[:] - lr * (m / bc1) / (jnp.sqrt(v / bc2) + EPS)
    p_out[:] = p
    m_out[:] = m
    v_out[:] = v
    t_out[:] = tau * p + (1.0 - tau) * t_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_flat(flat_p, flat_m, flat_v, flat_g, flat_t, scalars, interpret=False):
    n = flat_p.shape[0]
    rows = -(-n // _LANES)
    rows_padded = -(-rows // _BLOCK_ROWS) * _BLOCK_ROWS
    pad = rows_padded * _LANES - n

    def shape2d(x):
        return jnp.pad(x, (0, pad)).reshape(rows_padded, _LANES)

    grid = rows_padded // _BLOCK_ROWS
    block = pl.BlockSpec(
        (_BLOCK_ROWS, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    scal_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = jax.ShapeDtypeStruct((rows_padded, _LANES), jnp.float32)
    p2, m2, v2, t2 = pl.pallas_call(
        _kernel,
        grid=(grid,),
        in_specs=[scal_spec, block, block, block, block, block],
        out_specs=[block, block, block, block],
        out_shape=[out_shape] * 4,
        interpret=interpret,
    )(scalars, shape2d(flat_p), shape2d(flat_m), shape2d(flat_v),
      shape2d(flat_g), shape2d(flat_t))

    def unshape(x):
        return x.reshape(-1)[:n]

    return unshape(p2), unshape(m2), unshape(v2), unshape(t2)


def fused_adam_polyak(params, grads, opt: OptState, targets, lr, tau):
    """One fused step: (params, opt) <- Adam(params, grads, opt, lr);
    targets <- tau * new_params + (1 - tau) * targets.
    Returns (new_params, new_opt, new_targets)."""
    from jax.flatten_util import ravel_pytree

    flat_p, unravel = ravel_pytree(params)
    flat_m, _ = ravel_pytree(opt.mu)
    flat_v, _ = ravel_pytree(opt.nu)
    flat_g, _ = ravel_pytree(grads)
    flat_t, _ = ravel_pytree(targets)

    count = opt.count + 1
    c = count.astype(jnp.float32)
    scalars = jnp.stack(
        [
            jnp.asarray(lr, jnp.float32),
            1.0 - B1 ** c,
            1.0 - B2 ** c,
            jnp.asarray(tau, jnp.float32),
        ]
    )
    p, m, v, t = _fused_flat(
        flat_p, flat_m, flat_v, flat_g, flat_t, scalars,
        interpret=not runs_native(),
    )
    return (
        unravel(p),
        OptState(mu=unravel(m), nu=unravel(v), count=count),
        unravel(t),
    )
