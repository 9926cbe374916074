"""The scan leg's front: a launch's gathered ring rows to the scan's operands
in one pass (PERF.md PR 42).

`unpack_batch(storage[idx])` leaves the chip's compiler an f32[K*B, W] block
that it re-reads three times (observations, scalars, action) and relays
twice more: the scan wants its observations and action FEATURE-MAJOR, the
batch on lanes, and (where every reader is a matmul the TPU runs in one
bfloat16 pass) rounded to bfloat16. Here XLA's gather stays and one Pallas
kernel reads the gathered block through a BlockSpec, R rows at a time, and
does in VMEM: the transpose, the static cuts at o, o + a, o + a + 2 and
2o + a + 2, and the rounding (to nearest even, as the compiler's own hoisted
convert rounds), so that each field leaves once, in the layout the scan
reads: `[K, d, B]`, whose swap back to `[K, B, d]` XLA lays out as a bitcast.
The custom call reads under the program's `cut` scope.

Which launches take it is `front_for`'s rule on what the program sees; `xla`
is `unpack_batch(storage[idx])` as it always was. The form that gathers in
the kernel too (one row DMA an index) does not compile: Mosaic takes no
one-row slice of an (8, 128)-tiled HBM array (PERF.md PR 42).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_ddpg_tpu.ops.fused_chunk import runs_native
from distributed_ddpg_tpu.trace import device_scope
from distributed_ddpg_tpu.types import Batch

_LANES = 128
# A block's rows in VMEM, lane-padded: the pipeline holds two such buffers
# and the kernel a transposed third, beside the output blocks, so 1 MiB each
# stays far inside Mosaic's default scoped VMEM (16 MiB on a v5e).
_BLOCK_BYTES = 1 << 20


def block_rows(batch: int, width: int) -> int:
    """Rows of one kernel block: the largest multiple of 128 that divides the
    batch and whose lane-padded rows fit _BLOCK_BYTES (256 at Humanoid's 772,
    1,024 at 240 with a batch of 8,192); 0 where the batch is no multiple of
    128 (the transposed block puts the batch on lanes)."""
    if batch % _LANES:
        return 0
    cap = max(_LANES, _BLOCK_BYTES // (4 * _padded(width)) // _LANES * _LANES)
    rows = min(batch, cap)
    while batch % rows:
        rows -= _LANES
    return rows


def _padded(width: int) -> int:
    return -(-width // _LANES) * _LANES


def rounds_inputs(config) -> bool:
    """Whether the step reads obs, action and next_obs through nothing but
    matmuls the TPU runs in one bfloat16 pass, so that the compiled scan holds
    those fields in bfloat16 alone (XLA hoists the operands' convert out of
    the loop) and the front may hand them over rounded: every net without a
    normalising layer in front of its first dense one, at the default matmul
    precision. CrossQ's batch statistics read them in float32."""
    return not config.crossq and jax.config.jax_default_matmul_precision in (
        None, "default", "bfloat16"
    )


def rounds_action(config) -> bool:
    """Whether the action may leave the front rounded with the other two:
    not under MPO, whose critic maps the ring's action onto the canonical box
    (a subtraction and a division) in front of its first product: rounded
    first and divided then, it would be rounded twice."""
    return rounds_inputs(config) and not config.mpo


def front_for(*, width: int, batch: int, layout: str, replay_sharded: bool,
              model_axis: int, native: bool) -> str:
    """'cut' or 'xla' for a scan-leg launch that gathers `batch` rows a chip
    and update out of a ring of `width` floats a row held in `layout`
    (replay.device.ring_layout). The kernel where its pass replaces re-reads
    and relayouts of a lane-padded block: a plain row-major ring (so: a real
    TPU), replicated, the nets whole on every chip, the batch a multiple of
    the 128 lanes it lands on. Packed lines (the megakernel's), compact rows,
    row-sharded replay and tensor parallelism keep XLA's cuts."""
    if (
        native
        and layout == "row_major"
        and not replay_sharded
        and model_axis == 1
        and block_rows(batch, width)
    ):
        return "cut"
    return "xla"


def _cut_kernel(obs_dim, act_dim, rows_ref, obs_ref, act_ref, nobs_ref,
                scal_ref, tbuf):
    """One block: `rows_ref` f32[R, W] transposed a 128-lane group at a time
    into `tbuf` f32[Wp, R], then each field cut out of it at its static
    offset, cast to its output's dtype and stored: obs, action, next_obs as
    [1, d, R], the three scalars as the rows of [1, 3, R]."""
    o, a = obs_dim, act_dim
    width = rows_ref.shape[-1]
    for lo in range(0, width, _LANES):
        hi = min(lo + _LANES, width)
        tbuf[lo:hi, :] = rows_ref[:, lo:hi].T
    obs_ref[0] = tbuf[0:o, :].astype(obs_ref.dtype)
    act_ref[0] = tbuf[o : o + a, :].astype(act_ref.dtype)
    nobs_ref[0] = tbuf[o + a + 2 : 2 * o + a + 2, :].astype(nobs_ref.dtype)
    scal_ref[0, 0:2, :] = tbuf[o + a : o + a + 2, :]
    scal_ref[0, 2:3, :] = tbuf[2 * o + a + 2 : 2 * o + a + 3, :]


def cut_rows(packed, obs_dim: int, act_dim: int, rounded: bool,
             interpret: bool | None = None,
             action_rounded: bool | None = None) -> Batch:
    """`packed` f32[K, B, W] (a launch's gathered rows; B a multiple of 128)
    to the Batch `unpack_batch` cuts from it, in one pass: [K, B, d] float32
    fields, obs and next_obs holding their bfloat16 rounding where
    `rounded`, the action where `action_rounded` (as `rounded` unless said:
    rounds_action). Interpreted off the TPU."""
    K, B, W = packed.shape
    R = block_rows(B, W)
    nb = B // R
    wide = jnp.bfloat16 if rounded else jnp.float32
    if action_rounded is None:
        action_rounded = rounded
    dims = (obs_dim, act_dim, obs_dim)
    dtypes = (wide, jnp.bfloat16 if action_rounded else jnp.float32, wide)
    with device_scope("cut"):
        obs_t, act_t, nobs_t, scal = pl.pallas_call(
            functools.partial(_cut_kernel, obs_dim, act_dim),
            grid=(K * nb,),
            in_specs=[pl.BlockSpec((R, W), lambda i: (i, 0))],
            out_specs=[
                pl.BlockSpec((1, d, R), lambda i: (i // nb, 0, i % nb))
                for d in (*dims, 3)
            ],
            out_shape=[
                *(jax.ShapeDtypeStruct((K, d, B), t) for d, t in zip(dims, dtypes)),
                jax.ShapeDtypeStruct((K, 3, B), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((_padded(W), R), jnp.float32)],
            interpret=(not runs_native()) if interpret is None else interpret,
        )(packed.reshape(K * B, W))

        # Feature-major out of the kernel; the swap is a bitcast where the
        # compiler lays [K, B, d] out batch-minor, and a rounded field's
        # float32 holds its bfloat16 value exactly.
        def rows(x):
            return jnp.swapaxes(x, 1, 2).astype(jnp.float32)

        return Batch(
            obs=rows(obs_t),
            action=rows(act_t),
            reward=scal[:, 0, :],
            discount=scal[:, 1, :],
            next_obs=rows(nobs_t),
            weight=scal[:, 2, :],
        )
