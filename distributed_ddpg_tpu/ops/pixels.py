"""What the pixel configuration (config.pixels: DrQ-v2, arXiv 2107.09645) adds
around its nets: byte images in and out of the float32 ring's words, the
random-shift augmentation, and the noise scale's schedule.

**Bytes in a float32 ring.** The ring stays the one f32[capacity, width] array
every program and the benchmark's check read (`storage[idx]`); a pixel row is
[obs words | action | R | d | next_obs words | w] with four pixels to a word
(types.ObsSpec.words: 15,876 words an image of 9x84x84), 31,776 words,
127,104 B. A word that holds pixels is only ever MOVED (gather, slice,
dynamic-update-slice, concatenate, the n-step window's select between two
rows) and bitcast, `lax.bitcast_convert_type` on both sides: no arithmetic
and no convert ever sees it, so bytes that spell a NaN or a subnormal come
back as they went in (tests/test_pixels.py carries every byte value through
every position of a word). Why not a uint8 ring: the row-major layout, its insert programs, the
staging ring, the checkpoint and the harness's `storage[idx]` all hold one
float32 array today, and a row's 24 float fields would have needed the same
bitcast the other way round.

Byte order is the bitcast's: byte k of a word is bits 8k..8k+7, which on the
little-endian hosts this runs on is numpy's `view(np.uint8)` order, so host
rows and device rows agree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_ddpg_tpu.models.pixels import encoder_input
from distributed_ddpg_tpu.trace import device_scope
from distributed_ddpg_tpu.types import Batch, ObsSpec, unpack_batch


def words_of(images):
    """uint8[..., C, H, W] -> the float32 words f32[..., C * H * W / 4] that
    hold the same bytes in the same order."""
    lead = images.shape[:-3]
    quads = images.reshape(*lead, -1, 4)
    return jax.lax.bitcast_convert_type(quads, jnp.float32)


def images_of(words, obs: ObsSpec):
    """f32[..., words] -> uint8[..., C, H, W]: words_of's inverse."""
    quads = jax.lax.bitcast_convert_type(words, jnp.uint8)
    return quads.reshape(*words.shape[:-1], *obs.shape)


def cut_pixels(packed, obs: ObsSpec, act_dim: int) -> Batch:
    """A launch's gathered rows f32[K, B, width] -> the Batch the pixel step
    scans over: the float fields as unpack_batch cuts them, `obs` and
    `next_obs` as BYTE images uint8[K, B, C, H, W] (the same bytes the rows
    hold: a launch's images as float32 would be four times the gathered
    block). The bitcast reads under `prep/pixels`."""
    b = unpack_batch(packed, obs.words, act_dim)
    with device_scope("prep"), device_scope("pixels"):
        return b._replace(
            obs=images_of(b.obs, obs), next_obs=images_of(b.next_obs, obs)
        )


def random_shift(images, offsets, pad: int):
    """DrQ-v2's augmentation on uint8[B, C, H, W]: each image padded by `pad`
    pixels on every side by replicating its edge, and an H x W crop taken at
    its own `offsets` int32[B, 2] = (dy, dx) in 0..2*pad, the same for all
    its channels. The source does this with `grid_sample` at integer shifts,
    which lands on pixel centres: it is this crop (tests/test_pixels.py).
    Returns the encoder's input, f32[B, C, H, W] = crop / 255 - 0.5."""
    _, c, h, w = images.shape
    if pad:
        padded = jnp.pad(
            images, ((0, 0), (0, 0), (pad, pad), (pad, pad)), mode="edge"
        )
        images = jax.vmap(
            lambda im, off: jax.lax.dynamic_slice(
                im, (0, off[0], off[1]), (c, h, w)
            )
        )(padded, offsets)
    return encoder_input(images)


# Environment frames a learner update stands for: the source's one update in
# two agent steps, at its action repeat of 2 (the pixel environment's
# ACTION_REPEAT, inside its step; tests/test_pixels.py holds the two equal).
FRAMES_PER_UPDATE = 4


def sigma_at(schedule, step):
    """The source's `linear(initial, final, duration)` at learner update
    `step` (a traced or a Python number), which reads it at
    FRAMES_PER_UPDATE * step environment frames; `schedule` is
    config.sigma_schedule, the parsed explore_sigma_schedule."""
    init, final, duration = schedule
    frames = FRAMES_PER_UPDATE * step
    mix = jnp.clip(jnp.asarray(frames, jnp.float32) / duration, 0.0, 1.0)
    return (1.0 - mix) * init + mix * final


def clipped_action(mu, noise, low, high):
    """The source's truncated-normal sample around `mu`: mu + noise (already
    scaled and clipped to the noise clip) clamped into the action box shrunk
    by 1e-6 of its half-width, the clamp passing its gradient straight
    through (x - stop(x) + stop(clamp(x)))."""
    x = mu + noise
    eps = 1e-6 * (high - low) / 2.0
    return x + jax.lax.stop_gradient(jnp.clip(x, low + eps, high - eps) - x)
