"""What the pixel configuration (config.pixels: DrQ-v2, arXiv 2107.09645) adds
around its nets: byte images in and out of the float32 ring's words, the
random-shift augmentation that takes an update's images from those words to
the encoder's input, and the noise scale's schedule.

**Bytes in a float32 ring.** The ring stays the one f32[capacity, width] array
every program and the benchmark's check read (`storage[idx]`); a pixel row is
[obs words | action | R | d | next_obs words | w] with four pixels to a word
(types.ObsSpec.words: 15,876 words an image of 9x84x84), 31,776 words,
127,104 B. A word that holds pixels is only ever MOVED (gather, slice,
dynamic-update-slice, concatenate, the n-step window's select between two
rows) and reinterpreted: no float arithmetic and no convert ever sees it, so
bytes that spell a NaN or a subnormal come back as they went in
(tests/test_pixels.py carries every byte value through every position of a
word). Why not a uint8 ring: the row-major layout, its insert programs, the
staging ring, the checkpoint and the harness's `storage[idx]` all hold one
float32 array today, and a row's 24 float fields would have needed the same
bitcast the other way round.

Byte order: byte k of a word is bits 8k..8k+7 and holds pixel 4m + k of word
m, which on the little-endian hosts this runs on is numpy's `view(np.uint8)`
order, so host rows and device rows agree. `words_of` (the rollout's side)
packs with `lax.bitcast_convert_type` from uint8[..., 4].

**Where the bytes come out.** A launch's gathered block stays words all the
way into the scan (`cut_pixels`): an update unpacks its own 256 rows, by
integer arithmetic on the word reinterpreted at its own width
(`bitcast_convert_type` to int32, then `(w >> 8k) & 0xFF`), inside
`random_shift`. No `bitcast_convert_type` to a NARROWER type stands anywhere
on the learner's path: the TPU's compiler takes `f32 -> u8[..., 4]` apart as
a broadcast of every word to `u32[..., 4]`, 32 bits a pixel (at PR 47
`broadcast.739` / `.745` and `reshape.162` / `.164`, 2.08 GB a field of the
launch's block, then `and_convert_fusion.16`: 34.6 ms a launch for 1 GB of
useful bytes), and a batched `dynamic_slice` on the byte images became a
`while` of 256 trips, one image a trip (eight of them in the scan's body, 42
ms a launch; PERF.md, PR 48).
"""

from __future__ import annotations

import functools

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


def cut_pixels(packed, obs: ObsSpec, act_dim: int) -> Batch:
    """A launch's gathered rows f32[K, B, width] -> the Batch the pixel step
    scans over: unpack_batch's fields, with `obs` and `next_obs` still WORDS
    (nothing unpacks a launch's block) but turned batch-minor,
    f32[K, obs.words, B], the layout `random_shift` works in. The TPU's
    compiler makes that transposition in front of the scan whether it is
    written or not (only there is [.., 15876] -> [9, 84, 21] no lane
    shuffle); written here it reads under `prep/pixels`. So does the cut of
    the two image fields on the chip, which XLA fuses with the
    transposition's bitcast (3.15 of 6.35 ms a launch: PERF.md section 5)."""
    b = unpack_batch(packed, obs.words, act_dim)
    with device_scope("prep"), device_scope("pixels"):
        return b._replace(
            obs=jnp.swapaxes(b.obs, -1, -2),
            next_obs=jnp.swapaxes(b.next_obs, -1, -2),
        )


_LANES = 128


def _edge_words(pad: int) -> int:
    """Whole words a row can move either way: ceil(pad / 4)."""
    return -(-pad // 4)


def _crop_kernel(off_ref, x_ref, o_ref, row_ref, *, pad: int):
    """One channel of 128 images, the batch on the lanes. x_ref
    int32[1, H, W/4, 128]: the image's words, four pixels each; off_ref
    int32[2, 128]: the images' dy over their dx; o_ref f32[1, H, W, 128]: the
    cropped pixels, 0..255; row_ref int32[rows a trip, e + W/4 + e + 1, 128],
    e = _edge_words(pad): a row of words between its replicated edges, one
    slot for each of the rows a trip of the loop takes. The rows of a trip
    are independent chains the scheduler interleaves: at the cell's shapes
    a row alone is 73 bundles deep and six are 33 each
    (tools/kernel_bundles.py), which on the chip is 73 us a call for 100,
    the step's 4.5 MB of DMA being what is left to wait for (1.6% of the
    launch: PERF.md, PR 48). A row at a time: the row each image reads is
    picked among the 2*pad+1 it can be (a select a shift, on whole words),
    its columns move by whole words (2e+2 sublane windows on the padded row)
    and by the bytes left over (a funnel shift of two neighbouring words by
    each lane's own count), and only then are the four bytes taken apart,
    each plane stored to every fourth pixel."""
    from jax.experimental import pallas as pl

    _, h, wq, _ = x_ref.shape
    unroll, edge = row_ref.shape[0], _edge_words(pad)
    srl = jax.lax.shift_right_logical
    dy = off_ref[0:1, :]
    shift = off_ref[1:2, :] - pad  # -pad..pad pixels
    whole = shift >> 2  # floor(shift / 4) words, -edge..edge
    bits = (shift - 4 * whole) * 8
    rest = 32 - bits

    def spread(byte, rows):
        word = byte | (byte << 8) | (byte << 16) | (byte << 24)
        return jnp.broadcast_to(word, (rows, _LANES))

    def row(i, slot):
        words = x_ref[0, jnp.clip(i - pad, 0, h - 1)]
        for s in range(1, 2 * pad + 1):
            words = jnp.where(
                dy == s, x_ref[0, jnp.clip(i + s - pad, 0, h - 1)], words
            )
        if edge:
            row_ref[slot, 0:edge, :] = spread(words[0:1] & 0xFF, edge)
        row_ref[slot, edge : edge + wq, :] = words
        row_ref[slot, edge + wq :, :] = spread(
            srl(words[wq - 1 : wq], 24), edge + 1
        )
        at = [row_ref[slot, j : j + wq, :] for j in range(2 * edge + 2)]
        low, high = at[edge], at[edge + 1]  # words q and q + 1
        for step in range(1, edge + 1):
            low = jnp.where(whole == -step, at[edge - step], low)
            high = jnp.where(whole == -step, at[edge + 1 - step], high)
            low = jnp.where(whole == step, at[edge + step], low)
            high = jnp.where(whole == step, at[edge + 1 + step], high)
        moved = jnp.where(bits == 0, low, srl(low, bits) | (high << rest))
        for k in range(4):
            o_ref[0, i, pl.ds(k, wq, stride=4), :] = (
                srl(moved, 8 * k) & 0xFF
            ).astype(jnp.float32)

    def trip(g, carry):
        for slot in range(unroll):
            row(g * unroll + slot, slot)
        return carry

    jax.lax.fori_loop(0, h // unroll, trip, 0)


def _crop(x, offsets, pad: int, interpret: bool):
    """x int32[C, H, W/4, B] (B a multiple of 128), offsets int32[B, 2] ->
    f32[C, H, W, B]: each image's replicate-padded crop at its own (dy, dx),
    as floats 0..255. Reads under the caller's scope as `pixel_crop`."""
    # imported where it is used: the learner imports this module, and every
    # process that imports the learner would pay for Pallas (0.2 s)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c, h, wq, b = x.shape
    unroll = next(u for u in (6, 4, 3, 2, 1) if h % u == 0)  # rows a loop trip
    return pl.pallas_call(
        functools.partial(_crop_kernel, pad=pad),
        grid=(b // _LANES, c),
        in_specs=[
            pl.BlockSpec((2, _LANES), lambda j, ch: (0, j)),
            pl.BlockSpec((1, h, wq, _LANES), lambda j, ch: (ch, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, h, 4 * wq, _LANES), lambda j, ch: (ch, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((c, h, 4 * wq, b), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM(
                (unroll, wq + 2 * _edge_words(pad) + 1, _LANES), jnp.int32
            )
        ],
        interpret=interpret,
        name="pixel_crop",
    )(offsets.T, x)


def random_shift(words, offsets, pad: int, obs: ObsSpec, interpret=None):
    """DrQ-v2's augmentation, from an update's own rows of ring words to the
    encoder's input: words f32[obs.words, B] (four pixels each as the ring
    holds them, the batch minor as `cut_pixels` lays a launch), offsets
    int32[B, 2] = (dy, dx) in 0..2*pad -> f32[B, C, H, W] = crop / 255 - 0.5,
    where the crop is each image padded by `pad` pixels on every side by
    replicating its edge and cut H x W at its own offset, the same for all
    its channels: out[b, c, i, j] = image[b, c, clip(i + dy_b - pad),
    clip(j + dx_b - pad)]. The source does this with `grid_sample` at
    integer shifts, which lands on pixel centres: it is this crop
    (tests/test_pixels.py). `pad` 0 is no shift.

    One pass, one layout. The words are reinterpreted as int32 (the same
    width: no byte-wide bitcast, module text) and regrouped [C, H, W/4, B]:
    with the batch on the lanes the rows, the words of a row and, once
    unpacked, the pixels are all on major or sublane axes and every image
    is a lane, so nothing loops over images. `_crop_kernel` does the rest in
    VMEM, a channel of 128 images a grid step; the encoder's first
    convolution reads its output's layout as it is. `interpret`: None runs
    the kernel compiled on a TPU and interpreted elsewhere
    (ops/fused_chunk.runs_native, the kernels' one rule)."""
    c, h, w = obs.shape
    b = words.shape[-1]
    if w % 4:
        raise ValueError(
            f"random_shift moves rows of whole words: W % 4 == 0, got {w}"
        )
    if interpret is None:
        # imported here: fused_chunk imports the learner, which imports this
        from distributed_ddpg_tpu.ops.fused_chunk import runs_native

        interpret = not runs_native()
    x = jax.lax.bitcast_convert_type(words, jnp.int32).reshape(c, h, w // 4, b)
    spare = -b % _LANES
    if spare:  # a batch that fills no whole lane block: the kernel's tiles do
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, spare)))
        offsets = jnp.pad(offsets, ((0, spare), (0, 0)))
    pixels = _crop(x, offsets, pad, interpret)[..., :b]
    return encoder_input(pixels).transpose(3, 0, 1, 2)


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
