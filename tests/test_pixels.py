"""What the pixel configuration (DrQ-v2, config.pixels) added outside its
update, at small sizes on the CPU: the observation's spec and what every flat
configuration still derives from it; bytes through the float32 ring, every
value in every position of a word, NaN words included, through insert, wrap,
gather, cut and a save and restore; the update's image path (words in, the
encoder's float input out) against the index-map crop of the byte images,
every offset, and against the source's `grid_sample` form; what the lowered
pixel chunk holds; the hand-over from the encoder's block to the trunks against flatten-and-matmul
written out, and the seeded leaves it must not move; the stand-in
environment's frame stack across a reset;
the 3-step fold on rows of this width against the host accumulator; the
partition rules of the new trees; each refusal's message."""

import functools
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu.actors.device_pool import DeviceActorPool
from distributed_ddpg_tpu.actors.worker import _flush_truncated
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.envs import jax_envs
from distributed_ddpg_tpu.envs.jax_envs import PIXEL_STAND_IN_ID, PixelHumanoidStandIn
from distributed_ddpg_tpu.envs.registry import make, spec_of
from distributed_ddpg_tpu.learner import init_train_state, make_learner_step
from distributed_ddpg_tpu.models import pixels as pixnet
from distributed_ddpg_tpu.ops import pixels as pix
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.partition import state_pspec
from distributed_ddpg_tpu.replay.device import DeviceReplay, ring_layout, ring_row_bytes
from distributed_ddpg_tpu.replay.nstep import NStepAccumulator
from distributed_ddpg_tpu.types import ObsSpec, packed_width

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIDE, ACT = 28, PixelHumanoidStandIn.act_dim
OBS = ObsSpec((9, SIDE, SIDE), "uint8")


class Small(PixelHumanoidStandIn):
    SIDE, obs_shape, obs_dim = SIDE, OBS.shape, OBS.size


class Truncating(Small):
    max_episode_steps, BOX = 4, 100.0


class Brief(Small):
    max_episode_steps, BOX = 2, 0.19  # some episodes end by termination


def cfg(**kw):
    base = dict(
        backend="jax_tpu", env_id=PIXEL_STAND_IN_ID, pixels=True, twin_critic=True, action_insert_layer=0,
        actor_backend="device", num_actors=0, device_actor_envs=4, device_actor_chunk=2, n_step=3,
        actor_hidden=(32, 32), critic_hidden=(32, 32), encoder_channels=8, feature_dim=16, batch_size=8,
        replay_capacity=256, tau=0.01, target_noise_clip=0.3, explore_sigma_schedule="1.0,0.1,2000", seed=5,
    )
    base.update(kw)
    return DDPGConfig(**base)


def one_device_mesh():
    return mesh_lib.make_mesh(data_axis=1, model_axis=1, devices=jax.devices()[:1])


# --- the observation's spec: what a flat configuration derives is what it did ---


def cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return [c["name"] for c in bench["configs"]]


@pytest.mark.parametrize("name", cells())
def test_every_configurations_widths_and_row_bytes(name):
    """The nine flat configurations: the ring's width, layout and row bytes,
    and the policy's input width, through ObsSpec are what `obs_dim` gave.
    The pixel one: 15,876 words an image, 31,776 a row, 127,104 B of it
    pixels and fields, 127,488 B on the device."""
    env = json.load(open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")))["env"]
    if "obs_shape" in env:
        obs = ObsSpec(tuple(env["obs_shape"]), env["obs_dtype"])
        assert obs.pixels and obs.size == env["obs_dim"] == 63504 and obs.words == 15876
        width = packed_width(obs, env["act_dim"])
        assert (width, 4 * width) == (31776, 127104)
        assert ring_layout(width) == "row_major" and ring_row_bytes(width, "row_major") == 127488
        return
    d, a = env["obs_dim"], env["act_dim"]
    obs = ObsSpec.of(d)
    assert obs == ObsSpec((d,), "float32") and not obs.pixels and obs.words == obs.size == d
    assert packed_width(obs, a) == packed_width(d, a) == 2 * d + a + 3
    assert ObsSpec.of(obs) is obs


def test_env_spec_knows_both_kinds():
    flat = spec_of(make("Pendulum-v1", prefer_builtin=True))
    assert ObsSpec.of_env(flat) == ObsSpec((3,)) and ObsSpec.of_env(flat).words == flat.obs_dim == 3
    frames = spec_of(make(PIXEL_STAND_IN_ID))
    assert ObsSpec.of_env(frames) == ObsSpec((9, 84, 84), "uint8") and frames.obs_dim == 63504
    assert ObsSpec.of_env(PixelHumanoidStandIn()) == ObsSpec.of_env(frames)
    with pytest.raises(ValueError, match="whole 32-bit words"):
        ObsSpec((3, 5, 5), "uint8").words


# --- bytes through the float32 ring ---

TINY = ObsSpec((1, 16, 16), "uint8")  # 64 words an image
NASTY = np.array([0x7FC00000, 0x7F800001, 0xFFFFFFFF, 0x00000001, 0x80000000, 0xFF800000], np.uint32)


def byte_rows(n, act=3):
    """n >= 8 rows whose images hold every byte value in every position of
    a word, the first words of each the NaNs, the subnormal and the
    infinities of NASTY; the float fields ordinary floats."""
    w = TINY.words
    rows = np.zeros((n, packed_width(TINY, act)), np.float32)
    rng = np.random.default_rng(n)
    for which, at in ((0, 0), (1, w + act + 2)):
        img = np.zeros((n, TINY.size), np.uint8)
        j = np.arange(TINY.size)
        for r in range(n):
            img[r] = (r * 32 + j // 4 + 37 * (j % 4) + 11 * which) % 256
        words = img.view(np.uint32)
        words[:, : len(NASTY)] = np.roll(NASTY, which)[None]
        rows[:, at : at + w] = words.view(np.float32)
    rows[:, w : w + act + 2] = rng.normal(size=(n, act + 2)).astype(np.float32)
    rows[:, -1] = 1.0
    return rows


def test_byte_rows_hold_every_value_in_every_position():
    img = byte_rows(8).view(np.uint8)[:, 4 * len(NASTY) : 4 * TINY.words].reshape(8, -1, 4)
    for p in range(4):
        assert len(np.unique(img[:, :, p])) == 256
    assert np.isnan(byte_rows(8)[:, 0]).all()  # a word of pixels may spell a NaN


def bits(x):
    return np.ascontiguousarray(np.asarray(x)).view(np.uint32)


@pytest.mark.parametrize("capacity", [20, 24])  # 3 x 8 rows: a wrap inside a block, and an exact fit
def test_bytes_come_back_bit_for_bit_through_insert_wrap_gather_and_cut(capacity):
    act, mesh = 3, one_device_mesh()
    ring = DeviceReplay(capacity, TINY.words, act, mesh=mesh, block_size=8, async_ship=False)
    assert ring.ring_layout != "packed" and ring.width == packed_width(TINY, act)
    blocks = [byte_rows(8) for _ in range(3)]
    for k, b in enumerate(blocks):
        b[:, TINY.words] = k  # the action's first column tells the blocks apart
        ring.insert_device_rows(jnp.asarray(b))
    host = np.zeros((capacity, ring.width), np.float32)
    for k, b in enumerate(blocks):  # what a ring of `capacity` rows holds after them
        host.view(np.uint32)[(np.arange(8) + 8 * k) % capacity] = bits(b)
    storage, size = ring.device_state()
    assert int(size) == min(capacity, 24)
    np.testing.assert_array_equal(bits(storage), bits(host))
    idx = jnp.arange(capacity).reshape(2, -1)
    batch = jax.jit(lambda s: pix.cut_pixels(s[idx], TINY, act))(storage)
    w = TINY.words
    # the launch's images stay the words the gather produced, to the bit, the batch minor
    assert batch.obs.dtype == jnp.float32 and batch.obs.shape == (2, w, capacity // 2)
    rows_of = lambda field: bits(field).transpose(0, 2, 1).reshape(capacity, -1)
    np.testing.assert_array_equal(rows_of(batch.obs), bits(host[:, :w]))
    np.testing.assert_array_equal(rows_of(batch.next_obs), bits(host[:, w + act + 2 : 2 * w + act + 2]))
    np.testing.assert_array_equal(np.asarray(batch.action).reshape(capacity, -1), host[:, w : w + act])
    # and the update unpacks them into the bytes the rows hold (no pad: no shift)
    unpack = jax.jit(lambda words: pix.random_shift(words, jnp.zeros((words.shape[1], 2), jnp.int32), 0, TINY))
    for field, at in ((batch.obs, 0), (batch.next_obs, w + act + 2)):
        images = host[:, at : at + w].copy().view(np.uint8).reshape(capacity, *TINY.shape)
        words = jnp.concatenate(list(field), axis=-1)  # [w, capacity]: the two launches' rows side by side
        np.testing.assert_array_equal(np.asarray(unpack(words)), float_input(images))
    # and back into words, as the rollout program packs them
    again = jax.jit(pix.words_of)(jnp.asarray(host[:, :w].copy().view(np.uint8).reshape(capacity, *TINY.shape)))
    np.testing.assert_array_equal(bits(again).reshape(capacity, -1), bits(host[:, :w]))
    # a checkpoint of the ring: saved and restored to the bit
    saved = ring.state_dict()
    fresh = DeviceReplay(capacity, TINY.words, act, mesh=mesh, block_size=8, async_ship=False)
    fresh.load_state_dict(saved)
    np.testing.assert_array_equal(bits(fresh.device_state()[0]), bits(host))
    assert int(fresh.ptr) == int(ring.ptr) and len(fresh) == len(ring)


# --- the update's image path: words in, the encoder's float input out ---


def float_input(images):
    """What the first convolution reads of byte images, as the program that
    converts them computes it (models/pixels.encoder_input, jitted: XLA
    divides by a constant as it sees fit, the same way in every program)."""
    return np.asarray(jax.jit(pixnet.encoder_input)(jnp.asarray(images)))


def words_np(images):
    """uint8[B, C, H, W] -> the ring's words f32[B, words], as numpy views them."""
    return np.ascontiguousarray(images).reshape(images.shape[0], -1).view(np.float32)


def index_map_crop(images, offsets, pad):
    """uint8[B, C, H, W], int[B, 2] -> the replicate-padded crop at each
    image's own (dy, dx), as an index map on the bytes."""
    h, w = images.shape[-2:]
    out = np.empty_like(images)
    for b, (dy, dx) in enumerate(np.asarray(offsets)):
        rows = np.clip(np.arange(h) + dy - pad, 0, h - 1)
        cols = np.clip(np.arange(w) + dx - pad, 0, w - 1)
        out[b] = images[b][:, rows][:, :, cols]
    return out


PAD = 4
SHIFTS = [(dy, dx) for dy in range(2 * PAD + 1) for dx in range(2 * PAD + 1)]
CROP = ObsSpec((2, 16, 16), "uint8")  # 128 words an image


def every_byte_images(n):
    """n >= 8 images of CROP's shape that hold every byte value in every
    position of a word (byte_rows' pattern, without its NaN words: these
    bytes are read, and a word of them may spell anything)."""
    j = np.arange(CROP.size)
    img = np.stack([(r * 32 + j // 4 + 37 * (j % 4)) % 256 for r in range(n)]).astype(np.uint8)
    for p in range(4):
        assert len(np.unique(img.reshape(n, -1, 4)[:8, :, p])) == 256
    return img.reshape(n, *CROP.shape)


@pytest.fixture(scope="module")
def shift_words():
    """The new path, jitted once: (words f32[B, words], offsets) -> f32[B, C, H, W];
    random_shift takes the words batch-minor, as cut_pixels lays a launch."""
    return jax.jit(lambda words, offsets: pix.random_shift(words.T, offsets, PAD, CROP))


@pytest.mark.parametrize("dy,dx", SHIFTS)
def test_words_to_float_input_is_the_index_map_crop_at_every_offset(shift_words, dy, dx):
    """Entry 0 of the batch at (dy, dx), the seven others each at another
    offset: all eight equal the crop of their own bytes, float32 to the bit."""
    images = every_byte_images(8)
    at = SHIFTS.index((dy, dx))
    offsets = np.array([SHIFTS[(at + 10 * b) % len(SHIFTS)] for b in range(8)], np.int32)
    assert tuple(offsets[0]) == (dy, dx)
    got = shift_words(jnp.asarray(words_np(images)), jnp.asarray(offsets))
    assert got.dtype == jnp.float32 and got.shape == (8, *CROP.shape)
    np.testing.assert_array_equal(np.asarray(got), float_input(index_map_crop(images, offsets, PAD)))


def test_batch_entries_with_different_offsets_do_not_leak(shift_words):
    """All 81 offsets in one batch, and one image at one offset among 80 of
    another image at another: every entry is its own image at its own offset."""
    images = every_byte_images(81)
    offsets = np.array(SHIFTS, np.int32)
    got = np.asarray(shift_words(jnp.asarray(words_np(images)), jnp.asarray(offsets)))
    np.testing.assert_array_equal(got, float_input(index_map_crop(images, offsets, PAD)))
    lone = np.repeat(images[:1], 81, axis=0)
    lone[40] = images[7]
    offsets = np.tile(np.array([[8, 0]], np.int32), (81, 1))
    offsets[40] = (1, 6)
    got = np.asarray(shift_words(jnp.asarray(words_np(lone)), jnp.asarray(offsets)))
    np.testing.assert_array_equal(got, float_input(index_map_crop(lone, offsets, PAD)))
    np.testing.assert_array_equal(got[0], got[80])


@pytest.mark.parametrize("pad", [1, 3, 5, 6, 9])
def test_other_pads_move_rows_by_their_own_count_of_words(pad):
    """The kernel's padded row and its windows follow ceil(pad / 4): every
    offset of each pad, 16 images a batch."""
    images = every_byte_images(16)
    shifts = np.array([(dy, dx) for dy in range(2 * pad + 1) for dx in range(2 * pad + 1)], np.int32)
    run = jax.jit(lambda words, offsets: pix.random_shift(words.T, offsets, pad, CROP))
    for start in range(0, len(shifts), 16):
        offsets = shifts[(np.arange(16) + start) % len(shifts)]
        got = run(jnp.asarray(words_np(images)), jnp.asarray(offsets))
        np.testing.assert_array_equal(np.asarray(got), float_input(index_map_crop(images, offsets, pad)))


# --- what the lowered pixel chunk holds ---


def test_the_lowered_pixel_chunk_gathers_once_and_makes_no_bytes(monkeypatch):
    """`learner.chunk.uniform.pixels` (analysis/programs.py's spec: the chunk
    ShardedLearner builds, on 3 x 16 x 16 frames) lowered for the TPU from
    here, the crop kernel native as on the chip (interpreted, as this
    process's CPU would run it, a kernel is loops of slices): one gather,
    the ring's rows; no dynamic_slice and no gather on anything image-sized;
    no `bitcast_convert` to a byte type, which the TPU's compiler takes apart
    32 bits a pixel; each image's crop one `tpu_custom_call`, a data shard each."""
    from distributed_ddpg_tpu.analysis.programs import default_specs

    monkeypatch.setattr(pix, "random_shift", functools.partial(pix.random_shift, interpret=False))
    built = {spec.name: spec for spec in default_specs()}["learner.chunk.uniform.pixels"].build()
    text = built.fn.trace(*built.args).lower(lowering_platforms=("tpu",)).as_text()
    storage = built.args[2]
    gathers = [line for line in text.splitlines() if '"stablehlo.gather"(' in line]
    assert len(gathers) == 1 and f"(tensor<{storage.shape[0]}x{storage.shape[1]}xf32>" in gathers[0]
    sliced = re.findall(r"stablehlo\.dynamic_slice [^\n]*: \((tensor<[^>]*>)", text)
    assert all(re.fullmatch(r"tensor<\d+x[a-z]+\d+>", operand) for operand in sliced), sliced  # Adam's scalars
    converts = re.findall(r"stablehlo\.bitcast_convert [^\n]*-> tensor<([^>]*)>", text)
    assert converts and not [t for t in converts if re.search(r"x[us]?i8$", t)]
    assert len(re.findall(r"stablehlo\.custom_call @tpu_custom_call", text)) == 2  # an update's two images
    assert "shard_map" in text or "sdy.manual_computation" in text


# --- the encoder's block reaches the trunks as it lies: the source's flatten, written out ---


def written_out_trunk(trunk, block):
    """The source's hand-over: the block flattened channel-major against the
    stored weight, rows (c, h, w); LayerNorm at torch's eps; tanh."""
    y = block.reshape(block.shape[0], -1) @ trunk["w"] + trunk["b"]
    mean = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=-1, keepdims=True)
    return jnp.tanh((y - mean) / jnp.sqrt(var + 1e-5) * trunk["ln_scale"] + trunk["ln_shift"])


def written_out_policy(policy, images, scale, offset):
    x = images.astype(jnp.float32) / 255.0 - 0.5
    for layer, stride in zip(policy["encoder"], (2, 1, 1, 1)):
        x = jax.lax.conv_general_dilated(x, layer["w"], (stride, stride), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"))
        x = jax.nn.relu(x + layer["b"][None, :, None, None])
    h = written_out_trunk(policy["trunk"], x)
    for layer in policy["mlp"][:-1]:
        h = jax.nn.relu(h @ layer["w"] + layer["b"])
    return jnp.tanh(h @ policy["mlp"][-1]["w"] + policy["mlp"][-1]["b"]) * scale + offset


def close(got, want, rel=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=0, atol=rel * np.abs(want).max())


@pytest.mark.parametrize("b,c,s", [(4, 32, 35), (256, 32, 35), (3, 5, 7)])
def test_the_hand_over_is_the_sources_flatten_and_matmul(b, c, s):
    """trunk_apply on the encoder's block f32[B, C, S, S], against a STORED
    weight moved to the block's row order (block_rows), is flatten-and-matmul
    on the stored weight: the trunk's output, the gradient to the block and
    the gradient to the stored `w`, in float32 on the CPU, where only the
    order of a sum over C*S*S terms differs; and the move has an inverse."""
    f = 100 if c == 32 else 6
    rng = np.random.default_rng([b, c, s])
    block = jnp.asarray(rng.standard_normal((b, c, s, s)), jnp.float32)
    trunk = {
        "w": jnp.asarray(rng.standard_normal((c * s * s, f)) / np.sqrt(c * s * s), jnp.float32),
        "b": jnp.asarray(rng.standard_normal(f), jnp.float32),
        "ln_scale": jnp.asarray(1.0 + 0.1 * rng.standard_normal(f), jnp.float32),
        "ln_shift": jnp.asarray(0.1 * rng.standard_normal(f), jnp.float32),
    }
    weights = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)

    def ours(trunk, block):
        return pixnet.trunk_apply({**trunk, "w": pixnet.block_rows(trunk["w"], c)}, block)

    def outcome(apply):
        loss = lambda trunk, block: jnp.sum(weights * apply(trunk, block))
        (gtrunk, gblock) = jax.jit(jax.grad(loss, argnums=(0, 1)))(trunk, block)
        return jax.jit(apply)(trunk, block), gblock, gtrunk["w"]

    for got, want in zip(outcome(ours), outcome(written_out_trunk)):
        close(got, want)
    moved = pixnet.block_rows(trunk["w"], c)
    assert moved.shape == trunk["w"].shape and not np.array_equal(moved, trunk["w"])
    np.testing.assert_array_equal(pixnet.stored_rows(moved, c), trunk["w"])
    # row (h, w, c) of the moved weight is row (c, h, w) of the stored one
    np.testing.assert_array_equal(moved[(2 * s + 1) * c + 3], trunk["w"][3 * s * s + 2 * s + 1])


def test_the_encoders_relu_is_jax_nn_relu_with_its_mask_kept_as_bytes():
    """models/pixels._relu: jax.nn.relu's value and gradient bit for bit,
    zero, the signed zeros, the smallest and the largest floats among the
    inputs; what differs is what the forward pass keeps for the backward
    one: a boolean behind an optimization barrier (the compiler then stores
    the mask, a byte an activation, and no float32 pre-activation), where
    jax.nn.relu keeps its input."""
    x = jnp.asarray([-np.inf, -3.5, -1e-38, -0.0, 0.0, 1e-45, 1e-38, 2.25, 3e38, np.inf] * 3, jnp.float32).reshape(3, 10)
    g = jnp.asarray(np.random.default_rng(0).standard_normal(x.shape), jnp.float32)
    for ours, theirs in zip(jax.vjp(pixnet._relu, x), jax.vjp(jax.nn.relu, x)):
        got, want = (ours, theirs) if not callable(ours) else (ours(g)[0], theirs(g)[0])
        np.testing.assert_array_equal(np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32))
    text = jax.jit(jax.grad(lambda x: jnp.sum(g * pixnet._relu(x)))).lower(x).as_text()
    kept = re.findall(r"optimization_barrier[^\n]*tensor<3x10x(\w+)>", text)
    assert kept and set(kept) == {"i1"}, text


def digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.shape), str(a.dtype)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_the_seeded_leaves_are_the_parents_bit_for_bit():
    """critic_init / actor_init: every leaf's path, shape, dtype and bytes as
    the tree before PR 50 made them (the digests were taken from it), the
    trunks' `w` [C*S*S, F] with rows (c, h, w): what a checkpoint, the
    published policy and the benchmark's `init_gap` read. At the cell's own
    sizes the trunk's weight is f32[39200, 100], torch's orthogonal of
    [100, 39200] transposed."""
    critic = pixnet.critic_init(7, (9, 28, 28), 8, 16, (32, 32), 21)
    actor = pixnet.actor_init(7, critic["trunk"]["w"].shape[0], 16, (32, 32), 21)
    assert digest(critic) == "50a85a9352dbc2d278fb3bdd16942cffe5b06ca25b587847ba701f4bc85ea35f"
    assert digest(actor) == "78a216868f384ae70ca5fc0e472b8778c0684aae1cef09c1985d9577c46ac975"
    full = pixnet.trunk_init(3, 1, 32 * pixnet.feature_side(84) ** 2, 100)
    assert full["w"].shape == (39200, 100) and full["w"].dtype == jnp.float32
    np.testing.assert_array_equal(full["w"], pixnet.orthogonal(3, 1, 0, (100, 39200)).T)
    assert set(full) == {"w", "b", "ln_scale", "ln_shift"}


@pytest.mark.parametrize("batch", [1, 64])
def test_the_acting_policy_on_byte_frames_is_the_written_out_form(batch):
    """policy_apply on a STORED policy (the rollout program's, the
    evaluator's and the host's call): the parent's actions to float32
    rounding at any batch."""
    s = init_train_state(cfg(), OBS, ACT, 3)
    policy = pixnet.policy_params(s.critic_params, s.actor_params)
    images = jnp.asarray(np.random.default_rng(batch).integers(0, 256, (batch, *OBS.shape), dtype=np.uint8))
    got = jax.jit(lambda p, x: pixnet.policy_apply(p, x, 0.4, 0.1))(policy, images)
    close(got, written_out_policy(policy, images, 0.4, 0.1))
    assert np.ptp(np.asarray(got)) > 1e-3


def test_a_launch_moves_the_trunks_rows_once_and_ends_where_single_steps_end():
    """parallel/learner.scan_chunk reads `pixel_step.launch`: the state's
    trunk rows enter the block's order in front of the scan and leave it
    behind the scan. K updates that way end in the STORED state that K calls
    of pixel_step end in, each of which moves the rows both ways itself."""
    from distributed_ddpg_tpu.learner import chunk_noise, noise_base_key
    from distributed_ddpg_tpu.parallel.learner import scan_chunk
    from distributed_ddpg_tpu.types import Batch

    config, k, b = cfg(), 3, 8
    step = make_learner_step(config, 1.0, obs=OBS)
    enter, update, leave = step.launch
    state = init_train_state(config, OBS, ACT, 1)
    rng = np.random.default_rng(0)
    words = lambda: jnp.asarray(words_np(rng.integers(0, 256, (k * b, *OBS.shape), dtype=np.uint8)).reshape(k, b, -1).swapaxes(1, 2))
    batches = Batch(
        obs=words(), action=jnp.asarray(rng.uniform(-1, 1, (k, b, ACT)), jnp.float32),
        reward=jnp.asarray(rng.standard_normal((k, b)), jnp.float32), discount=jnp.full((k, b), 0.97, jnp.float32),
        next_obs=words(), weight=jnp.ones((k, b), jnp.float32),
    )
    noise = chunk_noise(config, noise_base_key(config), state.step, k, b, ACT)
    chunk = jax.jit(lambda s: scan_chunk(step, s, batches, noise, unroll=4))(state)
    single = state
    for i in range(k):
        single = jax.jit(step)(single, jax.tree.map(lambda x: x[i], batches), jax.tree.map(lambda x: x[i], noise)).state
    moved = chunk.state.critic_params["trunk"]["w"]
    assert not np.allclose(moved, state.critic_params["trunk"]["w"], atol=1e-6)  # it trained
    jax.tree.map(lambda got, want: close(got, want, 1e-4), chunk.state, single)
    # the moves are each other's inverse on every leaf they touch, and touch nothing else
    jax.tree.map(np.testing.assert_array_equal, leave(enter(state)), state)
    entered = enter(state)
    changed = [jax.tree_util.keystr(path) for (path, a), c in zip(jax.tree_util.tree_leaves_with_path(state), jax.tree.leaves(entered)) if not np.array_equal(a, c)]
    assert changed == [".actor_params['trunk']['w']", ".critic_params['trunk']['w']", ".target_critic_params['trunk']['w']"]  # the moments start at zero


# --- the crop is the source's grid_sample at integer shifts ---


def grid_sample(image, grid):
    """torch.nn.functional.grid_sample on one [C, H, W] image: bilinear,
    padding_mode zeros, align_corners False; grid [H', W', 2] of (x, y)."""
    c, h, w = image.shape
    x = ((grid[..., 0] + 1.0) * w - 1.0) / 2.0
    y = ((grid[..., 1] + 1.0) * h - 1.0) / 2.0
    x0, y0 = np.floor(x).astype(int), np.floor(y).astype(int)
    out = np.zeros((c, *x.shape))
    for dy in (0, 1):
        for dx in (0, 1):
            xi, yi = x0 + dx, y0 + dy
            wgt = (1 - np.abs(x - xi)) * (1 - np.abs(y - yi))
            inside = (xi >= 0) & (xi < w) & (yi >= 0) & (yi < h)
            out += np.where(inside, wgt, 0.0) * image[:, np.clip(yi, 0, h - 1), np.clip(xi, 0, w - 1)]
    return out


def source_shift(image, dy, dx, pad):
    """drqv2.py RandomShiftsAug for one image and one drawn shift."""
    c, h, w = image.shape
    padded = np.pad(image.astype(np.float64), ((0, 0), (pad, pad), (pad, pad)), mode="edge")
    eps = 1.0 / (h + 2 * pad)
    arange = np.linspace(-1.0 + eps, 1.0 - eps, h + 2 * pad)[:h]
    base = np.stack(np.meshgrid(arange, arange), axis=-1)  # [..., 0] runs along the width
    shift = np.array([dx, dy]) * 2.0 / (h + 2 * pad)
    return grid_sample(padded, base + shift)


@pytest.mark.parametrize("dy,dx", [(0, 0), (4, 4), (8, 8), (0, 8), (7, 2), (3, 5)])
def test_the_crop_is_the_sources_grid_sample_at_integer_shifts(dy, dx):
    pad, side = 4, 12
    image = np.random.default_rng(dy * 9 + dx).integers(0, 256, (3, side, side), dtype=np.uint8)
    want = source_shift(image, dy, dx, pad)
    rows = np.clip(np.arange(side) + dy - pad, 0, side - 1)
    cols = np.clip(np.arange(side) + dx - pad, 0, side - 1)
    np.testing.assert_allclose(want, image[:, rows][:, :, cols], atol=1e-9)  # a crop, to rounding
    got = pix.random_shift(jnp.asarray(words_np(image[None]).T), jnp.asarray([[dy, dx]]), pad, ObsSpec(image.shape, "uint8"))[0]
    np.testing.assert_allclose((np.asarray(got) + 0.5) * 255.0, want, atol=1e-4)
    assert got.dtype == jnp.float32 and float(jnp.max(jnp.abs(got))) <= 0.5


def test_no_padding_is_no_shift_and_the_schedule_is_the_sources_linear():
    image = (np.arange(2 * 3 * 4 * 4) * 5 % 256).astype(np.uint8).reshape(2, 3, 4, 4)
    unshifted = jax.jit(lambda words, offsets: pix.random_shift(words.T, offsets, 0, ObsSpec((3, 4, 4), "uint8")))
    np.testing.assert_array_equal(unshifted(jnp.asarray(words_np(image)), jnp.zeros((2, 2), jnp.int32)), float_input(image))
    # an update stands for two agent steps of the environment's action repeat
    assert pix.FRAMES_PER_UPDATE == 2 * PixelHumanoidStandIn.ACTION_REPEAT == 4
    for frames, want in ((0, 1.0), (1_000_000, 0.55), (2_000_000, 0.1), (5_000_000, 0.1)):
        assert float(pix.sigma_at((1.0, 0.1, 2_000_000.0), frames // 4)) == pytest.approx(want)


# --- the stand-in environment and the device pool on rows of this width ---


def test_frame_stack_repeats_the_first_frame_and_shifts_by_one_a_step():
    env = Truncating()
    s = env.init(jax.random.PRNGKey(0))
    assert s.frames.shape == OBS.shape and s.frames.dtype == jnp.uint8
    np.testing.assert_array_equal(s.frames[:3], s.frames[3:6])
    np.testing.assert_array_equal(s.frames[:3], s.frames[6:])
    step, key = jax.jit(env.step), jax.random.PRNGKey(1)
    seen = [np.asarray(s.frames)]
    for t in range(1, 5):
        key, k = jax.random.split(key)
        out = step(s, jnp.full((ACT,), 0.3), k)
        boot = np.asarray(out.boot_obs)
        np.testing.assert_array_equal(boot[:6], seen[-1][3:])  # the stack moved up by one frame
        assert not np.array_equal(boot[6:], boot[3:6])  # and the new frame differs from the last
        assert float(out.reward) > 1.0  # two sub-steps' alive bonuses: action repeat 2
        if t < 4:
            assert not bool(out.done)
            np.testing.assert_array_equal(out.obs, boot)
        else:  # the time limit: truncated, and the next observation is a fresh episode's
            assert bool(out.done) and not bool(out.terminated) and int(out.state.t) == 0
            fresh = np.asarray(out.obs)
            np.testing.assert_array_equal(fresh[:3], fresh[3:6])
            np.testing.assert_array_equal(fresh[:3], fresh[6:])
            assert not np.array_equal(fresh, boot)
        s = out.state
        seen.append(boot)
    a, b = (np.asarray(env.init(jax.random.PRNGKey(k)).frames) for k in (2, 3))
    assert np.mean(a != b) > 0.5  # environments differ


def pool_and_ring(config, mesh):
    pool = DeviceActorPool(config, mesh=mesh)
    s = init_train_state(config, OBS, ACT, config.seed)
    pool.set_params(pixnet.policy_params(s.critic_params, s.actor_params), 0)
    ring = DeviceReplay(config.replay_capacity, OBS.words, ACT, mesh=mesh, block_size=16, async_ship=False)
    return pool, ring


@pytest.mark.parametrize("env_cls", [Truncating, Brief])
def test_device_fold_on_pixel_rows_is_the_host_accumulators(monkeypatch, env_cls):
    """tests/test_device_nstep.py's comparison on rows of byte frames: two
    pools on one seed (one trajectory), the 1-step pool's rows pushed through
    replay/nstep.py as a worker would, against what the 3-step pool landed;
    observations compared as the bits they are."""
    monkeypatch.setitem(jax_envs._JAX_ENVS, PIXEL_STAND_IN_ID, env_cls)
    mesh, chunks, n, e_n, k_n, w = one_device_mesh(), 3, 3, 4, 2, OBS.words
    one, ring1 = pool_and_ring(cfg(n_step=1), mesh)
    three, ring3 = pool_and_ring(cfg(n_step=n), mesh)
    assert three.pending_rows == (n - 1) * e_n and ring3.width == packed_width(OBS, ACT)
    for _ in range(chunks + 1):
        one.run_chunk(ring1)
    for _ in range(chunks):
        three.run_chunk(ring3)
    steps = n - 1 + chunks * k_n
    flat = np.asarray(ring1.storage)[: steps * e_n].reshape(steps, e_n, -1)
    got = np.asarray(ring3.storage)[: chunks * k_n * e_n].reshape(chunks * k_n, e_n, -1)
    ends = 0
    for e in range(e_n):
        acc, want = NStepAccumulator(n, three.config.gamma), []
        for t in range(steps):
            row = flat[t, e]
            obs, action, reward = row[:w], row[w : w + ACT], row[w + ACT]
            boot = row[w + ACT + 2 : 2 * w + ACT + 2]
            terminated = row[w + ACT + 1] == 0.0
            truncated = not terminated and t + 1 < steps and not np.array_equal(bits(flat[t + 1, e, :w]), bits(boot))
            want += list(acc.push(obs[None], action[None], [reward], [terminated], boot[None]))
            if truncated:
                want += _flush_truncated(acc, boot)
            if terminated or truncated:
                acc.reset()
                ends += 1
        emitted = got[:, e]
        for t, (o, a, r, d, nobs) in enumerate(want[: len(emitted)]):
            np.testing.assert_array_equal(bits(emitted[t, :w]), bits(o))
            np.testing.assert_array_equal(emitted[t, w : w + ACT], a)
            np.testing.assert_allclose(emitted[t, w + ACT], r, rtol=1e-6)
            np.testing.assert_allclose(emitted[t, w + ACT + 1], d, rtol=1e-6)
            np.testing.assert_array_equal(bits(emitted[t, w + ACT + 2 : 2 * w + ACT + 2]), bits(nobs))
    assert ends >= 2  # episodes did end inside the rows compared
    # a frame stack as the policy sees it is the one the row holds
    first = np.ascontiguousarray(got[0, :, :w]).view(np.uint8).reshape(e_n, *OBS.shape)
    np.testing.assert_array_equal(first[:, :3], first[:, 3:6])  # step 0 of every episode: the first frame thrice


def test_rollout_noise_reads_the_learner_step_it_was_handed(monkeypatch):
    monkeypatch.setitem(jax_envs._JAX_ENVS, PIXEL_STAND_IN_ID, Small)
    pool, _ = pool_and_ring(cfg(n_step=1), one_device_mesh())
    assert int(pool._params["learner_step"]) == 0 and "encoder" in pool._params
    pool.set_params({k: v for k, v in pool._params.items() if k != "learner_step"}, 123)
    assert int(pool._params["learner_step"]) == 123 and pool.obs == OBS and pool.obs_dim == OBS.words


# --- the trees' partition rules ---


def test_state_pspec_places_every_leaf_of_the_pixel_state():
    s = init_train_state(cfg(), OBS, ACT, 0)
    assert s.target_actor_params is None and set(s.target_critic_params) == {"trunk", "heads"}
    assert s.critic_params["heads"][0]["w"].shape == (2, 16 + ACT, 32)
    assert s.critic_params["trunk"]["w"].shape == (8 * pixnet.feature_side(SIDE) ** 2, 16)
    for model in (1, 2):
        mesh = mesh_lib.make_mesh(data_axis=2, model_axis=model, devices=jax.devices()[: 2 * model])
        spec = state_pspec(s, mesh)
        is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)
        assert jax.tree.structure(spec, is_leaf=is_spec) == jax.tree.structure(jax.tree.map(lambda _: 0, s))
        enc = spec.critic_params["encoder"][0]["w"]
        assert all(ax is None for ax in enc) and all(ax is None for ax in spec.actor_params["trunk"]["w"])
    # hidden layers shard as every dense chain's do; the pair's leading axis replicates
    assert tuple(spec.critic_params["heads"][0]["w"]) == (None, None, "model")
    assert tuple(spec.actor_params["mlp"][1]["w"]) == ("model", None)
    assert spec.target_critic_params["heads"] == spec.critic_params["heads"]


# --- what is refused, and why ---

REFUSED = {
    "host workers": (dict(actor_backend="host", num_actors=2), "convolutional policy for\\s+host workers"),
    "prioritised replay": (dict(prioritized=True), "--prioritized.*R1"),
    "row-sharded replay": (dict(replay_sharding="sharded"), "--replay_sharding=sharded.*R7"),
    "host replay": (dict(host_replay=True), "--host_replay"),
    "the fused beat": (dict(fused_beat="on"), "--fused_beat=on.*D1"),
    "the superstep": (dict(superstep_beats=2), "superstep_beats"),
    "guardrails": (dict(guardrails=True), "--guardrails.*NaN"),
    "the megakernel": (dict(fused_chunk="on"), "--fused_chunk=on"),
    "bfloat16 compute": (dict(compute_dtype="bfloat16"), "compute_dtype='float32'"),
    "a delayed actor": (dict(policy_delay=2), "policy_delay at 1"),
    "a single critic": (dict(twin_critic=False), "twin_critic=True"),
    "the action at layer 1": (dict(action_insert_layer=1), "action_insert_layer=0"),
    "a schedule that is none": (dict(explore_sigma_schedule="0.3"), "initial,final,frames"),
    "a flat environment": (dict(env_id="IsaacHumanoidStandIn-v0"), "byte frames"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_each_refusal_names_the_flag_and_the_reason(what):
    changed, message = REFUSED[what]
    with pytest.raises(ValueError, match=f"(?s){message}"):
        cfg(**changed)


def test_byte_frames_without_the_pixel_learner_are_refused_too():
    with pytest.raises(ValueError, match="byte frames"):
        DDPGConfig(env_id=PIXEL_STAND_IN_ID, actor_backend="device", num_actors=0, twin_critic=True)
    from distributed_ddpg_tpu.ops import fused_chunk

    assert cfg().pixels and not fused_chunk.supported(cfg())


def test_a_pixel_step_without_the_images_spec_is_refused():
    """The step's batches hold words, which carry no shape."""
    with pytest.raises(ValueError, match="ring words.*ObsSpec"):
        make_learner_step(cfg(), 1.0)
    with pytest.raises(ValueError, match="whole words"):
        pix.random_shift(jnp.zeros((27, 8)), jnp.zeros((8, 2), jnp.int32), 4, ObsSpec((3, 6, 6), "uint8"))
