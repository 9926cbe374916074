"""DrQ-v2 (Yarats, Fergus, Lazaric and Pinto, "Mastering Visual Continuous
Control: Improved Data-Augmented Reinforcement Learning", ICLR 2022, arXiv
2107.09645; code facebookresearch/drqv2: `drqv2.py`, `cfgs/config.yaml`,
`cfgs/task/humanoid_walk.yaml` over `hard.yaml`), one update in plain float32 `jax.numpy`: DDPG from
pixels. It IS DDPG (a deterministic actor, clipped double Q, n-step returns,
Polyak targets) behind a convolutional encoder and an augmentation.

A row is [o words | action | R | d | o' words | w], float32: `o` and `o'` are
uint8[C, H, W] images (three stacked RGB frames: 9 x 84 x 84), four pixels to
a 32-bit word in memory order, which this file takes out again with an integer
bitcast and never computes on; R is the n-step discounted reward and d =
gamma^n * (not terminated), both folded by the actors and READ from the row.
For a batch of rows, update k (the learner's step count before it) is:

- augment: each image, `o` and `o'` independently, is padded by `aug_pad`
  pixels on every side by replicating its edge, and an H x W crop is taken at
  an offset (dy, dx) drawn uniformly from {0..2*aug_pad}^2, one draw an image,
  the same for all its channels. The source does this with a `grid_sample` of
  a base grid shifted by 2 * (dy, dx) / (H + 2 * aug_pad), `align_corners`
  false, which at integer shifts lands on pixel centres: it IS this crop.
  Written here as out[i, j] = image[clip(i + dy - pad), clip(j + dx - pad)],
  an index map and no padding, so it shares no code with the program's.
- encoder f: x = o / 255 - 0.5; four convolutions of `channels` outputs, 3 x 3,
  no padding, strides 2, 1, 1, 1, a relu behind each (84 -> 41 -> 39 -> 37 ->
  35); flattened channel-major: 39,200 features at 32 channels.
- critic: trunk h = tanh(LN(W_t f(o) + b_t)) (LayerNorm eps 1e-5), then two
  heads Q_i(h, a) = MLP_i([h | a]), relu, stacked on a leading axis of 2.
- actor: its own trunk of the same form on the DETACHED f(o), then an MLP,
  mu = tanh(.) onto the action box; a sampled action is clamp(mu + clip(sigma
  * eps, -c, c)) into the box shrunk by 1e-6, the clamp passing its gradient
  straight through (the source's TruncatedNormal).
- sigma_k = linear(initial, final, frames) at frames_per_update * k frames.
- critic loss: a' = pi(f(o')) with noise as above, y = R + d * min_i
  Qbar_i(f(o'), a'), f the ONLINE encoder under no gradient, Qbar the target
  trunk and heads; L = sum_i mean_b w (Q_i(f(o), a) - y)^2; its gradient moves
  encoder, critic trunk and both heads under ONE Adam.
- actor loss: -mean min_i Q_i(f(o)|, pi(f(o)|)), | = no gradient into the
  encoder; moves the actor's trunk and MLP only.
- targets: Polyak at tau on the critic's trunk and heads; the encoder has no
  target, and there is no target actor.

Randomness of update k: key = fold_in(PRNGKey(seed ^ 0xD2C), k), split three
ways: crop offsets int[B, 4] (dy, dx of `o`, then of `o'`), the target
action's normals, the actor loss's normals. `td`, per sample and signed, is
the mean over the two heads of y - Q_i. `encoder_grad_norm`, `explore_sigma`
and `aug_offset_mean` per update are the program's record keys of those names.

Departures from the source, each also under `assumed` in the configuration's
file; none is a width (channels, kernel, strides, `feature_dim`, hidden sizes
are the configuration's, and this file fixes none):
1. the flat transition row: the source stores one stacked observation a step
   and reads o' n steps on; here a row holds the whole folded transition, so a
   frame is stored twice and more (the harness hands a reference `storage[idx]`
   and nothing of a row's neighbours);
2. sigma from the LEARNER's step: the source reads its schedule at the agent's
   frame count; here update k reads it at frames_per_update * k frames (two
   agent steps an update, two frames a step);
3. initialisers both sides can make to the last bit: torch's `orthogonal_`
   (gain sqrt(2) on convolutions, 1 on dense layers, zero biases) written in
   numpy float64 on draws seeded by (seed, net, layer), cast to float32;
4. the environment is a stand-in (envs/jax_envs.py PixelHumanoidStandIn): no
   physics step, no rasteriser;
5. the program's convention, as in every reference here: the actor's loss goes
   through the critic as it stood BEFORE this update, where the source's critic
   has already taken its step (the features are the pre-update encoder's in
   both).
PAPERS.md holds what this tree knows of the source's settings, line by line.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as c
from .d4pg import products  # dense products with operands rounded by lax.reduce_precision

STRIDES, KERNEL, LN_EPS = (2, 1, 1, 1), 3, 1e-5
DIMS = ("NCHW", "OIHW", "NCHW")
ENCODER, CRITIC_TRUNK, ACTOR_TRUNK, ACTOR, HEAD = range(5)  # streams of seeded draws


def side_after(side):
    for stride in STRIDES:
        side = (side - KERNEL) // stride + 1
    return side


def orthogonal(seed, net, layer, shape, gain=1.0):
    """torch.nn.init.orthogonal_ for a weight (out, in, ...), in float64."""
    rows, cols = shape[0], int(np.prod(shape[1:]))
    flat = np.random.default_rng([int(seed), net, layer]).standard_normal((rows, cols))
    wide = rows < cols
    q, r = np.linalg.qr(flat.T if wide else flat)
    q = q * np.sign(np.diag(r))
    return (gain * (q.T if wide else q)).reshape(shape).astype(np.float32)


def dense(seed, net, layer, fan_in, fan_out):
    return {"w": jnp.asarray(orthogonal(seed, net, layer, (fan_out, fan_in)).T), "b": jnp.zeros((fan_out,), jnp.float32)}


def trunk(seed, net, features, width):
    return {**dense(seed, net, 0, features, width),
            "ln_scale": jnp.ones((width,), jnp.float32), "ln_shift": jnp.zeros((width,), jnp.float32)}


def chain(seed, net, dims):
    return tuple(dense(seed, net, i, a, b) for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])))


def init(seed, env, hp):
    ch, fd, act = hp["channels"], hp["feature_dim"], env["act_dim"]
    c_in, _, side = env["obs_shape"]
    features = ch * side_after(side) ** 2
    ins = [c_in] + [ch] * (len(STRIDES) - 1)
    encoder = tuple(
        {"w": jnp.asarray(orthogonal(seed, ENCODER, i, (ch, n, KERNEL, KERNEL), math.sqrt(2.0))),
         "b": jnp.zeros((ch,), jnp.float32)}
        for i, n in enumerate(ins)
    )
    heads = [chain(seed, HEAD + i, [fd + act, *hp["hidden"], 1]) for i in range(2)]
    critic = {
        "encoder": encoder,
        "trunk": trunk(seed, CRITIC_TRUNK, features, fd),
        "heads": jax.tree.map(lambda a, b: jnp.stack([a, b]), *heads),
    }
    actor = {"trunk": trunk(seed, ACTOR_TRUNK, features, fd), "mlp": chain(seed, ACTOR, [fd, *hp["hidden"], act])}
    return {
        "actor": actor,
        "critic": critic,
        "target_critic": {"trunk": critic["trunk"], "heads": critic["heads"]},
        "actor_opt": c.adam_init(actor),
        "critic_opt": c.adam_init(critic),
        "step": jnp.zeros((), jnp.int32),
        # carried in the state so that one compiled reference serves every seed
        "noise_key": jax.random.PRNGKey(seed ^ 0xD2C),
    }


def encoder_macs(env, hp):
    """(multiply-adds of the encoder on one image, those of its first layer)."""
    c_in, _, side = env["obs_shape"]
    ch, total, first = hp["channels"], 0, 0
    for i, stride in enumerate(STRIDES):
        side = (side - KERNEL) // stride + 1
        macs = side * side * ch * (c_in if i == 0 else ch) * KERNEL * KERNEL
        total, first = total + macs, first or macs
    return total, first


def work(env, hp):
    """{"flops", "row_bytes", "state_bytes", "encoder_flops"} of one update
    (common.py's conventions: 2 * multiply-adds of products and convolutions
    only, a backward pass as two forwards, each pass once however it is
    scheduled; the state read and written once a launch).
    - encoder, M multiply-adds an image, M_1 of them its first layer: B images
      of `o` forward, weight gradients and input gradients but the first
      layer's (3 M - M_1), B of `o'` forward (M): `encoder_flops`;
    - trunks, T = features * feature_dim: the critic's on f(o) forward and
      both gradients (3; the input's feeds the encoder), the target's on f(o')
      (1), the actor's on f(o') for a' (1) and on the detached f(o) forward and
      weight gradient (2): 7 T. The critic's trunk under the actor's loss is
      the pass already counted;
    - each head, S_h its layers' products and S'_h those behind the first: the
      target's forward (S_h); under the critic's loss forward, weight
      gradients, input gradients behind the first layer and the first's
      towards h (2 S_h + S'_h + feature_dim * width); under the actor's loss
      forward and the gradient back to the action (S_h + S'_h + act * width);
    - the actor's MLP, S_a: forward for a' (1), and forward and both gradients
      under its own loss (3)."""
    batch, act, fd = hp["batch_size"], env["act_dim"], hp["feature_dim"]
    m, m1 = encoder_macs(env, hp)
    features = hp["channels"] * side_after(env["obs_shape"][-1]) ** 2
    head = list(zip([fd + act, *hp["hidden"]], [*hp["hidden"], 1]))
    mlp = list(zip([fd, *hp["hidden"]], [*hp["hidden"], act]))
    s_h, t_h = sum(i * o for i, o in head), sum(i * o for i, o in head[1:])
    s_a = sum(i * o for i, o in mlp)
    width = head[0][1]
    heads = 2 * (s_h + (2 * s_h + t_h + fd * width) + (s_h + t_h + act * width))
    encoder = 2.0 * batch * (4 * m - m1)
    trunk_values = features * fd + 3 * fd
    ch = hp["channels"]
    encoder_values = sum(ch * n * KERNEL * KERNEL + ch for n in [env["obs_shape"][0]] + [ch] * (len(STRIDES) - 1))
    with_target = trunk_values + 2 * sum(i * o + o for i, o in head)
    trained = encoder_values + with_target + trunk_values + sum(i * o + o for i, o in mlp)
    words = 2 * int(np.prod(env["obs_shape"])) // 4 + act + 3
    return {
        "flops": encoder + 2.0 * batch * (7 * features * fd + heads + 4 * s_a),
        "row_bytes": 4.0 * batch * words,
        # trained values with both Adam moments, targets alone: read and written once each
        "state_bytes": 2.0 * 4 * (3 * trained + with_target),
        "encoder_flops": encoder,
    }


def convolution(operand_dtype=None):
    """conv(x, w, stride) at Precision.HIGHEST; with `operand_dtype`, both
    operands of the forward convolution AND of both gradients' (the cotangent
    with the saved operand) rounded to it first, as `products` rounds a dense
    product's."""

    def exact(x, w, stride):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "VALID", dimension_numbers=DIMS, precision=c.HIGHEST)

    if operand_dtype is None:
        return exact
    info = jnp.finfo(jnp.dtype(operand_dtype))

    def r(x):
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def conv(x, w, stride):
        return exact(r(x), r(w), stride)

    def backward(stride, saved, g):
        x, w = saved
        g = r(g)  # linear maps: where they are linearised does not matter
        dx = jax.vjp(lambda x_: exact(x_, r(w), stride), x)[1](g)[0]
        dw = jax.vjp(lambda w_: exact(r(x), w_, stride), w)[1](g)[0]
        return dx, dw

    conv.defvjp(lambda x, w, stride: (exact(r(x), r(w), stride), (x, w)), backward)
    return conv


def images(words, shape):
    """f32[..., words] -> uint8[..., C, H, W]: the bytes the words hold."""
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(*words.shape[:-1], *shape)


def unpack(rows, env):
    shape, a = tuple(env["obs_shape"]), env["act_dim"]
    o = int(np.prod(shape)) // 4
    return {
        "obs": images(rows[..., :o], shape),
        "action": rows[..., o : o + a],
        "reward": rows[..., o + a],
        "discount": rows[..., o + a + 1],
        "next_obs": images(rows[..., o + a + 2 : 2 * o + a + 2], shape),
        "weight": rows[..., 2 * o + a + 2],
    }


def shifted(image, offsets, pad):
    """The random shift as an index map: uint8[B, C, H, W], int[B, 2] -> the
    encoder's input f32[B, C, H, W]."""
    h, w = image.shape[-2:]
    rows = jnp.clip(jnp.arange(h)[None, :] + offsets[:, :1] - pad, 0, h - 1)  # [B, H]
    cols = jnp.clip(jnp.arange(w)[None, :] + offsets[:, 1:] - pad, 0, w - 1)  # [B, W]
    crop = jax.vmap(lambda im, r, cc: im[:, r][:, :, cc])(image, rows, cols)
    return crop.astype(jnp.float32) / 255.0 - 0.5


def draw_offsets(key, batch, pad):
    """One (dy, dx) an image, `o` then `o'`: int[B, 4] uniform in 0..2*pad."""
    return jax.random.randint(key, (batch, 4), 0, 2 * pad + 1)


def encoder_for_targets(s):
    """The encoder under the target's Q: the ONLINE one (there is no other)."""
    return s["critic"]["encoder"]


def features_for_actor(f):
    """What the actor's loss sees of the encoder's features: no gradient."""
    return jax.lax.stop_gradient(f)


def sigma_of(hp, step):
    init_, final, frames = hp["sigma_schedule"]
    mix = jnp.clip(hp["frames_per_update"] * step.astype(jnp.float32) / frames, 0.0, 1.0)
    return (1.0 - mix) * init_ + mix * final


def make_step(seed, env, hp, operand_dtype=None):
    mm, conv = products(operand_dtype), convolution(operand_dtype)
    scale = jnp.asarray(env["action_scale"], jnp.float32)
    offset = jnp.asarray(env["action_offset"], jnp.float32)
    low, high = offset - scale, offset + scale

    def encode(encoder, x):
        for layer, stride in zip(encoder, STRIDES):
            x = jax.nn.relu(conv(x, layer["w"], stride) + layer["b"][None, :, None, None])
        return x.reshape(x.shape[0], -1)

    def through(t, f):
        x = mm(f, t["w"]) + t["b"]
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return jnp.tanh((x - mean) / jnp.sqrt(var + LN_EPS) * t["ln_scale"] + t["ln_shift"])

    def policy(actor, f):
        return jnp.tanh(c.mlp_body(mm, actor["mlp"], through(actor["trunk"], f))) * scale + offset

    def sample(mu, noise):
        x = mu + noise
        edge = 1e-6 * scale
        return x + jax.lax.stop_gradient(jnp.clip(x, low + edge, high - edge) - x)

    def q(critic, f, action):
        x = jnp.concatenate([through(critic["trunk"], f), action], axis=-1)
        return jax.vmap(lambda head: c.mlp_body(mm, head, x)[..., 0])(critic["heads"])  # [2, B]

    def step(s, rows):
        b = unpack(rows, env)
        batch, act = b["action"].shape
        k_off, k_next, k_cur = jax.random.split(jax.random.fold_in(s["noise_key"], s["step"]), 3)
        offsets = draw_offsets(k_off, batch, hp["aug_pad"])
        sigma, clip = sigma_of(hp, s["step"]), hp["noise_clip"]
        noise_next, noise_cur = (
            jnp.clip(sigma * jax.random.normal(k, (batch, act)), -clip, clip) for k in (k_next, k_cur))
        x = shifted(b["obs"], offsets[:, :2], hp["aug_pad"])
        x_next = shifted(b["next_obs"], offsets[:, 2:], hp["aug_pad"])
        f_next = jax.lax.stop_gradient(encode(encoder_for_targets(s), x_next))
        next_q = q(s["target_critic"], f_next, sample(policy(s["actor"], f_next), noise_next))
        y = jax.lax.stop_gradient(b["reward"] + b["discount"] * jnp.min(next_q, axis=0))

        def critic_loss(cp):
            f = encode(cp["encoder"], x)
            td = y[None, :] - q(cp, f, b["action"])
            return jnp.sum(jnp.mean(b["weight"][None, :] * jnp.square(td), axis=1)), jnp.mean(td, axis=0)

        (closs, td), cgrad = jax.value_and_grad(critic_loss, has_aux=True)(s["critic"])

        def actor_loss(ap, encoder):  # through the critic as it stood before this update
            f = features_for_actor(encode(encoder, x))
            return -jnp.mean(jnp.min(q(s["critic"], f, sample(policy(ap, f), noise_cur)), axis=0))

        # The encoder's share of the actor's gradient is zero, by
        # features_for_actor; it is added all the same, so that whatever let
        # a gradient through there would move the encoder here too.
        aloss, (agrad, stray) = jax.value_and_grad(actor_loss, argnums=(0, 1))(s["actor"], s["critic"]["encoder"])
        cgrad = {**cgrad, "encoder": jax.tree.map(jnp.add, cgrad["encoder"], stray)}
        critic, critic_opt = c.adam(s["critic"], cgrad, s["critic_opt"], hp["critic_lr"])
        actor, actor_opt = c.adam(s["actor"], agrad, s["actor_opt"], hp["actor_lr"])
        target = c.polyak({"trunk": critic["trunk"], "heads": critic["heads"]}, s["target_critic"], hp["tau"])
        new = {
            "actor": actor, "critic": critic, "target_critic": target, "actor_opt": actor_opt,
            "critic_opt": critic_opt, "step": s["step"] + 1, "noise_key": s["noise_key"],
        }
        return new, {
            "td": td,
            "critic_loss": closs,
            "actor_loss": aloss,
            "critic_grad_norm": c.tree_norm(cgrad),
            "actor_grad_norm": c.tree_norm(agrad),
            "twin_gap": jnp.mean(jnp.abs(next_q[0] - next_q[1])),
            "encoder_grad_norm": c.tree_norm(cgrad["encoder"]),
            "explore_sigma": sigma,
            "aug_offset_mean": jnp.mean(offsets.astype(jnp.float32)),
        }

    return step
