"""DrQ-v2's nets (Yarats et al. 2022, arXiv 2107.09645): a convolutional
encoder over stacked byte frames, and the actor and the twin critics behind
it, as plain functional pytrees like models/mlp.py's.

- encoder f: four 3x3 convolutions of `channels` outputs, no padding,
  strides 2, 1, 1, 1, a relu behind each (_relu: its mask kept as bytes for
  the backward pass), on x = image / 255 - 0.5
  (ops/pixels.random_shift hands it that); the output is the last
  convolution's block f32[B, channels, side, side], `channels * side**2`
  features an image (84 -> 41 -> 39 -> 37 -> 35: 39,200 at 32).
- trunk: tanh(LayerNorm(W f + b)), `feature_dim` wide, f the block's
  features. The critics have one, the actor its own. `w` is STORED
  f32[channels * side**2, feature_dim] with its rows in (c, h, w) order, the
  source's flatten; a trunk READS it with its rows in (h, w, c) order
  (block_rows), the order the block holds its features in on the TPU (the
  convolutions run batch-minor, channels next: f32[B, C, S, S] laid
  {0,1,3,2}), so that the block reaches the product as a view and its
  gradient returns as one. Who holds a stored tree moves the rows first:
  policy_apply a call, the learner's update once a launch
  (learner.pixel_step).
- critic: {"encoder", "trunk", "heads"}: the encoder lives in the CRITIC's
  tree, because the critic's loss alone trains it and one Adam moves all
  three; `heads` is an MLP on [h | action] whose leaves carry a leading axis
  of 2 (the twin pair, as learner.init_train_state stacks every ensemble).
  The target tree is {"trunk", "heads"}: the encoder has no target.
- actor: {"trunk", "mlp"}: its trunk reads the encoder's features DETACHED,
  so the actor's tree holds no encoder. The policy that leaves the learner
  (`policy_params`) is encoder + actor.

Initialisers are the source's: orthogonal matrices (gain sqrt(2) on the
convolutions, 1 on dense layers), zero biases, LayerNorm at 1 and 0. They are
made on the HOST, in numpy, from draws seeded by (seed, net, layer): the
benchmark's reference makes its own the same way and the two must agree to
the last bit, which a QR on the device would not promise.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributed_ddpg_tpu.models.mlp import _layer_norm

STRIDES = (2, 1, 1, 1)
KERNEL = 3
LN_EPS = 1e-5  # torch.nn.LayerNorm's default, the source's
_DIMS = ("NCHW", "OIHW", "NCHW")
# `net` of the seed triple: one stream of draws a net, a layer at a time.
_ENCODER, _CRITIC_TRUNK, _ACTOR_TRUNK, _ACTOR, _HEAD = range(5)


def orthogonal(seed: int, net: int, layer: int, shape, gain: float = 1.0):
    """torch.nn.init.orthogonal_ on a weight of `shape` = (out, in, ...), in
    numpy float64, returned float32: the weight flattened to [out, rest]; a
    normal draw of that shape (transposed where it is wide); its QR's Q with
    each column's sign made R's diagonal's; transposed back; times `gain`."""
    rows, cols = shape[0], int(np.prod(shape[1:]))
    rng = np.random.default_rng([int(seed), net, layer])
    flat = rng.standard_normal((rows, cols))
    if rows < cols:
        flat = flat.T
    q, r = np.linalg.qr(flat)
    q = q * np.sign(np.diag(r))
    if rows < cols:
        q = q.T
    return (gain * q).reshape(shape).astype(np.float32)


def feature_side(side: int) -> int:
    """The encoder's output side for a square image of `side` pixels."""
    for stride in STRIDES:
        side = (side - KERNEL) // stride + 1
    return side


def _dense(seed, net, layer, fan_in, fan_out):
    # held [in, out] like every dense layer of this tree: torch's is [out, in]
    return {
        "w": jnp.asarray(orthogonal(seed, net, layer, (fan_out, fan_in)).T),
        "b": jnp.zeros((fan_out,), jnp.float32),
    }


def encoder_init(seed: int, in_channels: int, channels: int):
    ins = (in_channels,) + (channels,) * (len(STRIDES) - 1)
    return tuple(
        {
            "w": jnp.asarray(orthogonal(
                seed, _ENCODER, i, (channels, c_in, KERNEL, KERNEL), math.sqrt(2.0)
            )),
            "b": jnp.zeros((channels,), jnp.float32),
        }
        for i, c_in in enumerate(ins)
    )


def trunk_init(seed: int, net: int, features: int, feature_dim: int):
    return {
        **_dense(seed, net, 0, features, feature_dim),
        "ln_scale": jnp.ones((feature_dim,), jnp.float32),
        "ln_shift": jnp.zeros((feature_dim,), jnp.float32),
    }


def _mlp_init(seed, net, dims):
    return tuple(
        _dense(seed, net, i, dims[i], dims[i + 1]) for i in range(len(dims) - 1)
    )


def actor_init(seed: int, features: int, feature_dim: int, hidden: Sequence[int], act_dim: int):
    return {
        "trunk": trunk_init(seed, _ACTOR_TRUNK, features, feature_dim),
        "mlp": _mlp_init(seed, _ACTOR, [feature_dim, *hidden, act_dim]),
    }


def critic_init(seed: int, obs_shape, channels: int, feature_dim: int,
                hidden: Sequence[int], act_dim: int):
    features = channels * feature_side(obs_shape[-1]) ** 2
    heads = [  # the twin pair
        _mlp_init(seed, _HEAD + i, [feature_dim + act_dim, *hidden, 1])
        for i in range(2)
    ]
    return {
        "encoder": encoder_init(seed, obs_shape[0], channels),
        "trunk": trunk_init(seed, _CRITIC_TRUNK, features, feature_dim),
        "heads": jax.tree.map(lambda *members: jnp.stack(members), *heads),
    }


def is_pixel(params) -> bool:
    """Whether `params` is one of this file's trees: a dict with a `trunk`
    at its root (a recurrent net, models/recurrent.py, is a dict without
    one; every other net of this package is a tuple of layers)."""
    return isinstance(params, dict) and "trunk" in params


def trained_with_target(critic):
    """The part of a critic's tree that has a target: trunk and heads."""
    return {"trunk": critic["trunk"], "heads": critic["heads"]}


def policy_params(critic, actor):
    """What acts: the encoder (the critic's, live) in front of the actor."""
    return {"encoder": critic["encoder"], **actor}


def encoder_input(images):
    """Byte images -> what the first convolution reads: x / 255 - 0.5."""
    return images.astype(jnp.float32) / 255.0 - 0.5


@jax.custom_vjp
def _relu(x):
    """max(x, 0), value and gradient jax.nn.relu's, whose backward pass reads
    a MASK the forward pass kept: a byte an activation. jax.nn.relu keeps x
    itself for it, and a convolution then writes its float32 pre-activation
    beside the bfloat16 output the next layer reads, four bytes more an
    activation forward and four read backward by EVERY fusion the compiler
    folds the select into. The update is bound by HBM traffic: on the chip
    the four layers' masks took the launch from 102.9 to 94.4 ms, and to
    82.0 with the block handed to the trunks as it lies (PERF.md §6, PR 50).
    The barrier is what keeps the mask: without it the compiler recomputes
    `x > 0` from a kept x wherever the mask is read."""
    return jnp.maximum(x, 0.0)


_relu.defvjp(
    lambda x: (jnp.maximum(x, 0.0), jax.lax.optimization_barrier(x > 0)),
    lambda on, g: (jnp.where(on, g, 0.0),),
)


def encoder_apply(encoder, x):
    """x f32[B, C, H, W] in [-0.5, 0.5] -> the feature block f32[B, channels,
    side, side], as the last convolution leaves it."""
    for layer, stride in zip(encoder, STRIDES):
        x = jax.lax.conv_general_dilated(
            x, layer["w"], (stride, stride), "VALID", dimension_numbers=_DIMS
        )
        x = _relu(x + layer["b"][None, :, None, None])
    return x


def block_rows(w, channels: int):
    """A trunk's stored `w`, rows (c, h, w), with its rows in the block's
    order (h, w, c): a permutation of rows, the features stay where they are."""
    return w.reshape(channels, -1, w.shape[-1]).swapaxes(0, 1).reshape(w.shape)


def stored_rows(w, channels: int):
    """block_rows' inverse: rows (h, w, c) back in the stored order."""
    return w.reshape(-1, channels, w.shape[-1]).swapaxes(0, 1).reshape(w.shape)


def with_trunk_rows(tree, move, channels: int):
    """`tree` (a net with a trunk, or Adam's moments of one) with the trunk's
    `w` passed through `move` (block_rows or stored_rows)."""
    trunk = tree["trunk"]
    return {**tree, "trunk": {**trunk, "w": move(trunk["w"], channels)}}


def trunk_apply(trunk, block):
    """tanh(LayerNorm(W f + b)) on the encoder's block f32[B, C, S, S];
    `trunk["w"]` with its rows in the block's order (block_rows). On the TPU
    the transposed view is the block as it lies, and the product a plain
    matmul over all 39,200 features with no relayout in front of it or, for
    the block's gradient, behind it."""
    features = block.transpose(0, 2, 3, 1).reshape(block.shape[0], -1)
    return jnp.tanh(_layer_norm(features @ trunk["w"] + trunk["b"], trunk, LN_EPS))


def _mlp(params, x):
    for layer in params[:-1]:
        x = jax.nn.relu(x @ layer["w"] + layer["b"])
    return x @ params[-1]["w"] + params[-1]["b"]


def actor_apply(actor, block, action_scale, action_offset=0.0):
    """mu on the encoder's block: tanh onto the action box. `actor` is the
    actor's tree or `policy_params` (the encoder in it is the caller's to
    apply), its trunk's rows in the block's order."""
    mu = jnp.tanh(_mlp(actor["mlp"], trunk_apply(actor["trunk"], block)))
    return mu * action_scale + action_offset


def critic_apply(critic, block, action):
    """Q_i(block, action) of every head: [ensemble, B]. `critic` holds
    `trunk` and `heads` (the online tree, or a target), its trunk's rows in
    the block's order."""
    x = jnp.concatenate([trunk_apply(critic["trunk"], block), action], axis=-1)
    return jax.vmap(lambda head: _mlp(head, x)[..., 0])(critic["heads"])


def policy_apply(policy, images, action_scale, action_offset=0.0):
    """The acting policy (stored: policy_params of a state between
    launches) on byte frames uint8[B, C, H, W], no augmentation: what the
    rollout program, the evaluator and the host's copy call."""
    block = encoder_apply(policy["encoder"], encoder_input(images))
    policy = with_trunk_rows(policy, block_rows, block.shape[1])
    return actor_apply(policy, block, action_scale, action_offset)
