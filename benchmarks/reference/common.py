"""What the plain references share: MLPs, Adam, Polyak, the seeded initial
weights and the replay index draw, in float32 `jax.numpy` with every matrix
product at `Precision.HIGHEST` (on a TPU a float32 product otherwise runs
in bfloat16 passes). Imports nothing of the program under test.

Conventions shared with the program, and only these: how `jax.random` keys
are derived from the seed (so that both start from the same weights, draw
the same replay rows and the same policy noise), and the packed row layout
[obs | action | reward | discount | next_obs | weight]. The initial weights
are made here from the seed; the harness checks that the program's are
equal to the last bit.
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FINAL_INIT = 3e-3  # DDPG paper §7: final layers ~ U(-3e-3, 3e-3)
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8  # Kingma & Ba 2015 defaults


def products(operand_dtype=None):
    """The matrix product every layer uses. `operand_dtype` None is the
    reference itself: float32 operands, Precision.HIGHEST. A dtype (the
    control's: the next precision below what a configuration computes in)
    rounds both operands of every product to it first, those of the backward
    pass too (the cotangent with the saved operand, as a chip's default
    precision rounds them), and multiplies the rounded values exactly,
    accumulating in float32."""
    if operand_dtype is None:
        return lambda x, w: jnp.dot(x, w, precision=HIGHEST)
    dt = jnp.dtype(operand_dtype)

    def dot(a, b):
        return jnp.dot(a.astype(dt).astype(jnp.float32), b.astype(dt).astype(jnp.float32), precision=HIGHEST)

    @jax.custom_vjp
    def mm(x, w):  # x [B, in], w [in, out]
        return dot(x, w)

    mm.defvjp(lambda x, w: (dot(x, w), (x, w)), lambda xw, g: (dot(g, xw[1].T), dot(xw[0].T, g)))
    return mm


def linear_init(key, fan_in, fan_out, final):
    """DDPG paper §7: hidden layers U(+-1/sqrt(fan_in)), final U(+-3e-3)."""
    bound = FINAL_INIT if final else 1.0 / math.sqrt(fan_in)
    kw, kb = jax.random.split(key)
    return {
        "w": jax.random.uniform(kw, (fan_in, fan_out), jnp.float32, -bound, bound),
        "b": jax.random.uniform(kb, (fan_out,), jnp.float32, -bound, bound),
    }


def actor_init(key, obs_dim, out_dim, hidden):
    dims = [obs_dim, *hidden, out_dim]
    keys = jax.random.split(key, len(dims) - 1)
    return tuple(
        linear_init(keys[i], dims[i], dims[i + 1], i == len(dims) - 2)
        for i in range(len(dims) - 1)
    )


def critic_init(key, obs_dim, act_dim, hidden):
    """Q(s, a) with the action joining at the second layer (DDPG paper §7)."""
    dims = [obs_dim, *hidden, 1]
    keys = jax.random.split(key, len(dims) - 1)
    return tuple(
        linear_init(
            keys[i], dims[i] + (act_dim if i == 1 else 0), dims[i + 1],
            i == len(dims) - 2,
        )
        for i in range(len(dims) - 1)
    )


def mlp_body(mm, params, x):
    """relu hiddens, linear last layer."""
    for layer in params[:-1]:
        x = jax.nn.relu(mm(x, layer["w"]) + layer["b"])
    return mm(x, params[-1]["w"]) + params[-1]["b"]


def critic_apply(mm, params, obs, action):
    x = jax.nn.relu(mm(obs, params[0]["w"]) + params[0]["b"])
    x = jnp.concatenate([x, action], axis=-1)
    for layer in params[1:-1]:
        x = jax.nn.relu(mm(x, layer["w"]) + layer["b"])
    return (mm(x, params[-1]["w"]) + params[-1]["b"])[..., 0]


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros, "count": jnp.zeros((), jnp.int32)}


def adam(params, grads, opt, lr):
    count = opt["count"] + 1
    c = count.astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, opt["nu"], grads)
    new = jax.tree.map(
        lambda p, m, v: p
        - lr * (m / (1 - ADAM_B1**c)) / (jnp.sqrt(v / (1 - ADAM_B2**c)) + ADAM_EPS),
        params, mu, nu,
    )
    return new, {"mu": mu, "nu": nu, "count": count}


def polyak(online, target, tau):
    return jax.tree.map(lambda o, t: tau * o + (1 - tau) * t, online, target)


def tree_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def unpack(rows, obs_dim, act_dim):
    o, a = obs_dim, act_dim
    return {
        "obs": rows[..., :o],
        "action": rows[..., o : o + a],
        "reward": rows[..., o + a],
        "discount": rows[..., o + a + 1],
        "next_obs": rows[..., o + a + 2 : 2 * o + a + 2],
        "weight": rows[..., 2 * o + a + 2],
    }


def draw_indices(key, chunk, batch, size):
    """The replay draw of one chunk: uniform with replacement over the
    `size` rows the ring holds. Returns (next key, int32[chunk, batch])."""
    key, sub = jax.random.split(key)
    return key, jax.random.randint(sub, (chunk, batch), 0, jnp.maximum(size, 1))


# --- operations and bytes, from the shapes alone (read by chunk_roofline) ---
#
# FLOPs: copied from bench.py:flops_per_grad_step (matmul FLOPs only,
# forward = 2 * B * sum(in * out) per net; elementwise work excluded). A
# backward pass counts as two forwards. Recomputed operations do not count:
# each pass is counted once however the program schedules it.
#
# Bytes: what one launch of K updates cannot avoid moving to and from HBM:
# parameters, both Adam moments and the targets of every net read once and
# written once per launch (float32; between the updates of one launch they
# may stay on chip, as the megakernel keeps them), plus each update's batch
# rows read once. Counting the state once per update instead would charge
# the algorithm for one schedule's traffic, and reads over 100% at rates the
# chip has shown (190k updates/s x 4.6 MB > 819 GB/s).


def net_dims(obs, act, hidden, out, action_at_layer_1):
    """(fan in, fan out) of each layer of an MLP as `actor_init` and
    `critic_init` build them."""
    ins = [obs] + list(hidden)
    if action_at_layer_1:
        ins[1] += act
    return list(zip(ins, list(hidden) + [out]))


def work(env, hp, actor_out, n_critics, actor_passes, critic_passes):
    """{"flops", "row_bytes", "state_bytes"} of an actor-critic update with
    `n_critics` critics: `actor_passes` forward-equivalents through the
    actor and `critic_passes` through each critic."""
    obs, act, batch = env["obs_dim"], env["act_dim"], hp["batch_size"]
    actor = net_dims(obs, act, hp["hidden"], actor_out, False)
    critic = net_dims(obs, act, hp["hidden"], 1, True)
    f_actor = 2.0 * batch * sum(i * o for i, o in actor)
    f_critic = 2.0 * batch * sum(i * o for i, o in critic)
    values = sum(i * o + o for i, o in actor) + n_critics * sum(i * o + o for i, o in critic)
    return {
        "flops": actor_passes * f_actor + critic_passes * n_critics * f_critic,
        "row_bytes": 4.0 * batch * (2 * obs + act + 3),
        # params, mu, nu, target: read and written once each, 4 bytes a value
        "state_bytes": 2.0 * 4 * 4 * values,
    }
