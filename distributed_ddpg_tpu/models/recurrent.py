"""Recurrent actor and twin critic, each with a memory of its own
(config.recurrent: Ni, Eysenbach and Salakhutdinov 2022, "Recurrent
Model-Free RL Can Be a Strong Baseline for Many POMDPs", arXiv 2110.05038,
code twni2016/pomdp-baselines; the separate-memory TD3 of its "standard
POMDP" settings. PAPERS.md holds what this tree knows of them).

A net is a dict of plain {"w", "b"} layers, the third net form beside
models/mlp.py's chains and models/pixels.py's encoder:

  embed_obs   Linear(obs, obs_embed) + relu          of o_t
  embed_act   Linear(act, action_embed) + relu       of a_{t-1}
  embed_rew   Linear(1, reward_embed) + relu         of r_{t-1}
  lstm        one layer of H units over x_t = [E_o | E_a | E_r]:
              (i, f, g, u) = [x_t | h_{t-1}] W + b, W f32[X + H, 4 H],
              c_t = sigmoid(f) c_{t-1} + sigmoid(i) tanh(g),
              h_t = sigmoid(u) tanh(c_t)
              (one bias where torch's cell has two that only appear summed)
  shortcut    Linear(in, obs_embed) + relu of the CURRENT input: the
              actor's of o_t, the critic's of [o_t | a]
  head        actor: the chain MLP(H + obs_embed -> hidden -> act), tanh
  heads       critic: two such chains to 1, stacked on a leading axis of 2
              (ONE memory, TWO heads)

The plain form: every product is XLA's own, the memory is a `lax.scan` over
time of the cell on the concatenation [x_t | h_{t-1}] (one [B, X + H] x
[X + H, 4 H] product a step, nothing hoisted out of the loop), bracketed
`recur` (trace.CHUNK_SCOPES, ROLLOUT_SCOPES). The rollout's one step
(`actor_step`) and the learner's whole window (`memory` + `actor_head`) are
the same functions of the same leaves: `memory` scans `cell` over what
`embed` makes of the window, `actor_step` calls `embed` and `cell` once.

Initialisers are this tree's (models/mlp.py: U(+-1/sqrt(fan_in)), a final
layer U(+-3e-3)), the LSTM's matrix as one layer of fan-in X + H, where the
source has orthogonal LSTM weights (the configuration file's `assumed`).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from distributed_ddpg_tpu.models.mlp import _linear_init, mlp_init
from distributed_ddpg_tpu.trace import device_scope

BODY = ("embed_obs", "embed_act", "embed_rew", "lstm", "shortcut")


class Memory(NamedTuple):
    """What a recurrent policy owns between steps, a row an environment:
    the cell's state and the inputs its next step embeds beside o_t. All
    zero at an episode's first step (and at a window's, in the learner)."""

    h: Any            # f32[E, H]
    c: Any            # f32[E, H]
    prev_action: Any  # f32[E, act]  a_{t-1}, as the ring holds it
    prev_reward: Any  # f32[E]       r_{t-1}


def zero_memory(rows: int, units: int, act_dim: int) -> Memory:
    return Memory(
        h=jnp.zeros((rows, units), jnp.float32),
        c=jnp.zeros((rows, units), jnp.float32),
        prev_action=jnp.zeros((rows, act_dim), jnp.float32),
        prev_reward=jnp.zeros((rows,), jnp.float32),
    )


def is_recurrent(params) -> bool:
    return isinstance(params, dict) and "lstm" in params


def _body_init(keys, obs_dim, act_dim, shortcut_in, widths, dtype):
    units, obs_embed, action_embed, reward_embed = widths
    x = obs_embed + action_embed + reward_embed
    shapes = (
        (obs_dim, obs_embed), (act_dim, action_embed), (1, reward_embed),
        (x + units, 4 * units), (shortcut_in, obs_embed),
    )
    return {
        name: _linear_init(k, fan_in, fan_out, False, dtype)
        for name, k, (fan_in, fan_out) in zip(BODY, keys, shapes)
    }


def widths_of(config) -> tuple:
    """(units, obs_embed, action_embed, reward_embed) of `config`."""
    return (
        config.rnn_hidden, config.obs_embed, config.action_embed,
        config.reward_embed,
    )


def actor_init(key, obs_dim: int, act_dim: int, widths, hidden: Sequence[int], dtype=jnp.float32):
    keys = jax.random.split(key, len(BODY) + 1)
    params = _body_init(keys, obs_dim, act_dim, obs_dim, widths, dtype)
    params["head"] = mlp_init(keys[-1], [widths[0] + widths[1], *hidden, act_dim], dtype)
    return params


def critic_init(key, obs_dim: int, act_dim: int, widths, hidden: Sequence[int], dtype=jnp.float32):
    keys = jax.random.split(key, len(BODY) + 2)
    params = _body_init(keys, obs_dim, act_dim, obs_dim + act_dim, widths, dtype)
    params["heads"] = jax.tree.map(
        lambda a, b: jnp.stack([a, b]),
        *(mlp_init(k, [widths[0] + widths[1], *hidden, 1], dtype) for k in keys[-2:]),
    )
    return params


def _dense(layer, x):
    return jax.nn.relu(x @ layer["w"] + layer["b"])


def _chain(layers, x):
    for layer in layers[:-1]:
        x = _dense(layer, x)
    return x @ layers[-1]["w"] + layers[-1]["b"]


def embed(params, obs, prev_action, prev_reward):
    """x = [E_o(o) | E_a(a_prev) | E_r(r_prev)] on any leading axes."""
    return jnp.concatenate(
        [
            _dense(params["embed_obs"], obs),
            _dense(params["embed_act"], prev_action),
            _dense(params["embed_rew"], prev_reward[..., None]),
        ],
        axis=-1,
    )


def cell(lstm, state, x):
    """One LSTM step on rows: ((h, c), x f32[B, X]) -> (h', c')."""
    h, c = state
    i, f, g, u = jnp.split(
        jnp.concatenate([x, h], axis=-1) @ lstm["w"] + lstm["b"], 4, axis=-1
    )
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    return jax.nn.sigmoid(u) * jnp.tanh(c), c


def memory(params, obs, prev_action, prev_reward):
    """A net's memory over a batch of windows, from a zero state at each
    window's first step: obs f32[B, T, o], prev_action f32[B, T, a],
    prev_reward f32[B, T] -> h f32[B, T, H]. The embedders run on all B x T
    rows at once (they are feed-forward); the cell is scanned over T."""
    x = embed(params, obs, prev_action, prev_reward)
    units = params["lstm"]["w"].shape[-1] // 4
    zero = jnp.zeros((x.shape[0], units), x.dtype)

    def step(state, x_t):
        state = cell(params["lstm"], state, x_t)
        return state, state[0]

    with device_scope("recur"):
        _, h = jax.lax.scan(step, (zero, zero), jnp.swapaxes(x, 0, 1))
    return jnp.swapaxes(h, 0, 1)


def actor_head(params, h, obs, scale, offset):
    """pi = tanh(MLP([h | S(o)])) onto the action box."""
    joint = jnp.concatenate([h, _dense(params["shortcut"], obs)], axis=-1)
    return jnp.tanh(_chain(params["head"], joint)) * scale + offset


def critic_heads(params, h, obs, action):
    """Q_k = MLP_k([h | S([o | a])]), k = 1, 2: f32[2, ...]."""
    short = _dense(params["shortcut"], jnp.concatenate([obs, action], axis=-1))
    joint = jnp.concatenate([h, short], axis=-1)
    return jax.vmap(lambda head: _chain(head, joint)[..., 0])(params["heads"])


def actor_step(params, obs, mem: Memory, scale, offset):
    """The rollout's one step: (pi(o_t | memory), the cell's new state).
    `mem` holds (h, c) of the step before and the previous action and
    reward; the caller writes this step's action and reward into the
    Memory it carries on, and zeroes it where the episode ended."""
    x = embed(params, obs, mem.prev_action, mem.prev_reward)
    with device_scope("recur"):
        h, c = cell(params["lstm"], (mem.h, mem.c), x)
    return actor_head(params, h, obs, scale, offset), (h, c)
