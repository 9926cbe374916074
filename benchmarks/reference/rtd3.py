"""Recurrent TD3 (Ni, Eysenbach and Salakhutdinov, "Recurrent Model-Free RL Can
Be a Strong Baseline for Many POMDPs", ICML 2022, arXiv 2110.05038; code
twni2016/pomdp-baselines, `configs/pomdp/*/rnn.yml`, the TD3 form with SEPARATE
recurrent actor and recurrent critic; TD3 itself: Fujimoto et al. 2018, arXiv
1802.09477), one update in plain float32 `jax.numpy`, written from the
equations below and importing nothing of the program.

A row is a WINDOW of L = `hp["seq_len"]` steps of one episode, left-aligned:
[o_0 .. o_L | a_0 .. a_{L-1} | r_0 .. r_{L-1} | d_0 .. d_{L-1} | m_0 .. m_{L-1}],
each field time-major, d_t 1 where step t truly terminated, m_t 1 on a real
step and 0 on a padded one (an episode younger than L). With a_{-1} = 0 and
r_{-1} = 0, for a batch of B rows:

1. Memory, for each of the four nets (actor pi, critic Q, targets pi', Q'),
   t = 0 .. L: x_t = [E_o(o_t) | E_a(a_{t-1}) | E_r(r_{t-1})], each embedder a
   linear layer and a relu; (i, f, g, u) = [x_t | h_{t-1}] W + b;
   c_t = sigmoid(f) c_{t-1} + sigmoid(i) tanh(g); h_t = sigmoid(u) tanh(c_t);
   h_{-1} = c_{-1} = 0 at the window's first step (no stored state, no burn-in:
   the source's choice).
2. Heads. pi(t) = tanh(MLP_pi([h^pi_t | S_pi(o_t)])) onto the action box;
   Q_k(t, a) = MLP_k([h^Q_t | S_Q([o_t | a])]), k = 1, 2, S a linear layer and
   a relu of the CURRENT input (the source's shortcut), MLP relu chains. The
   critic has ONE memory and TWO heads, stacked on a leading axis of 2.
3. Critic. a~ = clip(pi'(t + 1) + eps_t, box), eps the clipped smoothing noise;
   y_t = r_t + gamma (1 - d_t) min_k Q'_k(t + 1, a~), no gradient, t = 0 .. L-1;
   L_Q = sum_t m_t sum_k (Q_k(t, a_t) - y_t)^2 / sum_t m_t, sums over the batch
   too.
4. Actor. L_pi = -sum_t m_t min_k Q_k(t, pi(t)) / sum_t m_t, t = 0 .. L-1; its
   gradient reaches the actor's leaves only, through the critic's shortcut
   (h^Q_t depends on the ring's actions, not on pi).
5. Adam on actor and critic; theta' <- (1 - tau) theta' + tau theta on every
   leaf of both targets, the memories' among them.

The smoothing noise of update k is `clip(sigma * normal(fold_in(PRNGKey(seed ^
0x7D3AF), k), (B, L, act)), -c, c)`, k the step count before the update: the one
random stream both sides must share (TD3's, reference/td3.py, with a time
axis), added in environment action units. `td`, per STEP and signed, f32[B, L],
is what the program reports as its TD errors: the mean over the two heads of
y_t - Q_k(t, a_t), 0 on a padded step. `seq_valid_frac` per update is the mean
of m.

Departures from the source, each also under `assumed` in the configuration's
file; none is a width (the embedders', the memory's and the heads' widths are
the configuration's `hp`, and this file fixes none):
1. one window row an env step: the source stores steps once and draws a window
   from any valid start inside one episode; here the actors write, at every
   step, the last <= L steps of the episode so far, left-aligned, so a drawn
   row is such a window (a short prefix is the source's short-episode case);
2. initialisers both sides can make to the last bit: this tree's U(+-1/sqrt(
   fan_in)) (final layers U(+-3e-3)), the LSTM's [X + H, 4 H] matrix as one
   layer of fan-in X + H with one bias, where the source has orthogonal LSTM
   weights and torch's two biases that only ever appear summed;
3. the policy loss through min(Q_1, Q_2) and no policy delay, as far as the
   source is known (PAPERS.md: loosely);
4. the program's convention, as in every reference here: the actor's loss goes
   through the critic as it stood BEFORE this update, its memory h^Q included;
5. the environment is a stand-in with half its state hidden
   (envs/jax_envs.py OccludedHumanoidStandIn), outside this update.
PAPERS.md holds what this tree knows of the source's settings, line by line.
"""

import jax
import jax.numpy as jnp

from . import common as c
from .d4pg import products  # dense products with operands rounded by lax.reduce_precision

BODY = ("embed_obs", "embed_act", "embed_rew", "lstm", "shortcut")  # streams of seeded draws, in order


def chain_init(key, dims):
    keys = jax.random.split(key, len(dims) - 1)
    return tuple(
        c.linear_init(keys[i], dims[i], dims[i + 1], i == len(dims) - 2) for i in range(len(dims) - 1)
    )


def body_init(keys, env, hp, shortcut_in):
    x = hp["obs_embed"] + hp["action_embed"] + hp["reward_embed"]
    shapes = (
        (env["obs_dim"], hp["obs_embed"]), (env["act_dim"], hp["action_embed"]), (1, hp["reward_embed"]),
        (x + hp["rnn_hidden"], 4 * hp["rnn_hidden"]), (shortcut_in, hp["obs_embed"]),
    )
    return {name: c.linear_init(k, i, o, False) for name, k, (i, o) in zip(BODY, keys, shapes)}


def init(seed, env, hp):
    k_actor, k_critic = jax.random.split(jax.random.PRNGKey(seed))
    joint = hp["rnn_hidden"] + hp["obs_embed"]
    ka = jax.random.split(k_actor, len(BODY) + 1)
    actor = body_init(ka, env, hp, env["obs_dim"])
    actor["head"] = chain_init(ka[-1], [joint, *hp["hidden"], env["act_dim"]])
    kc = jax.random.split(k_critic, len(BODY) + 2)
    critic = body_init(kc, env, hp, env["obs_dim"] + env["act_dim"])
    critic["heads"] = jax.tree.map(
        lambda a, b: jnp.stack([a, b]), *(chain_init(k, [joint, *hp["hidden"], 1]) for k in kc[-2:])
    )
    return {
        "actor": actor,
        "critic": critic,
        "target_actor": actor,
        "target_critic": critic,
        "actor_opt": c.adam_init(actor),
        "critic_opt": c.adam_init(critic),
        "step": jnp.zeros((), jnp.int32),
        # carried in the state so that one compiled reference serves every seed
        "noise_key": jax.random.PRNGKey(seed ^ 0x7D3AF),
    }


RECUR_PASSES = 8  # a memory's products, forward-equivalents an update: see work()


def work(env, hp):
    """{"flops", "row_bytes", "state_bytes", "recur_flops"} of one update
    (common.py's conventions: 2 * multiply-adds of products only, a backward
    pass as two forwards, each pass once however it is scheduled; the state
    read and written once a launch). A batch is B windows: B (L + 1) rows for
    the memories, B L for the heads.
    - `recur_flops`, the LSTM products alone: (X + H) * 4 H a row and pass;
      the two targets forward (1 each), critic and actor forward, weight
      gradients and the gradient back through time (3 each): RECUR_PASSES.
      The critic's memory under the actor's loss is the pass already counted
      (the same values: the program reuses them);
    - embedders, E = obs e_o + act e_a + e_r a row: the same passes less the
      input gradients nobody needs (1 + 1 + 2 + 2);
    - shortcuts: the actor's obs e_o (target 1, under its loss 2); the
      critic's (obs + act) e_o (target 1, under its loss 2, under the actor's
      forward and the action's columns back: 1 and act e_o);
    - heads, S a chain's products and S' those behind its first layer: the
      actor's target forward (S_a) and, under its loss, forward and both
      gradients (3 S_a); each of the critic's two heads target forward (S_c),
      under its loss 3 S_c, under the actor's loss forward and the gradient
      back to the shortcut alone (S_c + S'_c + e_o * width)."""
    obs, act, batch, steps = env["obs_dim"], env["act_dim"], hp["batch_size"], hp["seq_len"]
    units, e_o, e_a, e_r = hp["rnn_hidden"], hp["obs_embed"], hp["action_embed"], hp["reward_embed"]
    x = e_o + e_a + e_r
    lstm, embed = (x + units) * 4 * units, obs * e_o + act * e_a + e_r
    head_a = list(zip([units + e_o, *hp["hidden"]], [*hp["hidden"], act]))
    head_c = list(zip([units + e_o, *hp["hidden"]], [*hp["hidden"], 1]))
    s_a, s_c = (sum(i * o for i, o in h) for h in (head_a, head_c))
    t_c = sum(i * o for i, o in head_c[1:])
    recur = 2.0 * batch * (steps + 1) * lstm * RECUR_PASSES
    heads = (
        3 * obs * e_o + 4 * (obs + act) * e_o + act * e_o
        + 4 * s_a + 2 * (5 * s_c + t_c + e_o * hp["hidden"][0])
    )
    body = embed + x + lstm + 4 * units  # embedders and memory with their biases
    values = (
        body + (obs + 1) * e_o + sum(i * o + o for i, o in head_a)
        + body + (obs + act + 1) * e_o + 2 * sum(i * o + o for i, o in head_c)
    )
    return {
        "flops": recur + 2.0 * batch * ((steps + 1) * 6 * embed + steps * heads),
        "row_bytes": 4.0 * batch * ((steps + 1) * obs + steps * (act + 3)),
        # params, mu, nu, target: read and written once each, 4 bytes a value
        "state_bytes": 2.0 * 4 * 4 * values,
        "recur_flops": recur,
    }


def unpack(rows, env, hp):
    o, a, n = env["obs_dim"], env["act_dim"], hp["seq_len"]
    lead = rows.shape[:-1]
    at_a, at_r = (n + 1) * o, (n + 1) * o + n * a
    return {
        "obs": rows[..., :at_a].reshape(*lead, n + 1, o),
        "action": rows[..., at_a:at_r].reshape(*lead, n, a),
        "reward": rows[..., at_r : at_r + n],
        "terminated": rows[..., at_r + n : at_r + 2 * n],
        "mask": rows[..., at_r + 2 * n : at_r + 3 * n],
    }


# --- the equations' choices, one function each: a bent reference patches one ---


def mask_of(b):
    """m: 1 on a real step. (A reference that ignores it reads ones.)"""
    return b["mask"]


def first_state(batch, units):
    """(h, c) in front of a window's first step: zero, for every row. (A
    reference that carries the memory over would start a row from the row
    before's last state.)"""
    zero = jnp.zeros((batch, units), jnp.float32)
    return zero, zero


def bootstrap(b):
    """1 - d_t: a step that truly terminated bootstraps from nothing."""
    return 1.0 - b["terminated"]


def smoothing_noise(key, k, hp, shape):
    """Update k's target-policy smoothing noise, scaled and clipped."""
    eps = hp["target_noise"] * jax.random.normal(jax.random.fold_in(key, k), shape)
    return jnp.clip(eps, -hp["target_noise_clip"], hp["target_noise_clip"])


def actions_the_critic_remembers(ring_actions, policy_actions):
    """What a critic's memory embeds as a_{t-1} under the actor's loss: the
    RING's actions (its memory is no function of pi)."""
    return ring_actions


def leaves_that_trail(online, target, tau):
    """Polyak on every leaf of a target, the memory's among them."""
    return c.polyak(online, target, tau)


def make_step(seed, env, hp, operand_dtype=None):
    mm = products(operand_dtype)
    scale = jnp.asarray(env["action_scale"], jnp.float32)
    offset = jnp.asarray(env["action_offset"], jnp.float32)
    low, high = offset - scale, offset + scale
    units = hp["rnn_hidden"]

    def linear(layer, x):  # on any leading axes: `mm` takes rows
        return (mm(x.reshape(-1, x.shape[-1]), layer["w"]) + layer["b"]).reshape(*x.shape[:-1], -1)

    def relu_layer(layer, x):
        return jax.nn.relu(linear(layer, x))

    def chain(layers, x):
        for layer in layers[:-1]:
            x = relu_layer(layer, x)
        return linear(layers[-1], x)

    def remember(net, obs, prev_action, prev_reward):
        """h_t for t = 0 .. T-1 of obs f32[B, T, o]: a plain scan over time."""
        x = jnp.concatenate([
            relu_layer(net["embed_obs"], obs), relu_layer(net["embed_act"], prev_action),
            relu_layer(net["embed_rew"], prev_reward[..., None]),
        ], axis=-1)

        def cell(state, x_t):
            h, cell_state = state
            gates = linear(net["lstm"], jnp.concatenate([x_t, h], axis=-1))
            i, f, g, u = (gates[:, k * units : (k + 1) * units] for k in range(4))
            cell_state = jax.nn.sigmoid(f) * cell_state + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(u) * jnp.tanh(cell_state)
            return (h, cell_state), h

        _, hs = jax.lax.scan(cell, first_state(obs.shape[0], units), jnp.moveaxis(x, 1, 0))
        return jnp.moveaxis(hs, 0, 1)

    def policy(net, h, obs):
        joint = jnp.concatenate([h, relu_layer(net["shortcut"], obs)], axis=-1)
        return jnp.tanh(chain(net["head"], joint)) * scale + offset

    def q(net, h, obs, action):
        joint = jnp.concatenate([h, relu_layer(net["shortcut"], jnp.concatenate([obs, action], axis=-1))], axis=-1)
        return jnp.stack([
            chain(jax.tree.map(lambda leaf: leaf[k], net["heads"]), joint)[..., 0] for k in range(2)
        ])  # [2, B, T]

    def step(s, rows):
        b = unpack(rows, env, hp)
        obs, action, reward = b["obs"], b["action"], b["reward"]
        m = mask_of(b)
        n = jnp.maximum(jnp.sum(m), 1.0)
        shifted = lambda x: jnp.concatenate([jnp.zeros_like(x[:, :1]), x], axis=1)  # noqa: E731  x_{t-1}, zero at t = 0
        prev_a, prev_r = shifted(action), shifted(reward)
        eps = smoothing_noise(s["noise_key"], s["step"], hp, action.shape)

        h_ta = remember(s["target_actor"], obs, prev_a, prev_r)
        h_tc = remember(s["target_critic"], obs, prev_a, prev_r)
        a_next = jnp.clip(policy(s["target_actor"], h_ta[:, 1:], obs[:, 1:]) + eps, low, high)
        next_q = q(s["target_critic"], h_tc[:, 1:], obs[:, 1:], a_next)
        y = jax.lax.stop_gradient(reward + hp["gamma"] * bootstrap(b) * jnp.min(next_q, axis=0))

        def critic_loss(cp):
            h = remember(cp, obs, prev_a, prev_r)
            td = (y[None] - q(cp, h[:, :-1], obs[:, :-1], action)) * m[None]
            return jnp.sum(jnp.square(td)) / n, jnp.mean(td, axis=0)

        (closs, td), cgrad = jax.value_and_grad(critic_loss, has_aux=True)(s["critic"])

        def actor_loss(ap):  # through the critic as it stood before this update
            h = remember(ap, obs, prev_a, prev_r)
            pi = policy(ap, h[:, :-1], obs[:, :-1])
            h_q = remember(s["critic"], obs, shifted(actions_the_critic_remembers(action, pi)), prev_r)
            return -jnp.sum(jnp.min(q(s["critic"], h_q[:, :-1], obs[:, :-1], pi), axis=0) * m) / n

        aloss, agrad = jax.value_and_grad(actor_loss)(s["actor"])
        critic, critic_opt = c.adam(s["critic"], cgrad, s["critic_opt"], hp["critic_lr"])
        actor, actor_opt = c.adam(s["actor"], agrad, s["actor_opt"], hp["actor_lr"])
        new = {
            "actor": actor, "critic": critic,
            "target_actor": leaves_that_trail(actor, s["target_actor"], hp["tau"]),
            "target_critic": leaves_that_trail(critic, s["target_critic"], hp["tau"]),
            "actor_opt": actor_opt, "critic_opt": critic_opt, "step": s["step"] + 1,
            "noise_key": s["noise_key"],
        }
        return new, {
            "td": td,
            "critic_loss": closs,
            "actor_loss": aloss,
            "critic_grad_norm": c.tree_norm(cgrad),
            "actor_grad_norm": c.tree_norm(agrad),
            "twin_gap": jnp.sum(jnp.abs(next_q[0] - next_q[1]) * m) / n,
            "seq_valid_frac": jnp.mean(m),
        }

    return step
