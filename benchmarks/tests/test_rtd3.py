"""The recurrent TD3 configuration's files and its reference, at sizes the CPU
holds: the configuration states the source's widths and cuts none; the
reference's update 0 against values computed by hand (numpy, float64, the
equations of reference/rtd3.py's docstring written out step by step) on a
window of two steps; the control's rounding moves the TD errors by far more
than the stated precision does; the three readers this configuration brought
read what the program writes, and nothing where it writes nothing. The
program against the reference is tier-1's (tests/test_reference_rtd3.py)."""

import importlib
import json
import os

import numpy as np
import pytest

from conftest import BENCH

CONFIG = os.path.join(BENCH, "configs", "rtd3-isaac-humanoid-p.json")
ENV = {"obs_dim": 3, "act_dim": 2, "action_scale": 1.0, "action_offset": 0.0}
HP = {
    "seq_len": 2, "rnn_hidden": 4, "obs_embed": 3, "action_embed": 2, "reward_embed": 2, "hidden": [5, 5],
    "gamma": 0.99, "tau": 0.005, "actor_lr": 3e-4, "critic_lr": 3e-4, "batch_size": 2, "target_noise": 0.2,
    "target_noise_clip": 0.5,
}


def test_the_file_states_the_sources_widths_and_nothing_cut():
    config = json.load(open(CONFIG))
    flags = dict(f.lstrip("-").split("=", 1) for f in config["flags"])
    assert flags["recurrent"] == "true" and flags["seq_len"] == "64" and flags["batch_size"] == "64"
    assert [flags[k] for k in ("rnn_hidden", "obs_embed", "action_embed", "reward_embed")] == ["128", "32", "8", "8"]
    assert flags["actor_hidden"] == flags["critic_hidden"] == "128,128"
    assert float(flags["actor_lr"]) == float(flags["critic_lr"]) == 3e-4 and float(flags["tau"]) == 0.005
    assert (float(flags["target_noise"]), float(flags["target_noise_clip"]), flags["policy_delay"]) == (0.2, 0.5, "1")
    assert float(flags["explore_sigma_min"]) == float(flags["explore_sigma_max"]) == 0.1
    assert set(config["reduced"]) == {"replay_capacity"} and flags["replay_capacity"] == "245760"
    hp = config["reference"]["hp"]
    assert {k: hp[k] for k in ("seq_len", "rnn_hidden", "obs_embed", "action_embed", "reward_embed", "batch_size")} == {
        "seq_len": 64, "rnn_hidden": 128, "obs_embed": 32, "action_embed": 8, "reward_embed": 8, "batch_size": 64}
    assert hp["hidden"] == [128, 128] and (config["env"]["obs_dim"], config["env"]["act_dim"]) == (54, 21)
    assert config["expects"] == {"fused_chunk_active": False, "chunk_front": "xla"}
    assert len(config["source"]) > 0 and "2110.05038" in config["source"] and "rnn.yml" in config["source"]
    for key in ("initialisers", "one_window_row_an_env_step", "stand_in", "policy_loss_and_delay"):
        assert key in config["assumed"], key
    limits = config["check"]["limits"]
    assert set(limits) == {"init_gap", "td0_vs_stated", "update_effect_gap", "critic_loss_rel", "change_gap"}
    assert limits["init_gap"] == 0 and limits["change_gap"] < 1.0
    traffic = json.load(open(os.path.join(BENCH, "traffic", "devseq.json")))
    both = dict(f.lstrip("-").split("=", 1) for f in config["flags"] + traffic["flags"])
    assert both["replay_min_size"] == both["warmup_uniform_steps"] == both["replay_capacity"]  # full before update 0
    assert (both["device_actor_envs"], both["device_actor_chunk"], both["max_ingest_ratio"]) == ("64", "1", "1")


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def by_hand(state, row, eps, rtd3):
    """Update 0's y, td, both losses on ONE window row, in float64 numpy, a
    step at a time: nothing of the reference but its seeded weights and the
    row's layout."""
    net = lambda name: {k: np.asarray(v, np.float64) for k, v in _flat(state[name]).items()}  # noqa: E731
    b = {k: np.asarray(v, np.float64) for k, v in rtd3.unpack(row, ENV, HP).items()}
    units, steps = HP["rnn_hidden"], HP["seq_len"]

    def dense(p, name, x):
        return x @ p[name + "/w"] + p[name + "/b"]

    def relu(x):
        return np.maximum(x, 0.0)

    def memory(p):
        h, c, out = np.zeros(units), np.zeros(units), []
        for t in range(steps + 1):
            a_prev = b["action"][t - 1] if t else np.zeros(ENV["act_dim"])
            r_prev = b["reward"][t - 1] if t else 0.0
            x = np.concatenate([
                relu(dense(p, "embed_obs", b["obs"][t])), relu(dense(p, "embed_act", a_prev)),
                relu(dense(p, "embed_rew", np.array([r_prev])))])
            i, f, g, u = np.split(dense(p, "lstm", np.concatenate([x, h])), 4)
            c = sigmoid(f) * c + sigmoid(i) * np.tanh(g)
            h = sigmoid(u) * np.tanh(c)
            out.append(h)
        return out

    def head(p, key, k, joint):
        x = joint
        for layer in range(3):
            w, bias = p[f"{key}/{layer}/w"], p[f"{key}/{layer}/b"]
            w, bias = (w, bias) if k is None else (w[k], bias[k])
            x = x @ w + bias
            x = relu(x) if layer < 2 else x
        return x

    def policy(p, h, o):
        return np.tanh(head(p, "head", None, np.concatenate([h, relu(dense(p, "shortcut", o))])))

    def q(p, h, o, a):
        joint = np.concatenate([h, relu(dense(p, "shortcut", np.concatenate([o, a])))])
        return np.array([head(p, "heads", k, joint)[0] for k in range(2)])

    ta, tc, cr, ac = net("target_actor"), net("target_critic"), net("critic"), net("actor")
    h_ta, h_tc, h_c, h_a = memory(ta), memory(tc), memory(cr), memory(ac)
    y, td, min_q = [], [], []
    for t in range(steps):
        a_next = np.clip(policy(ta, h_ta[t + 1], b["obs"][t + 1]) + eps[t], -1.0, 1.0)
        y.append(b["reward"][t] + HP["gamma"] * (1.0 - b["terminated"][t]) * q(tc, h_tc[t + 1], b["obs"][t + 1], a_next).min())
        td.append((y[t] - q(cr, h_c[t], b["obs"][t], b["action"][t])) * b["mask"][t])
        min_q.append(q(cr, h_c[t], b["obs"][t], policy(ac, h_a[t], b["obs"][t])).min() * b["mask"][t])
    td = np.array(td)  # [steps, 2]
    return td.mean(axis=1), np.sum(td ** 2), -np.sum(min_q), b["mask"].sum()


def _flat(tree, prefix=""):
    if isinstance(tree, dict):
        return {k2: v2 for k, v in tree.items() for k2, v2 in _flat(v, f"{prefix}{k}/").items()}
    if isinstance(tree, (tuple, list)):
        return {k2: v2 for i, v in enumerate(tree) for k2, v2 in _flat(v, f"{prefix}{i}/").items()}
    return {prefix[:-1]: tree}


def window_rows(seed):
    rng = np.random.default_rng(seed)
    o, a, n = ENV["obs_dim"], ENV["act_dim"], HP["seq_len"]
    full = np.concatenate([rng.normal(size=(n + 1) * o), rng.uniform(-1, 1, n * a), rng.normal(size=n), [0.0, 1.0], [1.0, 1.0]])
    obs = rng.normal(size=(n + 1, o))
    obs[2] = 0.0  # a young episode: one real step, the rest padded
    young = np.concatenate([obs.ravel(), rng.uniform(-1, 1, a), np.zeros(a), rng.normal(size=1), [0.0], [0.0, 0.0], [1.0, 0.0]])
    return np.stack([full, young]).astype(np.float32)


def test_update_0_against_values_computed_by_hand():
    import jax

    from reference import rtd3

    seed = 5
    state = rtd3.init(seed, ENV, HP)
    # seeded, a final layer is U(+-3e-3): scale the heads' last layers up so that Q and pi say something
    for name in ("actor", "critic", "target_actor", "target_critic"):
        key = "head" if "head" in state[name] else "heads"
        chain = state[name][key]
        state[name] = {**state[name], key: (*chain[:-1], jax.tree.map(lambda x: 200.0 * x, chain[-1]))}
    rows = window_rows(3)
    _, out = jax.jit(rtd3.make_step(seed, ENV, HP))(state, rows)
    eps = np.asarray(rtd3.smoothing_noise(state["noise_key"], state["step"], HP, (2, HP["seq_len"], ENV["act_dim"])))
    assert np.abs(eps).max() <= HP["target_noise_clip"] and np.abs(eps).max() > 0.05
    tds, sq, minq, real = zip(*(by_hand(state, rows[i], eps[i], rtd3) for i in range(2)))
    np.testing.assert_allclose(np.asarray(out["td"]), np.stack(tds), rtol=2e-5, atol=2e-6)
    assert float(out["critic_loss"]) == pytest.approx(sum(sq) / sum(real), rel=2e-5)
    assert float(out["actor_loss"]) == pytest.approx(sum(minq) / sum(real), rel=2e-5)
    assert float(out["seq_valid_frac"]) == pytest.approx(3 / 4)
    assert out["td"].shape == (2, 2) and float(out["td"][1, 1]) == 0.0  # the padded step reads 0
    assert np.abs(np.asarray(out["td"])).max() > 0.1


def test_the_controls_rounding_is_the_next_precision_down():
    """Update 0's TD errors with the operands of every product rounded to
    bfloat16 (the stated precision) and to float8_e5m2 (the control's), each
    against the float32 reference: the control's distance is many times the
    stated one's, which is what `td0_vs_stated` divides by."""
    import jax
    import jax.numpy as jnp

    from reference import rtd3

    hp = {**HP, "seq_len": 8, "rnn_hidden": 16, "batch_size": 8, "hidden": [16, 16]}
    env = {**ENV, "obs_dim": 6, "act_dim": 3}
    rng = np.random.default_rng(1)
    n = hp["seq_len"]
    rows = jnp.asarray(np.concatenate([
        rng.normal(size=(8, (n + 1) * 6)), rng.uniform(-1, 1, (8, n * 3)), rng.normal(size=(8, n)),
        np.zeros((8, n)), np.ones((8, n))], axis=1), jnp.float32)
    state = rtd3.init(9, env, hp)
    td = {dt: jax.jit(rtd3.make_step(9, env, hp, dt))(state, rows)[1]["td"] for dt in (None, "bfloat16", "float8_e5m2")}
    stated = float(jnp.linalg.norm(td["bfloat16"] - td[None]))
    control = float(jnp.linalg.norm(td["float8_e5m2"] - td[None]))
    assert 0.0 < stated < 1e-2 * float(jnp.linalg.norm(td[None])) and control > 8.0 * stated


def read(metric, run):
    return importlib.import_module("metrics." + metric.replace(".", "_")).read(run)


def test_the_three_readers_read_the_programs_keys_and_nothing_without_them(monkeypatch):
    window = [{"learner_steps": 32 * i, "seq_valid_frac": 0.9 + 0.01 * i} for i in (1, 2, 3)]
    assert read("learner.seq_valid_pct", {"window": window}) == pytest.approx(92.0)
    assert read("learner.seq_valid_pct", {"window": [{"learner_steps": 32}]}) is None
    assert read("learner.seq_valid_pct", {"window": []}) is None
    # no trace, or a program without the scopes (the parent's): nothing, and no raise
    bare = {"trace": None, "summary": {}, "config": {}}
    for metric in ("chunk.recur_pct", "chunk.recur_roofline"):
        assert read(metric, bare) is None
    from harness import scopes
    from metrics import chunk_recur_pct
    from reference import rtd3

    found = {"scopes": {"update/target/recur": 20.0, "update/critic/recur": 30.0, "update/actor/recur": 25.0,
                        "update/target": 5.0, "update/critic": 8.0, "update/actor": 7.0, "update/optim": 3.0,
                        "update/polyak": 2.0, "gather": 40.0, "update": 0.0},
             "loop_self": 0.0, "launches": 3}
    assert chunk_recur_pct.recur_ns(found) == 75.0 and scopes.ns(found, "update") == 100.0
    monkeypatch.setattr(scopes, "of_run", lambda run: found)
    config = json.load(open(CONFIG))
    run = {"trace": {}, "summary": {"learner_chunk": 32}, "config": config, "reference": rtd3,
           "peaks": {"flops_per_s": 197e12}}
    assert read("chunk.recur_pct", run) == pytest.approx(75.0)
    need = rtd3.work(config["env"], config["reference"]["hp"])["recur_flops"]
    assert read("chunk.recur_roofline", run) == pytest.approx(100.0 * 32 * need / 197e12 / 75e-9)
    # a program without `recur` in any scope (every sibling's) gives both nothing to read
    monkeypatch.setattr(scopes, "of_run", lambda run: {"scopes": {"update/critic": 8.0}, "loop_self": 0.0})
    assert read("chunk.recur_pct", run) is None and read("chunk.recur_roofline", run) is None
