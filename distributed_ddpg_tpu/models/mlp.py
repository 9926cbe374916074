"""Actor/critic MLPs as plain functional pytrees.

Capability parity with the reference's `actor_network.py` / `critic_network.py`
(SURVEY.md §2 #3/#4 — mount empty, spec from [PAPER]/[DRIVER] rows):

- Actor mu(s; theta): MLP with relu hiddens, tanh-squashed final layer scaled
  to the action bounds.
- Critic Q(s, a; phi): MLP where the action enters at the SECOND layer
  (classic DDPG, arXiv 1509.02971 §7).
- Init: hidden layers ~ U(-1/sqrt(fan_in), +1/sqrt(fan_in)); final layers
  ~ U(-3e-3, 3e-3) so initial policy outputs / Q values are near zero [PAPER].

Design notes (TPU-first, not a port):
- Params are plain pytrees (tuple of {"w","b"} dicts) — no framework objects —
  so the same tree feeds the jitted TPU path, the numpy `native` backend
  (bit-comparability oracle, BASELINE.json:5), and `jax.sharding` spec trees
  that mirror the structure 1:1 (parallel/mesh.py).
- All matmuls are batched [B, in] @ [in, out] so XLA tiles them onto the MXU;
  no per-example Python loops anywhere.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from distributed_ddpg_tpu.trace import device_scope

Params = Tuple[Dict[str, Any], ...]

FINAL_INIT_SCALE = 3e-3


def _uniform(key, shape, bound, dtype):
    return jax.random.uniform(key, shape, dtype, minval=-bound, maxval=bound)


def _linear_init(key, in_dim: int, out_dim: int, final: bool, dtype) -> Dict[str, Any]:
    bound = FINAL_INIT_SCALE if final else 1.0 / math.sqrt(in_dim)
    kw, kb = jax.random.split(key)
    return {
        "w": _uniform(kw, (in_dim, out_dim), bound, dtype),
        "b": _uniform(kb, (out_dim,), bound, dtype),
    }


def mlp_init(key, dims: Sequence[int], dtype=jnp.float32) -> Params:
    """Init a chain of linear layers with sizes dims[0] -> ... -> dims[-1]."""
    n = len(dims) - 1
    keys = jax.random.split(key, n)
    return tuple(
        _linear_init(keys[i], dims[i], dims[i + 1], final=(i == n - 1), dtype=dtype)
        for i in range(n)
    )


def actor_init(
    key, obs_dim: int, act_dim: int, hidden: Sequence[int], dtype=jnp.float32,
    norm: bool = False,
) -> Params:
    params = mlp_init(key, [obs_dim, *hidden, act_dim], dtype)
    return with_norm(params) if norm else params


# --- batch normalisation (CrossQ, arXiv 1902.05605) ---
# A normalised net keeps a batch-norm layer IN FRONT of every dense layer
# (BN_0 on the net's input, BN_l on relu(dense_l)), as four more leaves of
# that dense layer's dict: `bn_scale` and `bn_shift`, which Adam trains, and
# the running `bn_mean` and `bn_var`, which the training-mode forward pass
# writes. The statistics live in the parameter tree so that everything that
# carries parameters (checkpoints, sharding rules, donation, the on-chip
# comparison) carries them; no gradient reaches them (training mode does not
# read them, evaluation mode is never differentiated with respect to them),
# Adam leaves a leaf with a zero gradient where it is (m = v = 0), and the
# learner overwrites them after the step (`norm_moved`).
BN_MOMENTUM = 0.99  # running <- 0.99 * running + 0.01 * batch
BN_EPS = 1e-3


def with_norm(params: Params) -> Params:
    """`params` with an identity batch-norm layer in front of every dense
    layer: scale 1, shift 0, running mean 0 and variance 1."""
    def leaves(layer):
        n, dtype = layer["w"].shape[-2], layer["w"].dtype
        return {
            "bn_scale": jnp.ones((n,), dtype), "bn_shift": jnp.zeros((n,), dtype),
            "bn_mean": jnp.zeros((n,), dtype), "bn_var": jnp.ones((n,), dtype),
        }

    return tuple({**layer, **leaves(layer)} for layer in params)


def _global_mean(x, axis_name):
    """A replica's row mean -> the global batch's, under a data axis."""
    if axis_name is None:
        return x
    # lint: ok(collective-discipline): only traced inside the jitted learner
    # step, whose shard_map builder (parallel/) threads axis_name; never eager
    return jax.lax.pmean(x, axis_name)


def _norm(x, layer, train: bool, axis_name, moments: list):
    """The batch-norm layer in front of `layer`, or `x` itself where the
    layer has none. Training mode normalises by the moments of ALL of x's
    rows (every axis but the last; the biased variance), the global batch's
    under a data axis (`axis_name`: two pmeans a layer), and appends them
    to `moments`; evaluation mode by the running statistics."""
    if "bn_scale" not in layer:
        return x
    with device_scope("norm"):
        if train:
            rows = tuple(range(x.ndim - 1))
            mean = _global_mean(jnp.mean(x, axis=rows), axis_name)
            var = _global_mean(jnp.mean(jnp.square(x - mean), axis=rows), axis_name)
            moments.append((mean, var))
        else:
            mean, var = layer["bn_mean"], layer["bn_var"]
        return (x - mean) * (
            layer["bn_scale"] * jax.lax.rsqrt(var + BN_EPS)
        ) + layer["bn_shift"]


def norm_moved(params: Params, moments) -> Params:
    """`params` with each layer's running statistics moved a step of
    1 - BN_MOMENTUM towards `moments` (a training-mode pass's, one (mean,
    var) a layer; stacked nets' carry the stack's leading axis)."""
    with device_scope("norm"):
        return tuple(
            {
                **layer,
                "bn_mean": BN_MOMENTUM * layer["bn_mean"] + (1.0 - BN_MOMENTUM) * mean,
                "bn_var": BN_MOMENTUM * layer["bn_var"] + (1.0 - BN_MOMENTUM) * var,
            }
            for layer, (mean, var) in zip(params, moments)
        )


def norm_stat_gap(params: Params, moments):
    """Mean over the normalised features of |batch mean - running mean| /
    running standard deviation: how far an evaluation-mode pass is from the
    training-mode pass that gave `moments`."""
    with device_scope("norm"):
        gaps = [
            jnp.abs(mean - layer["bn_mean"]) * jax.lax.rsqrt(layer["bn_var"] + BN_EPS)
            for layer, (mean, _) in zip(params, moments)
        ]
        return sum(jnp.sum(g) for g in gaps) / sum(g.size for g in gaps)


def fold_norm(params):
    """A normalised net in evaluation mode as a plain {w, b} MLP: each
    batch-norm layer is an affine map in front of a dense layer, x -> x * k
    + t with k = scale / sqrt(var + eps) and t = shift - mean * k, so the
    dense layer becomes w' = diag(k) w and b' = b + t w. Host side (numpy
    leaves in, numpy out): what leaves the learner for the actors, the
    evaluator and the serving engine is a plain MLP, and their layouts do
    not know the normalisation exists. A plain net comes back as it is; a
    residual net (`simba_init`) with its input statistics folded into its
    embedding (`fold_rsnorm`), which is as far as it folds."""
    import numpy as np

    if "rs_mean" in params[0]:
        return fold_rsnorm(params)
    if "bn_scale" not in params[0]:
        return params
    out = []
    for layer in params:
        f = {name: np.asarray(leaf, np.float64) for name, leaf in layer.items()}
        k = f["bn_scale"] / np.sqrt(f["bn_var"] + BN_EPS)
        t = f["bn_shift"] - f["bn_mean"] * k
        out.append({
            "w": (k[:, None] * f["w"]).astype(np.float32),
            "b": (f["b"] + t @ f["w"]).astype(np.float32),
        })
    return tuple(out)


def _dense(x, layer, mm_dtype):
    """x @ w + b. With mm_dtype (mixed precision): inputs/weights cast to
    the matmul dtype (bf16 -> MXU native rate), accumulation and bias stay
    f32 (`preferred_element_type`), so activations remain f32 throughout —
    the standard TPU mixed-precision recipe. Master params are always f32."""
    if mm_dtype is None:
        return x @ layer["w"] + layer["b"]
    return (
        jnp.dot(
            x.astype(mm_dtype),
            layer["w"].astype(mm_dtype),
            preferred_element_type=jnp.float32,
        )
        + layer["b"]
    )


# --- residual pre-LayerNorm nets behind a running-statistics input
# normaliser (SimBa, arXiv 2410.09754) ---
# A residual net is a tuple of dicts too, of three kinds. params[0] is the
# embedding {w, b} with the normaliser's statistics beside it: `rs_mean` and
# `rs_var` over the OBSERVATION's features (a critic's action columns, which
# join at the input, are not normalised) and the row count `rs_count`.
# params[1:-1] are the blocks {ln_scale, ln_shift, w1, b1, w2, b2}:
# x + w2 relu(w1 LN(x) + b1) + b2, w1 [h, 4h], w2 [4h, h]. params[-1] is the
# head {ln_scale, ln_shift, w, b}: the post-LayerNorm, then the output layer.
# The statistics are leaves of the parameter tree for batch norm's reasons
# above: no gradient reaches them, the optimiser's step on them is
# overwritten (`rs_written`), and whatever carries parameters carries them.
# `rs_count` is a float32 like every other leaf (Adam's moments mirror the
# tree, and an integer leaf has no gradient): it counts rows in whole
# batches, so it is exact up to 2**24 batches (4.3e9 rows at batch 256, some
# 2.3 hours at 2,000 updates a second); beyond that a further batch may
# round away, by which time one batch moves a statistic by under 2**-24 of
# its distance anyway.
RS_EPS = 1e-8
LN_EPS = 1e-6
SIMBA_EXPANSION = 4
RS_STATS = ("rs_mean", "rs_var", "rs_count")


def is_simba(params) -> bool:
    """Whether `params` is a residual net (folded for the host or not)."""
    return "ln_scale" in params[-1]


def simba_init(
    key, obs_dim: int, in_dim: int, out_dim: int, hidden: Sequence[int],
    dtype=jnp.float32,
) -> Params:
    """A residual net on `in_dim` inputs, the first `obs_dim` of them the
    observation: one block per entry of `hidden`, all of one width
    (config.py holds that). Initialisers are this tree's (mlp_init's)."""
    h = hidden[0]
    keys = jax.random.split(key, len(hidden) + 2)
    ln = lambda: {"ln_scale": jnp.ones((h,), dtype), "ln_shift": jnp.zeros((h,), dtype)}
    embed = {
        **_linear_init(keys[0], in_dim, h, final=False, dtype=dtype),
        "rs_mean": jnp.zeros((obs_dim,), dtype),
        "rs_var": jnp.ones((obs_dim,), dtype),
        "rs_count": jnp.zeros((), dtype),
    }
    blocks = []
    for k in keys[1:-1]:
        k1, k2 = jax.random.split(k)
        up = _linear_init(k1, h, SIMBA_EXPANSION * h, final=False, dtype=dtype)
        down = _linear_init(k2, SIMBA_EXPANSION * h, h, final=False, dtype=dtype)
        blocks.append({**ln(), "w1": up["w"], "b1": up["b"], "w2": down["w"], "b2": down["b"]})
    head = {**ln(), **_linear_init(keys[-1], h, out_dim, final=True, dtype=dtype)}
    return (embed, *blocks, head)


def _layer_norm(x, layer, eps: float = LN_EPS):
    """LayerNorm over the last axis with `layer`'s learned scale and shift;
    moments, division and the affine map in x's float32."""
    with device_scope("lnorm"):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + eps) * layer["ln_scale"] + layer["ln_shift"]


def simba_apply(params: Params, obs, action=None, mm_dtype=None, resid: bool = False):
    """The residual net's output on `obs` (with `action` beside the
    normalised observation, for a critic). A net folded for the host
    (`fold_rsnorm`) has no statistics and takes `obs` as it is. With `resid`
    returns (output, share[rows]): the mean over the blocks of
    |f(LN(x))| / |x + f(LN(x))|, row by row."""
    embed = params[0]
    x = obs
    if "rs_mean" in embed:
        with device_scope("rsnorm"):
            # no gradient reaches the statistics: the optimiser's moments
            # for them stay zero
            mean, var = jax.lax.stop_gradient((embed["rs_mean"], embed["rs_var"]))
            x = (obs - mean) * jax.lax.rsqrt(var + RS_EPS)
    if action is not None:
        x = jnp.concatenate([x, action], axis=-1)
    x = _dense(x, embed, mm_dtype)
    shares = []
    for block in params[1:-1]:
        f = jax.nn.relu(
            _dense(_layer_norm(x, block), {"w": block["w1"], "b": block["b1"]}, mm_dtype)
        )
        f = _dense(f, {"w": block["w2"], "b": block["b2"]}, mm_dtype)
        if resid:
            shares.append(
                jnp.linalg.norm(f, axis=-1) / jnp.linalg.norm(x + f, axis=-1)
            )
        x = x + f
    out = _dense(_layer_norm(x, params[-1]), params[-1], mm_dtype)
    return (out, sum(shares) / len(shares)) if resid else out


def rs_merged(embed, obs, axis_name=None):
    """The normaliser's statistics after `obs`'s rows (the global batch's
    under a data axis) have joined them, by Chan's merge of two sets'
    moments (biased variances): ((mean, var, count), drift), drift the mean
    over the features of |batch mean - running mean| / running standard
    deviation BEFORE the merge. After k batches the statistics are the
    moments of all their rows."""
    with device_scope("rsnorm"):
        rows = obs.shape[0]
        if axis_name is not None:
            # lint: ok(collective-discipline): traced only inside the jitted
            # learner step under shard_map (see _global_mean)
            rows = rows * jax.lax.psum(1, axis_name)
        b_mean = _global_mean(jnp.mean(obs, axis=0), axis_name)
        b_var = _global_mean(jnp.mean(jnp.square(obs - b_mean), axis=0), axis_name)
        n0, mean0, var0 = embed["rs_count"], embed["rs_mean"], embed["rs_var"]
        n = n0 + rows
        delta = b_mean - mean0
        mean = mean0 + delta * (rows / n)
        var = (n0 * var0 + rows * b_var) / n + jnp.square(delta) * (n0 * rows / (n * n))
        drift = jnp.mean(jnp.abs(delta) * jax.lax.rsqrt(var0 + RS_EPS))
        return (mean, var, n), drift


def rs_written(params: Params, stats) -> Params:
    """`params` with the normaliser's statistics overwritten by `stats`
    (rs_merged's), spread over a stack's leading axis where there is one:
    every net of a state holds the same statistics by construction."""
    with device_scope("rsnorm"):
        embed = params[0]
        new = {
            name: jnp.broadcast_to(value, embed[name].shape)
            for name, value in zip(RS_STATS, stats)
        }
        return ({**embed, **new}, *params[1:])


def fold_rsnorm(params):
    """A residual net for the host: the input normaliser is an affine map in
    front of the embedding, o -> (o - mean) * k with k = 1 / sqrt(var + eps),
    so the embedding's observation rows become diag(k) w and its bias b -
    (mean * k) w. LayerNorm depends on the row and does not fold: the blocks
    and the head leave as they are. Numpy in, numpy out."""
    import numpy as np

    f = {name: np.asarray(leaf, np.float64) for name, leaf in params[0].items()}
    k = 1.0 / np.sqrt(f["rs_var"] + RS_EPS)
    w = f["w"].copy()
    n = k.shape[-1]
    w[..., :n, :] *= k[..., :, None]
    b = f["b"] - np.einsum("...i,...io->...o", f["rs_mean"] * k, f["w"][..., :n, :])
    embed = {"w": w.astype(np.float32), "b": b.astype(np.float32)}
    rest = tuple(
        {name: np.asarray(leaf, np.float32) for name, leaf in layer.items()}
        for layer in params[1:]
    )
    return (embed, *rest)


# --- Acme's LayerNormMLP and its diagonal-Gaussian head (DMPO, arXiv
# 2006.00979: acme/tf/networks LayerNormMLP, MultivariateNormalDiagHead) ---
# A tuple of dicts again, of two kinds: params[1] is the LayerNorm behind the
# first linear layer, {ln_scale, ln_shift} and nothing else (what tells this
# form from the others: `is_lnmlp`), every other entry a {w, b} layer. The
# net is linear -> LayerNorm -> tanh, then linear -> ELU for each further
# width (the last one activated too), then the output layer. A policy's
# output layer is [mean | scale_raw], the two linear heads of the source side
# by side; a critic's takes [obs | action] at the first layer.
LNMLP_EPS = 1e-5        # Sonnet's LayerNorm
GAUSSIAN_INIT_SCALE = 0.7
GAUSSIAN_MIN_SCALE = 1e-6


def is_lnmlp(params) -> bool:
    """Whether `params` is a LayerNormMLP (`lnmlp_init`)."""
    return len(params) > 1 and set(params[1]) == {"ln_scale", "ln_shift"}


def lnmlp_init(
    key, in_dim: int, out_dim: int, hidden: Sequence[int], dtype=jnp.float32
) -> Params:
    """A LayerNormMLP on `in_dim` inputs with one layer per entry of
    `hidden` and an output layer `out_dim` wide. Initialisers are this
    tree's (mlp_init's)."""
    first, *rest = mlp_init(key, [in_dim, *hidden, out_dim], dtype)
    h = hidden[0]
    ln = {"ln_scale": jnp.ones((h,), dtype), "ln_shift": jnp.zeros((h,), dtype)}
    return (first, ln, *rest)


def lnmlp_apply(params: Params, x, mm_dtype=None):
    """The LayerNormMLP's output layer on `x`."""
    x = jnp.tanh(_layer_norm(_dense(x, params[0], mm_dtype), params[1], LNMLP_EPS))
    for layer in params[2:-1]:
        x = jax.nn.elu(_dense(x, layer, mm_dtype))
    return _dense(x, params[-1], mm_dtype)


def gaussian_scale(raw):
    """The head's scale from its raw half: init_scale * softplus(raw) /
    softplus(0) + min_scale, the initial scale at raw 0."""
    return (
        GAUSSIAN_INIT_SCALE / math.log(2.0) * jax.nn.softplus(raw)
        + GAUSSIAN_MIN_SCALE
    )


def gaussian_apply(params: Params, obs, mm_dtype=None):
    """MPO's policy: (mean, scale) of a diagonal Gaussian over the CANONICAL
    action box [-1, 1], no squashing: whoever acts on a draw clips it to the
    box and maps it onto the environment's."""
    mean, raw = jnp.split(lnmlp_apply(params, obs, mm_dtype), 2, axis=-1)
    return mean, gaussian_scale(raw)


def actor_apply(params: Params, obs, action_scale, action_offset=0.0, mm_dtype=None) -> Any:
    """mu(s): relu hiddens, tanh output mapped onto the action box
    [offset - scale, offset + scale] (offset != 0 for asymmetric spaces)."""
    x = obs
    for layer in params[:-1]:
        x = jax.nn.relu(_dense(x, layer, mm_dtype))
    x = _dense(x, params[-1], mm_dtype)
    return jnp.tanh(x) * action_scale + action_offset


def actor_gaussian_apply(
    params: Params, obs, log_std_min: float, log_std_max: float, mm_dtype=None,
    train: bool = False, axis_name=None,
):
    """SAC stochastic head: the final layer outputs [mean | log_std]
    (2*act_dim wide — build params with actor_init(act_dim=2*act_dim)).
    Returns RAW (mean, log_std); sampling + tanh squash + the log-prob
    correction live in ops/losses.py so this stays a pure network apply.
    log_std is soft-clamped onto [min, max] with a tanh map — a hard clip
    would zero its gradient exactly where autotuned-alpha training tends
    to push it. A normalised net (`with_norm`) runs in evaluation mode
    unless `train`, which returns ((mean, log_std), moments) for
    `norm_moved`. A residual net (`simba_init`) runs `simba_apply`."""
    x, moments = obs, []
    if is_simba(params):
        x = simba_apply(params, obs, None, mm_dtype)
    else:
        for layer in params[:-1]:
            x = jax.nn.relu(
                _dense(_norm(x, layer, train, axis_name, moments), layer, mm_dtype)
            )
        x = _dense(_norm(x, params[-1], train, axis_name, moments), params[-1], mm_dtype)
    mean, log_std_raw = jnp.split(x, 2, axis=-1)
    log_std = log_std_min + 0.5 * (log_std_max - log_std_min) * (
        jnp.tanh(log_std_raw) + 1.0
    )
    if train:
        return (mean, log_std), tuple(moments)
    return mean, log_std


def critic_init(
    key,
    obs_dim: int,
    act_dim: int,
    hidden: Sequence[int],
    action_insert_layer: int = 1,
    num_outputs: int = 1,
    dtype=jnp.float32,
    norm: bool = False,
) -> Params:
    """Critic params. The layer at index `action_insert_layer` takes
    [features, action] concatenated as its input (classic DDPG).
    `num_outputs > 1` builds the categorical head for the D4PG
    distributional critic (arXiv 1804.08617)."""
    dims = [obs_dim, *hidden, num_outputs]
    n = len(dims) - 1
    if not 0 <= action_insert_layer < n:
        raise ValueError(
            f"action_insert_layer={action_insert_layer} out of range for a "
            f"{n}-layer critic (valid: 0..{n - 1})"
        )
    keys = jax.random.split(key, n)
    layers = []
    for i in range(n):
        in_dim = dims[i] + (act_dim if i == action_insert_layer else 0)
        layers.append(_linear_init(keys[i], in_dim, dims[i + 1], final=(i == n - 1), dtype=dtype))
    return with_norm(tuple(layers)) if norm else tuple(layers)


def critic_apply(
    params: Params, obs, action, action_insert_layer: int = 1, mm_dtype=None,
    train: bool = False, axis_name=None, resid: bool = False,
) -> Any:
    """Q(s, a) -> f32[B] (or f32[B, num_atoms] logits when distributional).
    A normalised net (`with_norm`) runs in evaluation mode unless `train`,
    which returns (Q, moments) for `norm_moved`; its batch is then every
    leading axis of `obs` (CrossQ's joint pass: [2, B, obs]). A residual net
    (`simba_init`) runs `simba_apply`, and with `resid` returns (Q, its
    blocks' residual share row by row). A LayerNormMLP (`lnmlp_init`, DMPO's
    categorical critic) takes [obs | action] at its input and returns its
    logits."""
    if is_simba(params):
        if resid:
            q, share = simba_apply(params, obs, action, mm_dtype, resid=True)
            return jnp.squeeze(q, axis=-1), share
        return jnp.squeeze(simba_apply(params, obs, action, mm_dtype), axis=-1)
    if is_lnmlp(params):
        return lnmlp_apply(params, jnp.concatenate([obs, action], axis=-1), mm_dtype)
    x, moments = obs, []
    n = len(params)
    for i, layer in enumerate(params):
        if i == action_insert_layer:
            x = jnp.concatenate([x, action], axis=-1)
        x = _dense(_norm(x, layer, train, axis_name, moments), layer, mm_dtype)
        if i < n - 1:
            x = jax.nn.relu(x)
    if x.shape[-1] == 1:
        x = jnp.squeeze(x, axis=-1)
    return (x, tuple(moments)) if train else x
