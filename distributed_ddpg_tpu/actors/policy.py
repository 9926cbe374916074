"""Pure-numpy actor policy for rollout workers.

The north star keeps rollout workers on CPU, unchanged in role
(BASELINE.json:5, SURVEY.md §3.2). Workers here run a numpy mirror of the
actor MLP — they never import jax, so worker processes are cheap to spawn,
can't contend for the TPU, and can't deadlock a forked XLA runtime.

Params travel learner -> workers as ONE flat f32 array in shared memory
(pool.py); `param_layout`/`flatten_params`/`NumpyPolicy.load_flat` define
the stable layout (layer order, w-then-b, C order).

The layout says what each entry of the block is. A plain MLP's entries are
pairs `(w_shape, b_shape)`, relu between them and the last one linear: the
layout and the block every family but one has, unchanged. A residual net's
(models/mlp.simba_init, `param_layout(..., residual=True)`) entries are
triples whose third element is the entry's kind (`KINDS`), in the order the
block holds them: the embedding `linear`; per block `block_ln` (LayerNorm's
scale then shift, entering the branch), `relu` (w1, b1) and `add` (w2, b2,
added back onto the stream); then `ln` (the post-LayerNorm) and the head
`linear`. Its first entry's w is still (obs_dim, width) and its last entry's
(width, head), so what reads those two shapes reads them as before. The
input normaliser is not in the block: the learner folds it into the
embedding at every refresh (models/mlp.fold_rsnorm). A LayerNormMLP's
(models/mlp.lnmlp_init, DMPO's policy; `param_layout(..., lnmlp=True)`)
entries are `linear` (the first layer), `tanh` (the LayerNorm behind it, scale
then shift, and the tanh on its output), one `elu` (w, b, then ELU) per
further width and the head `linear`.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

Layout = List[tuple]  # [(w_shape, b_shape)] or [(w_shape, b_shape, kind)]

# A layered entry's kinds. x is the stream, r the value saved at `block_ln`.
KINDS = ("linear", "block_ln", "relu", "add", "ln", "tanh", "elu")
# models/mlp.py's LN_EPS, LNMLP_EPS, SIMBA_EXPANSION and the Gaussian head's
# two constants (a worker never imports that module: it loads JAX;
# tests/test_simba.py and tests/test_dmpo.py hold the two files together)
LN_EPS = 1e-6
LNMLP_EPS = 1e-5
EXPANSION = 4
GAUSSIAN_INIT_SCALE = 0.7
GAUSSIAN_MIN_SCALE = 1e-6
# A LayerNorm entry's epsilon, by its kind.
_NORM_EPS = {"ln": LN_EPS, "block_ln": LN_EPS, "tanh": LNMLP_EPS}
# The leaves of one layer of the learner's tree, in the block's order; a
# layer holds the ones its kind has.
LEAF_ORDER = ("ln_scale", "ln_shift", "w1", "b1", "w2", "b2", "w", "b")


def encode_version(version: int) -> np.float32:
    """Bit-cast an int32 param-version tag into the transition ring's f32
    version column. A plain float(version) loses integer exactness past
    2^24; bit-casting keeps the full int32 range. Safe because every hop
    (row assignment, concatenate, shm ring memcpy) is a bit-preserving
    f32 copy — nothing does arithmetic on the column."""
    return np.int32(version).view(np.float32)


def decode_version(tag) -> int:
    return int(np.float32(tag).view(np.int32))


def actor_head_dim(act_dim: int, sac: bool) -> int:
    """Actor output width: a Gaussian head (SAC's, MPO's:
    config.gaussian_head) is [mean | log_std or raw scale]."""
    return 2 * act_dim if sac else act_dim


def gaussian_scale(raw: np.ndarray) -> np.ndarray:
    """models/mlp.gaussian_scale in numpy: MPO's head's scale from its raw
    half."""
    return GAUSSIAN_INIT_SCALE / np.log(2.0) * np.logaddexp(raw, 0.0) + GAUSSIAN_MIN_SCALE


def layout_of(config, obs_dim: int, act_dim: int) -> Layout:
    """The layout of `config`'s policy on an environment's two sizes: what
    the pools, the evaluator and the serving engines build theirs from."""
    return param_layout(
        obs_dim,
        actor_head_dim(act_dim, config.gaussian_head),
        tuple(config.actor_hidden),
        residual=config.simba,
        lnmlp=config.mpo,
    )


def param_layout(
    obs_dim: int, act_dim: int, hidden: Sequence[int], residual: bool = False,
    lnmlp: bool = False,
) -> Layout:
    """`act_dim` here is the HEAD width — pass actor_head_dim(...) for SAC.
    `residual` (config.simba): one pre-LayerNorm block per entry of
    `hidden`; `lnmlp` (config.mpo): a LayerNorm and a tanh behind the first
    layer, ELU behind the others: the module docstring's layered layouts."""
    if lnmlp:
        dims = [obs_dim, *hidden]
        layout = [((dims[0], dims[1]), (dims[1],), "linear"),
                  ((dims[1],), (dims[1],), "tanh")]
        layout += [
            ((dims[i], dims[i + 1]), (dims[i + 1],), "elu")
            for i in range(1, len(dims) - 1)
        ]
        return layout + [((dims[-1], act_dim), (act_dim,), "linear")]
    if residual:
        h = hidden[0]
        layout = [((obs_dim, h), (h,), "linear")]
        for _ in hidden:
            layout += [
                ((h,), (h,), "block_ln"),
                ((h, EXPANSION * h), (EXPANSION * h,), "relu"),
                ((EXPANSION * h, h), (h,), "add"),
            ]
        return layout + [((h,), (h,), "ln"), ((h, act_dim), (act_dim,), "linear")]
    dims = [obs_dim, *hidden, act_dim]
    return [((dims[i], dims[i + 1]), (dims[i + 1],)) for i in range(len(dims) - 1)]


def layout_size(layout: Layout) -> int:
    return sum(int(np.prod(w)) + int(np.prod(b)) for w, b, *_ in layout)


def is_layered(layout: Layout) -> bool:
    return len(layout[0]) > 2


def seqlock_snapshot(shared, version, out: np.ndarray, seen_version: int):
    """One seqlock read attempt of the pool's broadcast buffer
    (ActorPool.broadcast writes it: version odd while the flat array is
    mid-write, even when consistent). Copies into `out` and returns the
    new version when a CONSISTENT, not-yet-seen snapshot was read; returns
    None otherwise (nothing new, write in progress, or torn — the caller
    keeps acting on its previous params). Shared by the worker's local
    mirror (worker.py) and the inference server (serve/server.py) so the
    subtle discard discipline lives in exactly one place."""
    v = version.value
    if v == seen_version or v % 2 == 1:
        return None
    flat = np.frombuffer(shared, dtype=np.float32)
    out[:] = flat[: out.size]
    if version.value != v:
        return None
    return v


def flatten_params(params, out: np.ndarray | None = None) -> np.ndarray:
    """Flatten a (tuple of {'w','b'}) tree into one f32 vector (w then b,
    layer order). Writes into `out` when given (the shared-memory buffer).
    A residual net's layers (as models/mlp.fold_rsnorm hands them on) hold
    other leaves: each layer's go in LEAF_ORDER, which is the layered
    layout's order."""
    chunks = [
        np.asarray(layer[name], np.float32).ravel()
        for layer in params
        for name in LEAF_ORDER
        if name in layer
    ]
    flat = np.concatenate(chunks)
    if out is not None:
        out[: flat.size] = flat
        return out
    return flat


class NumpyPolicy:
    """mu(s) in numpy: relu hiddens, tanh output onto the action box.

    `gaussian=True` mirrors the SAC head (models/mlp.actor_gaussian_apply):
    the final layer is [mean | log_std]; deterministic mode acts on
    tanh(mean), `stochastic=True` samples the tanh-Gaussian with a local
    numpy RNG (workers explore by sampling the policy — no OU noise).

    `squash=False` (with `gaussian`) mirrors MPO's head
    (models/mlp.gaussian_apply): [mean | raw scale] of a plain Gaussian on
    the canonical box; it acts on the mean, or on a draw, CLIPPED to the box.

    A layered layout (`is_layered`) runs the net its kinds spell out,
    LayerNorm and all, on the same block discipline."""

    def __init__(
        self,
        layout: Layout,
        action_scale,
        action_offset=0.0,
        gaussian: bool = False,
        stochastic: bool = False,
        seed: int | None = None,
        log_std_min: float = -5.0,
        log_std_max: float = 2.0,
        squash: bool = True,
    ):
        self.layout = layout
        self.squash = squash
        self.scale = np.asarray(action_scale, np.float32)
        self.offset = np.asarray(action_offset, np.float32)
        self.gaussian = gaussian
        self.stochastic = stochastic
        self.log_std_min = log_std_min
        self.log_std_max = log_std_max
        self._rng = np.random.default_rng(seed) if stochastic else None
        # a LayerNorm entry's "w" is its scale and its "b" its shift
        self.layers = [
            {"w": np.zeros(w, np.float32), "b": np.zeros(b, np.float32)}
            for w, b, *_ in layout
        ]
        self.kinds = [e[2] for e in layout] if is_layered(layout) else None

    def load_flat(self, flat: np.ndarray) -> None:
        i = 0
        for layer, (w_shape, b_shape, *_) in zip(self.layers, self.layout):
            n = int(np.prod(w_shape))
            layer["w"] = flat[i : i + n].reshape(w_shape).copy()
            i += n
            n = int(np.prod(b_shape))
            layer["b"] = flat[i : i + n].copy()
            i += n

    def head(self, obs: np.ndarray) -> np.ndarray:
        """Raw final-layer output — [mean | log_std_raw] for the SAC
        head, pre-tanh mu otherwise. The serve path's building block
        (serve/server.py): the server ships head rows and applies the
        squash/sampling itself, with per-client keys."""
        x = np.atleast_2d(obs)
        if self.kinds is not None:
            return self._layered(x)
        for layer in self.layers[:-1]:
            x = np.maximum(x @ layer["w"] + layer["b"], 0.0)
        return x @ self.layers[-1]["w"] + self.layers[-1]["b"]

    def _layered(self, x: np.ndarray) -> np.ndarray:
        """The net of a layered layout (module docstring)."""
        saved = None
        for layer, kind in zip(self.layers, self.kinds):
            if kind in _NORM_EPS:
                if kind == "block_ln":
                    saved = x
                mean = x.mean(axis=-1, keepdims=True)
                var = np.square(x - mean).mean(axis=-1, keepdims=True)
                x = (x - mean) / np.sqrt(var + np.float32(_NORM_EPS[kind])) * layer["w"] + layer["b"]
                if kind == "tanh":
                    x = np.tanh(x)
                continue
            x = x @ layer["w"] + layer["b"]
            if kind == "relu":
                x = np.maximum(x, 0.0)
            elif kind == "elu":
                x = np.where(x > 0.0, x, np.expm1(np.minimum(x, 0.0)))
            elif kind == "add":
                x = saved + x
        return x

    def tree(self):
        """The loaded block as the learner's tree (what `flatten_params`
        took): a tuple of {w, b} for a plain net, fold_rsnorm's layers for a
        layered one. The jax serving engine ships it to the device."""
        if self.kinds is None:
            return tuple(dict(layer) for layer in self.layers)
        if "tanh" in self.kinds:  # a LayerNormMLP: one dict an entry
            return tuple(
                {"ln_scale": layer["w"], "ln_shift": layer["b"]}
                if kind == "tanh" else dict(layer)
                for layer, kind in zip(self.layers, self.kinds)
            )
        embed, *rest = self.layers
        out, i = [dict(embed)], 0
        while self.kinds[1 + i] == "block_ln":
            ln, up, down = rest[i : i + 3]
            out.append({"ln_scale": ln["w"], "ln_shift": ln["b"], "w1": up["w"],
                        "b1": up["b"], "w2": down["w"], "b2": down["b"]})
            i += 3
        ln, head = rest[i:]
        out.append({"ln_scale": ln["w"], "ln_shift": ln["b"], **head})
        return tuple(out)

    def __call__(self, obs: np.ndarray) -> np.ndarray:
        x = self.head(obs)
        if self.gaussian and not self.squash:
            u, raw = np.split(x, 2, axis=-1)
            if self.stochastic:
                u = u + gaussian_scale(raw) * self._rng.standard_normal(
                    u.shape
                ).astype(np.float32)
            return (np.clip(u, -1.0, 1.0) * self.scale + self.offset).astype(np.float32)
        if self.gaussian:
            mean, log_std_raw = np.split(x, 2, axis=-1)
            if not self.stochastic:
                return np.tanh(mean) * self.scale + self.offset
            # Same soft clamp as the jax head so worker and learner agree
            # on the distribution the experience was drawn from.
            log_std = self.log_std_min + 0.5 * (
                self.log_std_max - self.log_std_min
            ) * (np.tanh(log_std_raw) + 1.0)
            u = mean + np.exp(log_std) * self._rng.standard_normal(
                mean.shape
            ).astype(np.float32)
            return np.tanh(u) * self.scale + self.offset
        return np.tanh(x) * self.scale + self.offset
