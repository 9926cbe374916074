"""Regex partition-rule engine: param-tree paths -> PartitionSpecs
(ROADMAP '2D (data, model) named mesh with regex partition rules';
SNIPPETS.md [2] `match_partition_rules`, [3] DreamZero's ('data','model')
rule tables).

The old `mesh._layer_pspec` hardcoded one network shape: an MLP whose
layers alternate Megatron column-/row-parallel by index parity. That
worked for the seed's two MLPs and nothing else — a pixel encoder's conv
kernels, a distributional critic's wide value head, or any future net
would each need another bespoke if-ladder. This module replaces it with
the idiom large-model JAX codebases converged on: an ORDERED rule table
mapping regex patterns over '/'-joined tree paths to PartitionSpecs,
first match wins.

Semantics (each one a contract tests/test_partition.py pins):

- **paths** — a leaf's path is its pytree key path '/'-joined: the actor
  tuple's layer-2 kernel is `2/w`. Rules are matched with `re.search`,
  so tables may anchor (`^...$`) or float.
- **first match wins** — the table is ordered; put specific overrides
  (the final-layer replication rule) ahead of generic parity rules.
- **rank alignment** — a spec shorter than the leaf's rank aligns to the
  TRAILING dims and the extra leading dims replicate. This is what makes
  one rule cover both a plain critic kernel `[in, out]` and the TD3
  twin-ensemble kernel `[2, in, out]` (learner.init_train_state stacks
  the pair on a leading axis).
- **indivisible -> replicated** — a leaf whose 'model'-sharded dim does
  not divide the model-axis size replicates instead of erroring (XLA
  would pad; we'd rather not). This is a per-leaf decision and exactly
  reproduces the old per-layer fallback: the seed critic's
  action-insert layer (in_dim = hidden + act_dim, usually odd) stays
  replicated while its neighbors shard.
- **scalars replicate** — rank-0 leaves get P() without consulting the
  table (the SNIPPETS.md [2] rule).
- **unmatched -> hard error** — a path no rule covers raises
  PartitionRuleError naming the path. A silently-replicated new layer
  is exactly the drift this engine exists to prevent: add a rule, on
  purpose, in review.

The default tables reproduce the old alternation bit-for-bit
(tests/test_partition.py pins the equality at the seed shapes):
even-index layers column-parallel (shard the output dim), odd-index
row-parallel (shard the input dim), final layer replicated (its output
dim is act_dim / 1 / num_atoms — tiny and indivisible). Even/odd is a
plain regex fact of decimal strings (last digit [02468] / [13579]); only
the final-layer override depends on the net's depth, so `mlp_rules(n)`
prepends it per net.

`state_pspec` derives the Adam-moment specs from the SAME tables the
params use — params and optimizer state can never shard differently,
which is the invariant that makes checkpoint restore and the
pointer-swap param refresh placement-oblivious.

Add-a-rule recipe and the data x model composition decision table:
docs/MESH.md.
"""

from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

from distributed_ddpg_tpu.models.mlp import is_lnmlp, is_simba
from distributed_ddpg_tpu.models.pixels import is_pixel
from distributed_ddpg_tpu.models.recurrent import is_recurrent
from distributed_ddpg_tpu.types import OptState, TrainState

# One rule: (regex over the '/'-joined tree path, PartitionSpec). The
# spec names mesh axes ('model' here; 'data' stays a batch-dim axis and
# never appears in param tables).
Rule = Tuple[str, P]


class PartitionRuleError(ValueError):
    """A param-tree path matched no rule in the table. Every leaf must be
    placed ON PURPOSE — extend the table (docs/MESH.md 'add a rule')
    rather than letting a new layer silently replicate."""


# Megatron alternation for a {w, b} MLP layer list, index-parity encoded
# as a regex over the layer index's last decimal digit. Final-layer
# replication is depth-dependent and prepended by mlp_rules().
DEFAULT_MLP_RULES: Tuple[Rule, ...] = (
    # a batch-norm layer's four vectors (models/mlp.with_norm) replicate:
    # they run over the layer's INPUT features, whatever its parity
    (r"(^|/)\d+/bn_(scale|shift|mean|var)$", P(None)),
    # even layers: column-parallel (shard the output dim; bias shards too)
    (r"(^|/)\d*[02468]/w$", P(None, "model")),
    (r"(^|/)\d*[02468]/b$", P("model")),
    # odd layers: row-parallel (shard the input dim; bias replicated —
    # it adds after the partial-sum reduction)
    (r"(^|/)\d*[13579]/w$", P("model", None)),
    (r"(^|/)\d*[13579]/b$", P(None)),
)


# A residual net (models/mlp.simba_init): the residual stream is replicated,
# so the embedding and the head (the {w, b} layers at its two ends) and the
# vectors that act on the stream replicate; inside a block w1 is
# column-parallel and w2 row-parallel, one reduction a block.
SIMBA_RULES: Tuple[Rule, ...] = (
    (r"(^|/)\d+/(ln_scale|ln_shift|rs_mean|rs_var|rs_count)$", P(None)),
    (r"(^|/)\d+/w1$", P(None, "model")),
    (r"(^|/)\d+/b1$", P("model")),
    (r"(^|/)\d+/w2$", P("model", None)),
    (r"(^|/)\d+/(b2|b)$", P(None)),
    (r"(^|/)\d+/w$", P(None, None)),
)


def lnmlp_rules(num_entries: int) -> Tuple[Rule, ...]:
    """A LayerNormMLP (models/mlp.lnmlp_init): the first layer feeds a
    LayerNorm over its whole output, so it and the LayerNorm's two vectors
    replicate; the dense layers behind follow the MLP table by their index
    in the tuple (the LayerNorm is entry 1, so they start column-parallel at
    entry 2), the output layer replicated as every net's."""
    return (
        (r"(^|/)0/(w|b)$", P(None)),
        (r"(^|/)1/(ln_scale|ln_shift)$", P(None)),
    ) + mlp_rules(num_entries)


def pixel_rules(params) -> Tuple[Rule, ...]:
    """A pixel net (models/pixels.py: a dict of `encoder`, `trunk`, `heads`
    or `mlp`): convolution kernels, their biases and the trunk (its wide
    [features, feature_dim] product feeds a LayerNorm over the whole
    feature axis) replicate; the dense layers behind it follow the MLP
    table by their index, as every dense chain does."""
    chain = params.get("heads", params.get("mlp", ()))
    return (
        (r"(^|/)encoder/\d+/(w|b)$", P(None)),
        (r"(^|/)trunk/(w|b|ln_scale|ln_shift)$", P(None)),
    ) + mlp_rules(len(chain))


# A recurrent net (models/recurrent.py: a dict of embedders, `lstm`,
# `shortcut` and `head` or `heads`): every leaf replicates. The embedders
# are 8 to 32 wide, the LSTM's [X + H, 4 H] matrix is read whole by every
# one of a window's dependent steps, and the heads are 128 wide: no leaf is
# worth a collective a step.
RECURRENT_RULES: Tuple[Rule, ...] = (
    (r"(^|/)(embed_obs|embed_act|embed_rew|lstm|shortcut)/(w|b)$", P(None)),
    (r"(^|/)heads?/\d+/(w|b)$", P(None)),
)


def mlp_rules(num_layers: int) -> Tuple[Rule, ...]:
    """The default table for an MLP of `num_layers` {w, b} layers: the
    final layer replicates (override first), everything else follows the
    parity alternation."""
    last = num_layers - 1
    return (
        (rf"(^|/){last}/w$", P(None, None)),
        (rf"(^|/){last}/b$", P(None)),
    ) + DEFAULT_MLP_RULES


def _path_str(path) -> str:
    """'/'-joined pytree key path: SequenceKey(2)/DictKey('w') -> '2/w'."""
    parts = []
    for k in path:
        if hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:  # pragma: no cover - future key kinds
            parts.append(str(k))
    return "/".join(parts)


def _fit(spec: P, shape: Tuple[int, ...], model_size: int) -> P:
    """Align `spec` to a leaf of `shape` under a model axis of
    `model_size`: trailing-dim alignment (extra leading dims replicate),
    whole-leaf replication when model_size == 1 or when any sharded dim
    does not divide it (module docstring 'indivisible -> replicated')."""
    if len(spec) > len(shape):
        raise PartitionRuleError(
            f"rule spec {spec} has rank {len(spec)} but the leaf has "
            f"shape {shape} — a spec must not outrank its leaf"
        )
    full = (None,) * (len(shape) - len(spec)) + tuple(spec)
    replicated = P(*(None,) * len(shape))
    if model_size == 1:
        return replicated
    for dim, ax in zip(shape, full):
        if ax is not None and dim % model_size != 0:
            return replicated
    return P(*full)


def match_partition_rules(rules: Sequence[Rule], tree, model_size: int):
    """PartitionSpec tree for `tree` under the ordered rule table
    (SNIPPETS.md [2]): scalars replicate, the first matching rule's spec
    is rank-aligned and divisibility-gated by _fit, and an unmatched
    path is a hard PartitionRuleError."""

    def place(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 0:
            return P()
        name = _path_str(path)
        for pattern, spec in rules:
            if re.search(pattern, name):
                return _fit(spec, shape, model_size)
        raise PartitionRuleError(
            f"no partition rule matches param path {name!r} (shape "
            f"{shape}) — extend the rule table (docs/MESH.md 'add a "
            "rule'); every leaf must be placed on purpose"
        )

    return jax.tree_util.tree_map_with_path(place, tree)


def net_pspec(params, model_size: int, rules: Optional[Sequence[Rule]] = None):
    """Spec tree for one {w, b}-layer param list. Default rules are the
    per-depth MLP table (mlp_rules), SIMBA_RULES for a residual net,
    lnmlp_rules for a LayerNormMLP, or RECURRENT_RULES for a recurrent net;
    pass `rules` for any other."""
    if rules is None:
        if is_pixel(params):
            rules = pixel_rules(params)
        elif is_recurrent(params):
            rules = RECURRENT_RULES
        elif is_simba(params):
            rules = SIMBA_RULES
        elif is_lnmlp(params):
            rules = lnmlp_rules(len(params))
        else:
            rules = mlp_rules(len(params))
    return match_partition_rules(rules, params, model_size)


def state_pspec(
    state: TrainState,
    mesh: Mesh,
    actor_rules: Optional[Sequence[Rule]] = None,
    critic_rules: Optional[Sequence[Rule]] = None,
) -> TrainState:
    """PartitionSpec tree mirroring TrainState 1:1. Actor/critic params,
    their targets, AND their Adam moments all derive from the same rule
    table per net — params and optimizer state can never shard
    differently. Scalars (step, SAC temperature machinery, Adam counts)
    replicate."""
    m = mesh.shape["model"]
    actor = net_pspec(state.actor_params, m, rules=actor_rules)
    critic = net_pspec(state.critic_params, m, rules=critic_rules)
    target_critic = critic
    if state.target_critic_params is not None and is_pixel(critic):
        # a pixel critic's target holds trunk and heads, not the encoder
        target_critic = {k: critic[k] for k in state.target_critic_params}
    return TrainState(
        actor_params=actor,
        critic_params=critic,
        # None (CrossQ has no targets) is an empty pytree node, as below.
        target_actor_params=None if state.target_actor_params is None else actor,
        target_critic_params=None if state.target_critic_params is None else target_critic,
        actor_opt=OptState(mu=actor, nu=actor, count=P()),
        critic_opt=OptState(mu=critic, nu=critic, count=P()),
        step=P(),
        # SAC's temperature scalar, or MPO's small tree of dual variables,
        # replicates leaf by leaf, and Adam's moments with it; None
        # (neither family) is an empty pytree node and needs no spec.
        log_alpha=(duals := jax.tree.map(lambda _: P(), state.log_alpha)),
        alpha_opt=(
            None
            if state.alpha_opt is None
            else OptState(mu=duals, nu=duals, count=P())
        ),
    )
