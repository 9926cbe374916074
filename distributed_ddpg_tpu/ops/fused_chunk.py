"""Pallas TPU megakernel: K full DDPG learner steps in ONE kernel launch,
with every parameter tensor resident in VMEM for the whole chunk.

Motivation (SURVEY.md §3.3 hot loop): at DDPG scale (2x256 MLPs, batch 64)
the XLA scan path is bound by parameter HBM traffic — each step re-reads and
re-writes params, targets, and both Adam moments (~5 MB/step), roughly half
the measured 11 us/step on v5e-1. This kernel walks the chunk as a grid of
K steps whose param/target/moment blocks have CONSTANT index maps, so Mosaic
fetches them into VMEM once, revisits them across all K grid steps, and
writes them back to HBM once at the end (the standard accumulator pattern).
Only the K minibatches stream from HBM (~11 KB/step), double-buffered by the
pallas pipeline.

The forward/backward math is written out by hand (trace-time Python loops
over layers; everything stays in VMEM):

  critic loss   L_c = mean(w * (r + disc * Q'(s', mu'(s')) - Q(s,a))^2)
  actor  loss   L_a = -mean(Q(s, mu(s)))          (DPG; bwd through the
                                                   critic to the action)
  Adam (ops/optim.py formulas, bias correction from the carried count)
  Polyak        t <- tau * p + (1 - tau) * t      (ops/polyak.py)

Semantics match learner.make_learner_step exactly: both gradients are taken
against the PRE-update params of the step; tests/test_fused_chunk.py pins the
kernel to the XLA scan path over a whole chunk.

D4PG (C51, ops/losses.py:111-160 semantics) runs in the same kernel: the
critic head emits num_atoms logits, the categorical projection is computed
in-kernel as an unrolled accumulation over the source atoms in the
triangular-kernel form of the lower/upper-neighbor mass split,
proj^T += p'^T[i] * relu(1 - |tz^T[i] - z|/dz), with ATOMS ON SUBLANES AND
BATCH ON LANES so that row i spreads over the sublanes on the VPU
(kernel_projection says what the other orientation costs), one transpose in
and one out, rank-2 throughout so Mosaic never sees a 3D tensor; reward and
discount stream in lane-major, [K, 2, B], for it. The hand-written backward
uses the closed-form categorical cotangents (softmax(logits) - proj for the
critic CE; -p * (z - E[Z]) / B for the actor's expected-value head).

SAC (ops/losses.py sac_critic_loss / sac_actor_loss semantics) runs in the
same kernel too: the Gaussian head's [mean | log_std] split, the tanh
soft-clamp of log_std, reparameterized sampling (the per-step standard
normals stream in pre-drawn by learner.chunk_noise, the one helper both
legs' chunks draw their launch's noise from, like TD3's smoothing noise),
the tanh-squash log-prob, the entropy-corrected twin-critic TD target, and
the learned temperature's scalar Adam all execute in-kernel; the
hand-written actor backward routes the min-Q gate with reduce_min's
tie-splitting vjp and chains d(log pi)/du = 2*scale*t*(1-t^2)/g through the
squash correction.

Each net's OUTPUT layer is resident lane-major, [out, F] for the
TrainState's [F, out], where that takes fewer (8, 128) tiles (lane_major; a
rule on the shape: [256, 1], [256, 6], [300, 51], SAC's [256, 12]), with its
target and both moments: a one-lane-wide [F, 1] tensor costs the optimiser
tail a vreg per eight weights. The wrapper transposes once a launch in and
out (on the TPU a bitcast: the runtime lays those tensors column-major
anyway); the body swaps the operand forms of the same three MXU products.

Mixed precision (config.compute_dtype='bfloat16') casts matmul operands to
bf16 with f32 accumulation (`preferred_element_type`), forward AND backward,
mirroring models/mlp._dense; params, Adam state, and activations stay f32.

Supported envelope (callers must check `supported(config)`):
  - action_insert_layer == 1, critic_l2 == 0
  - any MLP depths/widths that fit VMEM (the DDPG/D4PG families all do)

On non-TPU backends the kernel runs in pallas interpret mode: numerics are
identical, speed is not (the XLA scan path remains the CPU choice).
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import math

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import (
    chunk_noise,
    delayed_updates,
    metric_keys,
    noise_base_key,
)
from distributed_ddpg_tpu.ops.optim import B1, B2, EPS
from distributed_ddpg_tpu.trace import device_scope
from distributed_ddpg_tpu.types import TrainState, OptState

_LOG_B1 = math.log(B1)
_LOG_B2 = math.log(B2)
_LOG_2PI = math.log(2.0 * math.pi)
# Tanh-squash log-det guard — MUST match losses._TANH_EPS for parity.
_TANH_EPS = 1e-6

# Fixed order in which a params tree (tuple of {"w","b"} dicts) is flattened
# into the kernel's ref list: w0, b0, w1, b1, ...  Biases ride as (1, F) rows
# so every ref is rank-2 (TPU VMEM wants >= 2D; (F,) -> (1, F) is layout-free).
# A net's OUTPUT layer rides [out, F] where lane_major says (the module
# docstring has why); nothing outside make_fused_chunk_fn sees that shape.


def _tiles(rows: int, cols: int) -> int:
    """(8, 128) float32 tiles of a [rows, cols] array resident in VMEM."""
    return -(-rows // 8) * -(-cols // 128)


def lane_major(fan_in: int, out: int) -> bool:
    """Whether the kernel holds an output layer [fan_in, out] as
    [out, fan_in]: a rule on the shape alone, the one the wrapper's
    flatten, the kernel body's matmul forms and state_tiles all ask."""
    return _tiles(out, fan_in) < _tiles(fan_in, out)


def _flatten(params) -> list:
    out = []
    for i, layer in enumerate(params):
        w = layer["w"]
        if i == len(params) - 1 and lane_major(*w.shape):
            w = w.T
        out.append(w)
        out.append(layer["b"].reshape(1, -1))
    return out


def _unflatten(flat: Sequence[Any], like) -> Tuple:
    """The kernel's refs back in the TrainState's shapes (`like`'s)."""
    layers = []
    for i, layer in enumerate(like):
        w = flat[2 * i]
        if i == len(like) - 1 and lane_major(*layer["w"].shape):
            w = w.T
        layers.append({"w": w, "b": flat[2 * i + 1].reshape(layer["b"].shape)})
    return tuple(layers)


def _flatten_twin(params) -> list:
    """TD3 ensemble tree (leaves [2, ...]) -> member-0 layers then member-1
    layers, every ref rank-2 (Mosaic never sees the ensemble axis)."""
    out = []
    for m in range(2):
        out += _flatten(jax.tree.map(lambda x: x[m], params))
    return out


def _unflatten_twin(flat: Sequence[Any], like) -> Tuple:
    n2 = 2 * len(like)
    member = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), like
    )
    members = [
        _unflatten(flat[m * n2 : (m + 1) * n2], member) for m in range(2)
    ]
    return jax.tree.map(
        lambda a, b: jnp.stack([a, b]), members[0], members[1]
    )


def _layer_shapes(config: DDPGConfig, obs_dim: int, act_dim: int):
    """((fan_in, out) of each actor layer, of each layer of one critic,
    critics in the state)."""

    def net(dims, extra_in=0):
        return [
            (dims[i] + (extra_in if i == 1 else 0), dims[i + 1])
            for i in range(len(dims) - 1)
        ]

    # obs/act enter the actor/critic input dims; action rides into critic
    # layer 1 (action_insert_layer == 1 inside the supported envelope).
    # The C51 head widens the critic output to num_atoms logits; the TD3
    # twin ensemble doubles every critic tensor; SAC doubles the actor head
    # ([mean | log_std]) and has critic_ensemble critics (2 unless REDQ).
    out = config.num_atoms if config.distributional else 1
    head = 2 * act_dim if config.sac else act_dim
    members = config.critic_ensemble if (config.twin_critic or config.sac) else 1
    return (
        net([obs_dim, *config.actor_hidden, head]),
        net([obs_dim, *config.critic_hidden, out], extra_in=act_dim),
        members,
    )


def state_vmem_bytes(config: DDPGConfig, obs_dim: int, act_dim: int) -> int:
    """f32 bytes of the kernel's VMEM-resident state: 8 copies of each net's
    tensors (params, targets, mu, nu for actor+critic), unpadded. What
    Mosaic allocates is this about once plus temporaries that grow with the
    batch: the table above VMEM_STATE_BUDGET has the measured pairs."""
    actor, critic, members = _layer_shapes(config, obs_dim, act_dim)
    a = sum(i * o + o for i, o in actor)
    c = members * sum(i * o + o for i, o in critic)
    return 4 * (4 * a + 4 * c)


def state_tiles(config: DDPGConfig, obs_dim: int, act_dim: int) -> int:
    """(8, 128) tiles of ONE copy of the resident parameters (actor plus
    every critic) as the kernel holds them, output layers lane-major where
    lane_major says; the targets and the two Adam moments are three copies
    more. What the optimiser tail walks, a vreg a tile: the run fact
    `kernel_state_tiles` (DDPG 2x256 at 17 / 6: 156, where [F, out] heads
    made it 216)."""
    actor, critic, members = _layer_shapes(config, obs_dim, act_dim)

    def net(layers):
        *body, (f, out) = layers
        head = _tiles(out, f) if lane_major(f, out) else _tiles(f, out)
        return head + sum(_tiles(i, o) for i, o in body) + sum(
            _tiles(1, o) for _, o in layers
        )

    return net(actor) + members * net(critic)


# VMEM budget for the resident state. It decides the leg (fits_vmem); it is
# not what Mosaic counts. What has met the compiler (libtpu 0.0.34), as the
# smallest vmem_limit_bytes each kernel compiles under for a described v5e,
# bisected to 1/16 MiB (obs 17 / act 6, chunk 800; Mosaic's default limit is
# 16 MiB and no caller passes another). Re-bisected in PR 34 with each net's
# output layer lane-major; the last column is the same bisection of the tree
# before it, [F, out] heads (PRs 31 and 32 read the same to 1/16):
#
#   family, widths, batch        state_vmem_bytes   scoped VMEM   [F, out] heads
#   DDPG  2x256    64            2.20 MiB            3.31 MiB      3.56
#   TD3   2x256    64            3.30                3.25          4.06
#   SAC   2x256   256            3.32                5.13          5.56
#   C51   2x256   256            2.40                5.44          5.25
#   C51   400-300 100            4.18                5.50          5.31
#   C51   400-300 256            4.18                8.38          7.88
#   TD3   400-300 100            5.93                8.06          9.25
#   TD3   400-300 256            5.93               11.25         15.50
#
# So the scoped allocation is the state about once plus the body's
# temporaries, and those grow with the batch (at 400-300: TD3 21 KiB a row,
# C51 19) and with what the branch keeps alive, not with the state. The
# lane-major heads took a quarter of a MiB to four MiB off the scalar-headed
# families (each [F, 1] or [F, 6] tensor was 128-152 KiB of padding, four
# copies a net, and TD3's per-row temporaries halved) and ADDED 0.19-0.50
# MiB to the categorical ones, whose [51, 300] head saves 17 tiles a copy
# and whose batch-contracting products hold other temporaries: still half
# the default. A loop that spills shows here first: with the projection's
# operands batch-on-sublanes the C51 rows read 13.19 / 8.01 / 14.60
# (kernel_projection, on [F, out] heads). All four benchmark configurations
# run through train() under the default (DDPG 2x256 batch 64, C51 400-300
# batch 256, TD3 400-300 batch 100; SAC at Humanoid's 376 / 17 is over this
# budget and takes the scan leg); tests/test_ring_layout.py compiles the
# cells' kernels, each under the default and under its own figure plus
# five eighths of a MiB to a MiB. The C51 kernel with its edge mass summed
# on every grid step and selected, as td3_twin_gap is, took 7.75 against
# 7.88 (on [F, out] heads). What would be refused today though fits_vmem says yes: by the
# table's slopes, a batch past about 480 rows at 400-300 on the TD3 branch
# (270 before). The repair then is vmem_limit_bytes, set from a fit to the
# table above, batch term first; no configuration anyone runs needs it.
VMEM_STATE_BUDGET = 6 * 1024 * 1024


def fits_vmem(config: DDPGConfig, obs_dim: int, act_dim: int) -> bool:
    return state_vmem_bytes(config, obs_dim, act_dim) <= VMEM_STATE_BUDGET


def supported(config: DDPGConfig) -> bool:
    return (
        config.action_insert_layer == 1
        and config.critic_l2 == 0.0
        # REDQ, CrossQ, SimBa: no kernel branch; the kernel's Adam holds
        # beta_1 as the constant B1 and has no decay term
        and not (config.redq or config.crossq or config.simba)
        # DrQ-v2: no convolution in the kernel
        and not config.pixels
        # DMPO: no pass on batch x samples rows, no dual variables; the
        # kernel's targets are Polyak averages, never copies
        and not config.mpo
        # recurrent TD3: no loop over time in the kernel (action_insert_layer
        # 0 already says no)
        and not config.recurrent
        and config.target_update_period == 0
        and config.adam_b1 == B1
        and config.weight_decay == 0.0
        and config.compute_dtype in ("float32", "bfloat16")
        # The hand-written backward assumes the action-insert layer (1) is
        # not the critic's output layer, i.e. at least 2 hidden layers.
        and len(config.critic_hidden) >= 2
        and len(config.actor_hidden) >= 1
        # The C51 projection unrolls num_atoms accumulation steps at trace
        # time; cap it so a pathological config can't explode the kernel.
        and (not config.distributional or config.num_atoms <= 256)
    )


def _sq(tree_leaves) -> Any:
    return sum(jnp.sum(x * x) for x in tree_leaves)


def kernel_projection(p_t, rew_row, disc_row, z_col, v_min, v_max):
    """The C51 branch's projection of the Bellman-shifted target distribution
    onto the support: p_t [B, A] (target softmax), rew_row / disc_row [1, B]
    (lane-major), z_col [A, 1] (the support down the sublanes) -> [B, A].

    proj[b, j] = sum_i p_t[b, i] * relu(1 - |tz[b, i] - z_j| / dz), summed
    over the source atom i in order: the triangular kernel IS the lower/upper-
    neighbour mass split of the classic projection (exact also when tz lands
    on an atom: weight 1 there, 0 elsewhere; a clipped row puts its mass on
    the end atom). The loop's operands lie with ATOMS ON SUBLANES AND BATCH ON
    LANES: row i of tz and of p_t^T spreads over the sublanes (a VPU sublane
    select, shared by every sublane tile of the atoms), where [B, A] operands
    need column i of each spread over 128 lanes, an XLU permute a row tile,
    twice an atom (3,264 an update at batch 256 and 51 atoms, 36% of the
    kernel's bundles: PERF.md, PR 32). One transpose in, one out; the same
    float32 VPU ops on the same operands in the same order, so the same bits."""
    num_atoms = z_col.shape[0]
    dz_atom = (v_max - v_min) / (num_atoms - 1)
    p_T = p_t.T  # [A, B]
    z_b = jnp.broadcast_to(z_col, p_T.shape)
    tz_T = jnp.clip(rew_row + disc_row * z_b, v_min, v_max)  # row i: atom i
    proj_T = jnp.zeros_like(p_T)
    for i in range(num_atoms):
        tri = jnp.maximum(
            0.0, 1.0 - jnp.abs(tz_T[i : i + 1, :] - z_b) / dz_atom
        )
        proj_T = proj_T + p_T[i : i + 1, :] * tri
    return proj_T.T


def _make_kernel(
    n_actor: int, n_critic: int, batch: int, chunk: int, config,
    sac_target_entropy: float | None = None,
):
    """Builds the kernel body. n_actor/n_critic = number of linear layers.
    `sac_target_entropy` is the trace-time scalar the wrapper resolves with
    the scan path's exact rule (learner.make_learner_step sac_step)."""
    tau = float(config.tau)
    lr_a = float(config.actor_lr)
    lr_c = float(config.critic_lr)
    inv_b = 1.0 / float(batch)
    inv_k = 1.0 / float(chunk)
    na2, nc2 = 2 * n_actor, 2 * n_critic
    distributional = bool(config.distributional)
    num_atoms = int(config.num_atoms)
    v_min, v_max = float(config.v_min), float(config.v_max)
    twin = bool(config.twin_critic)
    policy_delay = int(config.policy_delay)
    has_noise = twin and config.target_noise > 0.0
    sac = bool(config.sac)
    autotune = sac and bool(config.sac_autotune)
    # SAC log_std soft clamp: log_std = m0 + hw * (tanh(raw) + 1)
    # (models/mlp.actor_gaussian_apply).
    m0 = float(config.sac_log_std_min)
    hw = 0.5 * (float(config.sac_log_std_max) - m0)
    # Per-member critic ref count vs the total across the TD3/SAC ensemble.
    nct = nc2 * (2 if (twin or sac) else 1)
    # Resident temperature refs: log_alpha, plus its Adam mu/nu when learned.
    n_alpha = (3 if autotune else 1) if sac else 0

    # Mixed precision: cast matmul operands to bf16, accumulate f32 —
    # forward and backward alike (mirrors models/mlp._dense). Everything
    # outside the dots (activations, Adam, Polyak, projection) stays f32.
    if config.compute_dtype == "bfloat16":
        cast = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    else:
        cast = lambda x: x  # noqa: E731

    def _mm(a, b):
        return jnp.dot(cast(a), cast(b), preferred_element_type=jnp.float32)

    def _dW(x, dz):
        # x: [B, in], dz: [B, out] -> [in, out]; contract the batch dim
        # without materializing a transpose.
        return jax.lax.dot_general(
            cast(x), cast(dz), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _dx(dz, w):
        # dz: [B, out], w: [in, out] -> [B, in]; contract out dims.
        return jax.lax.dot_general(
            cast(dz), cast(w), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def kernel(*refs):
        it = iter(range(len(refs)))

        def take(n):
            return [refs[next(it)] for _ in range(n)]

        (count_ref,) = take(1)
        if distributional:
            # Reward and discount ride lane-major, one (2, B) block an update
            # (kernel_projection reads them as rows), and the support
            # twice: (1, num_atoms) along the lanes, (num_atoms, 1) down the
            # sublanes.
            obs_r, act_r, rd_r, nobs_r, wgt_r, scale_r, off_r = take(7)
            z_ref, zc_ref = take(2)
        else:
            obs_r, act_r, rew_r, disc_r, nobs_r, wgt_r, scale_r, off_r = (
                take(8)
            )
        if has_noise:
            (eps_r,) = take(1)  # target-smoothing noise stream, [K, B, act]
        if sac:
            # Pre-drawn standard normals: critic-target draw a'~pi(.|s')
            # and actor-pass draw a~pi(.|s), one [K, B, act] stream each.
            eps_next_r, eps_cur_r = take(2)
        actor_in = take(na2)
        critic_in = take(nct)
        t_actor_in = take(na2)
        t_critic_in = take(nct)
        amu_in, anu_in = take(na2), take(na2)
        cmu_in, cnu_in = take(nct), take(nct)
        alpha_in = take(n_alpha)
        td_out, met_out = take(2)
        actor_o = take(na2)
        critic_o = take(nct)
        t_actor_o = take(na2)
        t_critic_o = take(nct)
        amu_o, anu_o = take(na2), take(na2)
        cmu_o, cnu_o = take(nct), take(nct)
        alpha_o = take(n_alpha)

        def cm(group, m):
            """Member m's ref slice of a critic group (whole group when not
            an ensemble — the ensemble axis was flattened into the ref
            list)."""
            return (
                group[m * nc2 : (m + 1) * nc2] if (twin or sac) else group
            )

        k = pl.program_id(0)

        # Step 0: seed the VMEM-resident state blocks from the inputs. They
        # are revisited (constant index maps) for the rest of the grid, so
        # every later step reads/writes the output blocks only.
        @pl.when(k == 0)
        def _seed():
            for src, dst in zip(
                actor_in + critic_in + t_actor_in + t_critic_in
                + amu_in + anu_in + cmu_in + cnu_in + alpha_in,
                actor_o + critic_o + t_actor_o + t_critic_o
                + amu_o + anu_o + cmu_o + cnu_o + alpha_o,
            ):
                dst[...] = src[...]

        def W(group, i):
            return group[2 * i][...]

        def Bv(group, i):
            return group[2 * i + 1][...]

        # A net's output layer, held [out, F] where lane_major says (the
        # wrapper's _flatten asks the same function): the same three
        # bf16-operand MXU products over the same contractions as [F, out]
        # has, the operand forms swapped round.
        def head_fwd(group, n, h):
            """h [B, F] through output layer n - 1 -> [B, out]."""
            w, b = W(group, n - 1), Bv(group, n - 1)
            if not lane_major(h.shape[-1], b.shape[-1]):
                return _mm(h, w) + b
            if w.shape[0] > 1:
                return _dx(h, w) + b
            # The scalar head: h @ w.T through a transposed copy of the
            # weight, padded to the 8 sublanes (Mosaic refuses w.T of a
            # [1, F], and _dx(h, w): "Lane broadcast"). The copy depends on
            # the weights alone, so the XLU makes it off the critical path
            # and h streams past a stationary parameter as in every other
            # layer. The one other form Mosaic takes, _dx(w, h).T, makes the
            # fresh activations the MXU's stationary operand and transposes
            # the result, behind each of an update's three critic forwards:
            # 0.33 us of DDPG's 4.2 us update on the chip (PERF.md, PR 34).
            w8 = jnp.concatenate(
                [w, jnp.zeros((7, w.shape[1]), w.dtype)], axis=0
            ).T
            return _mm(h, w8)[:, :1] + b

        def head_dW(h, dz):
            """The output layer's weight gradient, in the shape it is held."""
            if lane_major(h.shape[-1], dz.shape[-1]):
                return _dW(dz, h)
            return _dW(h, dz)

        def head_dx(h, dz, w):
            """dz [B, out] back through the output layer to its input h's
            [B, F]."""
            if lane_major(h.shape[-1], dz.shape[-1]):
                return _mm(dz, w)
            return _dx(dz, w)

        obs = obs_r[0]
        action = act_r[0]
        if not distributional:
            rew = rew_r[0]
            disc = disc_r[0]
        nobs = nobs_r[0]
        wgt = wgt_r[0]
        scale = scale_r[...]
        offset = off_r[...]

        # ---- forwards ----------------------------------------------------
        def actor_fwd(group, x):
            """Returns (u, cache) where cache = (pre-acts h_i, activations)."""
            acts = [x]
            for i in range(n_actor - 1):
                z = _mm(acts[-1], W(group, i)) + Bv(group, i)
                acts.append(jnp.maximum(z, 0.0))
            t = jnp.tanh(head_fwd(group, n_actor, acts[-1]))
            return t * scale + offset, (acts, t)

        def critic_fwd(group, x, a):
            """Classic DDPG: action enters at layer 1 (split-weight trick —
            layer 1's weight rows [0:F) multiply the features, rows [F:F+A)
            multiply the action; same math as concat([h, a]) @ W)."""
            acts = [x]
            z0 = _mm(x, W(group, 0)) + Bv(group, 0)
            h0 = jnp.maximum(z0, 0.0)
            acts.append(h0)
            w1 = W(group, 1)
            f = h0.shape[-1]
            z1 = _mm(h0, w1[:f]) + _mm(a, w1[f:]) + Bv(group, 1)
            h1 = jnp.maximum(z1, 0.0)
            acts.append(h1)
            for i in range(2, n_critic - 1):
                z = _mm(acts[-1], W(group, i)) + Bv(group, i)
                acts.append(jnp.maximum(z, 0.0))
            return head_fwd(group, n_critic, acts[-1]), acts  # q: [B, 1]

        def critic_bwd(group, acts, a, dq_in, wgrads: bool):
            """Backprop dq through the critic. With wgrads, returns
            (param grads aligned with group order, d_action); without, only
            d_action is computed (the actor pass needs no critic dW — skips
            n_critic batch-contraction matmuls per step)."""
            grads = [None] * nc2
            dz = dq_in
            for i in range(n_critic - 1, 1, -1):
                head = i == n_critic - 1
                if wgrads:
                    grads[2 * i] = (head_dW if head else _dW)(acts[i], dz)
                    grads[2 * i + 1] = jnp.sum(dz, axis=0, keepdims=True)
                w = W(group, i)
                dh = head_dx(acts[i], dz, w) if head else _dx(dz, w)
                dz = dh * (acts[i] > 0.0)
            # layer 1 (split weights)
            w1 = W(group, 1)
            f = acts[1].shape[-1]
            da = _dx(dz, w1[f:])
            if not wgrads:
                return None, da
            grads[2] = jnp.concatenate(
                [_dW(acts[1], dz), _dW(a, dz)], axis=0
            )
            grads[3] = jnp.sum(dz, axis=0, keepdims=True)
            dh0 = _dx(dz, w1[:f])
            dz0 = dh0 * (acts[1] > 0.0)
            # layer 0
            grads[0] = _dW(acts[0], dz0)
            grads[1] = jnp.sum(dz0, axis=0, keepdims=True)
            return grads, da

        def mlp_bwd(group, acts, dz):
            """Plain-MLP backward from the output-layer cotangent dz
            ([B, out]); returns param grads aligned with the group order.
            Shared by the deterministic actor (after its tanh chain) and
            the SAC Gaussian head (whose output layer is linear)."""
            grads = [None] * na2
            grads[2 * (n_actor - 1)] = head_dW(acts[n_actor - 1], dz)
            grads[2 * (n_actor - 1) + 1] = jnp.sum(dz, axis=0, keepdims=True)
            for i in range(n_actor - 2, -1, -1):
                w = W(group, i + 1)
                if i == n_actor - 2:
                    dh = head_dx(acts[i + 1], dz, w)
                else:
                    dh = _dx(dz, w)
                dz = dh * (acts[i + 1] > 0.0)
                grads[2 * i] = _dW(acts[i], dz)
                grads[2 * i + 1] = jnp.sum(dz, axis=0, keepdims=True)
            return grads

        def adam_only(n2, p_o, mu_o, nu_o, grads, lr, t_step):
            # B^t as exp(t*log(B)) — Mosaic has no powf with a traced
            # exponent (fails to legalize 'math.powf' on real TPU).
            bc1 = 1.0 - jnp.exp(t_step * jnp.float32(_LOG_B1))
            bc2 = 1.0 - jnp.exp(t_step * jnp.float32(_LOG_B2))
            for j in range(n2):
                g = grads[j]
                m = B1 * mu_o[j][...] + (1.0 - B1) * g
                v = B2 * nu_o[j][...] + (1.0 - B2) * (g * g)
                mu_o[j][...] = m
                nu_o[j][...] = v
                p_o[j][...] = p_o[j][...] - lr * (m / bc1) / (
                    jnp.sqrt(v / bc2) + EPS
                )

        def polyak_only(n2, p_o, t_o):
            for j in range(n2):
                t_o[j][...] = tau * p_o[j][...] + (1.0 - tau) * t_o[j][...]

        def emit(td, step_metrics):
            """Write the per-step TD block and accumulate the chunk-MEAN
            metrics into the revisited (1, len(metric_keys)) block — see
            the layout rationale in the DDPG tail below."""
            td_out[0] = td
            assert len(step_metrics) == met_out.shape[-1]
            vals = jnp.stack(step_metrics).reshape(1, -1) * inv_k

            @pl.when(k == 0)
            def _met_seed():
                met_out[...] = vals

            @pl.when(k > 0)
            def _met_acc():
                met_out[...] = met_out[...] + vals

        if sac:
            # ==== SAC branch (losses.sac_critic_loss / sac_actor_loss ====
            # ==== + learner.sac_step semantics), then early return     ====
            A = scale.shape[-1]

            def gauss_fwd(group, x):
                """Gaussian head: relu MLP, linear [mean | log_std_raw]
                output, tanh soft-clamp of log_std onto [min, max]
                (models/mlp.actor_gaussian_apply). Returns
                (mean, log_std, tr, acts) with tr = tanh(raw) cached for
                the clamp's backward."""
                acts = [x]
                for i in range(n_actor - 1):
                    z = _mm(acts[-1], W(group, i)) + Bv(group, i)
                    acts.append(jnp.maximum(z, 0.0))
                zL = head_fwd(group, n_actor, acts[-1])
                mean = zL[:, :A]
                tr = jnp.tanh(zL[:, A:])
                log_std = m0 + hw * (tr + 1.0)
                return mean, log_std, tr, acts

            def sample(mean, log_std, eps):
                """Reparameterized tanh-Gaussian draw + log-prob
                (losses.sac_sample with the normal pre-drawn): because
                u = mean + std*eps, (u-mean)/std == eps exactly, so the
                Gaussian term needs no u."""
                std = jnp.exp(log_std)
                u = mean + std * eps
                t = jnp.tanh(u)
                a_env = t * scale + offset
                g = scale * (1.0 - t * t) + _TANH_EPS
                lp_dim = (
                    -0.5 * (eps * eps) - log_std - 0.5 * _LOG_2PI
                    - jnp.log(g)
                )
                lp = jnp.sum(lp_dim, axis=-1, keepdims=True)  # [B, 1]
                return std, t, a_env, g, lp

            la = alpha_o[0][...]  # (1, 1) resident log_alpha
            alpha = jnp.exp(la[0, 0])

            # ---- critic update: y = r + disc*(minQ' - alpha*logpi') ----
            meanN, log_stdN, _, _ = gauss_fwd(actor_o, nobs)
            _, _, aN, _, lpN = sample(meanN, log_stdN, eps_next_r[0])
            qt0, _ = critic_fwd(cm(t_critic_o, 0), nobs, aN)
            qt1, _ = critic_fwd(cm(t_critic_o, 1), nobs, aN)
            y = rew + disc * (jnp.minimum(qt0, qt1) - alpha * lpN)
            q0, acts0 = critic_fwd(cm(critic_o, 0), obs, action)
            q1_, acts1 = critic_fwd(cm(critic_o, 1), obs, action)
            td0 = y - q0
            td1 = y - q1_
            td = 0.5 * (td0 + td1)  # PER proxy: ensemble-mean TD
            # L = mean over [2, B] of w * td^2 -> dL/dq_m = -w * td_m / B.
            closs = (
                jnp.sum(wgt * td0 * td0) + jnp.sum(wgt * td1 * td1)
            ) * (0.5 * inv_b)
            c_grads0, _ = critic_bwd(
                cm(critic_o, 0), acts0, action, (-inv_b) * wgt * td0,
                wgrads=True,
            )
            c_grads1, _ = critic_bwd(
                cm(critic_o, 1), acts1, action, (-inv_b) * wgt * td1,
                wgrads=True,
            )

            # ---- actor update: L = E[alpha*logpi(a|s) - min_m Q_m(s,a)],
            # a = tanh(mean + std*eps)*scale + offset, pre-update critics.
            meanC, log_stdC, trC, a_acts = gauss_fwd(actor_o, obs)
            epsC = eps_cur_r[0]
            stdC, tC, aC, gC, lpC = sample(meanC, log_stdC, epsC)
            q_pi0, pia0 = critic_fwd(cm(critic_o, 0), obs, aC)
            q_pi1, pia1 = critic_fwd(cm(critic_o, 1), obs, aC)
            qmin = jnp.minimum(q_pi0, q_pi1)
            mean_lp = jnp.sum(lpC) * inv_b
            aloss = alpha * mean_lp - jnp.sum(qmin) * inv_b
            # Min gate with reduce_min's tie-splitting vjp (the scan path's
            # jnp.min over the member axis): equal rows split the cotangent.
            lt = (q_pi0 < q_pi1).astype(jnp.float32)
            gt = (q_pi0 > q_pi1).astype(jnp.float32)
            gate0 = lt + 0.5 * (1.0 - lt - gt)
            gate1 = 1.0 - gate0
            _, daA = critic_bwd(
                cm(critic_o, 0), pia0, aC, (-inv_b) * gate0, wgrads=False
            )
            _, daB = critic_bwd(
                cm(critic_o, 1), pia1, aC, (-inv_b) * gate1, wgrads=False
            )
            da = daA + daB
            # d(logpi)/du through the squash correction: lp's Gaussian term
            # is eps-only (see sample()), so only -log(g) carries u;
            # d(-log g)/du = 2*scale*t*(1-t^2)/g. The action path adds
            # da/du = scale*(1-t^2).
            dlp_row = alpha * inv_b  # dL/dlp per row (actor loss mean)
            one_m_t2 = 1.0 - tC * tC
            du = da * scale * one_m_t2 + dlp_row * (
                2.0 * scale * tC * one_m_t2 / gC
            )
            dmean = du  # du/dmean = 1
            # dlp/dlog_std (direct) = -1 per dim; du/dlog_std = std*eps.
            dlog_std = du * stdC * epsC - dlp_row
            # Soft clamp backward: log_std = m0 + hw*(tanh(raw)+1).
            draw = dlog_std * (hw * (1.0 - trC * trC))
            dzL = jnp.concatenate([dmean, draw], axis=-1)  # [B, 2A]
            a_grads = mlp_bwd(actor_o, a_acts, dzL)

            # ---- Adam (critic, actor), Polyak (both targets — SAC's math
            # has no target actor, but the slot trails for state parity
            # with the scan path), temperature Adam when autotuned.
            c_t = (count_ref[1] + k + 1).astype(jnp.float32)
            adam_only(nc2, cm(critic_o, 0), cm(cmu_o, 0), cm(cnu_o, 0),
                      c_grads0, lr_c, c_t)
            adam_only(nc2, cm(critic_o, 1), cm(cmu_o, 1), cm(cnu_o, 1),
                      c_grads1, lr_c, c_t)
            a_t = (count_ref[0] + k + 1).astype(jnp.float32)
            adam_only(na2, actor_o, amu_o, anu_o, a_grads, lr_a, a_t)
            polyak_only(nct, critic_o, t_critic_o)
            polyak_only(na2, actor_o, t_actor_o)
            if autotune:
                # J(log_alpha) = -log_alpha*(E[logpi]+H*): exact scalar
                # gradient, Adam at critic_lr (learner.sac_step).
                al_g = -(mean_lp + jnp.float32(sac_target_entropy))
                al_t = (count_ref[3] + k + 1).astype(jnp.float32)
                bc1 = 1.0 - jnp.exp(al_t * jnp.float32(_LOG_B1))
                bc2 = 1.0 - jnp.exp(al_t * jnp.float32(_LOG_B2))
                m_a = B1 * alpha_o[1][...] + (1.0 - B1) * al_g
                v_a = B2 * alpha_o[2][...] + (1.0 - B2) * (al_g * al_g)
                alpha_o[1][...] = m_a
                alpha_o[2][...] = v_a
                alpha_o[0][...] = la - lr_c * (m_a / bc1) / (
                    jnp.sqrt(v_a / bc2) + EPS
                )

            emit(
                td,
                [
                    closs,
                    aloss,
                    alpha * mean_lp - aloss,  # = E[minQ] (scan's mean_q)
                    jnp.sum(jnp.abs(td)) * inv_b,
                    jnp.sqrt(_sq(c_grads0) + _sq(c_grads1)),
                    jnp.sqrt(_sq(a_grads)),
                ],
            )
            return

        # Target path (no grads).
        u_t, _ = actor_fwd(t_actor_o, nobs)

        if twin:
            # ---- TD3 clipped double-Q (losses.td3_critic_loss) ----------
            if has_noise:
                # eps arrives pre-scaled AND pre-clipped (the wrapper draws
                # it from the same fold_in(seed, step) stream the scan path
                # uses, so the two paths are bit-comparable); only the
                # action-box clip happens here.
                na = jnp.clip(
                    u_t + eps_r[0], offset - scale, offset + scale
                )
            else:
                na = u_t
            qt0, _ = critic_fwd(cm(t_critic_o, 0), nobs, na)
            qt1, _ = critic_fwd(cm(t_critic_o, 1), nobs, na)
            y = rew + disc * jnp.minimum(qt0, qt1)
            # td3_twin_gap (learner.metric_keys): how far the two targets
            # lie apart, batch mean, of the launch's LAST update only
            # (learner.chunk_metrics). A sum over [B, 1] on every grid step
            # and a select, not c51_edge_mass's cond: on the chip the cond
            # cost this branch 1.5% of the launch, the select 0.2% (PR 31).
            # emit() scales every slot by 1/K, hence the K here.
            twin_gap = jnp.where(
                k == chunk - 1,
                jnp.sum(jnp.abs(qt0 - qt1)) * (inv_b * float(chunk)),
                0.0,
            )
            q0, acts0 = critic_fwd(cm(critic_o, 0), obs, action)
            q1_, acts1 = critic_fwd(cm(critic_o, 1), obs, action)
            td0 = y - q0
            td1 = y - q1_
            # PER proxy: ensemble-mean TD (losses.td3_critic_loss).
            td = 0.5 * (td0 + td1)
            # L = mean over [2, B] of w * td^2 -> dL/dq_m = -w * td_m / B.
            closs = (
                jnp.sum(wgt * td0 * td0) + jnp.sum(wgt * td1 * td1)
            ) * (0.5 * inv_b)
            c_grads0, _ = critic_bwd(
                cm(critic_o, 0), acts0, action, (-inv_b) * wgt * td0,
                wgrads=True,
            )
            c_grads1, _ = critic_bwd(
                cm(critic_o, 1), acts1, action, (-inv_b) * wgt * td1,
                wgrads=True,
            )
            c_grads = c_grads0 + c_grads1  # aligned with the twin flatten
        else:
            q_t, _ = critic_fwd(t_critic_o, nobs, u_t)
            q, c_acts = critic_fwd(critic_o, obs, action)

        if not twin and distributional:
            # ---- C51 critic loss (losses.py:111-160 semantics) ----------
            # q / q_t are [B, A] logit heads. Stable softmax over atoms.
            z = z_ref[...]  # (1, A)
            m_t = jnp.max(q_t, axis=-1, keepdims=True)
            e_t = jnp.exp(q_t - m_t)
            p_t = e_t / jnp.sum(e_t, axis=-1, keepdims=True)
            # proj is constant w.r.t. online params — the target path
            # carries no gradient, so forward-only is enough.
            rd = rd_r[0]  # (2, B): reward row, discount row
            proj = kernel_projection(
                p_t, rd[0:1, :], rd[1:2, :], zc_ref[...], v_min, v_max
            )
            m_q = jnp.max(q, axis=-1, keepdims=True)
            e_q = jnp.exp(q - m_q)
            sum_q = jnp.sum(e_q, axis=-1, keepdims=True)
            p_q = e_q / sum_q
            logp = q - (m_q + jnp.log(sum_q))
            ce = -jnp.sum(proj * logp, axis=-1, keepdims=True)  # [B, 1]
            closs = jnp.sum(wgt * ce) * inv_b
            # PER proxy (losses.py docstring): E[Z_target] - E[Z].
            mean_q_b = jnp.sum(p_q * z, axis=-1, keepdims=True)
            td = jnp.sum(proj * z, axis=-1, keepdims=True) - mean_q_b
            # c51_edge_mass (learner.metric_keys): the projected
            # target's mass on the support's two end atoms, batch mean, of
            # the launch's LAST update only (learner.chunk_metrics): the
            # other grid steps pay a compare for it, not two reductions.
            # emit() scales every slot by 1/K, hence the K here.
            edge_mass = jax.lax.cond(
                k == chunk - 1,
                lambda: (
                    jnp.sum(proj[:, :1]) + jnp.sum(proj[:, num_atoms - 1 :])
                ) * (inv_b * float(chunk)),
                lambda: jnp.float32(0.0),
            )
            # d(mean(w * ce))/dlogits = w/B * (softmax(logits) - proj)
            dq = (p_q - proj) * (wgt * inv_b)
        elif not twin:
            # ---- TD(0) critic loss --------------------------------------
            y = rew + disc * q_t
            td = y - q
            closs = jnp.sum(wgt * td * td) * inv_b
            # L_c = mean(w * td^2); dL/dq = -2/B * w * td
            dq = (-2.0 * inv_b) * wgt * td

        if not twin:
            c_grads, _ = critic_bwd(critic_o, c_acts, action, dq, wgrads=True)

        # ---- actor forward + backward (through the pre-update critic) ----
        # TD3: through critic member 0 only (the convention); cm() is the
        # whole group when not twin.
        u, (a_acts, t_u) = actor_fwd(actor_o, obs)
        q_pi, pi_acts = critic_fwd(cm(critic_o, 0), obs, u)
        if distributional:
            # L_a = -mean(E[Z(s, mu(s))]), E[Z] = sum_j softmax(logits)_j z_j.
            # Softmax jacobian gives the closed-form cotangent:
            # dL/dlogits_j = -(1/B) * p_j * (z_j - E[Z]).
            m_pi = jnp.max(q_pi, axis=-1, keepdims=True)
            e_pi = jnp.exp(q_pi - m_pi)
            p_pi = e_pi / jnp.sum(e_pi, axis=-1, keepdims=True)
            q_exp = jnp.sum(p_pi * z, axis=-1, keepdims=True)  # [B, 1]
            dq_pi = (-inv_b) * p_pi * (z - q_exp)
            aloss = -jnp.sum(q_exp) * inv_b
        else:
            # dL_a/dq = -1/B
            dq_pi = jnp.full_like(q_pi, -inv_b)
            aloss = -jnp.sum(q_pi) * inv_b
        _, da = critic_bwd(cm(critic_o, 0), pi_acts, u, dq_pi, wgrads=False)

        def actor_bwd(group, acts, t_out, da_in):
            # Chain through the tanh*scale output, then the shared MLP bwd.
            return mlp_bwd(group, acts, da_in * scale * (1.0 - t_out * t_out))

        a_grads = actor_bwd(actor_o, a_acts, t_u, da)

        # ---- Adam + Polyak, all in VMEM ---------------------------------
        # count_ref = [actor_count0, critic_count0, step0 (, alpha_count0
        # for SAC autotune)]: each net's bias correction follows ITS OWN
        # carried Adam count (they only coincide when the TrainState has
        # always stepped both nets together); step0 drives the TD3
        # delayed-update schedule. (adam_only/polyak_only are defined above
        # the SAC branch, which returns early.)
        def apply(n2, p_o, t_o, mu_o, nu_o, grads, lr, count0):
            adam_only(
                n2, p_o, mu_o, nu_o, grads, lr,
                (count0 + k + 1).astype(jnp.float32),
            )
            polyak_only(n2, p_o, t_o)

        if twin:
            # Critic ensemble steps every grid step; actor + ALL target
            # nets step on the TD3 delay schedule (matches the scan path's
            # lax.cond at state.step % delay == 0, with state.step = step0
            # + k pre-increment). Actor Adam bias correction follows the
            # number of REAL actor updates: learner.delayed_updates(n) counts
            # the multiples of delay below n, so the updates inside the
            # chunk before grid step k number its difference between
            # step0 + k and step0.
            c_t = (count_ref[1] + k + 1).astype(jnp.float32)
            adam_only(nc2, cm(critic_o, 0), cm(cmu_o, 0), cm(cnu_o, 0),
                      c_grads0, lr_c, c_t)
            adam_only(nc2, cm(critic_o, 1), cm(cmu_o, 1), cm(cnu_o, 1),
                      c_grads1, lr_c, c_t)
            step0 = count_ref[2]
            do_update = ((step0 + k) % policy_delay) == 0

            a_t = (
                count_ref[0] + delayed_updates(step0 + k, policy_delay)
                - delayed_updates(step0, policy_delay) + 1
            ).astype(jnp.float32)

            @pl.when(do_update)
            def _delayed():
                adam_only(na2, actor_o, amu_o, anu_o, a_grads, lr_a, a_t)
                polyak_only(na2, actor_o, t_actor_o)
                polyak_only(nct, critic_o, t_critic_o)
        else:
            apply(nc2, critic_o, t_critic_o, cmu_o, cnu_o, c_grads, lr_c,
                  count_ref[1])
            apply(na2, actor_o, t_actor_o, amu_o, anu_o, a_grads, lr_a,
                  count_ref[0])

        # ---- outputs -----------------------------------------------------
        # Order must match learner.metric_keys(config); the wrapper sizes
        # the metric block from its length and emit() asserts this stack
        # agrees (6 scalars, 7 on the C51 and twin-critic branches).
        # The chunk MEAN is accumulated in-kernel into a (1, 6) output whose
        # block IS the whole array (constant index map) — a per-step (K, 6)
        # output would need a (1, 6) block over K rows, which violates
        # Mosaic's layout rule (second-to-last block dim must be divisible
        # by 8 or equal the array dim; the round-2 TPU bench died on exactly
        # that, VERDICT.md Weak #1). Grid steps run sequentially on TPU, so
        # read-modify-write accumulation over the revisited block is sound.
        a_norm = jnp.sqrt(_sq(a_grads))
        if twin and policy_delay > 1:
            # Scan-path cond reports actor_grad_norm = 0 on skipped steps.
            a_norm = jnp.where(
                ((count_ref[2] + k) % policy_delay) == 0, a_norm, 0.0
            )
        emit(
            td,
            [
                closs,
                aloss,
                -aloss,
                jnp.sum(jnp.abs(td)) * inv_b,
                jnp.sqrt(_sq(c_grads)),
                a_norm,
            ]
            + ([edge_mass] if distributional else [])
            + ([twin_gap] if twin else []),
        )

    return kernel


def runs_native() -> bool:
    """True when the current backend compiles pallas TPU kernels with
    Mosaic; elsewhere they run in interpret mode (correct, far slower).
    The one platform predicate the kernels and every auto-sized chunk
    length key on."""
    return jax.default_backend() == "tpu"


def make_fused_chunk_fn(
    config: DDPGConfig,
    obs_dim: int,
    act_dim: int,
    action_scale,
    action_offset=0.0,
    chunk_size: int = 8,
    interpret: bool | None = None,
):
    """Returns jittable (state, batches[K, B, width]) ->
    (new_state, td[K, B], metrics{6 scalars}) running the whole chunk in one
    pallas launch. `batches` is the packed wire format (types.pack_batch_np
    layout); callers gather it from replay storage however they like."""
    if not supported(config):
        raise ValueError(
            "fused chunk kernel envelope: action_insert_layer=1, "
            "critic_l2=0, >=2 critic hidden layers, "
            ">=1 actor hidden, num_atoms<=256 when distributional"
        )
    if not fits_vmem(config, obs_dim, act_dim):
        raise ValueError(
            f"fused chunk kernel: VMEM-resident state would be "
            f"{state_vmem_bytes(config, obs_dim, act_dim)} bytes "
            f"(budget {VMEM_STATE_BUDGET}); use the XLA scan path "
            f"(fused_chunk='off') for nets this large"
        )
    K = int(chunk_size)
    B = int(config.batch_size)
    o, a = int(obs_dim), int(act_dim)
    interp = (not runs_native()) if interpret is None else interpret
    scale = jnp.broadcast_to(
        jnp.asarray(action_scale, jnp.float32), (1, a)
    )
    offset = jnp.broadcast_to(
        jnp.asarray(action_offset, jnp.float32), (1, a)
    )
    # The categorical support, twice: (1, A) along the lanes, (A, 1) down the
    # sublanes (kernel_projection).
    if config.distributional:
        z = jnp.linspace(
            config.v_min, config.v_max, config.num_atoms, dtype=jnp.float32
        )
        support_args = (z.reshape(1, -1), z.reshape(-1, 1))
    else:
        support_args = ()
    twin = bool(config.twin_critic)
    has_noise = twin and config.target_noise > 0.0
    sac = bool(config.sac)
    autotune = sac and bool(config.sac_autotune)
    if sac:
        from distributed_ddpg_tpu.ops.losses import sac_target_entropy

        tgt_h = sac_target_entropy(
            config.target_entropy, a, action_scale, config.target_entropy_scale
        )
    else:
        tgt_h = None

    keys = metric_keys(config)

    def run(state: TrainState, batches, eps=None):
        n_actor = len(state.actor_params)
        n_critic = len(state.critic_params)
        na2, nc2 = 2 * n_actor, 2 * n_critic

        with device_scope("cut"):
            obs = batches[..., :o]
            act = batches[..., o : o + a]
            if config.distributional:
                # The categorical branch takes reward and discount
                # lane-major, [K, 2, B], cut from the same gathered rows.
                rew_disc = (
                    jnp.swapaxes(batches[..., o + a : o + a + 2], 1, 2),
                )
            else:
                rew_disc = (
                    batches[..., o + a : o + a + 1],
                    batches[..., o + a + 1 : o + a + 2],
                )
            nobs = batches[..., o + a + 2 : 2 * o + a + 2]
            wgt = batches[..., 2 * o + a + 2 : 2 * o + a + 3]
        streams = (obs, act, *rew_disc, nobs, wgt)

        flat_c = _flatten_twin if (twin or sac) else _flatten
        # The state as the kernel takes it and, behind the call, back: the
        # call's own convention, so part of `update`.
        with device_scope("update"):
            state_flat = (
                _flatten(state.actor_params)
                + flat_c(state.critic_params)
                + _flatten(state.target_actor_params)
                + flat_c(state.target_critic_params)
                + _flatten(state.actor_opt.mu)
                + _flatten(state.actor_opt.nu)
                + flat_c(state.critic_opt.mu)
                + flat_c(state.critic_opt.nu)
            )
            if sac:
                # Resident temperature: log_alpha (+ its Adam moments when
                # learned), as (1, 1) VMEM blocks like every other tensor.
                state_flat = state_flat + [state.log_alpha.reshape(1, 1)]
                if autotune:
                    state_flat = state_flat + [
                        state.alpha_opt.mu.reshape(1, 1),
                        state.alpha_opt.nu.reshape(1, 1),
                    ]

        if eps is None:
            # The whole chunk's noise [K, B, act] (TD3: smoothing noise,
            # pre-scaled and pre-clipped; SAC: the (eps_next, eps_cur)
            # standard normals), from the stream the scan leg's chunks
            # pre-draw from too (learner.chunk_noise); it streams into the
            # kernel like the minibatches (~KB per step). Only a caller
            # outside the learner (a test, tools/kernel_bundles.py) leaves
            # the draw to this line and gets the base key as a constant of
            # its program: ShardedLearner passes eps drawn from the key its
            # chunk program takes as an argument (fused-mesh: axis-folded).
            eps = chunk_noise(
                config, noise_base_key(config), state.step, K, B, a
            )
        elif not (has_noise or sac):
            eps = None

        if sac:
            eps_args = tuple(eps)  # (eps_next, eps_cur)
        else:
            eps_args = (eps,) if eps is not None else ()

        def stream_spec(arr):
            # One update's block of a [K, ...] stream.
            return pl.BlockSpec(
                (1, *arr.shape[1:]), lambda k: (k, 0, 0),
                memory_space=pltpu.VMEM,
            )

        def pinned_spec(arr):
            nd = len(arr.shape)
            return pl.BlockSpec(
                arr.shape, lambda k: (0,) * nd, memory_space=pltpu.VMEM
            )

        in_specs = (
            [pl.BlockSpec(memory_space=pltpu.SMEM)]
            + [stream_spec(x) for x in streams]
            + [pinned_spec(x) for x in (scale, offset, *support_args)]
            + [stream_spec(x) for x in eps_args]
            + [pinned_spec(x) for x in state_flat]
        )
        out_specs = (
            [
                pl.BlockSpec(
                    (1, B, 1), lambda k: (k, 0, 0), memory_space=pltpu.VMEM
                ),
                # Chunk-mean metrics: the block is the whole (1, 6) array
                # (constant index map, accumulated across grid steps in the
                # kernel) — Mosaic-legal, unlike a (1, 6) block over (K, 6).
                pl.BlockSpec(
                    (1, len(keys)), lambda k: (0, 0),
                    memory_space=pltpu.VMEM,
                ),
            ]
            + [pinned_spec(x) for x in state_flat]
        )
        out_shape = (
            [
                jax.ShapeDtypeStruct((K, B, 1), jnp.float32),
                jax.ShapeDtypeStruct((1, len(keys)), jnp.float32),
            ]
            + [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in state_flat]
        )

        kernel = _make_kernel(
            n_actor, n_critic, B, K, config, sac_target_entropy=tgt_h
        )
        counts = [state.actor_opt.count, state.critic_opt.count, state.step]
        if autotune:
            counts.append(state.alpha_opt.count)
        # The inner bracket keeps the call's instruction the name every
        # record of a device trace knows it by (`fused_sample_chunk_fn.1`):
        # XLA names a custom call after the innermost scope it lies in.
        with device_scope("update"), jax.named_scope("fused_sample_chunk_fn"):
            count0 = jnp.stack(counts).astype(jnp.int32)
            outs = pl.pallas_call(
                kernel,
                grid=(K,),
                in_specs=in_specs,
                out_specs=out_specs,
                out_shape=out_shape,
                interpret=interp,
            )(
                count0, *streams, scale, offset,
                *support_args, *eps_args, *state_flat,
            )

        with device_scope("metrics"):
            td = outs[0][..., 0]
            met = outs[1][0]
            metrics = {k_: met[j] for j, k_ in enumerate(keys)}
        flat = list(outs[2:])
        unflat_c = _unflatten_twin if (twin or sac) else _unflatten
        nct = nc2 * (2 if (twin or sac) else 1)
        i = 0
        with device_scope("update"):
            actor_p = _unflatten(flat[i : i + na2], state.actor_params); i += na2
            critic_p = unflat_c(flat[i : i + nct], state.critic_params); i += nct
            t_actor = _unflatten(flat[i : i + na2], state.actor_params); i += na2
            t_critic = unflat_c(flat[i : i + nct], state.critic_params); i += nct
            amu = _unflatten(flat[i : i + na2], state.actor_params); i += na2
            anu = _unflatten(flat[i : i + na2], state.actor_params); i += na2
            cmu = unflat_c(flat[i : i + nct], state.critic_params); i += nct
            cnu = unflat_c(flat[i : i + nct], state.critic_params); i += nct
            new_log_alpha, new_alpha_opt = state.log_alpha, state.alpha_opt
            if sac:
                new_log_alpha = flat[i].reshape(()); i += 1
                if autotune:
                    new_alpha_opt = OptState(
                        mu=flat[i].reshape(()),
                        nu=flat[i + 1].reshape(()),
                        count=state.alpha_opt.count + K,
                    )
                    i += 2

        if twin and config.policy_delay > 1:
            # Actor count advances only on real updates: multiples of
            # policy_delay in [step0, step0 + K).
            d = config.policy_delay
            a_inc = delayed_updates(state.step + K, d) - delayed_updates(
                state.step, d
            )
        else:
            a_inc = K
        new_state = TrainState(
            actor_params=actor_p,
            critic_params=critic_p,
            target_actor_params=t_actor,
            target_critic_params=t_critic,
            actor_opt=OptState(
                mu=amu, nu=anu, count=state.actor_opt.count + a_inc
            ),
            critic_opt=OptState(mu=cmu, nu=cnu, count=state.critic_opt.count + K),
            step=state.step + K,
            log_alpha=new_log_alpha,
            alpha_opt=new_alpha_opt,
        )
        return new_state, td, metrics

    return run
