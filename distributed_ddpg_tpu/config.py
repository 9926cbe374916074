"""Single frozen dataclass config, CLI-overridable (SURVEY.md §5 'Config').

Replaces the reference's `tf.app.flags`/`settings.py` constants module
(SURVEY.md §2 #8). Hyperparameter defaults follow the DDPG paper
(arXiv 1509.02971) as recorded in SURVEY.md §2 #8: gamma=0.99, tau=1e-3,
lr_actor=1e-4, lr_critic=1e-3, batch=64, buffer ~1e6, OU theta=0.15 sigma=0.2.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence


@dataclasses.dataclass(frozen=True)
class DDPGConfig:
    """Hyperparameters and topology for one training run."""

    # --- environment ---
    env_id: str = "Pendulum-v1"
    seed: int = 0

    # --- networks (SURVEY.md §2 #3/#4: ~2 hidden layers, 400/300 or 256/256) ---
    actor_hidden: Sequence[int] = (256, 256)
    critic_hidden: Sequence[int] = (256, 256)
    # Classic DDPG injects the action at the second critic layer (SURVEY.md §2 #4).
    action_insert_layer: int = 1

    # --- algorithm ---
    gamma: float = 0.99
    tau: float = 1e-3                # Polyak soft-update coefficient
    actor_lr: float = 1e-4
    critic_lr: float = 1e-3
    critic_l2: float = 0.0           # weight decay on critic (paper uses 1e-2)
    batch_size: int = 64
    n_step: int = 1                  # n-step returns (D4PG, arXiv 1804.08617)

    # --- distributional critic (D4PG) ---
    distributional: bool = False
    num_atoms: int = 51
    # Value-support bounds. nan = AUTO (CLI: --v_min=auto --v_max=auto, both
    # together): sized from warmup reward statistics at learner start, then
    # expanded geometrically whenever mean_q approaches an edge
    # (ops/support_auto.py — kills the per-env hand knob that needed ±400
    # for LunarLander and [-1600, 0] for Pendulum, docs/EVIDENCE.md §3).
    # nan, not 0/inf, is the sentinel — same convention as target_entropy:
    # any concrete float is a legitimate hand-set bound.
    v_min: float = -150.0
    v_max: float = 150.0

    # --- TD3 (arXiv 1802.09477; beyond-parity family like D4PG) ---
    # twin_critic: a 2-critic ensemble (params stacked on a leading axis,
    # applied via vmap — one MXU-batched program, not two sequential nets),
    # with min-over-ensemble Bellman targets (clipped double-Q).
    twin_critic: bool = False
    # twin_critic: actor + target nets update once per `policy_delay`
    # critic steps. sac: the actor and the temperature do; the critics'
    # targets still move on every update (REDQ, below).
    policy_delay: int = 1
    # Target-policy smoothing: clip(N(0, target_noise), +-clip) added to
    # the target action inside the critic target (0 = off). The noise key
    # derives from fold_in(seed, state.step) — deterministic, replayable,
    # and identical across data-parallel replicas.
    target_noise: float = 0.0
    target_noise_clip: float = 0.5

    # --- SAC (arXiv 1801.01290/1812.05905; third beyond-parity family) ---
    # sac: stochastic tanh-Gaussian actor (head outputs [mean | log_std],
    # reparameterized sampling, tanh log-prob correction), twin critics
    # stacked on a leading axis exactly like TD3's, and entropy-regularized
    # Bellman targets min_i Q_i(s',a') - alpha * log pi(a'|s'). Exploration
    # comes from the policy itself: workers sample (no OU noise), eval acts
    # on tanh(mean).
    sac: bool = False
    # Entropy temperature. With sac_autotune the learner treats log(alpha)
    # as a learned scalar driving policy entropy toward target_entropy
    # (nan = auto = -act_dim + sum(log action_scale) — the 1812.05905
    # -act_dim heuristic expressed in this codebase's env-unit log-probs;
    # see learner.sac_step. nan, not 0, is the sentinel: an exact-zero
    # entropy target is inside the knob's valid domain); sac_alpha is then
    # just the initial value.
    sac_alpha: float = 0.2
    sac_autotune: bool = True
    target_entropy: float = float("nan")
    # log_std clamp for the Gaussian head (standard SAC stability bounds).
    sac_log_std_min: float = -5.0
    sac_log_std_max: float = 2.0
    # Uniform-random action warmup (SAC's classic `start_steps`): for the
    # first N env steps actions are drawn uniformly from the action box
    # instead of the policy. SAC NEEDS this: its exploration is the
    # policy's own (initially narrow, entropy-bounded) Gaussian, and
    # without broad seed data swing-up style tasks never see the good
    # region (measured: Pendulum stuck ~-1100 @25k without, solved -78
    # with — docs/EVIDENCE.md §3). OU-driven families explore broadly from
    # step 0, so warmup only applies where configured. -1 = auto
    # (replay_min_size when sac, else 0); 0 = off. In the actor pool the
    # budget is split evenly across workers.
    warmup_uniform_steps: int = -1

    # --- REDQ (arXiv 2101.05982, Algorithm 1; sac only) ---
    # critic_ensemble: N critics, independently seeded, stacked on the
    # leading axis that sac's and twin_critic's two already have.
    # target_subset: M distinct critics drawn uniformly for each update's
    # target, y = R + d * (min over the M drawn Q'_i - alpha * log pi);
    # M == N is the minimum over all, what sac computes today. When M < N
    # the actor ascends the ensemble MEAN (Algorithm 1) where sac has the
    # minimum. With policy_delay G (the paper's update-to-data ratio, as the
    # structure of the loop) the actor and temperature step on one update
    # in G. N = M = 2 and policy_delay 1 is plain sac, program for program.
    critic_ensemble: int = 2
    target_subset: int = 2

    # --- CrossQ (arXiv 1902.05605; sac only) ---
    # crossq: sac without target networks. One field turns its three
    # changes on together, since they are one algorithm: the TrainState's
    # target slots are None and no Polyak pass is traced; actor and critics
    # carry a batch-norm layer in front of every dense layer (models/mlp.py);
    # and the Bellman target is read from the SAME training-mode forward
    # pass as the prediction, on the joint batch [(s, a); (s', a')], so one
    # set of batch statistics normalises both halves. The source's other
    # settings are plain flags: critic_hidden 2048,2048, policy_delay 3,
    # adam_b1 0.5, action_insert_layer 0, both learning rates 1e-3.
    crossq: bool = False
    # Adam's beta_1, for every net's optimiser and the temperature's
    # (ops/optim.py; 0.9 there where unset). The megakernel and the native
    # backend hold 0.9 as a constant: another value takes the scan leg on
    # the one and is refused by the other.
    adam_b1: float = 0.9

    # --- SimBa (arXiv 2410.09754; sac only) ---
    # simba: actor and critics are residual nets (models/mlp.py:simba_init):
    # a running-statistics normaliser on the observation (RSNorm), a linear
    # embedding, one pre-LayerNorm residual block per entry of *_hidden
    # (each h -> 4h -> h, so every entry of a net's list is the same h: the
    # width of its residual stream), a post-LayerNorm and the head. The
    # action joins the critics at their input, not normalised
    # (action_insert_layer must be 0). The learner moves the statistics by
    # the moments of the `obs` rows of each update's batch; the policy that
    # leaves the learner carries them folded into its embedding, and is a
    # layered net that no chain of dense layers can stand for
    # (actors/policy.py). The source's other settings are plain flags:
    # weight_decay 1e-2, sac_alpha 1e-2, target_entropy_scale 0.5, both
    # learning rates 1e-4, tau 0.005.
    simba: bool = False
    # Decoupled weight decay (AdamW) on every trained leaf of actor and
    # critics: p <- p - lr * (adam's step + weight_decay * p). Not on the
    # temperature. 0 is plain Adam, program for program. The megakernel and
    # the native backend have no such term: another value takes the scan
    # leg on the one and is refused by the other.
    weight_decay: float = 0.0
    # The automatic entropy target is -target_entropy_scale * dim(A) (+ the
    # action box's log scale, as target_entropy says); 1 is 1812.05905's.
    target_entropy_scale: float = 1.0

    # --- DrQ-v2 (arXiv 2107.09645; DDPG from pixels, twin_critic only) ---
    # pixels: the observation is a stack of byte frames, uint8[C, H, W] (the
    # environment says which: envs/jax_envs.py), and the learner is DrQ-v2's
    # (models/pixels.py, ops/pixels.py, learner.make_learner_step): a shared
    # convolutional encoder of four 3x3 layers of encoder_channels (strides
    # 2, 1, 1, 1) that the CRITIC's loss trains, a random shift of aug_pad
    # pixels on every sampled image inside the update, a LayerNorm-tanh trunk
    # of feature_dim in front of the twin critics' heads and another in front
    # of the actor (on the encoder's features, detached), exploration and
    # target-smoothing noise of one scheduled scale, clipped at
    # target_noise_clip, and Polyak targets for the critics' trunk and heads
    # alone. A ring row holds both images as bytes, four to a float32 word
    # (types.ObsSpec). Device actors only; config.py refuses what this
    # learner does not carry (host workers, prioritised or row-sharded
    # replay, the fused beat, guardrails, bfloat16 compute), each with its
    # ROADMAP item. The source's other settings are plain flags:
    # twin_critic, n_step 3, tau 0.01, target_noise_clip 0.3, both learning
    # rates 1e-4 (8e-5 on its hard tasks), hidden 1024,1024, batch 256.
    pixels: bool = False
    encoder_channels: int = 32
    feature_dim: int = 50
    aug_pad: int = 4
    # The one noise scale's schedule, "initial,final,frames": linear from
    # initial to final over `frames` environment frames, then final
    # (the source's `linear(1.0,0.1,500000)`; 2,000,000 on its hard tasks).
    # Update k of the learner reads it at 4 k frames (the source's action
    # repeat 2 times its 2 agent steps an update: ops/pixels.py's
    # FRAMES_PER_UPDATE), and a rollout at the newest update it was handed
    # (ops/pixels.sigma_at).
    explore_sigma_schedule: str = "1.0,0.1,500000"

    # --- recurrent TD3 (Ni, Eysenbach and Salakhutdinov 2022, arXiv
    # 2110.05038, code twni2016/pomdp-baselines; twin_critic only) ---
    # recurrent: actor and twin critic each carry a memory of their own
    # (models/recurrent.py): embedders of the observation (obs_embed wide),
    # the previous action (action_embed) and the previous reward
    # (reward_embed), ONE LSTM layer of rnn_hidden units over their
    # concatenation, a shortcut embedder of the current input (obs_embed
    # wide: the actor's of o_t, the critic's of [o_t | a]) and heads of
    # actor_hidden / critic_hidden on [h_t | shortcut]; the critic has one
    # LSTM and two heads. A ring row is a WINDOW: the last seq_len steps of
    # one environment's current episode, left-aligned and masked where the
    # episode is younger (types.ObsSpec.steps; ops/exploration.seq_fold),
    # one row written an env step, and an update scans every net's memory
    # over the window from a zero state (learner.make_learner_step's
    # recurrent_update). The device pool carries each environment's policy
    # state (h, c), previous action and reward between steps and zeroes them
    # where an episode ends. Device actors only; _check_recurrent refuses
    # what this learner does not carry. The source's other settings are
    # plain flags: twin_critic, policy_delay 1, target_noise 0.2,
    # target_noise_clip 0.5, tau 0.005, both learning rates 3e-4, batch 64,
    # heads 128,128, exploration gaussian at sigma 0.1.
    recurrent: bool = False
    seq_len: int = 64
    rnn_hidden: int = 128
    obs_embed: int = 32
    action_embed: int = 8
    reward_embed: int = 8

    # --- DMPO (Acme's distributional MPO: arXiv 2006.00979, agents/tf/dmpo;
    # the policy step arXiv 1806.06920 in its decoupled form, 1812.02256) ---
    # mpo: the policy is improved without a gradient through the critic
    # (learner.make_learner_step's mpo_step; ops/losses.py): mpo_samples
    # actions a state drawn from the TARGET policy at s', valued by the
    # TARGET categorical critic (distributional must be on), a softmax over
    # the samples at a learned temperature, and a weighted maximum
    # likelihood fit of the online policy's mean and scale apart, each under
    # a per-dimension KL bound held by a learned multiplier. The critic's
    # target is the mixture of the samples' distributions. Both nets are
    # Acme's LayerNormMLP (models/mlp.lnmlp_init: linear, LayerNorm, tanh,
    # then linear and ELU per further entry of *_hidden), the policy's head a
    # diagonal Gaussian with a softplus scale and no squashing, the critic
    # on [obs | action clipped to the canonical box] (action_insert_layer
    # must be 0). The four dual variables ride TrainState.log_alpha as a
    # small tree under an Adam of their own rate (alpha_opt). The source's
    # other settings are plain flags: distributional, num_atoms 51, n_step 5,
    # batch 256, both learning rates 1e-4, target_update_period 100.
    mpo: bool = False
    mpo_samples: int = 20
    mpo_epsilon: float = 0.1            # KL bound of the E-step's softmax
    mpo_epsilon_penalty: float = 1e-3   # the same for the out-of-box penalty
    mpo_epsilon_mean: float = 2.5e-3    # per-dimension KL bound on the mean
    mpo_epsilon_stddev: float = 1e-6    # per-dimension KL bound on the scale
    mpo_init_log_temperature: float = 10.0
    mpo_init_log_alpha_mean: float = 10.0
    mpo_init_log_alpha_stddev: float = 1000.0
    dual_lr: float = 1e-2               # Adam's rate on the dual variables
    # Targets copied whole from the online nets every target_update_period
    # learner steps (after the update whose count ends a period), in place
    # of the Polyak average: 0 (default) keeps tau's average, program for
    # program. One rule (ops/polyak.target_update), any family of the scan
    # leg with targets may set it; the megakernel and the native backend
    # average only: a period takes the scan leg on the one and is refused
    # by the other.
    target_update_period: int = 0

    # --- replay (SURVEY.md §2 #5/#7) ---
    replay_capacity: int = 1_000_000
    replay_min_size: int = 1_000     # warmup before learning starts
    prioritized: bool = False
    per_alpha: float = 0.6
    per_beta: float = 0.4
    per_beta_final: float = 1.0
    per_eps: float = 1e-6
    # Force the host replay + prefetch pipeline in train_jax instead of the
    # HBM-resident DeviceReplay. The fallback for buffers too large for
    # device memory; the device path (uniform AND prioritized) is the
    # flagship zero-h2d steady state.
    host_replay: bool = False
    # Device-replay placement (replay/device.py; docs/REPLAY_SHARDING.md).
    # "replicated" (default): every device holds an identical copy kept
    # bit-identical via lockstep sync_ship — aggregate capacity equals ONE
    # device's HBM, every ingested row is copied to all N devices, and
    # this mode stays the bit-exact parity oracle. "sharded": the same
    # logical ring partitioned over the mesh's 'data' axis (strided
    # ownership — position p on shard p % N), so per-device storage is
    # capacity/N rows (~N× aggregate capacity at fixed HBM) and each
    # staged row is shipped only to its owner (~1/N landed ingest bytes:
    # replay_ingest_bytes_per_row, not measured on the chip). Sampling
    # draws replica-identical indices and reassembles the minibatch with an owner-masked
    # gather + psum inside the jitted chunk; sampled minibatches are
    # bit-identical to replicated mode. Forces the XLA scan path (the
    # megakernel reads replicated storage whole) and composes with
    # model_axis > 1 (ring on 'data' x params on 'model' — docs/MESH.md);
    # multi-host sharded runs omit replay contents from checkpoints (no
    # single-writer snapshot spans the shards).
    replay_sharding: str = "replicated"
    # Device-replay ingest pipeline (replay/device.py; docs/INGEST.md).
    # A single-process run ships host->HBM off the learner thread (bounded
    # by the staging ring; a full ring blocks the drain — backpressure) so
    # insert dispatch overlaps learner compute; not under strict_sync
    # (row-landing timing would make the sampled stream a function of host
    # scheduling, breaking the bit-identical-two-runs contract) nor on
    # multi-host (rows leave only via the lockstep sync_ship collective).
    # ingest_coalesce caps how many staged blocks fold into one device_put
    # + jitted scatter (power-of-two groups; 1 = the seed's serial
    # block-at-a-time ships).
    ingest_coalesce: int = 8
    # --- unified transfer scheduler (transfer/; docs/TRANSFER.md) ---
    # One dispatch thread owns every host<->device stream — replay-ingest
    # super-blocks, prefetch chunk h2d, learner d2h accounting, and the
    # multi-host lockstep ingest collective — with prioritized work
    # classes fair-queued by bytes so prefetch never starves under an
    # ingest flood (and vice versa). Forced off under strict_sync: the
    # scheduler thread's dispatch timing would make the metrics stream a
    # function of host scheduling. With the scheduler run two policies of
    # its ingest lane: the adaptive coalesce controller
    # (transfer/adaptive.py: the EFFECTIVE cap grows x2, up to
    # ingest_coalesce, while the staging queue trends up and shrinks on
    # dispatch stall; replay contents are bit-identical to the serial path
    # for ANY cap sequence, but the trajectory is wall-clock-driven;
    # single-process shipping only, since the lockstep collective keeps
    # the static cap so every process computes the identical k sequence)
    # and the staged host-buffer pool (transfer/hostbuf.py: the per-ship
    # staging copy recycles long-lived buffers fenced on the consuming
    # insert).
    transfer_scheduler: bool = True
    # Multi-host: run the lockstep sync_ship collective as BACKGROUND
    # beats on the scheduler's ordered lane (replay/device.py
    # sync_ship_begin) instead of blocking the learner thread at every
    # chunk boundary. Lockstep semantics are preserved by the token
    # protocol (docs/TRANSFER.md): pending counts snapshot at beat-issue
    # time, strict FIFO lane, and the learner gates its next dispatch on
    # the previous beat's enqueue. No effect single-process.
    sync_ship_background: bool = True

    # --- batched policy-inference service (serve/; docs/SERVING.md) ---
    # Serve actor workers from one InferenceServer instead of each worker
    # running its own private act(): workers send observations over a
    # bounded mp queue, a dynamic batcher dispatches at serve_max_batch OR
    # serve_max_latency_ms (whichever fires first — TorchBeast's knobs,
    # PAPERS.md arXiv 1910.03552), and actions flow back per worker. Off
    # by default: the per-worker act() path stays the default AND the
    # parity oracle (served actions are bit-identical to it under the
    # numpy serve backend — tests/test_serve.py). Workers that cannot get
    # a served action (overload, stall, dispatch failure) DEGRADE to their
    # local policy mirror for serve_fallback_s instead of blocking — a
    # broken serving stack costs latency, never a deadlock.
    serve_actors: bool = False
    # Dispatch triggers: a collected batch goes out when it reaches
    # serve_max_batch rows or when its oldest request has waited
    # serve_max_latency_ms, whichever comes first.
    serve_max_batch: int = 32
    serve_max_latency_ms: float = 5.0
    # Bounded request queue: submissions past this raise typed
    # ServeOverload (shed + degrade, never unbounded buffering).
    serve_queue: int = 1024
    # Served-client deadline: a worker waits this long for its action
    # before falling back to the local act() path...
    serve_timeout_s: float = 1.0
    # ...and stays on the local path this long before trying the server
    # again (degraded-mode cooldown; counted in serve_client_fallbacks).
    serve_fallback_s: float = 5.0
    # Serve compute backend: "numpy" = the bit-identical parity oracle
    # (row-wise NumpyPolicy — same kernels as the per-worker act());
    # "jax" = device-resident params, one jitted apply over batches padded
    # to the fixed (serve_max_batch, obs_dim) shape (float-tolerance
    # parity, like the learner itself).
    serve_backend: str = "numpy"

    # --- network serving front (serve/front/; docs/SERVING.md §front) ---
    # External ingress over the same Batcher the served actors use:
    # a length-prefixed-frame TCP server plus an HTTP/JSON adapter,
    # versioned policy snapshots with canary promote, and per-tenant QoS.
    # 0 = disabled (the default: serving stays in-process/mp-queue only);
    # any other value binds that port on localhost (0 is also what tests
    # pass programmatically to FrontServer for an ephemeral port — the
    # config knob reserves 0 for "off" and FrontServer itself treats 0 as
    # "pick one", matching the obs/ exporter convention).
    front_port: int = 0
    front_http_port: int = 0
    # Server-side deadline: a request older than this when its batch
    # completes is answered with a typed `timeout` wire error.
    front_timeout_s: float = 2.0
    # Canary split: fraction of traffic deterministically routed to the
    # candidate version while one is staged (crc32(tenant:request_id)
    # bucketing — replayable, not random).
    front_canary_fraction: float = 0.1
    # The live gate needs this many latency samples on BOTH stable and
    # candidate before it can promote (arm on first capture: never
    # promote on thin data).
    front_canary_min_requests: int = 50
    # Allowed relative p95-latency regression of candidate vs stable;
    # past it the canary auto-rolls-back (THRESHOLD's live twin).
    front_canary_threshold: float = 0.5
    # Tenant table: "name:priority[:rate[:burst]];..." — priority 0 is
    # highest (never depth-shed), rate is tokens/s (0 = uncapped),
    # burst defaults to max(1, rate). Unknown tenants get
    # front_default_priority and no rate cap.
    front_tenants: str = ""
    front_default_priority: int = 1
    # Queue-depth fraction where priority shedding begins: the LOWEST
    # priority class sheds at this depth, higher classes at staggered
    # deeper thresholds, priority 0 only at a full queue (typed
    # overload) — the "sheds lowest-priority first" contract.
    front_shed_start: float = 0.5

    # --- device-actor backend (actors/device_pool.py; docs/DEVICE_ACTORS.md) ---
    # Where rollouts run on the jax_tpu path. "host" (default): N worker
    # PROCESSES step CPU envs, OU noise runs in numpy, and rows cross
    # host->HBM through the ingest pipeline — the only option for
    # Gym/Mujoco envs. "device": a Podracer/Anakin-style vectorized actor
    # (PAPERS.md arXiv 2104.06272) — one jitted lax.scan advances
    # device_actor_envs copies of the JAX env (envs/jax_envs.py), the
    # policy mu(s) and per-env OU noise run in the same program, and the
    # transition rows scatter STRAIGHT into DeviceReplay's HBM ring with a
    # donated insert: no host staging, no transfer-scheduler ingest class,
    # zero host<->device bytes on the experience path. Param refresh is a
    # device-side pointer swap from the learner's live params. Requires a
    # JAX env implementation (has_jax_env), validated at parse. The
    # learner keeps its full feature set — PER, guardrails, serving,
    # multi-host — and the host pool can run alongside (num_actors > 0)
    # feeding the same replay.
    actor_backend: str = "host"
    # E: vectorized envs advanced per device-actor chunk (the rollout's
    # vmap width). Thousands are cheap on a TPU — env physics is a few
    # FLOPs per step; CPU tests use small values.
    device_actor_envs: int = 1024
    # K: env steps per rollout dispatch (the lax.scan length); each chunk
    # produces K * device_actor_envs transitions in one program.
    # 0 = auto: 64 on kernel-native TPU backends, 8 elsewhere (mirrors
    # learner_chunk's resolution discipline).
    device_actor_chunk: int = 0

    # --- fused training megastep (parallel/megastep.py; docs/FUSED_BEAT.md) ---
    # Anakin-style fused beat (PAPERS.md arXiv 2104.06272): compile the
    # whole rollout -> ring-scatter -> sample -> K-learner-updates beat
    # into ONE jitted program per loop iteration, so the host dispatches a
    # single program per beat (zero host round-trips inside it) instead of
    # three. Composes the device-actor rollout, the DeviceReplay insert
    # (replicated or sharded), and the learner's XLA-scan sampling chunk —
    # guarded or unguarded: the PR-7 guardrail probe threads through the
    # fused program, so guardrails=True keeps the fast path. "auto"
    # (default): fuse whenever actor_backend='device' on the device-replay
    # path with free-running ratios and the Pallas megakernel inactive
    # (the kernel has no rollout/probe slot inside a larger program);
    # "on": require it (config error when the composition is impossible);
    # "off": always dispatch per phase. Bit-identical to the separate
    # dispatch sequence for fixed seeds (tests/test_megastep.py).
    fused_beat: str = "auto"
    # Compile-once multi-beat superstep (parallel/superstep.py): compose B
    # fused beats inside one donated-carry lax.fori_loop, so an entire
    # epoch — B x (sample+learn, rollout, scatter, guardrail probe) — is a
    # SINGLE XLA program per dispatch and per-beat host Python goes to
    # zero (the full Anakin epoch-as-one-dispatch shape, PAPERS.md arXiv
    # 2104.06272; host-orchestration overhead per arXiv 2012.04210).
    # Stats/health accumulate in a device-side carry with ONE device_get
    # per superstep; multi-host sync_ship/ingest beats still ride BETWEEN
    # supersteps. 1 (default) = today's per-beat dispatch, bit-identical
    # oracle; B > 1 requires the fused beat to be active (fused_beat !=
    # 'off') and produces bit-identical state to B sequential beats
    # (tests/test_superstep.py). Budget/cadence checks run once per
    # superstep, so env-budget overshoot is bounded by B x rows-per-beat.
    superstep_beats: int = 1

    # --- exploration (SURVEY.md §2 #6) ---
    ou_theta: float = 0.15
    ou_sigma: float = 0.2
    ou_dt: float = 1.0
    # The device pool's exploration process (ops/exploration.py). "ou"
    # (default): Ornstein-Uhlenbeck with the three fields above. "gaussian":
    # PQL's mixed exploration (arXiv 2307.12983): environment i of E adds
    # sigma_i * N(0, I) of its own fixed scale, sigma_i spaced evenly from
    # explore_sigma_min (environment 0) to explore_sigma_max (the last), in
    # units of the action box's half-width. Device backend only: host
    # workers beside the pool keep OU.
    exploration: str = "ou"
    explore_sigma_min: float = 0.05
    explore_sigma_max: float = 0.8

    # --- distributed topology ---
    num_actors: int = 1
    # Actor->learner experience transport: "shm" = per-worker C++ SPSC ring
    # in shared memory (native/replay_core.cpp, zero pickling); "queue" =
    # mp.Queue; "auto" = shm when the native toolchain is available.
    transport: str = "auto"
    # Per-worker ring capacity (rows). Sized to absorb a learner-dispatch
    # of production smoothing, not to buffer stalls: a full ring BLOCKS its
    # worker (worker.py flush), mirroring the queue transport's backpressure.
    shm_ring_rows: int = 4096
    # {"native", "jax_tpu"} (BASELINE.json:5): the numpy CPU reference,
    # or the sharded JAX learner with host or on-device actors.
    backend: str = "jax_tpu"
    data_axis: int = -1              # -1: all devices on data axis
    # Tensor-parallel degree over hidden dims (the mesh's 'model' axis).
    # Params + Adam moments shard per the regex rule tables in
    # parallel/partition.py (per-device param+opt HBM / model_axis);
    # composes with sharded replay, device actors, the serve jax backend,
    # and the fused megastep — see docs/MESH.md for the decision table.
    model_axis: int = 1
    # Data-parallel batch semantics for the device-sampling learner paths:
    # True (default) = batch_size is PER-DEVICE — each data-axis device
    # draws its own batch_size rows and the global batch grows with the
    # mesh (grads merge via the sharding-induced AllReduce), so adding
    # chips adds throughput. False = batch_size is the GLOBAL batch sharded
    # ever thinner across devices (round-2 semantics, kept for fixed-batch
    # scaling studies; collective latency swamps compute past ~2 devices).
    scale_batch_with_data: bool = True
    train_every: int = 1             # env steps between learner steps (sync mode)
    # Async ingest rate limiter (the staleness-control knob SURVEY.md §7
    # 'hard parts (b)' calls for): cap drained env steps at
    # replay_min_size + ratio * learner_steps. When actors outpace the
    # learner the rings/queues fill and workers block, throttling the env
    # stepping itself. 0 = free-running async (the reference's semantics).
    max_ingest_ratio: float = 0.0
    # Learner-rate cap (the converse of max_ingest_ratio, and the knob the
    # equal-return quality gate turns): learner steps <= replay_min_size +
    # ratio * env steps. The reference's sync semantics are ratio = 1/
    # train_every; 0 = free-running async (learner as fast as the TPU goes).
    max_learn_ratio: float = 0.0
    # Experiment knob: per-env-step sleep (seconds) inside each worker.
    # 0 = off (production). Nonzero slows env production so the LEARNER can
    # saturate the ratio caps on hosts where it otherwise couldn't — the
    # staleness sweep (docs/EVIDENCE.md §4) needs learner capability >>
    # cap x env rate for a cap to bind at all; on the 1-core CPU host the
    # unthrottled 16-actor config keeps the effective ratio < 1 and every
    # sweep point would silently measure the same thing. Wall-clock only:
    # the algorithmic quantity (grad steps per env step) is unchanged.
    actor_throttle_s: float = 0.0
    # Lockstep debug mode (SURVEY.md §5 race detection): actors run INLINE
    # on the driver thread (actors/sync_pool.py) in deterministic
    # round-robin order, eval runs synchronously, and the wall-clock floors
    # on param refresh / metrics logging are ignored — two runs of the same
    # config produce bit-identical metrics, so any divergence against an
    # async run isolates a race in the async machinery. Requires both
    # ratio gates armed (the drain budget is the deterministic schedule).
    strict_sync: bool = False
    param_refresh_every: int = 1     # learner steps between actor param refresh
    # Wall-clock floor between actor param broadcasts in train_jax. A
    # broadcast must sync the in-flight chunk and round-trip params
    # device->host (cost not measured on the chip); the floor bounds
    # that overhead to a fixed fraction of wall time while
    # param_refresh_every keeps the learner-step semantics.
    param_refresh_interval_s: float = 0.1
    # Learner steps per dispatch (lax.scan / megakernel chunk length) in
    # train_jax. 0 = auto: 800 on kernel-native TPU backends — the length
    # all three benchmark cells run (PERF.md §5: 4.2, 17.5 and 48.5 ms a
    # launch); it has not been swept on the chip — 8 elsewhere (CPU
    # dev/test dispatches stay snappy).
    # Ingest, param refresh, and the env-step budget check all run once per
    # chunk, so the chunk also bounds ingest latency and budget overshoot.
    learner_chunk: int = 0

    # --- precision ---
    compute_dtype: str = "float32"   # bit-comparability oracle needs f32
    # Pallas megakernel: the whole K-step chunk in one kernel launch, params
    # VMEM-resident across the chunk (ops/fused_chunk.py). "auto" uses it on
    # the single-device TPU sample-chunk path whenever the config is in the
    # kernel's envelope; "on" requires it (error if unsupported); "off" never.
    fused_chunk: str = "auto"
    # Megakernel x mesh composition (parallel/learner.py fused-mesh path):
    # on a multi-device DATA-parallel mesh each device runs the megakernel
    # on its own independent minibatch draws for the whole K-step chunk,
    # and float state (params, targets, Adam moments) is AVERAGED across
    # the data axis at chunk boundaries (one params-sized AllReduce per K
    # steps instead of K per-step gradient psums — per-step sync would
    # evict params from VMEM every step and forfeit the kernel's entire
    # HBM-traffic win). This is K-step local SGD: sync semantics differ
    # from the scan path's per-step psum by a bounded O(lr*K) divergence
    # (docs/PERF_NOTES.md has the staleness argument + measured parity).
    # "auto": compose whenever the megakernel is active and the mesh is
    # data-only (model_axis == 1); "off": multi-device meshes always use
    # the scan path (exact per-step sync).
    fused_mesh: str = "auto"

    # --- run control ---
    # Stall watchdog (watchdog.py): if the jax_tpu trainer makes no
    # progress for this many seconds — including during learner
    # construction and the first params d2h, both unbounded blocking
    # device calls — dump every thread's stack and hard-exit(70)
    # instead of hanging silently. 0 = off (tests and
    # interactive runs); production/ladder runs should set ~300.
    watchdog_s: float = 0.0
    total_env_steps: int = 100_000
    eval_every: int = 5_000
    eval_episodes: int = 5
    checkpoint_every: int = 10_000
    checkpoint_dir: str = ""
    # Latest-N retention: a full-replay checkpoint is ~3 GB (1M rows), so
    # keeping every cadence point fills a disk mid-run (round-5 incident:
    # 6.4 GB by 340k steps of a 2M-step Humanoid run). 0 = keep all.
    checkpoint_keep: int = 3
    resume: bool = True              # auto-restore latest checkpoint_dir state
    log_path: str = ""               # JSONL metrics path ("" = stdout only)
    profile_dir: str = ""            # jax.profiler trace dir ("" = off)
    # Flight-recorder tracing (trace.py): when set, train_jax records
    # thread-tagged spans from every hot component (learner phases, ingest
    # shipper, prefetcher, eval/ckpt threads, actor workers) into a
    # preallocated ring and writes Perfetto-loadable Chrome trace JSON
    # here on clean exit, on SIGUSR2, and from the watchdog's stall path
    # (which also drops stall_report.json). "" = off (the span calls are
    # shared no-op context managers). Cheap enough to leave on for every
    # production run — see docs/OBSERVABILITY.md.
    trace_dir: str = ""
    # Ring capacity in events; at steady state ~4 events per learner chunk
    # + shipper/eval activity, 65536 holds tens of minutes of timeline.
    trace_events: int = 65_536
    # Telemetry-plane ingress (obs/; docs/OBSERVABILITY.md §4): when > 0,
    # train_jax starts one stdlib HTTP exporter thread on this port
    # serving /metrics (Prometheus text from the latest JSONL record),
    # /healthz (the typed healthy/degraded/draining state machine the
    # supervisor and canary gate consume), and /trace (on-demand
    # flight-recorder export). Read-only, no auth, binds all interfaces —
    # private networks only. 0 = off (default). Multi-process pods give
    # each process its OWN port (e.g. base + process index).
    obs_port: int = 0

    # --- fault injection & supervised recovery (docs/RESILIENCE.md) ---
    # Deterministic fault schedule (faults.FaultPlan grammar), e.g.
    # --faults='worker:2:crash@5000;worker:0:hang@8000;ckpt:write:ioerror@2'
    # — scripts crashes/hangs/slowdowns/IO errors into actor workers, the
    # ingest shipper, the prefetcher, and the checkpoint writer. Replaces
    # the old one-shot --inject_fault hook (its 'actor:<id>:<step>' form
    # still parses, as a worker crash). "" = no faults (production).
    faults: str = ""
    # Pool monitor: respawn a worker silent past this many seconds
    # (actors/pool.py heartbeats — SURVEY.md §5 'Failure detection').
    heartbeat_timeout_s: float = 30.0
    # Actor-side blind spot (watchdog.py coverage note): respawn a worker
    # that HEARTBEATS but has produced zero experience rows for this many
    # seconds. 0 = off — the default, because legitimate zero-row windows
    # (very long episodes with n-step holdback, heavy backpressure) are
    # config-dependent; chaos runs and production fleets should set it to
    # a few multiples of the expected flush interval.
    actor_no_progress_s: float = 0.0
    # Respawn backoff: the k-th recent failure of the SAME worker slot
    # waits min(base * 2^(k-1), max) seconds before the respawn — a
    # crash-looping worker must not be respawned in a tight loop (every
    # respawn re-pays cold-start cost and can itself re-trigger the
    # boot stampede the heartbeat sentinel exists for).
    respawn_backoff_s: float = 0.5
    respawn_backoff_max_s: float = 30.0
    # Crash-loop circuit breaker: this many failures of the same slot
    # within quarantine_window_s quarantines the slot — the pool logs
    # loudly, stops respawning it, and training continues degraded on the
    # remaining workers (SURVEY.md §5; a stampede of doomed respawns is
    # strictly worse than one missing actor). 0 = breaker off.
    quarantine_respawns: int = 5
    quarantine_window_s: float = 60.0
    # Quarantine probing (docs/RESILIENCE.md): after this cooldown the
    # monitor PROBES a quarantined slot with a single respawn attempt —
    # sustained progress (rows delivered + surviving quarantine_window_s)
    # un-quarantines it (counter actor_unquarantined), any failure during
    # the probe re-quarantines immediately for another cooldown. A
    # half-capacity fleet whose fault was transient (OOM storm, env-server
    # restart) recovers without a run restart. 0 = never probe (the
    # pre-PR-5 behavior: quarantine is permanent for the run's lifetime).
    quarantine_probe_s: float = 300.0
    # Checkpoint write retry (checkpoint.py): transient IO failures retry
    # up to this many times with exponential backoff before surfacing.
    ckpt_write_retries: int = 2
    ckpt_retry_backoff_s: float = 0.5
    # --- numerical-health guardrails (guardrails.py; docs/RESILIENCE.md) ---
    # On-device divergence detection fused into the learner chunk: finite
    # checks on TD targets/grads/updated params plus EWMA z-score anomaly
    # detection on critic loss & grad norm; a bad step's update is DROPPED
    # on device (bad-batch quarantine), non-finite sampled replay rows are
    # recorded for ingest-source attribution, and sustained divergence
    # triggers automatic rollback to the last manifest-valid checkpoint.
    # Off by default: guardrails force the XLA scan path (the Pallas
    # megakernel has no probe slot), add one tiny health-word d2h sync per
    # chunk, and the disabled path is pinned bit-identical to the
    # pre-guardrail programs (tests/test_guardrails.py parity). Turn on
    # for unattended/production runs.
    guardrails: bool = False
    # One-sided z-score threshold for the loss/grad-norm anomaly detector
    # (divergence is always UP). Generous by default: a false skip drops
    # one update; a false rollback costs a checkpoint cadence.
    guardrail_zmax: float = 8.0
    # Clean steps the EWMA absorbs before z-scores arm (early-training
    # loss scale is nonstationary; finite checks are armed from step 1).
    guardrail_warmup_steps: int = 64
    # Rollback trigger: this many anomalous (skipped) learner steps within
    # guardrail_rollback_window steps -> restore the last manifest-valid
    # checkpoint (PR-4 restore walk; pods coordinate the step through the
    # PR-6 election). 0 = detect/skip/quarantine only, never roll back.
    guardrail_rollback_k: int = 8
    guardrail_rollback_window: int = 256
    # Rollback budget: a run that needs more than this many rollbacks (or
    # needs one with no restorable checkpoint) aborts with the documented
    # EXIT_NUMERIC (77) instead of thrashing restore/diverge forever.
    guardrail_max_rollbacks: int = 3
    # LR cooldown on rollback: both learner LRs scale by this factor after
    # a rollback and restore once guardrail_lr_cooldown_steps clean steps
    # pass (each transition costs one XLA recompile, like a support
    # expansion). 1.0 = off.
    guardrail_lr_backoff: float = 0.5
    guardrail_lr_cooldown_steps: int = 2000
    # Ingest-source quarantine: this many non-finite replay rows attributed
    # to the same actor slot quarantine that slot through the pool's
    # breaker machinery (probing un-quarantines it later). 0 = off.
    guardrail_source_offenses: int = 3
    # --- pod resilience (parallel/multihost.py; docs/RESILIENCE.md) ---
    # Deadline on every host-initiated DCN collective (sync_ship beats,
    # the env-budget all-gather, the scheduler's lockstep lane): a
    # collective whose peer died surfaces as a typed PodPeerLost within
    # this many seconds — coordinated clean abort, emergency checkpoint,
    # exit EXIT_POD_DEGRADED (76) — instead of blocking the pod forever.
    # Armed only on multi-process runs (single-process collectives
    # short-circuit, zero overhead); known-long windows (first-chunk XLA
    # compile, support expansion) get the same grant the stall watchdog
    # gets, so compile skew between processes is not read as peer death.
    # Keep it well under watchdog_s where both are armed — peer loss
    # should exit 76 (resumable pod abort), not 70 (wedged device).
    # 0 = off (the pre-PR-6 block-forever behavior).
    pod_collective_timeout_s: float = 60.0
    # One-time startup rendezvous grace (multihost.startup_barrier),
    # deliberately much larger than the steady-state deadline: backend
    # init / import skew under host load is absorbed once at startup
    # instead of false-firing the per-beat deadline (the documented gloo
    # child startup flake, CHANGES.md PR 5).
    pod_startup_grace_s: float = 300.0

    def replace(self, **kwargs) -> "DDPGConfig":
        return dataclasses.replace(self, **kwargs)

    def fault_plan(self):
        """The parsed (seeded) FaultPlan for this run. Parsed on demand —
        validation already ran in __post_init__, so this cannot raise."""
        from distributed_ddpg_tpu.faults import FaultPlan

        return FaultPlan.parse(self.faults, seed=self.seed)

    def resolved_warmup_uniform(self) -> int:
        """Global uniform-warmup env-step budget (see warmup_uniform_steps:
        -1 = auto = replay_min_size for SAC, 0 otherwise)."""
        if self.warmup_uniform_steps >= 0:
            return self.warmup_uniform_steps
        return self.replay_min_size if self.sac else 0

    @classmethod
    def from_flags(cls, argv: Sequence[str]) -> "DDPGConfig":
        """Parse `--key=value` / `--key value` CLI overrides onto the defaults."""
        import argparse

        parser = argparse.ArgumentParser(prog="distributed_ddpg_tpu")
        for field in dataclasses.fields(cls):
            if field.type in ("bool", bool):
                parser.add_argument(
                    f"--{field.name}",
                    type=lambda s: s.lower() in ("1", "true", "yes"),
                    default=field.default,
                )
            elif field.name in ("actor_hidden", "critic_hidden"):
                parser.add_argument(
                    f"--{field.name}",
                    type=lambda s: tuple(int(x) for x in s.split(",")),
                    default=field.default,
                )
            elif field.name in ("v_min", "v_max"):
                # "auto" -> nan sentinel (warmup-derived support sizing).
                parser.add_argument(
                    f"--{field.name}",
                    type=lambda s: float("nan") if s == "auto" else float(s),
                    default=field.default,
                )
            else:
                ftype = {"int": int, "float": float, "str": str}.get(
                    str(field.type), str
                )
                parser.add_argument(f"--{field.name}", type=ftype, default=field.default)
        # Deprecated alias (pre-chaos-harness scripts): --inject_fault's
        # 'actor:<id>:<step>' one-shot crash folds into the --faults plan,
        # whose grammar accepts the legacy form directly.
        parser.add_argument("--inject_fault", type=str, default="")
        args = vars(parser.parse_args(argv))
        legacy = args.pop("inject_fault")
        if legacy:
            args["faults"] = ";".join(filter(None, [args["faults"], legacy]))
        return cls(**args)

    @property
    def redq(self) -> bool:
        """sac with REDQ's ensemble on: another ensemble size than two or a
        drawn in-target subset, or (without crossq, which has a delay of
        its own) a delayed policy. These runs carry `redq_q_spread` and
        `redq_policy_updates` in their records; plain sac and crossq do
        not."""
        return self.sac and (
            self.critic_ensemble != 2
            or self.target_subset != self.critic_ensemble
            or (self.policy_delay > 1 and not self.crossq)
        )

    @property
    def gaussian_head(self) -> bool:
        """Whether the policy's last layer is [mean | scale] (2 * act wide):
        SAC's squashed Gaussian and MPO's plain one."""
        return self.sac or self.mpo

    @property
    def window_steps(self) -> int:
        """Steps a ring row holds: seq_len for a recurrent configuration
        (a row is a window, types.ObsSpec.steps), 0 for a transition row."""
        return self.seq_len if self.recurrent else 0

    @property
    def sigma_schedule(self) -> tuple:
        """explore_sigma_schedule as (initial, final, frames)."""
        init, final, frames = self.explore_sigma_schedule.split(",")
        return float(init), float(final), float(frames)

    @property
    def v_support_auto(self) -> bool:
        """True when the C51 support is auto-sized (v_min/v_max = nan).
        Consumers must resolve concrete bounds (support_auto.initial_bounds)
        before building a learner step — linspace over nan is all-nan."""
        return math.isnan(self.v_min)

    def _check_pixels(self):
        """What the pixel learner needs, and what it refuses: each message
        names the flag and where ROADMAP.md holds the missing piece."""
        if not self.twin_critic or self.policy_delay != 1 or self.target_noise:
            raise ValueError(
                "pixels (DrQ-v2) is the deterministic twin-critic step with "
                "every update moving the actor and a scheduled noise scale: "
                "set twin_critic=True and leave policy_delay at 1 and "
                "target_noise at 0 (explore_sigma_schedule is the scale)"
            )
        if self.action_insert_layer != 0:
            raise ValueError(
                "a pixel critic's heads take [features | action] at their "
                "input: set action_insert_layer=0"
            )
        try:
            init, final, frames = self.sigma_schedule
            ok = init >= 0 and final >= 0 and frames > 0
        except ValueError:
            ok = False
        if not ok:
            raise ValueError(
                "explore_sigma_schedule must read 'initial,final,frames' "
                f"(e.g. 1.0,0.1,2000000), got {self.explore_sigma_schedule!r}"
            )
        if min(self.encoder_channels, self.feature_dim) < 1 or self.aug_pad < 0:
            raise ValueError(
                "encoder_channels and feature_dim must be >= 1 and "
                "aug_pad >= 0"
            )
        if self.backend != "jax_tpu" or self.compute_dtype != "float32":
            raise ValueError(
                "pixels needs backend='jax_tpu' and compute_dtype='float32': "
                "the native learner has no convolution, and the encoder has "
                "no bfloat16 path of its own (the TPU already multiplies "
                "float32 operands in one bfloat16 pass)"
            )
        if self.actor_backend != "device" or self.num_actors > 0:
            raise ValueError(
                "pixels runs device actors only (--actor_backend=device "
                "--num_actors=0): the numpy policy of the host workers "
                "(actors/policy.py) has no convolution, 85 MFLOP an image is "
                "no actor there (ROADMAP.md R7: a convolutional policy for "
                "host workers)"
            )
        if self.prioritized:
            raise ValueError(
                "pixels refuses --prioritized: the PER chunk overwrites the "
                "weight column of rows whose other words are pixel bytes and "
                "cuts them with unpack_batch (ROADMAP.md R1)"
            )
        if self.replay_sharding != "replicated" or self.host_replay:
            raise ValueError(
                "pixels refuses --replay_sharding=sharded and --host_replay: "
                "the row-sharded gather adds float zeros to rows (x + 0.0), "
                "arithmetic on words that hold pixel bytes, and the host "
                "replay packs float observations (ROADMAP.md R7: a ring that "
                "stores a frame once)"
            )
        if self.fused_beat == "on" or self.superstep_beats > 1:
            raise ValueError(
                "pixels refuses --fused_beat=on and superstep_beats > 1: the "
                "fused beat composes the flat rollout and chunk bodies and "
                "has no slot for the convolutional policy's parameters "
                "(ROADMAP.md D1); the loop dispatches per phase"
            )
        if self.guardrails:
            raise ValueError(
                "pixels refuses --guardrails: the row screen reads every "
                "gathered word as a float, and four pixel bytes can spell a "
                "NaN (ROADMAP.md R7)"
            )
        if self.fused_chunk == "on":
            raise ValueError(
                "pixels refuses --fused_chunk=on: the megakernel has no "
                "convolution (ops/fused_chunk.supported); the scan leg runs"
            )
        if self.serve_actors:
            raise ValueError(
                "pixels refuses --serve_actors: the serving engines batch "
                "flat float observations for host workers, and there are "
                "none (ROADMAP.md R5)"
            )

    def _check_recurrent(self):
        """What the recurrent learner needs, and what it refuses, each with
        its reason (ROADMAP.md R7 holds the missing pieces by mechanism)."""
        if not self.twin_critic or self.policy_delay != 1:
            raise ValueError(
                "recurrent is the twin-critic step with every update moving "
                "the actor, as its source trains (arXiv 2110.05038): set "
                "twin_critic=True and leave policy_delay at 1"
            )
        if self.pixels:
            # (every other family is refused beside twin_critic by the
            # families' own rule, before this check runs)
            raise ValueError(
                "recurrent refuses --pixels: the pixel learner's nets are "
                "functions of one row's frames and have no memory; the "
                "recurrent nets (models/recurrent.py) read flat observations"
            )
        if self.action_insert_layer != 0:
            raise ValueError(
                "a recurrent critic takes [obs | action] at its shortcut "
                "embedder's input: set action_insert_layer=0"
            )
        if self.seq_len < 2 or min(
            self.rnn_hidden, self.obs_embed, self.action_embed, self.reward_embed
        ) < 1:
            raise ValueError(
                "seq_len must be >= 2 (a window of one step has no memory) "
                "and rnn_hidden, obs_embed, action_embed and reward_embed "
                ">= 1"
            )
        if self.n_step != 1:
            raise ValueError(
                "recurrent refuses n_step > 1: a window row holds single "
                "steps (o_t, a_t, r_t, d_t) and the update's targets are "
                "per step; an n-step fold inside a window is not built"
            )
        if self.backend != "jax_tpu" or self.compute_dtype != "float32":
            raise ValueError(
                "recurrent needs backend='jax_tpu' and compute_dtype="
                "'float32': the native numpy learner has no LSTM, and the "
                "recurrent nets have no bfloat16 path of their own (the TPU "
                "already multiplies float32 operands in one bfloat16 pass)"
            )
        if self.actor_backend != "device" or self.num_actors > 0:
            raise ValueError(
                "recurrent runs device actors only (--actor_backend=device "
                "--num_actors=0): the host workers' numpy policy "
                "(actors/policy.py KINDS) has no recurrent kind and a worker "
                "carries no policy state between steps (ROADMAP.md R7)"
            )
        if self.exploration != "gaussian":
            raise ValueError(
                "recurrent explores with Gaussian noise on the policy's "
                "action (the source's N(0, 0.1^2)): set --exploration="
                "gaussian with explore_sigma_min = explore_sigma_max = the "
                "scale; the OU process would be a second state between steps"
            )
        if self.serve_actors or self.front_port or self.front_http_port:
            raise ValueError(
                "recurrent refuses --serve_actors and the network front: "
                "both serving engines answer one stateless request at a "
                "time and keep no session's memory (ROADMAP.md R7)"
            )
        if self.prioritized:
            raise ValueError(
                "recurrent refuses --prioritized: a window's TD errors are "
                "per step [B, L] and the PER chunk writes one priority a "
                "row back, and cuts its rows with unpack_batch"
            )
        if self.replay_sharding != "replicated" or self.host_replay:
            raise ValueError(
                "recurrent refuses --replay_sharding=sharded and "
                "--host_replay: only the replicated device ring's uniform "
                "chunk cuts window rows (types.unpack_windows)"
            )
        if self.fused_chunk == "on":
            raise ValueError(
                "recurrent refuses --fused_chunk=on: the megakernel has no "
                "loop over time (ops/fused_chunk.supported says no); the "
                "scan leg runs"
            )
        if self.fused_beat == "on" or self.superstep_beats > 1:
            raise ValueError(
                "recurrent refuses --fused_beat=on and superstep_beats > 1: "
                "the fused beat composes the flat rollout and chunk bodies "
                "and has no slot for the window rows or the policy state; "
                "the loop dispatches per phase"
            )
        if self.guardrails:
            raise ValueError(
                "recurrent refuses --guardrails: the row screen and the "
                "guarded chunk cut rows with unpack_batch, not windows"
            )
        if self.weight_decay or self.target_update_period or self.critic_l2:
            raise ValueError(
                "recurrent refuses --weight_decay, --target_update_period "
                "and --critic_l2: its source is plain Adam with Polyak "
                "targets every update, and the recurrent update traces "
                "nothing else"
            )

    def _check_mpo(self):
        """What the MPO learner needs, and what it refuses, each with its
        reason."""
        if not self.distributional or self.twin_critic or self.sac:
            raise ValueError(
                "mpo (DMPO) values its sampled actions with the categorical "
                "critic: set distributional=True; twin_critic and sac are "
                "other families (a clipped minimum and an entropy term have "
                "no place in the E-step's softmax)"
            )
        if self.pixels:
            raise ValueError(
                "mpo refuses --pixels: the pixel learner is DrQ-v2's "
                "deterministic step and has no sampled-action pass"
            )
        if self.action_insert_layer != 0:
            raise ValueError(
                "an mpo critic takes [obs | clipped action] at its input "
                "(Acme's CriticMultiplexer): set action_insert_layer=0"
            )
        if self.mpo_samples < 2:
            raise ValueError(
                "mpo_samples must be >= 2: a softmax over one sample weights "
                "it 1 whatever its value"
            )
        for knob in (
            "mpo_epsilon", "mpo_epsilon_penalty", "mpo_epsilon_mean",
            "mpo_epsilon_stddev", "dual_lr",
        ):
            if getattr(self, knob) <= 0:
                raise ValueError(f"{knob} must be > 0")
        if self.backend != "jax_tpu":
            raise ValueError(
                "mpo requires backend='jax_tpu': the native numpy learner "
                "has neither LayerNorm nets nor dual variables"
            )
        if self.fused_chunk == "on":
            raise ValueError(
                "mpo refuses --fused_chunk=on: the megakernel has no branch "
                "for a pass on batch x samples rows (ops/fused_chunk."
                "supported says no); the scan leg runs"
            )
        if self.prioritized:
            raise ValueError(
                "mpo refuses --prioritized: the critic's target is a mixture "
                "over drawn actions, so the expectation gap that would be "
                "written back as a priority carries the draw's noise, and "
                "the source replays uniformly"
            )
        if self.actor_backend != "host" or self.fused_beat == "on" or self.superstep_beats > 1:
            raise ValueError(
                "mpo runs host actors only (--actor_backend=host): the "
                "device pool's rollout explores with SAC's squashed draw or "
                "a noise ladder on a tanh policy, and has no plain Gaussian "
                "clipped to the box; the fused beat composes that rollout"
            )
        if self.exploration != "ou":
            raise ValueError(
                "mpo's actors explore by sampling the policy's own Gaussian: "
                "leave --exploration at its default (no noise process is "
                "added to the draw)"
            )

    def __post_init__(self):
        if self.backend not in ("native", "jax_tpu"):
            raise ValueError(
                f"backend must be 'native' or 'jax_tpu', got {self.backend!r}"
                " (envs, replay and learner all on the chip is "
                "backend='jax_tpu' with actor_backend='device', and "
                "fused_beat for one program a beat)"
            )
        if self.n_step < 1:
            raise ValueError("n_step must be >= 1")
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"compute_dtype must be 'float32' or 'bfloat16', got "
                f"{self.compute_dtype!r}"
            )
        if self.compute_dtype == "bfloat16" and self.backend == "native":
            raise ValueError(
                "compute_dtype='bfloat16' requires a JAX backend: the "
                "native numpy learner is the f32 bit-comparability oracle"
            )
        if self.fused_chunk not in ("auto", "on", "off"):
            raise ValueError(
                f"fused_chunk must be 'auto', 'on', or 'off', got "
                f"{self.fused_chunk!r}"
            )
        if self.fused_mesh not in ("auto", "off"):
            raise ValueError(
                f"fused_mesh must be 'auto' or 'off', got {self.fused_mesh!r}"
            )
        if self.ingest_coalesce < 1:
            raise ValueError("ingest_coalesce must be >= 1")
        if self.replay_sharding not in ("replicated", "sharded"):
            raise ValueError(
                f"replay_sharding must be 'replicated' or 'sharded', got "
                f"{self.replay_sharding!r}"
            )
        if self.replay_sharding == "sharded":
            if self.backend != "jax_tpu":
                raise ValueError(
                    "replay_sharding='sharded' partitions the DeviceReplay "
                    "HBM ring over the jax_tpu mesh; the native backend "
                    "has no sharded ring"
                )
            if self.host_replay:
                raise ValueError(
                    "replay_sharding='sharded' shards the DEVICE replay; "
                    "host_replay has no device ring to shard — disable one"
                )
            if self.fused_chunk == "on":
                raise ValueError(
                    "replay_sharding='sharded' forces the XLA scan path "
                    "(the Pallas megakernel reads replicated storage "
                    "whole) — incompatible with fused_chunk='on'; use "
                    "'auto' (degrades to scan) or 'off'"
                )
            if self.data_axis > 0:
                # Mesh-dependent alignment checks run again at replay
                # construction with the ACTUAL device count; with an
                # explicit data_axis they can fail fast at parse.
                if self.replay_capacity % self.data_axis:
                    raise ValueError(
                        f"replay_capacity {self.replay_capacity} must "
                        f"divide evenly over data_axis={self.data_axis} "
                        "shards (replay_sharding='sharded')"
                    )
                if self.actor_backend == "device":
                    from distributed_ddpg_tpu.actors.device_pool import (
                        resolve_device_actor_chunk,
                    )

                    rows = (
                        self.device_actor_envs
                        * resolve_device_actor_chunk(self)
                    )
                    if rows % self.data_axis:
                        raise ValueError(
                            f"one device-actor chunk produces {rows} rows, "
                            f"which do not divide over data_axis="
                            f"{self.data_axis} replay shards — sharded "
                            "mode requires every insert_device_rows "
                            "scatter to move a multiple of the shard "
                            "count (keeps the ring pointer shard-aligned)."
                            " Adjust device_actor_envs/device_actor_chunk"
                        )
        # --- tensor parallelism (model_axis > 1; parallel/partition.py,
        # docs/MESH.md). The composition matrix: TP is LEGAL with sharded
        # replay (ring on 'data' x params on 'model'), device actors, the
        # serve jax backend, and the fused megastep; the genuine
        # rejections below each name the knob to flip. ---
        if self.model_axis < 1:
            raise ValueError(
                f"model_axis must be >= 1, got {self.model_axis} (1 = "
                "data-parallel only)"
            )
        if self.model_axis > 1:
            if self.backend == "native":
                raise ValueError(
                    "model_axis > 1 shards params over a jax mesh; the "
                    "native numpy backend has no mesh — use "
                    "backend='jax_tpu', or set model_axis=1"
                )
            if self.fused_chunk == "on":
                raise ValueError(
                    "model_axis > 1 shards the param tensors the Pallas "
                    "megakernel needs VMEM-whole — incompatible with "
                    "fused_chunk='on'; use fused_chunk='auto' (degrades "
                    "to the XLA scan path) or 'off', or set model_axis=1"
                )
            for knob in ("actor_hidden", "critic_hidden"):
                bad = [
                    d for d in getattr(self, knob)
                    if d % self.model_axis != 0
                ]
                if bad:
                    raise ValueError(
                        f"model_axis={self.model_axis} cannot shard "
                        f"{knob}={tuple(getattr(self, knob))}: hidden "
                        f"dim(s) {bad} do not divide the model axis, so "
                        "every layer would silently replicate and TP "
                        f"would buy nothing — pick {knob} dims divisible "
                        f"by {self.model_axis}, or lower model_axis"
                    )
        if self.policy_delay < 1:
            raise ValueError("policy_delay must be >= 1")
        if self.target_noise < 0 or self.target_noise_clip < 0:
            raise ValueError("target_noise/target_noise_clip must be >= 0")
        if not self.twin_critic and (
            (self.policy_delay > 1 and not self.sac) or self.target_noise > 0
        ):
            raise ValueError(
                "policy_delay is consumed only by the twin-critic and sac "
                "steps and target_noise only by the twin-critic step — set "
                "twin_critic=True (or sac=True for policy_delay) or they "
                "would silently do nothing"
            )
        if not 1 <= self.target_subset <= self.critic_ensemble:
            raise ValueError(
                f"target_subset ({self.target_subset}) draws distinct "
                f"critics out of critic_ensemble ({self.critic_ensemble}): "
                "needs 1 <= target_subset <= critic_ensemble"
            )
        if not self.sac and (self.critic_ensemble, self.target_subset) != (2, 2):
            raise ValueError(
                "critic_ensemble/target_subset are consumed only by the sac "
                "step (REDQ) — set sac=True or they would silently do nothing"
            )
        v_min_auto = math.isnan(self.v_min)
        v_max_auto = math.isnan(self.v_max)
        if v_min_auto != v_max_auto:
            raise ValueError(
                "v_min/v_max auto-sizing derives BOTH bounds from the same "
                "warmup statistics — set both to 'auto' or neither"
            )
        if v_min_auto and not self.distributional:
            raise ValueError(
                "v_min/v_max='auto' sizes the distributional critic's "
                "support; it requires distributional=True"
            )
        if v_min_auto and not 0.0 < self.gamma < 1.0:
            raise ValueError(
                f"v_min/v_max='auto' needs 0 < gamma < 1 (got {self.gamma}): "
                "the sizing bound r/(1-gamma^n) blows up at gamma=1, and 51 "
                "atoms over a near-infinite range cannot resolve real "
                "returns — pass concrete bounds for undiscounted setups"
            )
        if not v_min_auto and self.distributional and self.v_min >= self.v_max:
            raise ValueError(
                f"v_min ({self.v_min}) must be < v_max ({self.v_max})"
            )
        if self.twin_critic and self.distributional:
            raise ValueError(
                "twin_critic (TD3) and distributional (D4PG) are separate "
                "algorithm families; enable one"
            )
        if self.sac and (self.twin_critic or self.distributional):
            raise ValueError(
                "sac is its own algorithm family (it builds its twin-critic "
                "ensemble internally); disable twin_critic/distributional"
            )
        if self.crossq and not self.sac:
            raise ValueError(
                "crossq is sac without target networks (joint batch-normalised "
                "critic pass) — set sac=True or it would silently do nothing"
            )
        if self.crossq and self.backend == "native":
            raise ValueError(
                "crossq requires a JAX backend: the native numpy learner has "
                "no batch normalisation and always carries target networks"
            )
        if self.crossq and (self.critic_ensemble, self.target_subset) != (2, 2):
            raise ValueError(
                "crossq reads its target from the joint pass of its own twin "
                "critics: there are no target critics to draw an ensemble "
                "subset from — leave critic_ensemble/target_subset at 2"
            )
        if self.simba:
            if not self.sac or self.crossq or self.redq:
                raise ValueError(
                    "simba is plain sac (twin critics, targets, no delay) on "
                    "residual nets — set sac=True and leave crossq and the "
                    "REDQ knobs off"
                )
            if self.action_insert_layer != 0:
                raise ValueError(
                    "a simba critic takes the action at its input, beside "
                    "the normalised observation: set action_insert_layer=0"
                )
            for knob in ("actor_hidden", "critic_hidden"):
                if len(set(getattr(self, knob))) != 1:
                    raise ValueError(
                        f"simba reads {knob} as one residual block per entry, "
                        "all of the stream's one width (512,512 is two blocks "
                        f"of 512 <-> 2048); got {tuple(getattr(self, knob))}"
                    )
        if self.pixels:
            self._check_pixels()
        if self.mpo:
            self._check_mpo()
        if self.recurrent:
            self._check_recurrent()
        if self.target_update_period < 0:
            raise ValueError(
                "target_update_period must be >= 0 (0 = Polyak with tau)"
            )
        if self.target_update_period and (
            self.crossq or self.pixels or self.simba
            or (self.twin_critic and self.policy_delay > 1)
        ):
            raise ValueError(
                "target_update_period copies target networks whole, on the "
                "learner's step count: crossq has none, the pixel learner's "
                "target holds a part of its critic, a residual net's target "
                "takes the input statistics on every update, and a delayed "
                "twin-critic step moves its targets on the actor's schedule "
                "(ROADMAP.md R10)"
            )
        if self.target_update_period and self.backend == "native":
            raise ValueError(
                "target_update_period is read by the jitted step "
                "(ops/polyak.target_update) only: the native backend "
                "averages its targets with tau"
            )
        if self.target_update_period and self.fused_chunk == "on":
            raise ValueError(
                "target_update_period refuses --fused_chunk=on: the "
                "megakernel averages its targets with tau on every update"
            )
        if self.weight_decay < 0:
            raise ValueError("weight_decay must be >= 0 (0 = plain Adam)")
        if self.weight_decay and self.backend == "native":
            raise ValueError(
                "weight_decay is read by the tree-level Adam (ops/optim.py) "
                "only: the native backend's formulas have no such term"
            )
        if self.target_entropy_scale <= 0:
            raise ValueError("target_entropy_scale must be > 0")
        if not 0.0 <= self.adam_b1 < 1.0:
            raise ValueError("adam_b1 must be in [0, 1)")
        if self.adam_b1 != 0.9 and self.backend == "native":
            raise ValueError(
                "adam_b1 is read by the tree-level Adam (ops/optim.py) only: "
                "the native backend's formulas hold 0.9 as a constant"
            )
        if self.sac and self.backend == "native":
            raise ValueError(
                "sac requires a JAX backend: the native numpy learner is "
                "the plain-DDPG bit-comparability oracle"
            )
        if self.sac_alpha <= 0:
            raise ValueError("sac_alpha must be > 0 (it is exp(log_alpha))")
        if self.sac_log_std_min >= self.sac_log_std_max:
            raise ValueError("sac_log_std_min must be < sac_log_std_max")
        if self.twin_critic and self.backend == "native":
            raise ValueError(
                "twin_critic requires a JAX backend: the native numpy "
                "learner is the plain-DDPG bit-comparability oracle"
            )
        if self.max_ingest_ratio < 0:
            raise ValueError("max_ingest_ratio must be >= 0 (0 = unlimited)")
        if self.learner_chunk < 0:
            raise ValueError("learner_chunk must be >= 0 (0 = auto)")
        if self.checkpoint_keep < 0:
            raise ValueError("checkpoint_keep must be >= 0 (0 = keep all)")
        if self.max_learn_ratio < 0:
            raise ValueError("max_learn_ratio must be >= 0 (0 = unlimited)")
        if self.actor_throttle_s < 0:
            raise ValueError("actor_throttle_s must be >= 0 (0 = off)")
        if self.strict_sync:
            if self.backend != "jax_tpu":
                raise ValueError(
                    "strict_sync is a train_jax (jax_tpu backend) debug "
                    "mode; the native backend is already single-threaded "
                    "and deterministic"
                )
            if self.max_learn_ratio <= 0 or self.max_ingest_ratio <= 0:
                raise ValueError(
                    "strict_sync derives its deterministic ingest schedule "
                    "from the ratio gates; set max_learn_ratio and "
                    "max_ingest_ratio (1.0 each = the reference's "
                    "synchronous 1:1 schedule)"
                )
            if self.host_replay:
                raise ValueError(
                    "strict_sync requires the device replay path: the host "
                    "prefetch thread samples concurrently with ingest, "
                    "which is exactly the nondeterminism this mode removes"
                )
        if self.warmup_uniform_steps < -1:
            raise ValueError(
                "warmup_uniform_steps must be >= -1 (-1 = auto, 0 = off)"
            )
        if (
            self.max_learn_ratio > 0
            and self.max_ingest_ratio > 0
            and self.max_learn_ratio * self.max_ingest_ratio < 1.0
        ):
            raise ValueError(
                "max_learn_ratio * max_ingest_ratio < 1 livelocks: each "
                "counter waits on the other and neither allowance can ever "
                "open. With product >= 1 (e.g. both 1.0 — the equal-return "
                "gate pinning ~1 grad step per env step from BOTH sides) "
                "the two advance together at the slower side's pace."
            )
        if self.param_refresh_interval_s < 0:
            raise ValueError("param_refresh_interval_s must be >= 0")
        if self.serve_max_batch < 1:
            raise ValueError("serve_max_batch must be >= 1")
        if self.serve_max_latency_ms < 0:
            raise ValueError("serve_max_latency_ms must be >= 0")
        if self.serve_queue < 1:
            raise ValueError("serve_queue must be >= 1")
        if self.serve_timeout_s <= 0:
            raise ValueError("serve_timeout_s must be > 0")
        if self.serve_fallback_s < 0:
            raise ValueError("serve_fallback_s must be >= 0")
        if self.serve_backend not in ("numpy", "jax"):
            raise ValueError(
                f"serve_backend must be 'numpy' or 'jax', got "
                f"{self.serve_backend!r}"
            )
        if self.serve_actors:
            if self.backend != "jax_tpu":
                raise ValueError(
                    "serve_actors serves the actor POOL (jax_tpu backend); "
                    "the native backend has no worker fleet to serve"
                )
            if self.strict_sync:
                raise ValueError(
                    "serve_actors is incompatible with strict_sync: batch "
                    "composition and dispatch timing are wall-clock-driven, "
                    "which breaks the bit-identical-two-runs contract"
                )
            # SAC is served too (PR 20): the server holds per-client
            # sampling keys derived from (seed, tenant, request_id) and
            # returns already-sampled actions (serve/server.py `sample`;
            # docs/SERVING.md 'SAC serve head') — the old rejection of
            # sac + serve_actors is lifted.
        if self.front_port < 0 or self.front_port > 65535:
            raise ValueError("front_port must be in [0, 65535] (0 = off)")
        if self.front_http_port < 0 or self.front_http_port > 65535:
            raise ValueError(
                "front_http_port must be in [0, 65535] (0 = off)"
            )
        if (
            self.front_port
            and self.front_http_port
            and self.front_port == self.front_http_port
        ):
            raise ValueError(
                "front_port and front_http_port must differ: the frame "
                "server and the HTTP adapter each bind their own socket"
            )
        if self.front_timeout_s <= 0:
            raise ValueError("front_timeout_s must be > 0")
        if not 0.0 < self.front_canary_fraction < 1.0:
            raise ValueError(
                "front_canary_fraction must be in (0, 1): 0 would starve "
                "the candidate of gate samples forever, 1 would route ALL "
                "traffic through an unproven version"
            )
        if self.front_canary_min_requests < 1:
            raise ValueError("front_canary_min_requests must be >= 1")
        if self.front_canary_threshold <= 0:
            raise ValueError("front_canary_threshold must be > 0")
        if self.front_default_priority < 0:
            raise ValueError("front_default_priority must be >= 0")
        if not 0.0 < self.front_shed_start <= 1.0:
            raise ValueError("front_shed_start must be in (0, 1]")
        if self.front_tenants:
            # Fail fast at parse, not at first shed: a typo'd tenant
            # table discovered mid-run would silently misprioritize.
            from distributed_ddpg_tpu.serve.front.qos import parse_tenants

            parse_tenants(self.front_tenants)
        if (self.front_port or self.front_http_port) and not self.serve_actors:
            raise ValueError(
                "the network front rides the serve subsystem's "
                "InferenceServer: set serve_actors=True (docs/SERVING.md "
                "'Network front')"
            )
        if self.actor_backend not in ("host", "device"):
            raise ValueError(
                f"actor_backend must be 'host' or 'device', got "
                f"{self.actor_backend!r}"
            )
        if self.device_actor_envs < 1:
            raise ValueError("device_actor_envs must be >= 1")
        if self.exploration not in ("ou", "gaussian"):
            raise ValueError(
                f"exploration must be 'ou' or 'gaussian', got "
                f"{self.exploration!r}"
            )
        if self.exploration == "gaussian":
            if self.actor_backend != "device" or self.sac:
                raise ValueError(
                    "exploration='gaussian' is the device pool's ladder of "
                    "per-environment scales (ops/exploration.py): it needs "
                    "actor_backend='device', and SAC samples its own policy"
                )
            if not 0.0 <= self.explore_sigma_min <= self.explore_sigma_max:
                raise ValueError(
                    "explore_sigma_min/explore_sigma_max must satisfy "
                    "0 <= min <= max"
                )
        if self.device_actor_chunk < 0:
            raise ValueError("device_actor_chunk must be >= 0 (0 = auto)")
        if self.num_actors < 0 or (
            self.num_actors == 0 and self.actor_backend != "device"
        ):
            raise ValueError(
                "num_actors must be >= 1 (0 is allowed only with "
                "actor_backend='device', where the on-device rollout loop "
                "is the experience source and the host pool runs empty)"
            )
        from distributed_ddpg_tpu.envs.registry import DEVICE_ONLY

        if (
            self.env_id in DEVICE_ONLY
            and (self.actor_backend != "device" or self.num_actors > 0)
        ):
            raise ValueError(
                f"{self.env_id!r} has JAX dynamics only "
                "(envs/jax_envs.py): a host worker cannot step it — use "
                "actor_backend='device' with num_actors=0"
            )
        if self.actor_backend == "device":
            if self.backend != "jax_tpu":
                raise ValueError(
                    "actor_backend='device' runs the vectorized rollout "
                    "loop inside the jax_tpu trainer; the native backend "
                    "has no device — use backend='jax_tpu'"
                )
            # Lazy import: jax_envs pulls in jax, which config parsing must
            # not pay for on the (default) host path.
            from distributed_ddpg_tpu.envs.jax_envs import (
                _JAX_ENVS,
                has_jax_env,
            )

            if not has_jax_env(self.env_id):
                raise ValueError(
                    f"actor_backend='device' needs an on-device (JAX) "
                    f"implementation of {self.env_id!r}; available: "
                    f"{sorted(set(_JAX_ENVS))} — keep actor_backend='host' "
                    "for Gym/Mujoco envs (docs/DEVICE_ACTORS.md)"
                )
            if (
                getattr(_JAX_ENVS[self.env_id], "obs_dtype", "float32") == "uint8"
            ) != self.pixels:
                raise ValueError(
                    f"{self.env_id!r} and pixels={self.pixels}: an "
                    "environment of byte frames needs --pixels=true (DrQ-v2's "
                    "learner, models/pixels.py), and that learner reads "
                    "nothing else"
                )
            if self.serve_actors:
                raise ValueError(
                    "serve_actors batches host workers' act() requests; "
                    "device actors never call act() on the host — mu(s) "
                    "runs inside the rollout program. Disable serve_actors "
                    "(or serve a host pool alongside via actor_backend="
                    "'host')"
                )
            if self.host_replay:
                raise ValueError(
                    "actor_backend='device' scatters rollout rows "
                    "directly into DeviceReplay's HBM ring; host_replay "
                    "has no device ring to insert into — disable one"
                )
            if self.strict_sync:
                raise ValueError(
                    "strict_sync's lockstep schedule is defined over the "
                    "host pool's deterministic drain budget; device-actor "
                    "chunks dispatch outside it — use actor_backend='host' "
                    "for lockstep debugging"
                )
            from distributed_ddpg_tpu.actors.device_pool import (
                resolve_device_actor_chunk,
            )

            rows = self.device_actor_envs * resolve_device_actor_chunk(self)
            if rows > self.replay_capacity:
                raise ValueError(
                    f"one device-actor chunk produces {rows} rows "
                    f"(device_actor_envs={self.device_actor_envs} x "
                    f"chunk {resolve_device_actor_chunk(self)}) — more "
                    f"than replay_capacity={self.replay_capacity}: the "
                    "scatter insert would write duplicate ring positions "
                    "in unspecified order. Shrink the chunk/env count or "
                    "grow the replay"
                )
        if self.fused_beat not in ("auto", "on", "off"):
            raise ValueError(
                f"fused_beat must be 'auto', 'on', or 'off', got "
                f"{self.fused_beat!r}"
            )
        if self.fused_beat == "on":
            # The fused megastep composes the device-actor rollout, the
            # device-replay insert, and the learner chunk into one program
            # (docs/FUSED_BEAT.md); every leg must exist. The device-actor
            # validation above already rejects serve_actors,
            # host_replay, and strict_sync for actor_backend='device', so
            # those combinations fail through their own messages.
            if self.backend != "jax_tpu":
                raise ValueError(
                    "fused_beat='on' fuses the jax_tpu training loop; the "
                    "native backend has no device programs"
                )
            if self.actor_backend != "device":
                raise ValueError(
                    "fused_beat='on' needs the on-device rollout leg "
                    "(actor_backend='device'): host actor processes step "
                    "envs outside XLA and cannot be compiled into the "
                    "beat — use the dispatch-per-phase loop for host "
                    "actors"
                )
            if self.fused_chunk == "on":
                raise ValueError(
                    "fused_beat='on' composes the XLA scan sampling chunk "
                    "(the Pallas megakernel has no rollout/probe slot "
                    "inside a larger traced program) — incompatible with "
                    "fused_chunk='on'; use 'auto' or 'off'"
                )
            if self.max_ingest_ratio > 0.0 or self.max_learn_ratio > 0.0:
                raise ValueError(
                    "fused_beat='on' fixes the rollout:learn ratio inside "
                    "one program (device_actor_envs x chunk rows per "
                    "learner_chunk steps, every beat); the "
                    "max_ingest_ratio/max_learn_ratio gates need "
                    "independently dispatchable phases to throttle — "
                    "disable the gates or use fused_beat='auto'/'off'"
                )
        if self.superstep_beats < 1:
            raise ValueError(
                f"superstep_beats must be >= 1, got {self.superstep_beats}"
            )
        if self.superstep_beats > 1 and self.fused_beat == "off":
            raise ValueError(
                "superstep_beats > 1 composes B FUSED beats into one "
                "lax.fori_loop program (parallel/superstep.py) — it has "
                "no unfused dispatch to wrap; use fused_beat='auto'/'on' "
                "or superstep_beats=1"
            )
        # Fail fast on fault-grammar typos: a bad spec must die at config
        # parse, not hours later when the fault was scheduled to fire.
        from distributed_ddpg_tpu.faults import FaultPlan

        FaultPlan.parse(self.faults, seed=self.seed)
        if self.heartbeat_timeout_s <= 0:
            raise ValueError("heartbeat_timeout_s must be > 0")
        if self.actor_no_progress_s < 0:
            raise ValueError("actor_no_progress_s must be >= 0 (0 = off)")
        if self.respawn_backoff_s < 0 or self.respawn_backoff_max_s < 0:
            raise ValueError("respawn backoff values must be >= 0")
        if self.quarantine_respawns < 0:
            raise ValueError("quarantine_respawns must be >= 0 (0 = off)")
        if self.quarantine_window_s <= 0:
            raise ValueError("quarantine_window_s must be > 0")
        if self.quarantine_probe_s < 0:
            raise ValueError("quarantine_probe_s must be >= 0 (0 = off)")
        if self.ckpt_write_retries < 0:
            raise ValueError("ckpt_write_retries must be >= 0")
        if self.ckpt_retry_backoff_s < 0:
            raise ValueError("ckpt_retry_backoff_s must be >= 0")
        if self.guardrails:
            if self.backend != "jax_tpu":
                raise ValueError(
                    "guardrails instrument the sharded-learner chunk "
                    "programs (jax_tpu backend); the native backend has "
                    "no probe slot"
                )
            if self.fused_chunk == "on":
                raise ValueError(
                    "guardrails=True forces the XLA scan path (the Pallas "
                    "megakernel has no health-probe slot) — incompatible "
                    "with fused_chunk='on'; use 'auto' (degrades to scan) "
                    "or 'off'"
                )
        if self.guardrail_zmax <= 0:
            raise ValueError("guardrail_zmax must be > 0")
        if self.guardrail_warmup_steps < 1:
            raise ValueError("guardrail_warmup_steps must be >= 1")
        if self.guardrail_rollback_k < 0:
            raise ValueError(
                "guardrail_rollback_k must be >= 0 (0 = never roll back)"
            )
        if self.guardrail_rollback_window < 1:
            raise ValueError("guardrail_rollback_window must be >= 1")
        if self.guardrail_max_rollbacks < 0:
            raise ValueError("guardrail_max_rollbacks must be >= 0")
        if not 0.0 < self.guardrail_lr_backoff <= 1.0:
            raise ValueError(
                "guardrail_lr_backoff must be in (0, 1] (1.0 = off)"
            )
        if self.guardrail_lr_cooldown_steps < 1:
            raise ValueError("guardrail_lr_cooldown_steps must be >= 1")
        if self.guardrail_source_offenses < 0:
            raise ValueError(
                "guardrail_source_offenses must be >= 0 (0 = off)"
            )
        if self.pod_collective_timeout_s < 0:
            raise ValueError("pod_collective_timeout_s must be >= 0 (0 = off)")
        if self.pod_startup_grace_s < 0:
            raise ValueError("pod_startup_grace_s must be >= 0")
        if self.trace_events < 16:
            raise ValueError("trace_events must be >= 16")
        if not 0 <= self.obs_port < 65536:
            raise ValueError(
                f"obs_port must be 0 (off) or a valid TCP port, "
                f"got {self.obs_port}"
            )
        if self.transport not in ("auto", "shm", "queue"):
            raise ValueError(
                f"transport must be 'auto', 'shm', or 'queue', got "
                f"{self.transport!r}"
            )
        if not 0 <= self.action_insert_layer <= len(self.critic_hidden):
            raise ValueError(
                f"action_insert_layer={self.action_insert_layer} out of range "
                f"for critic with {len(self.critic_hidden) + 1} layers"
            )
