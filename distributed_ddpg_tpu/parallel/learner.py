"""The sharded TPU learner (SURVEY.md §7 step 5; BASELINE.json:5's
"one pmap'd learner step replaces N separate backward passes" — realized
with the modern jit+sharding idiom instead of pmap).

Two execution modes over the same pure step function (learner.py):

- "auto" (default): `jax.jit` with NamedSharding in/out specs over the
  (data, model) mesh. Batches shard over 'data'; params/opt-state replicate
  (or TP-shard over 'model', mesh.py). XLA's SPMD partitioner inserts the
  gradient AllReduce over ICI — the collective that replaces the
  reference's async gRPC parameter-server push/pull (SURVEY.md §3.3).
  Where the scan chunk reduces over more than one chip and the TPU's
  compiler builds it, `chunk_program` hands jit two of that compiler's
  options (`mesh_compiler_options`, PR 45; no flag, and nothing on one chip
  or off the TPU): `xla_enable_async_all_reduce` and
  `xla_tpu_enable_async_collective_fusion_fuse_all_reduce`. With them the
  scheduler sees each update's gradient all-reduce as a start and a done it
  may place other work between. What libtpu 0.0.34 makes of that on a v5e
  (PERF.md §5, §6 PR 45): it can cut only a ONE-operand all-reduce into
  steps that ride other fusions, and the partitioner's reduce here is a
  tuple of the critics' leaves, the actor's and `mean_lp` (the combiner
  joins them), so each is written back as a plain all-reduce
  (`async_collective_name` stays on it) and the wire's 39.9 us an update
  stay exposed; the schedule it reached on the way fuses the update's own
  compute 2.8 us an update shorter, which is the whole gain. One flat
  buffer a net under `shard_map` does get the steps, and loses: the
  flatten costs 20 us an update and the fusions that carry a step run 14 us
  longer to hide 9. Tried and dropped, each without effect on the compiled
  text: `xla_tpu_enable_async_collective_fusion`, `..._multiple_steps`,
  `xla_tpu_overlap_compute_collective_tc`,
  `xla_tpu_enable_data_parallel_all_reduce_opt`,
  `xla_tpu_data_parallel_opt_different_sized_ops`.
- "explicit": `jax.shard_map` over the 'data' axis with a hand-written
  `jax.lax.pmean` in the step (axis_name plumbed through
  make_learner_step). Data-parallel only; exists to make the collective
  visible/testable and as the escape hatch if auto partitioning ever
  mis-schedules.

Both modes expose `run_chunk`: K learner steps per dispatch via `lax.scan`
over a stacked [K, B, ...] super-batch. One dispatch per K steps amortizes
the per-dispatch host cost; the donated TrainState never leaves HBM between
steps.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import (
    StepOutput,
    chunk_metrics,
    chunk_noise,
    draws_noise,
    init_train_state,
    make_learner_step,
    metric_keys,
    noise_base_key,
    noise_per_row,
)
from distributed_ddpg_tpu.models import pixels as pixnet
from distributed_ddpg_tpu.models.mlp import fold_norm
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.types import (
    Batch,
    ObsSpec,
    OptState,
    TrainState,
    pack_batch_np,
    packed_width,
    unpack_windows,
    unpack_batch,
)

def _ingest_lock(device_replay):
    """The replay's dispatch lock (replay/device.py): chunk dispatch must
    not interleave with the async ingest shipper's donate-and-swap of
    storage (a donated-away buffer read mid-swap is a deleted-array
    error), and the PER read -> dispatch -> set_per_state sequence must be
    atomic against shipper priority stamps (a stamp landing inside that
    window would be overwritten and leave fresh rows at priority 0).
    Dispatch is async, so the hold time is the enqueue, not the compute."""
    return getattr(device_replay, "dispatch_lock", None) or contextlib.nullcontext()


def resolve_learner_chunk(config: DDPGConfig) -> int:
    """Production learner steps-per-dispatch: config.learner_chunk when set,
    else the defaults — 800 on kernel-native TPU backends (the length all
    three benchmark cells run, 4.2, 17.5 and 48.5 ms a launch, PERF.md
    §5; not swept on the chip), 8 elsewhere (CPU scan dispatches in
    dev/test stay snappy). Every caller resolves through here, so the
    trainer and whatever measures it run the same program (VERDICT.md
    round-2 Weak #3)."""
    if config.learner_chunk > 0:
        return config.learner_chunk
    from distributed_ddpg_tpu.ops.fused_chunk import runs_native

    return 800 if runs_native() else 8


# What the TPU compiler is told for a scan chunk over a data mesh, beside
# jit's own arguments: the per-update gradient all-reduces as asynchronous
# instructions its scheduler may run other operations beside. The only two
# of MaxText's data-parallel family that change this program's text
# (libtpu 0.0.34; PERF.md §5 has each option tried and what it did):
_MESH_COMPILER_OPTIONS = {
    # an all-reduce becomes a start and a done the scheduler places apart
    "xla_enable_async_all_reduce": True,
    # and its steps may ride the fusions between them
    "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": True,
}


def mesh_compiler_options(data_size: int, native: bool) -> Optional[dict]:
    """The `compiler_options` a scan chunk program is jitted with: the two
    above where the program reduces gradients over more than one chip and
    the TPU's compiler builds it; None, and jit is handed no such argument,
    on one chip (no `data` axis to reduce over: the program's text stays
    what it was) and off the TPU (XLA:CPU knows none of these names, and an
    unknown option fails the compile)."""
    if data_size > 1 and native:
        return dict(_MESH_COMPILER_OPTIONS)
    return None


def _shape_of(x):
    """What `.lower()` needs of a launch's argument: a device array's shape,
    dtype, sharding and layout (the ring's may be its own: ring_format);
    host scalars as they are."""
    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.format, weak_type=x.weak_type
    )


class _ChunkProgram:
    """A jitted chunk program as the learner holds it. Called, lowered or
    traced with a launch's arguments, it hands the program one more, its
    last: the base key of the noise stream (ShardedLearner._noise_key). The
    key is an argument and not a constant of the program, so the text the
    compile cache keys on is the same for every seed."""

    def __init__(self, program, noise_key):
        self.program, self.noise_key = program, noise_key

    def __call__(self, *args):
        return self.program(*args, self.noise_key)

    def lower(self, *args):
        return self.program.lower(*args, _shape_of(self.noise_key))

    def trace(self, *args):
        return self.program.trace(*args, self.noise_key)


def _as_it_is(s: TrainState) -> TrainState:
    return s


def scan_chunk(step, s: TrainState, batches: Batch, noise, unroll: int):
    """K steps of `step` in one lax.scan over a [K, B, ...] Batch pytree and
    the chunk's pre-drawn `noise` (learner.chunk_noise; None, an empty
    pytree, where the algorithm draws none: the scan's operands are then
    the batches alone), metrics reduced over the chunk. A step whose
    updates run on a state laid out for them names the three as `launch`,
    (enter, update, leave): the state enters once in front of the scan and
    leaves once behind it (learner.pixel_step: the rows of its trunks)."""
    enter, update, leave = getattr(step, "launch", (_as_it_is, step, _as_it_is))
    s = enter(s)

    def body(carry, x):
        out = update(carry, *x)
        return out.state, (out.td_errors, out.metrics)

    with trace.device_scope("update"):
        s, (tds, ms) = jax.lax.scan(body, s, (batches, noise), unroll=unroll)
    return StepOutput(state=leave(s), td_errors=tds, metrics=chunk_metrics(ms))


class ShardedLearner:
    def __init__(
        self,
        config: DDPGConfig,
        obs_dim: int,
        act_dim: int,
        action_scale,
        action_offset=0.0,
        mesh: Optional[Mesh] = None,
        mode: str = "auto",
        chunk_size: int = 1,
        unroll: int = 4,
        replay_sharding: str = "replicated",
    ):
        if mode not in ("auto", "explicit"):
            raise ValueError(f"mode must be 'auto' or 'explicit', got {mode!r}")
        if replay_sharding not in ("replicated", "sharded"):
            raise ValueError(
                f"replay_sharding must be 'replicated' or 'sharded', got "
                f"{replay_sharding!r}"
            )
        # Sharded device replay (docs/REPLAY_SHARDING.md): the sampling
        # chunk programs take storage partitioned over 'data' (strided
        # ownership) and reassemble each replica-identical index draw into
        # the global minibatch with a masked-gather + psum exchange.
        self._replay_sharded = replay_sharding == "sharded"
        self.config = config
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(
            config.data_axis, config.model_axis
        )
        if mode == "explicit" and self.mesh.shape["model"] != 1:
            raise ValueError("explicit (shard_map) mode is data-parallel only")
        self.mode = mode
        self.chunk_size = int(chunk_size)
        # Scan-body unroll factor: four updates a trip of the `while`. What
        # the loop costs beyond its body's fusions (`chunk.update_gap_pct`:
        # 17.7% of `update` in the SAC cell before PR 46, 18 to 72 us a trip
        # between the cells' bodies at this one factor) is no per-trip
        # overhead that more steps a trip would share: it is the loop
        # waiting for scalars that cross between its fusions and the scalar
        # core, 0.35-0.7 us a crossing (PERF.md §6, PR 46). The factor
        # itself has not been swept on the chip (the six scan-leg cells run
        # 4). lax.scan handles unroll > length, so no clamping to chunk
        # sizes.
        # (Rejecting <1 rather than clamping: lax.scan gives unroll=0 its own
        # meaning — full unroll — which a silent clamp would invert.)
        if int(unroll) < 1:
            raise ValueError(f"unroll must be >= 1, got {unroll}")
        self.unroll = int(unroll)
        self.data_size = self.mesh.shape["data"]
        # Rows drawn per learner step on the device-sampling paths.
        # scale_batch_with_data (config.py): per-device independent draws —
        # every data-axis device effectively samples its own batch_size rows
        # from the replicated storage (one global (K, B*D) draw sharded over
        # 'data'; storage is replicated, so this IS D independent draws),
        # and the loss mean spans the global batch, merged by the
        # sharding-induced AllReduce. Equivalent algorithm to one big batch;
        # scales throughput with the mesh instead of slicing 64 rows ever
        # thinner (VERDICT.md round-2 Missing #4).
        self.global_batch = (
            config.batch_size * self.data_size
            if config.scale_batch_with_data
            else config.batch_size
        )
        if self.global_batch % self.data_size:
            raise ValueError(
                f"batch_size={config.batch_size} not divisible by data axis "
                f"size {self.data_size}"
            )

        # `obs_dim`: the observation's float count, or its types.ObsSpec (a
        # pixel configuration's byte frames). self.obs_dim is the float32
        # words of a ring row one observation takes, what every cut of a
        # packed row reads; the nets' builders take the spec.
        self.obs = ObsSpec.of(obs_dim)
        if self.obs.pixels != bool(config.pixels):
            raise ValueError(
                f"observations of dtype {self.obs.dtype} and pixels="
                f"{config.pixels}: byte frames need --pixels=true "
                "(DrQ-v2's learner) and it reads nothing else"
            )
        if self.obs.steps != config.window_steps:
            raise ValueError(
                f"rows of {self.obs.steps} steps and recurrent="
                f"{config.recurrent} (seq_len {config.seq_len}): a recurrent "
                "learner reads windows of seq_len steps "
                "(ObsSpec.of_env(env, config.window_steps)) and nothing else"
            )
        self.obs_dim, self.act_dim = self.obs.words, act_dim
        # Numerical-health guardrails (guardrails.py): the chunk programs
        # thread a small replicated GuardState through the scan and emit a
        # per-chunk health word. Off (default) builds the exact pre-
        # guardrail programs — the parity test pins bit-identity.
        self.guard_enabled = bool(config.guardrails)
        self._numeric_inject = (
            config.fault_plan().numeric_steps()
            if self.guard_enabled and config.faults
            else {}
        )
        self._health_cur = None
        # Superstep first-bad-beat accounting (parallel/superstep.py): the
        # anomaly count (nonfinite + spikes) as of the LAST poll, so a
        # stacked [B, 5] health fetch can localize which beat of the
        # superstep first went bad. Survives reset_guard — the cumulative
        # counters it differences against survive too.
        self._health_prev_anom = 0
        # LR cooldown hook (train.py rollback-repair): both LRs scale by
        # _lr_scale; set_lr_scale rebuilds the (lazily compiled) programs.
        self._lr_scale = 1.0
        state = init_train_state(config, self.obs, act_dim, config.seed)
        self._state_sharding = mesh_lib.to_named(
            self.mesh, mesh_lib.state_pspec(state, self.mesh)
        )
        # Minibatches cross host->HBM as ONE packed [.., B, D] array
        # (types.pack_batch_np): per-array transfer overhead is the dominant
        # feed cost, so 6 field arrays -> 1 wire array is a ~10x cut.
        self._batch_sharding = NamedSharding(self.mesh, P("data", None))
        self._chunk_sharding = NamedSharding(self.mesh, P(None, "data", None))
        self.state: TrainState = jax.device_put(state, self._state_sharding)
        self._action_scale = action_scale
        self._action_offset = action_offset
        # Unified transfer scheduler (docs/TRANSFER.md): when train_jax
        # attaches one, the learner's d2h pulls run through its inline
        # d2h class — absolute priority (no queueing on the hot path) but
        # full bytes/latency accounting in the transfer_* family.
        self.transfer = None
        # The base key of the learner's noise stream (learner.noise_base_key;
        # None, an empty pytree, where the algorithm draws none): every chunk
        # program's LAST argument (_ChunkProgram), so no program's text holds
        # a value derived from config.seed and the compile cache serves
        # every seed of a configuration.
        self._noise_key = jax.device_put(
            noise_base_key(config), NamedSharding(self.mesh, P())
        )
        self._build_programs()
        self._key = jax.device_put(
            jax.random.PRNGKey(config.seed),
            NamedSharding(self.mesh, P()),
        )
        if self.guard_enabled:
            from distributed_ddpg_tpu import guardrails as guard_lib

            self._guard = jax.device_put(
                guard_lib.init_guard_state(),
                NamedSharding(self.mesh, P()),
            )

    def set_value_bounds(self, v_min: float, v_max: float) -> None:
        """Swap the C51 support bounds and rebuild the (lazily compiled)
        chunk programs in place. Mesh, state, and the sampling key are
        untouched, so the training stream continues exactly where it was;
        the next dispatch pays one XLA recompile. The auto-support
        controller (config.v_support_auto, ops/support_auto.py) calls this
        once at warmup resolution and on each geometric expansion — O(log)
        times per run."""
        self.config = self.config.replace(v_min=float(v_min), v_max=float(v_max))
        self._build_programs()

    def _build_programs(self) -> None:
        """Build every jitted step/chunk program from self.config. jax.jit
        is lazy, so (re)building costs nothing until the next dispatch."""
        config = self.config
        if self._lr_scale != 1.0:
            # Guardrail LR cooldown (train.py rollback-repair): the scale
            # applies at program build, so every path — scan, PER, fused —
            # sees the identical effective LR.
            config = config.replace(
                actor_lr=config.actor_lr * self._lr_scale,
                critic_lr=config.critic_lr * self._lr_scale,
            )
        mode = self.mode
        obs_dim, act_dim = self.obs_dim, self.act_dim
        action_scale = self._action_scale
        action_offset = self._action_offset
        state = self.state
        keys = metric_keys(config)

        if mode == "auto":
            step = make_learner_step(
                config, action_scale, action_offset=action_offset,
                obs=self.obs, mesh=self.mesh,
            )
        else:
            inner = make_learner_step(
                config, action_scale, axis_name="data",
                action_offset=action_offset, obs=self.obs,
            )
            state_spec = mesh_lib.state_pspec(state, self.mesh)
            bspec = mesh_lib.batch_pspec()

            def step(s: TrainState, b: Batch, noise=None) -> StepOutput:
                # `noise` (a scan chunk's, below) rides sharded like the
                # batch: each shard takes the rows it drew. REDQ's subset
                # has no rows and is every shard's alike.
                noise_spec = None if noise is None else jax.tree.map(
                    lambda rows: P("data", None) if rows else P(),
                    noise_per_row(config),
                )
                return mesh_lib.shard_map(
                    inner,
                    mesh=self.mesh,
                    in_specs=(state_spec, bspec, noise_spec),
                    out_specs=StepOutput(
                        state=state_spec,
                        td_errors=P("data"),
                        metrics={k: P() for k in keys},
                    ),
                )(s, b, noise)

        replicated = NamedSharding(self.mesh, P())
        td_sharding = NamedSharding(self.mesh, P("data"))

        from distributed_ddpg_tpu.ops.fused_chunk import runs_native

        mesh_options = mesh_compiler_options(self.data_size, runs_native())

        def chunk_program(fn, scan=True, **jit_args):
            # Every chunk body below ends in `nkey`, the noise stream's base
            # key, which the learner binds here and passes at each launch.
            # A scan chunk over a data mesh on the chip is compiled with
            # mesh_compiler_options; the fused-mesh kernel (`scan` False),
            # whose one pmean stands at the chunk's end, is not.
            if scan and mesh_options:
                jit_args["compiler_options"] = mesh_options
            return _ChunkProgram(jax.jit(fn, **jit_args), self._noise_key)

        def packed_step(s: TrainState, packed):
            return step(s, unpack_batch(packed, obs_dim, act_dim))

        self._step = jax.jit(
            packed_step,
            in_shardings=(self._state_sharding, self._batch_sharding),
            out_shardings=StepOutput(
                state=self._state_sharding,
                td_errors=td_sharding,
                metrics={k: replicated for k in keys},
            ),
            donate_argnums=(0,),
        )

        # The launch's noise, drawn ONCE before the scan from the stream a
        # single step draws from (learner.chunk_noise: the same bits) and
        # scanned over beside the batches, so no threefry runs inside the
        # K-update loop; None (an empty pytree: the scan's operands are
        # the batches alone) where the algorithm draws none. Inside the
        # chunk's own jitted program: the launch pays for its draw. `nkey`
        # is the stream's base key, an argument of every chunk program.
        def draw_chunk_noise(s: TrainState, batches: Batch, nkey):
            if not draws_noise(config):
                return None
            K, B = batches.action.shape[:2]
            if mode == "auto":
                # On a mesh each chip draws its own rows of the global
                # [K, B, act]; with the partitionable threefry the values
                # do not depend on the sharding.
                return jax.tree.map(
                    lambda x, rows: jax.lax.with_sharding_constraint(
                        x, self._chunk_sharding if rows else replicated
                    ),
                    chunk_noise(config, nkey, s.step, K, B, act_dim),
                    noise_per_row(config),
                )
            # Explicit mode folds the shard's index into the key, as the
            # step under shard_map does when it draws for itself.
            return mesh_lib.shard_map(
                lambda base, step0: chunk_noise(
                    config, base, step0, K, B // self.data_size, act_dim,
                    device_fold=jax.lax.axis_index("data"),
                ),
                mesh=self.mesh, in_specs=(P(), P()),
                out_specs=jax.tree.map(
                    lambda rows: P(None, "data", None) if rows else P(),
                    noise_per_row(config),
                ),
            )(nkey, s.step)

        # The shared chunk body of the host-fed and the fused-sampling paths.
        def scan_steps(s: TrainState, batches: Batch, nkey) -> StepOutput:
            return scan_chunk(
                step, s, batches, draw_chunk_noise(s, batches, nkey),
                self.unroll,
            )

        # K-steps-per-dispatch scan over host-fed packed batches.
        def chunk_fn(s: TrainState, packed, nkey):
            return scan_steps(s, unpack_batch(packed, obs_dim, act_dim), nkey)

        td_chunk_sharding = NamedSharding(self.mesh, P(None, "data"))
        self._chunk_step = chunk_program(
            chunk_fn,
            in_shardings=(
                self._state_sharding, self._chunk_sharding, replicated,
            ),
            out_shardings=StepOutput(
                state=self._state_sharding,
                td_errors=td_chunk_sharding,
                metrics={k: replicated for k in keys},
            ),
            donate_argnums=(0,),
        )

        # Fused-sampling chunk over a DeviceReplay: K steps per dispatch with
        # uniform sampling + gather done ON DEVICE — zero h2d inside the
        # chunk (replay/device.py). PRNG key lives on device too.
        batch_size = self.global_batch

        # Sample ALL of the chunk's minibatch indices up front and gather
        # them in ONE [K*B]-row gather. Storage is immutable for the whole
        # dispatch (ingest lands between chunks), so the distribution is
        # identical to sampling inside the scan body — but one fused gather
        # replaces K tiny ones (`chunk.sample_ms` in the ledger is the draw
        # and the gather of a launch). Shared by the scan and megakernel
        # paths so their index streams stay bit-identical (parity tests rely
        # on it).
        def draw_chunk_idx(key, size):
            with trace.device_scope("draw"):
                key, sub = jax.random.split(key)
                idx = jax.random.randint(
                    sub, (self.chunk_size, batch_size), 0, jnp.maximum(size, 1)
                )
            return key, idx

        # Row gather behind every sampling path. Replicated storage: a
        # plain local gather. Sharded storage (docs/REPLAY_SHARDING.md):
        # indices are drawn replica-identically (same key on every
        # device), then each shard gathers the rows IT owns (logical
        # position p lives on shard p % N at local slot p // N) and a
        # psum — each row has exactly one owner, everyone else
        # contributes zeros, and x + 0.0 is exact in f32 — reassembles
        # the replicated minibatch: the index-exchange that replaces the
        # replicated copy. Same indices + same logical row contents =>
        # the sampled minibatch is BIT-IDENTICAL to replicated mode (the
        # parity oracle in tests/test_replay_sharding.py).
        n_shards = self.data_size

        def gather_rows(storage, idx):
            if not self._replay_sharded:
                with trace.device_scope("gather"):
                    return storage[idx]

            def body(st, ix):
                s = jax.lax.axis_index("data")
                owner = ix % n_shards
                rows = st[jnp.where(owner == s, ix // n_shards, 0)]
                return jax.lax.psum(
                    jnp.where((owner == s)[..., None], rows, 0.0), "data"
                )

            with trace.device_scope("gather"):
                return mesh_lib.shard_map(
                    body, self.mesh,
                    in_specs=(P("data", None), P()), out_specs=P(),
                )(storage, idx)

        def draw_chunk(key, storage, size):
            key, idx = draw_chunk_idx(key, size)
            return key, gather_rows(storage, idx)

        # The uniform scan chunk's front (ops/chunk_front.py): where the
        # rule says 'cut', one Pallas pass over the gathered rows hands the
        # scan its fields cut, rounded and feature-major, each chip its own
        # rows; elsewhere unpack_batch as ever. The PER and guarded chunks
        # (which screen and overwrite the gathered rows) and the host-fed
        # chunk keep unpack_batch.
        from distributed_ddpg_tpu.ops import chunk_front as front_lib
        from distributed_ddpg_tpu.ops.pixels import cut_pixels
        from distributed_ddpg_tpu.replay.device import ring_layout

        width = packed_width(self.obs, act_dim)
        # A pixel launch has a cut of its own (ops/pixels.cut_pixels: the
        # fields as unpack_batch cuts them, the images still words under
        # `prep/pixels`), and its words must not pass through the kernel's
        # rounding. A recurrent launch's rows are windows
        # (types.unpack_windows: five fields, each time-major), which the
        # kernel's four cuts of a transition row do not spell; its gather is
        # 1.3 MB an update beside hundreds of dependent LSTM steps.
        scan_front = "xla" if config.pixels or config.recurrent else front_lib.front_for(
            width=width,
            batch=batch_size // n_shards,
            layout=ring_layout(width, self._replay_sharded),
            replay_sharded=self._replay_sharded,
            model_axis=self.mesh.shape["model"],
            native=runs_native(),
        )

        def cut_chunk(packed) -> Batch:
            if config.pixels:
                return cut_pixels(packed, self.obs, act_dim)
            if config.recurrent:
                return unpack_windows(packed, obs_dim, act_dim, self.obs.steps)
            if scan_front == "xla":
                return unpack_batch(packed, obs_dim, act_dim)
            cut = partial(
                front_lib.cut_rows, obs_dim=obs_dim, act_dim=act_dim,
                rounded=front_lib.rounds_inputs(config),
                action_rounded=front_lib.rounds_action(config),
            )
            if n_shards == 1:
                return cut(packed)
            rows, row = P(None, "data", None), P(None, "data")
            return mesh_lib.shard_map(
                cut, self.mesh, in_specs=rows,
                out_specs=Batch(rows, rows, row, row, rows, row),
            )(packed)

        def sample_chunk_fn(s: TrainState, key, storage, size, nkey):
            key, packed = draw_chunk(key, storage, size)
            packed = jax.lax.with_sharding_constraint(
                packed, NamedSharding(self.mesh, P(None, "data", None))
            )
            return scan_steps(s, cut_chunk(packed), nkey), key

        # Pallas megakernel path (ops/fused_chunk.py): the whole chunk in one
        # kernel, params VMEM-resident.
        from distributed_ddpg_tpu.ops import fused_chunk as fused_chunk_lib

        # Whether the kernel runs is decided HERE, before tracing, by rules
        # the code states (supported / fits_vmem / runs_native below) — a
        # kernel that was selected and then fails to compile is an error,
        # never a reason to run another program. "auto" additionally
        # requires a real TPU (elsewhere the kernel would run in pallas
        # interpret mode — correct but far slower than the XLA scan; "on"
        # forces it anywhere, tests use this) and mode="auto":
        # mode="explicit" exists to make the shard_map path observable, so it
        # must never be silently replaced by the megakernel.
        envelope_ok = (
            config.fused_chunk != "off"
            # Guardrails need the probe threaded through every step — the
            # megakernel has no slot for it, so the scan path wins
            # (config validation rejects fused_chunk='on' + guardrails).
            and not config.guardrails
            # Sharded replay: the kernel reads replicated storage whole;
            # the shard-exchange gather lives in the XLA scan path only
            # (config validation rejects fused_chunk='on' + sharded).
            and not self._replay_sharded
            and self.mode == "auto"
            and fused_chunk_lib.supported(config)
            and fused_chunk_lib.fits_vmem(config, obs_dim, act_dim)
            and (config.fused_chunk == "on" or fused_chunk_lib.runs_native())
        )
        # Mesh composition (config.fused_mesh, VERDICT.md r3 Missing #3):
        # on a DATA-only mesh every device runs the megakernel on its own
        # independent draws for the whole chunk; float state is pmean'd at
        # the chunk boundary (K-step local SGD — one params AllReduce per
        # K steps, NOT K gradient psums, which would evict params from VMEM
        # every step and forfeit the kernel's HBM-traffic win). TP
        # (model_axis > 1) shards the param tensors the kernel needs whole,
        # so the scan path keeps those meshes.
        self.fused_mesh_active = (
            envelope_ok
            and self.mesh.size > 1
            and self.mesh.shape["model"] == 1
            and config.fused_mesh != "off"
        )
        self.fused_chunk_active = envelope_ok and (
            self.mesh.size == 1 or self.fused_mesh_active
        )
        # (8, 128) tiles of one copy of the kernel's resident parameters, its
        # output layers lane-major where the shape rule says (the run fact
        # `kernel_state_tiles`); None on the scan leg, which holds no tiles.
        self.kernel_state_tiles = (
            fused_chunk_lib.state_tiles(config, obs_dim, act_dim)
            if self.fused_chunk_active
            else None
        )
        # The run fact `chunk_front`: how run_sample_chunk's program turns a
        # launch's gathered rows into the update's operands ('cut': the
        # kernel above; 'xla': unpack_batch, which is also what the
        # megakernel's own cuts and the guarded chunk read).
        self.chunk_front = (
            "xla"
            if self.fused_chunk_active or self.guard_enabled
            else scan_front
        )
        if config.fused_chunk == "on" and not self.fused_chunk_active:
            raise ValueError(
                "fused_chunk='on' but the config/mesh is outside the kernel "
                "envelope: needs mode='auto', a single-device or data-only "
                "mesh (model_axis == 1, and fused_mesh != 'off' for "
                "multi-device), plus action_insert_layer=1, critic_l2=0, "
                ">=2 critic hidden layers, and nets small enough for VMEM "
                "(ops/fused_chunk.fits_vmem)"
            )
        scan_sample_chunk_fn = sample_chunk_fn
        fused_run = None  # set on the single-device kernel path; PER reuses it
        if self.fused_chunk_active and not self.fused_mesh_active:
            run_fused = fused_chunk_lib.make_fused_chunk_fn(
                config, obs_dim, act_dim, action_scale, action_offset,
                chunk_size=self.chunk_size,
            )

            def fused_run(s: TrainState, packed, nkey):
                # The kernel's noise streams in pre-drawn, from the base key
                # the program takes as an argument (None where it draws none).
                K, B, _ = packed.shape
                eps = chunk_noise(config, nkey, s.step, K, B, act_dim)
                return run_fused(s, packed, eps=eps)

            def fused_sample_chunk_fn(s: TrainState, key, storage, size, nkey):
                key, packed = draw_chunk(key, storage, size)
                new_s, tds, ms = fused_run(s, packed, nkey)
                return StepOutput(state=new_s, td_errors=tds, metrics=ms), key

            sample_chunk_fn = fused_sample_chunk_fn
        elif self.fused_mesh_active:
            sample_chunk_fn = self._make_fused_mesh_fn(
                fused_chunk_lib, action_scale, action_offset
            )

        # PER fused chunk (replay/device.py DevicePrioritizedReplay,
        # VERDICT.md round-1 Missing #4): stratified proportional draw from
        # the device-resident priority vector, IS-weighted scan, and the
        # (|td|+eps)^alpha scatter update — one dispatch, zero h2d. The
        # priority vector is donated in and handed back updated.
        from distributed_ddpg_tpu.replay.device import (
            draw_per_indices,
            make_sharded_per_draw,
        )

        # Sharded PER (docs/REPLAY_SHARDING.md): shard-local cumsums under
        # a replicated top-level sampler replace the full-vector cumsum,
        # and the post-chunk priority scatter routes each update to the
        # owner shard (drop-mode, exactly one owner per index).
        draw_per = (
            make_sharded_per_draw(self.mesh)
            if self._replay_sharded
            else draw_per_indices
        )

        def per_draw(*args):
            with trace.device_scope("draw"):
                return draw_per(*args)

        def write_back(priorities, maxp, idx, td_errors, alpha, eps):
            """PER's end of a chunk: the sampled rows re-stamped at
            (|td| + eps)^alpha, and the running maximum."""
            with trace.device_scope("priority"):
                new_p = (jnp.abs(td_errors) + eps) ** alpha
                priorities = scatter_prios(
                    priorities, idx.reshape(-1), new_p.reshape(-1)
                )
                return priorities, jnp.maximum(maxp, new_p.max())

        def scatter_prios(priorities, idx_flat, vals_flat):
            if not self._replay_sharded:
                return priorities.at[idx_flat].set(vals_flat)

            def body(pr, ix, vals):
                s = jax.lax.axis_index("data")
                loc = jnp.where(
                    ix % n_shards == s, ix // n_shards, pr.shape[0]
                )
                return pr.at[loc].set(vals, mode="drop")

            return mesh_lib.shard_map(
                body, self.mesh,
                in_specs=(P("data"), P(), P()), out_specs=P("data"),
            )(priorities, idx_flat, vals_flat)

        def per_sample_chunk_fn(s, key, storage, size, priorities, maxp,
                                beta, alpha, eps, nkey):
            key, sub = jax.random.split(key)
            idx, weights = per_draw(
                sub, priorities, size, (self.chunk_size, batch_size), beta
            )
            packed = gather_rows(storage, idx)
            packed = jax.lax.with_sharding_constraint(
                packed, NamedSharding(self.mesh, P(None, "data", None))
            )
            weights = jax.lax.with_sharding_constraint(
                weights, NamedSharding(self.mesh, P(None, "data"))
            )
            batches = unpack_batch(packed, obs_dim, act_dim)._replace(
                weight=weights
            )
            out = scan_steps(s, batches, nkey)
            priorities, maxp = write_back(
                priorities, maxp, idx, out.td_errors, alpha, eps
            )
            return out, key, priorities, maxp

        storage_sharding = NamedSharding(
            self.mesh,
            P("data", None) if self._replay_sharded else P(None, None),
        )
        prio_sharding = NamedSharding(
            self.mesh, P("data") if self._replay_sharded else P(None)
        )

        def _jit_per_chunk(fn):
            return chunk_program(
                fn,
                in_shardings=(
                    self._state_sharding, replicated, storage_sharding,
                    replicated, prio_sharding, replicated, replicated,
                    replicated, replicated, replicated,
                ),
                out_shardings=(
                    StepOutput(
                        state=self._state_sharding,
                        td_errors=NamedSharding(self.mesh, P(None, "data")),
                        metrics={k: replicated for k in keys},
                    ),
                    replicated,
                    prio_sharding,
                    replicated,
                ),
                donate_argnums=(0, 1, 4),
            )

        self.fused_per_active = fused_run is not None
        if self.fused_per_active:
            # PER x megakernel: the stratified proportional draw and the
            # priority scatter live OUTSIDE the kernel (they're cheap,
            # bandwidth-bound ops XLA handles fine); only the K learner
            # steps run in the single pallas launch. The IS weights ride in
            # through the packed wire row's trailing weight column — the
            # kernel already reads per-row weights from there, so the
            # kernel needs no PER-specific change. Draw order matches the
            # scan path exactly (split -> draw_per_indices with identical
            # shapes), so the two paths are bit-comparable and the fused
            # path inherits the same priority semantics.
            def fused_per_sample_chunk_fn(s, key, storage, size, priorities,
                                          maxp, beta, alpha, eps, nkey):
                key, sub = jax.random.split(key)
                idx, weights = per_draw(
                    sub, priorities, size, (self.chunk_size, batch_size), beta
                )
                packed = gather_rows(storage, idx)
                with trace.device_scope("cut"):
                    packed = packed.at[..., -1].set(weights)
                new_s, tds, ms = fused_run(s, packed, nkey)
                out = StepOutput(state=new_s, td_errors=tds, metrics=ms)
                priorities, maxp = write_back(
                    priorities, maxp, idx, tds, alpha, eps
                )
                return out, key, priorities, maxp

            self._per_sample_chunk_step = _jit_per_chunk(
                fused_per_sample_chunk_fn
            )
        else:
            self._per_sample_chunk_step = _jit_per_chunk(per_sample_chunk_fn)

        def _jit_sample_chunk(fn):
            return chunk_program(
                fn,
                scan=not self.fused_chunk_active,
                in_shardings=(
                    self._state_sharding, replicated, storage_sharding,
                    replicated, replicated,
                ),
                out_shardings=(
                    StepOutput(
                        state=self._state_sharding,
                        td_errors=td_chunk_sharding,
                        metrics={k: replicated for k in keys},
                    ),
                    replicated,
                ),
                donate_argnums=(0, 1),
            )

        # sample_chunk_fn is the kernel body when fused_chunk_active, the
        # scan body otherwise (rebound above).
        self._sample_chunk_step = _jit_sample_chunk(sample_chunk_fn)

        if self.guard_enabled:
            # --- guarded chunk programs (guardrails.py) ---
            # The same scan bodies with the health probe threaded through:
            # each program additionally takes/returns the replicated
            # GuardState (donated) and emits the per-chunk health word;
            # the sampling paths also screen the raw gathered rows and
            # capture bad replay indices for source attribution. jit is
            # lazy, so the unguarded builds above cost nothing.
            from distributed_ddpg_tpu import guardrails as guard_lib

            gstep = guard_lib.make_guarded_step(
                step,
                zmax=config.guardrail_zmax,
                warmup=config.guardrail_warmup_steps,
                inject=self._numeric_inject,
            )

            def guarded_scan(s, g, batches, pre_bad, nkey):
                # A dropped update still advances state.step, so the
                # pre-drawn noise stays aligned with the steps.
                def body(carry, x):
                    ns, ng, td, ms = gstep(*carry, *x)
                    return (ns, ng), (td, ms)

                noise = draw_chunk_noise(s, batches, nkey)
                with trace.device_scope("update"):
                    (s, g), (tds, ms) = jax.lax.scan(
                        body, (s, g), (batches, pre_bad, noise),
                        unroll=self.unroll,
                    )
                return StepOutput(
                    state=s,
                    td_errors=tds,
                    metrics=chunk_metrics(ms),
                ), g

            def guard_chunk_fn(s: TrainState, packed, g, nkey):
                # Host-fed path: the sampler owns replay indices, so the
                # row screen reports counts only (bad_idx rides as -1s).
                pre_bad, bad_count, _ = guard_lib.batch_row_health(
                    packed, None
                )
                g = g._replace(bad_rows=g.bad_rows + bad_count)
                out, g = guarded_scan(
                    s, g, unpack_batch(packed, obs_dim, act_dim), pre_bad,
                    nkey,
                )
                return out, g, guard_lib.health_vector(g)

            self._chunk_step = chunk_program(
                guard_chunk_fn,
                in_shardings=(
                    self._state_sharding, self._chunk_sharding, replicated,
                    replicated,
                ),
                out_shardings=(
                    StepOutput(
                        state=self._state_sharding,
                        td_errors=td_chunk_sharding,
                        metrics={k: replicated for k in keys},
                    ),
                    replicated,
                    replicated,
                ),
                donate_argnums=(0, 2),
            )

            def guard_sample_chunk_fn(s: TrainState, key, storage, size, g,
                                      nkey):
                key, idx = draw_chunk_idx(key, size)
                packed = gather_rows(storage, idx)
                packed = jax.lax.with_sharding_constraint(
                    packed, NamedSharding(self.mesh, P(None, "data", None))
                )
                pre_bad, bad_count, bad_idx = guard_lib.batch_row_health(
                    packed, idx
                )
                g = g._replace(bad_rows=g.bad_rows + bad_count)
                out, g = guarded_scan(
                    s, g, unpack_batch(packed, obs_dim, act_dim), pre_bad,
                    nkey,
                )
                return out, key, g, guard_lib.health_vector(g), bad_idx

            guard_out = (
                StepOutput(
                    state=self._state_sharding,
                    td_errors=td_chunk_sharding,
                    metrics={k: replicated for k in keys},
                ),
                replicated,  # key
                replicated,  # guard state
                replicated,  # health word
                replicated,  # bad replay indices
            )
            self._sample_chunk_step = chunk_program(
                guard_sample_chunk_fn,
                in_shardings=(
                    self._state_sharding, replicated, storage_sharding,
                    replicated, replicated, replicated,
                ),
                out_shardings=guard_out,
                donate_argnums=(0, 1, 4),
            )

            def guard_per_sample_chunk_fn(s, key, storage, size, priorities,
                                          maxp, beta, alpha, eps, g, nkey):
                key, sub = jax.random.split(key)
                idx, weights = per_draw(
                    sub, priorities, size, (self.chunk_size, batch_size),
                    beta,
                )
                packed = gather_rows(storage, idx)
                packed = jax.lax.with_sharding_constraint(
                    packed, NamedSharding(self.mesh, P(None, "data", None))
                )
                weights = jax.lax.with_sharding_constraint(
                    weights, NamedSharding(self.mesh, P(None, "data"))
                )
                pre_bad, bad_count, bad_idx = guard_lib.batch_row_health(
                    packed, idx
                )
                g = g._replace(bad_rows=g.bad_rows + bad_count)
                batches = unpack_batch(packed, obs_dim, act_dim)._replace(
                    weight=weights
                )
                out, g = guarded_scan(s, g, batches, pre_bad, nkey)
                # A bad step's td errors are zeroed by the probe, so its
                # sampled rows re-stamp at the (eps)^alpha floor instead
                # of inheriting NaN priorities that would poison every
                # later draw.
                priorities, maxp = write_back(
                    priorities, maxp, idx, out.td_errors, alpha, eps
                )
                return (
                    out, key, priorities, maxp, g,
                    guard_lib.health_vector(g), bad_idx,
                )

            self._per_sample_chunk_step = chunk_program(
                guard_per_sample_chunk_fn,
                in_shardings=(
                    self._state_sharding, replicated, storage_sharding,
                    replicated, prio_sharding, replicated, replicated,
                    replicated, replicated, replicated, replicated,
                ),
                out_shardings=(
                    StepOutput(
                        state=self._state_sharding,
                        td_errors=NamedSharding(self.mesh, P(None, "data")),
                        metrics={k: replicated for k in keys},
                    ),
                    replicated,
                    prio_sharding,
                    replicated,
                    replicated,
                    replicated,
                    replicated,
                ),
                donate_argnums=(0, 1, 4, 9),
            )

        # --- fused-megastep composition (parallel/megastep.py) ---
        # The pure (unjitted) XLA-scan sampling bodies, for composition
        # into the fused beat program. Always the SCAN variants: the
        # megastep composes whole-chunk bodies, and the Pallas megakernel
        # has no slot inside a larger traced program. Rebuilt with every
        # _build_programs call (LR backoff, support expansion), so the
        # version counter below lets the megastep detect staleness and
        # rebuild its beat program in step.
        self._launched = None  # chunk_ops: no launch of these programs yet
        self._chunk_ops = None
        self._pure_scan_fns = {
            "uniform": scan_sample_chunk_fn,
            "per": per_sample_chunk_fn,
        }
        if self.guard_enabled:
            self._pure_scan_fns["uniform.guarded"] = guard_sample_chunk_fn
            self._pure_scan_fns["per.guarded"] = guard_per_sample_chunk_fn
        self.programs_version = getattr(self, "programs_version", 0) + 1

    def _make_fused_mesh_fn(self, fused_chunk_lib, action_scale, action_offset):
        """Megakernel x data-parallel mesh (VERDICT.md r3 Missing #3).

        Every 'data'-axis device runs the whole K-step chunk in ONE pallas
        launch on its OWN independent minibatch draws (storage is replicated,
        so per-device draws from the full buffer are D independent batch
        streams), then the float state — params, targets, Adam moments — is
        pmean'd across the axis at the chunk boundary. That is K-step local
        SGD: one params-sized AllReduce per K steps instead of the scan
        path's K per-step gradient psums. Per-step sync inside the kernel
        would force params back to HBM every step, forfeiting exactly the
        VMEM-residency win the kernel exists for; at K=800 the boundary
        AllReduce (~5 MB of state) amortizes to ~6 KB/step — below even the
        batch stream. Divergence between replicas is bounded by O(lr * K)
        drift per chunk (each replica's Adam update is clipped to ~lr per
        step by normalization); docs/PERF_NOTES.md carries the measured
        parity + staleness argument. Adam counts/step advance identically
        on every replica and pass through un-averaged."""
        K = self.chunk_size
        b_local = self.global_batch // self.data_size
        run_fused = fused_chunk_lib.make_fused_chunk_fn(
            self.config.replace(batch_size=b_local),
            self.obs_dim, self.act_dim, action_scale, action_offset,
            chunk_size=K,
        )
        mesh = self.mesh
        state_spec = mesh_lib.state_pspec(self.state, mesh)
        keys = metric_keys(self.config)

        def local_chunk(s, sub, storage, size, nkey):
            axis_idx = jax.lax.axis_index("data")
            with trace.device_scope("draw"):
                dkey = jax.random.fold_in(sub, axis_idx)
                idx = jax.random.randint(
                    dkey, (K, b_local), 0, jnp.maximum(size, 1)
                )
            # Per-device iid noise: the fold_in(seed, step) stream with the
            # device index folded on top, as the step under shard_map
            # folds it (learner.chunk_noise).
            eps = chunk_noise(
                self.config, nkey, s.step, K, b_local, self.act_dim,
                device_fold=axis_idx,
            )
            with trace.device_scope("gather"):
                rows = storage[idx]
            new_s, tds, ms = run_fused(s, rows, eps=eps)
            avg = lambda x: jax.lax.pmean(x, "data")
            favg = lambda tree: jax.tree.map(avg, tree)
            # SAC temperature state is float — it local-SGDs inside the
            # chunk and pmeans at the boundary like every other float leaf.
            extra = {}
            if new_s.log_alpha is not None:
                extra["log_alpha"] = favg(new_s.log_alpha)  # a scalar or a tree
            if new_s.alpha_opt is not None:
                extra["alpha_opt"] = OptState(
                    mu=favg(new_s.alpha_opt.mu),
                    nu=favg(new_s.alpha_opt.nu),
                    count=new_s.alpha_opt.count,
                )
            new_s = TrainState(
                actor_params=favg(new_s.actor_params),
                critic_params=favg(new_s.critic_params),
                target_actor_params=favg(new_s.target_actor_params),
                target_critic_params=favg(new_s.target_critic_params),
                actor_opt=OptState(
                    mu=favg(new_s.actor_opt.mu),
                    nu=favg(new_s.actor_opt.nu),
                    count=new_s.actor_opt.count,
                ),
                critic_opt=OptState(
                    mu=favg(new_s.critic_opt.mu),
                    nu=favg(new_s.critic_opt.nu),
                    count=new_s.critic_opt.count,
                ),
                step=new_s.step,
                **extra,
            )
            return new_s, tds, {k: avg(v) for k, v in ms.items()}

        sharded = mesh_lib.shard_map(
            local_chunk,
            mesh=mesh,
            in_specs=(state_spec, P(), P(None, None), P(), P()),
            out_specs=(
                state_spec,
                P(None, "data"),
                {k: P() for k in keys},
            ),
        )

        def fused_mesh_sample_chunk_fn(s: TrainState, key, storage, size,
                                       nkey):
            key, sub = jax.random.split(key)
            new_s, tds, ms = sharded(s, sub, storage, size, nkey)
            return StepOutput(state=new_s, td_errors=tds, metrics=ms), key

        return fused_mesh_sample_chunk_fn

    # --- the launched chunk program, by part (trace.CHUNK_SCOPES) ---

    def _launch(self, program, *args):
        """One launch of a chunk program. The first of each build is kept
        (the program and its arguments' shapes, shardings and layouts) for
        chunk_ops to ask the executable back."""
        if self._launched is None:
            self._launched = (program, jax.tree.map(_shape_of, args))
        return program(*args)

    def chunk_hlo(self) -> Optional[str]:
        """The text of the executable behind this learner's chunk launches:
        lowering a jitted program again with the shapes it was launched
        with finds lowering and executable in JAX's in-memory caches, so
        nothing compiles. None before any launch."""
        if self._launched is None:
            return None
        program, shapes = self._launched
        return program.lower(*shapes).compile().as_text()

    def chunk_ops(self) -> Optional[dict]:
        """The table from instruction name to scope (trace.chunk_ops_table)
        of the chunk program this learner launches, from the text of the
        executable that ran. None before any launch. Read once a build:
        the run fact below and train.write_chunk_ops both ask."""
        if self._launched is None:
            return None
        if self._chunk_ops is None:
            self._chunk_ops = trace.chunk_ops_table(self.chunk_hlo())
        return self._chunk_ops

    def chunk_collectives(self) -> Optional[dict]:
        """The run fact `chunk_collectives`: how many collective instructions
        the launched chunk executable holds (`instructions`: the table's
        `served`, and the fusions that carry a step of one) and how many of
        them the device runs beside other operations (`asynchronous`). None
        on one device, whose program has none, and before any launch."""
        table = None if self.mesh.size == 1 else self.chunk_ops()
        if table is None:
            return None
        return {
            "instructions": len({*table["served"], *table["asynchronous"]}),
            "asynchronous": len(table["asynchronous"]),
        }

    def chunk_body_scalars(self) -> Optional[int]:
        """The run fact `chunk_body_scalars`: the unfused arithmetic
        instructions on a `[]` shape that one trip of the launched scan
        chunk's loop issues (the table's `scalars`). None on the kernel
        leg, which scans nothing, and before any launch."""
        return self._scan_body_fact("scalars")

    def chunk_body_copies(self) -> Optional[dict]:
        """The run fact `chunk_body_copies`: the relayouts one trip of the
        launched scan chunk's loop runs as operations of their own, their
        `count` and the `bytes` of their results (the table's `copies`).
        None on the kernel leg and before any launch."""
        return self._scan_body_fact("copies")

    def chunk_body_gathers(self) -> Optional[int]:
        """The run fact `chunk_body_gathers`: the gathers one trip of the
        launched scan chunk's loop issues, `gather` instructions and the
        fusions that hold one (the table's `gathers`). The ring's own row
        gather stands in front of the loop and is not among them. None on
        the kernel leg and before any launch."""
        return self._scan_body_fact("gathers")

    def _scan_body_fact(self, key: str):
        table = None if self.fused_chunk_active else self.chunk_ops()
        return None if table is None else table[key]

    # --- single step ---

    def step(self, np_batch: Dict[str, np.ndarray]) -> StepOutput:
        packed = jax.device_put(pack_batch_np(np_batch), self._batch_sharding)
        out = self._step(self.state, packed)
        self.state = out.state
        return out

    # --- K steps per dispatch ---

    def run_chunk(self, np_batches: Dict[str, np.ndarray]) -> StepOutput:
        """np_batches fields are [K, B, ...] stacked minibatches."""
        return self.run_chunk_async(self.put_chunk(np_batches))

    def run_chunk_async(self, device_chunk) -> StepOutput:
        """Same as run_chunk but takes an already-device_put packed chunk
        (from the prefetch pipeline) and does not block — callers sync on
        the outputs."""
        if self.guard_enabled:
            out, self._guard, health = self._launch(
                self._chunk_step, self.state, device_chunk, self._guard
            )
            self._health_cur = (health, None)
            self.state = out.state
            return out
        out = self._launch(self._chunk_step, self.state, device_chunk)
        self.state = out.state
        return out

    def put_chunk(self, np_batches: Dict[str, np.ndarray]):
        """Pack a [K, B, field] dict into the single wire array and start
        its (async) transfer to HBM with the chunk sharding."""
        with trace.span("chunk_h2d"):
            return jax.device_put(
                pack_batch_np(np_batches), self._chunk_sharding
            )

    # --- K steps per dispatch, sampling fused on device ---

    def run_sample_chunk(self, device_replay) -> StepOutput:
        """K learner steps sampling uniformly from a DeviceReplay — the
        zero-h2d steady-state path (batches never touch the host). Runs
        the program _build_programs selected (megakernel or scan); a
        compile or execution failure propagates."""
        with _ingest_lock(device_replay):
            storage, size = device_replay.device_state()
            if self.guard_enabled:
                out, self._key, self._guard, health, bad_idx = self._launch(
                    self._sample_chunk_step,
                    self.state, self._key, storage, size, self._guard,
                )
                self._health_cur = (health, bad_idx)
                self.state = out.state
                return out
            out, self._key = self._launch(
                self._sample_chunk_step, self.state, self._key, storage, size
            )
            self.state = out.state
            return out

    def run_sample_chunk_per(self, device_replay, beta: float) -> StepOutput:
        """K learner steps with proportional PER sampling + priority update
        fused on device (DevicePrioritizedReplay) — the same zero-h2d
        steady state as the uniform path; beta anneals host-side and rides
        in as a scalar argument. With the megakernel active the K steps
        run in one pallas launch (draw + priority scatter stay XLA ops)."""
        with _ingest_lock(device_replay):
            storage, size, priorities, maxp = device_replay.per_state()
            args = (
                np.float32(beta), np.float32(device_replay.alpha),
                np.float32(device_replay.eps),
            )
            if self.guard_enabled:
                out, self._key, new_p, new_maxp, self._guard, health, bad_idx = (
                    self._launch(
                        self._per_sample_chunk_step,
                        self.state, self._key, storage, size, priorities,
                        maxp, *args, self._guard,
                    )
                )
                self._health_cur = (health, bad_idx)
            else:
                out, self._key, new_p, new_maxp = self._launch(
                    self._per_sample_chunk_step,
                    self.state, self._key, storage, size, priorities, maxp,
                    *args,
                )
            self.state = out.state
            device_replay.set_per_state(new_p, new_maxp)
            return out

    # --- fused-megastep composition hooks (parallel/megastep.py) ---

    def pure_scan_sample_fn(self, per: bool):
        """The pure scan-path sampling-chunk body matching this learner's
        guard mode — uniform: (state, key, storage, size[, guard], nkey);
        PER: (state, key, storage, size, priorities, maxp, beta, alpha,
        eps[, guard], nkey), `nkey` the noise stream's base key
        (self._noise_key). The fused megastep composes it with the rollout and
        ring insert into one beat program; using the identical body is
        what makes fused-vs-separate dispatch bit-identity hold."""
        key = ("per" if per else "uniform") + (
            ".guarded" if self.guard_enabled else ""
        )
        return self._pure_scan_fns[key]

    def note_fused_health(self, guard, health, bad_idx) -> None:
        """Install the guard state + health word(s) a fused dispatch
        returned, so poll_health()/bad_indices() (the train.py guardrail
        monitor) read the fused program's probe exactly as they read a
        standalone guarded chunk's. A megastep beat hands a scalar health
        word (int32[5]) and bad-row capture (int32[GUARD_BAD_IDX]); a
        B-beat superstep (parallel/superstep.py) hands the stacked
        per-beat VECTORS (int32[B, 5] / int32[B, GUARD_BAD_IDX]) — the
        final row is the chunk-end cumulative counters, and the per-row
        deltas localize the first bad beat."""
        self._guard = guard
        self._health_cur = (health, bad_idx)

    # --- host-side views ---

    def policy_params(self):
        """The live, device-resident tree that acts: the actor's, and for a
        pixel configuration the encoder (the critic's) in front of it
        (models/pixels.policy_params). What the device pool's pointer swap
        takes; donated away with the state by the next launch."""
        if self.config.pixels:
            return pixnet.policy_params(
                self.state.critic_params, self.state.actor_params
            )
        return self.state.actor_params

    def actor_params_to_host(self):
        """Numpy actor params for broadcast to CPU rollout workers. Inside
        the loop train.py's `read_back` has waited out the launches in flight
        (one `launch_wait` span each), so `params_d2h` brackets the copy and
        the fold alone. A batch-normalised actor (CrossQ) leaves as the plain
        MLP it is in evaluation mode (mlp.fold_norm): the workers' layout,
        the evaluator and the serving engine never see the normalisation.
        A pixel configuration's policy (policy_params) and a recurrent one's
        leave as they are: nothing on the host but the evaluator and the
        checksum reads them."""
        def fetch():
            with trace.span("params_d2h"):
                host = jax.tree.map(
                    np.asarray, jax.device_get(self.policy_params())
                )
                if self.config.pixels or self.config.recurrent:
                    return host
                return fold_norm(host)

        if self.transfer is None:
            return fetch()
        return self.transfer.run_inline(
            "d2h", fetch, label="params_d2h",
            nbytes_of=lambda r: sum(l.nbytes for l in jax.tree.leaves(r)),
        )

    def metrics_to_host(self, out: StepOutput) -> Dict[str, float]:
        def fetch():
            with trace.span("metrics_d2h"):
                return {
                    k: float(v)
                    for k, v in jax.device_get(out.metrics).items()
                }

        if self.transfer is None:
            return fetch()
        return self.transfer.run_inline(
            "d2h", fetch, label="metrics_d2h",
            nbytes_of=lambda r: 8 * len(r),
        )

    # --- numerical-health guardrails (guardrails.py) ---

    def poll_health(self) -> Optional[Dict[str, int]]:
        """Cumulative probe counters of the most recent guarded dispatch
        — the one tiny d2h the guardrail monitor pays per sync point (it
        syncs the health word only, never params). None before the first
        guarded dispatch or with guardrails off.

        A superstep's stacked int32[B, 5] health vector (note_fused_
        health) syncs in the SAME single device_get: the returned dict is
        the final row (chunk-end cumulative counters, exactly what B
        sequential polls would have converged to), plus a
        "first_bad_beat" entry — the 0-based index of the first beat
        whose cumulative anomaly count (nonfinite + spikes) moved past
        the previous poll's, or -1 when the superstep was clean. Scalar
        fetches carry no such key, so GuardrailStats.absorb's .get-based
        delta accounting is untouched."""
        if not self.guard_enabled or self._health_cur is None:
            return None
        from distributed_ddpg_tpu import guardrails as guard_lib

        def fetch():
            with trace.span("health_d2h"):
                vec = np.asarray(jax.device_get(self._health_cur[0]))
            if vec.ndim == 1:
                return dict(
                    zip(guard_lib.HEALTH_KEYS, (int(v) for v in vec))
                )
            # Stacked [B, 5] superstep vector: one fetch, per-beat rows.
            keys = guard_lib.HEALTH_KEYS
            anom = (
                vec[:, keys.index("nonfinite")] + vec[:, keys.index("spikes")]
            ).astype(np.int64)
            fresh = np.flatnonzero(anom > self._health_prev_anom)
            h = dict(zip(keys, (int(v) for v in vec[-1])))
            h["first_bad_beat"] = int(fresh[0]) if fresh.size else -1
            return h

        if self.transfer is None:
            h = fetch()
        else:
            h = self.transfer.run_inline(
                "d2h", fetch, label="health_d2h",
                nbytes_of=lambda r: 4 * len(r),
            )
        if h is not None:
            self._health_prev_anom = (
                int(h.get("nonfinite", 0)) + int(h.get("spikes", 0))
            )
        return h

    def bad_indices(self) -> np.ndarray:
        """Replay indices of the non-finite rows the last guarded chunk
        sampled (first guardrails.GUARD_BAD_IDX; device pads with -1,
        filtered here). Fetch only when the health word shows fresh
        bad_rows — this d2h rides the rare bad path."""
        if not self.guard_enabled or self._health_cur is None:
            return np.empty(0, np.int64)
        bad = self._health_cur[1]
        if bad is None:
            return np.empty(0, np.int64)
        arr = np.asarray(jax.device_get(bad)).astype(np.int64)
        # A superstep hands the stacked [B, GUARD_BAD_IDX] capture;
        # beat order is row order, so a flatten preserves it.
        return arr.reshape(-1)[arr.reshape(-1) >= 0]

    def reset_guard(self) -> None:
        """Re-arm the probe after a rollback: EWMA statistics reset (the
        restored params have the pre-divergence loss scale), cumulative
        counters and the monotonic step clock survive (the host's delta
        accounting and the numeric-fault ordinals key on them)."""
        if not self.guard_enabled:
            return
        from distributed_ddpg_tpu import guardrails as guard_lib

        h = self.poll_health() or {}
        self._guard = jax.device_put(
            guard_lib.init_guard_state(
                total=h.get("total", 0),
                nonfinite=h.get("nonfinite", 0),
                spikes=h.get("spikes", 0),
                skipped=h.get("skipped", 0),
                bad_rows=h.get("bad_rows", 0),
            ),
            NamedSharding(self.mesh, P()),
        )
        self._health_cur = None

    def reseed(self, salt: int) -> None:
        """Fold `salt` into the device sampling key. Rollback-repair calls
        this so the resumed trajectory draws DIFFERENT minibatches than
        the one that diverged — restoring state alone would replay the
        identical sample stream into the identical divergence."""
        self._key = jax.random.fold_in(self._key, int(salt))

    @property
    def lr_scale(self) -> float:
        return self._lr_scale

    def set_lr_scale(self, scale: float) -> None:
        """Scale both learner LRs (guardrail rollback cooldown). Rebuilds
        the lazily-compiled chunk programs like set_value_bounds — one XLA
        recompile at the next dispatch, state/key/guard untouched."""
        scale = float(scale)
        if scale == self._lr_scale:
            return
        self._lr_scale = scale
        self._build_programs()


# ---------------------------------------------------------------------------
# program-contract analyzer hook (analysis/programs.py; docs/ANALYSIS.md
# "Layer 2")
# ---------------------------------------------------------------------------


def program_specs():
    """Every hot learner chunk program, built tiny (8-wide batch, 16-wide
    hiddens, chunk of 2) under the 2-device CPU probe mesh. jit is lazy,
    so each build costs one trace and zero compiles. The guarded and
    unguarded variants of each chunk shape dispatch at the SAME lockstep
    site (train.py picks per config), so they share a beat_group: their
    explicitly-staged collective order must be identical or a pod mixing
    configs would fork."""
    from distributed_ddpg_tpu.analysis.programs import (
        BuiltProgram,
        ProgramSpec,
        probe_config,
        probe_mesh,
    )

    OWNER = "parallel/learner.py"
    cache: Dict[tuple, ShardedLearner] = {}

    # REDQ's chunk (a critic ensemble of 5, a drawn pair, the policy's half
    # under a cond): the subset rides replicated beside noise sharded like
    # the batch, and the taken branch holds the actor's gradient pmean.
    ENSEMBLE = dict(
        sac=True, critic_ensemble=5, target_subset=2, policy_delay=3
    )
    # CrossQ's chunk (no targets, batch-normalised nets, the joint critic
    # pass, the same cond), as the benchmark's cell runs it: the partitioner
    # shards it. (Under explicit shard_map every normalised layer stages two
    # pmeans for its batch moments; tests/test_reference_crossq.py runs that.)
    CROSSQ = dict(
        sac=True, crossq=True, policy_delay=3, adam_b1=0.5,
        action_insert_layer=0,
    )

    # PQL's chunk (twin critics, no smoothing noise so no noise operand,
    # the actor and all three targets under the delay's cond, the action
    # at the critics' input, three hidden layers), as the benchmark's cell
    # runs it.
    PQL = dict(
        twin_critic=True, policy_delay=2, target_noise=0.0, n_step=3,
        action_insert_layer=0, actor_hidden=(16, 16, 8),
        critic_hidden=(16, 16, 8), tau=0.05,
    )

    # SimBa's chunk (residual nets, the input statistics' merge, AdamW, the
    # statistics copied into the targets), as the benchmark's cell runs it.
    # (Under explicit shard_map the merge stages two pmeans and a psum for
    # the batch's moments and rows; tests/test_reference_simba.py runs that.)
    SIMBA = dict(
        sac=True, simba=True, action_insert_layer=0, weight_decay=1e-2,
        actor_hidden=(16,), critic_hidden=(16,), target_entropy_scale=0.5,
    )

    # DrQ-v2's chunk (byte images cut out of the gathered words, the crop
    # offsets and both noises pre-drawn, the encoder's passes, one Adam over
    # encoder, trunk and heads, no target actor), as the benchmark's cell
    # runs it, on frames of 3 x 16 x 16 (the smallest the four layers take).
    PIXELS = dict(
        pixels=True, twin_critic=True, n_step=3, action_insert_layer=0,
        env_id="PixelHumanoidStandIn-v0", actor_backend="device",
        num_actors=0, device_actor_envs=4, encoder_channels=4, feature_dim=8,
        tau=0.01, target_noise_clip=0.3,
    )

    # DMPO's chunk (the E-step's draws pre-drawn [K, B, N, act], the target
    # critic on B x N rows, the mixture target, the decoupled M-step, the
    # dual variables' tree under its own Adam, the targets copied every
    # second update), as the benchmark's cell runs it.
    MPO = dict(
        mpo=True, distributional=True, num_atoms=11, n_step=5,
        action_insert_layer=0, mpo_samples=3, actor_hidden=(16, 16),
        critic_hidden=(16, 16), target_update_period=2,
    )

    # Recurrent TD3's chunk (rows that are windows of 4 steps cut five ways,
    # the smoothing noise pre-drawn [K, B, L, act], four memories scanned
    # over time inside the scan over updates, backward through time for two,
    # masked losses), as the benchmark's cell runs it.
    RECURRENT = dict(
        recurrent=True, twin_critic=True, action_insert_layer=0, seq_len=4,
        rnn_hidden=8, obs_embed=4, action_embed=2, reward_embed=2,
        actor_hidden=(8, 8), critic_hidden=(8, 8), target_noise=0.2,
        target_noise_clip=0.5, exploration="gaussian", actor_backend="device",
        num_actors=0, device_actor_envs=4,
    )

    def learner(
        guard: bool = False, sharded: bool = False, tp: bool = False,
        ensemble: bool = False, mode: str = "auto", crossq: bool = False,
        pql: bool = False, simba: bool = False, pixels: bool = False,
        mpo: bool = False, recurrent: bool = False,
    ) -> ShardedLearner:
        key = (guard, sharded, tp, ensemble, mode, crossq, pql, simba, pixels, mpo, recurrent)
        if key not in cache:
            cache[key] = ShardedLearner(
                probe_config(
                    guardrails=guard, model_axis=2 if tp else 1,
                    **(ENSEMBLE if ensemble else {}),
                    **(CROSSQ if crossq else {}),
                    **(PQL if pql else {}),
                    **(SIMBA if simba else {}),
                    **(PIXELS if pixels else {}),
                    **(MPO if mpo else {}),
                    **(RECURRENT if recurrent else {}),
                ),
                obs_dim=(
                    ObsSpec((3, 16, 16), "uint8") if pixels
                    else ObsSpec((3,), steps=4) if recurrent else 3
                ),
                act_dim=1,
                action_scale=np.ones(1, np.float32),
                mesh=probe_mesh(2 if tp else 1),
                chunk_size=2,
                replay_sharding="sharded" if sharded else "replicated",
                mode=mode,
            )
        return cache[key]

    def storage_for(L: ShardedLearner):
        width = packed_width(L.obs, L.act_dim)  # the packed replay row
        spec = P("data", None) if L._replay_sharded else P(None, None)
        storage = jax.device_put(
            np.zeros((64, width), np.float32), NamedSharding(L.mesh, spec)
        )
        return storage, np.int32(64)

    def hostfed(guard: bool):
        def build():
            L = learner(guard=guard)
            width = 2 * L.obs_dim + L.act_dim + 3
            chunk = jax.device_put(
                np.zeros((L.chunk_size, L.global_batch, width), np.float32),
                L._chunk_sharding,
            )
            if guard:
                return BuiltProgram(
                    L._chunk_step, (L.state, chunk, L._guard), (0, 2)
                )
            return BuiltProgram(L._chunk_step, (L.state, chunk), (0,))
        return build

    def uniform(guard: bool, sharded: bool, tp: bool = False, **kw):
        def build():
            L = learner(guard=guard, sharded=sharded, tp=tp, **kw)
            storage, size = storage_for(L)
            if guard:
                return BuiltProgram(
                    L._sample_chunk_step,
                    (L.state, L._key, storage, size, L._guard),
                    (0, 1, 4),
                )
            return BuiltProgram(
                L._sample_chunk_step, (L.state, L._key, storage, size),
                (0, 1),
            )
        return build

    def per(guard: bool, sharded: bool, tp: bool = False):
        def build():
            L = learner(guard=guard, sharded=sharded, tp=tp)
            storage, size = storage_for(L)
            prios = jax.device_put(
                np.zeros(64, np.float32),
                NamedSharding(
                    L.mesh, P("data") if L._replay_sharded else P(None)
                ),
            )
            scalars = (np.float32(1.0), np.float32(0.4), np.float32(0.6),
                       np.float32(1e-6))
            if guard:
                return BuiltProgram(
                    L._per_sample_chunk_step,
                    (L.state, L._key, storage, size, prios, *scalars,
                     L._guard),
                    (0, 1, 4, 9),
                )
            return BuiltProgram(
                L._per_sample_chunk_step,
                (L.state, L._key, storage, size, prios, *scalars),
                (0, 1, 4),
            )
        return build

    specs = []
    for guard in (False, True):
        tag = ".guarded" if guard else ""
        specs.extend([
            ProgramSpec(
                f"learner.chunk.hostfed{tag}", OWNER, hostfed(guard),
                beat_group="learner-beat-hostfed",
            ),
            ProgramSpec(
                f"learner.chunk.uniform{tag}", OWNER,
                uniform(guard, sharded=False),
                beat_group="learner-beat-uniform",
            ),
            ProgramSpec(
                f"learner.chunk.per{tag}", OWNER, per(guard, sharded=False),
                beat_group="learner-beat-per",
            ),
            ProgramSpec(
                f"learner.chunk.uniform.sharded{tag}", OWNER,
                uniform(guard, sharded=True),
                beat_group="learner-beat-uniform-sharded",
            ),
            ProgramSpec(
                f"learner.chunk.per.sharded{tag}", OWNER,
                per(guard, sharded=True),
                beat_group="learner-beat-per-sharded",
            ),
        ])
    # TP variants (docs/MESH.md): the same sharded sampling chunks under
    # the (data=2, model=2) probe mesh — the 'data'-axis gather/psum
    # exchange must stay collective-order-stable when params shard on
    # 'model' (the SPMD partitioner's own collectives are downstream of
    # this jaxpr and follow it deterministically). They SHARE the 1D
    # sharded variants' beat_group so the cross-variant order equality
    # is enforced by the group check, not just per-program goldens.
    specs.extend([
        ProgramSpec(
            "learner.chunk.uniform.sharded.tp", OWNER,
            uniform(False, sharded=True, tp=True),
            beat_group="learner-beat-uniform-sharded",
        ),
        ProgramSpec(
            "learner.chunk.per.sharded.tp", OWNER,
            per(False, sharded=True, tp=True),
            beat_group="learner-beat-per-sharded",
        ),
    ])
    # The ensemble chunk, as the partitioner shards it and under explicit
    # shard_map (where the critics', the actor's and the metrics' pmeans are
    # staged by hand, the actor's inside the cond's taken branch).
    specs.extend([
        ProgramSpec(
            "learner.chunk.uniform.ensemble", OWNER,
            uniform(False, sharded=False, ensemble=True),
        ),
        ProgramSpec(
            "learner.chunk.uniform.ensemble.explicit", OWNER,
            uniform(False, sharded=False, ensemble=True, mode="explicit"),
        ),
    ])
    specs.append(
        ProgramSpec(
            "learner.chunk.uniform.crossq", OWNER,
            uniform(False, sharded=False, crossq=True),
        )
    )
    specs.append(
        ProgramSpec(
            "learner.chunk.uniform.pql", OWNER,
            uniform(False, sharded=False, pql=True),
        )
    )
    specs.append(
        ProgramSpec(
            "learner.chunk.uniform.simba", OWNER,
            uniform(False, sharded=False, simba=True),
        )
    )
    specs.append(
        ProgramSpec(
            "learner.chunk.uniform.pixels", OWNER,
            uniform(False, sharded=False, pixels=True),
        )
    )
    specs.append(
        ProgramSpec(
            "learner.chunk.uniform.mpo", OWNER,
            uniform(False, sharded=False, mpo=True),
        )
    )
    specs.append(
        ProgramSpec(
            "learner.chunk.uniform.recurrent", OWNER,
            uniform(False, sharded=False, recurrent=True),
        )
    )
    return specs
