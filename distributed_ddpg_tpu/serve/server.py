"""InferenceServer: one policy, many callers (docs/SERVING.md).

The serving half of the TorchBeast topology (PAPERS.md arXiv 1910.03552):
the server owns the policy parameters, a dynamic `Batcher` collects client
observations, and each collected batch is applied in ONE policy evaluation
— the shape under which inference cost dominates at scale (the CPU-GPU
architectural-implications study, arXiv 2012.04210).

Two compute backends:

  numpy  (default) The parity oracle: each batch row is evaluated through
         the SAME NumpyPolicy `(1, obs_dim)` call the per-worker `act()`
         path runs, so served actions are BIT-IDENTICAL to local actions
         for the same params (tests/test_serve.py pins it). Row-wise
         evaluation is deliberate: batched BLAS GEMM is NOT row-wise
         bit-stable against the single-row kernel (measured ~2e-5
         divergence at 256-wide hiddens), and the bit-identity contract
         outranks CPU matmul efficiency — on CPU the batching win is in
         the dispatch/queueing machinery, not the math.
  jax    The device-serving path: params live device-resident, each batch
         is padded to the FIXED (max_batch, obs_dim) shape (one compiled
         program, no shape churn) and applied with a jitted mirror of
         models/mlp.actor_apply. Actions match the numpy oracle to float
         tolerance, not bitwise — same contract as the learner itself.

Param refresh rides the EXISTING pool-broadcast path: the server holds the
same shared-memory flat buffer + seqlock version the workers poll
(actors/pool.py `broadcast`), and re-reads it at most once per batch
dispatch — a torn snapshot is discarded exactly like a worker's
(actors/worker.py `maybe_refresh`).

Transfer integration (docs/TRANSFER.md): with a TransferScheduler
attached, every batch apply is submitted as a `serve` work item —
byte-fair against ingest/prefetch, never ahead of lockstep — so serving
and training share the host<->device bus under one accounting.
"""

from __future__ import annotations

import hashlib
import threading
from typing import Optional, Tuple

import numpy as np

from distributed_ddpg_tpu.actors.policy import (
    NumpyPolicy,
    gaussian_scale,
    layout_size,
)
from distributed_ddpg_tpu.metrics import ServeStats
from distributed_ddpg_tpu.serve.batcher import Batcher

# One serve dispatch is bounded by the scheduler's worst-case backlog
# (lockstep beats + ingest super-blocks ahead of it), not by compute.
_SCHED_TIMEOUT_S = 60.0


class InferenceServer:
    def __init__(
        self,
        layout,
        action_scale,
        action_offset=0.0,
        *,
        max_batch: int = 32,
        max_latency_s: float = 0.005,
        max_queue: int = 1024,
        backend: str = "numpy",
        param_source: Optional[Tuple] = None,  # (shared f32 array, version)
        scheduler=None,
        stats: Optional[ServeStats] = None,
        seed: int = 0,
        fault_batcher=None,
        fault_dispatch=None,
        mesh=None,
        sac: bool = False,
        log_std_min: float = -5.0,
        log_std_max: float = 2.0,
        squash: bool = True,
    ):
        if backend not in ("numpy", "jax"):
            raise ValueError(f"serve backend must be 'numpy' or 'jax', got {backend!r}")
        if mesh is not None and backend != "jax":
            raise ValueError(
                "mesh= shards the jitted serve apply; the numpy backend "
                "is the single-threaded bit-parity oracle — use "
                "backend='jax' or drop the mesh"
            )
        self.backend = backend
        # Optional (data, model) mesh for the jax backend: params shard
        # over 'model' per the partition rule tables (parallel/
        # partition.py; docs/MESH.md) — the serve path of the 2D
        # composition, so a TP learner's policy serves without gathering
        # the kernels onto one device. Activations stay replicated (the
        # padded (max_batch, obs) block is tiny next to the kernels).
        self._mesh = mesh
        self.layout = layout
        self.obs_dim = int(layout[0][0][0])  # first layer w is (obs, hidden)
        self.head_dim = int(layout[-1][0][1])
        # SAC head: the final layer is [mean | log_std] (2*act_dim wide,
        # actors/policy.actor_head_dim). The server ships HEAD rows
        # ([mean | soft-clamped log_std]) out of the batch apply and
        # squashes/samples per request with `sample()` — each client's
        # exploration stream keyed by (seed, tenant, request_id), so the
        # sampling RNG lives server-side without any cross-client
        # coupling (docs/SERVING.md 'SAC serve head').
        # `squash=False` (with `sac`): MPO's head, [mean | log scale] of a
        # plain Gaussian on the canonical box (models/mlp.gaussian_apply);
        # `sample()` clips the draw to the box where SAC's squashes it.
        self.sac = bool(sac)
        self.squash = bool(squash)
        if self.sac and self.head_dim % 2:
            raise ValueError(
                "SAC head layout must be [mean | log_std] (even width); "
                f"got final-layer width {self.head_dim} — build the "
                "layout with actor_head_dim(act_dim, sac=True)"
            )
        self.act_dim = self.head_dim // 2 if self.sac else self.head_dim
        self.log_std_min = float(log_std_min)
        self.log_std_max = float(log_std_max)
        self._sample_seed = int(seed)
        self._policy = NumpyPolicy(layout, action_scale, action_offset)
        self._param_lock = threading.Lock()
        self._param_source = param_source
        self._seen_version = -1
        self._scratch = np.empty(layout_size(layout), np.float32)
        self.scheduler = scheduler
        self.stats = stats or ServeStats(seed=seed, max_batch=max_batch)
        self._jax_apply = None
        self._jax_params = None
        if backend == "jax":
            self._build_jax_apply()
        self.batcher = Batcher(
            self._apply_batch,
            max_batch=max_batch,
            max_latency_s=max_latency_s,
            max_queue=max_queue,
            stats=self.stats,
            fault_batcher=fault_batcher,
            fault_dispatch=fault_dispatch,
        )

    # --- lifecycle ---

    def start(self) -> "InferenceServer":
        self.batcher.start()
        return self

    def overloaded(self, frac: float = 0.9) -> bool:
        """Live degraded-condition probe for the telemetry plane
        (obs/health.py `register_probe`): True while the bounded request
        queue sits past `frac` of capacity — the point where new
        requests are about to shed (Batcher's typed backpressure) and a
        canary gate must stop shifting traffic toward this process.
        Evaluated on the /healthz scrape thread, so it reads the queue
        as it is NOW, not at the last log cadence (docs/SERVING.md)."""
        return self.batcher.depth() >= frac * self.batcher.max_queue

    def close(self, timeout: float = 30.0) -> None:
        """Flush-on-shutdown: the batcher drains every accepted request
        before its thread exits (serve/batcher.py contract)."""
        self.batcher.close(timeout=timeout)

    def client(self, timeout_s: float = 1.0):
        from distributed_ddpg_tpu.serve.client import ServeClient

        return ServeClient(self, timeout_s=timeout_s)

    # --- params ---

    def refresh(self, flat: np.ndarray) -> None:
        """Install params directly from a flat f32 vector (serve_bench,
        tests; the pool path goes through _maybe_refresh instead)."""
        with self._param_lock:
            self._policy.load_flat(np.asarray(flat, np.float32))
            if self.backend == "jax":
                self._ship_jax_params()
        self.stats.record_refresh()

    def _maybe_refresh(self) -> None:
        """Seqlock read of the pool's broadcast buffer
        (policy.seqlock_snapshot — the same discard discipline the worker
        mirror uses). At most one check per batch dispatch — an int
        compare when nothing changed."""
        if self._param_source is None:
            return
        from distributed_ddpg_tpu.actors.policy import seqlock_snapshot

        shared, version = self._param_source
        v = seqlock_snapshot(shared, version, self._scratch,
                             self._seen_version)
        if v is not None:
            with self._param_lock:
                self._policy.load_flat(self._scratch)
                if self.backend == "jax":
                    self._ship_jax_params()
            self._seen_version = v
            self.stats.record_refresh()

    # --- compute ---

    def _apply_batch(self, obs: np.ndarray) -> np.ndarray:
        """The Batcher's apply_fn: refresh params, then run the batch —
        through the transfer scheduler's `serve` class when attached (the
        obs h2d + apply + action d2h accounted like any other bus user),
        inline otherwise."""
        self._maybe_refresh()
        out_dim = self.head_dim if self.sac else self.act_dim
        nbytes = obs.nbytes + obs.shape[0] * out_dim * 4
        if self.scheduler is not None:
            return self.scheduler.submit(
                "serve",
                lambda: self._compute(obs),
                nbytes=nbytes,
                label=f"serve_batch_{obs.shape[0]}",
            ).result(timeout=_SCHED_TIMEOUT_S)
        return self._compute(obs)

    def _compute(self, obs: np.ndarray) -> np.ndarray:
        with self._param_lock:
            if self.backend == "jax":
                return self._compute_jax(obs)
            # Row-wise (1, obs_dim) evaluation — the bit-identity parity
            # contract with the per-worker act() path (module docstring).
            if self.sac:
                return np.concatenate(
                    [self._head_row(row) for row in obs], axis=0
                )
            return np.concatenate([self._policy(row) for row in obs], axis=0)

    def _head_row(self, row: np.ndarray) -> np.ndarray:
        """SAC batch output: [mean | log_std] with the SAME soft clamp as
        the jax head (models/mlp.actor_gaussian_apply), so the two
        backends agree on the distribution `sample()` draws from."""
        raw = self._policy.head(row)
        mean, log_std_raw = np.split(raw, 2, axis=-1)
        if self.squash:
            log_std = self.log_std_min + 0.5 * (
                self.log_std_max - self.log_std_min
            ) * (np.tanh(log_std_raw) + 1.0)
        else:  # MPO's head: the softplus scale, no clamp
            log_std = np.log(gaussian_scale(log_std_raw))
        return np.concatenate([mean, log_std], axis=-1).astype(
            np.float32, copy=False
        )

    def sample(self, head, tenant: str, request_id: int,
               explore: bool = True) -> np.ndarray:
        """Turn one SAC head row [mean | log_std] into an action row.
        The exploration key is derived from (seed, tenant, request_id) —
        stable across processes and replayable, so the SAME request
        always samples the SAME action (the parity contract
        tests/test_serve_front.py pins) and no two clients ever share an
        RNG stream. explore=False returns the deterministic tanh(mean)
        squash (eval traffic)."""
        if not self.sac:
            # lint: ok(typed-error): caller bug (sampling a deterministic
            # head), not a runtime failure any recovery path handles
            raise RuntimeError("sample() is the SAC serve head's API")
        head = np.asarray(head, np.float32).reshape(-1)
        mean, log_std = head[: self.act_dim], head[self.act_dim:]
        if explore:
            digest = hashlib.sha256(
                f"{self._sample_seed}:{tenant}:{request_id}".encode()
            ).digest()
            rng = np.random.default_rng(
                int.from_bytes(digest[:8], "little")
            )
            eps = rng.standard_normal(mean.shape).astype(np.float32)
            u = mean + np.exp(log_std) * eps
        else:
            u = mean
        onto_box = np.tanh(u) if self.squash else np.clip(u, -1.0, 1.0)
        return (
            onto_box * self._policy.scale + self._policy.offset
        ).astype(np.float32)

    def _build_jax_apply(self) -> None:
        # THE learner's actor head (models/mlp.actor_apply), not a local
        # mirror: the serve jax backend must track any future change to
        # the head (activation, mixed-precision handling) automatically.
        import functools

        import jax

        from distributed_ddpg_tpu.models.mlp import (
            actor_apply,
            actor_gaussian_apply,
            gaussian_apply,
        )

        if self.sac and not self.squash:
            import jax.numpy as jnp

            def apply(params, obs):
                mean, std = gaussian_apply(params, obs)
                return jnp.concatenate([mean, jnp.log(std)], axis=-1)
        elif self.sac:
            # Head rows out, same [mean | log_std] contract as the numpy
            # path; sampling stays host-side in sample() (per-client
            # keys are a host concern, not a device one).
            import jax.numpy as jnp

            def apply(params, obs):
                mean, log_std = actor_gaussian_apply(
                    params, obs, self.log_std_min, self.log_std_max
                )
                return jnp.concatenate([mean, log_std], axis=-1)
        else:
            apply = functools.partial(
                actor_apply,
                action_scale=self._policy.scale,
                action_offset=self._policy.offset,
            )
        if self._mesh is None:
            self._jax_apply = jax.jit(apply)
        else:
            # TP-sharded apply (docs/MESH.md): params carry their rule-
            # table shardings (shipped below); actions come back
            # replicated so the d2h slice is placement-oblivious.
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._jax_apply = jax.jit(
                apply, out_shardings=NamedSharding(self._mesh, P())
            )
        self._ship_jax_params()

    def _ship_jax_params(self) -> None:
        import jax
        import jax.numpy as jnp

        # the learner's tree again (a residual policy's blocks and
        # LayerNorms with it), so the learner's own apply runs it
        params = jax.tree.map(jnp.asarray, self._policy.tree())
        if self._mesh is None:
            self._jax_params = jax.device_put(params)
            return
        # Same rule table as the learner (parallel/partition.py), so the
        # served mu(s) shards exactly like the training-time actor.
        from distributed_ddpg_tpu.parallel import mesh as mesh_lib

        specs = mesh_lib.net_pspec(params, self._mesh.shape["model"])
        self._jax_params = jax.device_put(
            params, mesh_lib.to_named(self._mesh, specs)
        )

    def _compute_jax(self, obs: np.ndarray) -> np.ndarray:
        n = obs.shape[0]
        if n < self.batcher.max_batch:
            # Pad to the ONE compiled shape; padded rows compute garbage
            # that is sliced away below.
            padded = np.zeros((self.batcher.max_batch, self.obs_dim), np.float32)
            padded[:n] = obs
            obs = padded
        return np.asarray(self._jax_apply(self._jax_params, obs))[:n]

    # --- observability ---

    def snapshot(self) -> dict:
        """The serve_* family (metrics.ServeStats) with the live queue
        depth riding in as a gauge."""
        return self.stats.snapshot(queue_depth=self.batcher.depth())


# ---------------------------------------------------------------------------
# program-contract analyzer hook (analysis/programs.py; docs/ANALYSIS.md
# "Layer 2")
# ---------------------------------------------------------------------------


def program_specs():
    """The jax-backend serve apply: one fixed-shape jitted mu(s) over the
    padded (max_batch, obs_dim) batch. No donation (params are shared
    across dispatches); the checks that matter here are the callback leak
    (a debug print in the serve path would ride inside every request
    deadline) and the empty collective fingerprint (serving must never
    stage a collective — it runs outside the pod's lockstep beats)."""
    from distributed_ddpg_tpu.analysis.programs import (
        BuiltProgram,
        ProgramSpec,
    )

    def build(tp: bool = False):
        def _build():
            from distributed_ddpg_tpu.actors.policy import param_layout

            layout = param_layout(3, 1, (16, 16))
            mesh = None
            if tp:
                from distributed_ddpg_tpu.analysis.programs import probe_mesh

                mesh = probe_mesh(2)
            server = InferenceServer(
                layout, np.ones(1, np.float32), backend="jax", max_batch=8,
                mesh=mesh,
            )
            obs = np.zeros((8, 3), np.float32)
            return BuiltProgram(server._jax_apply, (server._jax_params, obs))
        return _build

    return [
        ProgramSpec("serve.apply.jax", "serve/server.py", build()),
        # TP-sharded apply (docs/MESH.md): still collective-free at the
        # jaxpr level — the partitioner's kernel-shard exchange follows
        # the lowering deterministically, and serving must never stage an
        # EXPLICIT collective (it runs outside the pod's lockstep beats).
        ProgramSpec("serve.apply.jax.tp", "serve/server.py", build(tp=True)),
    ]
