"""--strict_sync lockstep mode (actors/sync_pool.py; SURVEY.md §5 race
detection, VERDICT r4 Missing #5): two runs of the same config must produce
BIT-IDENTICAL metrics — content and order — once wall-clock-derived fields
are stripped. This is the deterministic-repro contract that makes async
races debuggable by contrast."""

import json

import pytest

from distributed_ddpg_tpu.config import DDPGConfig

# Wall-clock-derived fields: everything else must match bit for bit. The
# ingest COUNT fields (ship_calls, coalesce_mean, queue_rows) stay in the
# contract — strict_sync forces inline shipping, so the ship schedule
# itself must be deterministic; only its timings may vary.
_TIME_KEYS = (
    "wall_time", "learner_steps_per_sec", "actor_steps_per_sec",
    "ingest_rows_per_sec", "ingest_stall_ms", "ingest_ship_ms",
    # Replay-placement dispatch tails (metrics.ReplayShardStats) are
    # wall-clock like ingest_ship_ms; the placement COUNT fields
    # (replay_ingest_bytes*, shard count/fill) stay in the contract.
    "replay_exchange_ms_p50", "replay_exchange_ms_p95",
    # The final record's compile / steady split of the loop's wall time.
    "first_chunk_s", "steady_s",
    # Set-up spans and compile seconds; and the launch queue's depth, which
    # is how far the device happened to lag the host at each dispatch.
    "setup_spans", "setup_compile_s", "setup_programs_compiled",
    "launches_in_flight_mean", "launches_in_flight_max",
    "n_dispatch_starved",
)


def _strip(record: dict) -> dict:
    return {
        k: v
        for k, v in record.items()
        if k not in _TIME_KEYS and not k.startswith("t_")
    }


def _run(tmp_path, tag: str) -> list:
    from distributed_ddpg_tpu.train import train_jax

    log = tmp_path / f"{tag}.jsonl"
    config = DDPGConfig(
        env_id="Pendulum-v1",
        backend="jax_tpu",
        strict_sync=True,
        num_actors=2,
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        n_step=2,
        batch_size=32,
        replay_min_size=192,
        total_env_steps=1000,
        max_learn_ratio=1.0,
        max_ingest_ratio=1.0,
        eval_every=400,
        log_path=str(log),
    )
    train_jax(config)
    return [json.loads(line) for line in log.read_text().splitlines()]


class TestStrictSync:
    def test_two_runs_bit_identical(self, tmp_path):
        a = _run(tmp_path, "a")
        b = _run(tmp_path, "b")
        assert len(a) == len(b)
        assert any(r["kind"] == "train" for r in a)
        assert any(r["kind"] == "eval" for r in a)
        for ra, rb in zip(a, b):
            assert _strip(ra) == _strip(rb)

    def test_requires_ratio_gates(self):
        with pytest.raises(ValueError, match="ratio"):
            DDPGConfig(strict_sync=True)

    def test_rejects_native_backend(self):
        with pytest.raises(ValueError, match="native"):
            DDPGConfig(
                strict_sync=True, backend="native",
                max_learn_ratio=1.0, max_ingest_ratio=1.0,
            )

    def test_rejects_host_replay(self):
        with pytest.raises(ValueError, match="device replay"):
            DDPGConfig(
                strict_sync=True, backend="jax_tpu", host_replay=True,
                max_learn_ratio=1.0, max_ingest_ratio=1.0,
            )
