"""The learner thread's read-backs (ISSUE 49): `LaunchQueue.drain` waits for
the launches in flight one by one, a `launch_wait` span each, and every
`refresh` / `sync` of `train()` runs that drain under a nested phase before
its fetch, so no span that waits for the device is longer than one launch
and the copy's spans (`params_d2h`, `metrics_d2h`, `transfer_d2h`) bracket
the host's turnaround alone. Order and nesting only: no wall-clock
threshold."""

import json

import jax
import pytest

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.metrics import LaunchQueue


class _Leaf:
    """A launch's output leaf: ready once somebody has waited for it."""

    def __init__(self, log, k):
        self.ready, self._log, self._k = False, log, k

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self._log.append(self._k)
        self.ready = True


@pytest.fixture
def ring():
    rec = trace.configure(capacity=256)
    try:
        yield rec
    finally:
        trace.disable()


def _waits(rec):
    return [e["args"]["chunk"] for e in rec.events() if e.get("name") == "launch_wait"]


@pytest.mark.parametrize("queued, finished", [(1, 0), (3, 0), (4, 2), (5, 5)])
def test_drain_waits_for_every_queued_launch_oldest_first_under_its_dispatch_index(ring, queued, finished):
    """`finished` launches were dropped by an earlier poll: the spans carry
    the index since the run began, not the place in the queue."""
    q, waited = LaunchQueue(), []
    leaves = [_Leaf(waited, k) for k in range(queued)]
    for leaf in leaves[:finished]:
        q.poll()
        q.add(leaf, 8)
        leaf.ready = True
    for leaf in leaves[finished:]:
        q.poll()
        q.add(leaf, 8)
    in_flight = len(q)
    assert q.drain() == 8 * in_flight
    first = queued - in_flight
    assert waited == list(range(first, queued))  # oldest first, each once
    assert _waits(ring) == waited  # one span a launch, under the index `dispatch` carried
    assert len(q) == 0 and q.steps_done == 8 * queued and q.n_dispatched == queued  # as after settle()


def test_drain_of_an_empty_queue_opens_no_span_and_starves_nothing(ring):
    q = LaunchQueue()
    assert q.drain() == 0 and _waits(ring) == []
    leaf = _Leaf([], 0)
    q.poll()
    q.add(leaf, 8)
    assert q.drain() == 8 and q.drain() == 0  # the second finds nothing in flight
    assert _waits(ring) == [0]
    assert q.snapshot()["n_dispatch_starved"] == 0  # only a dispatch can find the device idle


def test_drain_without_a_recorder_still_waits():
    q, waited = LaunchQueue(), []
    q.add(_Leaf(waited, 0), 8)
    q.add(_Leaf(waited, 1), 8)
    assert not trace.enabled()
    assert q.drain() == 16 and waited == [0, 1] and len(q) == 0


# --------------------------------------------------------------------------
# train() on the CPU, flight recorder on, one host worker
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One short run for all the cases below: (learner thread's spans,
    train records). One device, as `conftest.one_chip` pins it."""
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib
    from distributed_ddpg_tpu.train import train_jax

    tmp = tmp_path_factory.mktemp("read_back")
    config = DDPGConfig(
        actor_hidden=(16, 16), critic_hidden=(16, 16), num_actors=1,
        # paced past the 50-chunk record cadence, as tests/test_trace.py explains
        total_env_steps=4_000, replay_min_size=1_500, replay_capacity=16_384, max_ingest_ratio=6.0,
        eval_every=0, param_refresh_every=32, param_refresh_interval_s=0.0,  # a refresh every fourth launch
        trace_dir=str(tmp), log_path=str(tmp / "records.jsonl"),
    )
    make = mesh_lib.make_mesh
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mesh_lib, "make_mesh", lambda data_axis=-1, model_axis=1, devices=None: make(1, 1, jax.devices()[:1]))
        out = train_jax(config)
    assert out["learner_steps"] > 0
    events = json.loads((tmp / "trace.json").read_text())["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    learner_tid = {e["tid"] for e in spans if e["name"] == "dispatch"}
    assert len(learner_tid) == 1  # every launch is made on one thread
    mine = sorted((e for e in spans if e["tid"] in learner_tid), key=lambda e: (e["ts"], -e["dur"]))
    records = [json.loads(line) for line in open(config.log_path)]
    return mine, [r for r in records if r.get("kind") == "train"]


EPS = 1.0  # microseconds: the recorder's clock resolution


def _inside(inner, outer):
    return outer["ts"] - EPS <= inner["ts"] and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + EPS


def _named(spans, name, within=None):
    return [e for e in spans if e["name"] == name and (within is None or _inside(e, within))]


@pytest.mark.parametrize("phase, copy", [("refresh", "params_d2h"), ("sync", "metrics_d2h")])
def test_the_copy_begins_after_the_last_launch_wait_of_its_read_back(run, phase, copy):
    spans, _ = run
    sites = _named(spans, phase)
    assert sites
    drained_something = 0
    for site in sites:
        (drain,) = _named(spans, f"{phase}_drain", within=site)
        (fetch,) = _named(spans, copy, within=site)
        waits = _named(spans, "launch_wait", within=site)
        assert all(_inside(w, drain) for w in waits)  # no wait for the device outside the drain
        assert fetch["ts"] >= drain["ts"] + drain["dur"] - EPS
        # the transfer class's own bracket counts the copy, not the queue
        (transfer,) = _named(spans, "transfer_d2h", within=site)
        assert transfer["ts"] >= drain["ts"] + drain["dur"] - EPS and _inside(fetch, transfer)
        drained_something += bool(waits)
    assert drained_something or phase == "sync"  # a sync right behind a refresh finds nothing in flight


def test_every_launch_wait_lies_in_a_drain_and_carries_a_dispatched_index(run):
    spans, _ = run
    dispatched = [e["args"]["chunk"] for e in _named(spans, "dispatch")]
    assert dispatched == list(range(len(dispatched)))
    drains = [e for e in spans if e["name"].endswith("_drain")]
    waits = _named(spans, "launch_wait")
    assert waits and all(any(_inside(w, d) for d in drains) for w in waits)
    waited = [w["args"]["chunk"] for w in waits]
    assert waited == sorted(set(waited))  # each launch is waited for once, oldest first
    assert set(waited) <= set(dispatched)


def test_a_drain_ends_with_the_newest_launch_so_nothing_is_left_in_flight(run):
    spans, _ = run
    for drain in (e for e in spans if e["name"].endswith("_drain")):
        waits = _named(spans, "launch_wait", within=drain)
        made = [e["args"]["chunk"] for e in _named(spans, "dispatch") if e["ts"] < drain["ts"]]
        if waits:
            assert waits[-1]["args"]["chunk"] == made[-1]
            chunks = [w["args"]["chunk"] for w in waits]
            assert chunks == list(range(chunks[0], chunks[0] + len(chunks)))


@pytest.mark.parametrize("phase", ["refresh", "sync"])
def test_records_carry_the_drain_beside_its_phase(run, phase):
    _, records = run
    have = [r for r in records if f"n_{phase}" in r]
    assert have
    for r in have:
        assert r[f"n_{phase}_drain"] == r[f"n_{phase}"]  # host actors: every read-back drains first
        assert r[f"t_{phase}_drain_ms"] <= r[f"t_{phase}_ms"]
        assert f"t_{phase}_drain_p95" in r


def test_the_first_dispatch_behind_a_read_back_finds_the_queue_empty(run):
    spans, _ = run
    for site in _named(spans, "refresh") + _named(spans, "sync"):
        after = [e for e in _named(spans, "dispatch") if e["ts"] >= site["ts"] + site["dur"] - EPS]
        if after:
            assert after[0]["args"]["in_flight"] == 0
