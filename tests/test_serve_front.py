"""Network serving front tests (serve/front/; docs/SERVING.md 'Network
front').

Pins the PR-20 acceptance contract: the wire framing + typed error codes
(no request-level failure ever kills the acceptor), per-tenant QoS with
STRICTLY lowest-priority-first overload shedding, versioned snapshots
with canary promote / gated rollback / re-promote (the tier-1 drill,
driven by the injected `front:canary:regress` chaos), the SAC serve
head's per-client sampling parity, and the front_*/tenant_* digest."""

import hashlib
import http.client
import json
import socket
import struct
import threading
import time

import numpy as np
import pytest

from distributed_ddpg_tpu.actors.policy import (
    NumpyPolicy,
    actor_head_dim,
    layout_size,
    param_layout,
)
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.faults import FaultPlan, InjectedFault
from distributed_ddpg_tpu.serve import InferenceServer
from distributed_ddpg_tpu.serve.batcher import Batcher
from distributed_ddpg_tpu.serve.front import (
    CanaryGate,
    FrontClient,
    FrontError,
    FrontServer,
    QosGate,
    SnapshotStore,
    TokenBucket,
    parse_tenants,
    wire,
)

OBS, ACT = 5, 2
LAYOUT = param_layout(OBS, ACT, (16, 16))


def _flat(seed=0, layout=LAYOUT):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(layout_size(layout)) * 0.3).astype(
        np.float32
    )


def _obs(seed=1):
    return np.random.default_rng(seed).standard_normal(OBS).astype(
        np.float32
    )


def _make_engine(**kw):
    def make():
        return InferenceServer(
            LAYOUT, np.ones(ACT, np.float32),
            max_batch=kw.get("max_batch", 8),
            max_latency_s=kw.get("max_latency_s", 0.002),
            max_queue=kw.get("max_queue", 64),
        )
    return make


def _start_front(**kw):
    """A started FrontServer with 'v1' published stable (ephemeral ports;
    http unless disabled)."""
    front = FrontServer(_make_engine(), **kw)
    front.publish("v1", _flat(1))
    return front.start()


# ---------------------------------------------------------------------------
# wire: framing + request validation + typed error contract
# ---------------------------------------------------------------------------


def test_wire_frame_roundtrip_and_framing_errors():
    a, b = socket.socketpair()
    try:
        wire.send_frame(a, {"tenant": "t", "request_id": 1, "obs": [0.5]})
        obj = wire.read_frame(b)
        assert obj == {"tenant": "t", "request_id": 1, "obs": [0.5]}

        # Oversized length prefix = lost framing.
        a.sendall(struct.pack(">I", wire.MAX_FRAME + 1))
        with pytest.raises(wire.WireError) as e:
            wire.read_frame(b)
        assert e.value.code == "bad_frame"

        # Well-framed garbage body is recoverable (typed, not torn).
        a2, b2 = socket.socketpair()
        try:
            body = b"not json"
            a2.sendall(struct.pack(">I", len(body)) + body)
            with pytest.raises(wire.WireError):
                wire.read_frame(b2)
            # A non-dict JSON body is bad_frame too.
            body = b"[1,2]"
            a2.sendall(struct.pack(">I", len(body)) + body)
            with pytest.raises(wire.WireError):
                wire.read_frame(b2)
        finally:
            a2.close()
            b2.close()

        # Clean EOF before any byte -> None; EOF mid-frame -> torn.
        a3, b3 = socket.socketpair()
        a3.close()
        assert wire.read_frame(b3) is None
        b3.close()
        a4, b4 = socket.socketpair()
        a4.sendall(struct.pack(">I", 100) + b"{")
        a4.close()
        with pytest.raises(wire.WireError):
            wire.read_frame(b4)
        b4.close()
    finally:
        a.close()
        b.close()


def test_wire_validate_request_and_error_codes():
    good = wire.validate_request(
        {"tenant": "t", "request_id": 3, "obs": [1, 2.5]}
    )
    assert good == {"tenant": "t", "request_id": 3, "obs": [1, 2.5],
                    "version": None}
    for bad in (
        {},                                             # no tenant
        {"tenant": 7, "request_id": 1, "obs": [1.0]},   # non-str tenant
        {"tenant": "t", "obs": [1.0]},                  # no request_id
        {"tenant": "t", "request_id": True, "obs": [1.0]},  # bool rid
        {"tenant": "t", "request_id": 1},               # no obs
        {"tenant": "t", "request_id": 1, "obs": []},    # empty obs
        {"tenant": "t", "request_id": 1, "obs": [1.0, "x"]},  # non-number
        {"tenant": "t", "request_id": 1, "obs": [1.0], "version": 4},
    ):
        with pytest.raises(wire.WireError) as e:
            wire.validate_request(bad)
        assert e.value.code == "bad_frame"

    assert set(wire.error_response(1, "shed", "m")) == {
        "request_id", "error", "message",
    }
    with pytest.raises(ValueError):
        wire.error_response(1, "not_a_code", "m")
    with pytest.raises(ValueError):
        wire.WireError("not_a_code", "m")
    with pytest.raises(wire.WireError):
        wire.encode_frame({"obs": [0.0] * (wire.MAX_FRAME // 4)})


# ---------------------------------------------------------------------------
# qos: tenant table grammar, token bucket, priority-staggered thresholds
# ---------------------------------------------------------------------------


def test_parse_tenants_grammar():
    table = parse_tenants("gold:0;silver:1:10;bronze:3:5:20")
    assert table["gold"].priority == 0 and table["gold"].rate == 0.0
    assert table["silver"] == ("silver", 1, 10.0, 10.0)  # burst = rate
    assert table["bronze"].burst == 20.0
    assert parse_tenants("") == {}
    assert parse_tenants(" ; ") == {}
    for bad in (
        "gold",            # no priority
        "gold:0:1:2:3",    # too many fields
        ":0",              # empty name
        "gold:x",          # non-numeric priority
        "gold:-1",         # negative priority
        "gold:0:-2",       # negative rate
        "gold:0:5:0.5",    # burst < 1
        "gold:0;gold:1",   # duplicate
    ):
        with pytest.raises(ValueError):
            parse_tenants(bad)


def test_token_bucket_fake_clock():
    b = TokenBucket(rate=2.0, burst=2.0)
    assert b.allow(0.0) and b.allow(0.0)   # burst drains
    assert not b.allow(0.0)                # empty
    assert not b.allow(0.25)               # 0.5 tokens refilled: still < 1
    assert b.allow(0.5)                    # 1 token back
    assert b.allow(10.0)                   # refill caps at burst
    assert b.allow(10.0)
    assert not b.allow(10.0)


def test_qos_thresholds_strictly_priority_ordered():
    gate = QosGate(parse_tenants("a:0;b:1;c:2;d:3"), default_priority=2,
                   shed_start=0.5)
    # Priority 0 never depth-sheds; lower classes shed strictly earlier.
    assert gate.threshold(0) == 1.0
    ts = [gate.threshold(p) for p in (1, 2, 3)]
    assert ts[0] > ts[1] > ts[2] == 0.5  # lowest class sheds at shed_start
    assert gate.priority("a") == 0
    assert gate.priority("unknown") == 2  # default class


def test_qos_admit_rate_and_priority_causes():
    clock = [0.0]
    gate = QosGate(
        parse_tenants("gold:0;capped:1:1:1;bronze:2"),
        shed_start=0.5, clock=lambda: clock[0],
    )
    # Token bucket fires regardless of load.
    assert gate.admit("capped", 0, 100) is None
    assert gate.admit("capped", 0, 100) == "rate"
    clock[0] = 1.0
    assert gate.admit("capped", 0, 100) is None
    # Depth shedding: bronze (lowest) sheds at 50%, gold never.
    assert gate.admit("bronze", 49, 100) is None
    assert gate.admit("bronze", 50, 100) == "priority"
    assert gate.admit("gold", 99, 100) is None


# ---------------------------------------------------------------------------
# snapshots: store lifecycle + deterministic canary routing
# ---------------------------------------------------------------------------


def test_snapshot_store_lifecycle_and_routing():
    store = SnapshotStore()
    with pytest.raises(RuntimeError):
        store.route("t", 1)  # nothing published yet
    store.publish("v1", _flat(1))
    assert store.stable == "v1"  # first publish becomes stable
    with pytest.raises(ValueError):
        store.publish("v1", _flat(2))  # versions are immutable
    frozen = store.get("v1")
    with pytest.raises(ValueError):
        frozen[0] = 9.0  # read-only copy

    store.publish("v2", _flat(2))
    assert store.route("t", 1) == ("v1", False)  # no canary yet
    with pytest.raises(ValueError):
        store.start_canary("v1", 0.5)  # already stable
    with pytest.raises(KeyError):
        store.start_canary("v9", 0.5)
    with pytest.raises(ValueError):
        store.start_canary("v2", 1.0)  # fraction must be in (0,1)
    store.start_canary("v2", 0.5)
    with pytest.raises(ValueError):
        store.start_canary("v2", 0.5)  # one canary at a time

    # Deterministic split: same request always routes the same way, and
    # both arms actually receive traffic at fraction=0.5.
    routes = [store.route("tenant", rid) for rid in range(200)]
    assert routes == [store.route("tenant", rid) for rid in range(200)]
    arms = {is_canary for _, is_canary in routes}
    assert arms == {True, False}

    assert store.promote() == "v2"
    assert store.stable == "v2" and store.candidate is None
    assert store.route("tenant", 1) == ("v2", False)
    assert store.rollback() is None  # idempotent with no canary
    store.publish("v3", _flat(3))
    store.start_canary("v3", 0.3)
    assert store.rollback() == "v3"
    assert store.stable == "v2"
    with pytest.raises(ValueError):
        store.promote()  # no candidate left


def test_canary_gate_verdicts():
    # Not enough data -> None; clean candidate -> promote.
    gate = CanaryGate(min_requests=5, threshold=0.5)
    for i in range(4):
        gate.record(False, 0.010)
        gate.record(True, 0.010)
    assert gate.verdict() is None
    gate.record(False, 0.010)
    gate.record(True, 0.010)
    assert gate.verdict() == "promote"

    # Latency regression past threshold -> rollback.
    gate.reset()
    for i in range(6):
        gate.record(False, 0.010)
        gate.record(True, 0.030)  # 3x stable p95
    assert gate.verdict() == "rollback"
    s = gate.stats()
    assert s["candidate_p95_ms"] > s["stable_p95_ms"]

    # Error-rate gate trips WITHOUT waiting for the latency quota.
    gate.reset()
    for i in range(5):
        gate.record(False, 0.010)
        gate.record(True, 0.010, error=True)
    assert gate.verdict() == "rollback"

    # reset() forgets the previous round.
    gate.reset()
    assert gate.verdict() is None


# ---------------------------------------------------------------------------
# front server end to end: TCP, HTTP, typed errors, acceptor survival
# ---------------------------------------------------------------------------


def test_front_tcp_end_to_end_and_typed_errors():
    front = _start_front()
    try:
        with FrontClient(front.port, tenant="t0") as cli:
            action, version = cli.act(_obs())
            assert action.shape == (ACT,) and version == "v1"
            # Served action matches the engine's policy math.
            pol = NumpyPolicy(LAYOUT, np.ones(ACT, np.float32))
            pol.load_flat(_flat(1))
            assert np.array_equal(action, pol(_obs()).reshape(-1))

            # Explicit version pin; unknown version is a typed bad_frame.
            _, v = cli.act(_obs(), version="v1")
            assert v == "v1"
            with pytest.raises(FrontError) as e:
                cli.act(_obs(), version="nope")
            assert e.value.code == "bad_frame"

            # Malformed request objects answer typed ON THE SAME
            # connection — which keeps serving afterwards.
            resp = cli.request({"tenant": "", "request_id": 1,
                                "obs": [1.0]})
            assert resp["error"] == "bad_frame"
            resp = cli.request({"tenant": "t0", "request_id": "x",
                                "obs": [1.0]})
            assert resp["error"] == "bad_frame"
            action, _ = cli.act(_obs())
            assert action.shape == (ACT,)
        snap = front.snapshot()
        assert snap["front_requests"] >= 4
        assert snap["front_bad_frames"] >= 2
        assert snap["front_wire_p95_ms"] > 0.0
        assert snap["tenant_served"] >= 3
    finally:
        front.stop()


def test_front_bad_length_prefix_tears_only_that_connection():
    front = _start_front()
    try:
        good = FrontClient(front.port, tenant="survivor")
        bad = socket.create_connection(("127.0.0.1", front.port),
                                       timeout=5.0)
        # Garbage length prefix: one typed bad_frame answer, then THAT
        # connection closes.
        bad.sendall(struct.pack(">I", wire.MAX_FRAME + 7))
        resp = wire.read_frame(bad)
        assert resp["error"] == "bad_frame"
        assert bad.recv(1) == b""  # server closed it
        bad.close()
        # Everyone else keeps serving.
        action, _ = good.act(_obs())
        assert action.shape == (ACT,)
        good.close()
        assert front.snapshot()["front_bad_frames"] >= 1
    finally:
        front.stop()


def test_front_http_adapter():
    front = _start_front()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", front.http_port,
                                          timeout=5.0)
        body = json.dumps({"tenant": "h", "request_id": 1,
                           "obs": _obs().tolist()})
        conn.request("POST", "/act", body,
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        obj = json.loads(r.read())
        assert r.status == 200
        assert obj["version"] == "v1" and len(obj["action"]) == ACT

        # Unparseable body -> 400 typed bad_frame.
        conn.request("POST", "/act", "not json")
        r = conn.getresponse()
        assert r.status == 400
        assert json.loads(r.read())["error"] == "bad_frame"

        # Wrong path -> 404.
        conn.request("POST", "/elsewhere", body)
        r = conn.getresponse()
        assert r.status == 404
        r.read()

        # Typed request-level error maps to its advisory status.
        conn.request("POST", "/act", json.dumps({"request_id": 1,
                                                 "obs": [1.0]}))
        r = conn.getresponse()
        assert r.status == 400
        assert json.loads(r.read())["error"] == "bad_frame"
        conn.close()
        snap = front.snapshot()
        assert snap["front_http_requests"] >= 1
    finally:
        front.stop()


# ---------------------------------------------------------------------------
# chaos: fault grammar + injected drills (acceptor never dies)
# ---------------------------------------------------------------------------


def test_front_fault_grammar():
    plan = FaultPlan.parse(
        "front:accept:stall@1~0.01;front:frame:corrupt@2;"
        "front:canary:regress@3~0.05"
    )
    assert plan.front_canary_regressions() == ((3, 0.05),)
    assert plan.site("front", "accept")._by_at  # accept specs routed
    for bad in (
        "front:accept:corrupt@1",   # corrupt is frame-only
        "front:canary:stall@1",     # regress is the only canary kind
        "front:frame:stall@1",
        "front:unknown:stall@1",
    ):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)


def test_front_frame_corrupt_fault_connection_survives():
    plan = FaultPlan.parse("front:frame:corrupt@2")
    front = FrontServer(_make_engine(),
                        fault_frame=plan.site("front", "frame"))
    front.publish("v1", _flat(1))
    front.start()
    try:
        with FrontClient(front.port, tenant="t") as cli:
            cli.act(_obs())                      # frame 1: clean
            with pytest.raises(FrontError) as e:
                cli.act(_obs())                  # frame 2: injected corrupt
            assert e.value.code == "bad_frame"
            action, _ = cli.act(_obs())          # frame 3: SAME connection
            assert action.shape == (ACT,)
        assert front.snapshot()["front_bad_frames"] >= 1
    finally:
        front.stop()


def test_front_accept_stall_fault_acceptor_survives():
    plan = FaultPlan.parse("front:accept:stall@1~0.05")
    site = plan.site("front", "accept")
    front = FrontServer(_make_engine(), fault_accept=site)
    front.publish("v1", _flat(1))
    front.start()
    try:
        t0 = time.monotonic()
        with FrontClient(front.port, tenant="t") as cli:
            cli.act(_obs())  # first connection eats the stall
        assert time.monotonic() - t0 >= 0.05
        assert site.fired
        with FrontClient(front.port, tenant="t") as cli:
            cli.act(_obs())  # later connections unaffected
    finally:
        front.stop()


# ---------------------------------------------------------------------------
# tier-1 drill: overload sheds strictly lowest-priority-first
# ---------------------------------------------------------------------------


class _BlockedEngine:
    """A front engine whose dispatcher is parked inside apply until
    released — the queue DEPTH is under test control, so shed thresholds
    are exercised deterministically instead of by racing load."""

    sac = False

    def __init__(self, max_queue=20):
        self.release = threading.Event()
        self.entered = threading.Event()
        self.batcher = Batcher(self._apply, max_batch=1,
                               max_latency_s=0.001, max_queue=max_queue)

    def _apply(self, batch):
        self.entered.set()
        self.release.wait(timeout=30.0)
        return batch[:, :ACT].copy()

    def refresh(self, flat):
        pass

    def start(self):
        self.batcher.start()
        return self

    def close(self, timeout=5.0):
        self.release.set()
        self.batcher.close(timeout=timeout)


def test_shed_ordering_strictly_lowest_priority_first():
    """The QoS acceptance drill: under a deep queue, bronze (priority 2)
    sheds before silver (1), silver before gold (0), and gold NEVER
    depth-sheds — with the per-tenant counters proving the order."""
    engines = []

    def make():
        eng = _BlockedEngine(max_queue=20)
        engines.append(eng)
        return eng

    front = FrontServer(
        make, tenants="gold:0;silver:1;bronze:2",
        shed_start=0.5, timeout_s=0.05, http_port=None,
    )
    front.publish("v1", _flat(1))
    front.start()
    try:
        def req(tenant, rid):
            return front.handle_request(
                {"tenant": tenant, "request_id": rid,
                 "obs": _obs().tolist()}
            )

        # Park the dispatcher inside apply with one sacrificial request.
        resp = req("gold", 1)
        eng = engines[0]
        assert eng.entered.wait(timeout=5.0)
        assert resp["error"] == "timeout"  # typed, acceptor alive

        def fill_to(depth):
            while eng.batcher.depth() < depth:
                eng.batcher.submit(np.zeros(OBS, np.float32),
                                   lambda _r: None)

        # Thresholds (max_queue=20, shed_start=0.5, P=2):
        # bronze sheds at depth >= 10, silver at >= 15, gold never.
        fill_to(10)
        assert req("bronze", 2)["error"] == "shed"
        assert req("silver", 3)["error"] == "timeout"  # admitted
        assert req("gold", 4)["error"] == "timeout"    # admitted

        fill_to(16)
        assert req("bronze", 5)["error"] == "shed"
        assert req("silver", 6)["error"] == "shed"
        assert req("gold", 7)["error"] == "timeout"    # still admitted

        per = front.tenant_stats.per_tenant()
        assert per["bronze"]["shed_priority"] == 2
        assert per["silver"]["shed_priority"] == 1
        assert per["gold"]["shed_priority"] == 0
        # Strict ordering: shed counts are monotone in priority class.
        assert (per["bronze"]["shed_priority"]
                > per["silver"]["shed_priority"]
                > per["gold"]["shed_priority"])
        snap = front.snapshot()
        assert snap["front_sheds"] == 3
        assert snap["tenant_shed_priority"] == 3
        assert snap["front_timeouts"] == 4

        # Release the dispatcher; everything drains and serves again.
        eng.release.set()
        deadline = time.monotonic() + 5.0
        while eng.batcher.depth() > 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        # (retry: the 0.05s server deadline is tight under box load)
        for attempt in range(20):
            ok = req("bronze", 8 + attempt)
            if "action" in ok:
                break
        assert "action" in ok
    finally:
        front.stop()


def test_tenant_rate_cap_shed_cause():
    """The 'rate' shed cause fires from the tenant's own bucket even with
    an empty queue — counted under tenant_shed_rate, not priority."""
    front = FrontServer(_make_engine(), tenants="capped:1:0.001:1",
                        http_port=None)
    front.publish("v1", _flat(1))
    front.start()
    try:
        def req(rid):
            return front.handle_request(
                {"tenant": "capped", "request_id": rid,
                 "obs": _obs().tolist()}
            )
        assert "action" in req(1)        # burst token
        resp = req(2)                    # bucket empty (0.001/s refill)
        assert resp["error"] == "shed" and "rate" in resp["message"]
        per = front.tenant_stats.per_tenant()
        assert per["capped"]["shed_rate"] == 1
        assert per["capped"]["shed_priority"] == 0
    finally:
        front.stop()


# ---------------------------------------------------------------------------
# tier-1 drill: canary promote -> gated rollback -> re-promote
# ---------------------------------------------------------------------------


def test_canary_drill_rollback_then_repromote():
    """The version-lifecycle acceptance drill: an injected sustained
    candidate regression (front:canary:regress) must be auto-rolled-back
    by the live gate — never promoted — and once the regression is gone
    the SAME version re-canaries and promotes, all over one surviving
    TCP connection with typed responses throughout."""
    plan = FaultPlan.parse("front:canary:regress@1~0.05")
    front = FrontServer(
        _make_engine(), canary_fraction=0.5, canary_min_requests=5,
        canary_threshold=0.5, http_port=None,
        canary_regressions=plan.front_canary_regressions(),
    )
    front.publish("v1", _flat(1))
    front.publish("v2", _flat(2))
    front.start()
    try:
        cli = FrontClient(front.port, tenant="drill", timeout_s=10.0)

        def drive_until(pred, budget=400):
            for _ in range(budget):
                cli.act(_obs())  # front_timeout_s=2 bounds each request
                if pred(front.snapshot()):
                    return True
            return False

        # Round 1: regressing candidate. The gate must roll back.
        front.start_canary("v2")
        assert drive_until(lambda s: s["front_rollbacks"] >= 1), \
            "regressing canary was never rolled back"
        snap = front.snapshot()
        assert snap["front_promotes"] == 0, "regressing canary promoted!"
        assert snap["front_canary_requests"] > 0
        assert front.store.stable == "v1"
        assert front.store.candidate is None

        # Round 2: the regression is fixed (injection cleared); the same
        # version re-canaries and must promote. Both arms now run the
        # identical engine, but scheduler jitter on a loaded box can
        # still fake a p95 delta over 5-sample arms — re-canary on a
        # spurious rollback rather than flake.
        front._canary_regs = ()
        promoted = False
        for _attempt in range(5):
            before = front.snapshot()
            front.start_canary("v2")
            assert drive_until(
                lambda s, b=before: s["front_promotes"] > b["front_promotes"]
                or s["front_rollbacks"] > b["front_rollbacks"]
            )
            if front.snapshot()["front_promotes"] > before["front_promotes"]:
                promoted = True
                break
        assert promoted, "fixed candidate never re-promoted"
        assert front.store.stable == "v2"

        # Zero acceptor deaths: the connection that drove the whole
        # drill still serves, from the promoted version.
        action, version = cli.act(_obs())
        assert action.shape == (ACT,) and version == "v2"
        cli.close()
    finally:
        front.stop()


# ---------------------------------------------------------------------------
# SAC serve head: per-client server-side sampling parity
# ---------------------------------------------------------------------------

SAC_LAYOUT = param_layout(OBS, actor_head_dim(ACT, sac=True), (16, 16))
SAC_SEED = 11
LOG_STD_MIN, LOG_STD_MAX = -5.0, 2.0


def _sac_server(**kw):
    return InferenceServer(
        SAC_LAYOUT, np.ones(ACT, np.float32), sac=True, seed=SAC_SEED,
        log_std_min=LOG_STD_MIN, log_std_max=LOG_STD_MAX,
        max_batch=kw.get("max_batch", 8),
        max_latency_s=kw.get("max_latency_s", 0.002),
        max_queue=kw.get("max_queue", 64),
    )


def _local_sac_reference(flat, obs, tenant, request_id):
    """Independent recomputation of the served SAC sample: the same head
    math (soft clamp incl.) and the same sha256-derived per-request key —
    the parity oracle docs/SERVING.md 'SAC serve head' promises."""
    pol = NumpyPolicy(SAC_LAYOUT, np.ones(ACT, np.float32))
    pol.load_flat(flat)
    raw = pol.head(obs).reshape(-1)
    mean, log_std_raw = raw[:ACT], raw[ACT:]
    log_std = LOG_STD_MIN + 0.5 * (LOG_STD_MAX - LOG_STD_MIN) * (
        np.tanh(log_std_raw) + 1.0
    )
    head = np.concatenate([mean, log_std]).astype(np.float32)
    mean, log_std = head[:ACT], head[ACT:]
    digest = hashlib.sha256(
        f"{SAC_SEED}:{tenant}:{request_id}".encode()
    ).digest()
    rng = np.random.default_rng(int.from_bytes(digest[:8], "little"))
    eps = rng.standard_normal(mean.shape).astype(np.float32)
    u = mean + np.exp(log_std) * eps
    return np.tanh(u).astype(np.float32)  # scale=1, offset=0


def test_sac_sample_parity_and_key_schedule():
    server = _sac_server().start()
    try:
        flat = _flat(3, SAC_LAYOUT)
        server.refresh(flat)
        client = server.client(timeout_s=5.0)
        obs = _obs(7)
        for tenant, rid in (("local", 1), ("local", 2)):
            served = client.act(obs)
            expected = _local_sac_reference(flat, obs, tenant, rid)
            assert np.array_equal(served, expected), (tenant, rid)
        # Different (tenant, request_id) -> different exploration draws;
        # identical key -> identical action (replayable).
        head = server._compute(obs[None, :])[0]
        a = server.sample(head, tenant="a", request_id=1)
        b = server.sample(head, tenant="b", request_id=1)
        a2 = server.sample(head, tenant="a", request_id=1)
        assert np.array_equal(a, a2)
        assert not np.array_equal(a, b)
        # explore=False is the deterministic squash.
        det = server.sample(head, tenant="a", request_id=1, explore=False)
        assert np.array_equal(det, np.tanh(head[:ACT]).astype(np.float32))
        assert np.all(np.abs(a) <= 1.0)
    finally:
        server.close()

    # The deterministic server rejects sample() loudly.
    det_server = InferenceServer(LAYOUT, np.ones(ACT, np.float32))
    with pytest.raises(RuntimeError):
        det_server.sample(np.zeros(ACT), tenant="t", request_id=1)


def test_sac_served_over_the_network_front():
    """End-to-end wire parity: the SAME (tenant, request_id) replays to
    the SAME sampled action across connections, bit-identical to the
    local reference for a fixed key schedule."""
    flat = _flat(5, SAC_LAYOUT)
    front = FrontServer(_sac_server, http_port=None)
    front.publish("v1", flat)
    front.start()
    try:
        obs = _obs(9)
        with FrontClient(front.port, tenant="alice") as cli:
            for rid in (10, 11):
                action, version = cli.act(obs, request_id=rid)
                expected = _local_sac_reference(flat, obs, "alice", rid)
                assert np.array_equal(action, expected)
            replay, _ = cli.act(obs, request_id=10)
        with FrontClient(front.port, tenant="bob") as cli:
            other, _ = cli.act(obs, request_id=10)
        assert np.array_equal(
            replay, _local_sac_reference(flat, obs, "alice", 10)
        )
        assert not np.array_equal(replay, other)  # no shared RNG stream
    finally:
        front.stop()


# ---------------------------------------------------------------------------
# config: the front knob surface
# ---------------------------------------------------------------------------


def test_config_front_validation():
    # sac + serve_actors is now a supported pairing (the SAC serve head).
    DDPGConfig(serve_actors=True, sac=True)
    # The front rides serve_actors.
    with pytest.raises(ValueError):
        DDPGConfig(front_port=7777)
    DDPGConfig(serve_actors=True, front_port=7777)
    DDPGConfig(serve_actors=True, front_http_port=7778)
    for bad in (
        dict(front_port=-1),
        dict(front_port=70000),
        dict(front_http_port=70000),
        dict(serve_actors=True, front_port=7777, front_http_port=7777),
        dict(front_timeout_s=0.0),
        dict(front_canary_fraction=0.0),
        dict(front_canary_fraction=1.0),
        dict(front_canary_min_requests=0),
        dict(front_canary_threshold=0.0),
        dict(front_default_priority=-1),
        dict(front_shed_start=0.0),
        dict(front_shed_start=1.5),
        dict(front_tenants="gold"),           # malformed table
        dict(front_tenants="a:0;a:1"),        # duplicate tenant
    ):
        with pytest.raises(ValueError):
            DDPGConfig(**bad)


# ---------------------------------------------------------------------------
# tools: socket bench + runs digest
# ---------------------------------------------------------------------------


def test_socket_bench_closed_loop():
    from distributed_ddpg_tpu.tools.serve_bench import run_socket_bench

    r = run_socket_bench(
        clients=2, duration_s=0.4, obs_dim=4, act_dim=2, hidden=(8, 8),
        max_batch=4, max_latency_ms=2.0, tenants="gold:0;bronze:3",
    )
    assert r["transport"] == "socket"
    assert r["served_rps"] > 0
    assert r["front_requests"] > 0
    assert r["wire_p95_ms"] > 0
    assert r["front_wire_p95_ms"] > 0
    assert r["tenant_count"] == 2  # the tenant table named the clients


def test_runs_summarize_and_compare_render_front_digest(tmp_path):
    from distributed_ddpg_tpu.tools import runs

    path = tmp_path / "front.jsonl"
    recs = [
        {"kind": "train", "step": 100, "wall_time": 1.0,
         "front_requests": 40, "front_sheds": 1, "front_wire_p95_ms": 3.0,
         "front_rollbacks": 0, "tenant_served": 39, "tenant_shed_total": 1},
        {"kind": "train", "step": 200, "wall_time": 2.0,
         "front_requests": 90, "front_sheds": 3, "front_wire_p95_ms": 5.0,
         "front_rollbacks": 1, "tenant_served": 87, "tenant_shed_total": 3},
        {"kind": "final", "step": 200, "wall_time": 2.5,
         "front_requests": 95, "front_wire_p95_ms": 4.0},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    digest = runs.summarize_run(str(path))
    assert digest["front"]["front_requests"]["last"] == 95
    assert digest["front"]["front_wire_p95_ms"]["max"] == 5.0
    text = runs.render_summary(digest)
    assert "network front" in text
    assert "front_wire_p95_ms" in text
    _, rows = runs.compare_runs(str(path), str(path))
    assert any(r[0] == "front_wire_p95_ms" for r in rows)


# ---------------------------------------------------------------------------
# slow: end-to-end train run with the front armed (FRONT_FULL smoke)
# ---------------------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_train_with_front_armed(tmp_path):
    """train.py arms the front next to served actors: external TCP
    traffic lands during the run and front_* / tenant_* ride the final
    record."""
    from distributed_ddpg_tpu.train import train_jax

    port = _free_port()
    cfg = DDPGConfig(
        env_id="Pendulum-v1",
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        num_actors=2,
        total_env_steps=1_500,
        replay_min_size=256,
        replay_capacity=20_000,
        eval_every=0,
        max_learn_ratio=1.0,
        max_ingest_ratio=1.0,
        log_path=str(tmp_path / "front.jsonl"),
        serve_actors=True,
        serve_max_batch=8,
        serve_max_latency_ms=1.0,
        front_port=port,
        front_tenants="gold:0;bronze:3",
    )
    served = [0]
    stop = threading.Event()

    def external_traffic():
        obs = np.zeros(3, np.float32)  # Pendulum obs dim
        while not stop.is_set():
            try:
                with FrontClient(port, tenant="gold",
                                 timeout_s=2.0) as cli:
                    while not stop.is_set():
                        cli.act(obs)
                        served[0] += 1
                        time.sleep(0.01)
            except (OSError, FrontError, ConnectionError):
                time.sleep(0.05)  # front not up yet / shutting down

    t = threading.Thread(target=external_traffic, daemon=True)
    t.start()
    try:
        out = train_jax(cfg)
    finally:
        stop.set()
        t.join(timeout=5.0)
    assert out["learner_steps"] > 0
    with open(cfg.log_path) as f:
        recs = [json.loads(ln) for ln in f if ln.strip().startswith("{")]
    finals = [r for r in recs if r.get("kind") == "final"]
    assert finals
    final = finals[-1]
    for key in ("front_requests", "front_sheds", "front_wire_p95_ms",
                "tenant_count", "tenant_served"):
        assert key in final, f"{key} missing from the final record"
    if served[0]:
        assert final["front_requests"] >= served[0]
        assert final["tenant_count"] >= 1
