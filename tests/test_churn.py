"""Fault injection + node churn (docs/architecture.md, "Failure model").

Covers the full failure stack: visible send failures on the simulated
network, the durable replication outbox (ack-on-delivery, backoff retry,
delta gap re-ship), tombstoned deletes, crash/restart with anti-entropy
catch-up, client-side timeout + failover, and the STRONG/AVAILABLE
consistency contract under failure.
"""

import pytest

from repro.core import (
    ConsistencyPolicy,
    RetryPolicy,
    is_node_down_error,
)
from repro.core.tokens import TokenizedContext
from repro.edge import EchoLLMService, EdgeCluster, LLMClient
from repro.store import (
    DistributedKVStore,
    DropWindow,
    FaultPlan,
    Link,
    Network,
    NodeDownWindow,
    PartitionWindow,
)
from repro.tokenizer import get_tokenizer


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def make_store(replication="full", latency=2.0, bw=100.0):
    net = Network(default_link=Link(latency_ms=latency, bandwidth_mbps=bw))
    store = DistributedKVStore(net, replication=replication)
    tok = get_tokenizer(32000, seed=0)
    store.create_keygroup(
        "m", ["a", "b", "c"],
        size_fn=lambda v: v.wire_bytes(tok),
        delta_size_fn=lambda v, since: v.delta_wire_bytes(tok, since),
        ttl_ms=None,
    )
    return net, store, tok


def ctx_with_turns(tok, n_turns, model="m"):
    ctx = TokenizedContext(model=model)
    for i in range(n_turns):
        ctx.extend(tok.encode(f"turn {i} about robot sensors and maps"))
        ctx.commit_turn()
    return ctx


def build_echo(n_nodes=3, latency=3.0, **client_kw):
    cluster = EdgeCluster.build(
        [f"n{i}" for i in range(n_nodes)],
        lambda nid: EchoLLMService(
            model="m", vocab_size=32000, kv_reuse=True, tokenize_scale=0.0
        ),
        inter_node_link=Link(latency_ms=latency, bandwidth_mbps=100.0),
        client_link=Link(latency_ms=1.0, bandwidth_mbps=1000.0),
    )
    return cluster


# ---------------------------------------------------------------------------
# network: visible failures + run_until truth value
# ---------------------------------------------------------------------------

def test_run_until_returns_whether_condition_held():
    net = Network()
    hits = []
    net.schedule(5.0, lambda: hits.append(1))
    # condition that never holds: queue drains -> False (was silent before)
    assert net.run_until(lambda: len(hits) >= 2) is False
    assert hits == [1]
    net.schedule(3.0 + net.clock.now_ms, lambda: hits.append(2))
    assert net.run_until(lambda: len(hits) >= 2) is True


def test_send_to_down_node_fails_visibly():
    net = Network()
    outcomes = []
    net.set_node_down("b", True)
    net.send_async("a", "b", 1000, "t", lambda: outcomes.append("delivered"),
                   on_failure=lambda r: outcomes.append(r))
    net.run_until_quiet()
    assert outcomes == ["node-down: b"]
    assert net.failed_sends == 1
    # no payload bytes billed for a refused connection
    assert net.bytes_for_tag("t") == 0


def test_partition_window_cuts_link_then_heals():
    net = Network()
    net.install_faults(FaultPlan(
        partitions=[PartitionWindow("a", "b", 10.0, 50.0)],
    ))
    assert net.reachable("a", "b")
    net.clock.advance_to(20.0)
    assert not net.reachable("a", "b")
    assert net.reachable("a", "c")          # only the named link is cut
    assert net.next_reachable_at("a", "b") == 50.0
    net.clock.advance_to(60.0)
    assert net.reachable("a", "b")


def test_message_in_flight_when_node_dies_is_lost_visibly():
    """A message already on the wire when its destination crashes is lost
    at arrival time, not silently delivered to a dead process."""
    net = Network(default_link=Link(latency_ms=10.0))
    outcomes = []
    net.send_async("a", "b", 100, "t", lambda: outcomes.append("delivered"),
                   on_failure=lambda r: outcomes.append(r))
    net.advance(5.0)
    net.set_node_down("b", True)
    net.run_until_quiet()
    assert outcomes == ["node-down: b"]
    assert net.dropped_messages == 1


# ---------------------------------------------------------------------------
# outbox: ack-on-delivery, retry, delta gap re-ship
# ---------------------------------------------------------------------------

def test_peer_acked_advances_only_on_delivery():
    """Regression for the schedule-time ack bug (distributed.py): the
    watermark must not move until the peer confirms receipt."""
    net, store, tok = make_store(latency=5.0)
    store.put("a", "m", "k", ctx_with_turns(tok, 1), 1)
    assert store._peer_acked.get(("m", "k", "a", "b"), 0) == 0  # in flight
    net.run_until_quiet()  # payload delivered + ack returned
    assert store._peer_acked[("m", "k", "a", "b")] == 1
    assert store.outbox_size() == 0


def test_dropped_delta_message_reships_the_gap():
    """Satellite regression: under delta replication a lost first message
    must not permanently diverge the peer — the retry re-ships the whole
    unacked token gap, and the replicas converge."""
    net, store, tok = make_store("delta", latency=2.0)
    # the very first sync messages (t=0) are dropped on every link
    net.install_faults(FaultPlan(
        drops=[
            DropWindow("a", "b", 0.0, 1.0, prob=1.0),
            DropWindow("a", "c", 0.0, 1.0, prob=1.0),
        ],
        seed=7,
    ))
    ctx = ctx_with_turns(tok, 1)
    store.put("a", "m", "k", ctx, 1)
    # second turn while the first message is still (droppably) in flight
    ctx.extend(tok.encode("turn 2 about particle filters"))
    ctx.commit_turn()
    store.put("a", "m", "k", ctx, 2)
    net.run_until_quiet()
    assert net.dropped_messages >= 1
    assert store.outbox_retries >= 1
    # both peers fully caught up, watermarks confirmed at the final version
    assert store.replicas_converged("m")
    assert store.get("b", "m", "k").version == 2
    assert store._peer_acked[("m", "k", "a", "b")] == 2
    assert store.outbox_size() == 0


def test_outbox_parks_while_peer_down_and_catches_up_on_restart():
    """Acceptance: a peer that is down during writes receives them all on
    rejoin via the outbox/anti-entropy path — no polling while down, no
    version lost."""
    cluster = build_echo(n_nodes=3)
    net, store = cluster.network, cluster.store
    tok = get_tokenizer(32000, seed=0)
    cluster.crash("n2")
    ctx = TokenizedContext(model="m")
    for v in (1, 2, 3):
        ctx.extend(tok.encode(f"churn write {v}"))
        ctx.commit_turn()
        store.put("n0", "m", "k", ctx, v)
    net.run_until_quiet()
    # n1 caught up normally; n2's stream is parked, not hammering the net
    assert store.get("n1", "m", "k").version == 3
    assert store.get("n2", "m", "k") is None
    assert store.outbox_size("n2") >= 1
    before = net.pending_events
    assert before == 0  # parked means parked: no retry polling events
    cluster.restart("n2")
    net.run_until_quiet()
    assert store.get("n2", "m", "k").version == 3
    assert store.replicas_converged("m")
    assert store.outbox_size() == 0
    assert cluster.converged()


def test_tombstone_blocks_inflight_stale_put():
    """Privacy path (§3.3): a client-requested delete leaves a tombstone at
    the client's turn counter, so a replicated put still in flight (or
    retrying) cannot resurrect the deleted context anywhere."""
    net, store, tok = make_store(latency=2.0)
    ctx = ctx_with_turns(tok, 2)
    # v2 ships from a but the first attempt is dropped -> retry pending
    net.install_faults(FaultPlan(
        drops=[DropWindow("a", "b", 0.0, 1.0, prob=1.0)], seed=3
    ))
    store.put("a", "m", "k", ctx, 2)
    net.advance(6.0)  # drop observed; retry scheduled but not yet fired
    # client deletes via b, passing its turn counter (2)
    store.delete("b", "m", "k", version=2)
    net.run_until_quiet()
    # the retried v2 put must NOT resurrect the context on any replica
    for n in ("a", "b", "c"):
        assert store.get(n, "m", "k") is None, n
    assert store.replica("b", "m").tombstone_rejections >= 1
    # ...but a genuinely newer session write (v3) clears the tombstone
    ctx3 = ctx_with_turns(tok, 3)
    store.put("a", "m", "k", ctx3, 3)
    net.run_until_quiet()
    assert store.get("b", "m", "k").version == 3
    assert store.replicas_converged("m")


def test_apply_hook_exception_does_not_poison_replication():
    """Satellite: one broken apply hook must not break the apply, other
    hooks, or replication — it is counted, not propagated."""
    net, store, tok = make_store()
    fired = []

    def bad_hook(kg, key, vv):
        raise RuntimeError("boom")

    store.on_apply("b", bad_hook)
    store.on_apply("b", lambda kg, key, vv: fired.append((key, vv.version)))
    store.put("a", "m", "k", ctx_with_turns(tok, 1), 1)
    net.run_until_quiet()
    assert store.prime_failures == 1
    # counted, not dropped: the error's text is kept with the counter
    assert len(store.prime_errors) == 1
    assert "RuntimeError: boom" in store.prime_errors[0]
    assert fired == [("k", 1)]
    assert store.get("b", "m", "k").version == 1
    assert store.replicas_converged("m")


# ---------------------------------------------------------------------------
# crash/restart semantics through the edge stack
# ---------------------------------------------------------------------------

def test_crash_fails_inflight_tickets_fast():
    """In-flight turns on a crashing node resolve promptly with a node-down
    error instead of hanging forever on a completion that never fires."""
    cluster = build_echo(n_nodes=1)
    client = LLMClient(cluster, model="m", failover=False)
    ticket = client.submit("hello there", "n0")
    # let the uplink arrive and the request enter the service
    cluster.run_until(lambda: ticket.request is not None and
                      cluster.network.clock.now_ms >= 2.0, max_ms=3.0)
    assert not ticket.done
    t_crash = cluster.network.clock.now_ms
    failed = cluster.crash("n0")
    assert failed == 1
    cluster.run_until_quiet()
    assert ticket.done
    assert is_node_down_error(ticket.response.error)
    # resolved ~immediately after the crash (downlink latency only), not
    # after the inference that will never complete
    assert ticket.completed_at_ms - t_crash < 100.0


def test_crash_drops_volatile_session_kv():
    cluster = build_echo(n_nodes=1)
    client = LLMClient(cluster, model="m")
    client.chat("seed the kv pool", "n0")
    svc = cluster.node("n0").service
    assert svc._kv_prefix  # session KV held
    cluster.crash("n0")
    assert not svc._kv_prefix  # volatile pool lost
    cluster.restart("n0")
    # restart re-primes from the surviving local replica
    assert svc._kv_prefix
    assert cluster.node("n0").warm_starts >= 1


def test_submit_to_down_node_fails_without_hanging():
    cluster = build_echo(n_nodes=1)
    cluster.crash("n0")
    client = LLMClient(cluster, model="m", failover=False)
    ticket = client.submit("anyone home?", "n0")
    cluster.run_until_quiet()
    assert ticket.done
    assert is_node_down_error(ticket.response.error)


def test_restart_with_lost_replica_catches_up_via_anti_entropy():
    """lose_replica=True models a non-durable store: after restart the node
    holds nothing, and anti-entropy re-fetches every context from peers —
    including re-priming the session pool through the warm-start hook."""
    cluster = build_echo(n_nodes=2)
    client = LLMClient(cluster, model="m")
    client.chat("build up context", "n0")
    client.think(500)
    client.chat("more context", "n0")
    cluster.converge()
    key = f"{client.user_id}/{client.session_id}"
    assert cluster.store.get("n1", "m", key).version == 2
    cluster.crash("n1", lose_replica=True)
    assert cluster.store.get("n1", "m", key) is None
    warm_before = cluster.node("n1").warm_starts
    cluster.restart("n1")
    cluster.converge()
    vv = cluster.store.get("n1", "m", key)
    assert vv is not None and vv.version == 2
    assert cluster.store.replicas_converged("m")
    assert cluster.node("n1").warm_starts > warm_before  # re-primed
    assert cluster.converged()


# ---------------------------------------------------------------------------
# client-side timeout + failover
# ---------------------------------------------------------------------------

def test_client_fails_over_to_keygroup_peer_on_crash():
    cluster = build_echo(n_nodes=3)
    # pin the failover rotation: this test asserts ring order specifically
    # (the salted spread has its own test in test_fleet.py)
    client = LLMClient(cluster, model="m", failover_salt=0)
    client.chat("first turn", "n0")
    cluster.converge()  # context replicated to n1/n2
    cluster.crash("n0")
    ticket = client.submit("second turn", "n0")
    cluster.run_until_quiet()
    assert ticket.done and ticket.response.error is None
    assert ticket.attempts == 2
    assert ticket.nodes_tried == ["n0", "n1"]
    assert ticket.response.served_by == "n1"
    assert ticket.response.turn == 2          # full context on the peer
    assert client.failovers == 1


def test_ticket_deadline_resolves_and_counts_timeout():
    """A node that accepts the request but never answers in time: the
    per-attempt deadline fires, the client fails over, and after the
    attempt budget the ticket resolves explicitly."""
    cluster = build_echo(n_nodes=2)
    for nid in ("n0", "n1"):
        cluster.node(nid).service.decode_ms_per_token = 1e6  # never answers
    client = LLMClient(cluster, model="m", timeout_ms=500.0, max_attempts=2)
    ticket = client.submit("too slow", "n0")
    resolved = cluster.network.run_until(lambda: ticket.done, max_ms=1e5)
    assert resolved is True
    assert is_node_down_error(ticket.response.error)
    assert "timeout" in ticket.response.error
    assert client.timeouts == 2
    assert ticket.nodes_tried == ["n0", "n1"]


def test_strong_fails_explicitly_available_serves_stale_after_failover():
    """The end-to-end consistency contract under failure: after failover to
    a peer whose replica is behind, STRONG fails explicitly (no silent
    stale serve) and AVAILABLE serves flagged-stale — the paper's §3.3
    trade-off, now exercised by a crash instead of a healthy roam."""
    def run(policy):
        cluster = build_echo(n_nodes=2, latency=1e6)  # replication never lands
        client = LLMClient(
            cluster, model="m", policy=policy, failover_backoff_ms=5.0
        )
        r1 = client.chat("first", "n0")
        assert r1.error is None
        cluster.crash("n0")  # n1's replica never caught up
        ticket = client.submit("second", "n0")
        cluster.network.run_until(lambda: ticket.done)
        return ticket.response

    strong = run(ConsistencyPolicy.STRONG)
    assert strong.error is not None and "turn" in strong.error
    assert not is_node_down_error(strong.error)   # protocol, not node, error
    assert strong.served_by == "n1"

    avail = run(ConsistencyPolicy.AVAILABLE)
    assert avail.error is None
    assert avail.stale is True                    # served, but flagged
    assert avail.served_by == "n1"


def test_node_down_window_recovers_after_plan_interval():
    """A fault-plan down window (no explicit crash call): submits during
    the window fail over or fail fast; after it ends the node serves."""
    cluster = build_echo(n_nodes=2)
    cluster.install_faults(FaultPlan(
        node_down=[NodeDownWindow("n0", 0.0, 1000.0)],
    ))
    client = LLMClient(cluster, model="m")
    t1 = client.submit("during the outage", "n0")
    assert cluster.network.run_until(lambda: t1.done) is True
    assert t1.response.served_by == "n1"              # failed over
    assert t1.nodes_tried[0] == "n0"
    cluster.network.clock.advance_to(1500.0)
    r = client.chat("after recovery", "n0")
    assert r.error is None and r.served_by == "n0"


# ---------------------------------------------------------------------------
# mini end-to-end churn
# ---------------------------------------------------------------------------

def test_routed_turn_survives_crash_behind_stale_heartbeat():
    """Fleet routing under churn (docs/architecture.md, "Fleet layer"):
    the router's freshest heartbeat for a node predates its crash, so the
    router still places the session there — the client-side failover
    backstop must turn that stale decision into a served turn on a peer,
    never a hung ticket."""
    cluster = EdgeCluster.build(
        [f"n{i}" for i in range(3)],
        lambda nid: EchoLLMService(
            model="m", vocab_size=32000, kv_reuse=True, tokenize_scale=0.0
        ),
        router="residency",
        # lazy warm start: only the serving node is KV-resident, so the
        # router provably steers this session back into the crashed node
        warm_start="off",
    )
    client = LLMClient(cluster, model="m", failover_backoff_ms=5.0)
    first = client.chat("turn one", None)
    assert first.error is None
    cluster.converge()                      # replicas + heartbeats settled
    home = first.served_by
    router = cluster.router
    assert router.reports[home].resident    # router knows the session lives here

    cluster.crash(home)                     # heartbeat now lies: report is stale
    ticket = client.submit("turn two", None)
    cluster.run_until_quiet()
    assert ticket.done and ticket.response.error is None
    assert ticket.nodes_tried[0] == home    # routed into the crash...
    assert ticket.response.served_by != home  # ...failover resolved it
    assert ticket.response.turn == 2        # on the replicated context
    assert cluster.network.pending_events == 0  # and the bus went quiet


def test_mini_churn_run_converges_and_leaves_no_hung_tickets():
    """Small end-to-end churn: roaming tenants + a crash/restart cycle +
    a partition window. Every ticket resolves (success or explicit error)
    and all live replicas are identical after convergence."""
    cluster = build_echo(n_nodes=3)
    cluster.install_faults(FaultPlan(
        partitions=[PartitionWindow("n1", "n2", 2000.0, 4000.0)],
        drop_prob=0.05,
        seed=11,
    ))
    clients = [
        LLMClient(cluster, model="m", timeout_ms=30_000.0,
                  failover_backoff_ms=10.0)
        for _ in range(4)
    ]
    nodes = ["n0", "n1", "n2"]
    traces = [
        c.run_session(
            [(f"c{i} turn {t}", nodes[(i + t) % 3]) for t in range(4)],
            think_ms=400.0,
            continue_on_error=True,
        )
        for i, c in enumerate(clients)
    ]
    # crash n0 mid-run, restart it later
    cluster.network.schedule(1000.0, lambda: cluster.crash("n0"))
    cluster.network.schedule(3000.0, lambda: cluster.restart("n0"))
    cluster.run_until_quiet()
    assert all(t.done for t in traces)
    responses = [r for t in traces for r in t.responses]
    assert len(responses) == 16               # no hung tickets, no lost turns
    ok = [r for r in responses if r.error is None]
    assert len(ok) >= 8                       # the fleet still mostly serves
    # zero silent stale serves under STRONG
    assert all(not r.stale for r in ok)
    cluster.converge()
    assert cluster.converged()
    assert cluster.store.outbox_size() == 0
