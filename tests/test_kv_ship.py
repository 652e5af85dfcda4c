"""KV-page shipping (docs/architecture.md, "KV page shipping").

Covers the wire protocol at the unit level (digest-verified chunks,
watermark ACKs, resume after crash, corrupt/stale rejection, billed-bytes
accounting), the cost model's crossover, the cluster integration (echo
services shipping virtual pages between nodes with full provenance), and
MB-scale transfer timing under degraded links.
"""

import pytest

from _hypothesis_support import HAVE_HYPOTHESIS, given, settings, st

from repro.core.tokens import TokenizedContext
from repro.edge import EchoLLMService, EdgeCluster, LLMClient
from repro.store import (
    DegradedWindow,
    DistributedKVStore,
    DropWindow,
    FaultPlan,
    KVShipper,
    Link,
    MESSAGE_OVERHEAD_BYTES,
    Network,
    NodeShipProfile,
    PageShipment,
    PartitionWindow,
    page_digests,
)
from repro.store.kv_ship import (
    ACK_BYTES,
    CHUNK_HEADER_BYTES,
    CTRL_BYTES,
    DIGEST_BYTES,
    KV_SHIP_DATA_TAG,
)
from repro.tokenizer import get_tokenizer


# ---------------------------------------------------------------------------
# unit harness: KVShipper over stub nodes with dict-backed "KV pools"
# ---------------------------------------------------------------------------

PS = 16              # page size
PAGE_WIRE = 65536    # bytes per page on the wire


def _payload(digest: bytes) -> bytes:
    reps = -(-PAGE_WIRE // len(digest))
    return (digest * reps)[:PAGE_WIRE]


class StubNode:
    """Dict-backed shipping hooks: resident token prefixes instead of a
    real page pool, payloads derived from the page digests (so two stubs
    holding the same prefix export identical bytes)."""

    def __init__(self, prefill_ms=0.9, state_is_o1=False):
        self.resident = {}           # key -> token ids
        self.installs = []           # (key, n_tokens, n_payloads, have)
        self.fallbacks = []          # (key, reason)
        self.prefill_ms = prefill_ms
        self.state_is_o1 = state_is_o1

    def profile(self):
        return NodeShipProfile(
            page_size=PS, page_wire_bytes=PAGE_WIRE,
            prefill_ms_per_token=self.prefill_ms,
            state_is_o1=self.state_is_o1,
        )

    def exporter(self, key):
        ids = self.resident.get(key)
        if ids is None:
            return None
        return PageShipment(
            token_ids=list(ids),
            payloads=[_payload(d) for d in page_digests(ids, PS)],
        )

    def installer(self, key, token_ids, payloads, have):
        digs = page_digests(token_ids, PS)
        for i, p in enumerate(payloads):
            if p != _payload(digs[have + i]):
                return False
        self.resident[key] = list(token_ids)
        self.installs.append((key, len(token_ids), len(payloads), have))
        return True

    def fallback(self, key, token_ids, reason):
        self.resident[key] = list(token_ids)
        self.fallbacks.append((key, reason))

    def coverage(self, key, token_ids):
        prev = self.resident.get(key)
        if prev is None:
            return 0
        n = min(len(prev), len(token_ids))
        lcp = 0
        while lcp < n and prev[lcp] == token_ids[lcp]:
            lcp += 1
        return lcp // PS


def make_harness(latency=3.0, bw=100.0, force="ship", **kw):
    net = Network(default_link=Link(latency_ms=latency, bandwidth_mbps=bw))
    store = DistributedKVStore(net, replication="full")
    tok = get_tokenizer(32000, seed=0)
    store.create_keygroup(
        "m", ["a", "b", "c"],
        size_fn=lambda v: v.wire_bytes(tok),
        delta_size_fn=lambda v, since: v.delta_wire_bytes(tok, since),
        ttl_ms=None,
    )
    shipper = KVShipper(net, store, force=force, **kw)
    nodes = {}
    for nid in ("a", "b", "c"):
        stub = StubNode()
        nodes[nid] = stub
        shipper.register_node(
            nid, "m", profile=stub.profile, exporter=stub.exporter,
            installer=stub.installer, fallback=stub.fallback,
            coverage=stub.coverage,
        )
    return net, store, tok, shipper, nodes


def seed_context(net, store, tok, n_turns=10):
    """Commit a multi-turn context on node a and replicate it everywhere
    (the store's replicas are the digest ground truth at apply time)."""
    ctx = TokenizedContext(model="m")
    for i in range(n_turns):
        ctx.extend(tok.encode(
            f"turn {i} about robot sensors and maps around the charging dock"
        ))
        ctx.commit_turn()
    store.put("a", "m", "s", ctx, n_turns)
    net.run_until_quiet()
    return list(ctx.ids)


# ---------------------------------------------------------------------------
# protocol basics
# ---------------------------------------------------------------------------

def test_basic_ship_installs_with_exact_byte_accounting():
    net, store, tok, shipper, nodes = make_harness()
    ids = seed_context(net, store, tok)
    nodes["a"].resident["s"] = list(ids)
    want = len(ids) // PS
    assert want >= 2
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    net.run_until_quiet()

    assert shipper.installed == 1
    assert shipper.installed_pages == want
    assert shipper.fallbacks == 0 and shipper.active_streams() == 0
    assert nodes["b"].resident["s"] == ids
    assert nodes["b"].installs == [("s", len(ids), want, 0)]

    # billed DATA bytes == shipped chunk bytes exactly (header + per-page
    # digest + page payloads, plus the network's fixed per-message overhead)
    n_chunks = -(-want // shipper.chunk_pages)
    expected = 0
    for lo in range(0, want, shipper.chunk_pages):
        n = min(shipper.chunk_pages, want - lo)
        expected += (
            CHUNK_HEADER_BYTES + n * DIGEST_BYTES + n * PAGE_WIRE
            + MESSAGE_OVERHEAD_BYTES
        )
    assert shipper.chunks_sent == n_chunks
    assert net.messages_for_tag(KV_SHIP_DATA_TAG) == n_chunks
    assert shipper.data_bytes() == expected


def test_installer_error_falls_back_visibly():
    """An installer that raises (a device error in the page install) must
    not vanish into a silent fallback: the stream resolves into the token
    recompute path, counted as an install failure, with the exception's
    text and the fallback's reason on record."""
    net, store, tok, shipper, nodes = make_harness()
    ids = seed_context(net, store, tok)
    nodes["a"].resident["s"] = list(ids)

    def broken(key, token_ids, payloads, have):
        raise RuntimeError("device lost")

    shipper._nodes["b"]["installer"] = broken
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    net.run_until_quiet()

    assert shipper.installed == 0 and shipper.install_failures == 1
    assert shipper.install_errors == ["b s: RuntimeError: device lost"]
    assert shipper.fallback_log == [("b", "s", "install-failed")]
    assert nodes["b"].fallbacks == [("s", "install-failed")]
    assert nodes["b"].resident["s"] == ids


def test_dropped_chunk_is_billed_and_reshipped():
    """Mid-transfer loss: the dropped chunk's bytes ARE billed (the paper's
    traffic metric counts what crossed the wire, not what arrived) and the
    stop-and-wait pump re-ships it after backoff — install still completes."""
    net, store, tok, shipper, nodes = make_harness()
    ids = seed_context(net, store, tok)
    nodes["a"].resident["s"] = list(ids)
    want = len(ids) // PS
    # drop draws happen at SEND time: open the window after the request
    # leaves (now) but before the first DATA send (~one link latency later,
    # when the request arrives at the sender)
    t0 = net.clock.now_ms
    net.install_faults(FaultPlan(
        drops=[DropWindow(
            a="a", b="b", start_ms=t0 + 1.0, end_ms=t0 + 10.0, prob=1.0,
        )],
    ))
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    net.run_until_quiet()

    n_chunks = -(-want // shipper.chunk_pages)
    assert shipper.installed == 1 and shipper.active_streams() == 0
    assert shipper.chunk_retries >= 1
    assert shipper.chunks_sent == n_chunks + 1  # the dropped one re-shipped
    assert net.dropped_messages >= 1
    # billed = every send including the dropped first chunk
    first_n = min(shipper.chunk_pages, want)
    per_chunk = lambda n: (
        CHUNK_HEADER_BYTES + n * DIGEST_BYTES + n * PAGE_WIRE
        + MESSAGE_OVERHEAD_BYTES
    )
    expected = per_chunk(first_n)  # the dropped copy
    for lo in range(0, want, shipper.chunk_pages):
        expected += per_chunk(min(shipper.chunk_pages, want - lo))
    assert shipper.data_bytes() == expected


def test_corrupt_chunk_rejected_then_fallback_after_retries():
    """A persistently tampered chunk never installs: every retry fails the
    digest check, retries exhaust, and the stream degrades VISIBLY to the
    token-recompute fallback — which leaves the same resident prefix."""
    net, store, tok, shipper, nodes = make_harness(max_stream_retries=3)
    ids = seed_context(net, store, tok)
    nodes["a"].resident["s"] = list(ids)

    def tamper(stream_id, seq, payloads):
        if seq == 1:
            payloads[0] = b"\x00" * len(payloads[0])
            return payloads
        return None

    shipper._tamper = tamper
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    net.run_until_quiet()

    assert shipper.installed == 0
    assert shipper.corrupt_chunks >= 1
    assert shipper.aborted == 1 and shipper.fallbacks == 1
    assert shipper.active_streams() == 0
    assert nodes["b"].fallbacks and "retries-exhausted" in nodes["b"].fallbacks[0][1]
    # graceful degradation: the fallback primed the same prefix the shipped
    # path would have installed
    assert nodes["b"].resident["s"] == ids


def test_transient_corruption_recovers_without_fallback():
    """One corrupted delivery: the receiver refuses the chunk, the
    no-progress ACK triggers a retry, and the clean re-send installs."""
    net, store, tok, shipper, nodes = make_harness()
    ids = seed_context(net, store, tok)
    nodes["a"].resident["s"] = list(ids)
    hits = []

    def tamper_once(stream_id, seq, payloads):
        if seq == 0 and not hits:
            hits.append(seq)
            payloads[-1] = payloads[-1][:-1] + b"\xff"
            return payloads
        return None

    shipper._tamper = tamper_once
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    net.run_until_quiet()
    assert shipper.corrupt_chunks == 1 and shipper.chunk_retries >= 1
    assert shipper.installed == 1 and shipper.fallbacks == 0
    assert nodes["b"].resident["s"] == ids


def test_stale_sender_nacks_into_fallback():
    """The sender's resident pages no longer match the receiver's ground
    truth (diverged history) -> NACK -> token recompute, never an install."""
    net, store, tok, shipper, nodes = make_harness()
    ids = seed_context(net, store, tok)
    diverged = list(ids)
    diverged[3] = (diverged[3] + 1) % 32000
    nodes["a"].resident["s"] = diverged
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    net.run_until_quiet()
    assert shipper.nacks == 1 and shipper.fallbacks == 1
    assert shipper.installed == 0 and shipper.active_streams() == 0
    assert nodes["b"].resident["s"] == ids  # fallback primed the real ids


def test_not_resident_sender_nacks():
    net, store, tok, shipper, nodes = make_harness()
    ids = seed_context(net, store, tok)
    # sender has nothing resident for the key
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    net.run_until_quiet()
    assert shipper.nacks == 1 and shipper.fallbacks == 1
    assert nodes["b"].resident["s"] == ids


def test_receiver_down_mid_stream_resumes_from_watermark():
    """Crash the receiver after the first chunk is applied: the inbox
    (watermark + buffered chunks) is durable, the sender parks, and after
    restart the stream resumes — no chunk is applied twice and fewer than
    2x the chunks cross the wire."""
    net, store, tok, shipper, nodes = make_harness(latency=3.0, bw=50.0)
    ids = seed_context(net, store, tok, n_turns=30)
    nodes["a"].resident["s"] = list(ids)
    want = len(ids) // PS
    n_chunks = -(-want // shipper.chunk_pages)
    assert n_chunks >= 3
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    sid = next(iter(shipper._inbox))
    net.run_until(lambda: shipper._inbox[sid].watermark >= 1)
    wm0 = shipper._inbox[sid].watermark
    assert 1 <= wm0 < n_chunks

    net.set_node_down("b", True)     # receiver process down
    net.run_until_quiet()            # in-flight chunk fails; sender parks
    assert shipper.active_streams() == 1
    assert shipper._inbox[sid].watermark == wm0  # durable, not wiped

    net.set_node_down("b", False)
    shipper.kick("b")
    net.run_until_quiet()
    assert shipper.installed == 1 and shipper.fallbacks == 0
    # at most one duplicate: the lost-final-ACK retransmit, which the
    # watermark detects and discards instead of re-applying
    assert shipper.duplicate_chunks <= 1
    assert shipper.chunks_sent < 2 * n_chunks
    assert nodes["b"].resident["s"] == ids
    # the install path skipped nothing and re-applied nothing
    assert nodes["b"].installs == [("s", len(ids), want, 0)]


def test_sender_crash_drops_stream_and_receiver_rerequests():
    """Sender-side streams hold exported bytes in process memory: a sender
    crash drops them; the receiver re-requests on the sender's restart and
    resumes from its durable watermark."""
    net, store, tok, shipper, nodes = make_harness(latency=3.0, bw=50.0)
    ids = seed_context(net, store, tok, n_turns=30)
    nodes["a"].resident["s"] = list(ids)
    want = len(ids) // PS
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    sid = next(iter(shipper._inbox))
    net.run_until(lambda: shipper._inbox[sid].watermark >= 1)
    wm0 = shipper._inbox[sid].watermark

    net.set_node_down("a", True)
    assert shipper.crash("a") == 1   # the sender stream dies with the process
    net.run_until_quiet()
    assert shipper.active_streams() == 1  # inbox survives, parked

    net.set_node_down("a", False)
    shipper.kick("a")                # receiver re-requests, resume=True
    net.run_until_quiet()
    assert shipper.resumed >= 1
    assert shipper.installed == 1 and shipper.duplicate_chunks == 0
    assert shipper._inbox == {} and nodes["b"].resident["s"] == ids
    assert wm0 >= 1  # progress before the crash was real


def test_reconcile_drops_stream_whose_replica_diverged():
    """Anti-entropy parity: a rejoining receiver whose replica ground truth
    no longer matches the stream's digest commitment must drop the stream
    (counted), never install it."""
    net, store, tok, shipper, nodes = make_harness()
    ids = seed_context(net, store, tok)
    nodes["a"].resident["s"] = list(ids)
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    assert shipper.active_streams() == 1
    # replica replaced while "down": different history under the same key
    ctx2 = TokenizedContext(model="m")
    ctx2.extend(tok.encode("completely different session history"))
    ctx2.commit_turn()
    store.put("b", "m", "s", ctx2, 999)
    assert shipper.reconcile("b") == 1
    assert shipper.reconciled_dropped == 1 and shipper.active_streams() == 0
    net.run_until_quiet()
    assert shipper.installed == 0


def test_stale_at_apply_rejects_and_falls_back():
    """The replica moved under a completed stream (superseded while the
    chunks were in flight): the apply-time ground-truth re-check rejects
    the install and falls back — a stale page stream is never installed."""
    net, store, tok, shipper, nodes = make_harness()
    ids = seed_context(net, store, tok)
    nodes["a"].resident["s"] = list(ids)
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    # divergent replica lands on b before the stream completes
    ctx2 = TokenizedContext(model="m")
    ctx2.extend(tok.encode("edited history that replaces everything"))
    ctx2.commit_turn()
    store.replica("b", "m").put("s", ctx2, 999, 0.0, origin="b")
    net.run_until_quiet()
    assert shipper.rejected == 1 and shipper.fallbacks == 1
    assert shipper.installed == 0 and shipper.active_streams() == 0


def test_delta_ship_covers_only_the_gap():
    """A second ship for a grown context ships only the pages past the
    receiver's resident coverage."""
    net, store, tok, shipper, nodes = make_harness()
    ids = seed_context(net, store, tok, n_turns=6)
    nodes["a"].resident["s"] = list(ids)
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    net.run_until_quiet()
    have = len(ids) // PS
    assert shipper.installed_pages == have

    # grow the context, replicate, ship again
    ctx = store.replica("a", "m").get("s", net.clock.now_ms).value
    for i in range(6):
        ctx.extend(tok.encode(f"later turn {i} with more robot words"))
        ctx.commit_turn()
    store.put("a", "m", "s", ctx, 12)
    net.run_until_quiet()
    ids2 = list(ctx.ids)
    nodes["a"].resident["s"] = list(ids2)
    assert shipper.maybe_ship("m", "s", "a", "b", ids2)
    net.run_until_quiet()
    want2 = len(ids2) // PS
    assert shipper.installed == 2
    assert shipper.installed_pages == want2          # cumulative: gap only
    assert nodes["b"].installs[-1] == ("s", len(ids2), want2 - have, have)
    assert nodes["b"].resident["s"] == ids2


def test_coalesce_rides_active_stream():
    """A re-delivery for the same (still valid) context while its stream is
    active coalesces instead of double-shipping."""
    net, store, tok, shipper, nodes = make_harness()
    ids = seed_context(net, store, tok)
    nodes["a"].resident["s"] = list(ids)
    assert shipper.maybe_ship("m", "s", "a", "b", ids)
    assert shipper.maybe_ship("m", "s", "a", "b", ids)  # duplicate arrival
    net.run_until_quiet()
    assert shipper.coalesced == 1
    assert shipper.requested == 1 and shipper.installed == 1


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_cost_model_crossover_regimes():
    """The measured crossover: per-chunk link latencies + tail recompute
    dominate short histories (recompute wins); the per-token wire advantage
    dominates long ones (ship wins). A weak receiver moves the crossover
    down; a slow link moves it up past any history; O(1) recurrent state
    always ships."""
    net, store, tok, shipper, nodes = make_harness(
        latency=10.0, bw=200.0, force=None,
    )
    # default receiver: 0.9 ms/token; wire cost ~0.16 ms/token at 200 Mbps
    n_short, n_long = 40, 1500                # 2 pages vs ~93 pages
    est_short = shipper.estimate("a", "b", n_short)
    est_long = shipper.estimate("a", "b", n_long)
    assert est_short.decision == "recompute"
    assert est_short.recompute_ms < est_short.ship_ms
    assert est_long.decision == "ship"
    assert est_long.ship_ms < est_long.recompute_ms

    # weak receiver (TX2-class, 6 ms/token): even the short history ships
    nodes["b"].prefill_ms = 6.0
    est_weak = shipper.estimate("a", "b", n_short)
    assert est_weak.decision == "ship"
    nodes["b"].prefill_ms = 0.9

    # fast receiver, slow link: recompute wins even for the long history
    nodes["c"].prefill_ms = 0.2
    net.set_link("a", "c", Link(latency_ms=40.0, bandwidth_mbps=5.0))
    est_slow = shipper.estimate("a", "c", n_long)
    assert est_slow.decision == "recompute"

    # O(1) state (SSM/hybrid snapshot): ship regardless of history length
    nodes["b"].state_is_o1 = True
    nodes["a"].state_is_o1 = True
    est_o1 = shipper.estimate("a", "b", n_short)
    assert est_o1.decision == "ship"


def test_estimate_accounts_degraded_link():
    """The ship estimate reads the link's CURRENT (degraded) bandwidth —
    mid-window the same transfer costs more, flipping the decision."""
    net, store, tok, shipper, nodes = make_harness(
        latency=3.0, bw=100.0, force=None,
    )
    nodes["b"].prefill_ms = 6.0
    n_long = 1500
    assert shipper.estimate("a", "b", n_long).decision == "ship"
    net.install_faults(FaultPlan(degraded=[DegradedWindow(
        a="a", b="b", start_ms=0.0, end_ms=1e6,
        latency_mult=4.0, bandwidth_mult=0.01,
    )]))
    est = shipper.estimate("a", "b", n_long)
    assert est.decision == "recompute"      # 1 Mbps effective: ship loses


def test_sub_page_history_always_recomputes():
    net, store, tok, shipper, nodes = make_harness(force=None)
    est = shipper.estimate("a", "b", PS - 1)
    assert est.want_pages == 0 and est.decision == "recompute"
    # even under force="ship" there is nothing to ship
    shipper.force = "ship"
    est2 = shipper.estimate("a", "b", PS - 1)
    assert est2.decision == "recompute"


# ---------------------------------------------------------------------------
# MB-scale transfer timing (satellite: Link/DegradedWindow at stream sizes)
# ---------------------------------------------------------------------------

def test_link_transfer_ms_at_page_stream_sizes():
    link = Link(latency_ms=5.0, bandwidth_mbps=100.0)
    mb = 1_000_000
    # 4 MB of KV pages at 100 Mbps: 320 ms of serialization + latency
    assert link.transfer_ms(4 * mb) == pytest.approx(5.0 + 320.0)
    # chunking preserves total serialization cost, adds per-chunk latency
    chunk = link.transfer_ms(mb)
    assert 4 * chunk == pytest.approx(4 * 5.0 + 320.0)


def test_degraded_window_scales_mb_transfers():
    net = Network(default_link=Link(latency_ms=2.0, bandwidth_mbps=100.0))
    net.install_faults(FaultPlan(degraded=[DegradedWindow(
        a="a", b="b", start_ms=100.0, end_ms=200.0,
        latency_mult=4.0, bandwidth_mult=0.25,
    )]))
    mb = 1_000_000
    base = net.transfer_ms("a", "b", mb)
    assert base == pytest.approx(2.0 + 80.0)
    net.clock.advance_to(150.0)
    degraded = net.transfer_ms("a", "b", mb)
    assert degraded == pytest.approx(4 * 2.0 + 4 * 80.0)
    net.clock.advance_to(250.0)
    assert net.transfer_ms("a", "b", mb) == pytest.approx(base)


# ---------------------------------------------------------------------------
# cluster integration: echo services shipping virtual pages
# ---------------------------------------------------------------------------

def build_ship_cluster(
    kv_ship=True, force=None, latency=3.0, bw=100.0,
    kv_bytes_per_token=4096.0, prefill=0.9,
):
    return EdgeCluster.build(
        ["n0", "n1", "n2"],
        lambda nid: EchoLLMService(
            model="m", vocab_size=32000, kv_reuse=True, tokenize_scale=0.0,
            kv_bytes_per_token=kv_bytes_per_token,
            prefill_ms_per_token=prefill,
        ),
        inter_node_link=Link(latency_ms=latency, bandwidth_mbps=bw),
        client_link=Link(latency_ms=1.0, bandwidth_mbps=1000.0),
        kv_ship=kv_ship, kv_ship_force=force,
    )


def run_session(cluster, turns, roam_to=None, session="s", user="u"):
    """Drive one scripted session; returns the response texts."""
    client = LLMClient(cluster, "m", user_id=user, session_id=session)
    texts = []
    for i, prompt in enumerate(turns):
        node = roam_to if roam_to is not None and i == len(turns) - 1 else "n0"
        t = client.submit(prompt, node_id=node)
        cluster.run_until_quiet()
        assert t.done and t.response.error is None, t.response
        texts.append(t.response.text)
    return texts, t.response


def test_cluster_roam_reports_pages_provenance():
    cluster = build_ship_cluster(force="ship")
    turns = [f"turn {i} about robots and sensors" for i in range(8)]
    _, last = run_session(cluster, turns + ["roam turn"], roam_to="n1")
    assert last.timing.kv_warm_start
    assert last.timing.kv_warm_source == "pages"
    stats = cluster.kv_ship_stats()
    assert stats["installed"] > 0 and stats["active_streams"] == 0
    assert stats["node_ships"] == stats["installed"]
    assert stats["fallbacks"] == 0


def test_cluster_recompute_reports_tokens_provenance():
    cluster = build_ship_cluster(force="recompute")
    turns = [f"turn {i} about robots and sensors" for i in range(8)]
    _, last = run_session(cluster, turns + ["roam turn"], roam_to="n1")
    assert last.timing.kv_warm_start
    assert last.timing.kv_warm_source == "tokens"
    stats = cluster.kv_ship_stats()
    assert stats["installed"] == 0 and stats["decide_recompute"] > 0


def test_cluster_ship_off_has_no_shipper():
    cluster = build_ship_cluster(kv_ship=False)
    assert cluster.kv_ship is None and cluster.kv_ship_stats() == {}
    turns = [f"turn {i} words" for i in range(3)]
    _, last = run_session(cluster, turns + ["roam"], roam_to="n1")
    assert last.timing.kv_warm_source == "tokens"


def test_ship_and_recompute_clusters_agree_on_outputs():
    """Greedy outputs are a pure function of the token history — shipping
    pages instead of recomputing them must never change a single text."""
    turns = [f"turn {i} about maps and control" for i in range(6)] + ["roam"]
    texts = {}
    for mode, (ship, force) in {
        "ship": (True, "ship"),
        "recompute": (True, "recompute"),
        "off": (False, None),
    }.items():
        cluster = build_ship_cluster(kv_ship=ship, force=force)
        texts[mode], _ = run_session(cluster, turns, roam_to="n1")
    assert texts["ship"] == texts["recompute"] == texts["off"]


def test_cluster_corrupt_stream_falls_back_with_identical_outputs():
    """Persistent in-flight corruption: every ship aborts into the token
    recompute fallback, outputs stay identical to a no-ship cluster, and
    the failure is visible in the counters."""
    turns = [f"turn {i} about filters" for i in range(6)] + ["roam"]
    baseline, _ = run_session(build_ship_cluster(kv_ship=False), turns,
                              roam_to="n1")
    cluster = build_ship_cluster(force="ship")
    cluster.kv_ship._tamper = lambda sid, seq, p: [b"\x00" * len(x) for x in p]
    got, last = run_session(cluster, turns, roam_to="n1")
    assert got == baseline
    stats = cluster.kv_ship_stats()
    assert stats["installed"] == 0
    assert stats["fallbacks"] > 0 and stats["corrupt_chunks"] > 0
    assert stats["fallbacks"] == stats["node_fallbacks"]
    assert stats["active_streams"] == 0
    # the fallback still warm-started the roam turn — by recompute
    assert last.timing.kv_warm_source == "tokens"


def test_cluster_crash_restart_mid_ship_converges():
    """Churn e2e with shipping on: crash the receiving node mid-run (with
    replica loss), restart, and require convergence, drained streams, and
    a correct final roam turn."""
    cluster = build_ship_cluster(force="ship")
    client = LLMClient(
        cluster, "m", user_id="u", session_id="s", timeout_ms=60_000.0,
    )
    for i in range(5):
        t = client.submit(f"turn {i} about robots", node_id="n0")
        cluster.run_until_quiet()
        assert t.response.error is None
    cluster.crash("n1", lose_replica=True)
    t = client.submit("turn while n1 is down", node_id="n0")
    cluster.run_until_quiet()
    assert t.response.error is None
    cluster.restart("n1")
    cluster.converge()
    assert cluster.converged()
    stats = cluster.kv_ship_stats()
    assert stats["active_streams"] == 0
    # every requested stream resolved into exactly one visible outcome
    assert stats["requested"] + stats["resumed"] >= stats["installed"]
    t = client.submit("roam after recovery", node_id="n1")
    cluster.run_until_quiet()
    assert t.response.error is None
    assert t.response.timing.kv_warm_start


# ---------------------------------------------------------------------------
# property: any seed/fault plan -> ship and recompute agree, nothing hangs
# ---------------------------------------------------------------------------

def _assert_ship_equals_recompute(seed, n_turns, part_start, part_len, drop_prob):
    """Under an inter-node partition + loss schedule, the ship cluster and
    the recompute cluster produce identical texts for the same scripted
    session, and every stream resolves (none hang)."""
    plan = FaultPlan(
        partitions=[PartitionWindow(
            a="n0", b="n1",
            start_ms=float(part_start), end_ms=float(part_start + part_len),
        )],
        drops=[DropWindow(
            a="n0", b="n1", start_ms=0.0, end_ms=1e7, prob=drop_prob,
        )],
        seed=seed,
    )
    turns = [f"turn {i} seed {seed} robots" for i in range(n_turns)] + ["roam"]
    results = {}
    for mode, force in (("ship", "ship"), ("recompute", "recompute")):
        cluster = build_ship_cluster(force=force)
        cluster.install_faults(plan)
        results[mode], _ = run_session(cluster, turns, roam_to="n2")
        stats = cluster.kv_ship_stats()
        assert stats["active_streams"] == 0, stats
    assert results["ship"] == results["recompute"]


@pytest.mark.parametrize("seed,n_turns,part_start,part_len,drop_prob", [
    (0, 5, 0, 2000, 0.0),        # clean partition from the start
    (7, 6, 1500, 3000, 0.15),    # mid-run partition + moderate loss
    (1234, 4, 100, 500, 0.3),    # short cut, heavy loss
])
def test_ship_equals_recompute_under_faults(
    seed, n_turns, part_start, part_len, drop_prob,
):
    """Deterministic fault sweep (always runs, even without hypothesis)."""
    _assert_ship_equals_recompute(seed, n_turns, part_start, part_len, drop_prob)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@given(
    seed=st.integers(0, 2**16),
    n_turns=st.integers(3, 7),
    part_start=st.integers(0, 4000),
    part_len=st.integers(500, 4000),
    drop_prob=st.floats(0.0, 0.3),
)
@settings(max_examples=15, deadline=None)
def test_property_ship_equals_recompute_under_faults(
    seed, n_turns, part_start, part_len, drop_prob,
):
    _assert_ship_equals_recompute(seed, n_turns, part_start, part_len, drop_prob)


# ---------------------------------------------------------------------------
# real engine: shipped pages == token recompute == cold, bit for bit
# ---------------------------------------------------------------------------

@pytest.fixture
def jax_cfg():
    from repro.models import ModelConfig
    return ModelConfig(
        name="ship-mini", arch_type="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=4096,
        param_dtype="float32", compute_dtype="float32",
    )


@pytest.mark.slow
def test_jax_shipped_pages_equal_token_recompute(jax_cfg):
    """The real paged engine: node A serves a session, exports its KV
    pages; B installs them (native-dtype round trip), C recomputes from
    tokens. All three — plus a cold engine — generate bit-identical greedy
    continuations, and provenance distinguishes the warm paths."""
    from repro.serving import JaxLLMService

    def mk():
        return JaxLLMService.create(
            "ship-mini", jax_cfg, max_len=256, page_size=16, kv_pages=48,
        )

    a, b, c = mk(), mk(), mk()
    tok = a.tokenizer
    p1 = tok.encode(
        "a long opening question about wheel odometry covariance and loop "
        "closure detection for the warehouse robot near the charging dock"
    )
    r1 = a.completion([], p1, 24, cache_key="s")
    hist = p1 + r1.token_ids

    ship = a.export_kv_pages("s")
    assert ship is not None and len(ship.payloads) >= 2
    assert hist[: len(ship.token_ids)] == ship.token_ids

    assert b.install_kv_pages("s", ship.token_ids, ship.payloads, 0)
    assert c.prime("s", hist)

    p2 = tok.encode("and a follow-up about sensor fusion")
    rb = b.completion(hist, p2, 16, cache_key="s")
    rc = c.completion(hist, p2, 16, cache_key="s")
    cold = JaxLLMService.create(
        "ship-mini", jax_cfg, max_len=256, kv_reuse=False,
    ).completion(hist, p2, 16)
    assert rb.token_ids == rc.token_ids == cold.token_ids
    assert rb.cache_hit and rb.warm_start and rb.warm_source == "pages"
    assert rc.cache_hit and rc.warm_start and rc.warm_source == "tokens"
    # warm reuse actually happened: only the prompt was prefilled
    assert rb.reused_tokens == len(hist) and rb.prefill_tokens == len(p2)


@pytest.mark.slow
def test_jax_ship_profile_gated_by_constant(jax_cfg):
    """kv_ship_profile is None until the node has a measured prefill
    constant — an unmeasured node never volunteers to ship."""
    from repro.serving import JaxLLMService

    svc = JaxLLMService.create(
        "ship-mini", jax_cfg, max_len=256, page_size=16, kv_pages=48,
    )
    assert svc.kv_ship_profile() is None
    svc.ship_prefill_ms_per_token = 1.0
    prof = svc.kv_ship_profile()
    assert prof is not None and prof.page_size == 16
    assert prof.page_wire_bytes > 0 and prof.prefill_ms_per_token == 1.0
