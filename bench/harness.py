"""The harness: builds a cell's cluster, drives its traffic through the
served path, measures a window on the cluster clock and checks what the
window produced.

The served path is the program's own: ``LLMClient.submit`` ->
``EdgeCluster`` -> ``EdgeNode`` / ``ContextManager`` ->
``BatchedLLMService`` -> ``BatchedServer.step``. The cluster clock
(``Network.clock``) is the program's definition of what a user waits for:
network time is modelled, and every scheduler step's host-clock duration is
laid onto it. The window is ``seconds`` long on that clock, after a warm-up
phase of the same traffic. The harness records, from wrappers it puts on
the instances (not edits of the program):

- each ``BatchedServer.step``: its node, its span on the cluster clock, and
  which requests got tokens in it (a token's time is the cluster-clock end
  of the step that produced it);
- each ``BatchedServer.submit``: the ids the model saw for a turn;
- each prefill chunk, prime and page install, as host spans for the trace.
"""

from __future__ import annotations

import gc
import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

from . import flops, reference, traffic
from .blocks import block_for
from .stats import MISSED_MS
from .weights import check_layout, make_params

ROOT = Path(__file__).resolve().parents[1]
TRACE_WALL_S = 8.0          # wall seconds of the window the traced run records
TEMPLATE_TOKENS = 12        # chat-template tokens a turn adds, at most
SAMPLE_TURNS = 32           # most turns compared with the reference
# HLO instruction-name prefixes under which each kernel runs in a trace
# (the shared-prefix pass runs inside the jitted ``paged_attention``)
KERNELS = {
    "paged_attention": ("%paged_attention", "%shared_prefix"),
    "chunked_prefill": ("%chunked_prefill",),
}


@dataclass
class CellSpec:
    name: str
    conf: Dict            # bench/configs/<config>.json
    mix: Dict             # bench/traffic/<traffic>.json
    cell: Dict            # bench/cells/<name>.json
    program_cfg: object   # the program's ModelConfig, as served


@dataclass
class TurnRec:
    key: str              # the session's context key
    index: int            # the turn's place in its session
    roam: bool
    sent_ms: float
    in_window: bool
    prompt_tokens: int
    rid: Optional[int] = None
    input_ids: List[int] = field(default_factory=list)
    token_ms: List[float] = field(default_factory=list)
    generated: List[int] = field(default_factory=list)
    response: object = None
    done_ms: Optional[float] = None

    @property
    def ok(self) -> bool:
        return self.response is not None and self.response.error is None

    @property
    def response_ms(self) -> float:
        return self.done_ms - self.sent_ms if self.ok else MISSED_MS


@dataclass
class StepRec:
    wall0: float              # host clock at the step's start
    kv_lens: List[int]        # keys each decoding lane attended
    shared_pages: int         # the shared-prefix run read once for all lanes


@dataclass
class ChunkRec:
    wall0: float
    c: int                    # prompt tokens in the chunk
    p0: int                   # their offset


@dataclass
class Run:
    """Everything one run measured; the metric readers read this."""
    spec: CellSpec
    block: ModuleType             # bench/blocks/<model_type>.py
    dims: object                  # the block's Dims
    window_ms: float
    setup_s: float
    turns: List[TurnRec]          # turns sent within the window
    steps: List[StepRec]
    chunks: List[ChunkRec]
    counters: Dict[str, float]
    page_size: int
    trace: Optional[Dict] = None  # bench.trace.reduce() of the traced run
    peaks: Optional[Dict] = None
    trace_wall: tuple = (0.0, 0.0)

    def traced(self, recs):
        w0, w1 = self.trace_wall
        return [r for r in recs if w0 <= r.wall0 < w1]


def track_compiles():
    """Count and time XLA's backend compiles through ``jax.monitoring``."""
    import jax

    log = {"seconds": 0.0, "backend_compiles": 0, "names": []}

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            log["seconds"] += duration
            log["backend_compiles"] += 1
            log["names"].append(str(kw.get("fun_name", "?")))

    jax.monitoring.register_event_duration_secs_listener(listener)
    return log


# ----------------------------------------------------------------------------
# cluster
# ----------------------------------------------------------------------------

def build_cluster(spec: CellSpec, params, device):
    """The cell's nodes, as ``repro.launch.serve.build_cluster`` builds them
    (one paged Pallas ``BatchedLLMService`` per node, services on one
    device sharing one copy of the weights, the same links), with the
    benchmark's weights and the configuration file's deployment sizes."""
    from repro.edge import EdgeCluster
    from repro.serving import BatchedLLMService
    from repro.store import Link

    dep = spec.conf["deployment"]
    cfg = spec.program_cfg
    node_ids = [f"edge-{i}" for i in range(spec.cell["nodes"])]
    services = {}
    for nid in node_ids:
        svc = BatchedLLMService.create(
            cfg.name, cfg, n_slots=dep["slots_per_node"], max_len=dep["max_len"],
            session_cache_capacity=dep["session_cache_capacity"], paged=True,
            page_size=dep["page_size"], kv_pages=dep["kv_pages_per_node"],
            prefill_chunk_tokens=dep["prefill_chunk_tokens"], device=device,
            params=params,
        )
        if spec.cell["kv_ship"]:
            svc.measure_prefill_ms_per_token()
        services[nid] = svc
    return EdgeCluster.build(
        node_ids, services.__getitem__,
        inter_node_link=Link(**dep["inter_node_link"]),
        client_link=Link(**dep["client_link"]),
        replication="full", kv_ship=spec.cell["kv_ship"],
    )


def _pow2s(lo: int, hi: int) -> List[int]:
    out, p = [], 1
    while p <= hi:
        if p >= lo:
            out.append(p)
        p *= 2
    return out


def warm_pages(svc) -> None:
    """Run a node's own page helpers once (each allocator jits its own):
    the tail-page copy, and the page export and import of KV shipping."""
    alloc = svc.server.allocator
    pages = alloc.alloc(2)
    assert pages is not None, "no free pages to warm up with"
    try:
        alloc.copy_page(pages[0], pages[1])
        alloc.import_page_bytes(pages[1], alloc.export_page_bytes(pages[0]))
    finally:
        alloc.decref(pages)


def warm_shapes(svc, min_ctx: int, max_ctx: int, max_chunk: int,
                shared_pages: int) -> int:
    """Run every program shape the cell's traffic can reach once, outside
    the window: prefill chunks (power-of-two token buckets up to
    ``max_chunk``, every power-of-two table width) and decode steps (every
    power-of-two page width from the shortest to the longest context, with
    each shared-prefix run up to ``shared_pages``). Decode lanes are pointed
    past the table, so their writes drop and no state changes. Returns the
    number of calls."""
    import jax
    from repro.serving import sample

    srv = svc.server
    alloc = srv.allocator
    ps = alloc.page_size
    mp_all = srv._table.shape[1]
    w_max = min(mp_all, traffic.ceil_pow2(alloc.pages_for(max_ctx + 1)))
    w_min = min(w_max, traffic.ceil_pow2(alloc.pages_for(min_ctx + 1)))
    pages = alloc.alloc(w_max)
    assert pages is not None, "no free pages to warm up with"
    calls = 0
    try:
        ids = [7 + (i % 91) for i in range(max_chunk)]
        for mp in _pow2s(1, w_max):
            for s in _pow2s(ps, min(max_chunk, mp * ps)):
                srv._prefiller.run_chunk(pages[:mp], ids[:s], mp * ps - s)
                calls += 1
        for w in _pow2s(w_min, w_max):
            wp = w * ps
            dec_pos = np.full(srv.n_slots, wp, np.int32)
            runs = [0] + _pow2s(1, min(shared_pages, w - 1))
            for run in runs:
                sp = srv._put(np.arange(1, run + 1, dtype=np.int32)) if run else None
                tokens = srv._put(srv._next_tok[:, None])
                if w < mp_all:
                    logits, pools, kvp = srv._decode_paged(
                        srv.params, alloc.pools, srv._put(srv._table[:, :w]),
                        srv._kv_pos[:, :wp], tokens, srv._put(dec_pos), sp)
                    srv._kv_pos = srv._kv_pos.at[:, :wp].set(kvp)
                else:
                    logits, pools, srv._kv_pos = srv._decode_paged(
                        srv.params, alloc.pools, srv._put(srv._table),
                        srv._kv_pos, tokens, srv._put(dec_pos), sp)
                alloc.pools = pools
                np.asarray(sample(logits[:, 0]))
                calls += 1
    finally:
        alloc.decref(pages)
    jax.block_until_ready(alloc.pools)
    return calls


# ----------------------------------------------------------------------------
# instrumentation
# ----------------------------------------------------------------------------

class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class Recorder:
    """Benchmark-side wrappers on one cluster's service instances."""

    def __init__(self, cluster, annotate: bool):
        self.net = cluster.network
        self.annotate = annotate
        self.inflight: Dict[str, TurnRec] = {}
        self.by_rid: Dict[tuple, TurnRec] = {}
        self.steps: List[StepRec] = []
        self.chunks: List[ChunkRec] = []
        self.on_step: Optional[Callable[[], None]] = None
        # the most KV pages each node held at once (slots and cached sessions)
        self.peak_pages: Dict[str, int] = {nid: 0 for nid in cluster.nodes}
        for nid, node in cluster.nodes.items():
            self._wrap(nid, node.service)

    def _span(self, name):
        if self.annotate:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return _Null()

    def _wrap(self, nid: str, svc) -> None:
        srv = svc.server
        net = self.net
        alloc = srv.allocator
        submit, step, run_chunk = srv.submit, srv.step, srv._prefiller.run_chunk
        prime, install = svc.prime, svc.install_kv_pages

        def submit_w(token_ids, max_new=32, cache_key=None):
            rid = submit(token_ids, max_new=max_new, cache_key=cache_key)
            rec = self.inflight.get(cache_key)
            if rec is not None and rec.rid is None:
                rec.rid = rid
                rec.input_ids = list(srv.queue[-1][1])
                self.by_rid[(nid, rid)] = rec
            return rid

        def step_w():
            t0 = net.clock.now_ms
            ready = [i for i, s in enumerate(srv.slots) if s is not None and s.prefilled]
            width = traffic.ceil_pow2(max([len(srv.slot_pages[i]) for i in ready] or [1]))
            shared = len(srv._shared_prefix_run(width)) if len(ready) > 1 else 0
            before = {s.request_id: len(s.generated) for s in srv.slots if s is not None}
            n_fin = len(srv.finished)
            with self._span(f"bench.step {nid}"):
                w0 = time.perf_counter()
                step()
                w1 = time.perf_counter()
            end = t0 + (w1 - w0) * 1e3
            self.peak_pages[nid] = max(self.peak_pages[nid], alloc.used_pages)
            kv_lens = []
            got = [(s.request_id, s.generated, s.pos) for s in srv.slots if s is not None]
            got += [(f.request_id, f.token_ids, None) for f in srv.finished[n_fin:]]
            for rid, toks, pos in got:
                rec = self.by_rid.get((nid, rid))
                new = len(toks) - before.get(rid, 0)
                if new <= 0:
                    continue
                # a lane that decoded attended every position it holds now
                if pos is None and rec is not None:
                    pos = len(rec.input_ids) + len(toks)
                if pos is not None:
                    kv_lens.append(pos)
                if rec is not None:
                    rec.token_ms.extend([end] * new)
                    rec.generated = list(toks)
            self.steps.append(StepRec(w0, kv_lens, shared if len(kv_lens) > 1 else 0))
            if self.on_step is not None:
                self.on_step()

        def run_chunk_w(pages, chunk_ids, p0, n_skip=0):
            self.chunks.append(ChunkRec(time.perf_counter(), len(chunk_ids), p0))
            with self._span("bench.prefill_chunk"):
                return run_chunk(pages, chunk_ids, p0, n_skip=n_skip)

        def prime_w(key, ids):
            with self._span(f"bench.prime {nid}"):
                out = prime(key, ids)
            self.peak_pages[nid] = max(self.peak_pages[nid], alloc.used_pages)
            return out

        def install_w(key, token_ids, payloads, have_pages):
            with self._span(f"bench.page_install {nid}"):
                out = install(key, token_ids, payloads, have_pages)
            self.peak_pages[nid] = max(self.peak_pages[nid], alloc.used_pages)
            return out

        srv.submit, srv.step, srv._prefiller.run_chunk = submit_w, step_w, run_chunk_w
        svc.prime, svc.install_kv_pages = prime_w, install_w


# ----------------------------------------------------------------------------
# the load generator
# ----------------------------------------------------------------------------

class LoadGenerator:
    """Sends the plan's turns through ``LLMClient.submit`` on the cluster
    clock, open loop: each session starts at its arrival time and each next
    turn is sent its think time after the previous response arrived. No
    turn is sent at or after the window's end."""

    def __init__(self, cluster, spec: CellSpec, plan: traffic.Plan,
                 rec: Recorder, t0: float, t1: float):
        from repro.core import ConsistencyPolicy, ContextMode

        self.cluster, self.plan, self.rec = cluster, plan, rec
        self.t0, self.t1 = t0, t1
        self.model = spec.program_cfg.name
        self.policy = ConsistencyPolicy(spec.cell["policy"])
        self.mode = ContextMode(spec.cell["mode"])
        self.turns: List[TurnRec] = []
        self._clients: Dict[int, object] = {}

    def start(self) -> None:
        net = self.cluster.network
        for s in self.plan.sessions:
            net.schedule(s.start_ms, lambda s=s: self._send(s, 0, 0.0))

    def _send(self, s: traffic.Session, j: int, delay: float) -> None:
        from repro.core.session import context_key
        from repro.edge import LLMClient

        net = self.cluster.network
        sent = net.clock.now_ms + delay
        if sent >= self.t1:
            return
        t = s.turns[j]
        uid, sid = f"u{s.idx}", f"s{s.idx}"
        if j == 0:
            self._clients[s.idx] = LLMClient(
                self.cluster, model=self.model, mode=self.mode,
                policy=self.policy, user_id=uid, session_id=sid)
        client = self._clients[s.idx]
        client.max_new_tokens = t.max_new
        key = context_key(uid, sid)
        rec = TurnRec(key=key, index=j, roam=t.roam, sent_ms=sent,
                      in_window=self.t0 <= sent, prompt_tokens=t.prompt_tokens)
        self.turns.append(rec)
        self.rec.inflight[key] = rec

        def done(resp):
            rec.response, rec.done_ms = resp, net.clock.now_ms
            if resp.error is None and j + 1 < len(s.turns):
                self._send(s, j + 1, s.turns[j + 1].think_ms)

        client.submit(t.prompt, t.node, delay_ms=delay, on_response=done)


# ----------------------------------------------------------------------------
# checks
# ----------------------------------------------------------------------------

def store_violations(cluster, turns: List[TurnRec], max_len: int) -> Dict[str, int]:
    """The store's guarantees, read back once the run is quiet: every replica
    of the keygroup holds each session's last acknowledged turn, at its turn
    counter, ending with that turn's model input and output; and every turn
    after a session's first saw all of the previous turn (no STRONG turn was
    served a stale context). A turn whose context outgrew ``max_len`` sees
    the tail of it (the program drops the oldest tokens)."""
    store = cluster.store
    model = next(iter(cluster.nodes.values())).service.model
    members = store.keygroup(model).members
    sessions: Dict[str, List[TurnRec]] = {}
    for t in turns:
        sessions.setdefault(t.key, []).append(t)
    overhead = next((len(t.input_ids) - t.prompt_tokens for t in turns
                     if t.index == 0 and t.ok), 0)
    replica = stale = 0
    for key, ts in sessions.items():
        acked = [t for t in sorted(ts, key=lambda r: r.index) if t.ok]
        for prev, cur in zip(acked, acked[1:]):
            want = prev.input_ids + prev.generated
            ctx = cur.input_ids[: len(cur.input_ids) - cur.prompt_tokens - overhead]
            whole = ctx == want
            tail = (0 < len(ctx) < len(want) and want[-len(ctx):] == ctx
                    and len(cur.input_ids) >= max_len - 17)
            if cur.response.stale or not (whole or tail):
                stale += 1
        if not acked:
            continue
        last = acked[-1]
        want = last.input_ids + last.generated
        for node in members:
            vv = store.get(node, model, key)
            ids = list(vv.value.ids) if vv is not None else []
            if vv is None or vv.version != last.response.turn or ids[-len(want):] != want:
                replica += 1
    return {"replica_mismatches": replica, "stale_turns": stale}


# ----------------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------------

def run(spec: CellSpec, seed: int, seconds: float, trace: bool,
        process_start: float, device, readers: Dict[str, Callable],
        control: bool = False, log: Callable = print) -> Dict:
    """One run of one cell. Returns the result line's content (without
    ``device``) and the numbers compared, under ``checks``. ``control``
    judges, in the program's place, the tokens the reference computed in
    fp8 would have served."""
    import jax
    from repro.models import abstract_params

    compiles = track_compiles()
    block = block_for(spec.conf)
    dims = block.dims_of(spec.conf)
    block.check_program_config(dims, spec.program_cfg)
    dep = spec.conf["deployment"]
    params = make_params(block, dims, spec.conf["weights"], seed, device)
    check_layout(params, abstract_params(spec.program_cfg))
    cluster = build_cluster(spec, params, device)
    nodes = list(cluster.nodes)
    plan = traffic.generate(spec.mix, seed, nodes)
    log(f"traffic: {traffic.describe(plan)}")
    ps = dep["page_size"]
    first = [s.turns[0].prompt_tokens for s in plan.sessions]
    shared = (int(spec.mix.get("system_prompt_tokens", 0)) + 4) // ps
    calls = warm_shapes(
        cluster.node(nodes[0]).service,
        min_ctx=min(first), max_ctx=min(dep["max_len"], traffic.max_context_tokens(
            plan, TEMPLATE_TOKENS)),
        max_chunk=256 if len(nodes) > 1 else dep["prefill_chunk_tokens"],
        shared_pages=shared if spec.mix.get("system_prompt_tokens") else 0,
    )
    for nid in nodes:
        svc = cluster.node(nid).service
        warm_pages(svc)
        # one small request through the node's own blocking shim runs the
        # eager operations of admission, prefill completion and finish
        svc.completion([], list(range(8, 8 + 2 * ps)), 2)
    log(f"warm-up: {calls} shape calls, {compiles['backend_compiles']} backend "
        f"compiles in {compiles['seconds']:.1f} s so far")

    rec = Recorder(cluster, annotate=trace)
    net = cluster.network
    t0 = float(spec.cell["warmup_s"]) * 1e3
    t1 = t0 + seconds * 1e3
    load = LoadGenerator(cluster, spec, plan, rec, t0, t1)
    marks: Dict[str, float] = {}
    trace_dir = str(ROOT / ".bench_out" / "trace")

    def stop_trace():
        if "trace_w1" not in marks and "trace_w0" in marks:
            # the window ends before the profiler writes its trace out
            marks["trace_w1"] = time.perf_counter()
            jax.profiler.stop_trace()

    def at_t0():
        marks["w0"] = time.perf_counter()
        marks["setup_s"] = marks["w0"] - process_start
        marks["c0"] = compiles["backend_compiles"]
        marks["up0"], marks["sync0"] = cluster.client_bytes_up(), cluster.sync_bytes()
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            marks["trace_w0"] = time.perf_counter()

    def at_t1():
        marks["w1"] = time.perf_counter()
        marks["up1"], marks["sync1"] = cluster.client_bytes_up(), cluster.sync_bytes()
        stop_trace()

    def on_step():
        if "trace_w0" in marks and time.perf_counter() - marks["trace_w0"] >= TRACE_WALL_S:
            stop_trace()

    rec.on_step = on_step
    net.schedule(t0, at_t0)
    net.schedule(t1, at_t1)
    load.start()
    cluster.run_until_quiet()
    stop_trace()
    marks["w_end"] = time.perf_counter()
    in_window = [t for t in load.turns if t.in_window]
    log(f"window: {seconds:g} s on the cluster clock took {marks['w1'] - marks['w0']:.3f} s "
        f"of wall time ({marks['w_end'] - marks['w0']:.3f} s until its last turn "
        f"was answered); backend compiles inside it: "
        f"{compiles['backend_compiles'] - marks['c0']} "
        f"{compiles['names'][int(marks['c0']):]}")
    n_win = max(1, len(in_window))
    counters = {
        "uplink_bytes": marks["up1"] - marks["up0"],
        "sync_bytes": marks["sync1"] - marks["sync0"],
    }
    pool_pages = {nid: cluster.node(nid).service.server.allocator.n_pages - 1
                  for nid in nodes}
    log(f"pages: peak KV pages in use per node {rec.peak_pages} of "
        f"{pool_pages} (warm-up traffic and window)")
    log(f"bytes: uplink_bytes_per_turn={counters['uplink_bytes'] / n_win:.1f} "
        f"sync_bytes_per_turn={counters['sync_bytes'] / n_win:.1f} "
        f"({len(in_window)} turns sent in the window)")
    mem = device.memory_stats() or {}
    memory_peak = mem.get("peak_bytes_in_use")
    violations = store_violations(cluster, load.turns, dep["max_len"])
    unresolved = sum(t.response is None for t in in_window)

    tr_reduced, peaks, trace_wall = None, None, (0.0, 0.0)
    if trace:
        from . import trace as trace_mod

        peaks = flops.peaks_for(device.device_kind)
        trace_wall = (marks["trace_w0"], marks["trace_w1"])
        tr_reduced = trace_mod.reduce(
            trace_mod.collect(trace_dir), trace_wall[1] - trace_wall[0], KERNELS)
        shutil.rmtree(trace_dir, ignore_errors=True)

    result_run = Run(
        spec=spec, block=block, dims=dims, window_ms=seconds * 1e3,
        setup_s=marks["setup_s"], turns=in_window, steps=rec.steps, chunks=rec.chunks,
        counters=counters, page_size=ps, trace=tr_reduced, peaks=peaks,
        trace_wall=trace_wall,
    )
    metrics = {}
    for name, (read, unit) in readers.items():
        value = read(result_run)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": unit}

    # free the program's state before the reference runs: a process's
    # peak memory never falls again
    want_tokens = int(spec.cell["sample_tokens"])
    sample = reference.turn_sample(
        [t for t in in_window if t.ok and t.generated],
        np.random.default_rng(seed), want_tokens, SAMPLE_TURNS)
    del cluster, load, rec, result_run
    gc.collect()
    gaps = reference.gaps_of(params, block, dims, sample, control=control)
    log(f"reference: {len(sample)} turns, {gaps['tokens']} served tokens compared; "
        f"widest gap {gaps['served_gap']:.6g}"
        + (f", fp8 control's {gaps['control_gap']:.6g}" if control else ""))
    gap = gaps["control_gap"] if control else gaps["served_gap"]
    limit = float(spec.cell["gap_limit"])

    checks = {
        "served_logit_gap": (gap, limit),
        "replica_mismatches": (violations["replica_mismatches"], 0),
        "stale_turns": (violations["stale_turns"], 0),
        "unresolved_turns": (unresolved, 0),
        "tokens_compared": (gaps["tokens"], want_tokens // 2),
    }
    correct = (gap <= limit and unresolved == 0
               and violations["replica_mismatches"] == 0
               and violations["stale_turns"] == 0
               and gaps["tokens"] >= want_tokens // 2)
    out = {
        "correct": bool(correct),
        "attempted": len(in_window),
        "failed": sum(not t.ok for t in in_window),
        "metrics": metrics,
        "memory_peak_bytes": memory_peak,
        "checks": {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()},
    }
    if tr_reduced is not None:
        out["busy_s"] = tr_reduced["busy_s"]
        out["window_s"] = tr_reduced["window_s"]
        out["breakdown"] = {"device_ops": tr_reduced["device_ops"],
                            "idle_gaps": tr_reduced["idle_gaps"]}
    return out
