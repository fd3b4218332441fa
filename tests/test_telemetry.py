"""Serving telemetry: tracer, metrics, decision audit, Chrome-trace export.

Covers the tentpole acceptance bar (ISSUE 8): a 2-span heterogeneous-switch
run exports a *valid* Chrome trace-event JSON with one track per replica,
per-request flow arrows across migrations, and switch-phase begin/end
spans; the fake-clock engine test proves timestamps come from the
injectable clock (deterministic TTFT); the frozen ``load_stats`` schema is
pinned; and the chaos-marked completeness test asserts that under a seeded
fault plan (replica crash + a switch that fails mid-migration) every
submitted request's event stream still ends in exactly one terminal event
and every migration pairs a source with a destination replica.
"""
import dataclasses
import gc
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.costmodel import CostModel
from repro.core.orchestrator import Orchestrator, OrchestratorConfig
from repro.core.types import (ClusterSpec, Deployment, H100_SPEC,
                              ReplicaConfig, WorkloadType)
from repro.models import init_params
from repro.serving.cluster import ClusterRuntime
from repro.serving.engine import LOAD_STATS_KEYS, ServingEngine
from repro.serving.faults import FaultPlan
from repro.serving import telemetry as telemetry_mod
from repro.serving.router import FlowRouter
from repro.serving.telemetry import (NULL_TELEMETRY, ORCH_TID, TERMINAL_KINDS,
                                     DecisionAudit, Histogram, Telemetry,
                                     Tracer, export_chrome_trace,
                                     validate_chrome_trace)

ARCH = [WorkloadType(1275, 287), WorkloadType(139, 133),
        WorkloadType(1181, 1824), WorkloadType(282, 1121)]


def ws(rates):
    return [a.with_rate(float(r)) for a, r in zip(ARCH, rates)]


@pytest.fixture(scope="module")
def cfg_params():
    cfg = get_smoke_config("yi-9b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.125            # deterministic, strictly increasing
        return self.t


# ---------------------------------------------------------------------------
# Primitives: tracer ring buffer, disabled no-op, histogram quantiles.
# ---------------------------------------------------------------------------


def test_tracer_ring_bound_and_disabled_noop():
    tr = Tracer(clock=FakeClock(), capacity=4)
    for i in range(10):
        tr.emit("submit", rid=i)
    assert len(tr.events) == 4
    assert tr.dropped == 6
    assert [e.rid for e in tr.events] == [6, 7, 8, 9]   # oldest evicted

    off = Telemetry(enabled=False)
    off.emit("submit", rid=0)
    off.metrics.count("x")
    off.metrics.observe("h", 1.0)
    off.audit.record_realized(None)      # must not even touch the report
    assert not off.tracer.events and not off.metrics.counters
    assert not off.metrics.histograms and not off.audit.records


def test_histogram_log_bucket_percentiles():
    h = Histogram()
    vals = [0.001 * (i + 1) for i in range(1000)]      # 1ms .. 1s uniform
    for v in vals:
        h.record(v)
    assert h.count == 1000
    assert h.min == pytest.approx(0.001) and h.max == pytest.approx(1.0)
    assert h.mean == pytest.approx(np.mean(vals))
    # log-bucketed: ~5% relative resolution at base 1.1
    for p in (50, 95, 99):
        exact = float(np.percentile(vals, p))
        assert h.percentile(p) == pytest.approx(exact, rel=0.11)
    # clamped to observed range
    assert h.percentile(0) >= h.min and h.percentile(100) <= h.max
    h2 = Histogram()
    h2.record(-1.0)                      # underflow bucket
    assert h2.percentile(50) == 0.0


def test_validator_rejects_malformed_traces():
    ok = {"traceEvents": [
        {"ph": "B", "name": "sw", "pid": 0, "tid": 1, "ts": 0},
        {"ph": "E", "name": "sw", "pid": 0, "tid": 1, "ts": 5}]}
    assert validate_chrome_trace(ok)["be_pairs"] == 1
    bad_pairs = {"traceEvents": [
        {"ph": "B", "name": "sw", "pid": 0, "tid": 1, "ts": 0}]}
    with pytest.raises(ValueError, match="unclosed"):
        validate_chrome_trace(bad_pairs)
    bad_flow = {"traceEvents": [
        {"ph": "s", "name": "m", "pid": 0, "tid": 1, "ts": 0, "id": "a"}]}
    with pytest.raises(ValueError, match="unpaired"):
        validate_chrome_trace(bad_flow)
    bad_dur = {"traceEvents": [
        {"ph": "X", "name": "r", "pid": 0, "tid": 1, "ts": 0, "dur": -1}]}
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(bad_dur)


# ---------------------------------------------------------------------------
# Host spans on the profiler's clock: no-op when disabled, TraceAnnotation
# when enabled, the gc hook's lifetime, and their nesting in a cluster tick.
# ---------------------------------------------------------------------------


@pytest.fixture()
def annotations(monkeypatch):
    """``jax.profiler.TraceAnnotation`` replaced by a recorder; returns the
    log of ``(enter|exit, name, meta)``."""
    log = []

    class Recorder:
        def __init__(self, name, **meta):
            self.name, self.meta = name, meta

        def __enter__(self):
            log.append(("enter", self.name, self.meta))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name, self.meta))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return log


def test_disabled_span_is_one_shared_no_op(monkeypatch):
    class Refuse:
        def __init__(self, *a, **kw):
            raise AssertionError("a disabled bundle built a TraceAnnotation")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refuse)
    off = Telemetry(enabled=False)
    span = off.span("cluster.step")
    assert span is off.span("engine.admit", replica=3)
    assert span is NULL_TELEMETRY.span("engine.finish", replica=0)
    with span:
        with NULL_TELEMETRY.span("engine.wait_device"):
            pass


def test_enabled_span_enters_a_trace_annotation(annotations):
    tm = Telemetry(clock=FakeClock())
    with tm.span("engine.admit", replica=2):
        with tm.span("engine.wait_device"):
            pass
    assert [x for x in annotations if x[1] != "gc"] == [
        ("enter", "engine.admit", {"replica": 2}),
        ("enter", "engine.wait_device", {}),
        ("exit", "engine.wait_device", {}),
        ("exit", "engine.admit", {"replica": 2})]
    assert not tm.tracer.events          # spans stay out of the ring


def test_gc_span_hook_lives_with_the_enabled_bundles(annotations):
    hook = telemetry_mod._GC_SPAN
    gc.collect()                         # release bundles other tests left
    live = hook.live
    Telemetry(enabled=False)
    assert hook.live == live
    tm = Telemetry()
    assert hook.live == live + 1 and hook in gc.callbacks
    annotations.clear()
    gc.collect()
    assert annotations[:1] == [("enter", "gc", {})]
    assert annotations[-1:] == [("exit", "gc", {})]
    del tm
    gc.collect()
    assert hook.live == live
    assert (hook in gc.callbacks) == (live > 0)


def test_cluster_tick_spans_nest(cfg_params, annotations):
    cfg, params = cfg_params
    rt = ClusterRuntime(cfg, params, total_chips=1, blocks_per_chip=32,
                        seqs_per_chip=2, block_size=8,
                        telemetry=Telemetry(clock=FakeClock()))

    class _Plan:
        deployment = type("D", (), {"replicas": [ReplicaConfig(1)]})()
        fractions = [[1.0]]

    rt.apply_plan(_Plan())
    rng = np.random.RandomState(5)
    for rid in range(3):
        rt.submit(rid, rng.randint(0, cfg.vocab_size, 8).astype(np.int32), 3)
    annotations.clear()
    rt.run_until_idle()
    assert len(rt.results) == 3
    stack, seen = [], set()
    for kind, name, meta in annotations:
        if name == "gc":
            continue
        if kind == "enter":
            seen.add((name, stack[-1] if stack else None))
            assert meta == ({"replica": 0} if name.startswith("engine.")
                            else {})
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack
    top = {"engine.admit", "engine.prefill", "engine.table_update",
           "engine.decode_dispatch", "engine.finish", "cluster.sync_log",
           "cluster.post"}
    assert seen == ({("cluster.step", None)}
                    | {(n, "cluster.step") for n in top}
                    | {("engine.wait_device", "engine.prefill"),
                       ("engine.wait_device", "engine.finish")})


# ---------------------------------------------------------------------------
# Decision audit: FIFO join, calibration error, replica-count mismatch.
# ---------------------------------------------------------------------------


class _Plan:
    def __init__(self, rcs, fractions, throughput=10.0):
        self.deployment = Deployment(tuple(rcs))
        self.fractions = fractions
        self.throughput = throughput
        self.kv_migration_seconds = 0.0


class _Report:
    def __init__(self, tokens, completed=0):
        self.tokens = tokens
        self.completed = completed


def test_audit_fifo_join_and_calibration():
    audit = DecisionAudit()
    # two replicas, all traffic to replica 0 for type 0, etc.
    plan = _Plan([ReplicaConfig(1, 1)] * 2, [[1.0, 0.0], [0.0, 1.0]])
    w = [WorkloadType(10, 10, rate=3.0), WorkloadType(10, 10, rate=1.0)]
    audit.record_plan(plan, w, hysteresis_margin=0.1, switched=True)
    assert audit.records[0].predicted_share == pytest.approx([0.75, 0.25])
    assert not audit.records[0].joined
    # realized exactly the predicted split -> zero error
    audit.record_realized(_Report([75, 25], completed=4))
    assert audit.records[0].joined
    assert audit.calibration_error() == pytest.approx(0.0)
    # second decision realized fully inverted -> L1 = 1.0, mean 0.5
    audit.record_plan(plan, w)
    audit.record_realized(_Report([25, 75]))
    assert audit.calibration_error() == pytest.approx(0.5)
    # replica-count mismatch (death mid-span) scores the 2.0 sentinel
    audit.record_plan(plan, w)
    audit.record_realized(_Report([100]))
    assert audit.records[2].share_l1 == 2.0


# ---------------------------------------------------------------------------
# Frozen load_stats schema (engine + cluster adds "dead").
# ---------------------------------------------------------------------------


def test_load_stats_schema_frozen(cfg_params):
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, num_blocks=32, block_size=8, max_seqs=2)
    assert set(eng.load_stats()) == set(LOAD_STATS_KEYS), \
        "engine load_stats keys drifted from the frozen schema"
    rt = ClusterRuntime(cfg, params, total_chips=2, blocks_per_chip=16,
                        seqs_per_chip=2, block_size=8,
                        router=FlowRouter([[1.0]]))
    rt.apply_plan(_Plan([ReplicaConfig(1, 1)], [[1.0]]))
    (d,) = rt.load_stats()
    assert set(d) == set(LOAD_STATS_KEYS) | {"dead"}, \
        "cluster load_stats keys drifted from the frozen schema"


# ---------------------------------------------------------------------------
# Engine lifecycle with an injected clock: deterministic trace + TTFT.
# ---------------------------------------------------------------------------


def test_engine_trace_deterministic_with_fake_clock(cfg_params):
    cfg, params = cfg_params

    def run():
        tm = Telemetry(clock=FakeClock())
        eng = ServingEngine(cfg, params, num_blocks=64, block_size=8,
                            max_seqs=2, telemetry=tm)
        assert eng.clock is tm.clock     # unified timekeeping
        rng = np.random.RandomState(3)
        for i in range(2):
            eng.submit(i, rng.randint(0, cfg.vocab_size, 8)
                       .astype(np.int32), 4)
        eng.run_to_completion()
        return tm

    a, b = run(), run()
    assert [(e.kind, e.ts, e.rid) for e in a.tracer.events] == \
           [(e.kind, e.ts, e.rid) for e in b.tracer.events]
    kinds = {e.kind for e in a.tracer.events}
    assert {"submit", "admit", "first_token", "dispatch", "sync",
            "retire"} <= kinds
    # TTFT is measured on the fake clock, hence identical across runs
    ttft = a.metrics.histograms["ttft_s"].summary()
    assert ttft["count"] == 2
    assert ttft == b.metrics.histograms["ttft_s"].summary()
    # each request: one submit, one first_token, one terminal
    for rid, evs in a.tracer.by_request().items():
        ks = [e.kind for e in evs]
        assert ks.count("submit") == 1 and ks.count("first_token") == 1
        assert sum(1 for k in ks if k in TERMINAL_KINDS) == 1


# ---------------------------------------------------------------------------
# Acceptance: 2-span orchestrated heterogeneous switch -> valid Chrome
# trace with per-replica tracks, migration flows, and switch-phase spans.
# ---------------------------------------------------------------------------


def test_orchestrated_switch_exports_valid_trace(cfg_params, tmp_path):
    cfg, params = cfg_params
    cm = CostModel(get_config("opt-30b").profile(), hw=H100_SPEC)
    orch = Orchestrator(cm, ClusterSpec(6, hw=H100_SPEC),
                        OrchestratorConfig(search_patience=10))
    tm = Telemetry()
    rt = ClusterRuntime(cfg, params, orch, blocks_per_chip=16,
                        seqs_per_chip=1, block_size=8, drain_steps=0,
                        telemetry=tm)
    rng = np.random.RandomState(0)
    rid = 0
    for rates in ([5, 300, 2, 3], [40, 10, 60, 40]):
        plan = orch.plan_span(ws(rates))
        rt.apply_plan(plan)
        for _ in range(6):
            t = int(rng.randint(0, 4))
            prompt = rng.randint(0, cfg.vocab_size,
                                 6 + 2 * t).astype(np.int32)
            rt.submit(rid, prompt, 8 + t, type_id=t)
            rid += 1
        for _ in range(4):
            rt.step()
        rt.finish_span()
    rt.run_until_idle()
    assert len(rt.results) == rid

    kinds = {e.kind for e in tm.tracer.events}
    assert "migrate" in kinds, "the heterogeneous switch migrated nothing"
    assert "switch_prepare" in kinds and "switch_commit" in kinds

    # the export round-trips through JSON and validates
    out = tmp_path / "trace.json"
    export_chrome_trace(tm, path=str(out))
    obj = json.loads(out.read_text())
    counts = validate_chrome_trace(obj)
    tids = {e["tid"] for e in obj["traceEvents"] if e["ph"] != "M"}
    assert len(tids - {ORCH_TID}) >= 2, "need one track per replica"
    assert ORCH_TID in tids
    assert counts["flows"] >= 1, "migrations must draw flow arrows"
    assert counts["be_pairs"] >= 2, "switch phases must pair begin/end"
    assert counts["slices"] >= rid, "every request needs residency slices"
    # every track carries a thread_name metadata record
    named = {e["tid"] for e in obj["traceEvents"] if e["ph"] == "M"}
    assert tids <= named

    # decision audit joined both spans
    assert sum(1 for r in tm.audit.records if r.joined) == 2
    assert np.isfinite(tm.audit.calibration_error())

    # latency histograms populated: exactly one TTFT/TPOT per request
    # (migrated re-prefills must not re-enter), >= one queue delay (a
    # re-prefill migration re-admits and is counted again)
    assert tm.metrics.histograms["ttft_s"].count == rid
    assert tm.metrics.histograms["tpot_s"].count == rid
    assert tm.metrics.histograms["queue_delay_s"].count >= rid


# ---------------------------------------------------------------------------
# Chaos: trace completeness under a seeded crash + mid-switch failure.
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.parametrize("case,kw", [
    ("crash", dict(crashes=1, stalls=0)),
    ("failed-switch", dict(crashes=0, stalls=0,
                           switch_failure="switch_migrate")),
    ("crash+failed-switch", dict(crashes=1, stalls=0,
                                 switch_failure="switch_migrate")),
])
def test_trace_complete_under_chaos(cfg_params, case, kw):
    cfg, params = cfg_params
    faults = FaultPlan.seeded(11, n_replicas=2, horizon_ticks=6, **kw)
    tm = Telemetry()
    rt = ClusterRuntime(cfg, params, total_chips=4, blocks_per_chip=32,
                        seqs_per_chip=4, block_size=8, drain_steps=1,
                        router=FlowRouter([[0.5], [0.5]]), faults=faults,
                        telemetry=tm)
    rt.apply_plan(_Plan([ReplicaConfig(1, 1), ReplicaConfig(1, 1)],
                        [[0.5], [0.5]]))
    rng = np.random.RandomState(7)
    for rid in range(8):
        prompt = rng.randint(0, cfg.vocab_size,
                             6 + (rid % 3) * 2).astype(np.int32)
        rt.submit(rid, prompt, 6 + (rid % 4))
    for _ in range(6):
        rt.step()
    # switch ordinal 2: the target of the switch_migrate fault
    rt.apply_plan(_Plan([ReplicaConfig(2, 1), ReplicaConfig(1, 1)],
                        [[0.6], [0.4]]))
    rt.run_until_idle()
    rt.finish_span()

    # exactly one terminal event per submitted request, no extras
    submitted = {e.rid for e in tm.tracer.events if e.kind == "submit"}
    assert submitted == set(range(8))
    terminals: dict[int, int] = {}
    for e in tm.tracer.events:
        if e.kind in TERMINAL_KINDS:
            terminals[e.rid] = terminals.get(e.rid, 0) + 1
    assert terminals.keys() == submitted, \
        f"{case}: requests without a terminal event"
    assert all(c == 1 for c in terminals.values()), \
        f"{case}: duplicated terminal events {terminals}"

    # every migration names a real source and destination replica
    n_rep = len(rt.replicas)
    for e in tm.tracer.events:
        if e.kind == "migrate":
            assert 0 <= e.data["src"] < n_rep
            assert 0 <= e.data["dst"] < n_rep
            assert e.data["path"] in ("handoff", "copy", "reprefill",
                                      "requeue")

    # crash events balance recovery events, and the export stays valid
    n_crash = sum(1 for e in tm.tracer.events if e.kind == "crash")
    n_recov = sum(1 for e in tm.tracer.events if e.kind == "recovered")
    assert n_crash == n_recov
    if kw.get("crashes"):
        assert n_crash >= 1
    counts = validate_chrome_trace(export_chrome_trace(tm))
    assert counts["events"] > 0


# ---------------------------------------------------------------------------
# Live rebalancing (ISSUE 9): rebalance events render as flow arrows, the
# trace still validates, and a preempted-then-resumed request keeps the
# one-terminal-per-rid invariant.
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_rebalance_trace_flows_valid(cfg_params, tmp_path):
    from repro.serving.cluster import RebalanceConfig
    from repro.serving.faults import FaultSpec

    faults = FaultPlan([FaultSpec("stall", 2, replica=0, steps=10_000)])
    tm = Telemetry()
    rt = ClusterRuntime(cfg_params[0], cfg_params[1], total_chips=4,
                        blocks_per_chip=32, seqs_per_chip=4, block_size=8,
                        drain_steps=1, router=FlowRouter([[0.5], [0.5]]),
                        faults=faults, telemetry=tm,
                        rebalance=RebalanceConfig(max_moves_per_tick=4))
    rt.apply_plan(_Plan([ReplicaConfig(1, 1), ReplicaConfig(1, 1)],
                        [[0.5], [0.5]]))
    rng = np.random.RandomState(7)
    for rid in range(8):
        prompt = rng.randint(0, cfg_params[0].vocab_size,
                             6 + (rid % 3) * 2).astype(np.int32)
        rt.submit(rid, prompt, 6 + (rid % 4))
    rt.run_until_idle()
    rt.finish_span()

    kinds = {e.kind for e in tm.tracer.events}
    assert "rebalance" in kinds, "watchdog drains must emit rebalance events"
    assert "degraded" in kinds, "the watchdog must announce degradation"
    for e in tm.tracer.events:
        if e.kind == "rebalance":
            assert 0 <= e.data["src"] < 2 and 0 <= e.data["dst"] < 2
            assert e.data["path"] in ("handoff", "copy", "reprefill",
                                      "requeue")
    out = tmp_path / "trace.json"
    export_chrome_trace(tm, path=str(out))
    counts = validate_chrome_trace(json.loads(out.read_text()))
    assert counts["flows"] >= 1, "rebalances must draw flow arrows"


def test_preempt_evict_resume_one_terminal(cfg_params):
    """Eviction closes the victim's residency but is NOT terminal: the
    resumed request retires exactly once, and the preempt event carries
    the action and the waiter it made room for."""
    cfg, params = cfg_params
    tm = Telemetry()
    rt = ClusterRuntime(cfg, params, total_chips=4, blocks_per_chip=32,
                        seqs_per_chip=4, block_size=8, drain_steps=1,
                        router=FlowRouter([[0.5], [0.5]]), telemetry=tm,
                        rebalance=True)
    rt.apply_plan(_Plan([ReplicaConfig(1, 1), ReplicaConfig(1, 1)],
                        [[0.5], [0.5]]))
    rng = np.random.RandomState(3)
    for rid in range(10):
        prompt = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
        rt.submit(rid, prompt, 8)
    for _ in range(3):
        rt.step()                    # both replicas saturated
    rt.submit(10, np.arange(8, dtype=np.int32), 6, priority=2)
    rt.step()
    rt.run_until_idle()
    rt.finish_span()

    evs = [e for e in tm.tracer.events if e.kind == "preempt"]
    assert evs, "saturated replicas must preempt for the high-pri waiter"
    assert all(e.data["action"] in ("relocate", "evict") for e in evs)
    assert all(e.data["for_rid"] == 10 for e in evs)
    terminals: dict[int, int] = {}
    for e in tm.tracer.events:
        if e.kind in TERMINAL_KINDS:
            terminals[e.rid] = terminals.get(e.rid, 0) + 1
    assert terminals.keys() == set(range(11))
    assert all(c == 1 for c in terminals.values()), \
        f"preempted-then-resumed requests duplicated terminals: {terminals}"


def test_moe_route_one_event_per_dispatch_with_exact_counts():
    """An expert model's decode tick emits one ``moe_route`` event whose
    ``routed_here`` is tokens x top_k x held / E per layer, exactly: with
    top_k = E every token routes to every expert, so the held share is
    exact whatever the router says; the padded row of the batch bucket is
    not counted."""
    cfg = dataclasses.replace(get_smoke_config("olmoe-1b-7b"), n_experts=8,
                              top_k=8, experts_held=4, expert_offset=2)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tm = Telemetry(clock=FakeClock())
    eng = ServingEngine(cfg, params, num_blocks=64, block_size=8,
                        max_seqs=4, telemetry=tm)
    rng = np.random.RandomState(5)
    for i, n in enumerate((6, 7, 9)):
        eng.submit(i, rng.randint(0, cfg.vocab_size, n).astype(np.int32), 4)
    eng.step()                                  # prefill only
    pre = [e for e in tm.tracer.events if e.kind == "moe_route"]
    assert [e.data["phase"] for e in pre] == ["prefill"] * 3
    assert sorted(e.data["tokens"] for e in pre) == [6, 7, 9]
    L, K, held, E = cfg.n_layers, cfg.top_k, cfg.n_held, cfg.n_experts
    for e in pre:
        assert e.data["routed_here"] == e.data["tokens"] * K * held // E * L
        assert e.data["max_load"] == e.data["tokens"]
    eng.step()                                  # one decode tick, B=3 of 4
    dec = [e for e in tm.tracer.events if e.kind == "moe_route"][len(pre):]
    assert len(dec) == 1
    d = dec[0].data
    assert (d["phase"], d["tokens"]) == ("decode", 3)
    assert d["routed_here"] == 3 * K * held // E * L
    assert d["max_load"] == 3
