"""Continuous-batching serving engine (one model replica), real JAX compute.

vLLM-style loop: admit prompts while KV blocks remain, run batched prefill,
then step decode over the active set, emitting one token per sequence per
step; finished sequences free their pages immediately.  Prefill and decode
interleave within a step, so admissions never starve running sequences.

The decode path is device-resident end to end: one jitted fused call
(``models.decode_loop_paged``) scans up to ``decode_horizon`` decode steps
— paged attention, K/V token scatter, SSM update, sampling with per-step
key folding, and the device ``seq_lens_dev`` advance — against the paged
KV pool through the device block table, returning a ``[B, H]`` token block
with **one device→host transfer per horizon** instead of per token.  On
TPU the Pallas paged kernel reads pages in place (gather-free); the
CPU/jnp fallback still gathers the table's pages inside the jit, so its
win comes from bucketed shapes and the removed host round-trips, not
memory traffic.  Active batches are padded to power-of-two buckets, the
page count to power-of-two page buckets, and the horizon to a power-of-two
*floor* of the safe step count, so the number of distinct compilations is
O(log max_seqs * log max_pages * log decode_horizon) instead of one per
(batch, length, steps) shape.  The legacy dense-gather path survives as
``decode_mode="dense"`` for A/B benchmarking (``benchmarks/
bench_engine.py``).

Horizon contract (``decode_horizon > 1``): the host stays authoritative
for admission, retirement, block ownership, and ``seq_lens`` — before each
dispatch it computes the *safe* horizon ``min(decode_horizon, min
remaining max_new_tokens over the batch)``, collapsed to 1 whenever a
scheduling event must interleave per step (a request was admitted this
step, or a chunked prefill is mid-flight), then rounds it DOWN to a power
of two.  Page capacity for the whole horizon is pre-extended against the
sequence's admission-time lifetime reservation (``kvcache.extend_for``),
so the device loop writes new tokens through the block table with no host
allocation; host ``seq_lens`` advances at dispatch and the device mirror
advances inside the loop, so the two re-converge at every sync.  Under
greedy decoding the token stream is identical for every horizon size; with
sampling, per-step key folding (``sampling.step_key``) keeps it identical
too.  ``decode_horizon=1`` (the default) reproduces the per-step engine
exactly.

Dispatch/sync split: ``step_async()`` runs the host-side scheduling and
*fires* the fused decode without reading it back; ``finish_step(pending)``
performs the one device→host token transfer and retirement.  ``step()``
is the synchronous composition.  ``ClusterRuntime.step`` uses the split to
dispatch every replica's fused call before syncing any of them, so the N
device→host transfers and the host-side scheduling overlap the in-flight
device work instead of interleaving N blocking round-trips (shared-pool
replicas' device compute still chains through the pool arrays).

Replica lifecycle API (used by ``repro.serving.cluster.ClusterRuntime`` to
execute orchestrator deployment switches on live engines):

  * ``pause_admission()`` / ``resume_admission()`` — gate ``_admit`` so a
    replica slated for reconfiguration stops taking new work while its
    in-flight sequences keep decoding.
  * ``drain(max_steps)`` — run admission-free steps until the active set
    empties (or the budget runs out), finishing short sequences in place.
  * ``export_inflight(release=...)`` — snapshot every in-flight and queued
    request.  With ``release=True`` the snapshot is host token state only
    (prompt + generated) and the KV blocks return to the pool; with
    ``release=False`` the snapshot additionally *keeps ownership of the
    live KV pages* (plus SSM state rows), so a destination replica can
    resume the sequence without recomputing anything — see
    ``repro.serving.migration``.
  * ``import_by_pages(snaps)`` — adopt migrated sequences directly from
    their KV pages: a same-pool migration re-registers page ownership
    (zero tokens recomputed, no data movement); a cross-pool one runs the
    jitted page copy / relayout.  Returns the snapshots it could not place.
  * ``import_inflight(snaps)`` — the re-prefill fallback: resume migrated
    requests by re-prefilling ``prompt + generated`` as one context; under
    greedy decoding the next token equals what an uninterrupted engine
    would have produced, so either restore path is token-for-token
    transparent.
  * ``load_stats()`` — queue depth / occupancy / block headroom for routers
    and the cluster health loop.

Chunked prefill (``prefill_chunk_tokens=``): prompts longer than the chunk
size run through ``models.prefill_chunk`` one fixed-size chunk per engine
step, with the prefill->page scatter fused into the chunk forward, so a
long prompt (or a migrated context re-prefilling after a cross-pool switch)
never stalls the replica's decode batch.  ``prefill_tokens`` counts every
token that went through a prefill forward — the zero-recompute guarantee of
page-handoff migration is asserted against it in tests.

Engines can share one device ``BlockPool`` (``pool=`` + ``kv_quota=``): the
cluster partitions a single allocation across heterogeneous replicas
instead of each replica reserving a max-size cache.

Telemetry (``telemetry=``, see ``repro.serving.telemetry``): the engine
emits lifecycle events (submit / admit / prefix_hit / prefill_chunk /
first_token / dispatch / sync / retire / shed) and records TTFT / TPOT /
queue-delay histograms, all at host-side scheduling boundaries — never
inside jitted code.  ``clock=`` overrides the time source (defaulting to
the telemetry bundle's clock, itself ``time.monotonic``), so traces and
TTFT/TPOT deadlines share one injectable clock in tests.  The default
``NULL_TELEMETRY`` is disabled end to end: every emit point is a guarded
no-op, keeping the uninstrumented hot path unchanged.

``load_stats()`` schema — FROZEN: these keys are consumed by
``FlowRouter``, ``ClusterRuntime``'s health loop, and the benchmarks;
``tests/test_telemetry.py`` asserts the exact key set, so additions are
fine but renames/removals are breaking:

=======================  ====================================================
key                      meaning
=======================  ====================================================
waiting                  queued requests not yet admitted
active                   requests holding slots (prefilling or decoding)
max_seqs                 slot capacity of this replica
free_blocks              KV pool blocks free right now (this view's quota)
free_blocks_effective    free + cold prefix-cache pages evictable on demand
tokens_out               total tokens emitted since construction
steps                    scheduler iterations since construction
prefill_tokens           tokens run through a prefill forward (see above)
prefix_hits              admissions that reused >= 1 cached page
prefix_misses            admissions with no cached prefix
prefix_hit_tokens        prompt tokens served from the prefix cache
prefix_evicted_bytes     KV bytes moved device -> host tier
prefix_restored_bytes    KV bytes moved host tier -> device
shed                     requests shed for SLO (TTFT queue + TPOT mid-flight)
decode_syncs             fused-decode device->host syncs (one per horizon)
load                     (waiting + active) / max_seqs
rebalanced_in            sequences the cluster rebalancer moved ONTO this
                         replica mid-span (adoption / requeue / re-prefill)
rebalanced_out           sequences the rebalancer moved OFF this replica
preempted                lower-priority sequences preempted here (relocated
                         or evicted) to admit a higher-priority request
fragmentation            internal waste of allocated KV pages: 1 - resident
                         tokens / (held pages * block_size), in [0, 1]
handoff_in               first-token-ready contexts a disaggregated
                         prefill replica handed TO this replica mid-span
handoff_out              contexts this (prefill-role) replica handed off
=======================  ====================================================
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import models
from repro.models import DecodeCache, PagedDecodeState, decode_loop_paged
from repro.models.config import ModelConfig
from repro.models.sampling import sample
from repro.pshard import sharding_rules
from repro.serving.kvcache import (BlockPool, PagedKVCache, copy_blocks,
                                   relayout_blocks, reshard_blocks)
from repro.serving.prefixcache import PrefixCache
from repro.serving.telemetry import NULL_TELEMETRY

# the frozen load_stats() key set (see the module docstring table);
# ClusterRuntime.load_stats adds "dead" on top of these
LOAD_STATS_KEYS = frozenset({
    "waiting", "active", "max_seqs", "free_blocks",
    "free_blocks_effective", "tokens_out", "steps", "prefill_tokens",
    "prefix_hits", "prefix_misses", "prefix_hit_tokens",
    "prefix_evicted_bytes", "prefix_restored_bytes", "shed",
    "decode_syncs", "load",
    "rebalanced_in", "rebalanced_out", "preempted", "fragmentation",
    "handoff_in", "handoff_out",
})


def resolve_attn_impl(attn_impl: str, sharded: bool = False
                      ) -> tuple[str, bool]:
    """Resolve "auto" to the backend's implementation; returns (impl, interpret).

    A ``sharded`` replica (one with a mesh) resolves "auto" to "jnp": the
    Pallas kernel is not ``shard_map``-wired yet, so GSPMD partitions the
    gathered jnp path instead."""
    on_tpu = jax.default_backend() == "tpu"
    if attn_impl == "auto":
        attn_impl = "kernel" if on_tpu and not sharded else "jnp"
    return attn_impl, attn_impl == "kernel" and not on_tpu


def head_pad_for(attn_impl: str) -> int:
    """Pool head_dim padding: the Pallas kernel wants lane-aligned heads."""
    return 128 if attn_impl == "kernel" else 1


@dataclasses.dataclass
class EngineRequest:
    rid: int
    prompt: np.ndarray           # int32 [S] — the ORIGINAL prompt, always
    max_new_tokens: int
    slot: int = -1
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    # resumed (migrated) requests prefill prompt+generated as one context
    ctx: np.ndarray | None = None
    # chunked prefill: tokens of ``prefill_tokens`` already in pages
    prefill_pos: int = 0
    # SLO shedding: absolute TTFT deadline (engine clock); a waiting request
    # whose deadline has passed is rejected before prefill ever starts
    deadline: float | None = None
    # SLO shedding, decode side: per-token pace budget (seconds/token) and
    # the first-token timestamp it is measured from; a mid-flight request
    # whose average pace exceeds the budget is shed (see ``_shed_slow``)
    tpot_budget: float | None = None
    t_first: float | None = None
    # engine-clock submission time (telemetry: queue delay / TTFT)
    t_submit: float | None = None
    # scheduling priority (higher = more important): orders admission and
    # selects preemption victims in the cluster rebalancer
    priority: int = 0

    @property
    def prefill_tokens(self) -> np.ndarray:
        return self.ctx if self.ctx is not None else self.prompt

    @property
    def prefilling(self) -> bool:
        """The context is not fully in pages yet: excluded from decode
        batches, advanced chunk by chunk.  (Resumed requests re-prefilling
        ``prompt + generated`` are prefilling despite non-empty
        ``generated``; page-adopted ones start with ``prefill_pos`` at the
        end.)"""
        return self.prefill_pos < len(self.prefill_tokens)


@dataclasses.dataclass
class InflightSnapshot:
    """State of one request, sufficient to resume it anywhere.

    The token fields alone (``release=True`` exports) support the re-prefill
    restore path.  A ``release=False`` export additionally carries the live
    KV state — the physical pages (whose allocator refcounts the snapshot
    now owns), the resident length, and the SSM state rows — enabling
    zero-recompute restores via ``import_by_pages``.  Held pages must end in
    exactly one of: adoption by a destination engine, or
    ``migration.release_snapshot_pages``.
    """
    rid: int
    prompt: np.ndarray
    generated: list
    max_new_tokens: int
    # live KV state (page-handoff exports only)
    blocks: list | None = None       # physical page ids, sequence order
    seq_len: int = 0                 # tokens resident in those pages
    n_shared: int = 0                # leading prefix-cache pages (refcounted)
    pool: "BlockPool | None" = None  # the pool the pages live in
    ssm: jax.Array | None = None     # [L, ...] this sequence's SSM state row
    conv: jax.Array | None = None
    deadline: float | None = None    # TTFT deadline, carried across migration
    tpot: float | None = None        # TPOT pace budget, carried likewise
    priority: int = 0                # scheduling priority, carried likewise


@dataclasses.dataclass
class PendingDecode:
    """A dispatched-but-unsynced fused decode horizon.

    Holds the device token block between ``step_async`` and
    ``finish_step`` so cross-replica dispatch can overlap device work; the
    single read of ``tokens`` (with an expert model's ``route`` counts) in
    ``finish_step`` is the horizon's one device→host transfer.
    """
    slots: list[int]
    tokens: jax.Array    # [B_bucket, horizon] device-resident token block
    horizon: int
    route: tuple | None = None   # routing counts of an expert model


def _route_last(cfg, out: tuple) -> tuple:
    """A model program's outputs with its routing counts last: an expert
    model's programs return them, any other's get None there."""
    return out if cfg.is_moe else (*out, None)


def _pow2_bucket(n: int, cap: int) -> int:
    """Smallest power of two >= n, clipped to cap."""
    return min(cap, 1 << max(0, n - 1).bit_length())


def _pow2_floor(n: int) -> int:
    """Largest power of two <= n (n >= 1)."""
    return 1 << (n.bit_length() - 1)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, num_blocks: int = 512,
                 block_size: int = 16, max_seqs: int = 8,
                 dtype=jnp.float32, greedy: bool = True, seed: int = 0,
                 decode_mode: str = "paged", attn_impl: str = "auto",
                 pool: BlockPool | None = None, kv_quota: int | None = None,
                 max_blocks_per_seq: int | None = None,
                 prefill_chunk_tokens: int | None = None,
                 decode_horizon: int = 1,
                 prefix_cache: bool = False,
                 mesh=None, shard_plan=None,
                 clock=None, telemetry=None, trace_id: int = 0,
                 role: str = "mixed"):
        """``mesh`` + ``shard_plan`` turn on real intra-replica model
        parallelism: params are placed with ``param_pspecs`` shardings, the
        paged K/V pool is sharded along its KV-head (tp) and layer (pp)
        axes (``pool_pspecs``), and every jitted forward is traced under the
        plan's logical-axis rules so GSPMD partitions prefill, the fused
        decode loop, and chunked prefill across the replica's devices.  The
        host scheduler / allocator / block tables are sharding-oblivious.
        ``cfg``/``params`` must already be the plan's run config (head-
        padded when ``shard_plan.attn_mode == "pad"`` — see
        ``launch.sharding.pad_attention_params``).
        """
        self.cfg = cfg
        self.params = params
        if decode_mode not in ("paged", "dense"):
            raise ValueError(f"unknown decode_mode {decode_mode!r}")
        if decode_horizon < 1:
            raise ValueError("decode_horizon must be >= 1")
        if decode_horizon > 1 and decode_mode != "paged":
            raise ValueError("decode_horizon > 1 needs decode_mode='paged'")
        self.decode_mode = decode_mode
        self.decode_horizon = decode_horizon
        attn_impl, self.interpret = resolve_attn_impl(
            attn_impl, sharded=mesh is not None)
        self.attn_impl = attn_impl
        self._mesh = mesh
        self._shard_plan = shard_plan
        if mesh is not None:
            if shard_plan is None:
                raise ValueError("a sharded engine needs shard_plan "
                                 "(launch.sharding.make_plan(..., 'serve'))")
            if decode_mode != "paged":
                raise ValueError("sharded engines need decode_mode='paged'")
            if attn_impl == "kernel":
                raise NotImplementedError(
                    "the Pallas kernel path is not shard_map-wired yet; "
                    "sharded engines use attn_impl='jnp'")
            from repro.launch.sharding import named, param_pspecs
            self.params = jax.device_put(
                params, named(mesh, param_pspecs(cfg, shard_plan)))
        # the kernel path wants lane-aligned head_dim; pad the pool once at
        # allocation rather than re-padding it every decode step
        head_pad = head_pad_for(attn_impl)
        if max_blocks_per_seq is None:
            max_blocks_per_seq = cfg.max_seq_len // block_size
        if pool is not None:
            if mesh is not None and pool.mesh != mesh:
                raise ValueError("shared pool lives on a different mesh "
                                 "than this engine")
            if pool.block_size != block_size:
                raise ValueError(
                    f"shared pool block_size {pool.block_size} != engine "
                    f"block_size {block_size}")
            if cfg.has_attn and pool.head_pad % head_pad:
                raise ValueError(
                    f"shared pool head_pad {pool.head_pad} incompatible with "
                    f"attn_impl {attn_impl!r} (needs multiple of {head_pad})")
            self.cache = PagedKVCache.from_pool(
                pool, max_seqs, max_blocks_per_seq, quota=kv_quota)
        else:
            kv_spec = None
            if mesh is not None:
                from repro.launch.sharding import pool_pspecs
                kv_spec = pool_pspecs(cfg, shard_plan)
            self.cache = PagedKVCache.create(
                cfg, num_blocks, block_size, max_seqs,
                max_blocks_per_seq=max_blocks_per_seq, dtype=dtype,
                head_pad=head_pad, mesh=mesh, kv_spec=kv_spec,
                rules=shard_plan.rules if shard_plan else None)
        self.max_seqs = max_seqs
        self.dtype = dtype
        self.greedy = greedy
        self.key = jax.random.PRNGKey(seed)
        self.waiting: list[EngineRequest] = []
        self.active: dict[int, EngineRequest] = {}    # slot -> request
        self.admitting = True
        self.steps = 0
        self.tokens_out = 0
        # tokens that went through a prefill forward (one-shot or chunked);
        # page-handoff migration adds ZERO here — tests assert on it
        self.prefill_tokens = 0
        # global decode-step counter: step t samples with
        # step_key(self.key, t) in BOTH the per-step and horizon paths, so
        # sampled streams are horizon-invariant
        self._sample_step = 0
        # one increment per fused-decode device→host sync (the horizon's
        # single transfer) — benches assert syncs << decode token-steps
        self.decode_syncs = 0
        # dispatched horizon histogram {h: count} + the last dispatched h
        self.horizon_counts: dict[int, int] = {}
        self.last_horizon = 0
        # chunked-prefill round-robin rotation pointer
        self._chunk_rr = 0
        # SLO shedding: rids rejected because their TTFT budget was already
        # blown while still waiting
        self.shed_rids: list[int] = []
        # rebalancer traffic: sequences moved onto/off this replica mid-span
        # and lower-priority sequences preempted here (cluster increments)
        self.rebalanced_in = 0
        self.rebalanced_out = 0
        self.preempted = 0
        # disaggregated serving role ("mixed" | "prefill" | "decode") and
        # its first-token-ready context traffic: the engine itself is
        # role-oblivious (the cluster routes and hands off); the role tag
        # and counters exist for telemetry and the health loop
        self.role = role
        self.handoff_in = 0
        self.handoff_out = 0
        # one time source for deadlines, TPOT pacing, AND trace events:
        # ``clock`` wins, else the telemetry bundle's clock (time.monotonic
        # on the disabled default) — inject a fake via either for
        # deterministic tests
        self.telemetry = telemetry if telemetry is not None else NULL_TELEMETRY
        self.trace_id = trace_id          # replica index on the trace
        self.clock = clock if clock is not None else self.telemetry.clock
        # chaos injection: when set, called as ``fault_hook("admit")`` at
        # the top of the admission path (before any state is mutated) and
        # may raise (e.g. an injected pool-reservation OOM).  The cluster
        # wires this to its ``FaultPlan``; standalone engines leave it None.
        self.fault_hook = None
        # chunked prefill needs per-position resumable state; the SSD scan
        # has none, so SSM/hybrid archs keep the one-shot path
        if prefill_chunk_tokens is not None and cfg.has_ssm:
            prefill_chunk_tokens = None
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # content-addressed prefix reuse: resuming prefill mid-prompt rides
        # the chunked-prefill forward, which SSM archs don't have, and pages
        # carry no SSM state — so the cache is attention-only
        self.prefix_cache = None
        if prefix_cache and cfg.has_attn and not cfg.has_ssm:
            self.prefix_cache = (self.cache.pool.prefix_cache
                                 or PrefixCache(self.cache.pool))
            if self.telemetry.enabled:
                # pool-scoped sink: evict/restore events carry replica=-1
                self.prefix_cache.telemetry = self.telemetry
        # (rid, cached_tokens, ctx_tokens) per admission — the cluster
        # drains these into per-workload-type hit rates for the planner
        self.prefix_events: list[tuple[int, int, int]] = []

        trash = self.cache.num_blocks

        # named functions, so each program is ``jit_<name>`` in a trace
        def prefill(p, toks):
            return _route_last(cfg, models.prefill(p, cfg, tokens=toks))

        def prefill_chunk(p, t, k, v, tab, s, nv):
            return _route_last(cfg, models.prefill_chunk(
                p, cfg, t, k, v, tab, s, nv, trash))

        def decode_dense(p, toks, cache):
            return models.decode_step(p, cfg, toks, cache)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode_dense)
        self._fused = self._build_fused()
        donate = (2, 3) if jax.default_backend() != "cpu" else ()
        self._chunk = jax.jit(prefill_chunk, donate_argnums=donate)

    def _span(self, name: str):
        """A host span of this replica (``serving.telemetry``'s names)."""
        return self.telemetry.span(name, replica=self.trace_id)

    def _note_route(self, phase: str, tokens: int, route,
                    steps: int = 1) -> None:
        """Telemetry: one ``moe_route`` event per dispatch of an expert
        model, from the routing counts read back with its tokens."""
        if route is None or not self.telemetry.enabled:
            return
        per_expert, max_load = route
        self.telemetry.emit("moe_route", replica=self.trace_id, phase=phase,
                            tokens=int(tokens), steps=int(steps),
                            routed_here=int(np.sum(per_expert)),
                            max_load=int(max_load))

    def _rules_ctx(self):
        """Context installing the replica's logical-axis sharding rules.

        The ``logical(...)`` annotations in the model only bind at *trace*
        time, so every jitted call site enters this context — re-traces for
        new shape buckets then pick up the replica's mesh rules; unsharded
        engines get a no-op."""
        if self._mesh is None:
            return contextlib.nullcontext()
        return sharding_rules(self._mesh, self._shard_plan.rules)

    def _local(self, x):
        """Bring a (possibly other-mesh) array onto this engine's devices.

        Migration snapshots carry SSM state rows that live on the *source*
        replica's mesh; mixing them into this engine's arrays needs an
        explicit cross-mesh hop first."""
        if x is None:
            return None
        if self._mesh is not None:
            return jax.device_put(x, NamedSharding(self._mesh, P()))
        return jax.device_put(x, jax.devices()[0])

    def _build_fused(self):
        """The jitted device-resident decode loop (up to ``horizon`` steps).

        Gathers per-slot metadata/state from the full-size device arrays,
        scans ``horizon`` fused decode steps (``models.decode_loop_paged``:
        attention + K/V scatter + SSM update + in-loop sampled key folding
        + device lens advance), and scatters lens/SSM state back — the
        ``[B, horizon]`` token block is the only thing that crosses back to
        the host, once per horizon.
        """
        cfg, greedy = self.cfg, self.greedy
        impl, interp = self.attn_impl, self.interpret
        trash = self.cache.trash_slot

        def fused(params, k, v, table_full, lens_full, ssm_full, conv_full,
                  slots, tokens, key, step0, n_pages, horizon):
            table = table_full[slots, :n_pages]
            lens = lens_full[slots]
            ssm = ssm_full[:, slots] if ssm_full is not None else None
            conv = conv_full[:, slots] if conv_full is not None else None
            st = PagedDecodeState(k=k, v=v, block_table=table, lens=lens,
                                  ssm=ssm, conv=conv)
            # bucket padding rides the trash slot and is not traffic
            toks, st, route = _route_last(cfg, decode_loop_paged(
                params, cfg, tokens, st, key, step0, horizon,
                attn_impl=impl, interpret=interp,
                temperature=0.0 if greedy else 1.0, valid=slots != trash))
            # padded rows advanced the trash slot's lens inside the loop;
            # pin it back to 0 so the trash row stays inert
            lens_full = lens_full.at[slots].set(st.lens).at[trash].set(0)
            if ssm_full is not None:
                ssm_full = ssm_full.at[:, slots].set(st.ssm)
                conv_full = conv_full.at[:, slots].set(st.conv)
            return toks, st.k, st.v, lens_full, ssm_full, conv_full, route

        # donate the pools/state so XLA updates pages in place (no-op on CPU)
        donate = (1, 2, 4, 5, 6) if jax.default_backend() != "cpu" else ()
        return jax.jit(fused, static_argnames=("n_pages", "horizon"),
                       donate_argnums=donate)

    # -- submission ------------------------------------------------------------

    @property
    def max_context(self) -> int:
        """Tokens one sequence's block table can address."""
        return self.cache.max_blocks_per_seq * self.cache.block_size

    def _capacity_blocks(self) -> int:
        """Blocks one sequence may ever hold on this replica."""
        cap = min(self.cache.max_blocks_per_seq, self.cache.num_blocks)
        if self.cache.quota is not None:
            cap = min(cap, self.cache.quota)
        return cap

    def fits(self, ctx_len: int, new_tokens: int) -> bool:
        """Can this replica *ever* serve a request of this size?  (Same
        bound ``_validate`` enforces; used by routers to mask out replicas
        whose context ceiling is too small.)"""
        if new_tokens < 1:
            return False
        need = ctx_len + new_tokens - 1
        bs = self.cache.block_size
        return (need + bs - 1) // bs <= self._capacity_blocks()

    def _validate(self, ctx_len: int, new_tokens: int, rid: int) -> None:
        if new_tokens < 1:
            raise ValueError(f"request {rid}: max_new_tokens must be >= 1")
        # the final generated token is returned but never written to a page,
        # so lifetime cache footprint is ctx + new - 1 positions
        if not self.fits(ctx_len, new_tokens):
            need = ctx_len + new_tokens - 1
            raise ValueError(
                f"request {rid}: context {ctx_len} + {new_tokens} new tokens "
                f"needs {need} cache positions but this replica's "
                f"per-sequence block capacity is "
                f"{self._capacity_blocks()} x {self.cache.block_size} tokens")

    def submit(self, rid: int, prompt: np.ndarray, max_new_tokens: int,
               ttft_deadline: float | None = None,
               tpot_deadline: float | None = None,
               type_id: int = -1, priority: int = 0) -> None:
        """Queue a request.  ``ttft_deadline`` (engine-clock absolute time)
        arms SLO-aware shedding: if the deadline passes while the request is
        still waiting, it is rejected instead of admitted (its TTFT budget
        is already blown, so prefilling it would only waste capacity).
        ``tpot_deadline`` (seconds per output token) arms the decode-side
        counterpart: a request whose average token pace, measured from its
        first token, exceeds the budget is shed mid-flight (its slot and
        pages go to requests that can still meet their SLO).  ``type_id``
        only labels the request's workload type on telemetry events.
        ``priority`` (higher = more important) orders admission — the queue
        is stable-sorted by priority whenever any waiter is non-zero — and
        marks preemption victims for the cluster rebalancer."""
        prompt = np.asarray(prompt, np.int32)
        self._validate(len(prompt), max_new_tokens, rid)
        req = EngineRequest(rid, prompt, max_new_tokens,
                            deadline=ttft_deadline,
                            tpot_budget=tpot_deadline,
                            priority=priority)
        tm = self.telemetry
        if tm.enabled:
            req.t_submit = self.clock()
            tm.emit("submit", rid=rid, replica=self.trace_id,
                    type_id=type_id, prompt_len=len(prompt),
                    max_new=max_new_tokens)
        self.waiting.append(req)

    def _free_slots(self) -> list[int]:
        return [s for s in range(self.max_seqs) if s not in self.active]

    # -- replica lifecycle (cluster runtime) -----------------------------------

    def pause_admission(self) -> None:
        """Stop moving waiting requests into slots (switch in progress)."""
        self.admitting = False

    def resume_admission(self) -> None:
        self.admitting = True

    def drain(self, max_steps: int | None = None) -> list[EngineRequest]:
        """Run admission-free steps until the active set empties.

        Short in-flight sequences finish in place (the paper's drain path);
        whatever is still running after ``max_steps`` is left for
        ``export_inflight``.  Admission stays paused on return.
        """
        self.pause_admission()
        finished: list[EngineRequest] = []
        steps = 0
        while self.active and (max_steps is None or steps < max_steps):
            finished.extend(self.step())
            steps += 1
        return finished

    def export_inflight(self, release: bool = True) -> list[InflightSnapshot]:
        """Snapshot and evict every in-flight + queued request.

        ``release=True``: host token state only — prompt and generated
        tokens — KV blocks return to the pool and the target replica
        re-prefills ``prompt + generated`` (see ``import_inflight``).

        ``release=False`` (page handoff): snapshots of sequences that hold a
        useful KV prefix (fully prefilled, mid-generation) keep ownership of
        their physical pages and SSM state rows so a destination can adopt
        them via ``import_by_pages`` with zero recompute.  The caller is
        responsible for every held page (adopt or
        ``migration.release_snapshot_pages``).
        """
        snaps: list[InflightSnapshot] = []
        for slot in sorted(self.active):
            r = self.active.pop(slot)
            snaps.append(self._snapshot_slot(slot, r, release))
        for r in self.waiting:
            snaps.append(InflightSnapshot(r.rid, r.prompt,
                                          list(r.generated),
                                          r.max_new_tokens,
                                          deadline=r.deadline,
                                          tpot=r.tpot_budget,
                                          priority=r.priority))
        self.waiting = []
        return snaps

    def _snapshot_slot(self, slot: int, r: EngineRequest,
                       release: bool) -> InflightSnapshot:
        """Snapshot one evicted active request (slot already popped).

        ``release=True`` or mid-prefill: token state only, pages back to
        the pool.  Otherwise a page-handoff snapshot that owns the slot's
        disowned pages and SSM rows (caller must adopt or release them).
        """
        if release or r.prefilling:
            # mid-chunk prefixes are not resumable state: drop the pages
            self.cache.release_slot(slot)
            return InflightSnapshot(r.rid, r.prompt, list(r.generated),
                                    r.max_new_tokens,
                                    deadline=r.deadline,
                                    tpot=r.tpot_budget,
                                    priority=r.priority)
        ssm_row = (self.cache.ssm[:, slot]
                   if self.cache.ssm is not None else None)
        conv_row = (self.cache.conv[:, slot]
                    if self.cache.conv is not None else None)
        n_shared = self.cache.seq_shared.get(slot, 0)
        blocks, seq_len = self.cache.disown_slot(slot)
        return InflightSnapshot(
            r.rid, r.prompt, list(r.generated), r.max_new_tokens,
            blocks=blocks, seq_len=seq_len, n_shared=n_shared,
            pool=self.cache.pool,
            ssm=ssm_row, conv=conv_row, deadline=r.deadline,
            tpot=r.tpot_budget, priority=r.priority)

    def export_request(self, rid: int,
                       release: bool = False) -> InflightSnapshot | None:
        """Evict ONE request mid-span without touching admission.

        The cluster rebalancer's single-sequence primitive: an active
        request comes out as a page-handoff snapshot (unless ``release`` or
        still prefilling — then token-state only, pages freed), a queued
        one as a plain token snapshot.  Returns None if ``rid`` is not
        here.  Unlike ``export_inflight`` this leaves every other request
        and the admission gate untouched, so the engine keeps serving.
        """
        for slot, r in list(self.active.items()):
            if r.rid == rid:
                del self.active[slot]
                return self._snapshot_slot(slot, r, release)
        for i, r in enumerate(self.waiting):
            if r.rid == rid:
                self.waiting.pop(i)
                return InflightSnapshot(r.rid, r.prompt, list(r.generated),
                                        r.max_new_tokens,
                                        deadline=r.deadline,
                                        tpot=r.tpot_budget,
                                        priority=r.priority)
        return None

    def import_by_pages(self, snaps: list[InflightSnapshot]
                        ) -> list[InflightSnapshot]:
        """Adopt migrated sequences directly from their live KV pages.

        Same-pool snapshots transfer by re-registering block ownership (no
        data movement, zero tokens recomputed); cross-pool ones run the
        jitted page copy (or the dense relayout when page geometry differs)
        and release the source pages.  Adopted requests join ``active``
        mid-generation — the next ``step`` decodes them.

        Returns the snapshots that could NOT be adopted (no free slot /
        quota / no pages); callers fall back to ``import_inflight``, which
        still owns releasing those snapshots' pages.
        """
        rejected: list[InflightSnapshot] = []
        for s in snaps:
            if s.blocks is None or s.pool is None or not s.generated:
                rejected.append(s)
                continue
            ctx = len(s.prompt) + len(s.generated)
            remaining = s.max_new_tokens - len(s.generated)
            if remaining < 1:
                raise ValueError(f"request {s.rid}: nothing left to generate")
            free = self._free_slots()
            # lifetime positions: resident prefix + tokens still to cache
            total = ctx + remaining - 1
            if not free or not self.fits(ctx, remaining):
                rejected.append(s)
                continue
            same_pool = s.pool is self.cache.pool
            if same_pool:
                if (s.pool.block_size != self.cache.block_size
                        or not self.cache.can_adopt(len(s.blocks), total,
                                                    n_shared=s.n_shared)):
                    rejected.append(s)
                    continue
                slot = free[0]
                self.cache.adopt_slot(slot, s.blocks, s.seq_len,
                                      total_tokens=total,
                                      n_shared=s.n_shared)
            else:
                if not self.cache.can_admit(s.seq_len, total_tokens=total):
                    rejected.append(s)
                    continue
                slot = free[0]
                self.cache.admit(slot, s.seq_len, total_tokens=total)
                dst_blocks = self.cache.seq_blocks[slot]
                same_place = s.pool.placement == self.cache.pool.placement
                same_heads = (s.pool.k is None
                              or s.pool.k.shape[2] == self.cache.k.shape[2])
                if s.pool.k is None:
                    pass      # attn-free arch: state is the SSM rows below
                elif (same_place and same_heads
                        and s.pool.block_size == self.cache.block_size
                        and s.pool.k.shape[2:] == self.cache.k.shape[2:]):
                    copy_blocks(s.pool, self.cache.pool, s.blocks, dst_blocks)
                elif same_place and same_heads:
                    relayout_blocks(s.pool, self.cache.pool, s.blocks,
                                    dst_blocks, s.seq_len)
                else:
                    # pools on different meshes / head shardings / padded
                    # head counts: dense gather + explicit cross-mesh hop +
                    # head fix + re-chunked scatter
                    reshard_blocks(s.pool, self.cache.pool, s.blocks,
                                   dst_blocks, s.seq_len)
                s.pool.allocator.release(s.blocks)
            if s.ssm is not None:
                self.cache.ssm = self.cache.ssm.at[:, slot].set(
                    self._local(s.ssm))
            if s.conv is not None:
                self.cache.conv = self.cache.conv.at[:, slot].set(
                    self._local(s.conv))
            r = EngineRequest(s.rid, np.asarray(s.prompt, np.int32),
                              s.max_new_tokens, slot=slot,
                              generated=list(s.generated),
                              tpot_budget=s.tpot, priority=s.priority)
            r.prefill_pos = len(r.prefill_tokens)   # prefix already in pages
            # the pace clock restarts on the adopting engine: migration
            # stall is accounted to the switch, not to this request's TPOT
            r.t_first = self.clock()
            self.active[slot] = r
            # this engine owns the pages now: neuter the snapshot so a later
            # release cannot double-free them
            s.blocks = None
            s.pool = None
            s.ssm = None
            s.conv = None
        return rejected

    def import_inflight(self, snaps: list[InflightSnapshot]) -> None:
        """Resume migrated requests (re-prefill of prompt + generated).

        The resumed context re-computes KV pages / SSM state here, and the
        prefill's final-position logits produce exactly the token a decode
        step on the source replica would have produced next (greedy).
        """
        for s in snaps:
            if not s.generated:          # never prefilled: plain submission
                self.submit(s.rid, s.prompt, s.max_new_tokens,
                            ttft_deadline=s.deadline,
                            tpot_deadline=s.tpot, priority=s.priority)
                continue
            remaining = s.max_new_tokens - len(s.generated)
            if remaining < 1:
                raise ValueError(f"request {s.rid}: nothing left to generate")
            ctx = np.concatenate([np.asarray(s.prompt, np.int32),
                                  np.asarray(s.generated, np.int32)])
            self._validate(len(ctx), remaining, s.rid)
            self.waiting.append(EngineRequest(
                s.rid, np.asarray(s.prompt, np.int32), s.max_new_tokens,
                generated=list(s.generated), ctx=ctx,
                tpot_budget=s.tpot, priority=s.priority))

    def release_all(self) -> None:
        """Teardown: hand every block back to the (shared) pool."""
        self.active = {}
        self.waiting = []
        self.cache.release_all()

    def load_stats(self) -> dict:
        """Occupancy snapshot for routers / the cluster health loop.

        The key set is FROZEN (``LOAD_STATS_KEYS``): see the module
        docstring table; ``tests/test_telemetry.py`` asserts it."""
        pc = self.prefix_cache
        return {
            "waiting": len(self.waiting),
            "active": len(self.active),
            "max_seqs": self.max_seqs,
            "free_blocks": self.cache.n_free_blocks,
            # hit-rate-adjusted capacity: cold cached pages are evictable on
            # demand, so they count as free for admission planning
            "free_blocks_effective": (self.cache.n_free_blocks
                                      + (pc.cold_blocks() if pc else 0)),
            "tokens_out": self.tokens_out,
            "steps": self.steps,
            "prefill_tokens": self.prefill_tokens,
            "prefix_hits": pc.hits if pc else 0,
            "prefix_misses": pc.misses if pc else 0,
            "prefix_hit_tokens": pc.hit_tokens if pc else 0,
            "prefix_evicted_bytes": pc.evicted_bytes if pc else 0,
            "prefix_restored_bytes": pc.restored_bytes if pc else 0,
            "shed": len(self.shed_rids),
            "decode_syncs": self.decode_syncs,
            "load": (len(self.waiting) + len(self.active)) / self.max_seqs,
            "rebalanced_in": self.rebalanced_in,
            "rebalanced_out": self.rebalanced_out,
            "preempted": self.preempted,
            "fragmentation": self._fragmentation(),
            "handoff_in": self.handoff_in,
            "handoff_out": self.handoff_out,
        }

    def _fragmentation(self) -> float:
        """Internal waste of the pages this replica's sequences hold:
        1 - resident tokens / (held pages * block_size).  High values mean
        many partially-filled tail pages — cheap sequences for the
        rebalancer to relocate, since moving them frees whole pages."""
        held = sum(len(b) for b in self.cache.seq_blocks.values())
        if not held:
            return 0.0
        resident = sum(int(self.cache.seq_lens[s])
                       for s in self.cache.seq_blocks)
        return 1.0 - resident / (held * self.cache.block_size)

    def inflight_context_lens(self) -> list[int]:
        """Context length of every sequence that holds live KV pages (the
        orchestrator's migration-cost input for the next switch decision).

        Queued and mid-prefill requests are excluded: they migrate by free
        requeue, not by moving KV state, so pricing them as byte transfers
        would wrongly inflate the switch-cost term."""
        return [len(r.prompt) + len(r.generated)
                for r in self.active.values() if not r.prefilling]

    # -- scheduling ------------------------------------------------------------

    def _shed_blown(self) -> None:
        """SLO-aware queue shedding: drop waiting requests whose TTFT
        deadline has already passed — prefilling them cannot meet the SLO,
        so the capacity goes to requests that can still make theirs."""
        if not any(r.deadline is not None for r in self.waiting):
            return
        now = self.clock()
        keep = []
        tm = self.telemetry
        for r in self.waiting:
            if r.deadline is not None and now > r.deadline:
                self.shed_rids.append(r.rid)
                if tm.enabled:
                    tm.emit("shed", rid=r.rid, replica=self.trace_id,
                            reason="ttft")
                    tm.metrics.count("shed_ttft")
            else:
                keep.append(r)
        self.waiting = keep

    def _admit(self) -> list[EngineRequest]:
        """Move waiting requests into free slots while KV blocks remain."""
        admitted = []
        if not self.admitting:
            return admitted
        if self.fault_hook is not None:
            self.fault_hook("admit")
        self._shed_blown()
        # priority-aware ordering: stable sort keeps FIFO within a class;
        # the all-default (priority 0) path is left untouched
        if any(r.priority for r in self.waiting):
            self.waiting.sort(key=lambda r: -r.priority)
        free = self._free_slots()
        while self.waiting and free:
            req = self.waiting[0]
            ctx = len(req.prefill_tokens)
            # reserve the sequence's lifetime footprint (prompt + remaining
            # decode growth) so later extends can't exhaust the shared pool
            total = ctx + (req.max_new_tokens - len(req.generated)) - 1
            cached, shared, cow = 0, (), None
            if self.prefix_cache is not None and req.prefill_pos == 0:
                # attach (which restores host-tier pages) must precede the
                # capacity check: a failed restore shrinks the match.  The
                # cap at ctx - 1 keeps at least one token in the prefill
                # forward so its logits produce the first generated token.
                m = self.prefix_cache.match(req.prefill_tokens, ctx - 1)
                cached, shared, cow = self.prefix_cache.attach(m)
            if not self.cache.can_admit(ctx, total_tokens=total,
                                        shared_blocks=shared):
                break
            self.waiting.pop(0)
            req.slot = free.pop(0)
            self.cache.admit(req.slot, ctx, total_tokens=total,
                             shared_blocks=shared, cow_src=cow)
            if cached:
                req.prefill_pos = cached   # prefill starts past the prefix
            if self.prefix_cache is not None:
                self.prefix_events.append((req.rid, cached, ctx))
            tm = self.telemetry
            if tm.enabled:
                now = self.clock()
                delay = (now - req.t_submit
                         if req.t_submit is not None else 0.0)
                tm.emit("admit", rid=req.rid, replica=self.trace_id,
                        reserved_bytes=(self.cache.seq_reserved.get(
                            req.slot, 0) * self.cache.pool.page_nbytes),
                        cached_tokens=cached, queue_delay_s=delay)
                tm.metrics.observe("queue_delay_s", delay)
                if cached:
                    tm.emit("prefix_hit", rid=req.rid,
                            replica=self.trace_id, tokens=cached,
                            pages=len(shared) + (1 if cow is not None
                                                 else 0))
            self.active[req.slot] = req
            admitted.append(req)
        return admitted

    def _publish(self, slot: int, r: EngineRequest) -> None:
        """Hand this sequence's full resident pages to the prefix index so
        later prompts with the same leading tokens attach them by refcount.
        Called whenever the context is fully paged (prefill complete) and
        again at retirement/shedding — by then decode has extended the
        stream, so multi-turn follow-ups hit the generated pages too."""
        if self.prefix_cache is None:
            return
        blocks = self.cache.seq_blocks.get(slot)
        if not blocks:
            return
        resident = int(self.cache.seq_lens[slot])
        stream = np.asarray(r.prompt, np.int32)
        if r.generated:
            stream = np.concatenate(
                [stream, np.asarray(r.generated, np.int32)])
        self.prefix_cache.publish(stream[:resident], blocks)

    def _note_first_token(self, r: EngineRequest, now: float) -> None:
        """Telemetry: a request's FIRST ever token just materialized.

        Callers gate on ``not r.generated`` *before* appending — a migrated
        request re-prefilling ``prompt + generated`` produced its first
        token on its origin replica, so it must not re-enter the TTFT
        histogram here."""
        tm = self.telemetry
        if not tm.enabled:
            return
        ttft = now - r.t_submit if r.t_submit is not None else 0.0
        tm.emit("first_token", rid=r.rid, replica=self.trace_id,
                ttft_s=ttft)
        tm.metrics.observe("ttft_s", ttft)

    def _run_prefill(self, reqs: list[EngineRequest]) -> None:
        # bucket by prompt length: same-length batches need no padding, so
        # RoPE positions stay exact for every sequence
        by_len: dict[int, list[EngineRequest]] = {}
        for r in reqs:
            by_len.setdefault(len(r.prefill_tokens), []).append(r)
        for pl, group in by_len.items():
            toks = np.stack([r.prefill_tokens for r in group])
            with self._rules_ctx():
                logits, cache, route = self._prefill(self.params,
                                                     jnp.asarray(toks))
            # one sync per prefill group
            first, route = self._pick(logits, route)
            self._note_route("prefill", pl * len(group), route)
            self.prefill_tokens += pl * len(group)
            t_first = self.clock()
            for i, r in enumerate(group):
                r.t_first = t_first
                if self.cfg.has_attn:
                    self.cache.write_prefill(r.slot, cache.k[:, i],
                                             cache.v[:, i])
                if self.cfg.has_ssm:
                    self.cache.ssm = self.cache.ssm.at[:, r.slot].set(
                        cache.ssm[:, i])
                    self.cache.conv = self.cache.conv.at[:, r.slot].set(
                        cache.conv[:, i])
                r.prefill_pos = pl
                fresh = not r.generated
                r.generated.append(int(first[i]))
                self.tokens_out += 1
                if fresh:
                    self._note_first_token(r, t_first)
                self._publish(r.slot, r)

    def _resume_prefill(self, reqs: list[EngineRequest]) -> None:
        """One-shot prefill of the *uncached suffix* only.

        Prefix-cache admissions land with ``prefill_pos`` at the first
        uncached token; the suffix runs through the chunked-prefill forward
        (which attends to the cached pages via the block table) in a single
        call, so only ``len(prompt) - prefill_pos`` tokens hit
        ``prefill_tokens``.  Writes start at ``prefill_pos``, whose page is
        always private (fresh or COW), so shared pages stay immutable.
        """
        for r in reqs:
            toks = r.prefill_tokens
            start = r.prefill_pos
            n_valid = len(toks) - start
            cb = 1 << max(0, n_valid - 1).bit_length()
            buf = np.zeros((1, cb), np.int32)
            buf[0, :n_valid] = toks[start:]
            bs = self.cache.block_size
            need = (len(toks) + bs - 1) // bs
            n_pages = _pow2_bucket(need, self.cache.max_blocks_per_seq)
            table = self.cache.block_table_dev[r.slot:r.slot + 1, :n_pages]
            with self._rules_ctx():
                logits, k, v, route = self._chunk(
                    self.params, jnp.asarray(buf), self.cache.k,
                    self.cache.v, table, jnp.int32(start),
                    jnp.int32(n_valid))
            self.cache.k, self.cache.v = k, v
            self.prefill_tokens += n_valid      # cached tokens cost zero
            r.prefill_pos = len(toks)
            first, route = self._pick(logits, route)
            self._note_route("prefill", n_valid, route)
            r.t_first = self.clock()
            fresh = not r.generated
            r.generated.append(int(first[0]))
            self.tokens_out += 1
            if fresh:
                self._note_first_token(r, r.t_first)
            self._publish(r.slot, r)

    def _advance_chunked(self) -> None:
        """Spread this step's chunk-token budget over ALL mid-prefill
        sequences.

        One bounded chunk-token budget per engine step (Sarathi-style): the
        prefill->page scatter is fused into each chunk forward, and the
        decode batch for already-running sequences proceeds in the same
        step, so a long prompt never stalls decoding.  The budget is split
        evenly round-robin across every mid-prefill sequence (rotating the
        start slot each step so leftover tokens don't always favor the same
        sequence) instead of dedicating it all to the oldest — two long
        prompts stream in concurrently rather than serializing head-of-line.
        """
        slots = sorted(s for s, r in self.active.items() if r.prefilling)
        if not slots:
            return
        rot = self._chunk_rr % len(slots)
        self._chunk_rr += 1
        order = slots[rot:] + slots[:rot]
        budget = self.prefill_chunk_tokens
        # floor the per-slot share at C/4: a wide mid-prefill set otherwise
        # degenerates into many tiny per-slot forwards whose dispatch
        # overhead eats the fused-chunk win — at most 4 streams advance per
        # step, the rotation rotates who they are
        floor = max(1, self.prefill_chunk_tokens // 4)
        for idx, slot in enumerate(order):
            if budget <= 0:
                break
            # even split over the slots still to be served this step —
            # recomputed each iteration so budget a short prefill leaves on
            # the table flows to the longer ones behind it
            share = max(floor, budget // (len(order) - idx))
            r = self.active[slot]
            toks_all = r.prefill_tokens
            start = r.prefill_pos
            n_valid = min(share, budget, len(toks_all) - start)
            cb = _pow2_bucket(n_valid, self.prefill_chunk_tokens)
            buf = np.zeros((1, cb), np.int32)
            buf[0, :n_valid] = toks_all[start:start + n_valid]
            bs = self.cache.block_size
            need = (start + n_valid + bs - 1) // bs
            n_pages = _pow2_bucket(need, self.cache.max_blocks_per_seq)
            table = self.cache.block_table_dev[slot:slot + 1, :n_pages]
            with self._rules_ctx():
                logits, k, v, route = self._chunk(
                    self.params, jnp.asarray(buf), self.cache.k,
                    self.cache.v, table, jnp.int32(start),
                    jnp.int32(n_valid))
            self.cache.k, self.cache.v = k, v
            self.prefill_tokens += n_valid
            budget -= n_valid
            r.prefill_pos = start + n_valid
            if self.telemetry.enabled:
                self.telemetry.emit("prefill_chunk", rid=r.rid,
                                    replica=self.trace_id, tokens=n_valid,
                                    pos=r.prefill_pos)
            if r.prefill_pos >= len(toks_all):   # final chunk emits token 1
                first, route = self._pick(logits, route)
                self._note_route("prefill", n_valid, route)
                r.t_first = self.clock()
                fresh = not r.generated
                r.generated.append(int(first[0]))
                self.tokens_out += 1
                if fresh:
                    self._note_first_token(r, r.t_first)
                self._publish(slot, r)

    def _pick(self, logits: jax.Array, route=None):
        """(the sampled ids, ``route``) on the host: an expert model's
        routing counts come back in the same transfer as its ids."""
        if self.greedy:
            ids = sample(logits, self.cfg, self.key)
        else:
            self.key, sub = jax.random.split(self.key)
            ids = sample(logits, self.cfg, sub, temperature=1.0)
        with self._span("engine.wait_device"):
            return jax.device_get((ids, route))

    # -- decode paths ----------------------------------------------------------

    def _safe_horizon(self, slots: list[int], event: bool) -> int:
        """How many decode steps the next fused dispatch may take.

        ``min(decode_horizon, min remaining max_new_tokens over the batch)``
        — so no sequence overshoots its budget and retirement lands exactly
        on a horizon boundary — collapsed to 1 whenever a per-step
        scheduling event must interleave (``event``: a request was admitted
        this step, or a chunked prefill advanced — either way a sequence
        should join the decode batch next step, not a horizon later), then
        floored to a power of two so the horizon adds only
        O(log decode_horizon) compilations.  Any still-waiting request is
        blocked until a retirement frees capacity, and retirements bound
        the horizon already, so the queue itself never shrinks it.
        """
        H = self.decode_horizon
        if H <= 1:
            return 1
        if event:
            return 1
        rem = min(self.active[s].max_new_tokens - len(self.active[s].generated)
                  for s in slots)
        H = min(H, rem)
        return _pow2_floor(H) if H > 1 else 1

    def _dispatch_decode(self, slots: list[int], horizon: int
                         ) -> PendingDecode:
        """Fire the fused decode loop over the given slots; no host sync.

        Pre-extends page capacity for the whole horizon (against the
        admission-time lifetime reservation, so allocation cannot fail for
        in-budget growth) and advances the host ``seq_lens``; the device
        mirror advances inside the loop.
        """
        slots = sorted(slots)
        with self._span("engine.table_update"):
            updates = []                 # page capacity for the whole horizon
            for s in slots:
                upd = self.cache.extend_for(s, horizon, sync_device=False)
                if upd is not None:
                    updates.append(upd)
            self.cache.apply_table_updates(updates)   # one scatter, the batch
        with self._span("engine.decode_dispatch"):
            B = len(slots)
            bucket = _pow2_bucket(B, self.max_seqs)
            trash = self.cache.trash_slot
            pad = bucket - B
            slot_arr = np.array(slots + [trash] * pad, np.int32)
            last = np.array([self.active[s].generated[-1] for s in slots]
                            + [0] * pad, np.int32)
            bs = self.cache.block_size
            need = (int(self.cache.seq_lens[slots].max()) + bs - 1) // bs
            n_pages = _pow2_bucket(need, self.cache.max_blocks_per_seq)
            step0 = self._sample_step
            self._sample_step += horizon
            self.horizon_counts[horizon] = (
                self.horizon_counts.get(horizon, 0) + 1)
            self.last_horizon = horizon
            if self.telemetry.enabled:
                self.telemetry.emit("dispatch", replica=self.trace_id,
                                    n=B, h=horizon)
            with self._rules_ctx():
                toks, k, v, lens_dev, ssm, conv, route = self._fused(
                    self.params, self.cache.k, self.cache.v,
                    self.cache.block_table_dev, self.cache.seq_lens_dev,
                    self.cache.ssm, self.cache.conv,
                    jnp.asarray(slot_arr), jnp.asarray(last), self.key,
                    jnp.int32(step0), n_pages=n_pages, horizon=horizon)
            self.cache.k, self.cache.v = k, v
            self.cache.seq_lens_dev = lens_dev
            self.cache.ssm, self.cache.conv = ssm, conv
            return PendingDecode(slots, toks, horizon, route)

    def _finish_decode(self, pending: PendingDecode) -> None:
        """Sync a dispatched horizon: ONE [B, H] device→host transfer."""
        with self._span("engine.wait_device"):
            toks, route = jax.device_get((pending.tokens, pending.route))
        self.decode_syncs += 1
        self._note_route("decode", len(pending.slots) * pending.horizon,
                         route, pending.horizon)
        for i, s in enumerate(pending.slots):
            r = self.active[s]
            r.generated.extend(int(t) for t in toks[i, :pending.horizon])
            self.tokens_out += pending.horizon
        if self.telemetry.enabled:
            self.telemetry.emit("sync", replica=self.trace_id,
                                n=len(pending.slots),
                                tokens=len(pending.slots) * pending.horizon)

    def _run_decode(self, slots: list[int], horizon: int = 1) -> None:
        """Device-resident paged decode over the given slots (gather-free):
        synchronous dispatch + sync."""
        self._finish_decode(self._dispatch_decode(slots, horizon))

    def _run_decode_dense(self, slots: list[int]) -> None:
        """Legacy dense-gather decode (A/B baseline for bench_engine)."""
        slots = np.array(sorted(slots), np.int32)
        B = len(slots)
        lens = self.cache.seq_lens[slots].copy()
        max_len = int(lens.max()) + 1
        last = np.array([self.active[s].generated[-1] for s in slots], np.int32)
        if self.cfg.has_attn:
            k, v, _ = self.cache.gather_dense(slots, max_len)
        else:
            k = v = None
        ssm = self.cache.ssm[:, slots] if self.cache.ssm is not None else None
        conv = self.cache.conv[:, slots] if self.cache.conv is not None else None
        dc = DecodeCache(k=k, v=v, ssm=ssm, conv=conv,
                         pos=jnp.asarray(lens, jnp.int32))
        logits, new_cache = self._decode(self.params, jnp.asarray(last), dc)
        toks, _ = self._pick(logits)
        # persist the new KV token + SSM state back into the pool
        for s in slots:
            self.cache.extend(int(s))
        if self.cfg.has_attn:
            bidx = jnp.arange(B)
            k_new = new_cache.k[:, bidx, jnp.asarray(lens)]   # [L, B, H, D]
            v_new = new_cache.v[:, bidx, jnp.asarray(lens)]
            self.cache.write_token(slots, k_new, v_new, lens)
        if self.cfg.has_ssm:
            self.cache.ssm = self.cache.ssm.at[:, slots].set(new_cache.ssm)
            self.cache.conv = self.cache.conv.at[:, slots].set(new_cache.conv)
        for i, s in enumerate(slots):
            r = self.active[int(s)]
            r.generated.append(int(toks[i]))
            self.tokens_out += 1

    def _retire(self) -> list[EngineRequest]:
        done = []
        tm = self.telemetry
        for s in list(self.active):
            r = self.active[s]
            if len(r.generated) >= r.max_new_tokens:
                r.done = True
                self._publish(s, r)   # decode pages join the prefix index
                self.cache.release_slot(s)
                del self.active[s]
                done.append(r)
                if tm.enabled:
                    now = self.clock()
                    tm.emit("retire", rid=r.rid, replica=self.trace_id,
                            tokens=len(r.generated))
                    if r.t_first is not None and len(r.generated) > 1:
                        tm.metrics.observe(
                            "tpot_s", (now - r.t_first)
                            / (len(r.generated) - 1))
        return done

    # -- main loop ---------------------------------------------------------------

    def step_async(self) -> PendingDecode | None:
        """The host half of one scheduler iteration: admission (with SLO
        shedding), prefill, chunked-prefill advance, and the fused decode
        *dispatch* — but NOT the decode sync.  Returns the pending decode
        handle (None when nothing decoded); the caller must pass it to
        ``finish_step``.  ``ClusterRuntime.step`` fires every replica's
        ``step_async`` before finishing any of them, so no replica's
        device→host sync blocks another replica's dispatch.

        Prefill and decode interleave: sequences that were already active
        still emit decode tokens on a step that admits new prompts (newly
        admitted requests get their first token from prefill itself).
        Prompts longer than ``prefill_chunk_tokens`` advance by a round-
        robin-shared chunk budget per step instead of one-shot prefilling,
        so the decode batch keeps emitting while long contexts stream into
        their pages.  With ``decode_horizon > 1`` the decode dispatch runs
        up to that many device-resident steps (see ``_safe_horizon``);
        ``self.steps`` counts scheduler iterations, not tokens.
        """
        self.steps += 1
        decode_slots = [s for s, r in self.active.items() if not r.prefilling]
        with self._span("engine.admit"):
            admitted = self._admit()
        chunk = self.prefill_chunk_tokens
        # prefix-cache hits (prefill_pos > 0) must not re-run the full
        # prompt: they resume mid-prompt via the chunk forward instead
        oneshot = [r for r in admitted
                   if (chunk is None or len(r.prefill_tokens) <= chunk)
                   and r.prefill_pos == 0]
        if oneshot:
            with self._span("engine.prefill"):
                self._run_prefill(oneshot)
        if chunk is None:
            resumed = [r for r in admitted
                       if 0 < r.prefill_pos < len(r.prefill_tokens)]
            if resumed:
                with self._span("engine.prefill"):
                    self._resume_prefill(resumed)
        # chunked engines resume cached admissions in _advance_chunked,
        # which already starts each chunk at prefill_pos
        # capture the chunk event BEFORE advancing: a prefill that completes
        # this very step is still a per-step event (its sequence must join
        # the decode batch next step, not a horizon later)
        chunking = any(r.prefilling for r in self.active.values())
        if chunk is not None and chunking:
            with self._span("engine.prefill"):
                self._advance_chunked()  # longer admissions, chunk by chunk
        if decode_slots:
            if self.decode_mode == "paged":
                h = self._safe_horizon(decode_slots,
                                       bool(admitted) or chunking)
                return self._dispatch_decode(decode_slots, h)
            with self._span("engine.decode_dispatch"):
                self._run_decode_dense(decode_slots)
        return None

    def _shed_slow(self) -> None:
        """TPOT-aware mid-flight shedding: release active requests whose
        average decode pace (measured from their first token) has blown
        their per-token budget — their SLO is already lost, so the slot and
        pages go to requests that can still meet theirs.  Shed after retire,
        so a request that just produced its final token always completes."""
        if not any(r.tpot_budget is not None for r in self.active.values()):
            return
        now = self.clock()
        for s in list(self.active):
            r = self.active[s]
            if (r.tpot_budget is None or r.t_first is None
                    or len(r.generated) < 2):
                continue
            pace = (now - r.t_first) / (len(r.generated) - 1)
            if pace > r.tpot_budget:
                self.shed_rids.append(r.rid)
                if self.telemetry.enabled:
                    self.telemetry.emit("shed", rid=r.rid,
                                        replica=self.trace_id,
                                        reason="tpot")
                    self.telemetry.metrics.count("shed_tpot")
                self._publish(s, r)   # evicted work still warms the cache
                self.cache.release_slot(s)
                del self.active[s]

    def finish_step(self, pending: PendingDecode | None
                    ) -> list[EngineRequest]:
        """Sync a dispatched step (one device→host token transfer), retire
        finished requests, and shed TPOT-blown ones."""
        with self._span("engine.finish"):
            if pending is not None:
                self._finish_decode(pending)
            done = self._retire()
            self._shed_slow()
        return done

    def step(self) -> list[EngineRequest]:
        """One synchronous scheduler iteration; returns requests finished
        this step (``finish_step(step_async())``)."""
        return self.finish_step(self.step_async())

    def run_to_completion(self, max_steps: int = 100_000
                          ) -> list[EngineRequest]:
        finished = []
        while (self.waiting or self.active) and self.steps < max_steps:
            finished.extend(self.step())
        return finished
