"""Real-compute serving engine: continuous batching over actual JAX forward
passes, on whatever device JAX gives the process (serving/server.py is the
front end; chip_smoke.py drives it on one TPU).

``RealInstance`` is one pipeline instance worth of compute. KevlarFlow's
mechanisms appear here for real:

  * decoupled init — ``RealEngine`` builds params ONCE per stage signature
    and hands node-resident references to instances; replacing a failed
    instance's executor re-uses the already-materialized weights + the
    jit cache (no re-init, no reload);
  * paged KV — every instance's cache IS a ``PagedKVPool`` (kernel-layout
    real buffers); decode attends through block tables with the Pallas
    paged-attention kernel (interpret on CPU, Mosaic on TPU), prefill is
    bucketed to power-of-2 lengths so the jit cache stays O(log max_seq);
  * KV replication — block-granular deltas: only blocks dirtied by
    ``append_token`` since the last pass are copied to the ring target
    (invariant: a block is re-replicated iff ``BlockRef.replicated`` is
    False). Per decode step that is at most ONE block per active request,
    not the request's whole cache;
  * failover — ``fail_instance`` promotes the hosted replica blocks in
    place (``promote_replica``) and the request continues byte-identically
    on the target (tested in tests/test_engine.py).

Every serving family rides this one code path. Dense and MoE differ only in
the per-layer MLP (MoE routes each decoded token drop-free — see
``paged_decode.mlp_apply``); the hybrid family (RecurrentGemma) pages its
local-attention layers and carries RG-LRU recurrent state as opaque
fixed-size blobs in the pool's blob store — dirtied every decode step,
delta-replicated next to the KV blocks, and promoted in place on failover.

Sliding-window archs (mixtral, RecurrentGemma local attention) serve ANY
``max_seq``: each request's block table is a ring over the resident window
(``ceil(window/page) + 1`` pages); pages that fall fully out of the window
are recycled back to the pool as decode advances
(``PagedKVPool.recycle_out_of_window``) and their hosted replicas retired
on the ring peer with a metadata-only retire message — so steady-state
replication stays ≤ 1 KV block (+ 1 blob on hybrid) per request per step
and ``promote_replica`` reconstructs exactly the live window.

Dynamic traffic rerouting (paper Sec 3.2 mechanism #2) is the LB layer of
``RealEngine``: every instance owns a waiting queue, new arrivals route to
the least-loaded alive instance (queue depth + active slots, never
round-robin), queued work an instance cannot place flows to any peer with
headroom, and ``fail_instance`` drains the dead instance's queue onto the
survivors while in-flight requests resume from promoted replicas. Recovery
itself is mode-switched (``EngineConfig.recovery``): ``kevlarflow`` brings
the failed instance back as a warm spare via ``rejoin_instance`` —
decoupled init means it reuses the node-resident weights AND the shared
compiled programs, re-entering the LB group and replication ring without
touching live traffic — while ``standard`` models the classic path: every
victim restarts and the WHOLE group stalls for ``reload_penalty`` clock
units of weight reloading before serving resumes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding as SH
from repro.kernels import ops, paged_attention
from repro.models import api
from repro.models import paged_decode as PD
from repro.models.hybrid import state_blob_words
from repro.serving import tracing
from repro.serving.api_types import FaultSpec
from repro.serving.controlplane import ControlPlane
from repro.serving.kvcache import PagedKVPool
from repro.serving.request import Request, RequestState
from repro.serving.sampling import sample
from repro.serving.transport import (TransportChannel, collect_dirty,
                                     host_table_growth, reconcile_replica)

SCRATCH_RID = -7  # pool rid reserved for the idle-slot scratch block


def _wait_stats(req: Request) -> dict:
    """An admission span's waits in microseconds, while a profiler
    records: ``lock_wait_us`` in the front end (arrival - submit; -1 for a
    request that did not come through ``EngineService``) and ``queue_us``
    in the engine's admission queue (admit - arrival)."""
    if not tracing.enabled():
        return {}
    lock = round((req.arrival_time - req.submit_time) * 1e6) \
        if req.submit_time >= 0 else -1
    return {"lock_wait_us": lock,
            "queue_us": round((req.admit_time - req.arrival_time) * 1e6)}


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 8
    max_seq: int = 256
    temperature: float = 0.0
    replicate: bool = True
    replication: str = "delta"   # "delta" (dirty blocks) | "full" (all blocks)
    pool_blocks: int = 0         # 0 -> primaries + replicas + scratch
    # Pallas kernels in interpret mode? None -> decided once, when the
    # executor is built, from the default backend (Mosaic on TPU only)
    interpret: Optional[bool] = None
    # int8-quantized KV pool: pages (and hybrid state blobs) are stored as
    # int8 + per-row scales, decode runs through the int8 Pallas kernel,
    # and replication ships the quantized bytes — roughly half the HBM read
    # per decode step and half the bytes per replication message
    kv_quant: bool = False
    # chunked prefill: split each admitted prompt into fixed-size chunks
    # (normalized to a power of two >= page_size) and run ONE chunk per
    # mid-prefill slot per engine step, interleaved with ongoing decodes —
    # admissions never stall the decode batch on a whole-prompt forward
    # pass. 0 = monolithic admission (prefill inline at admit time), which
    # is the exact pre-chunking code path.
    prefill_chunk: int = 0
    # prefix caching: fully-covered prompt pages are content-hashed
    # (token ids + arch + kv dtype chain key), interned in the pool's
    # prefix index, and attached by reference on admission — the longest
    # cached page-aligned prefix costs no fresh pages, no prefill compute
    # (chunked prefill resumes from the first uncached token when the
    # page bytes are bitwise-exact for the activation dtype), and no
    # replication bytes beyond one ship per (ring target, page). Writes
    # landing on a shared page copy-on-write to a private slot first.
    prefix_cache: bool = False
    # async double-buffered replication: _replicate STAGES the step's dirty
    # block/blob ids (metadata only) and the data copies ship at the top of
    # the NEXT step, overlapped with that step's compute. flush_replication
    # is the barrier — fail_instance/rejoin_instance flush before touching
    # replicas, so failover stays byte-identical. False = ship in-step and
    # block until the replica is durable (the synchronous baseline
    # bench_overhead's repl_overlap section measures against).
    repl_async: bool = True
    # prefill/decode disaggregation: instances get roles — the first
    # max(1, n//2) run chunked prefill ONLY and stream each fully-covered
    # prompt page (plus the hybrid state blob, and the chain key for
    # prefix-cached pages, which the decode side interns rather than
    # copies) to a decode-role instance over the SAME block transport
    # replication uses; the decode instance seats the request when the
    # final chunk's pages land. Serving is byte-identical to colocated
    # mode (tokens AND raw page bytes); int8 pools stream quantized pages
    # 1.9-3.2x smaller. Requires prefill_chunk > 0 and >= 2 instances.
    # Roles are soft: if every prefill-role instance is dead, survivors
    # serve colocated; a decode-side kill re-streams to another target.
    disaggregate: bool = False
    # replication placement policy (controlplane.PlacementPolicy):
    # "successor" = classic ring, next-alive instance id (the historical
    # behaviour, bit-for-bit); "rendezvous" = highest-random-weight
    # hashing — a membership change re-targets only the instances whose
    # winner left (or that the joiner now wins), so fleet-scale failures
    # re-host a bounded slice of replica bytes instead of cascading
    placement: str = "successor"
    # recovery policy applied by fail_instance. "kevlarflow": in-flight
    # requests resume from promoted replicas, the dead instance's queue
    # reroutes to survivors, and a warm spare rejoins after rejoin_delay
    # (decoupled init: no weight reload, no recompile). "standard": victims
    # restart from scratch and the whole LB group stalls for reload_penalty
    # clock units (full re-init incl. weight load) before serving resumes.
    recovery: str = "kevlarflow"   # "kevlarflow" | "standard"
    auto_rejoin: bool = False      # schedule rejoin_instance automatically
    rejoin_delay: float = 1.0      # kevlarflow spare re-form (clock units)
    reload_penalty: float = 20.0   # standard full re-init (clock units)
    # modeled tensor-parallel shards per instance. A shard-granularity
    # fault (apply_fault / fail_shard) degrades the instance onto its
    # surviving slice instead of killing it: params/KV re-lay over the
    # smaller model axis (distributed.sharding.degraded_spec — replicate-
    # fallback where divisibility breaks), slot capacity drops to
    # floor(max_slots * surviving / n_shards), and the ClusterView marks
    # it DEGRADED (its own epoch bump) so placement deprioritizes it and
    # routing discounts it. Under "standard" recovery a shard fault
    # escalates to whole-instance failure — degraded serving IS the
    # kevlarflow capability.
    n_shards: int = 4
    # load multiplier routing applies to a DEGRADED instance (its queue
    # drains on fewer shards, so equal depth is not equal capacity)
    degraded_load_penalty: float = 2.0


class FamilyExecutor:
    """The jit'd prefill + decode programs for one (cfg, EngineConfig) pair.

    Built ONCE per RealEngine and shared by every instance — including a
    warm spare rejoining after a failure. This is the compute half of
    decoupled init: the spare re-enters with the node-resident weights and
    the already-compiled programs, so rejoining costs neither a weight
    reload nor a recompile."""

    def __init__(self, cfg, ecfg: EngineConfig):
        if cfg.arch_type not in PD.PAGED_FAMILIES:
            raise ValueError(
                f"paged serving covers {PD.PAGED_FAMILIES}, not "
                f"{cfg.arch_type!r} (encoder-only / pure-recurrent families "
                "are not engine targets)")
        temp = ecfg.temperature
        interp = self.interpret = ops.default_interpret() \
            if ecfg.interpret is None else ecfg.interpret
        quant = ecfg.kv_quant
        # the int8 pool threads its scale side arrays through the same
        # signature (None when kv_quant is off — leafless pytree args, so
        # the jit program is identical to before). Pool buffers are
        # donated: decode updates pages/scales/blobs in place; donation
        # indices cover only real buffers.
        if cfg.arch_type == "hybrid":
            def _step(p, tok, k_pages, v_pages, ks, vs, blobs, bscales,
                      bt, bslots, pos, base, rng):
                return PD.decode_step_paged_hybrid(
                    cfg, p, tok, k_pages, v_pages, blobs, bt, bslots,
                    pos, rng, base=base, k_scales=ks, v_scales=vs,
                    blob_scales=bscales, temperature=temp,
                    interpret=interp)

            self.decode = jax.jit(
                _step,
                donate_argnums=(2, 3, 4, 5, 6, 7) if quant else (2, 3, 6))
            self.prefill = jax.jit(
                lambda p, toks, n: PD.prefill_hybrid_bucketed(cfg, p, toks, n))
            self.prefill_chunk = jax.jit(
                lambda p, toks, start, take, kb, vb, st:
                PD.prefill_hybrid_chunk(cfg, p, toks, start, take, kb, vb,
                                        st))
        else:
            def _step(p, tok, k_pages, v_pages, ks, vs, bt, pos, base, rng):
                return PD.decode_step_paged(
                    cfg, p, tok, k_pages, v_pages, bt, pos, rng,
                    base=base, k_scales=ks, v_scales=vs,
                    temperature=temp, interpret=interp)

            self.decode = jax.jit(
                _step, donate_argnums=(2, 3, 4, 5) if quant else (2, 3))
            self.prefill = jax.jit(
                lambda p, toks, n: PD.prefill_bucketed(cfg, p, toks, n))
            self.prefill_chunk = jax.jit(
                lambda p, toks, start, take, kb, vb:
                PD.prefill_chunk(cfg, p, toks, start, take, kb, vb))
        # chunked admission: chunk size normalized to a power of two >= the
        # page size so chunks always tile the prefill bucket exactly
        # (dynamic_update_slice must never clamp) and the chunk-program jit
        # cache stays O(log max_seq) like the bucketed prefill's
        self.chunk = PD.next_bucket(ecfg.prefill_chunk,
                                    lo=cfg.page_size) \
            if ecfg.prefill_chunk > 0 else 0


class RealInstance:
    """One serving instance: any paged-family model over a paged KV pool."""

    def __init__(self, cfg, params, ecfg: EngineConfig, instance_id: int = 0,
                 executor: Optional[FamilyExecutor] = None,
                 clock: Optional[Callable[[], float]] = None,
                 role: str = "both"):
        self.cfg = cfg
        self.family = cfg.arch_type
        self.params = params          # node-resident weights (shared ref!)
        self.ecfg = ecfg
        self.instance_id = instance_id
        self.alive = True
        # shard-level degradation (FailSafe-style): lost TP shard indices.
        # A degraded instance keeps serving on the surviving slice —
        # params/KV re-laid per sharding.degraded_spec (the layout summary
        # lands in degraded_layout), slot capacity scaled by the surviving
        # fraction (slot_cap), decode itself byte-identical.
        self.n_shards = max(1, ecfg.n_shards)
        self.lost_shards: set = set()
        self.degraded_layout: Optional[dict] = None
        # disaggregation role: "prefill" instances run chunked prefill only
        # and hand finished prompts to the engine's handoff stream instead
        # of seating them; "decode" instances receive streamed pages and
        # decode; "both" is colocated serving (disaggregate=False)
        self.role = role
        self.handoff_mode = role == "prefill"
        # prefill jobs whose final chunk just ran under handoff_mode: the
        # engine drains these into its handoff records each step
        self.ready_handoffs: List[dict] = []
        B, S = ecfg.max_slots, ecfg.max_seq
        page = cfg.page_size
        # sliding-window archs serve any max_seq: the block table holds only
        # the resident ring (ceil(window/page)+1 pages); older pages are
        # recycled as decode advances (paged_decode.table_pages)
        self.window = cfg.sliding_window
        self.pages_per_seq = PD.table_pages(cfg, S)
        n_blocks = ecfg.pool_blocks or (2 * B * self.pages_per_seq + 1)
        # hybrid: recurrent state blobs ride in the pool next to the KV
        # blocks (B primaries + B hosted replicas + 1 scratch)
        blob_words = state_blob_words(cfg) if self.family == "hybrid" else 0
        self.pool = PagedKVPool(
            n_blocks, page, n_layers=len(PD.kv_layer_indices(cfg)),
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, real=True,
            dtype=PD.kv_dtype(cfg), blob_words=blob_words,
            n_blobs=(2 * B + 1) if blob_words else 0,
            window=self.window, quantized=ecfg.kv_quant,
            prefix_cache=ecfg.prefix_cache,
            # chain-hash identity: a page is only reusable under the same
            # model AND the same on-page byte representation
            arch_key=f"{cfg.name}|{cfg.arch_type}"
                     f"|{jnp.dtype(PD.kv_dtype(cfg)).name}"
                     f"|q{int(ecfg.kv_quant)}")
        # idle batch slots write/attend into one scratch block, never freed
        self.scratch = self.pool.allocate(SCRATCH_RID, 1)[0].slot
        self.block_table = np.full((B, self.pages_per_seq), self.scratch,
                                   np.int32)
        self.slot_rid = [-1] * B      # request id per slot
        self.slot_pos = np.zeros(B, np.int32)
        # absolute position of each slot's first resident page (recycling)
        self.slot_base = np.zeros(B, np.int32)
        # (rid, logical_idx) of pages recycled this step: the engine turns
        # these into retire messages for the ring peer hosting the replica
        self.pending_retires: List[tuple] = []
        self.scratch_blob = 0
        if blob_words:
            self.scratch_blob = self.pool.allocate_blob(SCRATCH_RID).slot
        self.slot_blob = np.full(B, self.scratch_blob, np.int32)
        self.requests: Dict[int, Request] = {}

        # per-instance sampling stream (used only when temperature > 0)
        self._rng = jax.random.PRNGKey(instance_id + 1)
        # wall clock for request timestamps (None -> caller-supplied ticks)
        self.clock = clock
        # compiled programs, shared across the engine's instances (and with
        # any warm spare that rejoins — see FamilyExecutor)
        ex = executor or FamilyExecutor(cfg, ecfg)
        self._decode = ex.decode
        self._prefill = ex.prefill
        self._prefill_chunk = ex.prefill_chunk
        self.chunk = ex.chunk
        # slot -> in-flight chunked-prefill job (PREFILL-state requests)
        self.prefill_jobs: Dict[int, dict] = {}
        # prefix-cache accounting (prefix_stats aggregates across instances)
        self.prefill_total_tokens = 0
        self.prefill_compute_tokens = 0
        self.prefix_cached_tokens = 0
        # compute-skip eligibility: chunked prefill can resume from the
        # first uncached token only when seeding the chunk buffers from
        # cached pool pages is bitwise-lossless — pages must store exactly
        # the activation dtype (no int8 quantization) and the family must
        # carry no cross-page recurrent state (hybrid RG-LRU summarizes the
        # whole prefix). Ineligible configs still share pages — they
        # recompute the full prompt but skip the writes to shared pages
        # (deterministic recompute reproduces the interned bytes).
        # chunk buffers can be seeded from pool pages only when the page
        # bytes ARE the activation dtype (hybrid carries cross-page
        # recurrent state; int8 pages are lossy) — shared by the prefix
        # cache's compute skip and the streamed-handoff resume path
        self._can_seed_chunks = (
            self.chunk > 0 and self.family != "hybrid"
            and not ecfg.kv_quant
            and jnp.dtype(cfg.dtype) == jnp.dtype(PD.kv_dtype(cfg)))
        self.prefix_skip_compute = ecfg.prefix_cache and self._can_seed_chunks

    def _stamp(self, now: float) -> float:
        """Timestamp an event: fresh wall-clock reading when a clock is
        wired (admission/prefill take real time), else the caller's tick."""
        return self.clock() if self.clock is not None else now

    # -- admission -----------------------------------------------------------
    @property
    def slot_cap(self) -> int:
        """Concurrent-slot capacity under the current shard set: the full
        ``max_slots`` when whole, scaled by the surviving fraction when
        degraded (never below 1 — a degraded instance still serves)."""
        if not self.lost_shards:
            return self.ecfg.max_slots
        surviving = self.n_shards - len(self.lost_shards)
        return max(1, (self.ecfg.max_slots * surviving) // self.n_shards)

    def capacity_frac(self) -> float:
        """Throughput cap as a fraction of the whole instance (0 dead)."""
        if not self.alive:
            return 0.0
        if not self.lost_shards:
            return 1.0
        return (self.n_shards - len(self.lost_shards)) / self.n_shards

    def free_slots(self) -> List[int]:
        """Admittable slot indices, capacity-capped: a degraded instance
        exposes only the headroom under ``slot_cap``, so every admission
        path — queue admit, replica adoption, handoff seating — respects
        the reduced-capacity executor without special-casing."""
        free = [i for i, r in enumerate(self.slot_rid) if r < 0]
        occupied = len(self.slot_rid) - len(free)
        headroom = max(0, self.slot_cap - occupied)
        return free[:headroom]

    def degrade(self, shard_idx: int) -> List[Request]:
        """Lose one shard: record it, shrink capacity, and hand back the
        EXCESS in-flight requests (most-recently-seated first — the least
        progress to lose if one must restart). The engine migrates them;
        the pool, and every request that stays, is untouched — decode on
        survivors is byte-identical."""
        self.lost_shards.add(shard_idx)
        occupied = [i for i, r in enumerate(self.slot_rid) if r >= 0]
        excess = len(occupied) - self.slot_cap
        if excess <= 0:
            return []
        return [self.requests[self.slot_rid[i]]
                for i in occupied[-excess:]]

    def restore_shards(self):
        """Every lost shard rejoined: full spec, full capacity."""
        self.lost_shards.clear()
        self.degraded_layout = None

    def _allocate(self, rid: int, n_tokens: int, token_ids=None):
        """Allocate primary blocks (and, for hybrid, the state blob),
        evicting hosted replicas under pressure (the paper's rule: replicas
        are the first thing dropped)."""
        need = self.pool.resident_blocks_for(n_tokens)
        protect = ()
        if self.ecfg.prefix_cache and token_ids is not None \
                and not self.pool.window:
            # pressure estimate: pages the prefix cache will cover cost no
            # fresh slots — don't evict failover state to make room for them
            matched, partial = self.pool.match_prefix(
                token_ids[:n_tokens], peek=True)
            need -= len(matched) + (1 if partial else 0)
            protect = {e.key for e in matched}
            if partial:
                protect.add(partial[0].key)
        if need > self.pool.n_free and not self.pool.window:
            # unwindowed pools raise without evicting. Windowed pools get
            # the cheaper remedy first: allocate's own fallback recycles
            # live requests' out-of-window head pages and only then evicts
            # hosted replicas — pre-evicting here would drop peers'
            # failover state that recycling could have kept. Warm
            # refcount-0 prefix pages are pure cache: reclaim them first.
            self.pool.evict_cached_prefixes(need, protect=protect)
            if need > self.pool.n_free:
                self.pool.evict_replicas_for_pressure(need)
        try:
            refs = self.pool.allocate(rid, n_tokens, token_ids=token_ids)
        finally:
            # allocate's windowed fallback may have recycled other
            # requests' out-of-window head pages — even on a failed
            # allocation their hosted replicas still need retiring on the
            # ring peer, or the host leaks blocks for the request's life
            self.pending_retires.extend(
                (r.rid, r.logical_idx)
                for r in self.pool.drain_pending_recycles())
        if self.family == "hybrid":
            self.pool.evict_blob_replicas_for_pressure()
            try:
                self.pool.allocate_blob(rid)
            except MemoryError:
                self.pool.free(rid)
                raise
        return refs

    def admit(self, req: Request, now: float = 0.0) -> bool:
        slots = self.free_slots()
        if not slots or not self.alive:
            return False
        slot = slots[0]
        n = req.prompt_len
        try:                           # reserve blocks BEFORE prefill so a
            refs = self._allocate(     # full pool costs no compute
                req.rid, n, token_ids=req.prompt_tokens)
        except MemoryError:
            return False
        # prefix-cache hit accounting: tokens covered by interned pages
        # attached during allocation (0 when the cache is off or cold)
        cached = self.pool.prefix_hits_by_rid.pop(req.rid, 0) \
            if self.ecfg.prefix_cache else 0
        self.prefill_total_tokens += n
        self.prefix_cached_tokens += cached
        page = self.pool.page_size
        # write plan over the cached run: fully-covered shared pages are
        # never written; a shared page the prompt diverges INSIDE is CoW'd
        # to a private slot and rewritten (cow_page); a shared page the
        # prompt merely ends inside is kept shared (rows past the prompt
        # are masked by seq_lens)
        skip_pages, cow_page = 0, -1
        if cached:
            skip_pages = cached // page
            if cached % page:
                if n > cached:
                    cow_page = skip_pages
                else:
                    skip_pages += 1
        req.admit_time = self._stamp(now)       # prefill starts now
        bucket = PD.next_bucket(n, lo=page)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = req.prompt_tokens
        req.instance_id = self.instance_id
        self.slot_rid[slot] = req.rid
        self.requests[req.rid] = req
        if self.chunk:
            # chunked admission: pages are reserved, compute is deferred —
            # prefill_step runs one chunk per engine step so the decode
            # batch never stalls on a whole-prompt forward pass
            req.state = RequestState.PREFILL
            k_buf, v_buf = PD.init_chunk_buffers(self.cfg, bucket)
            done = 0
            if cached and self.prefix_skip_compute:
                # resume from the first uncached token, floored to a chunk
                # boundary; the final chunk always runs (its logits sample
                # the first token), so resume stays < n
                c = min(self.chunk, bucket)
                done = (min(cached, n - 1) // c) * c
                if done:
                    seed_slots = [r.slot
                                  for r in refs[:-(-cached // page)]]
                    k_buf, v_buf = PD.seed_chunk_buffers(
                        k_buf, v_buf, self.pool.k, self.pool.v, seed_slots)
            self.prefill_jobs[slot] = {
                "req": req, "refs": refs, "toks": toks, "bucket": bucket,
                "done": done, "pages_written": skip_pages if cow_page < 0
                else cow_page,
                "cow_page": cow_page, "k_buf": k_buf, "v_buf": v_buf,
                "rstates": PD.init_hybrid_chunk_state(self.cfg)
                if self.family == "hybrid" else None,
            }
            return True
        with tracing.span("prefill", rid=req.rid, tokens=n, bucket=bucket,
                          **_wait_stats(req)):
            if self.family == "hybrid":
                logits, k_seq, v_seq, blob = self._prefill(
                    self.params, jnp.asarray(toks), jnp.int32(n))
                bref = self.pool.blob_ref(req.rid)
                self.pool.write_blob(bref.slot, blob[0])
                self.slot_blob[slot] = bref.slot
            else:
                logits, k_seq, v_seq = self._prefill(
                    self.params, jnp.asarray(toks), jnp.int32(n))
            self.prefill_compute_tokens += n   # monolithic: full compute
            # windowed archs: only the window-covering tail pages were
            # allocated (refs[0].logical_idx > 0 for long prompts) — write
            # just those. Shared prefix pages (lo > 0) already hold these
            # exact bytes and are never written in place; the diverging
            # page goes private first
            first_page = refs[0].logical_idx
            lo = skip_pages if cow_page < 0 else cow_page
            if cow_page >= 0:
                self.pool.ensure_private(req.rid, cow_page)
            if lo < len(refs):
                span = (first_page + lo) * page
                self.pool.write_blocks(
                    [r.slot for r in refs[lo:]],
                    *PD.pack_pages(k_seq[:, span:], v_seq[:, span:],
                                   len(refs) - lo, page))
            self._seat(slot, req, refs, logits, now)
        return True

    def _first_token(self, req: Request, logits, now: float):
        """Sample the prompt's first token off the final prefill logits and
        stamp TTFT — shared by colocated seating and the handoff path (the
        PREFILL side samples, so TTFT means prefill completion in both
        modes)."""
        if self.ecfg.temperature > 0:
            self._rng, admit_rng = jax.random.split(self._rng)
        else:
            admit_rng = None
        first = sample(logits, rng=admit_rng,
                       temperature=self.ecfg.temperature)
        req.output_tokens = [int(first[0])]
        req.generated = 1
        req.prefill_progress = 1.0
        if req.first_token_time < 0:
            # the prefill produced the first token — stamp AFTER it (so
            # first_token_time - admit_time is the prefill cost)
            req.first_token_time = self._stamp(now)

    def _seat(self, slot: int, req: Request, refs, logits, now: float):
        """Shared admission tail: point the slot at its pages, sample the
        prompt's first token, and flip the request to DECODE."""
        if self.ecfg.prefix_cache and req.prompt_tokens is not None:
            # prefill wrote every prompt page: publish the fully-covered
            # ones into the prefix index (no-op pages already shared)
            self.pool.intern_prefix(req.rid,
                                    req.prompt_tokens[:req.prompt_len])
        row = np.full(self.pages_per_seq, self.scratch, np.int32)
        row[:len(refs)] = [r.slot for r in refs]
        self.block_table[slot] = row
        self.slot_base[slot] = refs[0].logical_idx * self.pool.page_size
        if req.generated == 0:
            # a handoff that fell back to local seating already sampled
            self._first_token(req, logits, now)
        req.state = RequestState.DECODE
        self.slot_pos[slot] = req.prompt_len

    # -- chunked prefill -------------------------------------------------------
    def prefill_depth(self) -> int:
        """Slots currently mid-chunked-prefill (pending work for the
        service loop and the /health endpoint)."""
        return len(self.prefill_jobs)

    def prefill_step(self, now: float = 0.0) -> int:
        """Advance every mid-prefill slot by ONE chunk — the interleaving
        policy: each engine step gives each admitted-but-unprefilled slot
        one chunk of prompt compute next to the ongoing decodes. Returns
        the number of chunks run."""
        if not self.alive or not self.prefill_jobs:
            return 0
        ran = 0
        for slot in sorted(self.prefill_jobs):
            job = self.prefill_jobs[slot]
            req = job["req"]
            n = req.prompt_len
            # short prompts collapse to a single whole-bucket chunk; both
            # sizes are powers of two, so chunks tile the bucket exactly
            c = min(self.chunk, job["bucket"])
            c0 = job["done"]
            take = min(c, n - c0)
            with tracing.span("prefill.chunk", rid=req.rid, tokens=take,
                              start=c0, **_wait_stats(req)):
                tc = np.zeros((1, c), np.int32)
                hi = min(c0 + c, job["bucket"])
                tc[0, :hi - c0] = job["toks"][0, c0:hi]
                if self.family == "hybrid":
                    (logits, job["k_buf"], job["v_buf"], job["rstates"],
                     blob) = self._prefill_chunk(
                        self.params, jnp.asarray(tc), jnp.int32(c0),
                        jnp.int32(take), job["k_buf"], job["v_buf"],
                        job["rstates"])
                else:
                    logits, job["k_buf"], job["v_buf"] = self._prefill_chunk(
                        self.params, jnp.asarray(tc), jnp.int32(c0),
                        jnp.int32(take), job["k_buf"], job["v_buf"])
                    blob = None
                job["done"] = c0 + take
                self.prefill_compute_tokens += take
                req.prefill_progress = job["done"] / n
                ran += 1
                final = job["done"] >= n
                self._write_ready_pages(job, final)
                if final:
                    if self.family == "hybrid":
                        bref = self.pool.blob_ref(req.rid)
                        self.pool.write_blob(bref.slot, blob[0])
                        self.slot_blob[slot] = bref.slot
                    if self.handoff_mode:
                        # disaggregation: the prompt's pages (and blob)
                        # are in the pool but the slot parks in PREFILL
                        # state — the engine streams the remaining pages
                        # to the decode target and seats the request
                        # THERE. The first token is sampled now, so TTFT
                        # means the same thing it does colocated: prefill
                        # completion.
                        self._first_token(req, logits, now)
                        self.ready_handoffs.append(
                            {"slot": slot, "req": req, "refs": job["refs"],
                             "logits": logits})
                    else:
                        self._seat(slot, req, job["refs"], logits, now)
                    del self.prefill_jobs[slot]
        return ran

    def _write_ready_pages(self, job: dict, final: bool):
        """Incremental page writes: pages fully covered by the rows prefilled
        so far land in the pool as soon as their last row is computed (the
        final chunk also flushes the partial tail page). On a windowed pool
        only the allocated window-tail pages exist — writes start at the
        first allocated logical page."""
        page = self.pool.page_size
        refs = job["refs"]
        first_page = refs[0].logical_idx
        if final:
            ready = len(refs)
        else:
            ready = min(max(0, job["done"] // page - first_page), len(refs))
        lo = job["pages_written"]
        if ready <= lo:
            return
        cow = job.get("cow_page", -1)
        if 0 <= cow < ready:
            # this batch writes into a shared page the prompt diverges
            # inside: copy-on-write to a private slot before the write
            # lands (the interned page is never mutated in place)
            self.pool.ensure_private(job["req"].rid,
                                     refs[cow].logical_idx)
            job["cow_page"] = -1
        kv_dt = PD.kv_dtype(self.cfg)
        span0 = (first_page + lo) * page
        span1 = (first_page + ready) * page
        self.pool.write_blocks(
            [r.slot for r in refs[lo:ready]],
            *PD.pack_pages(job["k_buf"][:, span0:span1].astype(kv_dt),
                           job["v_buf"][:, span0:span1].astype(kv_dt),
                           ready - lo, page))
        job["pages_written"] = ready

    # -- one continuous-batching iteration ------------------------------------
    def step(self, now: float = 0.0) -> List[Request]:
        if not self.alive:
            return []
        # mid-chunked-prefill slots (PREFILL state) hold pages but no first
        # token yet — they join the decode batch the step after their final
        # chunk lands
        active = [i for i, r in enumerate(self.slot_rid)
                  if r >= 0 and self.requests[r].state == RequestState.DECODE]
        if not active:
            return []
        # ctx_tokens: the attention's work this step, for the profile
        ctx = {"ctx_tokens": sum(int(self.slot_pos[i]) + 1 for i in active)} \
            if tracing.enabled() else {}
        with tracing.span("decode", instance=self.instance_id,
                          slots=len(active), **ctx) as dec:
            with tracing.span("decode.prepare"):
                toks = np.zeros(self.ecfg.max_slots, np.int32)
                for i in active:
                    rid = self.slot_rid[i]
                    toks[i] = self.requests[rid].output_tokens[-1]
                    # sliding window: pages fully below the window of the
                    # position this step writes are recycled BEFORE
                    # allocating the new page (freed slots are the first
                    # candidates for reuse); their hosted replicas are
                    # retired on the ring peer by the engine
                    recycled = self.pool.recycle_out_of_window(rid) \
                        if self.window else []
                    self.pending_retires.extend(
                        (rid, r.logical_idx) for r in recycled)
                    # account the KV row this step writes; may open a
                    # fresh block (marks the receiving block dirty ->
                    # delta replication unit)
                    try:
                        ref = self.pool.append_token(rid)
                    except MemoryError:
                        self.pool.evict_replicas_for_pressure(1)
                        ref = self.pool.append_token(rid)
                    self.pending_retires.extend(
                        (r.rid, r.logical_idx)
                        for r in self.pool.drain_pending_recycles())
                    if self.window:
                        # window-relative row: column j = j-th resident page
                        table = self.pool.table(rid)
                        row = np.full(self.pages_per_seq, self.scratch,
                                      np.int32)
                        row[:len(table)] = [r.slot for r in table]
                        self.block_table[i] = row
                        self.slot_base[i] = \
                            table[0].logical_idx * self.pool.page_size
                    else:
                        self.block_table[i, ref.logical_idx] = ref.slot
                    # the recurrent state advances every step -> blob
                    # always dirty
                    self.pool.mark_blob_dirty(rid)
                if self.ecfg.temperature > 0:
                    self._rng, step_rng = jax.random.split(self._rng)
                else:
                    step_rng = self._rng       # unused by greedy sample()
            if tracing.enabled():
                # the kernel's blocks, from the tables this step launches
                # with (recycling may have moved a window's base)
                dec.set_metadata(**self.kv_blocks())
            pool = self.pool
            with tracing.span("decode.launch"):
                out = self._decode(*self.decode_args(toks, step_rng))
                if self.family == "hybrid" and pool.quantized:
                    (nxt, _, pool.k, pool.v, pool.blobs, pool.k_scale,
                     pool.v_scale, pool.blob_scales) = out
                elif self.family == "hybrid":
                    nxt, _, pool.k, pool.v, pool.blobs = out
                elif pool.quantized:
                    (nxt, _, pool.k, pool.v, pool.k_scale, pool.v_scale) = out
                else:
                    nxt, _, pool.k, pool.v = out
            # the step's single host sync; the chip idles in it too, while
            # the uploads land and the program starts, and while the
            # tokens come back
            with tracing.span("decode.sync"):
                nxt = np.asarray(nxt)
            with tracing.span("decode.finish") as fin:
                finished = []
                for i in active:
                    req = self.requests[self.slot_rid[i]]
                    req.output_tokens.append(int(nxt[i]))
                    req.generated += 1
                    self.slot_pos[i] += 1
                    if req.generated >= req.max_new_tokens or \
                            self.slot_pos[i] >= self.ecfg.max_seq - 1:
                        req.state = RequestState.DONE
                        req.finish_time = self._stamp(now)
                        finished.append(req)
                        self.release(req.rid)
                fin.set_metadata(finished=len(finished))
        return finished

    def kv_blocks(self) -> dict:
        """``kv_blocks``: the blocks the paged kernel fetches per layer in a
        decode step from this state, over every slot it walks (idle ones
        too); ``kv_blocks_table``: the blocks its block table holds."""
        lengths, starts = PD.attended_range(self.slot_pos, self.slot_base,
                                            self.window, np)
        fetched, table = paged_attention.kv_blocks(
            lengths, starts, self.pool.page_size, self.pages_per_seq,
            self.cfg.n_kv_heads, self.cfg.head_dim)
        return {"kv_blocks": fetched, "kv_blocks_table": table}

    def decode_args(self, toks, rng) -> tuple:
        """The decode program's arguments for last tokens ``toks`` (B,):
        the weights, this pool's buffers and the slots' addressing."""
        pool = self.pool
        if self.family == "hybrid":
            return (self.params, jnp.asarray(toks), pool.k, pool.v,
                    pool.k_scale, pool.v_scale, pool.blobs, pool.blob_scales,
                    jnp.asarray(self.block_table),
                    jnp.asarray(self.slot_blob), jnp.asarray(self.slot_pos),
                    jnp.asarray(self.slot_base), rng)
        return (self.params, jnp.asarray(toks), pool.k, pool.v,
                pool.k_scale, pool.v_scale, jnp.asarray(self.block_table),
                jnp.asarray(self.slot_pos), jnp.asarray(self.slot_base), rng)

    def release(self, rid: int):
        """Free a request's engine slot + primary blocks (+ state blob)."""
        if rid in self.requests:
            slot = self.slot_rid.index(rid)
            self.prefill_jobs.pop(slot, None)
            self.slot_rid[slot] = -1
            self.slot_pos[slot] = 0
            self.slot_base[slot] = 0
            self.block_table[slot] = self.scratch
            self.slot_blob[slot] = self.scratch_blob
            self.pool.free(rid)
            self.requests.pop(rid)

    def slot_of(self, rid: int) -> int:
        return self.slot_rid.index(rid)

    def drain_retires(self) -> List[tuple]:
        """(rid, logical_idx) pages recycled since the last drain."""
        out, self.pending_retires = self.pending_retires, []
        return out

    def drain_ready_handoffs(self) -> List[dict]:
        """Prefill jobs whose final chunk ran since the last drain (handoff
        mode): their pages are written and the request is ready to stream
        to its decode target."""
        out, self.ready_handoffs = self.ready_handoffs, []
        return out

    # -- failover --------------------------------------------------------------
    def adopt_replica(self, peer: int, req: Request, meta,
                      migration: bool = True) -> bool:
        """Failover entry: promote hosted replica blocks to primary and
        resume the request here — no buffer copy, just ownership flip. The
        promoted table is the live WINDOW on sliding-window archs: it must
        contiguously cover every page the next decode step can attend to
        (replica pages keep their absolute logical indices)."""
        slots = self.free_slots()
        if not slots or not self.alive:
            return False
        page = self.pool.page_size
        total = meta["pos"]
        refs = self.pool.promote_replica(peer, req.rid)
        bref = self.pool.blob_ref(req.rid)
        for ref in refs:
            ref.n_filled = max(0, min(page, total - ref.logical_idx * page))
            ref.replicated = False     # re-replicate to OUR ring target
        # the replica may carry one page the primary had already recycled
        # (hosting lags the live window by the in-flight retire): drop it
        self.pool.recycle_out_of_window(req.rid)
        refs = self.pool.table(req.rid)
        pages = [r.logical_idx for r in refs]
        first_needed = max(0, total + 1 - self.window) // page \
            if self.window else 0
        complete = (
            pages and pages[0] <= first_needed
            and pages[-1] == (total - 1) // page
            and pages == list(range(pages[0], pages[0] + len(pages)))
            and len(refs) <= self.pages_per_seq
            and all(r.n_filled > 0 for r in refs))
        if not complete or (self.family == "hybrid" and bref is None):
            self.pool.free(req.rid)    # incomplete replica: can't resume
            return False
        slot = slots[0]
        row = np.full(self.pages_per_seq, self.scratch, np.int32)
        row[:len(refs)] = [r.slot for r in refs]
        self.block_table[slot] = row
        self.slot_base[slot] = refs[0].logical_idx * page
        if bref is not None:
            bref.replicated = False
            self.slot_blob[slot] = bref.slot
        self.slot_pos[slot] = total
        req.output_tokens = list(meta["tokens"])
        req.state = RequestState.DECODE
        req.instance_id = self.instance_id
        if migration:
            req.n_migrations += 1
        self.slot_rid[slot] = req.rid
        self.requests[req.rid] = req
        return True

    # -- disaggregated handoff (decode side) -----------------------------------
    def seat_handoff(self, peer: int, req: Request) -> bool:
        """Seat a fully-streamed prefill: promote the hosted pages (and
        blob) to primary and start decoding — the handoff twin of
        ``adopt_replica``, minus the migration count (a handoff is the
        normal path, not a failure). The promoted pages carry the exact
        bytes the prefill wrote, so decode is byte-identical to colocated
        serving. Returns False (hosted table untouched) when no slot is
        free yet — the engine retries next step."""
        meta = {"pos": req.prompt_len, "tokens": list(req.output_tokens)}
        if not self.adopt_replica(peer, req, meta, migration=False):
            return False
        if self.ecfg.prefix_cache and req.prompt_tokens is not None:
            # same publication a colocated _seat does: the streamed prompt
            # pages become this pool's warm prefix chain
            self.pool.intern_prefix(req.rid,
                                    req.prompt_tokens[:req.prompt_len])
        return True

    def adopt_prefill_stream(self, peer: int, req: Request) -> bool:
        """Streamed-handoff recovery: the prefill source died mid-stream,
        and the pages it already shipped are hosted HERE. Promote them and
        resume the chunked prefill from the first unstreamed chunk, seeding
        the chunk buffers from the streamed pages — no recompute for work
        that already crossed the wire. Only bitwise-lossless configs can
        seed (``_can_seed_chunks``); everything else returns False and the
        caller restarts the request from scratch (deterministic recompute
        keeps the stream byte-identical either way)."""
        hosted = self.pool.replica_table(peer, req.rid)
        page = self.pool.page_size
        n = req.prompt_len
        usable = 0
        for i, ref in enumerate(hosted):
            if ref.logical_idx != i or ref.n_filled < page:
                break
            usable += 1
        slots = self.free_slots()
        if not (slots and self.alive and self._can_seed_chunks
                and usable and usable == len(hosted)
                and usable * page < n):
            # nothing streamed, a windowed tail (logical start > 0), or a
            # config that cannot seed buffers losslessly: full restart
            self.pool.drop_replica(peer, req.rid)
            return False
        # snapshot: promote returns the LIVE table list, which the extending
        # allocate below appends into — concatenating without the copy would
        # double-count the fresh tail pages
        refs = list(self.pool.promote_replica(peer, req.rid))
        for ref in refs:
            ref.n_filled = page
            ref.replicated = False
        try:
            refs = refs + self.pool.allocate(req.rid, n - usable * page)
        except MemoryError:
            self.pool.free(req.rid)
            return False
        slot = slots[0]
        bucket = PD.next_bucket(n, lo=page)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = req.prompt_tokens
        k_buf, v_buf = PD.init_chunk_buffers(self.cfg, bucket)
        c = min(self.chunk, bucket)
        # resume floored to a chunk boundary; the final chunk always runs
        # (its logits sample the first token), so resume stays < n
        done = (min(usable * page, n - 1) // c) * c
        if done:
            k_buf, v_buf = PD.seed_chunk_buffers(
                k_buf, v_buf, self.pool.k, self.pool.v,
                [r.slot for r in refs[:usable]])
        self.slot_rid[slot] = req.rid
        self.requests[req.rid] = req
        req.state = RequestState.PREFILL
        req.instance_id = self.instance_id
        req.prefill_progress = done / n
        req.n_migrations += 1
        self.prefill_jobs[slot] = {
            "req": req, "refs": refs, "toks": toks, "bucket": bucket,
            "done": done, "pages_written": usable, "cow_page": -1,
            "k_buf": k_buf, "v_buf": v_buf, "rstates": None,
        }
        return True

    def finish_handoff(self, rid: int):
        """The decode side seated the streamed request: publish its prompt
        pages into OUR prefix index (warm for future arrivals with the
        same prefix) and free the parked slot."""
        req = self.requests.get(rid)
        if req is None:
            return
        if self.ecfg.prefix_cache and req.prompt_tokens is not None:
            self.pool.intern_prefix(rid, req.prompt_tokens[:req.prompt_len])
        self.release(rid)

    def fail(self):
        self.alive = False
        self.pending_retires.clear()   # a dead primary sends no retires
        self.prefill_jobs.clear()      # mid-chunk work is lost with the node
        self.ready_handoffs.clear()
        # a dead instance holds no requests (its memory is lost) — the
        # engine captures the victims first; leaving them here would keep
        # has_pending() true forever and hang drain()
        self.requests = {}


class RealEngine:
    """LB group of RealInstances with ring block-delta replication, dynamic
    traffic rerouting, and mode-switched failover/recovery."""

    def __init__(self, cfg, ecfg: Optional[EngineConfig] = None,
                 n_instances: int = 2, seed: int = 0,
                 clock: Optional[Callable[[], float]] = None):
        self.cfg = cfg
        self.ecfg = ecfg or EngineConfig()
        # monotonic engine time: ticks (one per step) by default, or the
        # injected wall clock (EngineService passes time.time so request
        # timestamps — arrival/TTFT/latency — share one timebase)
        self.clock = clock
        # decoupled init: ONE weight materialization shared by all replicas
        # (every node "holds the same portion of model weights") and ONE set
        # of compiled programs shared by all instances + rejoining spares
        self.params = api.init_params(cfg, jax.random.PRNGKey(seed))
        self.executor = FamilyExecutor(cfg, self.ecfg)
        # resolved kernel mode (True = Pallas interpreter, False = Mosaic)
        self.interpret = self.executor.interpret
        # prefill/decode disaggregation: the first max(1, n//2) instances
        # take the prefill role, the rest decode; without it every
        # instance is colocated ("both")
        if self.ecfg.disaggregate:
            if n_instances < 2:
                raise ValueError("disaggregate=True needs >= 2 instances "
                                 "(one per role)")
            if self.ecfg.prefill_chunk <= 0:
                raise ValueError(
                    "disaggregate=True requires prefill_chunk > 0 — pages "
                    "stream to the decode side as chunks complete")
            n_pre = max(1, n_instances // 2)
            self.roles = {i: "prefill" if i < n_pre else "decode"
                          for i in range(n_instances)}
        else:
            self.roles = {i: "both" for i in range(n_instances)}
        # the control plane: membership/epoch (ClusterView), replication
        # placement, least-loaded routing (shared with the sim LB), and
        # the multi-failure recovery planner. Every policy decision the
        # data-plane code below makes is delegated here.
        self.control = ControlPlane(
            n_instances, placement=self.ecfg.placement, roles=self.roles,
            degraded_load_penalty=self.ecfg.degraded_load_penalty)
        self.instances = [
            RealInstance(cfg, self.params, self.ecfg, i,
                         executor=self.executor, clock=clock,
                         role=self.roles[i])
            for i in range(n_instances)]
        # rid -> {"peer", "home", "pos", "tokens"} (tiny host-side metadata;
        # the KV payload lives in the target pool's hosted replica blocks)
        self.replica_meta: Dict[int, dict] = {}
        # the staged block/blob transport both byte streams ride: ring
        # replication ("repl") and the prefill->decode handoff ("handoff").
        # Copy jobs staged at the end of step N ship at the top of step
        # N+1 (or at the fail/rejoin barrier); byte totals are accounted
        # at FLUSH time so a job dropped for a dead target never counts
        self.transport = TransportChannel(self.instances,
                                          view=self.control.view)
        # rid -> in-flight handoff record (disaggregation): which prefill
        # instance is streaming it, the decode target, and whether the
        # final chunk's pages have landed (seat condition)
        self._handoffs: Dict[int, dict] = {}
        self.handoffs_seated = 0
        self.handoff_streams_resumed = 0
        # arrivals not yet routed (normally drained every step; holds work
        # only while NO instance is alive)
        self.waiting: List[Request] = []
        # dynamic traffic rerouting: per-instance waiting queues, fed by
        # least-loaded routing and drained/requeued on failure
        self.queues: Dict[int, List[Request]] = {
            i: [] for i in range(n_instances)}
        self.done: List[Request] = []
        self.t = self.clock() if self.clock is not None else 0.0
        # standard-recovery stall: until this time the WHOLE group is down
        # reloading weights (the classic fault path KevlarFlow removes)
        self.stall_until = -1.0
        # one dict per fail_instance call; "mttr" lands at rejoin time
        self.failure_events: List[dict] = []
        self.repl_steps = 0
        self.active_request_steps = 0
        # sliding-window recycling: retire messages sent to replica hosts
        # (metadata-only — a retire carries no KV payload)
        self.retire_msgs_total = 0
        # shared-page replication: a prefix page ships AT MOST ONCE per
        # (ring target, chain key); later requests referencing it on the
        # same target add a refcount, not bytes. Hosting events count per
        # (target, key) MEMBERSHIP: fail_instance prunes a dead target's
        # keys, so a rejoin's fresh pool re-counts the hosting when the
        # key ships again — the ship ratio stays exact across failure
        # cycles instead of drifting on a stale denominator
        self.repl_shared_refs_total = 0
        self.repl_shared_hostings_total = 0
        self._shared_hosted_keys: set = set()   # live (target, key) pairs
        # (n_active_slots, wall_seconds, capacity_frac) per decode step —
        # bench_latency aggregates these into its TPOT-vs-active-slots
        # sweep; capacity_frac < 1.0 marks steps served while some
        # instance ran degraded (shard loss caps its slots)
        self.step_samples: List[tuple] = []

    # -- replication traffic accounting (bench_overhead reads these) ---------
    # Shipped totals count bytes that actually LANDED: flush skips (and
    # tallies separately) jobs whose target died between stage and flush,
    # so the totals can never over-count under failure. Staged totals keep
    # the old stage-time view for the overhead bench's staging-cost story.
    @property
    def repl_blocks_total(self) -> int:
        return self.transport.shipped["repl"].blocks

    @property
    def repl_blobs_total(self) -> int:
        return self.transport.shipped["repl"].blobs

    @property
    def repl_bytes_total(self) -> int:
        return self.transport.shipped["repl"].bytes

    @property
    def repl_shared_copies_total(self) -> int:
        return self.transport.shipped["repl"].shared_copies

    @property
    def repl_blocks_staged(self) -> int:
        return self.transport.staged["repl"].blocks

    @property
    def repl_blobs_staged(self) -> int:
        return self.transport.staged["repl"].blobs

    @property
    def repl_bytes_staged(self) -> int:
        return self.transport.staged["repl"].bytes

    @property
    def repl_bytes_dropped(self) -> int:
        return self.transport.dropped["repl"].bytes

    @property
    def _pending_ship(self) -> List[dict]:
        return self.transport.pending

    def submit(self, req: Request):
        self.waiting.append(req)

    # -- dynamic traffic rerouting (LB) ---------------------------------------
    def _load(self, inst: RealInstance) -> int:
        """Instance load as the LB sees it: active slots + queued depth."""
        return len(inst.requests) + len(self.queues[inst.instance_id])

    def _admit_targets(self) -> List[RealInstance]:
        """Instances that accept NEW work. With disaggregation, arrivals go
        to prefill-role instances only (decode instances receive requests
        by handoff, not admission); if every prefill-role instance is dead
        the survivors serve colocated — roles are soft."""
        alive = [i for i in self.instances if i.alive]
        if not self.ecfg.disaggregate:
            return alive
        return [i for i in alive if i.role == "prefill"] or alive

    def _route(self, req: Request, front: bool = False):
        """Queue-depth-aware admission: place the request on the least-
        loaded ALIVE instance's queue (front=True preserves the position of
        requeued work ahead of later arrivals)."""
        alive = self._admit_targets()
        if not alive:
            # nobody to serve it — park in the arrival buffer; the next
            # rejoin re-routes it
            self.waiting.insert(0, req) if front else self.waiting.append(req)
            return
        tgt = self.control.routing.pick(alive, self._load)
        req.instance_id = tgt.instance_id
        q = self.queues[tgt.instance_id]
        q.insert(0, req) if front else q.append(req)

    def queued_requests(self) -> List[Request]:
        """Requests routed to an instance but not yet admitted."""
        return [r for q in self.queues.values() for r in q]

    def has_pending(self) -> bool:
        """True while any request is waiting, queued, or in flight."""
        return bool(self.waiting) or \
            any(self.queues.values()) or \
            any(i.requests for i in self.instances)

    def queue_depth(self) -> int:
        return len(self.waiting) + sum(len(q) for q in self.queues.values())

    def recovery_pending(self) -> bool:
        """True while a spare is waiting to rejoin or the group is inside a
        standard-mode reload stall — step() must keep running through idle
        periods so recovery completes without traffic."""
        return self.control.planner.has_pending() or self.t < self.stall_until

    @property
    def _pending_rejoins(self) -> List[tuple]:
        """(instance_id, ready_at) spares scheduled to rejoin — a read
        view over the recovery planner (the legacy attribute's shape)."""
        return self.control.planner.pending_rejoins()

    def _ring_target(self, instance_id: int) -> int:
        """Replication target under the control plane's placement policy
        (successor ring by default; rendezvous-hash with
        ``EngineConfig.placement="rendezvous"``)."""
        return self.control.placement.target(instance_id, self.control.view)

    def step(self) -> int:
        """One engine iteration: rejoin due spares, route + admit, decode
        everywhere, replicate deltas. Returns the number of requests that
        made forward progress (0 while stalled or idle — the service loop
        backs off instead of spinning)."""
        active = sum(len(i.requests) for i in self.instances if i.alive)
        with tracing.span("engine.step", active=active) as sp:
            progressed, admitted = self._step()
            sp.set_metadata(admitted=admitted)
        return progressed

    def _step(self) -> tuple:
        """``step``'s body; returns (progressed, requests admitted)."""
        self.t = self.clock() if self.clock is not None else self.t + 1.0
        _t0 = time.perf_counter()
        # async shipping: flush the PREVIOUS step's staged jobs (replica
        # deltas AND handoff pages) before anything here mutates the pools
        # — the copies execute on the backend while this step's host-side
        # work and decode dispatch proceed (step N's bytes overlap step
        # N+1's compute) — then seat any handoff whose final pages landed
        self.flush_replication()
        if self._handoffs:
            self._complete_handoffs()
        # coordinated recovery: the planner hands back AT MOST ONE due
        # spare per step (earliest failure first) — serialized rejoins let
        # each re-form settle against a stable topology before the next
        # membership change re-targets the ring again
        due = self.control.planner.next_due(self.t)
        if due is not None:
            # the plan interleaves both granularities earliest-first: a
            # shard rejoin restores the full spec in place, an instance
            # rejoin brings back a warm spare
            if self.control.planner.pending_kind(due) == "shard":
                self.rejoin_shards(due)
            else:
                self.rejoin_instance(due)
        if self.t < self.stall_until:
            return 0, 0    # standard recovery: group-wide weight reload
        alive = [i for i in self.instances if i.alive]
        # rerouting part 1: arrivals go to the least-loaded alive instance
        while self.waiting and alive:
            self._route(self.waiting.pop(0))
        # each instance admits from its OWN queue...
        admitted = 0
        for inst in alive:
            q = self.queues[inst.instance_id]
            while q and inst.free_slots() and inst.admit(q[0], self.t):
                q.pop(0)
                admitted += 1
        # ...then (rerouting part 2) queued work an instance cannot place —
        # full pool, busy slots — flows to any peer with headroom: an
        # instance can have free slots but a full pool, and vice versa
        # (under disaggregation only prefill-capable peers take overflow)
        overflow = self._admit_targets()
        for inst in alive:
            q = self.queues[inst.instance_id]
            if not q:
                continue
            for other in self.control.routing.order(overflow, self._load):
                if other is inst:
                    continue
                while q and other.free_slots() and other.admit(q[0], self.t):
                    q.pop(0)
                    admitted += 1
        progressed = admitted
        n_active = sum(len(i.requests) for i in alive)
        for inst in alive:
            self.active_request_steps += len(inst.requests)
            progressed += len(inst.requests)
            # one prompt chunk per mid-prefill slot, then the decode batch:
            # admissions interleave with generation instead of stalling it
            inst.prefill_step(self.t)
            if inst.handoff_mode:
                # stream every page the chunks just finished writing (and
                # the whole remainder for prompts whose final chunk ran); a
                # decode-role instance serving colocated (soft roles) seats
                # its own prefills locally and never streams
                self._stage_handoffs(inst)
            finished = inst.step(self.t)
            # retire hosted replicas of pages the primary recycled this
            # step — BEFORE the delta pass, so replica tables mirror the
            # live window when new blocks are hosted against them
            for rid, lidx in inst.drain_retires():
                meta = self.replica_meta.get(rid)
                if meta is None or not self.instances[meta["home"]].alive:
                    continue
                if self.instances[meta["home"]].pool.retire_replica_block(
                        meta["peer"], rid, lidx):
                    self.retire_msgs_total += 1
            for req in finished:
                self._drop_replica_of(req.rid)
                self.done.append(req)
        # per-step admission, second pass: slots and pool pages freed by
        # this step's completions/recycles admit queued work NOW instead of
        # waiting a full engine iteration
        for inst in alive:
            q = self.queues[inst.instance_id]
            while q and inst.free_slots() and inst.admit(q[0], self.t):
                q.pop(0)
                admitted += 1
                progressed += 1
        if self.ecfg.replicate:
            self._replicate()
            self.repl_steps += 1
        if self._handoffs and not self.ecfg.repl_async:
            # synchronous shipping: the handoff pages staged this step are
            # already durable (the _replicate barrier above) — seat now
            # instead of waiting for the next step's flush
            self.flush_replication(block=True)
            self._complete_handoffs()
        if n_active:
            # third element: the fleet's serving-capacity fraction this
            # step — degraded instances cap below max_slots, so the sweep
            # can separate full-capacity from degraded-throughput samples
            cap = sum(i.slot_cap for i in alive)
            cap_frac = cap / max(len(self.instances) * self.ecfg.max_slots, 1)
            self.step_samples.append(
                (n_active, time.perf_counter() - _t0, cap_frac))
            if len(self.step_samples) > 20000:      # bound long-run memory
                del self.step_samples[:10000]
        return progressed, admitted

    def _drop_replica_of(self, rid: int):
        meta = self.replica_meta.pop(rid, None)
        if meta is not None:
            home = self.instances[meta["home"]]
            home.pool.drop_replica(meta["peer"], rid)

    def _replicate(self):
        """Background KV replication at block granularity. Delta mode copies
        only blocks with ``replicated == False`` (cleared by ``append_token``
        / prefill allocation); full mode re-copies every live block — the
        seed's whole-snapshot behaviour, kept for the overhead benchmark.

        The pass is split in two: ``_stage_replication`` runs now and does
        ALL the metadata work (hosting, retire/drop bookkeeping, dirty-flag
        clearing, byte accounting) plus snapshots the dirty block/blob slot
        ids; the data copies ship at the top of the next step
        (``flush_replication``) so they overlap that step's compute. With
        ``repl_async=False`` the copies ship here and the step blocks until
        the replica is durable — the synchronous baseline."""
        self._stage_replication()
        if not self.ecfg.repl_async:
            self.flush_replication(block=True)

    def flush_replication(self, block: bool = False,
                          exclude: Optional[int] = None):
        """Ship every staged copy job now — the async double-buffer's
        barrier. Called at the top of every step, and by ``fail_instance``
        / ``rejoin_instance`` BEFORE they touch replicas, so a promoted
        replica always carries the bytes of the primary's last completed
        step (failover stays byte-identical under async shipping).

        Safe between steps: nothing mutates the pools between the stage at
        the end of step N and this flush. A target that died since staging
        — or the instance ``fail_instance`` is about to kill (``exclude``)
        — is skipped AND its jobs' bytes stay out of the shipped totals:
        they never landed, so they must never be accounted."""
        self.transport.flush(block=block, exclude=exclude)

    def _commit_shared_hostings(self, tgt_id: int, grown):
        """Account one growth's shared-page hostings: refcounts per
        reference; hosting events per NEW (target, key) membership — the
        ship-ratio denominator (fail_instance prunes dead targets' keys,
        so a post-rejoin re-host counts again and the ratio stays exact)."""
        for key in grown.shared_keys:
            self.repl_shared_refs_total += 1
            if (tgt_id, key) not in self._shared_hosted_keys:
                self._shared_hosted_keys.add((tgt_id, key))
                self.repl_shared_hostings_total += 1

    def _stage_replication(self):
        staged = self.transport.staged["repl"]
        before = (staged.msgs, staged.blocks, staged.bytes)
        with tracing.span("repl.stage") as sp:
            self._stage_deltas()
            sp.set_metadata(jobs=staged.msgs - before[0],
                            blocks=staged.blocks - before[1],
                            bytes=staged.bytes - before[2])

    def _stage_deltas(self):
        """Host this pass's dirty blocks on each ring target and stage
        their copies."""
        full = self.ecfg.replication == "full"
        pc = self.ecfg.prefix_cache
        for inst in self.instances:
            if not inst.alive:
                continue
            tgt_id = self._ring_target(inst.instance_id)
            if tgt_id < 0:
                continue
            tgt = self.instances[tgt_id]
            src_slots: List[int] = []
            dst_slots: List[int] = []
            blob_src: List[int] = []
            blob_dst: List[int] = []
            shared_copies = 0
            for rid, req in inst.requests.items():
                # mid-chunked-prefill requests have no complete page set to
                # resume from (and no sampled tokens): their pages ship in
                # the first pass after they enter DECODE
                if req.state != RequestState.DECODE:
                    continue
                # the ring target can change (failure, spare rejoin): drop
                # the replica still hosted on the PREVIOUS home, or its
                # blocks leak for the request's lifetime
                meta = self.replica_meta.get(rid)
                if meta is not None and meta["home"] != tgt_id and \
                        self.instances[meta["home"]].alive:
                    self.instances[meta["home"]].pool.drop_replica(
                        meta["peer"], rid)
                table = inst.pool.table(rid)
                # retires keep the hosted table in lockstep with the live
                # window; if it ever drifts, drop it and re-host the
                # current window with matching sharedness
                reconcile_replica(inst.pool, tgt.pool, inst.instance_id,
                                  rid, table, prefix_cache=pc)
                rtab = tgt.pool.replica_table(inst.instance_id, rid)
                grown = None
                if len(table) > len(rtab):
                    grown = host_table_growth(
                        inst.pool, tgt.pool, inst.instance_id, rid, table,
                        prefix_cache=pc)
                    if grown is None:
                        continue   # no headroom on target; retry next pass
                bref = inst.pool.blob_ref(rid)
                rbref = None
                if bref is not None:   # hybrid: state blob rides along
                    if not tgt.pool.host_blob_replica(inst.instance_id, rid):
                        # KV without state can't be resumed: roll back this
                        # pass's hostings first (pages it interned never
                        # ship), then drop the stale earlier table
                        if grown is not None:
                            grown.rollback(tgt.pool, inst.instance_id, rid)
                        tgt.pool.drop_replica(inst.instance_id, rid)
                        continue
                    rbref = tgt.pool.blob_replica_ref(inst.instance_id, rid)
                if grown is not None:
                    self._commit_shared_hostings(tgt_id, grown)
                    for s, d in grown.copies:
                        src_slots.append(s)
                        dst_slots.append(d)
                    shared_copies += len(grown.copies)
                rtab = tgt.pool.replica_table(inst.instance_id, rid)
                # copy when the primary block is dirty OR the hosted block
                # has never received content (fresh hosting — incl.
                # re-hosting after a pressure eviction)
                s, d = collect_dirty(tgt.pool, table, rtab, full=full,
                                     prefix_cache=pc)
                src_slots += s
                dst_slots += d
                if bref is not None:
                    if full or not bref.replicated or not rbref.replicated:
                        blob_src.append(bref.slot)
                        blob_dst.append(rbref.slot)
                        bref.replicated = True
                        rbref.replicated = True
                self.replica_meta[rid] = {
                    "peer": inst.instance_id, "home": tgt_id,
                    "pos": int(inst.slot_pos[inst.slot_of(rid)]),
                    "tokens": list(req.output_tokens),
                }
                req.replicated_through = req.total_len
            if src_slots or blob_src:
                self.transport.stage(
                    "repl", inst.instance_id, tgt_id,
                    (src_slots, dst_slots), (blob_src, blob_dst),
                    shared_copies=shared_copies)

    # -- prefill/decode disaggregation (handoff stream) ------------------------
    def _pick_decode_target(self, src_id: int) -> Optional[int]:
        """Least-loaded alive decode-role instance (any other alive peer if
        no decode-role instance survives; None means seat locally — the
        colocated fallback)."""
        cands = [i for i in self.instances
                 if i.alive and i.instance_id != src_id
                 and i.role != "prefill"]
        if not cands:
            cands = [i for i in self.instances
                     if i.alive and i.instance_id != src_id]
        if not cands:
            return None
        return min(cands,
                   key=lambda i: (len(i.requests), i.instance_id)).instance_id

    def _stage_handoffs(self, inst: RealInstance):
        """Stream ``inst``'s prefill output: every fully-covered prompt
        page written since the last pass is hosted on (and staged to) the
        decode target; a prompt whose final chunk just ran streams its
        whole remainder (partial tail page + hybrid blob included) and is
        marked ready to seat once those bytes land."""
        for h in inst.drain_ready_handoffs():
            rec = self._handoffs.setdefault(
                h["req"].rid, {"src": inst.instance_id, "dst": None,
                               "req": h["req"], "gen": 0, "inflight": 0})
            rec.update(refs=h["refs"], logits=h["logits"], final=True,
                       slot=h["slot"], ready_to_seat=False)
        for slot, job in list(inst.prefill_jobs.items()):
            rid = job["req"].rid
            if rid not in self._handoffs:
                self._handoffs[rid] = {
                    "src": inst.instance_id, "dst": None, "req": job["req"],
                    "gen": 0, "inflight": 0, "final": False}
        for rid, rec in list(self._handoffs.items()):
            if rec["src"] == inst.instance_id:
                self._stream_handoff(inst, rec)

    def _stream_handoff(self, inst: RealInstance, rec: dict):
        """Advance one handoff record: (re)pick the decode target, host +
        stage the pages that are ready but not yet hosted there, and flag
        the record seatable when the final message lands."""
        req = rec["req"]
        rid = req.rid
        if rec["dst"] is not None and not self.instances[rec["dst"]].alive:
            # decode target died before seating: hosted pages died with its
            # pool — re-target and re-stream from the source (which still
            # holds everything)
            rec.update(dst=None, inflight=0, ready_to_seat=False)
            rec["gen"] += 1
        if rec["dst"] is None:
            rec["dst"] = self._pick_decode_target(inst.instance_id)
        if rec["dst"] is None or rec["dst"] == inst.instance_id:
            # no peer to decode on: colocated fallback — the parked slot
            # seats right here once the final chunk has run
            if rec.get("final"):
                inst._seat(rec["slot"], req, rec["refs"], rec["logits"],
                           self.t)
                self.handoffs_seated += 1
                del self._handoffs[rid]
            return
        dst = self.instances[rec["dst"]]
        if rec.get("final"):
            refs, ready = rec["refs"], len(rec["refs"])
        else:
            job = inst.prefill_jobs.get(inst.slot_of(rid))
            if job is None:
                return
            refs, ready = job["refs"], job["pages_written"]
        pc = self.ecfg.prefix_cache
        reconcile_replica(inst.pool, dst.pool, inst.instance_id, rid,
                          refs[:ready], prefix_cache=pc)
        rtab = dst.pool.replica_table(inst.instance_id, rid)
        src_slots: List[int] = []
        dst_slots: List[int] = []
        shared_copies = 0
        if ready > len(rtab):
            grown = host_table_growth(inst.pool, dst.pool, inst.instance_id,
                                      rid, refs[:ready], prefix_cache=pc)
            if grown is None:
                return      # no headroom on the target yet; retry next step
            self._commit_shared_hostings(rec["dst"], grown)
            for s, d in grown.copies:
                src_slots.append(s)
                dst_slots.append(d)
            shared_copies = len(grown.copies)
            rtab = dst.pool.replica_table(inst.instance_id, rid)
        # fresh private hostings carry rref.replicated == False — the same
        # dirty walk replication uses picks exactly those up
        s, d = collect_dirty(dst.pool, refs[:ready], rtab, full=False,
                             prefix_cache=pc)
        src_slots += s
        dst_slots += d
        blob_src: List[int] = []
        blob_dst: List[int] = []
        if rec.get("final") and inst.family == "hybrid":
            if not dst.pool.host_blob_replica(inst.instance_id, rid):
                return      # retry next step; KV pages stay hosted
            rbref = dst.pool.blob_replica_ref(inst.instance_id, rid)
            bref = inst.pool.blob_ref(rid)
            if not rbref.replicated:
                blob_src.append(bref.slot)
                blob_dst.append(rbref.slot)
                bref.replicated = True
                rbref.replicated = True
        if src_slots or blob_src:
            gen = rec["gen"]

            def landed(rec=rec, gen=gen):
                if rec["gen"] == gen:
                    rec["inflight"] -= 1
            rec["inflight"] += 1
            self.transport.stage(
                "handoff", inst.instance_id, rec["dst"],
                (src_slots, dst_slots), (blob_src, blob_dst),
                shared_copies=shared_copies, on_shipped=landed)
        if rec.get("final") and len(rtab) == len(refs) and \
                (inst.family != "hybrid"
                 or dst.pool.blob_replica_ref(inst.instance_id, rid)):
            rec["ready_to_seat"] = True

    def _complete_handoffs(self):
        """Seat every handoff whose final pages have landed on a live
        decode target, then release the prefill side's parked slot (its
        pages stay warm in the source's prefix index)."""
        for rid, rec in list(self._handoffs.items()):
            if not (rec.get("ready_to_seat") and rec.get("inflight", 0) == 0):
                continue
            dst = self.instances[rec["dst"]]
            if not dst.alive:
                continue    # re-targeted by the next stream pass
            if not dst.seat_handoff(rec["src"], rec["req"]):
                continue    # no free slot on the target yet; retry
            self.handoffs_seated += 1
            src = self.instances[rec["src"]]
            if src.alive:
                src.finish_handoff(rid)
            del self._handoffs[rid]

    def disagg_stats(self) -> dict:
        """Disaggregation accounting: handoff stream traffic (same wire
        format as replication — check the bytes against block_nbytes) and
        seat/resume counts for the /health endpoint and the bench."""
        shipped = self.transport.shipped["handoff"]
        return {
            "enabled": self.ecfg.disaggregate,
            "roles": {i.instance_id: i.role for i in self.instances},
            "handoffs_in_flight": len(self._handoffs),
            "handoffs_seated": self.handoffs_seated,
            "handoff_streams_resumed": self.handoff_streams_resumed,
            "handoff_blocks_total": shipped.blocks,
            "handoff_blobs_total": shipped.blobs,
            "handoff_bytes_total": shipped.bytes,
            "handoff_shared_zero_copy_pages":
                self.repl_shared_refs_total - shipped.shared_copies
                - self.transport.shipped["repl"].shared_copies,
        }

    def replication_stats(self) -> dict:
        steps = max(self.repl_steps, 1)
        return {
            "mode": self.ecfg.replication if self.ecfg.replicate else "off",
            "blocks_total": self.repl_blocks_total,
            "blobs_total": self.repl_blobs_total,
            "bytes_total": self.repl_bytes_total,
            "blocks_per_step": self.repl_blocks_total / steps,
            "bytes_per_step": self.repl_bytes_total / steps,
            "blocks_per_request_step":
                self.repl_blocks_total / max(self.active_request_steps, 1),
            "blobs_per_request_step":
                self.repl_blobs_total / max(self.active_request_steps, 1),
            "retire_msgs_total": self.retire_msgs_total,
            "retires_per_request_step":
                self.retire_msgs_total / max(self.active_request_steps, 1),
            # replication load landing on degraded targets (placement
            # deprioritizes them, so this should hover near zero)
            "bytes_to_degraded":
                self.transport.shipped_degraded["repl"].bytes,
            "blocks_to_degraded":
                self.transport.shipped_degraded["repl"].blocks,
        }

    def prefix_stats(self) -> dict:
        """Prefix-cache effectiveness (bench_overhead's prefix section):
        hit rate over admitted prompt tokens, prefill compute actually run,
        CoW/eviction churn, and the shared-page replication dedup ratio
        (staged copies per distinct (target, chain key) hosting — 1.0
        means every shared page shipped exactly once per target)."""
        insts = self.instances
        total = sum(i.prefill_total_tokens for i in insts)
        compute = sum(i.prefill_compute_tokens for i in insts)
        cached = sum(i.prefix_cached_tokens for i in insts)
        return {
            "enabled": self.ecfg.prefix_cache,
            "prefill_total_tokens": total,
            "prefill_compute_tokens": compute,
            "prefix_cached_tokens": cached,
            "hit_rate": cached / max(total, 1),
            "lookups": sum(i.pool.prefix_lookups for i in insts),
            "interned_pages":
                sum(i.pool.prefix_interned_pages for i in insts),
            "hosted_pages": sum(i.pool.prefix_hosted_pages for i in insts),
            "evicted_pages":
                sum(i.pool.prefix_evicted_pages for i in insts),
            "cow_copies": sum(i.pool.cow_copies for i in insts),
            "shared_replica_refs": self.repl_shared_refs_total,
            "shared_replica_copies": self.repl_shared_copies_total,
            # denominator is the monotone hosting COUNTER, not the live key
            # set: a target that failed and rejoined re-hosts (and re-ships)
            # the same keys, and both sides of the ratio must see that
            "shared_page_ship_ratio":
                self.repl_shared_copies_total
                / max(self.repl_shared_hostings_total, 1),
        }

    def _handoffs_on_fail(self, instance_id: int, victims, resumed, event,
                          standard: bool):
        """Failover for in-flight prefill→decode handoffs.

        A dead DECODE target costs nothing: the source still holds every
        page, so the record re-targets and re-streams on the next pass. A
        dead PREFILL source resumes on the instance its stream already
        landed on — seated outright if the final chunk had arrived,
        otherwise prefill restarts from the last fully streamed page
        (chunk-aligned) instead of from token zero. Returns the victims
        list with handoff requests (handled here) removed."""
        handled = set()
        for rid, rec in list(self._handoffs.items()):
            if rec["dst"] == instance_id:
                rec.update(dst=None, inflight=0, ready_to_seat=False)
                rec["gen"] += 1
            if rec["src"] != instance_id:
                continue
            req = rec["req"]
            handled.add(rid)
            dst = None if rec["dst"] is None else self.instances[rec["dst"]]
            ok = False
            if not standard and dst is not None and dst.alive:
                if rec.get("ready_to_seat") and rec.get("inflight", 0) == 0:
                    ok = dst.seat_handoff(instance_id, req)
                    if ok:
                        self.handoffs_seated += 1
                else:
                    ok = dst.adopt_prefill_stream(instance_id, req)
                    if ok:
                        self.handoff_streams_resumed += 1
                if not ok:
                    dst.pool.drop_replica(instance_id, rid)
            if ok:
                resumed.append(rid)
                event["resumed"] += 1
            else:
                req.restart()
                req.state = RequestState.QUEUED
                event["restarted"] += 1
                self._route(req, front=True)
            del self._handoffs[rid]
        return [r for r in victims if r.rid not in handled]

    # -- unified fault entry points (instance- and shard-granularity) ----------
    def apply_fault(self, spec: FaultSpec) -> Optional[List[int]]:
        """THE fault entry point — instance kills and shard losses share
        this one code path (the HTTP layer's ``POST /v1/admin/fault`` maps
        straight onto it). Malformed specs raise ValueError here, before
        any state changes; ``if_busy`` specs no-op (return None) on an
        idle instance. Returns the rids that resumed seamlessly."""
        spec.validate(len(self.instances), self.ecfg.n_shards)
        if spec.if_busy and not self.instances[spec.instance_id].requests:
            return None
        with tracing.span("fault", instance=spec.instance_id,
                          granularity=spec.granularity) as sp:
            if spec.granularity == "shard":
                resumed = self._apply_shard_fault(spec.instance_id,
                                                  spec.shard_idx)
            else:
                resumed = self._apply_instance_fault(spec.instance_id)
            sp.set_metadata(resumed=len(resumed))
        return resumed

    def recover(self, spec: FaultSpec):
        """THE recovery entry point (``POST /v1/admin/recover``): instance
        granularity rebuilds the warm spare (``spec.shard_idx`` must be
        None), shard granularity restores a degraded instance's lost
        shards in place. State conflicts — rejoining an alive instance,
        restoring a non-degraded one — raise ValueError (HTTP 409)."""
        spec.validate(len(self.instances), self.ecfg.n_shards,
                      for_recover=True)
        # every rejoin comes through here, the planner's own included
        with tracing.span("recover", instance=spec.instance_id,
                          granularity=spec.granularity):
            if spec.granularity == "shard":
                return self._recover_shards(spec.instance_id)
            return self._recover_instance(spec.instance_id)

    def fail_instance(self, instance_id: int) -> List[int]:
        """Kill a whole instance (thin wrapper over ``apply_fault``)."""
        return self.apply_fault(
            FaultSpec(granularity="instance", instance_id=instance_id))

    def fail_shard(self, instance_id: int, shard_idx: int) -> List[int]:
        """Lose ONE shard of an instance (thin wrapper over
        ``apply_fault``): the instance degrades instead of dying."""
        return self.apply_fault(
            FaultSpec(granularity="shard", instance_id=instance_id,
                      shard_idx=shard_idx))

    def rejoin_instance(self, instance_id: int) -> RealInstance:
        """Warm-spare rejoin (thin wrapper over ``recover``)."""
        return self.recover(
            FaultSpec(granularity="instance", instance_id=instance_id))

    def rejoin_shards(self, instance_id: int) -> RealInstance:
        """Restore a degraded instance's lost shards (thin wrapper over
        ``recover``)."""
        return self.recover(
            FaultSpec(granularity="shard", instance_id=instance_id))

    def _apply_instance_fault(self, instance_id: int) -> List[int]:
        """Kill an instance and run the configured recovery policy.

        kevlarflow: in-flight requests resume from the replica blocks
        already hosted on the ring target (``promote_replica``), the dead
        instance's WAITING QUEUE drains onto the survivors (dynamic traffic
        rerouting — new arrivals and queued work keep flowing), and a warm
        spare is scheduled to rejoin after ``rejoin_delay``.

        standard: no replicas to promote — every victim restarts from
        scratch, and the whole group stalls for ``reload_penalty`` clock
        units (the classic full re-init with weight reload).

        Returns the rids that resumed seamlessly."""
        inst = self.instances[instance_id]
        if not inst.alive:
            return []      # already dead: idempotent (e.g. an HTTP retry) —
            #                re-processing would restart requests that now
            #                live on survivors and double-schedule the rejoin
        if self.clock is not None:
            # callable from outside the step loop (HTTP admin thread): the
            # last step's stamp may be stale on an idle engine, and the
            # stall/rejoin deadlines anchor on failure time
            self.t = self.clock()
        # async-replication barrier: the last step's staged delta must land
        # on the hosts before any replica is promoted or dropped, or
        # failover would resume from one-step-stale bytes. Copies INTO the
        # dying instance are dropped, not shipped — its pool is about to be
        # discarded, so those bytes never become real
        self.flush_replication(exclude=instance_id)
        standard = self.ecfg.recovery == "standard"
        victims = list(inst.requests.values())
        drained = self.queues[instance_id]
        self.queues[instance_id] = []
        inst.fail()
        # membership change: the view's epoch bump is what downstream
        # consumers (transport flush, placement, /health topology) key on
        self.control.view.mark_failed(instance_id)
        event = {"instance": instance_id, "granularity": "instance",
                 "shard_idx": None, "mode": self.ecfg.recovery,
                 "t_fail": self.t, "n_victims": len(victims),
                 "requeued": len(drained), "resumed": 0, "restarted": 0,
                 "t_rejoin": -1.0, "mttr": -1.0}
        self.failure_events.append(event)
        resumed = []
        if self._handoffs:
            victims = self._handoffs_on_fail(instance_id, victims, resumed,
                                             event, standard)
        restarted: List[Request] = []
        for req in victims:
            meta = self.replica_meta.pop(req.rid, None)
            target = None
            if meta is not None and self.instances[meta["home"]].alive:
                target = self.instances[meta["home"]]
            if not standard and target is not None and \
                    target.adopt_replica(meta["peer"], req, meta):
                resumed.append(req.rid)
                event["resumed"] += 1
            else:
                if target is not None:
                    target.pool.drop_replica(meta["peer"], req.rid)
                req.restart()
                req.state = RequestState.QUEUED
                event["restarted"] += 1
                restarted.append(req)
        # restarted victims requeue ahead of everything else, in their
        # ORIGINAL order: reversed front-insertion keeps request i ahead
        # of request j (i admitted first) whether they land on a survivor
        # queue or — when this was the last alive instance — in the
        # arrival buffer, where per-request front-inserts used to reverse
        # them
        for req in reversed(restarted):
            self._route(req, front=True)
        # the dead instance's queued (never-admitted) work reroutes to the
        # survivors behind the restarted victims, ahead of future arrivals
        for req in drained:
            self._route(req)
        # replicas the dead instance hosted for others are gone: mark those
        # primaries dirty so the next pass re-replicates to a new target
        for other in self.instances:
            if not other.alive:
                continue
            for rid in other.requests:
                meta = self.replica_meta.get(rid)
                if meta is not None and meta["home"] == instance_id:
                    self.replica_meta.pop(rid)
                    for ref in other.pool.table(rid):
                        ref.replicated = False
                    other.pool.mark_blob_dirty(rid)
        # the dead pool's interned pages died with it: forget its hosting
        # keys so a re-host after rejoin counts as a fresh hosting AND a
        # fresh copy — the ship-ratio denominator tracks live state instead
        # of drifting across failure cycles
        self._shared_hosted_keys = {
            (t, k) for (t, k) in self._shared_hosted_keys
            if t != instance_id}
        if standard:
            # classic fault path: the group re-initializes together —
            # nothing serves until the weights are back
            self.stall_until = self.t + self.ecfg.reload_penalty
        if self.ecfg.auto_rejoin:
            delay = self.ecfg.reload_penalty if standard \
                else self.ecfg.rejoin_delay
            self.control.planner.on_failure(instance_id, self.t,
                                            rejoin_at=self.t + delay,
                                            kind="instance")
        else:
            # manual recovery: recorded (it shows in /health's plan) but
            # never scheduled — an admin rejoin_instance clears it
            self.control.planner.on_failure(instance_id, self.t,
                                            kind="instance")
        return resumed

    def _apply_shard_fault(self, instance_id: int,
                           shard_idx: int) -> List[int]:
        """Lose ONE tensor-parallel shard: the instance DEGRADES instead
        of dying (FailSafe, paper's partial-fault premise). The surviving
        slice keeps serving — params/KV re-lay per
        ``sharding.degraded_spec`` (the layout summary lands on the
        instance and in /health), slot capacity drops to the surviving
        fraction, and only the EXCESS in-flight requests migrate (replica
        promotion on the ring target, byte-identical; restart fallback
        otherwise). The ClusterView marks the instance DEGRADED with its
        own epoch bump, so placement stops preferring it as a replica
        host and routing discounts it. Under ``standard`` recovery — or
        when this is the LAST surviving shard — the fault escalates to
        whole-instance failure: degraded serving is the kevlarflow
        capability. Returns the rids that resumed seamlessly."""
        inst = self.instances[instance_id]
        if not inst.alive:
            raise ValueError(
                f"instance {instance_id} is dead — recover it at instance "
                "granularity before injecting shard faults")
        if shard_idx in inst.lost_shards:
            return []      # idempotent retry (e.g. an HTTP retry)
        if self.ecfg.recovery == "standard" or \
                len(inst.lost_shards) + 1 >= inst.n_shards:
            return self._apply_instance_fault(instance_id)
        if self.clock is not None:
            self.t = self.clock()       # admin-thread call (see above)
        # async-replication barrier: the last step's staged deltas must
        # land on the ring hosts before any excess victim is migrated off
        # its promoted replica — same rule as whole-instance failover
        self.flush_replication()
        victims = inst.degrade(shard_idx)
        inst.degraded_layout = self._degradation_layout(inst.lost_shards)
        # degradation is a topology change: its own epoch bump re-derives
        # placement (healthy-preferred ring) and routing (load discount)
        self.control.view.mark_degraded(instance_id, shard_idx)
        event = {"instance": instance_id, "granularity": "shard",
                 "shard_idx": shard_idx, "mode": self.ecfg.recovery,
                 "t_fail": self.t, "n_victims": len(victims),
                 "requeued": 0, "resumed": 0, "restarted": 0,
                 "t_rejoin": -1.0, "mttr": -1.0}
        self.failure_events.append(event)
        # in-flight handoff streams keep their parked prefill slot — the
        # shards serving the stream survived; only seated work re-seats
        victims = [r for r in victims if r.rid not in self._handoffs]
        resumed: List[int] = []
        restarted: List[Request] = []
        for req in victims:
            meta = self.replica_meta.pop(req.rid, None)
            # the pool SURVIVES a shard loss: the seat frees cleanly (no
            # lost bytes) before the request resumes elsewhere
            inst.release(req.rid)
            target = None
            if meta is not None and self.instances[meta["home"]].alive:
                target = self.instances[meta["home"]]
            if target is not None and \
                    target.adopt_replica(meta["peer"], req, meta):
                resumed.append(req.rid)
                event["resumed"] += 1
            else:
                if target is not None:
                    target.pool.drop_replica(meta["peer"], req.rid)
                req.restart()
                req.state = RequestState.QUEUED
                event["restarted"] += 1
                restarted.append(req)
        for req in reversed(restarted):
            self._route(req, front=True)
        if self.ecfg.auto_rejoin:
            self.control.planner.on_failure(
                instance_id, self.t,
                rejoin_at=self.t + self.ecfg.rejoin_delay, kind="shard")
        else:
            self.control.planner.on_failure(instance_id, self.t,
                                            kind="shard")
        return resumed

    # lazy caches for the degradation layout (one eval_shape per engine)
    _params_struct = None
    _cache_struct = None
    _shard_mesh = None

    def _degradation_layout(self, lost_shards) -> dict:
        """The sharding story of serving on the surviving slice, computed
        through the production rules in ``distributed/sharding.py``: specs
        re-derived against a mesh whose model axis shrank to the surviving
        shard count, replicate-fallback wherever divisibility broke."""
        if self._shard_mesh is None:
            self._shard_mesh = SH.abstract_mesh(
                (1, self.ecfg.n_shards), ("data", "model"))
            self._params_struct = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                self.params)
            self._cache_struct = jax.eval_shape(
                lambda: api.init_cache(self.cfg, self.ecfg.max_slots,
                                       self.ecfg.max_seq))
        return SH.degradation_summary(
            self._params_struct, self._shard_mesh, lost_shards,
            cache_shape=self._cache_struct, arch_type=self.cfg.arch_type)

    def _recover_shards(self, instance_id: int) -> RealInstance:
        """Shard rejoin: restore the full spec and full slot capacity in
        place — nothing about the surviving-shard state changes, so every
        request that rode out the degradation resumes byte-identically.
        The flush barrier mirrors the fault side: the epoch bump below
        re-targets the ring, and staged copies must land against the
        topology they were staged under."""
        inst = self.instances[instance_id]
        if not inst.alive:
            raise ValueError(
                f"instance {instance_id} is dead — recover it at instance "
                "granularity")
        if not inst.lost_shards:
            raise ValueError(f"instance {instance_id} is not degraded")
        if self.clock is not None:
            self.t = self.clock()
        self.flush_replication()
        inst.restore_shards()
        self.control.view.mark_restored(instance_id)
        self.control.planner.on_rejoined(instance_id, self.t)
        # every open shard event closes: the restore brings back ALL lost
        # shards at once
        for event in self.failure_events:
            if event["instance"] == instance_id and \
                    event.get("granularity") == "shard" and \
                    event["t_rejoin"] < 0:
                event["t_rejoin"] = self.t
                event["mttr"] = self.t - event["t_fail"]
        return inst

    def _recover_instance(self, instance_id: int) -> RealInstance:
        """Warm-spare rejoin (decoupled init, paper Sec 3.2 mechanism #1):
        rebuild the failed instance around the node-resident weights and the
        engine's shared compiled programs — no weight reload, no recompile —
        and re-enter the LB group and the replication ring. Live traffic on
        the survivors is untouched; the next ``_replicate`` pass re-hosts
        against the new ring topology."""
        if self.instances[instance_id].alive:
            raise ValueError(f"instance {instance_id} is alive")
        if self.clock is not None:
            self.t = self.clock()       # admin-thread call: stamp MTTR now
        # barrier before the instance object (and its pool) is replaced —
        # staged copies must never resolve against the fresh pool's slots
        self.flush_replication()
        self.control.planner.on_rejoined(instance_id, self.t)
        inst = RealInstance(self.cfg, self.params, self.ecfg, instance_id,
                            executor=self.executor, clock=self.clock,
                            role=self.roles[instance_id])
        self.instances[instance_id] = inst
        self.queues[instance_id] = []
        # back in the membership AFTER the flush barrier: staged copies
        # toward the dead incarnation were dropped, not seated in the
        # fresh pool; the epoch bump re-targets the ring for survivors
        self.control.view.mark_alive(instance_id)
        # fresh pool, no hosted keys (defensive: fail_instance pruned these)
        self._shared_hosted_keys = {
            (t, k) for (t, k) in self._shared_hosted_keys
            if t != instance_id}
        for event in reversed(self.failure_events):
            if event["instance"] == instance_id and \
                    event.get("granularity", "instance") == "instance" and \
                    event["t_rejoin"] < 0:
                event["t_rejoin"] = self.t
                event["mttr"] = self.t - event["t_fail"]
                break
        # parked arrivals (possible while NO instance was alive) flow again
        while self.waiting:
            self._route(self.waiting.pop(0))
        return inst

    def mttr_events(self) -> List[dict]:
        """Completed failure->rejoin cycles (mttr in engine clock units)."""
        return [e for e in self.failure_events if e["mttr"] >= 0]

    def run(self, max_iters: int = 1000):
        while self.has_pending() and max_iters > 0:
            self.step()
            max_iters -= 1
        return self.done
