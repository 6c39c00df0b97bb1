"""Staged block/blob transport between instance pools.

One channel abstraction carries BOTH inter-instance byte streams the engine
owns — ring KV replication and the prefill→decode handoff stream
(``EngineConfig.disaggregate``) — because they are the same wire format:
paged KV blocks (int8 payload + scales on a quantized pool) and hybrid
RG-LRU state blobs, addressed by pool slot.

The channel is the async double-buffer extracted from
``RealEngine._stage_replication`` / ``flush_replication``:

  * ``stage`` records a copy job (metadata only — slot id lists) tagged
    with its kind (``"repl"`` | ``"handoff"``) at the end of step N;
  * ``flush`` ships every staged job at the top of step N+1 (or at the
    fail/rejoin barrier), overlapping the copies with that step's compute.

Byte accounting is split by when the bytes become REAL:

  * ``staged[kind]`` tallies at stage time — what the engine *intended*
    to ship (the overhead bench's per-step staging cost);
  * ``shipped[kind]`` tallies at flush time, and ONLY for jobs whose
    target is still alive — a job whose target died between stage and
    flush lands in ``dropped[kind]`` instead. Totals the benches gate on
    (``repl_bytes_total``) read the shipped tally, so they can never
    over-count bytes that never landed.

Replica-table hosting (including the shared-page dedup path through
``PagedKVPool.host_shared_block``) lives here too, as ``host_table_growth``:
it grows the target's hosted table to cover the source table and is
ALL-OR-NOTHING — if the target runs out of headroom mid-request, every
hosting this call made is rolled back (shared pages deref'd, pages interned
by this very call fully evicted so no future lookup can attach a page whose
bytes never shipped, private slots freed) and the caller simply retries next
pass. Nothing is ever left half-staged.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax

from repro.serving import tracing

KINDS = ("repl", "handoff")


@dataclasses.dataclass
class Tally:
    """Byte/message accounting for one (kind, outcome) bucket."""
    msgs: int = 0
    blocks: int = 0
    blobs: int = 0
    bytes: int = 0
    shared_copies: int = 0

    def add(self, msg: dict):
        self.msgs += 1
        self.blocks += len(msg["blocks"][0])
        self.blobs += len(msg["blobs"][0])
        self.bytes += msg["nbytes"]
        self.shared_copies += msg["shared_copies"]


@dataclasses.dataclass
class Growth:
    """Result of one all-or-nothing ``host_table_growth`` call. Carries
    enough to undo itself: the caller rolls back when a LATER per-request
    hosting step fails (e.g. no blob headroom on a hybrid), so the
    request's staging stays all-or-nothing end to end."""
    copies: List[tuple]            # (src_slot, dst_slot) shared pages to ship
    shared_keys: List[bytes]       # chain key per shared-page hosting
    n_hosted: int = 0              # blocks this call appended to the table
    fresh_keys: List[bytes] = dataclasses.field(default_factory=list)
    flag_saves: List[tuple] = dataclasses.field(default_factory=list)

    def rollback(self, dst_pool, peer: int, rid: int):
        """Undo every hosting this growth made: shared pages deref'd
        (pages interned BY this growth — bytes never shipped — are fully
        evicted), private slots freed, source dirty flags restored."""
        dst_pool.unhost_tail(peer, rid, self.n_hosted,
                             fresh_keys=self.fresh_keys)
        for ref, prior in self.flag_saves:
            ref.replicated = prior
        self.copies.clear()
        self.shared_keys.clear()
        self.n_hosted = 0


class TransportChannel:
    """Double-buffered block/blob transport over a live instance list.

    ``instances`` is the engine's OWN list (not a copy): a rejoin that
    replaces an instance object is visible to the next flush, and a dead
    target is skipped — its hosted slots died with its pool, so shipping
    would scribble on a future pool's blocks.

    Target liveness resolves through the control plane's ``ClusterView``
    when one is supplied (``view.is_alive`` — the membership truth the
    engine updates in the same breath it fails/replaces an instance);
    without a view it falls back to the instance objects' own flags, so
    the channel still works standalone in tests.
    """

    def __init__(self, instances: list, view=None):
        self.instances = instances
        self.view = view
        self.pending: List[dict] = []
        self.staged: Dict[str, Tally] = {k: Tally() for k in KINDS}
        self.shipped: Dict[str, Tally] = {k: Tally() for k in KINDS}
        self.dropped: Dict[str, Tally] = {k: Tally() for k in KINDS}
        # subset of shipped: jobs whose target was serving DEGRADED at
        # flush time (shard loss). Placement deprioritizes degraded ring
        # targets, so this tally should stay near zero — /health surfaces
        # it as the residual replication load a degraded instance carries
        self.shipped_degraded: Dict[str, Tally] = {k: Tally() for k in KINDS}

    def stage(self, kind: str, src_id: int, dst_id: int, blocks, blobs,
              shared_copies: int = 0, on_shipped=None) -> dict:
        """Queue one copy job: ``blocks``/``blobs`` are (src_slots,
        dst_slots) pairs addressing the source / target pools.
        ``on_shipped`` (if given) fires when the job's bytes actually land
        — never when the job is dropped for a dead target."""
        src_pool = self.instances[src_id].pool
        msg = {"src": src_id, "dst": dst_id,
               "blocks": blocks, "blobs": blobs,
               "kind": kind, "shared_copies": shared_copies,
               "nbytes": len(blocks[0]) * src_pool.block_nbytes
               + len(blobs[0]) * src_pool.blob_nbytes,
               "on_shipped": on_shipped}
        self.pending.append(msg)
        self.staged[kind].add(msg)
        return msg

    def flush(self, block: bool = False, exclude: Optional[int] = None):
        """Ship every staged job now — the double-buffer's barrier.

        A job whose target died since staging (or whose target is
        ``exclude`` — the instance a failover is about to kill) is dropped
        and accounted as such: its bytes never land, so they never count
        toward the shipped totals."""
        pending, self.pending = self.pending, []
        shipped = []
        nbytes = 0
        with tracing.span("transport.flush") as sp:
            for msg in pending:
                dst = self.instances[msg["dst"]]
                dst_alive = (self.view.is_alive(msg["dst"])
                             if self.view is not None else dst.alive)
                if not dst_alive or msg["dst"] == exclude:
                    self.dropped[msg["kind"]].add(msg)
                    continue
                src = self.instances[msg["src"]]
                src.pool.copy_blocks_to(dst.pool, *msg["blocks"])
                src.pool.copy_blobs_to(dst.pool, *msg["blobs"])
                self.shipped[msg["kind"]].add(msg)
                if self.view is not None and \
                        self.view.is_degraded(msg["dst"]):
                    self.shipped_degraded[msg["kind"]].add(msg)
                if msg["on_shipped"] is not None:
                    msg["on_shipped"]()
                shipped.append(dst)
                nbytes += msg["nbytes"]
            if block and shipped:
                jax.block_until_ready([d.pool.k for d in shipped])
            sp.set_metadata(jobs=len(shipped), bytes=nbytes,
                            dropped=len(pending) - len(shipped))


def reconcile_replica(src_pool, dst_pool, peer: int, rid: int, table,
                      prefix_cache: bool):
    """Drop a hosted table that drifted out of lockstep with the live one:
    the ring target changed after a failure, or copy-on-write turned a
    shared page private since hosting. The caller re-hosts the current
    window with matching sharedness."""
    rtab = dst_pool.replica_table(peer, rid)
    if any(a.logical_idx != b.logical_idx
           or (prefix_cache and src_pool.prefix_key_of(a.slot)
               != dst_pool.prefix_key_of(b.slot))
           for a, b in zip(table, rtab)):
        dst_pool.drop_replica(peer, rid)


def host_table_growth(src_pool, dst_pool, peer: int, rid: int, table,
                      prefix_cache: bool) -> Optional[Growth]:
    """Grow dst_pool's hosted table for (peer, rid) to cover ``table``.

    Shared prefix pages go through ``host_shared_block`` — the target
    interns them in ITS OWN index keyed by chain hash, so bytes ship only
    if no page with that key is already resident there (at most once per
    target, however many requests reference it). Private pages reserve a
    fresh hosted slot each (``rref.replicated`` False → the caller's dirty
    walk ships their bytes).

    ALL-OR-NOTHING: returns the Growth on success; on target-headroom
    exhaustion every hosting this call made is rolled back (leaving the
    table exactly as found) and None is returned — the caller retries next
    pass. Without the rollback a bail mid-request left shared pages
    refcounted and queued to ship while ``replica_meta`` was never written,
    so failover restarted a request whose pages had partially landed.
    """
    rtab = dst_pool.replica_table(peer, rid)
    grown = Growth(copies=[], shared_keys=[])
    target = len(table) - len(rtab)
    for ref in table[len(rtab):]:
        key = src_pool.prefix_key_of(ref.slot) if prefix_cache else None
        if key is not None:
            res = dst_pool.host_shared_block(
                peer, rid, src_pool.prefix_index[key], ref.logical_idx)
            if res is None:
                break
            rref, needs_copy = res
            grown.shared_keys.append(key)
            if needs_copy:
                grown.copies.append((ref.slot, rref.slot))
                grown.fresh_keys.append(key)
            grown.flag_saves.append((ref, ref.replicated))
            ref.replicated = True
            rref.replicated = True
        elif not dst_pool.host_replica(peer, rid, 1,
                                       first_logical=ref.logical_idx):
            break
        grown.n_hosted += 1
    if grown.n_hosted == target:
        return grown
    grown.rollback(dst_pool, peer, rid)
    return None


def collect_dirty(dst_pool, table, rtab, full: bool, prefix_cache: bool):
    """Walk a (primary, hosted) table pair and pick the blocks whose bytes
    must ride the wire: primary dirty since the last pass, or hosted slot
    never filled (fresh hosting). Immutable shared pages ship at host time
    only — never per referencing request, even in full mode. Marks both
    sides replicated; returns (src_slots, dst_slots)."""
    src_slots, dst_slots = [], []
    for ref, rref in zip(table, rtab):
        if prefix_cache and dst_pool.prefix_key_of(rref.slot) is not None:
            continue
        if full or not ref.replicated or not rref.replicated:
            src_slots.append(ref.slot)
            dst_slots.append(rref.slot)
            ref.replicated = True
            rref.replicated = True
    return src_slots, dst_slots
