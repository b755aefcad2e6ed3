"""Chipmink: the object store (paper §3.1 user API + full save/load flow).

    save(state) -> TimeID
    load(names, time_id) -> {name: value}

A save runs the paper's pipeline: build the ObjectGraph → active-variable
filter → change detection (device fingerprints) → podding (LGA) → pod
digests → thesaurus lookup (synonyms) → write dirty pods + manifest.
A load reverses it: manifest → resolve pods (synonyms are content-addressed)
→ unpod only what the requested names reach (partial loading).

Incremental save pipeline (contract)
------------------------------------
With ``incremental=True`` (default) the host-side half of a save scales
with the *delta*, mirroring the device half's batched digest engine:

  * **Stable node ids** — `GraphCache` re-walks only changed subtrees and
    splices reused nodes; a key whose node survives keeps its id, so the
    previous `PodAssignment` (keyed by node id) stays addressable.
  * **Memo-local preservation** — when the build reports zero structural
    changes, the previous assignment (pods, locals, pages, edges) is
    reused verbatim: every untouched pod keeps its memo locals bit-exact,
    and only pods containing dirty chunks or changed scalars re-hash
    their structural digest.  Any structural change falls back to the
    full LGA walk, which — thanks to per-key decision memoization (§7.3)
    — is itself the parity oracle: from-scratch and incremental saves
    produce bit-identical pod bytes and manifests (modulo the timing
    stats block).
  * **Snapshot-before-overlap** — `save()` builds the graph (and thereby
    captures host scalar values and device array references) on the
    *caller's* thread before the body is enqueued.  jax.Arrays are
    immutable, so those references are the snapshot; host-mutable numpy
    leaves must not be mutated in place until `wait()` returns (same
    rule as the paper's l_active discipline).  With that snapshot taken,
    the async saver (depth 2) no longer joins the previous save: save
    N's decide/gather/write overlaps step N+1's compute, and save bodies
    retire strictly FIFO so cross-save state (digest table, previous
    assignment, thesaurus) is race-free.  Thesaurus/store mutation is
    additionally serialized under the namespace lock ``l_ns``.

Single-sync save (``fused=True``, default)
------------------------------------------
The device half of a save is one round-trip: the fused digest kernels
compare against the device-resident previous table and the packed word
rows of *speculated* chunks (flip-EMA above ``spec_threshold``, expanded
to pod granularity plus the pods of changed scalars) ride along in the
same `jax.device_get` as the digests and dirty bitmask.  The gather
phase then serializes written pods from those prefetched bytes; only
mispredicted-dirty chunks pay one corrective batched fetch
(``n_corrective_syncs``), so a warm sparse save costs exactly one
blocking device sync and any save at most two.  ``fused=False``
restores the two-sync path (digest fetch + payload gather); manifests
are bit-identical either way.

Ablation switches (`enable_cd`, `enable_avf`, `async_mode`) exist to
reproduce the paper's §8.8/§8.9 baselines (NoCD/AVF, OnlyCD, OnlyAVF,
Sync); `incremental=False` restores the from-scratch host path.

Delta-chain pod storage (``delta_chains=True``)
-----------------------------------------------
When a reuse-path save re-serializes a pod that the detector mask shows
changed in only a few chunks (or scalars), the pod can be stored as a
chunk-granular binary delta against its parent-commit pod instead of
whole (`core/delta.py`).  The patch set comes for free: under
assignment reuse with no structural change, only CHUNK entries in
``report.dirty`` and SCALAR entries in ``scalar_changed_keys`` can
differ from the parent blob, so those entry indices ARE the delta.  A
per-pod cost model (`DeltaPolicy`) admits the delta only when its bytes
plus an expected chain-reconstruction charge beat the whole blob, and
never past ``max_chain_depth`` links from a whole base.  A pod stored
as a delta records its base in the manifest as
``pods[pid]["delta_of"] = <parent digest hex>``; the digest still names
the *full* content, and `BaseStore.get_pod` reconstructs it
transparently (chain walk + patch replay), so checkouts are
bit-identical to the whole-pod oracle (``delta_chains=False``).  GC
re-materializes live delta descendants before sweeping their base, and
fsck validates/repairs chains (see the storage contract in
`core/store.py`).  Per-save stats: ``n_delta_pods``,
``t_delta_encode``, ``chain_depth_max``.  Default off: the from-scratch
oracle never reuses assignments, so parity-tested manifests stay free
of storage-form fields unless explicitly opted in.

Versioning contract (repro.version)
-----------------------------------
Every save is a *commit*: its manifest records the parent TimeID (by
default the current HEAD — pass ``parent=`` to override), a commit DAG
with named branch refs / tags / HEAD persists alongside the store
(`store.put_meta("refs")`), and the chunk-digest table of the save is
embedded in the manifest (``"chunks"``) so a later checkout can prime
change detection without re-fingerprinting.  The surface mirrors git:

  * ``branch(name)`` forks at HEAD and switches to the new branch;
    subsequent saves advance it.  ``tag(name)`` pins a commit.
  * ``checkout(ref)`` restores a branch/tag/TimeID **delta-aware**: pods
    whose digest matches the live in-memory state are re-serialized from
    memory, so store reads scale with the branch delta, not model size.
    Checkout then primes `GraphCache`, the `ChangeDetector` table, and
    the committed `PodAssignment`, so the very next ``save()`` runs the
    incremental path (``n_pods_reused > 0``) instead of a from-scratch
    fallback.  Checkout drains in-flight async saves first; the delta
    path assumes the tracked state was not mutated in place since the
    last save (the l_active discipline).
  * ``gc()`` mark-and-sweeps pods/manifests unreachable from any branch,
    tag, or HEAD (dry-run supported; the in-memory HEAD is always a
    root).  Swept digests are pruned from the thesaurus so a future save
    that recreates identical content rewrites the pod instead of
    aliasing a deleted blob.
  * ``log()`` / ``diff(a, b)`` answer lineage and pod-granular deltas.

Copy-on-submit: with ``async_mode=True``, host-mutable numpy leaves no
larger than ``copy_on_submit_bytes`` (default 1 MiB) are snapshotted on
the caller's thread at ``save()`` time (counted in ``n_leaf_copies``),
so in-place mutation of small host state (counters, cursors, norm stats)
before ``wait()`` can no longer corrupt an in-flight save.  Larger numpy
leaves keep the must-not-mutate-before-wait rule; jax.Arrays were always
immune.

Durability & recovery contract
------------------------------
Every save is a transaction with a strictly ordered commit protocol:

  1. **pods** — content-addressed payload blobs.  Each is written
     tmp-file + atomic rename on the file backend; a crash mid-pod
     leaves only a ``.tmp`` orphan, never a half blob at a live address.
  2. **manifest** — one atomic write naming every pod digest.  This is
     the commit point for the *data*: once the manifest exists and all
     its pods exist, the commit is complete and loadable.
  3. **refs** — the commit DAG advances HEAD/branch via compare-and-swap
     on the refs meta blob (`BaseStore.compare_and_put_meta`).  This is
     the commit point for *visibility*; concurrent writers rebase and
     retry on conflict, so no mutation is ever silently clobbered.

A crash between any two steps leaves the store recoverable: debris from
step 1 is invisible (content addressing dedups or ignores it), a
dangling step-2 manifest is unreachable until GC sweeps it, and refs
always name a commit that finished step 2.  ``fsck_on_open`` (default
True) runs `repro.version.fsck` before the first save of a reopened
store: it classifies torn saves, rolls refs back to the newest complete
commit, sweeps debris, and — pass ``fsck_on_open="deep"`` — validates
every pod byte-level (required after a crash on a backend without
atomic renames, since a torn pod squats on a content address future
saves would dedup against).  `Chipmink.fsck()` reruns it on demand,
pruning swept digests from the thesaurus.

Transient I/O faults (`OSError`) in the write phase retry with
exponential backoff under ``retry_policy`` (default: 3 retries); the
per-save retry count lands in ``save_stats[-1]["n_retries"]``.  The
write → manifest → refs steps are individually idempotent, so a retried
step never double-applies.  Durability on the file backend is opt-in:
``FileStore(root, fsync=True)`` fsyncs data + directory around every
rename (the paper's workloads prefer throughput; crash-*consistency* —
never serving a torn commit — holds either way).

Multi-writer contract (leases & fencing)
----------------------------------------
The protocol above is crash-safe but single-writer: two processes on one
store race TimeID allocation, and a concurrent GC can sweep pods a save
has written (or is about to dedup against) before their manifest lands.
``multi_writer=True`` layers `core.lease` on the same CAS primitive:

  * the instance holds a shared **writer lease** (TTL ``lease_ttl_s``,
    renewed by a heartbeat thread unless ``lease_heartbeat=False``, and
    inline at every save);
  * TimeIDs come from a CAS counter meta blob, so concurrent writers
    never mint the same commit id;
  * step 0 of every save — before pods are written and before dedup is
    trusted — registers a **save intent** (the TimeID, its parent, and
    every digest the manifest will reference) under the lease.  GC pins
    intent-held
    tids/digests; aliased pods are re-verified (``has_pod``) after the
    intent lands and rewritten if a pre-intent sweep removed them
    (``n_alias_rewrites`` in save stats);
  * the refs CAS is **fenced**: the writer re-validates its lease
    immediately before step 3 and aborts with `LeaseLost` if it was
    reaped or taken over — a paused/partitioned writer can never publish
    a commit whose pods a fenced GC already swept;
  * ``gc()`` runs under the exclusive gc lease with the sweep-phase
    fence (see version/gc.py), and ``fsck()`` reaps dead writers'
    expired leases while honoring live peers' intents.

Everything is keyed off the one `compare_and_put_meta` primitive, so the
contract holds on any backend that has it (both built-ins do).  With the
default ``multi_writer=False`` no lease traffic exists and the PR-6
single-writer behavior is byte-identical.  ``close()`` drains the async
pipeline and releases the lease so peers need not wait out the TTL.

Multi-tenant sessions & refcount GC (``refcounts=True``)
--------------------------------------------------------
The fleet-serving scenario (`repro.sessions.SessionService`) multiplexes
thousands of session branches onto one store, which changes what GC must
cost: evicting ONE idle session cannot pay a full mark-and-sweep of the
whole store.  Three hooks make that path O(session delta):

  * ``save(state, branch="sessions/<id>", parent=<tip>)`` commits onto a
    named ref without moving this instance's HEAD — the DAG's
    `record(branch=)` create-or-advance path, so interleaved saves from
    many sessions share one instance (the service swaps the per-session
    detector/cache state around each call).
  * ``refcounts=True`` maintains `repro.version.refcount.RefcountIndex`
    in store meta (key ``pod_refcounts``) through the same
    `compare_and_put_meta` CAS as refs/leases: per-pod manifest
    refcounts, per-commit child counts, and physical delta-chain links,
    updated inside the commit step (manifest put → **record_commit** →
    refs CAS; idempotent per TimeID, so the retried commit unit never
    double-counts).
  * ``evict_branch(name)`` deletes the ref and immediately reclaims its
    exclusive commits/pods via `refcount_reclaim` — a first-parent walk
    from the dead tip that stops at the fork back into surviving
    history, **bit-identical in what it frees to a full mark-and-sweep
    of the same store** (the tested contract; mark-and-sweep stays on as
    the fsck-time oracle and `fsck` rebuilds the index after repairs).
    ``gc()`` with refcounts on drains the backlog of plain
    `delete_branch` tips the same way; ``gc(full=True)`` forces the
    mark-and-sweep oracle and trues the index up afterwards.
  * ``shared_tids=True`` routes TimeID allocation through the CAS
    counter even in single-writer mode — required when a *pool* of
    instances shares one store without the full lease protocol (the
    session service's configuration), since two local counters would
    mint colliding commit ids.

Large host leaves in async mode: copy-on-submit snapshots only leaves ≤
``copy_on_submit_bytes``, so a larger writable numpy leaf still carries
the must-not-mutate-before-`wait()` rule.  ``large_leaf_action``
(default ``"warn"``) surfaces that footgun per offending leaf —
``"raise"`` makes it an error, ``"ignore"`` restores the silent
pre-PR-10 behavior, and ``copy_on_submit_bytes=0`` (the explicit
copy-off opt-out) disables the guard with the copies.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time as _time
import warnings
from typing import Any, Callable, Dict, Iterable, List, Optional, Set

import msgpack
import numpy as np

from .active_filter import ActiveVariableFilter
from .async_saver import AsyncSaver, QueueTimes
from .change_detector import ChangeDetector, pack_digest_table
from .delta import DeltaPolicy, encode_pod_delta
from .faults import RetryPolicy, call_with_retries
from .graph import CHUNK, ObjectGraph, build_graph, rebuild_tree
from .graph_cache import GraphCache, IncrementalBuildInfo
from .lease import Lease, LeaseHeartbeat, LeaseLost, LeaseManager
from .lga import LGA, PoddingPolicy
from .phases import Phases
from .podding import (PodAssignment, Unpodder, batched_chunk_fetch,
                      fused_chunk_fetch, open_manifest, pod_graph,
                      pod_structural_digest, serialize_pod)
from .store import BaseStore, MemoryStore
from .thesaurus import PodThesaurus
from .volatility import FlipTracker

TimeID = int

#: meta blob holding the next unissued TimeID (multi-writer mode only):
#: a CAS counter, so concurrent writers never mint the same commit id.
TID_COUNTER_META_KEY = "tid_counter"


class Chipmink:
    def __init__(
        self,
        store: Optional[BaseStore] = None,
        policy: Optional[PoddingPolicy] = None,
        *,
        chunk_bytes: int = 1 << 22,
        thesaurus_capacity: int = 1 << 30,
        memo_page_size: int = 1024,
        use_kernel: bool = True,
        enable_cd: bool = True,
        enable_avf: bool = True,
        async_mode: bool = False,
        async_depth: int = 2,
        incremental: bool = True,
        fused: bool = True,
        spec_threshold: float = 0.25,
        track_flips: bool = True,
        copy_on_submit_bytes: int = 1 << 20,
        seed: int = 0,
        retry_policy: Optional[RetryPolicy] = None,
        fsck_on_open: Any = True,
        multi_writer: bool = False,
        lease_ttl_s: float = 10.0,
        lease_heartbeat: bool = True,
        max_refs_cas_retries: Optional[int] = None,
        refs_cas_backoff: Optional[RetryPolicy] = None,
        delta_chains: bool = False,
        delta_policy: Optional[DeltaPolicy] = None,
        refcounts: bool = False,
        shared_tids: bool = False,
        large_leaf_action: str = "warn",
    ) -> None:
        self.store = store if store is not None else MemoryStore()
        self.policy = policy if policy is not None else LGA()
        self.chunk_bytes = chunk_bytes
        self.memo_page_size = memo_page_size
        self.enable_cd = enable_cd
        self.enable_avf = enable_avf
        self.async_mode = async_mode
        self.incremental = incremental
        self.detector = ChangeDetector(chunk_bytes=chunk_bytes, seed=seed,
                                       use_kernel=use_kernel, fused=fused)
        self.fused = self.detector.fused
        self.spec_threshold = spec_threshold
        self.thesaurus = PodThesaurus(capacity_bytes=thesaurus_capacity)
        self.tracker = FlipTracker() if track_flips else None
        self.avf = ActiveVariableFilter()
        self.saver = AsyncSaver(depth=async_depth)
        self._graph_cache = (GraphCache(chunk_bytes=chunk_bytes)
                             if incremental else None)
        self.copy_on_submit_bytes = copy_on_submit_bytes
        if large_leaf_action not in ("warn", "raise", "ignore"):
            raise ValueError(
                f"large_leaf_action must be 'warn', 'raise' or 'ignore', "
                f"got {large_leaf_action!r}")
        self.large_leaf_action = large_leaf_action
        self._large_leaves_warned: Set[str] = set()
        self._prev_pods: Optional[PodAssignment] = None
        self._prev_graph: Optional[ObjectGraph] = None
        self._pod_digests: Dict[int, bytes] = {}   # prev save's pod digests
        self.delta_chains = delta_chains
        self.delta_policy = (delta_policy if delta_policy is not None
                             else DeltaPolicy())
        self.retry_policy = (retry_policy if retry_policy is not None
                             else RetryPolicy())
        # Multi-writer mode: lease manager + lazily-acquired writer lease
        # (see the "Multi-writer contract" in the module docstring).
        self.leases: Optional[LeaseManager] = (
            LeaseManager(self.store, ttl_s=lease_ttl_s)
            if multi_writer else None)
        self._writer_lease: Optional[Lease] = None
        self._heartbeat: Optional[LeaseHeartbeat] = None
        self._lease_heartbeat = lease_heartbeat
        # Recovery scan before anything reads the store: a previous
        # process may have died mid-transaction.  True = quick (existence
        # + non-empty of every referenced pod); "deep" additionally
        # validates every pod's bytes — see the durability contract above.
        self.last_fsck = None
        if fsck_on_open:
            from ..version import fsck as _fsck
            self.last_fsck = _fsck(self.store,
                                   deep=(fsck_on_open == "deep"),
                                   leases=self.leases)
        # Resume TimeIDs after the store's newest manifest: a reopened
        # store must append commits, never overwrite them (TimeIDs are
        # namespace-global, not per-process).
        existing = self.store.list_time_ids()
        self._next_time = (existing[-1] + 1) if existing else 1
        # runtime import: version depends on core, never the reverse at
        # module import time.  Built eagerly so the caller thread and the
        # podding thread share one DAG instance from the start.
        from ..version import CommitDAG
        self.versions = CommitDAG(self.store,
                                  max_cas_retries=max_refs_cas_retries,
                                  cas_backoff=refs_cas_backoff)
        #: last saved/checked-out tid; resumes from the persisted HEAD so
        #: a reopened instance chains its first commit to the old tip.
        self._head: Optional[TimeID] = self.versions.head_commit()
        self.last_checkout_stats = None
        self.save_stats: List[Dict[str, Any]] = []
        #: pool mode: CAS TimeID counter without the full lease protocol
        #: (see "Multi-tenant sessions" in the module docstring).
        self._shared_tids = shared_tids
        # Refcount index (incremental GC): loaded-or-rebuilt now so the
        # first evict/gc never pays a surprise full scan mid-request.
        self.refcounts = None
        if refcounts:
            from ..version.refcount import RefcountIndex
            self.refcounts = RefcountIndex(self.store)
            self.refcounts.ensure()
        #: tips orphaned by delete_branch, awaiting an incremental gc()
        self._gc_backlog: List[TimeID] = []

    # ------------------------------------------------------------------
    # multi-writer plumbing (leases, fenced TimeIDs)
    # ------------------------------------------------------------------
    def _alloc_time_id(self) -> TimeID:
        """Next TimeID.  Single-writer: the local counter.  Multi-writer:
        a CAS counter meta blob, seeded no lower than the local counter
        (which itself started past the newest on-disk manifest), so two
        writers can never mint the same commit id.  ``shared_tids`` opts
        a lease-less pool of instances into the same CAS counter."""
        if self.leases is None and not self._shared_tids:
            tid = self._next_time
            self._next_time += 1
            return tid
        while True:
            cur = self.store.get_meta(TID_COUNTER_META_KEY)
            floor = self._next_time
            if cur is not None:
                floor = max(floor, msgpack.unpackb(cur, raw=False))
            blob = msgpack.packb(floor + 1, use_bin_type=True)
            if self.store.compare_and_put_meta(TID_COUNTER_META_KEY, cur,
                                               blob):
                self._next_time = floor + 1
                return floor

    def _ensure_writer_lease(self) -> Optional[Lease]:
        """The instance's writer lease: acquired lazily, renewed inline
        when past half-TTL, re-acquired after a loss (an expired writer
        that was reaped simply rejoins — its next save re-registers its
        intent under the new fence token)."""
        if self.leases is None:
            return None
        lease = self._writer_lease
        if lease is not None:
            if self._heartbeat is not None and self._heartbeat.lost:
                lease = None
            else:
                try:
                    if self.leases.now() >= lease.expires - lease.ttl_s / 2:
                        self.leases.renew(lease)
                    return lease
                except LeaseLost:
                    lease = None
        if self._heartbeat is not None:
            self._heartbeat.stop()
            self._heartbeat = None
        lease = self.leases.acquire_writer()
        self._writer_lease = lease
        if self._lease_heartbeat:
            self._heartbeat = LeaseHeartbeat(self.leases, lease).start()
        return lease

    def close(self) -> List[BaseException]:
        """Shut down: drain the async pipeline (returning — not raising —
        any pending save errors), stop the heartbeat, and release the
        writer lease so peers need not wait out its TTL.  Idempotent."""
        errors = self.saver.drain()
        if self._heartbeat is not None:
            self._heartbeat.stop()
            self._heartbeat = None
        if self.leases is not None and self._writer_lease is not None:
            try:
                self.leases.release(self._writer_lease)
            except Exception:
                pass                  # store down: the lease just expires
            self._writer_lease = None
        return errors

    # ------------------------------------------------------------------
    # save
    # ------------------------------------------------------------------
    def save(
        self,
        state: Any,
        *,
        accessed_vars: Optional[Iterable[str]] = None,
        touched_prefixes: Optional[Iterable[str]] = None,
        readonly_paths: Optional[Set[str]] = None,
        parent: Optional[TimeID] = None,
        branch: Optional[str] = None,
    ) -> TimeID:
        time_id = self._alloc_time_id()
        if parent is None:
            if branch is not None:
                # commit onto a named ref: chain to ITS tip (None for a
                # branch this commit will create), never to local HEAD.
                parent = self.versions.branches.get(branch)
            else:
                parent = self._head      # commit chains to HEAD by default

        # graph build runs on the caller's thread: it is the snapshot that
        # makes overlapped async saves sound (scalar values are copied into
        # SCALAR nodes; device array references are immutable).  The
        # caller fills `stats` before the submit and never after it: from
        # there on the body owns the dict.
        stats: Dict[str, Any] = {"time_id": time_id}
        ph = Phases(stats, time_id)
        with ph.span("save"):
            with ph("graph"):
                graph, ginfo, n_leaf_copies = self._build_snapshot(
                    state, time_id)
            stats["n_leaf_copies"] = n_leaf_copies

            times = QueueTimes() if self.async_mode else None

            def work() -> None:
                self._save_body(time_id, graph, ginfo, accessed_vars,
                                touched_prefixes, readonly_paths, parent,
                                stats, times, branch)

            if times is not None:
                try:
                    # overlapped; FIFO on the podding thread.  May re-raise
                    # a PREVIOUS save's failure, in which case THIS save is
                    # dropped (its body never enqueued).
                    with ph.span("submit_wait"):
                        self.saver.submit(work, times)
                except BaseException:
                    # The graph cache already advanced for the dropped
                    # save, so a later identical state would diff as
                    # "unchanged" against a build that never persisted.
                    # Invalidating the cache forces the next save to
                    # rebuild (and therefore re-pod and re-hash) from
                    # scratch; this is the only race-free reset — queued
                    # bodies still in flight may re-arm _prev_pods /
                    # _pod_digests after the fact, but a from-scratch
                    # build never consults them.
                    if self._graph_cache is not None:
                        self._graph_cache.invalidate()
                    raise
            else:
                work()
        self._head = time_id
        return time_id

    def _build_snapshot(self, state: Any, time_id: TimeID) -> tuple:
        """The save's graph, its incremental build info and the number of
        host leaves copied on submit, on the caller's thread."""
        if self._graph_cache is not None:
            graph, ginfo = self._graph_cache.build(state)
        else:
            graph = build_graph(state, chunk_bytes=self.chunk_bytes)
            ginfo = None

        # copy-on-submit: small host-mutable numpy leaves are snapshotted
        # on the caller's thread so in-place mutation before wait() cannot
        # corrupt the overlapped body (jax.Arrays are immutable already;
        # large host leaves keep the must-not-mutate-before-wait rule).
        n_leaf_copies = 0
        large_leaves: List[str] = []
        if self.async_mode and self.copy_on_submit_bytes > 0:
            for key, arr in graph.arrays.items():
                if isinstance(arr, np.ndarray) and arr.flags.writeable:
                    if arr.nbytes <= self.copy_on_submit_bytes:
                        graph.arrays[key] = arr.copy()
                        n_leaf_copies += 1
                    else:
                        large_leaves.append(key)
        if large_leaves and self.large_leaf_action != "ignore":
            msg = (
                f"async save {time_id}: host leaf(s) "
                f"{sorted(large_leaves)[:4]}"
                f"{'...' if len(large_leaves) > 4 else ''} exceed "
                f"copy_on_submit_bytes={self.copy_on_submit_bytes} and are "
                "snapshotted BY REFERENCE — mutating them in place before "
                "wait() returns corrupts the in-flight save.  Either raise "
                "copy_on_submit_bytes past the largest host leaf, call "
                "wait() before mutating, or silence with "
                "large_leaf_action='ignore'.")
            if self.large_leaf_action == "raise":
                if self._graph_cache is not None:
                    # the cache advanced for a save that will never run —
                    # same reset as a rejected submit in save().
                    self._graph_cache.invalidate()
                raise ValueError(msg)
            fresh = [k for k in large_leaves
                     if k not in self._large_leaves_warned]
            if fresh:
                self._large_leaves_warned.update(fresh)
                warnings.warn(msg, RuntimeWarning, stacklevel=3)
        return graph, ginfo, n_leaf_copies

    def wait(self) -> None:
        self.saver.wait()

    def _save_body(self, time_id, graph, ginfo, accessed_vars,
                   touched_prefixes, readonly_paths, parent, stats,
                   times=None, branch=None) -> None:
        stats["t_submit_wait"] = times.submit_wait if times else 0.0
        stats["t_queue"] = times.queue if times else 0.0
        ph = Phases(stats, time_id)
        try:
            with ph("body"):
                self._save_body_inner(time_id, graph, ginfo, accessed_vars,
                                      touched_prefixes, readonly_paths,
                                      parent, ph, branch)
        except BaseException as exc:
            # A half-applied save poisons the reuse chain: the graph cache
            # has already advanced (build happens at save() call time), so
            # the next save must re-pod and re-hash from its own graph
            # rather than trust artifacts of a save that never finished.
            # Lineage must not name the failed TimeID (it has no manifest)
            # as a parent: fall back to the last commit that actually
            # landed, so the branch's ancestry stays intact.
            self._prev_pods = None
            self._prev_graph = None
            self._pod_digests = {}
            self._head = (self.versions.branches.get(branch)
                          if branch is not None
                          else self.versions.head_commit())
            # the failed save's intent pins nothing worth keeping: drop
            # it (best-effort — an expired lease is reaped by peers/fsck
            # anyway, and the original error must surface, not this).  A
            # save fenced out by LeaseLost forgets its lease entirely so
            # the next save re-acquires under a fresh token instead of
            # presenting the dead one again.
            if self.leases is not None and self._writer_lease is not None:
                if isinstance(exc, LeaseLost):
                    self._writer_lease = None
                else:
                    try:
                        self.leases.clear_intent(self._writer_lease)
                    except Exception:
                        self._writer_lease = None
            raise
        # the body's last act: the commit time is taken at this append
        self.save_stats.append(stats)

    def _speculate(self, graph: ObjectGraph,
                   ginfo: Optional[IncrementalBuildInfo]) -> Optional[Set[str]]:
        """Speculative dirty set for the fused single-sync save.

        Seeds: chunk keys whose flip EMA exceeds ``spec_threshold``
        (`FlipTracker.predicted`) plus keys of scalars the incremental
        build saw change (their pods re-serialize this save even though
        no chunk flipped — the step counter is the canonical case).

        The seed set is then expanded to **pod granularity** against the
        previous assignment: `serialize_pod` needs every chunk of a
        written pod, so speculating a chunk without its pod siblings
        would still pay the corrective gather.  Expansion requires the
        previous assignment to still describe this graph — same
        condition as assignment reuse (no structural change); otherwise
        speculation is skipped (a from-scratch save is all-dirty anyway
        and pays its one corrective gather).
        """
        if not self.fused or self.tracker is None:
            return None
        asg = self._prev_pods
        if (asg is None or ginfo is None or ginfo.from_scratch
                or ginfo.structural_change):
            return None
        seeds = self.tracker.predicted(self.spec_threshold)
        seeds.update(ginfo.scalar_changed_keys)
        pods: Set[int] = set()
        for key in seeds:
            nid = graph.by_key.get(key)
            if nid is not None and nid in asg.node_pod:
                pods.add(asg.node_pod[nid])
        out: Set[str] = set()
        for pid in pods:
            for nid in asg.pods[pid].node_ids:
                node = graph.node(nid)
                if node.kind == CHUNK:
                    out.add(node.key)
        return out or None

    def _save_body_inner(self, time_id, graph, ginfo, accessed_vars,
                         touched_prefixes, readonly_paths, parent, ph,
                         branch=None) -> None:
        # Every statement below lies in exactly one top-level phase, so
        # the phases' times add up to the body's (`core/phases.py`).
        stats = ph.stats
        with ph("avf"):
            # Only the newest snapshot keeps its arrays: this body reads
            # the previous graph by structure alone (AVF), and checkout,
            # which drains saves first, reads the graph this body leaves
            # behind.  Dropping the older arrays frees that device state.
            if self._prev_graph is not None and self._prev_graph is not graph:
                self._prev_graph = dataclasses.replace(self._prev_graph,
                                                       arrays={})
            if ginfo is not None:
                stats["t_graph_inc"] = stats["t_graph"]
                stats["n_nodes_reused"] = ginfo.n_nodes_reused
                stats["n_nodes_rebuilt"] = ginfo.n_nodes_rebuilt
            if self.enable_avf:
                active = self.avf.active_leaves(
                    graph,
                    readonly_paths=readonly_paths,
                    touched_prefixes=touched_prefixes,
                    prior_pods=(self._prev_pods if accessed_vars is not None
                                else None),
                    prior_graph=self._prev_graph,
                    accessed_vars=accessed_vars,
                )
            else:
                active = {n.key for n in graph.leaf_nodes()}
            stats["n_leaves"] = len(list(graph.leaf_nodes()))
            stats["n_active_leaves"] = len(active)

        with ph("digest"):
            spec = self._speculate(graph, ginfo)
            report = self.detector.detect(graph, active, speculate=spec,
                                          phases=ph)
            stats["n_chunks"] = len(report.digests)
            stats["n_dirty_chunks"] = len(report.dirty)
            stats["bytes_dirty"] = report.dirty_bytes
            stats["bytes_partly_dirty"] = report.partly_dirty_bytes
            stats["bytes_in_partly_dirty"] = report.bytes_in_partly_dirty
            stats["n_digest_syncs"] = report.n_syncs
            stats["n_spec_predicted"] = len(spec) if spec else 0
            stats["n_spec_hits"] = report.n_spec_hits
            stats["n_spec_misses"] = report.n_spec_misses
            stats["n_fused_rows"] = report.fused_rows

        with ph("observe"):
            if self.tracker is not None:
                active_chunks = [n.key for n in graph.chunk_nodes()
                                 if "/".join(n.path) in active]
                self.tracker.observe(graph, report.dirty, active_chunks)

        # podding: reuse the previous assignment verbatim when the graph
        # structure is unchanged (memo locals preserved, §7.3 stability);
        # otherwise rerun the full LGA walk — the parity oracle — with the
        # rebuilt-key set so feature preparation stays incremental.
        with ph("podding"):
            pods_reused = (self.incremental and ginfo is not None
                           and not ginfo.from_scratch
                           and not ginfo.structural_change
                           and self._prev_pods is not None)
            if pods_reused:
                asg = self._prev_pods
                stats["n_pods_reused"] = len(asg.pods)
            else:
                asg = pod_graph(graph, self.policy,
                                flip_ema=(self.tracker.ema if self.tracker
                                          else None),
                                memo_page_size=self.memo_page_size,
                                changed_keys=(ginfo.rebuilt_keys
                                              if ginfo is not None else None))
                stats["n_pods_reused"] = 0
            stats["n_pods"] = len(asg.pods)

        # decide phase: structural digests + synonym lookups; no payload
        # bytes move yet.  With a reused assignment, only pods touched by
        # dirty chunks or changed scalar values re-hash their digest; the
        # rest reuse the previous save's digest (bit-identical: the digest
        # is a pure function of unchanged inputs).
        with ph("decide"):
            touched_pods = None
            if pods_reused and self._pod_digests:
                touched_pods = set()
                for key in report.dirty:
                    nid = graph.by_key.get(key)
                    if nid is not None:
                        touched_pods.add(asg.node_pod[nid])
                for key in (ginfo.scalar_changed_keys if ginfo else ()):
                    nid = graph.by_key.get(key)
                    if nid is not None:
                        touched_pods.add(asg.node_pod[nid])
            pods_meta: Dict[int, Dict[str, Any]] = {}
            written = aliased = digests_reused = 0
            bytes_before = self.store.total_bytes()
            #: the parent commit's digest per pod id — the delta base each
            #: touched pod would chain to (captured before new_digests
            #: lands).
            prev_pod_digests = self._pod_digests
            new_digests: Dict[int, bytes] = {}
            to_write: List[tuple] = []        # (pid, pod, dig_hex, digest)
            aliased_entries: List[tuple] = []  # same shape; dedup-skipped
            for pid, pod in asg.pods.items():
                if touched_pods is not None and pid not in touched_pods \
                        and pid in self._pod_digests:
                    digest = self._pod_digests[pid]
                    digests_reused += 1
                else:
                    digest = pod_structural_digest(pod, graph, asg,
                                                   report.digests)
                new_digests[pid] = digest
                dig_hex = digest.hex()
                skip = False
                if self.enable_cd:
                    # only the thesaurus probe touches shared namespace
                    # state; hashing above runs lock-free so concurrent
                    # loads are not blocked for the decide phase.
                    with self.saver.l_ns:
                        ref = self.thesaurus.lookup(digest)
                    if ref is not None:
                        skip = True           # synonymous pod (§4.2)
                if not skip:
                    if not self.enable_cd:
                        # NoCD baseline: every save writes unconditionally
                        # under a unique key (true snapshot cost, no dedup).
                        h = hashlib.blake2b(digest, digest_size=16,
                                            person=b"nocd")
                        h.update(time_id.to_bytes(8, "little"))
                        dig_hex = h.hexdigest()
                    to_write.append((pid, pod, dig_hex, digest))
                else:
                    aliased += 1
                    aliased_entries.append((pid, pod, dig_hex, digest))
                pods_meta[pid] = {
                    "d": dig_hex,
                    "pages": (asg.memo.pods[pid].pages
                              if pid in asg.memo.pods else []),
                    "n": len(pod.node_ids),
                }
            self._pod_digests = new_digests
            stats["n_pod_digests_reused"] = digests_reused

        # intent phase (multi-writer): declare the commit — its TimeID
        # and every digest the manifest will reference — under the
        # writer lease BEFORE any pod byte lands and before dedup is
        # trusted.  From here the concurrent GC pins these digests
        # (sweep-fence argument in core/lease.py).  Aliased pods are
        # then re-verified: a sweep that ran before the intent landed
        # may have deleted the blob the thesaurus still points at, in
        # which case the pod is rewritten instead of aliased.
        with ph("intent"):
            lease = self._ensure_writer_lease()
            n_alias_rewrites = 0
            if lease is not None:
                # the parent tid rides along in the intent so a concurrent
                # sweep cannot reclaim the manifest this commit will chain
                # to while the save is still in flight.
                self.leases.set_intent(
                    lease,
                    time_ids=tuple(t for t in (time_id, parent)
                                   if t is not None),
                    digests=sorted({m["d"] for m in pods_meta.values()}))
                for pid, pod, dig_hex, digest in aliased_entries:
                    if not self.store.has_pod(dig_hex):
                        to_write.append((pid, pod, dig_hex, digest))
                        with self.saver.l_ns:
                            self.thesaurus.prune([dig_hex])
                        aliased -= 1
                        n_alias_rewrites += 1
            stats["n_alias_rewrites"] = n_alias_rewrites

        # gather phase.  Fused path: payload bytes of speculated chunks
        # already arrived with the digest fetch; only mispredicted chunks
        # pay one corrective batched fetch (zero when speculation covered
        # every written pod — the single-sync save).  Non-fused: ONE
        # batched device fetch for every chunk of every dirty pod (clean
        # pods never touch the device either way).
        with ph("gather"):
            gather_nodes = [graph.node(nid) for _, pod, _, _ in to_write
                            for nid in pod.node_ids]
            if self.fused:
                chunk_bytes_of, gather_syncs = fused_chunk_fetch(
                    graph, gather_nodes, report.payload, phases=ph)
                stats["n_corrective_syncs"] = gather_syncs
            else:
                chunk_bytes_of, gather_syncs = batched_chunk_fetch(
                    graph, gather_nodes, phases=ph)
            stats["n_gather_syncs"] = gather_syncs

        # write phase: serialize + store from the prefetched host bytes.
        # Thesaurus/store mutation is serialized under the namespace lock,
        # taken per pod so serialization itself never blocks concurrent
        # readers (save bodies are FIFO already; l_ns shields readers).
        # Each store write retries transient I/O errors with backoff
        # (retry_policy); puts are idempotent — a pod is content-addressed
        # and the rename is atomic — so a retried step never double-
        # applies.  InjectedCrash (BaseException) punches through.
        with ph("write"):
            n_retries = 0
            # delta-chain eligibility for this save: only the reuse path
            # has a per-pod parent digest AND the soundness proof
            # (assignment reuse + detector mask) that non-patched entries
            # are byte-identical.
            delta_eligible = (self.delta_chains and self.enable_cd
                              and pods_reused and touched_pods is not None)
            scalar_changed = (set(ginfo.scalar_changed_keys) if ginfo
                              else set())
            n_delta_pods = 0
            chain_depth_max = 0
            stats.update(t_serialize=0.0, t_delta_encode=0.0,
                         t_store_put=0.0, t_ns_wait=0.0,
                         bytes_serialized=0)
            fs_calls_before = self.store.stats.pod_fs_calls
            for pid, pod, dig_hex, digest in to_write:
                with ph("write.serialize", "t_serialize"):
                    data = serialize_pod(pod, graph, asg, chunk_bytes_of)
                ph.add("bytes_serialized", len(data))

                delta_blob = base_hex = None
                delta_depth = 0
                base = prev_pod_digests.get(pid) if delta_eligible else None
                if base is not None and base != digest:
                    with ph("write.delta_encode", "t_delta_encode"):
                        delta_blob, base_hex, delta_depth = \
                            self._delta_candidate(pod, graph, report, data,
                                                  base, scalar_changed)

                def put_one(dig_hex=dig_hex, data=data, digest=digest,
                            delta_blob=delta_blob) -> bool:
                    t0 = _time.perf_counter()
                    with self.saver.l_ns:
                        ph.add("t_ns_wait", _time.perf_counter() - t0)
                        with ph("write.store_put", "t_store_put"):
                            if not self.enable_cd:
                                self.store.put_pod(dig_hex, data)
                                return True
                            if delta_blob is not None:
                                fresh = self.store.put_pod_delta(dig_hex,
                                                                 delta_blob)
                            else:
                                fresh = self.store.put_pod(dig_hex, data)
                            self.thesaurus.insert(digest, dig_hex)
                            return fresh

                fresh, nr = call_with_retries(put_one, self.retry_policy)
                n_retries += nr
                if fresh:
                    written += 1
                    if delta_blob is not None:
                        # the manifest records chain structure only for
                        # pods this commit actually stored in delta form
                        # (a dedup hit keeps whatever form the digest
                        # already has).
                        pods_meta[pid]["delta_of"] = base_hex
                        n_delta_pods += 1
                        chain_depth_max = max(chain_depth_max, delta_depth)
                else:
                    aliased += 1              # disk-level synonym
            stats["n_retries"] = n_retries
            stats["pods_written"] = written
            stats["pods_aliased"] = aliased
            stats["n_delta_pods"] = n_delta_pods
            stats["chain_depth_max"] = chain_depth_max
            stats["bytes_written"] = self.store.total_bytes() - bytes_before
            stats["n_store_fs_calls"] = (self.store.stats.pod_fs_calls
                                         - fs_calls_before)

        # commit phase: manifest, refcount and refs compare-and-swap as
        # one retried unit, then the intent is cleared.
        with ph("commit"):
            manifest = {
                "time_id": time_id,
                "parent": parent,
                "root_pod": asg.root_pod,
                "page_size": self.memo_page_size,
                "pods": {str(pid): meta for pid, meta in pods_meta.items()},
                # the save's full chunk-digest table, so a later delta-
                # aware checkout can prime change detection without
                # re-hashing
                "chunks": pack_digest_table(report.digests),
                "stats": {k: v for k, v in stats.items()
                          if isinstance(v, (int, float, str))},
            }

            def commit() -> None:
                with self.saver.l_ns:
                    # fencing gate: the refs CAS must not publish a commit
                    # whose lease was reaped or taken over mid-save — a
                    # fenced GC may already have swept what the dead
                    # intent pinned.  LeaseLost aborts the save (not
                    # retried: it is a RuntimeError, outside the
                    # transient-OSError class).
                    if lease is not None:
                        self.leases.check(lease)
                    # the manifest put is the data commit point; the refs
                    # CAS in record() is the visibility commit point.
                    # Both are idempotent (atomic rename; CAS rebases;
                    # record_commit is a no-op for an already-counted
                    # TimeID), so the triple is safe to retry as a unit on
                    # transient I/O errors.  The refcount lands BEFORE the
                    # refs CAS: a crash in between leaves a counted
                    # dangling commit — conservative (pods kept, never
                    # lost) and exactly what rebuild() computes.
                    self.store.put_manifest(time_id, manifest)
                    if self.refcounts is not None:
                        self.refcounts.record_commit(time_id, manifest)
                    self.versions.record(time_id, parent, branch=branch)

            _, nr = call_with_retries(commit, self.retry_policy)
            stats["n_retries"] = n_retries + nr
            if lease is not None:
                # the commit is now pinned by refs; the intent has done
                # its job.  Best-effort: a lease lost in this instant
                # cannot un-commit anything.
                try:
                    self.leases.clear_intent(lease)
                except Exception:
                    pass
            self._prev_pods = asg
            self._prev_graph = graph

    def _delta_candidate(self, pod, graph, report, data, base,
                         scalar_changed) -> tuple:
        """(delta blob, base hex, chain depth) to store `pod` as a delta
        against its parent-commit digest `base`, or (None, None, 0) where
        the cost model or the base's chain refuses it."""
        cand_hex = base.hex()
        try:
            # depth the new pod would sit at if chained to base; a
            # missing/broken/cyclic base chain disqualifies.
            depth = self.store.pod_chain_depth(cand_hex) + 1
        except (FileNotFoundError, ValueError):
            return None, None, 0
        if depth > self.delta_policy.max_chain_depth:
            return None, None, 0
        changed_locals = [
            i for i, nid in enumerate(pod.node_ids)
            if ((n := graph.node(nid)).kind == CHUNK
                and n.key in report.dirty)
            or n.key in scalar_changed]
        cand = encode_pod_delta(data, cand_hex, changed_locals)
        if self.delta_policy.admit(len(cand), len(data), depth):
            return cand, cand_hex, depth
        return None, None, 0

    # ------------------------------------------------------------------
    # load
    # ------------------------------------------------------------------
    def _open(self, time_id: Optional[TimeID]) -> tuple:
        # Manifest resolution takes the namespace lock: an overlapped save
        # body may be inserting manifests concurrently.  Pod fetches after
        # this stay lock-free — pods are content-addressed, internally
        # locked, and fully written before their manifest lands (the
        # manifest put is the l_ns-serialized commit point).
        with self.saver.l_ns:
            if time_id is None:
                tids = self.store.list_time_ids()
                if not tids:
                    raise FileNotFoundError("no checkpoints in store")
                time_id = tids[-1]
            manifest = self.store.get_manifest(time_id)
        memo, digests = open_manifest(manifest)

        def fetch(pod_id: int) -> bytes:
            return self.store.get_pod(digests[pod_id])

        return manifest, Unpodder(memo, fetch)

    def load(self, names: Optional[Set[str]] = None,
             time_id: Optional[TimeID] = None,
             like: Any = None) -> Any:
        """Restore variables.  `names=None` loads the full namespace;
        otherwise only pods reachable from the requested variables are read
        (partial loading, §3.1)."""
        manifest, up = self._open(time_id)
        root_pod = manifest["root_pod"]
        root_entry = up.entry(root_pod, 0)
        names_avail = root_entry["m"]["names"]
        out: Dict[str, Any] = {}
        for name, vid in zip(names_avail, root_entry["r"]):
            if names is not None and name not in names:
                continue
            cp, cl = up.resolve(root_pod, vid)
            out[name] = up.value(cp, cl)
        self.last_load_pods = len(up.loaded_pods)
        if like is not None:
            return reflow(like, out)
        return out

    # ------------------------------------------------------------------
    # versioning (see "Versioning contract" in the module docstring)
    # ------------------------------------------------------------------
    def branch(self, name: str, at: Any = None) -> TimeID:
        """Create branch `name` (at HEAD unless `at` gives a ref/TimeID)
        and switch to it: subsequent saves advance the new branch."""
        self.wait()
        with self.saver.l_ns:
            return self.versions.create_branch(name, at=at)

    def tag(self, name: str, at: Any = None) -> TimeID:
        """Pin a commit under an immutable name (a GC root)."""
        self.wait()
        with self.saver.l_ns:
            return self.versions.create_tag(name, at=at)

    def delete_branch(self, name: str) -> None:
        """Drop a branch ref; its exclusive commits become GC-eligible.
        Drains in-flight saves first — an async commit still targeting
        the branch would otherwise resurrect it after the deletion.
        With refcounts on, the orphaned tip is remembered so the next
        ``gc()`` reclaims it incrementally (O(branch delta)); call
        ``evict_branch`` to delete and reclaim in one step."""
        self.wait()
        with self.saver.l_ns:
            tip = self.versions.branches.get(name)
            self.versions.delete_branch(name)
            if self.refcounts is not None and tip is not None:
                self._gc_backlog.append(tip)

    def checkout(self, ref: Any = None, *, like: Any = None) -> Any:
        """Restore the state of a branch / tag / TimeID, delta-aware.

        Only pods whose digest differs from the live in-memory state are
        read from the store; afterwards the incremental save pipeline is
        primed so the next `save()` reuses the checked-out assignment.
        Moves HEAD (onto the branch, or detached for tags/TimeIDs) and
        returns the restored state (re-flowed into `like` if given).
        Fine-grained stats land in `self.last_checkout_stats`.
        """
        self.wait()
        from ..version import delta_checkout
        dag = self.versions
        tid = dag.resolve(ref)
        if tid is None:
            raise FileNotFoundError("no commit to check out")
        state, stats = delta_checkout(self, tid)
        self.last_checkout_stats = stats
        with self.saver.l_ns:
            if ref is not None:
                dag.set_head(ref)
            self._head = tid
        if like is not None:
            return reflow(like, state)
        return state

    def log(self, ref: Any = None, limit: Optional[int] = None):
        """First-parent history of a ref (default HEAD), newest first.
        Drains in-flight saves so the newest commit is visible."""
        self.wait()
        return self.versions.log(ref, limit=limit)

    def diff(self, a: Any, b: Any):
        """Pod-granular delta between two refs (see `PodDelta`)."""
        self.wait()
        return self.versions.diff(a, b)

    def gc(self, *, dry_run: bool = False, full: Optional[bool] = None):
        """Reclaim pods/manifests unreachable from branch refs, tags,
        and HEAD.  Drains in-flight async saves first, so a pending
        manifest always lands — and roots its pods — before anything is
        marked.  Swept digests are pruned from the thesaurus so a
        future save rewrites, not aliases, them.  `dry_run=True` reports
        the same reclaim the real sweep would free, deleting nothing.

        With ``refcounts=True`` the default is the **incremental** path:
        drain the backlog of `delete_branch` tips through
        `refcount_reclaim` — O(sum of the deleted branches' deltas), not
        O(store).  ``full=True`` forces the mark-and-sweep oracle (which
        also catches garbage the backlog can't know about, e.g. commits
        orphaned by a peer process) and trues the refcount index up
        afterwards.  Without refcounts every gc is full.
        """
        self.wait()
        from ..version import mark_and_sweep, refcount_reclaim
        if full is None:
            full = self.refcounts is None
        with self.saver.l_ns:
            if not full and self.refcounts is not None:
                stats = refcount_reclaim(self.store, self.versions,
                                         self.refcounts,
                                         list(self._gc_backlog),
                                         extra_roots=(self._head,),
                                         dry_run=dry_run,
                                         leases=self.leases)
                if not dry_run:
                    self._gc_backlog.clear()
                    if stats.deleted_pod_digests:
                        self.thesaurus.prune(stats.deleted_pod_digests)
                return stats
            stats = mark_and_sweep(self.store, self.versions,
                                   extra_roots=(self._head,),
                                   dry_run=dry_run,
                                   leases=self.leases)
            if not dry_run:
                self._gc_backlog.clear()
                if stats.deleted_pod_digests:
                    self.thesaurus.prune(stats.deleted_pod_digests)
                if self.refcounts is not None:
                    # the sweep bypassed the index by design (it is the
                    # oracle); reconcile it with the store it just edited.
                    self.refcounts.rebuild()
        return stats

    def evict_branch(self, name: str, *, dry_run: bool = False):
        """Delete branch `name` and reclaim its exclusive commits and
        pods immediately — the multi-tenant eviction path.  Requires
        ``refcounts=True``; cost scales with the branch's delta against
        surviving history, not store size, and what it frees is
        bit-identical to a full mark-and-sweep after the same deletion
        (the tested contract).  ``dry_run=True`` estimates the reclaim
        without touching the ref or the store.  Returns `GCStats`.
        """
        if self.refcounts is None:
            raise RuntimeError("evict_branch requires refcounts=True "
                               "(otherwise: delete_branch + gc)")
        self.wait()
        from ..version import refcount_reclaim
        with self.saver.l_ns:
            # a pool peer may have advanced the branch since we last read
            # refs: evict the CURRENT tip, and fail loudly on a branch a
            # peer already deleted.
            self.versions.sync()
            tip = self.versions.branches.get(name)
            if tip is None:
                raise KeyError(f"unknown branch {name!r}")
            # the live in-memory state pins its own commit (extra_roots)
            # — unless that commit IS the evicted tip, in which case the
            # live incremental state dies with the branch: reset it like
            # a failed save, so the next save rebuilds from scratch
            # instead of delta-ing against reclaimed pods.
            head_root = self._head if self._head != tip else None
            if dry_run:
                # the branch still exists, so its own tip must not stop
                # the walk (exclude_refs) — same plan the real path runs.
                return refcount_reclaim(self.store, self.versions,
                                        self.refcounts, [tip],
                                        extra_roots=(head_root,),
                                        exclude_refs=(name,),
                                        dry_run=True,
                                        leases=self.leases)
            if self._head == tip:
                self._prev_pods = None
                self._prev_graph = None
                self._pod_digests = {}
                if self._graph_cache is not None:
                    self._graph_cache.invalidate()
                self._head = None
            self.versions.delete_branch(name)
            stats = refcount_reclaim(self.store, self.versions,
                                     self.refcounts, [tip],
                                     extra_roots=(head_root,),
                                     leases=self.leases)
            if stats.deleted_pod_digests:
                self.thesaurus.prune(stats.deleted_pod_digests)
        return stats

    def fsck(self, *, deep: bool = False, repair: bool = True):
        """Recovery scan (see the durability contract above): classify
        torn saves, roll refs back to the newest complete commit, sweep
        debris.  Drains in-flight saves first; afterwards the in-memory
        DAG and HEAD are re-synced to the repaired refs, and swept pod
        digests are pruned from the thesaurus so a future save rewrites
        — not aliases — them.  Returns the `FsckReport` (also kept in
        ``self.last_fsck``)."""
        self.wait()
        from ..version import fsck as _fsck
        with self.saver.l_ns:
            report = _fsck(self.store, deep=deep, repair=repair,
                           leases=self.leases)
            if report.swept_pod_digests:
                self.thesaurus.prune(report.swept_pod_digests)
            if repair:
                self.versions.reload()
                self._head = self.versions.head_commit()
                # a swept torn save may have consumed TimeIDs; never
                # reissue one below an existing manifest.
                existing = self.store.list_time_ids()
                if existing:
                    self._next_time = max(self._next_time,
                                          existing[-1] + 1)
                if self.refcounts is not None:
                    # version.fsck rebuilt the persisted index after its
                    # repairs; adopt that truth locally.
                    self.refcounts.ensure()
        self.last_fsck = report
        return report


def reflow(like: Any, loaded: Dict[str, Any]) -> Any:
    """Re-flow loaded values into the structure of `like` (so custom pytree
    containers survive a round-trip).

    Tuples are rebuilt positionally; namedtuple-style containers (anything
    exposing `_fields`) are reconstructed with positional-star args, since
    their constructors take fields, not an iterable.
    """
    def walk(template: Any, value: Any) -> Any:
        if isinstance(template, dict):
            return {k: walk(template[k], value[k]) for k in template}
        if isinstance(template, (list, tuple)) and not hasattr(template, "shape"):
            t = type(template)
            vals = [walk(t_i, value[str(i)] if isinstance(value, dict) else value[i])
                    for i, t_i in enumerate(template)]
            if hasattr(template, "_fields"):   # namedtuple-style
                return t(*vals)
            return t(vals)
        return value

    return walk(like, loaded)
