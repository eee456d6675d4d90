"""Preemption-safe decode sessions: atomic, digest-verified snapshot /
restore of a live ``SlotDecodeSession``.

PR 5 taught *training* to survive SIGKILL (atomic checkpoints, resume,
die-by-the-signal); the serving stack built since loses every in-flight
generation, every shared KV page and the whole prefix trie on any
preemption. This module closes that gap on the same discipline — the
user-level checkpoint/restore of mutable state the TensorFlow paper
(Abadi et al., 2016) treats as THE fault-tolerance mechanism — made
cheap by the paged-KV layout: the page table already names exactly
which device pages are live, so a snapshot gathers only those.

:class:`DecodeSnapshotManager` rides ``resilience.CheckpointManager``'s
write/restore machinery (tmp-dir + fsynced manifest + atomic rename,
per-var sha256 digests, async background writer, corrupt-serial
quarantine) with a decode-specific dialect:

* **Device state, live-page gathered.** The per-slot loop state
  (``pgd_table``/``pgd_pos``/``pgd_tok``/``pgd_done``/``pgd_group_of``/
  ``pgd_src_mask``) is saved whole; each layer's self-KV pools are
  saved as ``pgd_kpool_i__live`` — only pages with a nonzero refcount,
  gathered in page-id order — and the cross-attention group pools as
  the live GROUP rows. Dead pages/groups are skipped: their bits are
  never read (the admit contract) so they are not state.
* **Host allocator state, exactly.** The refcounted ``PagePool`` (free
  list in LIFO order — recycling determinism is part of bit-exactness),
  every refcount, the ``PrefixCache`` trie with its LRU sequence, slot
  page lists, fork-group membership, reservations, leak ledger, the
  per-slot sampler lifecycle (position/eos come back through
  ``pgd_pos``/``pgd_done`` + the live ``trg`` rows) and the pending
  ``generate()`` queue (request ids, sources, forced prefixes).
* **Bit-exact resumption.** Sampling PRNG keys are
  ``(seed, slot, position)`` — never a host counter — so a restored
  session's subsequent tokens are bit-identical to the uninterrupted
  run's; ``tests/test_serving_resilience.py`` SIGKILLs a decoding child
  and holds another process's restored token streams byte-for-byte, and
  a restore inside one process at 0 fresh compiles.
* **Graceful preemption.** ``install_signal_handlers`` wires SIGTERM/
  SIGINT exactly like ``TrainSession``: a signal landing mid-dispatch
  defers to the session's quiesce point (the in-flight dispatch
  finishes), a final SYNC snapshot lands, the previous handler chain is
  restored and the signal re-delivered — the black box still dumps, the
  process still dies BY the signal.

Restore order is the reverse: build the model scope, construct a fresh
``SlotDecodeSession`` with the SAME geometry (checked, typed
:class:`SnapshotMismatchError` on drift), then ``manager.restore()`` —
verified newest-first, corrupt serials quarantined, live pages
scattered back through the page table before the trie that references
them is rebuilt.

``snapshot.write`` is a chaos site (per var file, like ``ckpt.write``):
a kill mid-snapshot leaves a temp dir the next restore must ignore, an
IO fault fails the save without touching the live session.
"""

import json
import os
import signal
import threading
import time
from collections import deque

import numpy as np

from paddle_tpu.observability import tracing as _tracing
from paddle_tpu.resilience import chaos as _chaos
from paddle_tpu.resilience.checkpoint import (
    CheckpointManager,
    assemble_var,
    complete_serials,
    read_manifest,
    verify_checkpoint_dir,
)
from paddle_tpu.serving.generation import Sampler
from paddle_tpu.serving.kv_pool import PagePool, PrefixCache
from paddle_tpu.serving.server import ServingError

__all__ = ["DecodeSnapshotManager", "SnapshotMismatchError",
           "DIALECT", "DIALECT_VERSION"]

DIALECT = "decode_snapshot"
DIALECT_VERSION = 1
# what a ``pgd_*pool_i__live`` page holds: whole token rows
# ``[page_size, H * dh]``. A snapshot whose meta names no layout was
# written when a page was per-head ``[H, page_size, dh]``.
POOL_LAYOUT = "token_rows"

_HANDLED_SIGNALS = (signal.SIGTERM, signal.SIGINT)

# the loop-state vars saved whole (everything else is gathered live)
_SMALL_VARS = ("pgd_table", "pgd_pos", "pgd_tok", "pgd_done",
               "pgd_group_of", "pgd_src_mask")


class SnapshotMismatchError(ServingError):
    """The snapshot's recorded session geometry (slots, pages, groups,
    layers, sampler) does not match the session being restored into —
    an operator error (wrong model/config), NOT corruption: the serial
    is left in place, never quarantined."""


def _unaliased_host_copy(arr):
    """A host copy of ``arr`` whose buffer is deliberately NOT 64-byte
    aligned. Restored values enter the scope as host arrays (exactly
    what a running session's fetched state looks like), but
    ``jax.device_put`` ZERO-COPIES a 64-byte-aligned numpy buffer on
    CPU — and the decode dispatch DONATES its state inputs, so an
    aliased buffer would have XLA freeing memory numpy still owns
    (heap corruption, found the hard way under a SIGKILL drill).
    Staging in a misaligned buffer forces device_put to copy into
    XLA-owned memory on every dispatch. (The obvious alternative,
    jnp.array, traces one tiny convert computation per shape/dtype —
    fresh compiles the restored warm process must not pay.)"""
    arr = np.ascontiguousarray(arr)
    itemsize = arr.dtype.itemsize
    raw = np.empty(arr.nbytes + 64 + itemsize, dtype=np.uint8)
    for off in range(0, 64 + itemsize, max(1, itemsize)):
        if (raw.ctypes.data + off) % 64 != 0:
            break
    staged = raw[off:off + arr.nbytes].view(arr.dtype).reshape(arr.shape)
    np.copyto(staged, arr)
    return staged


def _sampler_state(sampler):
    if sampler is None:
        return None
    if isinstance(sampler, Sampler):
        return {"strategy": sampler.strategy,
                "temperature": sampler.temperature,
                "top_k": sampler.top_k, "seed": sampler.seed}
    return dict(sampler)


class DecodeSnapshotManager(CheckpointManager):
    """Snapshot/restore one (paged) :class:`SlotDecodeSession`.

    ``interval_steps`` / ``interval_secs`` arm periodic async snapshots
    taken at the session's quiesce points (after a ``step()``/``admit``
    completes — never mid-dispatch, so host mirrors and device state
    are always consistent in a snapshot). ``install_signal_handlers``
    adds the TrainSession-style preemption path. The manager writes
    ``checkpoint_<serial>`` dirs readable by ``tools/ckpt_inspect.py``
    (which knows this dialect) and restorable only by this class.
    """

    def __init__(self, session, snapshot_dir, interval_steps=0,
                 interval_secs=0.0, max_to_keep=None,
                 install_signal_handlers=False):
        if not getattr(session, "_paged", False):
            raise ValueError(
                "DecodeSnapshotManager needs a paged SlotDecodeSession "
                "— the dense layout has no page table to gather live "
                "state through (run the paged session in production; "
                "it is also the fast one)")
        super(DecodeSnapshotManager, self).__init__(
            snapshot_dir, executor=session._exe, main_program=None,
            scope=session._scope, max_to_keep=max_to_keep)
        self._session = session
        self.interval_steps = int(interval_steps)
        self.interval_secs = float(interval_secs)
        self._last_save_steps = session.steps_done
        self._last_save_time = time.monotonic()
        self.last_save_seconds = None
        self.restored_serial = None
        self._stop_signum = None
        self._closed = False
        self._prev_handlers = {}
        session._after_dispatch = self._on_quiesce
        if install_signal_handlers:
            self._install_signal_handlers()

    # -- capture ------------------------------------------------------------

    def _session_scope(self):
        if self._scope is not None:
            return self._scope
        from paddle_tpu.executor import global_scope

        return global_scope()

    def _config(self):
        s = self._session
        return {
            "num_slots": s._S, "max_length": s._T, "d_model": s._D,
            "page_size": s._ps, "num_pages": s._P, "num_groups": s._G,
            "steps": s._steps, "n_layer": s._n_layer,
            "n_head": s._n_head, "bos_id": s._bos, "eos_id": s._eos,
            "prefix_cache": s._prefix_cache is not None,
            "sampler": _sampler_state(s._sampler),
            # beam geometry is part of the snapshot contract: restoring
            # a beam snapshot into a differently-tiled session would
            # scramble every lane's lattice — SnapshotMismatchError
            "beam_width": s._beam_width,
            # speculative config too: a mid-speculation snapshot names
            # draft-pool rows and a drafter watermark a non-speculative
            # (or differently-drafted) session could not re-own
            "speculative": (
                {"k": int(s._spec_k), "drafter": s._spec_drafter.kind}
                if getattr(s, "_spec_k", 0) else None),
        }

    def _small_vars(self):
        s = self._session
        return _SMALL_VARS + (("pgd_score",)
                              if s._beam_width > 1 else ())

    def _capture(self):
        """(vars dict, dialect meta) — the consistent host+device image
        of the session, gathered on the calling thread (the only part a
        decode loop waits for on an async save)."""
        s = self._session
        if s.in_dispatch:
            raise RuntimeError(
                "decode snapshot requested mid-dispatch: the host "
                "mirrors and device state are torn inside a "
                "step/admit window — snapshot at a quiesce point")
        scope = self._session_scope()
        snap = {}
        # np.array (copy=True), NOT np.asarray: on the CPU backend
        # np.asarray of a jax array can be a ZERO-COPY view of the XLA
        # buffer, and the decode dispatches that continue while the
        # async writer serializes this snapshot DONATE those buffers —
        # the writer would read freed/reused memory and bank a torn
        # snapshot whose digests verify (computed over the garbage).
        # The copy happens HERE, synchronously at the quiesce point,
        # before any further dispatch can touch the buffers.
        for name in self._small_vars():
            snap[name] = np.array(np.asarray(scope.get_value(name)))
        live_pages = sorted(s._pool._ref)
        live_groups = sorted(s._group_members)
        for i in range(s._n_layer):
            for kind in ("kpool", "vpool"):
                if live_pages:
                    pool = np.asarray(
                        scope.get_value("pgd_%s_%d" % (kind, i)))
                    snap["pgd_%s_%d__live" % (kind, i)] = \
                        pool[np.asarray(live_pages)]
            for kind in ("kcross", "vcross"):
                if live_groups:
                    cross = np.asarray(
                        scope.get_value("pgd_%s_%d" % (kind, i)))
                    snap["pgd_%s_%d__live" % (kind, i)] = \
                        cross[np.asarray(live_groups)]
        trg = np.full((s._S, s._T), s._eos, dtype="int64")
        for slot, st in s._live.items():
            trg[slot] = st["trg"]
        snap["live_trg"] = trg
        for req in s._pending:
            snap["req_%d_src" % req["id"]] = req["src"]
        for rid, tokens in s._results.items():
            # completed-but-unclaimed results survive the preemption too
            snap["req_%d_result" % rid] = np.asarray(tokens)
        for rid, res in s._beam_results.items():
            # banked beam n-bests (tokens + scores) survive too
            snap["req_%d_beam_tokens" % rid] = np.asarray(res["tokens"])
            snap["req_%d_beam_scores" % rid] = np.asarray(res["scores"])
        meta = {
            "version": DIALECT_VERSION,
            "pool_layout": POOL_LAYOUT,
            "config": self._config(),
            # beam slots carry their hypothesis lifecycle (done latch +
            # accumulated score) beside the position
            "live": {str(slot): (
                {"pos": int(st["pos"]), "done": bool(st["done"]),
                 "score": float(st["score"])}
                if "done" in st else {"pos": int(st["pos"])})
                for slot, st in s._live.items()},
            "free_slots": list(s._free),
            "slot_pages": {str(k): [int(p) for p in v]
                           for k, v in s._slot_pages.items()},
            "slot_group": {str(k): int(g)
                           for k, g in s._slot_group.items()},
            "free_groups": list(s._free_groups),
            "group_members": {str(g): sorted(m)
                              for g, m in s._group_members.items()},
            "reserved_pages": s._reserved_pages,
            "leaked_pages": s._leaked_pages,
            "leaked_page_ids": sorted(s._leaked_page_ids),
            "pool": s._pool.state_dict(),
            "prefix_cache": (s._prefix_cache.state_dict()
                             if s._prefix_cache is not None else None),
            "live_pages": live_pages,
            "live_groups": live_groups,
            "pending": [{"id": r["id"], "len": r["len"],
                         "prefix": r["prefix"]} for r in s._pending],
            "results": sorted(s._results),
            "owner": {str(slot): int(rid)
                      for slot, rid in s._owner.items()},
            # request-trace bindings (observability/tracing.py): the
            # restored process continues banked backlog + unclaimed
            # results under their ORIGINAL trace ids
            "trace_ids": {str(rid): str(tid)
                          for rid, tid in s._trace_ids.items()},
            "next_req": s._next_req,
            "steps_done": s.steps_done,
        }
        if getattr(s, "_spec_k", 0):
            # speculative state: acceptance books + the drafter's own
            # state (ngram: config only — its lookup state IS the
            # emitted history; model: the per-slot cache watermark).
            # The DRAFT K/V pools ride the live-page gather below:
            # they index through the same page table, so the same live
            # page ids name exactly the rows a restored drafter's
            # replay relies on. Draft model PARAMETERS travel too:
            # accepted CONTENT never depends on them (accepted tokens
            # are target samples), but acceptance TIMING does, and
            # timing decides which slot each backlog request lands in
            # after the restore — the slot keys the sampler stream, so
            # a drafter with different (freshly random) params would
            # diverge the restored session's future content.
            meta["speculative"] = {
                "counters": {
                    "proposed": int(s.spec_proposed),
                    "accepted": int(s.spec_accepted),
                    "dispatches": int(s.spec_dispatches),
                },
                "drafter": {"kind": s._spec_drafter.kind,
                            "state": s._spec_drafter.state_dict()},
            }
            if s._spec_drafter.kind == "model":
                dparams = s._spec_drafter.param_arrays()
                meta["speculative"]["drafter"]["params"] = \
                    sorted(dparams)
                for pname, arr in dparams.items():
                    snap["spec_dparam__" + pname] = arr
                if live_pages:
                    for kind in ("kpool", "vpool"):
                        pool = np.asarray(
                            scope.get_value("pgd_draft_%s_0" % kind))
                        snap["pgd_draft_%s_0__live" % kind] = \
                            pool[np.asarray(live_pages)]
        if s._beam_width > 1:
            # the hypothesis->slot binding, lane occupancy, last parent
            # permutation and banked n-bests — mid-beam restores resume
            # the lattice bit-exactly (scores ride pgd_score + live[])
            meta["beam"] = {
                "width": s._beam_width,
                "lanes": {str(lane): {"slots": [int(x)
                                                for x in b["slots"]]}
                          for lane, b in s._beam_live.items()},
                "free_lanes": [int(x) for x in s._free_lanes],
                "last_parents": {str(lane): [int(p) for p in perm]
                                 for lane, perm
                                 in s._last_parents.items()},
                "owner": {str(lane): int(rid)
                          for lane, rid in s._beam_owner.items()},
                "results": sorted(s._beam_results),
            }
        return snap, meta

    # -- save ---------------------------------------------------------------

    def _write_one_var(self, tmp_dir, name, arr):
        meta = super(DecodeSnapshotManager, self)._write_one_var(
            tmp_dir, name, arr)
        if _chaos.ENABLED:
            # the mid-snapshot kill/IO point (beside the inherited
            # ckpt.write site): var files exist, no manifest yet — a
            # crash here must be invisible to the next restore
            _chaos.fault("snapshot.write")
        return meta

    def save(self, step=None, serial=None, extra=None):
        """Synchronous snapshot (capture + write + rename before
        returning); the preemption finalizer's path. Returns the final
        snapshot dir."""
        snap, meta = self._capture()
        rng = self._rng_state()
        step = int(self._session.steps_done if step is None else step)
        serial = int(step if serial is None else serial)
        payload = dict(extra or {})
        payload[DIALECT] = meta
        self.wait()
        self._track_snapshot_ledger(snap)
        t0 = time.perf_counter()
        try:
            out = self._write(snap, rng, step, serial, payload)
        finally:
            self._drop_snapshot_ledger()
        self.last_save_seconds = time.perf_counter() - t0
        self._mark_saved()
        return out

    def save_async(self, step=None, serial=None, extra=None):
        """Capture on the calling thread (the decode loop pays only the
        device->host gather), write on a background one. Returns the
        serial."""
        snap, meta = self._capture()
        rng = self._rng_state()
        step = int(self._session.steps_done if step is None else step)
        serial = int(step if serial is None else serial)
        payload = dict(extra or {})
        payload[DIALECT] = meta
        self.wait()
        self._track_snapshot_ledger(snap)
        t = threading.Thread(
            target=self._write_guarded,
            args=(snap, rng, step, serial, payload),
            name="paddle-tpu-decode-snap-writer", daemon=True)
        self._thread = t
        t.start()
        self._mark_saved()
        return serial

    def _mark_saved(self):
        self._last_save_steps = self._session.steps_done
        self._last_save_time = time.monotonic()

    def _snapshot_due(self):
        if (self.interval_steps > 0
                and self._session.steps_done - self._last_save_steps
                >= self.interval_steps):
            return True
        if (self.interval_secs > 0
                and time.monotonic() - self._last_save_time
                >= self.interval_secs):
            return True
        return False

    # -- restore ------------------------------------------------------------

    def restore(self, serial=None):
        """Load the newest *verified* decode snapshot (or exactly
        ``serial``) into the attached session. Corrupt/partial serials
        are quarantined and skipped (the CheckpointManager discipline);
        manifests of other dialects are skipped silently; a geometry
        mismatch raises :class:`SnapshotMismatchError` without
        quarantining. Returns the manifest (with ``serial``) or None
        when nothing restorable exists."""
        serials = complete_serials(self.checkpoint_dir)
        if serial is not None:
            serials = [s for s in serials if s == int(serial)]
        for s in reversed(serials):
            step_dir = os.path.join(self.checkpoint_dir,
                                    "checkpoint_%d" % s)
            manifest = read_manifest(step_dir)
            meta = ((manifest or {}).get("extra") or {}).get(DIALECT)
            if meta is None:
                continue  # some other manager's checkpoint: not ours
            problems = verify_checkpoint_dir(step_dir, manifest)
            if problems:
                self._quarantine(s, problems)
                continue
            if meta.get("config") != self._config():
                raise SnapshotMismatchError(
                    "decode snapshot serial %d was taken from a "
                    "different session geometry:\n  recorded:  %s\n  "
                    "restoring: %s" % (s, json.dumps(
                        meta.get("config"), sort_keys=True),
                        json.dumps(self._config(), sort_keys=True)))
            try:
                self._apply(step_dir, manifest, meta)
            except Exception as exc:  # noqa: BLE001 - treat as corrupt
                self._quarantine(s, ["decode apply failed: %s" % exc])
                continue
            self._restore_rng(manifest.get("rng"))
            self.restored_serial = s
            from paddle_tpu.observability import blackbox

            if blackbox.ENABLED:
                blackbox.record("decode_snapshot_restored", serial=s,
                                steps_done=self._session.steps_done)
            return manifest
        return None

    def _apply(self, step_dir, manifest, meta):
        """Rebuild the session from one verified serial. Everything
        fallible (file loads, allocator reconstruction — including the
        conservation re-check in ``PagePool.from_state``) happens
        BEFORE the first mutation, so a torn snapshot quarantines
        without leaving the session half-restored."""
        s = self._session
        if s.in_dispatch:
            raise RuntimeError("cannot restore mid-dispatch")
        vars_meta = manifest.get("vars", {})

        def load(name):
            return assemble_var(step_dir, vars_meta[name])

        per_head = meta.get("pool_layout") != POOL_LAYOUT

        def load_pages(name):
            pages = load(name)
            if per_head:  # [n, H, page_size, dh] -> [n, page_size, H * dh]
                pages = np.ascontiguousarray(
                    np.transpose(pages, (0, 2, 1, 3))).reshape(
                        pages.shape[0], pages.shape[2], -1)
            return pages

        # -- phase 1: load + validate (no session mutation) ---------------
        small = {name: load(name) for name in self._small_vars()}
        live_trg = load("live_trg")
        live_pages = [int(p) for p in meta["live_pages"]]
        live_groups = [int(g) for g in meta["live_groups"]]
        gathered = {}
        for i in range(s._n_layer):
            for kind in ("kpool", "vpool"):
                if live_pages:
                    gathered["pgd_%s_%d" % (kind, i)] = (
                        live_pages,
                        load_pages("pgd_%s_%d__live" % (kind, i)))
            for kind in ("kcross", "vcross"):
                if live_groups:
                    gathered["pgd_%s_%d" % (kind, i)] = (
                        live_groups, load("pgd_%s_%d__live" % (kind, i)))
        spec_meta = meta.get("speculative")
        spec_dparams = {}
        if spec_meta is not None:
            if live_pages:
                for kind in ("kpool", "vpool"):
                    name = "pgd_draft_%s_0" % kind
                    if name + "__live" in vars_meta:
                        gathered[name] = (live_pages,
                                          load_pages(name + "__live"))
            spec_dparams = {
                pname: load("spec_dparam__" + pname)
                for pname in (spec_meta.get("drafter") or {}).get(
                    "params", ())}
        pool = PagePool.from_state(meta["pool"])
        cache = None
        if meta.get("prefix_cache") is not None:
            cache = PrefixCache.from_state(pool, meta["prefix_cache"])
        pending = [{
            "id": int(r["id"]),
            "src": np.asarray(load("req_%d_src" % int(r["id"]))),
            "len": int(r["len"]),
            "prefix": (None if r["prefix"] is None
                       else [int(t) for t in r["prefix"]]),
        } for r in meta["pending"]]
        results = {int(r): np.asarray(load("req_%d_result" % int(r)))
                   for r in meta.get("results", ())}
        beam_meta = meta.get("beam")
        beam_results = {}
        if beam_meta is not None:
            beam_results = {
                int(r): {
                    "tokens": np.asarray(
                        load("req_%d_beam_tokens" % int(r))),
                    "scores": np.asarray(
                        load("req_%d_beam_scores" % int(r))),
                } for r in beam_meta.get("results", ())}
        live = {}
        for k, v in meta["live"].items():
            st = {"trg": np.array(live_trg[int(k)]),
                  "pos": int(v["pos"])}
            if "done" in v:
                st["done"] = bool(v["done"])
                st["score"] = float(v["score"])
            live[int(k)] = st

        # -- phase 2: commit ----------------------------------------------
        scope = self._session_scope()
        for name, arr in small.items():
            scope.set_value(name, _unaliased_host_copy(arr))
        for name, (ids, rows) in gathered.items():
            full = np.array(np.asarray(scope.get_value(name)))
            full[np.asarray(ids)] = rows
            scope.set_value(name, _unaliased_host_copy(full))
        s._pool = pool
        s._prefix_cache = cache
        s._live = live
        s._free = [int(x) for x in meta["free_slots"]]
        s._slot_pages = {int(k): [int(p) for p in v]
                         for k, v in meta["slot_pages"].items()}
        s._slot_group = {int(k): int(g)
                         for k, g in meta["slot_group"].items()}
        s._cross_view = None  # the restored device state is the truth
        s._free_groups = [int(g) for g in meta["free_groups"]]
        s._group_members = {int(g): set(int(m) for m in v)
                            for g, v in meta["group_members"].items()}
        s._reserved_pages = int(meta["reserved_pages"])
        s._leaked_pages = int(meta["leaked_pages"])
        s._leaked_page_ids = set(
            int(p) for p in meta.get("leaked_page_ids", ()))
        s._pending = deque(pending)
        s._results = results
        s._owner = {int(k): int(v) for k, v in meta["owner"].items()}
        s._trace_ids = {int(k): str(v)
                        for k, v in meta.get("trace_ids", {}).items()}
        s._slot_traces = {}
        s._trace_cow = {}
        if s._trace_ids and _tracing.ENABLED:
            # requests LIVE at snapshot time: continue their traces as
            # session-origin continuations under the ORIGINAL ids, so
            # the restored process's remaining dispatches (and the
            # eventual bank) attribute to the same trace the client
            # holds. Queued entries re-bind at their re-admission.
            by_rid = {rid: slot for slot, rid in s._owner.items()}
            for rid, tid in s._trace_ids.items():
                slot = by_rid.get(rid)
                if slot is None or slot not in s._live:
                    continue
                s._slot_traces[slot] = (
                    _tracing.inflight_get(tid)
                    or _tracing.start(tid, endpoint="generate",
                                      origin="session"))
        s._next_req = int(meta["next_req"])
        s.steps_done = int(meta["steps_done"])
        if spec_meta is not None:
            counters = spec_meta.get("counters", {})
            s.spec_proposed = int(counters.get("proposed", 0))
            s.spec_accepted = int(counters.get("accepted", 0))
            s.spec_dispatches = int(counters.get("dispatches", 0))
            s._spec_drafter.load_state_dict(
                (spec_meta.get("drafter") or {}).get("state") or {})
            if spec_dparams:
                s._spec_drafter.load_param_arrays(spec_dparams)
        if beam_meta is not None:
            from paddle_tpu.serving.generation import _active_beams

            s._beam_live = {
                int(lane): {"slots": [int(x) for x in b["slots"]]}
                for lane, b in beam_meta["lanes"].items()}
            s._free_lanes = [int(x) for x in beam_meta["free_lanes"]]
            s._last_parents = {
                int(lane): [int(p) for p in perm]
                for lane, perm in beam_meta["last_parents"].items()}
            s._beam_owner = {int(lane): int(rid)
                             for lane, rid
                             in beam_meta["owner"].items()}
            s._beam_results = beam_results
            s._beam_events = {}
            s._last_finished_beams = {}
            _active_beams.set(len(s._beam_live))
        s._update_pool_gauges()
        from paddle_tpu.serving.generation import _active_slots

        _active_slots.set(len(s._live))

    # -- preemption plumbing (the TrainSession discipline) ------------------

    def _install_signal_handlers(self):
        if threading.current_thread() is not threading.main_thread():
            return
        for sig in _HANDLED_SIGNALS:
            try:
                self._prev_handlers[sig] = signal.signal(
                    sig, self._signal_handler)
            except (ValueError, OSError):
                pass

    def _uninstall_signal_handlers(self):
        for sig, prev in self._prev_handlers.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError, TypeError):
                pass
        self._prev_handlers = {}

    def _signal_handler(self, signum, frame):
        if self._closed:
            # already finalized — necessarily on a NON-main thread (a
            # quiesce hook on a serving frontend's decode worker),
            # where restoring the handlers was impossible
            # (signal.signal raises off the main thread), so the
            # re-raised signal landed back here. This handler DOES run
            # on the main thread: restore the default disposition and
            # die by the signal instead of re-entering the finalize
            # chain forever.
            try:
                signal.signal(signum, signal.SIG_DFL)
            except (ValueError, OSError):
                pass
            os.kill(os.getpid(), signum)
            return
        self._stop_signum = signum
        from paddle_tpu.observability import blackbox

        if blackbox.ENABLED:
            blackbox.record(
                "preemption_signal", signal=int(signum),
                steps_done=self._session.steps_done,
                in_dispatch=self._session.in_dispatch)
        if not self._session.in_dispatch:
            # idle between dispatches: finalize in handler context
            self._finalize_and_reraise()
        # else: _on_quiesce finalizes once the in-flight window closes

    def should_stop(self):
        """True once a preemption signal landed (pollable by the
        serving loop between pumps)."""
        return self._stop_signum is not None

    def _on_quiesce(self):
        """The session's post-dispatch hook: finalize a deferred
        preemption, else take a periodic snapshot when due."""
        if self._stop_signum is not None:
            self._finalize_and_reraise()
        elif not self._closed and self._snapshot_due():
            self.save_async()

    def _finalize_and_reraise(self):
        signum = self._stop_signum
        try:
            self.save()
        except Exception:
            # the signal must still propagate even if the final
            # snapshot failed (metrics/blackbox recorded the failure)
            pass
        self.close(save=False)
        os.kill(os.getpid(), signum)

    # -- lifecycle ----------------------------------------------------------

    def close(self, save=True):
        """Detach from the session and (by default) bank a final sync
        snapshot. Idempotent."""
        if self._closed:
            return
        self._closed = True
        if save:
            try:
                self.save()
            except Exception:
                pass
        else:
            self.wait()
        self._uninstall_signal_handlers()
        if self._session._after_dispatch is self._on_quiesce:
            self._session._after_dispatch = None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        # clean exit banks the final state; an exception keeps the last
        # periodic snapshot (saving mid-exception could bank a torn op)
        self.close(save=exc_type is None)
        return False
