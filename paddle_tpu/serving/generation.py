"""SlotDecodeSession: continuous batching for KV-cached generation.

``models.transformer.build_slot_decoder`` turns the KV caches into a
slot-paged pool; this module is the host-side slot manager. One
fixed-shape step executable advances every in-flight sequence per
token; sequences are admitted into free slots MID-FLIGHT (one
fixed-shape admission executable scatters the new sequence's encoder
state into its slot rows) and release their slot the moment they
finish — the serving property that matters: a long sequence no longer
holds the whole batch hostage, and a new request never waits for the
current batch to drain. Token streams are identical to running each
sequence through a dedicated-batch decoder (rows are independent;
tests/test_serving.py pins the staggered-admission parity).

``paged=True`` swaps the dense per-slot caches for the BLOCK-PAGED
layout (``build_paged_slot_decoder`` + ``kernels/paged_attention.py``):
self K/V lives in fixed-size pages shared by every slot through a
per-slot page table this session allocates from a REFCOUNTED
``kv_pool.PagePool`` (page 0 is the reserved trash page unoccupied
slots write into), decode attention is ragged — per-step cost scales
with tokens actually RESIDENT, not ``num_slots x max_length`` — and
the step program is a self-contained loop body, so one
``run_multi_step(steps=K)`` dispatch advances every slot K tokens and
fetches ``[K, S, 1]`` int ids instead of per-token ``[S, 1, V]``
logits. Token selection (greedy / temperature / top-k, ``Sampler``)
runs on device in BOTH layouts; the dense path too now fetches token
ids, never vocab-sized logits.

Cross-request KV reuse (the PR 12 layer over the page table):

* ``admit_group(src, n=N)`` admits N sampled continuations of ONE
  source that run one encoder forward and reference one group-pooled
  set of cross-attention K/V rows (``[G, H, T, dh]`` + ``group_of``) —
  N slots cost one group's cross HBM, not N dense rows.
* Self-KV pages are shared by REFERENCE (refcount > 1) until a slot's
  write position enters a shared page; the session then runs the
  on-device ``copy_prog`` (page copy + table-row repoint in one
  dispatch) first — copy-on-write, so shared page bits are immutable
  and a fork's greedy member is bit-identical to a solo admission.
* ``admit(src, prefix_tokens=[...])`` forces a decoder prefix
  (few-shot/system preamble) through ONE chunked-prefill dispatch
  instead of token-by-token stepping, and a ``kv_pool.PrefixCache``
  keyed by (source fingerprint, prefix tokens) maps repeated prefixes
  to refcounted full pages — a hit provisions the table row by
  reference and prefills only the uncached suffix.

Batched BEAM search (the PR 15 layer): ``beam_width=K`` partitions the
slots into ``S / K`` beam LANES. Per step the program runs one
``lax.top_k`` lattice per lane (``slot_beam_search`` — the same
``beam_step`` the dense ``beam_search`` op uses) and executes the
hypothesis reorder IN-GRAPH as a parent gather of the page-table rows;
the host's only reorder work is REFCOUNT REBINDS — surviving parents'
pages gain references, dropped hypotheses deref — so a pure parent
permutation moves ZERO KV bytes in HBM, and copy-on-write fires only
when a duplicated parent's in-progress WRITE page is next written.
``FLAGS_beam_reorder=reference`` is the in-tree copy-reorder oracle
(every survivor physically copies its parent's resident pages); token
streams are bit-identical between the two, which is what makes the
bench's ``beam_speedup`` an honest A/B. COW pairs are COALESCED: one
bucket-laddered ``build_cow_batch_prog`` dispatch per step window
covers every pair (and growth rebind) instead of one dispatch per
pair.

Everything stays inside the zero-recompile contract: shapes are fixed;
only table rows, group ids and refcounts change between dispatches.
``docs/SERVING.md`` "KV reuse" / "Beam over the slot pool" have the
lifecycle diagrams.
"""

import hashlib
import time
from collections import deque

import numpy as np

from paddle_tpu.observability import explain as _explain
from paddle_tpu.observability import tracing as _tracing
from paddle_tpu.observability.metrics_registry import REGISTRY as _REGISTRY
from paddle_tpu.resilience import chaos as _chaos
from paddle_tpu.resilience import retry as _retry
from paddle_tpu.serving.kv_pool import (
    NoFreeGroupError,
    NoFreePageError,
    PagePool,
    PrefixCache,
)
from paddle_tpu.serving.server import ServingError

__all__ = ["SlotDecodeSession", "Sampler", "NoFreeSlotError",
           "NoFreePageError", "NoFreeGroupError"]


# rows of the batched admission executables: ``admit_pending`` pads the
# head run of its queue up to the smallest rung that holds it and splits
# a run above the top one (rung 1 is the builder's own ``admit_prog``; a
# session of fewer slots than the top rung tops its ladder at its slots).
# Two rungs, not more: a rung costs the served session 4-6 s of set-up
# (its program built, traced and lowered). The padding of the top rung
# costs 4-5 ms of device time in the way of the batch's first tokens,
# two one-row dispatches' worth: a run that would fill less than
# 1/_ADMIT_MIN_FILL of its rung goes one request at a time through rung 1.
# The release path's table programs (``_repoint``) go by the same rungs
# without the fill rule: their padding is dropped rows of one row scatter
_ADMIT_RUNGS = (1, 32)
_ADMIT_MIN_FILL = 8


class NoFreeSlotError(ServingError):
    """admit() with every slot occupied — the generation-side admission
    reject; retry after a step() frees slots."""


class Sampler(object):
    """Token-selection spec for the on-device decode loop.

    ``strategy``: ``"greedy"`` (argmax, the default), ``"temperature"``
    (softmax sampling at ``temperature``), or ``"top_k"`` (restrict to
    the ``top_k`` highest logits, then temperature-sample). Stochastic
    strategies draw from per-slot PRNG streams keyed on
    ``(seed, slot, position)`` — never the dispatch key — so a session
    rebuilt with the same ``seed`` replays bit-identical tokens
    regardless of slot assignment timing or how many tokens each
    dispatch advances."""

    def __init__(self, strategy="greedy", temperature=1.0, top_k=0,
                 seed=0):
        if strategy not in ("greedy", "temperature", "top_k"):
            raise ValueError(
                "Sampler strategy must be greedy/temperature/top_k, "
                "got %r" % (strategy,))
        if strategy == "top_k" and int(top_k) < 1:
            raise ValueError(
                "Sampler(strategy='top_k') needs top_k >= 1 — top_k=0 "
                "would silently sample the full vocabulary")
        self.strategy = strategy
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)


_active_slots = _REGISTRY.gauge(
    "paddle_tpu_serving_active_slots",
    "in-flight sequences in the slot-paged decode session")
_sequences_total = _REGISTRY.counter(
    "paddle_tpu_serving_sequences_total",
    "slot-decode sequences by lifecycle event",
    labels=("event",))  # admitted | completed
_pages_in_use = _REGISTRY.gauge(
    "paddle_tpu_serving_kv_pages_in_use",
    "KV pages currently referenced (live slots + prefix cache; paged "
    "sessions)")
_pages_per_slot = _REGISTRY.gauge(
    "paddle_tpu_serving_pages_per_slot",
    "mean KV pages held per live slot (paged sessions)")
_decode_tps = _REGISTRY.gauge(
    "paddle_tpu_serving_decode_tokens_per_sec",
    "decode tokens consumed per second of step() dispatch wall time")
_pages_shared = _REGISTRY.gauge(
    "paddle_tpu_serving_kv_pages_shared",
    "KV pages with refcount > 1 (fork/prefix sharing in flight)")
_dedup_bytes = _REGISTRY.gauge(
    "paddle_tpu_serving_kv_dedup_bytes",
    "HBM bytes deduplicated by sharing: extra page references and "
    "extra group members that would each be a physical copy unshared")
_prefix_hit_rate = _REGISTRY.gauge(
    "paddle_tpu_serving_prefix_hit_rate",
    "prefix-cache lookups that reused at least one full page / all "
    "lookups (session lifetime)")
_prefill_saved = _REGISTRY.counter(
    "paddle_tpu_serving_prefill_tokens_saved_total",
    "forced-prefix positions provisioned by reference (prefix-cache "
    "hits + group-fork joins) instead of being prefilled")
_active_beams = _REGISTRY.gauge(
    "paddle_tpu_serving_active_beams",
    "beam lanes currently decoding (beam sessions; occupancy is this "
    "over num_slots / beam_width)")
_beam_reorder_bytes = _REGISTRY.counter(
    "paddle_tpu_serving_beam_reorder_bytes_total",
    "KV bytes physically copied by beam hypothesis reorders: 0 under "
    "the rebind path for pure parent permutations, O(resident pages) "
    "per reorder under FLAGS_beam_reorder=reference")
_beam_cow = _REGISTRY.counter(
    "paddle_tpu_serving_beam_cow_copies_total",
    "copy-on-write page copies triggered by beam decode (a duplicated "
    "parent's write page splitting before the next token lands)")
_cow_dispatches = _REGISTRY.counter(
    "paddle_tpu_serving_cow_dispatches_total",
    "coalesced COW/table-rebind dispatches (one bucket-laddered "
    "executable per step window, however many pairs it carries)")
_spec_proposed = _REGISTRY.counter(
    "paddle_tpu_serving_speculative_proposed_tokens_total",
    "draft tokens proposed to the speculative verify dispatch (K per "
    "live slot per dispatch)")
_spec_accepted = _REGISTRY.counter(
    "paddle_tpu_serving_speculative_accepted_tokens_total",
    "draft tokens the target's accept walk committed (excludes the "
    "per-slot correction/bonus token every dispatch commits anyway)")
_spec_accept_rate = _REGISTRY.gauge(
    "paddle_tpu_serving_speculative_acceptance_rate",
    "accepted / proposed draft tokens, session lifetime — the lever "
    "behind speculative_speedup: committed tokens per target dispatch "
    "is 1 + rate * K")


def _check_cow_window(copies):
    """The copy program (``build_cow_batch_prog``) gathers every source
    page of a window and then scatters them onto the destinations, which
    leaves what copying the pairs IN ORDER would only if no destination
    is another pair's source or destination. A destination is a page the
    window itself acquired (``_cow_copies``, the copy-reorder oracle), so
    it is nobody's source and nobody else's destination by construction;
    this holds every window to that before it is dispatched. ``copies``:
    the window's real ``(slot, src, dst)`` pairs (the ``(0, 0)`` trash
    self-copies that pad a rung write a page onto itself)."""
    dsts = {dst for _slot, _src, dst in copies}
    if (len(dsts) != len(copies) or 0 in dsts
            or not dsts.isdisjoint(src for _slot, src, _dst in copies)):
        raise RuntimeError(
            "copy-on-write window %r: a destination page is the trash "
            "page, repeated, or another pair's source" % (copies,))


class SlotDecodeSession(object):
    """Continuous-batching decode over a slot-paged cache pool.

    Build it with the trained scope live (parameters bind by name, the
    ``build_cached_decoder`` convention) — typically under the same
    ``scope_guard`` the training/loading session used::

        sess = SlotDecodeSession(exe, num_slots=8, max_length=seq,
                                 d_model=D, src_vocab_size=V,
                                 trg_vocab_size=V, n_layer=2, n_head=2,
                                 d_inner=64)
        slot = sess.admit(src_row, src_len)   # anytime, mid-flight
        finished = sess.step()                # {slot: tokens} as they end

    ``paged=True`` uses the block-paged KV pool + ragged
    paged-attention kernel (``page_size`` tokens per page,
    ``num_pages`` total — default one trash page plus full-occupancy
    worst case) and advances ``steps`` tokens per host dispatch.
    ``num_groups`` sizes the group-pooled cross-attention K/V (default
    ``num_slots``: every solo admission gets its own group);
    ``prefix_cache_pages`` > 0 enables the forced-prefix page cache
    with that page capacity. ``sampler`` is a :class:`Sampler` (or
    dict) selecting greedy / temperature / top-k, identical semantics
    in both layouts. ``decoder_cfg`` forwards to the builder
    (``src_vocab_size``, ``trg_vocab_size``, ``n_layer``, ``n_head``,
    ``d_inner``).

    ``speculative=K`` (or ``{"k": K, "drafter": "ngram"|"model",
    ...}``; paged sampler sessions, ``steps=1``) decodes by
    draft-then-verify: a host drafter proposes K tokens per slot, ONE
    tree-attention target dispatch verifies them and commits the
    longest prefix the target itself would have sampled (1 to K + 1
    tokens per dispatch). Token streams are BIT-identical to the same
    session under ``FLAGS_speculative=off`` — the drafter only moves
    throughput, never content. See ``serving/speculative.py`` and
    docs/SERVING.md "Speculative decode".
    """

    # the set-up ledger (docs/OBSERVABILITY.md): the root span; under it
    # one span a program family and member, around its building and,
    # where the constructor makes it, its first run
    @_explain.spanned("session.init")
    def __init__(self, exe, num_slots, max_length=64, d_model=128,
                 bos_id=1, eos_id=2, scope=None, paged=False,
                 page_size=8, num_pages=None, num_groups=None, steps=1,
                 sampler=None, prefix_cache_pages=0, degradation=None,
                 beam_width=1, speculative=None, **decoder_cfg):
        from paddle_tpu.models import transformer

        self._transformer = transformer
        self._exe = exe
        self._scope = scope
        self._S, self._T, self._D = int(num_slots), int(max_length), \
            int(d_model)
        self._bos, self._eos = int(bos_id), int(eos_id)
        self._paged = bool(paged)
        self._steps = max(1, int(steps))
        self._sampler = sampler
        self._n_layer = int(decoder_cfg.get("n_layer", 2))
        self._n_head = int(decoder_cfg.get("n_head", 4))
        # speculative decode config: int K (n-gram drafter) or a dict
        # {"k": K, "drafter": "ngram"|"model", ...drafter kwargs}
        if speculative is None:
            spec_cfg = {}
        elif isinstance(speculative, dict):
            spec_cfg = dict(speculative)
        else:
            spec_cfg = {"k": int(speculative)}
        self._spec_cfg = spec_cfg
        self._spec_k = int(spec_cfg.get("k", 0) or 0)
        self.spec_proposed = 0    # draft tokens offered
        self.spec_accepted = 0    # draft tokens committed
        self.spec_dispatches = 0  # verify dispatches run
        if self._spec_k < 0:
            raise ValueError("speculative k must be >= 0 (0 disables), "
                             "got %d" % self._spec_k)
        if self._spec_k:
            if not self._paged:
                raise ValueError(
                    "speculative decode needs paged=True — the tree "
                    "writes/compaction ARE page-table operations")
            if int(steps) != 1:
                raise ValueError(
                    "speculative decode needs steps=1: drafting and "
                    "accept bookkeeping happen on the host BETWEEN "
                    "dispatches (each dispatch already advances up to "
                    "k + 1 tokens)")
            if int(beam_width) > 1:
                raise ValueError(
                    "speculative decode verifies the sampler stream — "
                    "it does not compose with beam_width > 1")
        self._beam_width = int(beam_width)
        if self._beam_width < 1:
            raise ValueError("beam_width must be >= 1, got %d"
                             % self._beam_width)
        if self._beam_width > 1:
            if not self._paged:
                raise ValueError(
                    "beam_width > 1 needs paged=True — the zero-copy "
                    "reorder IS the page-table indirection")
            if int(steps) != 1:
                raise ValueError(
                    "beam_width > 1 needs steps=1: the reorder's "
                    "refcount rebinds (and COW of a duplicated "
                    "parent's write page) happen on the host BETWEEN "
                    "dispatches — a multi-token scan would write "
                    "through unprovisioned, un-COWed rows")
            if self._S % self._beam_width:
                raise ValueError(
                    "beam_width=%d does not tile num_slots=%d into "
                    "aligned beam lanes"
                    % (self._beam_width, self._S))
        if self._paged:
            from paddle_tpu.kernels.paged_attention import pages_for

            self._pages_for = pages_for
            self._ps = int(page_size)
            self._npp = pages_for(self._T, self._ps)
            self._P = (int(num_pages) if num_pages
                       else 1 + self._S * self._npp)
            self._G = int(num_groups) if num_groups else self._S
            if self._P < 1 + self._npp:
                raise ValueError(
                    "num_pages=%d cannot cover even ONE sequence: the "
                    "pool needs 1 trash page + ceil(max_length / "
                    "page_size) = %d pages, or every admit() would "
                    "fail its reservation" % (self._P, 1 + self._npp))
            built = transformer.build_paged_slot_decoder(
                num_slots, max_length=max_length, d_model=d_model,
                page_size=self._ps, num_pages=self._P,
                num_groups=self._G, bos_id=bos_id, eos_id=eos_id,
                sampler=sampler, beam_width=self._beam_width,
                speculative=self._spec_k, **decoder_cfg)
            if self._spec_k:
                (self._init_prog, self._admit_prog, self._join_prog,
                 self._prefill_prog, self._table_prog, self._step_prog,
                 self._spec_prog, spec_fetches) = built
                self._spec_fetches = dict(spec_fetches)
                self._fetch_name = self._spec_fetches["token"]
            else:
                (self._init_prog, self._admit_prog, self._join_prog,
                 self._prefill_prog, self._table_prog,
                 self._step_prog, self._fetch_name) = built
            if self._beam_width > 1:
                # the beam builder returns a fetch-name DICT (token /
                # parent / score / logits); the session fetches the
                # first three every step
                self._beam_fetches = dict(self._fetch_name)
                self._fetch_name = self._beam_fetches["token"]
            pe = transformer.position_encoding_table(self._T, self._D)
            # the pools' device allocations: the init program fills them
            with _explain.setup_span("pools"):
                self._run(self._init_prog, {"pe_table": pe}, [])
            # page 0 is the trash page: never allocated, every
            # unoccupied slot's table row points at it. Pages carry
            # refcounts (kv_pool.PagePool): shared pages free only when
            # the LAST reference drops, and a refcount > 1 means
            # read-only — writes copy first (_cow_copies).
            self._pool = PagePool(self._P)
            self._prefix_cache = (
                PrefixCache(self._pool, self._ps,
                            max_pages=int(prefix_cache_pages))
                if prefix_cache_pages else None)
            self._slot_pages = {}  # slot -> [page ids], ordered by index
            self._slot_group = {}  # slot -> group id
            # host mirror of the device's (group_of [S], source length
            # [G]) for the round's cross-attention counts; None = read
            # it from the scope at the next traced dispatch
            self._cross_view = None
            self._free_groups = list(range(self._G - 1, -1, -1))
            self._group_members = {}  # group id -> set(slot)
            # reservation-based admission control: every live slot has
            # its WORST-CASE pages reserved (a counter, not physical
            # pages — allocation stays lazy), so mid-flight _provision
            # and COW copies can never fail and an oversubscribed pool
            # rejects at admit() instead of wedging at step(). Pages
            # held only by the prefix cache don't count against
            # reservations: the cache evicts under free-list pressure
            # (PagePool.acquire's reclaim hook). Pages LEAKED by failed
            # rollback/COW dispatches (kept allocated so a possibly-
            # committed device row can never corrupt a recycled page)
            # are not reclaimable, so they shrink the capacity bound.
            self._reserved_pages = 0
            self._leaked_pages = 0
            # which pages the leak count abandoned (refcounts held but
            # no slot/trie holder): the decode snapshot records them so
            # offline refcount verification (ckpt_inspect --verify) can
            # tell a by-design leak from a torn snapshot
            self._leaked_page_ids = set()
            # coalesced COW dispatch machinery: one bucket-laddered
            # executable per step window (build_cow_batch_prog), rung =
            # smallest ladder entry >= the window's pair count. Rung
            # programs build lazily and content-address across
            # sessions; the ladder follows the suggest_buckets rung
            # discipline so the executable set is finite and warm.
            from paddle_tpu.analysis.lint import suggest_buckets

            worst_pairs = max(
                1, self._S * (1 + (self._steps - 1) // self._ps + 1))
            self._cow_rungs = suggest_buckets([1, worst_pairs],
                                              max_buckets=4)
            self._cow_progs = {}
            self.cow_dispatches = 0   # coalesced dispatch count (tests)
            self.cow_pairs = 0        # real COW pairs dispatched
            # eager rung warmup: every ladder executable compiles (and
            # lands in the exec cache) at session BUILD, via a pad-only
            # window — trash-page self-copies bound to slot 0's (still
            # trash) table row, bit-neutral by construction. The
            # zero-recompile steady state must not depend on which
            # window sizes churn happens to produce first.
            for rung in self._cow_rungs:
                with _explain.setup_span("cow/%d" % rung):
                    self._run(self._cow_prog(rung), {
                        "src_pages": np.zeros(rung, "int64"),
                        "dst_pages": np.zeros(rung, "int64"),
                        "slot_idxs": np.zeros(rung, "int64"),
                        "page_rows": np.zeros((rung, self._npp), "int64"),
                    }, [])
            # batched admission (admit_pending): one encoder dispatch for
            # the head run of the queue, its row count a rung of a short
            # ladder. Every rung is built and warmed HERE by an
            # all-padding call (rows whose slot and group index lie past
            # the pools' ends: the scatter drops them, no ``pgd_`` array
            # changes) — a server that enqueues one request at a time
            # while it warms up would otherwise compile the larger rungs
            # under its first burst. Beam sessions admit by lane
            # (admit_beam) and keep no ladder.
            self._admit_progs = {1: self._admit_prog}
            self.admit_dispatches = 0  # encoder dispatches run (tests)
            self.admit_rows = 0        # sources they encoded
            top = min(_ADMIT_RUNGS[-1], self._S)
            self._rungs = tuple([r for r in _ADMIT_RUNGS if r < top] + [top])
            self._admit_rungs = () if self._beam_width > 1 else self._rungs
            # batched release (_release_many): the slots ONE cancel_many,
            # one dispatch's finishers or one rollback give up are
            # pointed at the trash page by one table dispatch of the same
            # ladder (rung 1 is the builder's own ``table_prog``), each
            # rung built and warmed here by an all-padding call too
            self._table_progs = {1: self._table_prog}
            self.release_dispatches = 0  # table dispatches run (tests)
            self.release_rows = 0        # slots they repointed
            self.release_pad_rows = 0    # rows of padding beside them
            for rung in self._rungs[1:]:
                with _explain.setup_span("release/%d" % rung):
                    self._table_progs[rung] = \
                        transformer.build_table_batch_prog(
                            rung, self._S, max_length=self._T,
                            page_size=self._ps)
                    self._run(self._table_progs[rung],
                              self._trash_feed((), rung), [])
            for rung in self._admit_rungs[1:]:
                with _explain.setup_span("admit/%d" % rung):
                    self._admit_progs[rung] = \
                        transformer.build_admit_batch_prog(
                            rung, self._S, max_length=self._T,
                            d_model=self._D, page_size=self._ps,
                            num_groups=self._G,
                            **{k: v for k, v in decoder_cfg.items()
                               if k != "trg_vocab_size"})
                    self._run(self._admit_progs[rung],
                              self._admit_feed((), rung), [])
            # beam bookkeeping (beam_width > 1): lanes of K aligned
            # slots; per-step parent permutations mirrored here
            self._beam_live = {}      # lane -> {"slots": [...]}
            self._free_lanes = list(
                range(self._S // self._beam_width - 1, -1, -1)) \
                if self._beam_width > 1 else []
            self._last_parents = {}   # lane -> last local parent perm
            self._beam_events = {}    # lane -> last step's wire event
            self._last_finished_beams = {}  # lane -> n-best payload
            self._beam_owner = {}     # lane -> request id (wire/bank)
            self._beam_results = {}   # rid -> {"tokens", "scores"}
            self.beam_reorder_pages = 0  # physical page copies, reorder
            self.beam_cow_copies = 0     # COW splits charged to beam
            # speculative decode plumbing: the drafter, the (static)
            # chain-tree feeds, and the acceptance books. The plain
            # step program stays built and warm — FLAGS_speculative is
            # read at EVERY step, so the off-oracle flips mid-session
            # with zero recompiles on either side.
            self._spec_drafter = None
            if self._spec_k:
                from paddle_tpu.serving import speculative as _spec_mod

                kind = str(spec_cfg.get("drafter", "ngram"))
                if kind == "ngram":
                    self._spec_drafter = _spec_mod.NgramDrafter(
                        self._S, self._spec_k, eos_id=self._eos,
                        order=int(spec_cfg.get("order", 3)))
                elif kind == "model":
                    self._spec_drafter = _spec_mod.DraftModelDrafter(
                        exe, self._S, self._spec_k,
                        trg_vocab_size=int(decoder_cfg.get(
                            "trg_vocab_size", 1000)),
                        max_length=self._T, n_head=self._n_head,
                        d_model=self._D, page_size=self._ps,
                        num_pages=self._P, eos_id=self._eos,
                        scope=scope,
                        d_inner=spec_cfg.get("draft_d_inner"))
                else:
                    raise ValueError(
                        "speculative drafter must be 'ngram' or "
                        "'model', got %r" % (kind,))
                parent, anc = _spec_mod.chain_tree(self._spec_k)
                n_nodes = self._spec_k + 1
                self._spec_parent = np.tile(parent[None, :],
                                            (self._S, 1))
                self._spec_anc = np.tile(anc[None, :, :],
                                         (self._S, 1, 1))
                self._spec_nodes = n_nodes
        else:
            if steps != 1:
                raise ValueError(
                    "multi-token dispatch (steps > 1) needs paged=True "
                    "— the dense step program is not a self-contained "
                    "loop body")
            if prefix_cache_pages or num_groups:
                raise ValueError(
                    "prefix_cache_pages / num_groups need paged=True — "
                    "the dense layout has no shareable KV state")
            (self._init_prog, self._admit_prog, self._step_prog,
             self._fetch_name) = transformer.build_slot_decoder(
                num_slots, max_length=max_length, d_model=d_model,
                eos_id=eos_id, sampler=sampler, **decoder_cfg)
            with _explain.setup_span("pools"):
                self._run(self._init_prog, {}, [])
            self._admit_rungs = ()  # the dense layout admits one by one
        self._free = list(range(self._S - 1, -1, -1))
        self._live = {}  # slot -> {"trg": [T] int64, "pos": int}
        # session-level request queue: generate() drains it, snapshot
        # captures it — a preempted process restores WITH its backlog
        self._pending = deque()  # {"id", "src" [1,T], "len", "prefix"}
        self._owner = {}         # slot -> request id
        self._results = {}       # request id -> [T] tokens, until taken
        self._next_req = 0
        self.steps_done = 0      # step() dispatches completed (chaos key)
        # whoever drives step() may leave a callable here: the decode
        # dispatch runs it once between its launch and its wait (the
        # decode worker's event flush; run_multi_step's ``in_flight``)
        self.in_flight = None
        # request tracing (observability/tracing.py): rid -> trace id
        # rides the decode snapshot, so a restored process re-emits its
        # banked streams under the ORIGINAL ids; slot -> Trace is
        # runtime rebind state admissions rebuild. Both stay empty with
        # FLAGS_request_tracing off — every hot-path hook gates on that.
        self._trace_ids = {}
        self._slot_traces = {}
        self._trace_cow = {}     # slot -> COW copies this step window
        # preemption plumbing: public ops run inside a dispatch window;
        # serving/snapshot.py's manager defers a SIGTERM snapshot until
        # the window closes (host mirrors and device state consistent)
        self._dispatch_depth = 0
        self._after_dispatch = None
        # graceful degradation (serving/degradation.py), opt-in: None
        # keeps the hard typed rejects (NoFreeSlot/NoFreePage) as the
        # only admission control, exactly the pre-PR-13 behavior
        if degradation is not None:
            from paddle_tpu.serving.degradation import HealthMonitor

            cfg = dict(degradation) if isinstance(degradation, dict) \
                else {}
            cfg.setdefault("on_transition", self._on_health_transition)
            self._monitor = HealthMonitor("decode", **cfg)
        else:
            self._monitor = None

    def _run(self, prog, feed, fetch_list):
        # with tracing on and a decode worker's round open, each
        # executor call is a child ``<open span>.dispatch``
        with _tracing.span(".dispatch"):
            return self._exe.run(prog, feed=feed, fetch_list=fetch_list,
                                 scope=self._scope)

    # -- preemption / degradation plumbing ----------------------------------
    def _begin_op(self):
        self._dispatch_depth += 1

    def _end_op(self):
        self._dispatch_depth -= 1
        if self._dispatch_depth == 0 and self._after_dispatch is not None:
            # the quiesce point: the snapshot manager banks a final
            # snapshot / runs a periodic one here, never mid-dispatch
            self._after_dispatch()

    @property
    def step_program(self):
        """The decode-step Program every ``step()`` dispatches — the
        handle for ``Executor.compiled_text`` (which attention kernel
        the compiled step really holds)."""
        return self._step_prog

    @property
    def in_dispatch(self):
        """True while a public op (admit/step) is mutating state — the
        window a preemption snapshot must NOT land inside."""
        return self._dispatch_depth > 0

    def _health_load(self, taking=0):
        """Load fraction the degradation monitor keys on: page
        occupancy (reservations over the leak-shrunk capacity) and slot
        occupancy, whichever is tighter. ``taking`` counts admissions
        the caller has decided on and not made yet (a batch's earlier
        members), so a batch's k-th request sees the load sequential
        admission would have shown it."""
        slot_load = (len(self._live) + taking) / float(self._S)
        if not self._paged:
            return slot_load
        cap = max(1, self._P - 1 - self._leaked_pages)
        reserved = (self._reserved_pages
                    + taking * self._pages_for(self._T, self._ps))
        return max(slot_load, reserved / float(cap))

    def _on_health_transition(self, frm, to):
        from paddle_tpu.serving.degradation import BROWNOUT, HEALTHY

        if frm == HEALTHY and to == BROWNOUT:
            # brownout's first act: give cached-but-idle pages back to
            # the free list so live admissions stop competing with the
            # prefix cache for capacity
            self.clear_prefix_cache()

    def _gate_admission(self, n, taking=0):
        """Degradation gate, BEFORE any slot/page/queue mutation (a
        degraded reject is never a partial admission) and OUTSIDE the
        classified-retry wrap (a shed session must answer the caller
        immediately with the retry-after hint, not burn the in-process
        retry budget sleeping on itself)."""
        if self._monitor is None:
            return
        from paddle_tpu.serving.degradation import BROWNOUT, SHED

        state = self._monitor.observe(self._health_load(taking))
        if state == SHED:
            raise self._monitor.reject("admission (draining in-flight)")
        if state == BROWNOUT and n > 1:
            raise self._monitor.reject(
                "fork admission (n=%d) — brownout serves n=1 only" % n)

    @property
    def health(self):
        """Degradation state ('healthy' when the monitor is off)."""
        from paddle_tpu.serving.degradation import HEALTHY

        return self._monitor.state if self._monitor is not None \
            else HEALTHY

    # -- paged pool management ----------------------------------------------
    def _page_row(self, pages):
        """A slot's [npp] table row: its pages, the tail aliased to the
        LAST valid page so the kernel's skipped grid steps repeat the
        previous block index (the DMA-elision contract) — or the trash
        page for a row with no pages."""
        row = list(pages) if pages else [0]
        row = row + [row[-1]] * (self._npp - len(row))
        return np.asarray([row], dtype="int64")

    def _acquire_page(self):
        reclaim = (self._prefix_cache.reclaim
                   if self._prefix_cache is not None else None)
        return self._pool.acquire(reclaim)

    def _provision(self, slot, length):
        """Grow ``slot``'s page list to cover ``length`` resident
        tokens; returns True when the table row changed. Cannot fail:
        admit() reserved the slot's worst-case pages up front."""
        pages = self._slot_pages[slot]
        need = self._pages_for(min(int(length), self._T), self._ps)
        grew = False
        while len(pages) < need:
            pages.append(self._acquire_page())
            grew = True
        return grew

    def _cow_copies(self, slot, pos, pending=None, span=None):
        """Copy-on-write scan for one dispatch: every page this slot
        will WRITE in positions ``[pos, pos + steps)`` that is still
        shared (refcount > 1 — a fork sibling or the prefix cache
        holds it) is swapped for a freshly acquired private page.
        Returns [(src, dst)] pairs to copy; the slot's page list is
        already repointed. Shared pages are thereby immutable: no slot
        ever writes a page another reference can read.

        ``pending`` maps src page -> derefs already PLANNED by earlier
        pairs of the same coalesced window (the window derefs only
        after its one dispatch lands): the LAST planned holder still
        writes in place, exactly as the sequential per-pair path did —
        N sharers cost N-1 copies, not N."""
        pages = self._slot_pages[slot]
        span = self._steps if span is None else int(span)
        first = int(pos) // self._ps
        last = min(int(pos) + span - 1, self._T - 1) // self._ps
        copies = []
        pending = pending if pending is not None else {}
        for i in range(first, min(last + 1, len(pages))):
            pg = pages[i]
            if self._pool.refcount(pg) - pending.get(pg, 0) > 1:
                dst = self._acquire_page()
                copies.append((pg, dst))
                pages[i] = dst
                pending[pg] = pending.get(pg, 0) + 1
        return copies

    def _cow_prog(self, rung):
        prog = self._cow_progs.get(rung)
        if prog is None:
            prog = self._transformer.build_cow_batch_prog(
                self._S, self._T, self._n_layer, self._n_head,
                self._D, self._ps, self._P, rung)
            self._cow_progs[rung] = prog
        return prog

    def _dispatch_cow(self, window):
        """ONE coalesced dispatch for a step window's COW pairs and
        growth rebinds. ``window`` is ``[(slot, src, dst)]`` —
        ``(slot, 0, 0)`` entries are rebind-only (a provisioned slot
        whose row grew; the trash-page self-copy they pad the bucket
        with is bit-neutral). The window pads up the rung ladder, every
        copy lands before any repoint, and each slot's FINAL row rides
        the same executable — the per-pair copy_prog's atomicity,
        without its per-pair dispatch tax. The program reads every
        source page before it writes any destination
        (``_check_cow_window`` holds the window to what makes that equal
        to copying in order; a window it refuses is a failed dispatch).

        A FAILED dispatch may or may not have committed device-side, so
        the host restores every shared source in its slot's row
        (consistent with an uncommitted dispatch) and LEAKS every
        destination page of the window (never freed — if the dispatch
        DID commit, the device rows point at them, and recycling would
        hand a future sequence a page a stale row still writes; if it
        didn't, the copies' writes can only land in pages nobody else
        owns). Same corruption-beats-capacity rule as
        ``_rollback_admission``; leaked pages shrink the admission
        capacity bound."""
        if not window:
            return
        n = len(window)
        rung = next((r for r in self._cow_rungs if r >= n),
                    self._cow_rungs[-1])
        if rung < n:  # window above the top rung: split it
            self._dispatch_cow(window[:rung])
            self._dispatch_cow(window[rung:])
            return
        pad_slot = window[0][0]
        entries = list(window) + [(pad_slot, 0, 0)] * (rung - n)
        feed = {
            "src_pages": np.asarray([e[1] for e in entries], "int64"),
            "dst_pages": np.asarray([e[2] for e in entries], "int64"),
            "slot_idxs": np.asarray([e[0] for e in entries], "int64"),
            "page_rows": np.concatenate(
                [self._page_row(self._slot_pages[e[0]])
                 for e in entries], axis=0),
        }
        copies = [(s, src, dst) for s, src, dst in window
                  if not (src == 0 and dst == 0)]
        try:
            _check_cow_window(copies)
            self._run(self._cow_prog(rung), feed, [])
        except BaseException:
            for slot, src_pg, dst_pg in copies:
                pages = self._slot_pages[slot]
                pages[pages.index(dst_pg)] = src_pg
                self._leaked_pages += 1  # stays allocated forever
                self._leaked_page_ids.add(dst_pg)
            raise
        for _slot, src_pg, _dst in copies:
            self._pool.deref(src_pg)
        if self._slot_traces and copies:
            # per-slot COW attribution for the step window's traces
            # (cleared by step() before each dispatch window opens)
            for slot, _src, _dst in copies:
                self._trace_cow[slot] = self._trace_cow.get(slot, 0) + 1
        self.cow_dispatches += 1
        self.cow_pairs += len(copies)
        _cow_dispatches.inc()

    def _trash_feed(self, slots, rung):
        """A table program's feed that points ``slots``' rows at the
        trash page, padded to ``rung`` rows. A row of padding carries a
        slot index past the table's end, so the scatter drops it."""
        return {
            "slot_idx": np.asarray(
                list(slots) + [self._S] * (rung - len(slots)), "int64"),
            "page_row": np.zeros((rung, self._npp), "int64"),
        }

    def _repoint(self, slots):
        """Point the table rows of ``slots`` at the trash page: ONE
        dispatch of the smallest rung that holds them (a lone slot goes
        through ``table_prog`` itself), in chunks above the top rung. A
        dispatch that fails past the retry budget may or may not have
        landed, so every slot of it LEAKS its pages (recorded, never
        freed, the capacity bound shrunk: a stale row could still write
        them) -- its ``_slot_pages`` entry is gone when this returns."""
        top = self._rungs[-1]
        for at in range(0, len(slots), top):
            chunk = slots[at:at + top]
            rung = self._rung_of(len(chunk))
            try:
                self._run(self._table_progs[rung],
                          self._trash_feed(chunk, rung), [])
            except BaseException as exc:
                for slot in chunk:
                    pages = set(self._slot_pages.pop(slot))
                    self._leaked_pages += len(
                        pages - self._leaked_page_ids)
                    self._leaked_page_ids.update(pages)
                if not isinstance(exc, Exception):
                    raise  # an interrupt or exit is not ours to absorb
                continue
            self.release_dispatches += 1
            self.release_rows += len(chunk)
            self.release_pad_rows += rung - len(chunk)

    def _update_pool_gauges(self):
        in_use = self._pool.allocated_count
        _pages_in_use.set(in_use)
        _pages_per_slot.set(in_use / len(self._live) if self._live
                            else 0.0)
        _pages_shared.set(self._pool.shared_count)
        dh = self._D // self._n_head
        page_bytes = 2 * self._n_layer * self._n_head * self._ps * dh * 4
        cross_bytes = 2 * self._n_layer * self._n_head * self._T * dh * 4
        extra_members = sum(
            len(m) - 1 for m in self._group_members.values())
        _dedup_bytes.set(self._pool.extra_refs * page_bytes
                         + extra_members * cross_bytes)
        if self._prefix_cache is not None:
            _prefix_hit_rate.set(self._prefix_cache.hit_rate)

    def _note_cross(self, slot, gid, src_len=None):
        """An admission dispatch landed: ``slot`` reads group ``gid``
        (whose source is ``src_len`` long, when this dispatch wrote it)."""
        if self._cross_view is not None:
            group_of, lengths = self._cross_view
            group_of[slot] = gid
            if src_len is not None:
                lengths[gid] = src_len

    def _count_cross(self, calls):
        """The round's ``cross_blocks_read`` / ``cross_blocks_grid``:
        K/V blocks the dispatch's ``calls`` cross-attention kernel calls
        copy, and the grid steps they run, by the kernel's own grid
        rules (kernels/cross_attention_decode.grid_accounting) over the
        device's ``group_of`` and source lengths as the host mirrors
        them and the slots that hold a stream as the dispatch starts
        (``_live``, a beam's done hypotheses left out) — a released slot
        keeps its group and is dead to the kernel, whose step program
        reads the same from ``pgd_done`` and the slot's table row. Call
        under ``tracing.ENABLED``."""
        from paddle_tpu.kernels.cross_attention_decode import (
            grid_accounting)

        if self._cross_view is None:
            scope = self._scope
            if scope is None:
                from paddle_tpu.executor import global_scope

                scope = global_scope()
            self._cross_view = (
                np.array(scope.get_value("pgd_group_of"),
                         dtype="int64").reshape(-1),
                (np.asarray(scope.get_value("pgd_src_mask")) > 0).sum(
                    axis=-1).astype("int64"))
        group_of, lengths = self._cross_view
        live = np.zeros(self._S, bool)
        live[[s for s, st in self._live.items()
              if not st.get("done")]] = True
        acct = grid_accounting(group_of, lengths, self._n_head, self._T,
                               self._D // self._n_head, live=live)
        _tracing.round_count("cross_blocks_read",
                             calls * acct["blocks_read"])
        _tracing.round_count("cross_blocks_grid",
                             calls * acct["grid_steps"])

    def _release_many(self, slots):
        """Recycle the references of ``slots`` (finished or cancelled,
        already out of ``_live``): EVERY table row is pointed back at
        the trash page FIRST, by one dispatch (the still-stepping done
        slots' writes must never land in a recycled page, and no other
        dispatch may run before the repoint), then slot by slot in the
        order given every page reference drops -- a page frees only when
        its LAST reference (fork sibling or prefix-cache entry) goes --
        and the slot's group loses a member; the group id frees with its
        last member. The pool's free list and the group stack end as
        releasing the slots one by one would leave them. A slot whose
        repoint failed keeps its pages allocated (``_repoint``); its
        books close all the same, so it re-admits cleanly."""
        self._repoint(slots)
        drafter = getattr(self, "_spec_drafter", None)
        worst = self._pages_for(self._T, self._ps)
        for slot in slots:
            for pg in self._slot_pages.pop(slot, ()):
                self._pool.deref(pg)
            if drafter is not None:
                # the slot's next occupant must not inherit this one's
                # draft-cache watermark
                drafter.forget(slot)
            gid = self._slot_group.pop(slot, None)
            members = self._group_members.get(gid)
            if members is not None:
                members.discard(slot)
                if not members:
                    del self._group_members[gid]
                    self._free_groups.append(gid)
            self._reserved_pages -= worst

    @property
    def free_pages(self):
        """Unallocated KV pages (paged sessions; trash page excluded)."""
        return self._pool.free_count if self._paged else 0

    @property
    def pages_in_use(self):
        """Pages referenced by live slots or the prefix cache."""
        return self._pool.allocated_count if self._paged else 0

    @property
    def shared_pages(self):
        """Pages with refcount > 1 (fork/prefix sharing in flight)."""
        return self._pool.shared_count if self._paged else 0

    @property
    def cached_pages(self):
        """Distinct pages the prefix cache holds references on."""
        return (self._prefix_cache.pages
                if self._paged and self._prefix_cache is not None else 0)

    @property
    def free_groups(self):
        return len(self._free_groups) if self._paged else 0

    @property
    def pool_conserved(self):
        """The page-pool conservation law, live: ``free +
        unique-allocated == P - 1`` (True for dense sessions, which
        have no pool). The number every teardown path — release,
        rollback, disconnect cancellation — must leave intact."""
        if not self._paged:
            return True
        return (self._pool.free_count + self._pool.allocated_count
                == self._pool.num_pages - 1)

    def prefix_cache_stats(self):
        """{'lookups', 'hits', 'hit_rate', 'tokens_saved', 'pages'} —
        zeros when the cache is disabled."""
        c = self._prefix_cache if self._paged else None
        if c is None:
            return {"lookups": 0, "hits": 0, "hit_rate": 0.0,
                    "tokens_saved": 0, "pages": 0}
        return {"lookups": c.lookups, "hits": c.hits,
                "hit_rate": c.hit_rate, "tokens_saved": c.tokens_saved,
                "pages": c.pages}

    def clear_prefix_cache(self):
        """Drop every cached prefix page (references released; pages
        free once no live slot shares them)."""
        if self._paged and self._prefix_cache is not None:
            self._prefix_cache.clear()
            self._update_pool_gauges()

    def _take_slot(self):
        """Claim the LOWEST-numbered free slot. Deterministic placement
        is part of the seeded-sampling story: the PRNG stream is keyed
        on (seed, slot, position), so two runs that admit the same
        requests in the same order must land them on the same slots for
        their sampled tokens to be bit-identical (the
        ``FLAGS_speculative`` on/off oracle relies on this). A plain
        ``list.pop()`` would hand out slots in RELEASE order, which
        depends on completion timing."""
        slot = min(self._free)
        self._free.remove(slot)
        return slot

    # -- lifecycle -----------------------------------------------------------
    @property
    def free_slots(self):
        return len(self._free)

    @property
    def active_slots(self):
        return sorted(self._live)

    @staticmethod
    def _src_fp(src, length):
        """Prefix-cache source fingerprint: prefix K/V past layer 0
        depends on the source (cross attention feeds every decoder
        layer), so cached pages are keyed by source content too."""
        h = hashlib.sha256(np.ascontiguousarray(src).tobytes())
        h.update(str(int(length)).encode())
        return h.hexdigest()

    def _full_prefix(self, prefix_tokens):
        prefix = [self._bos] + [int(t) for t in (prefix_tokens or ())]
        if len(prefix) > self._T - 1:
            raise ValueError(
                "prefix_tokens too long: bos + %d forced tokens leave "
                "no position to sample (max_length=%d)"
                % (len(prefix) - 1, self._T))
        return prefix

    def admit(self, src, src_len=None, prefix_tokens=None):
        """Claim a free slot for one source sequence (``src``: [T] or
        [1, T] int ids; ``src_len``: its true length, default T) and
        run the admission program — encoder forward + scatter into the
        slot's pool rows. ``prefix_tokens`` (paged sessions) forces a
        decoder prefix: the slot starts sampling AFTER the forced
        tokens, whose K/V is provisioned from the prefix cache where
        possible and chunked-prefilled otherwise. Returns the slot id.
        Raises :class:`NoFreeSlotError` when every slot is occupied
        (and, for paged sessions, :class:`NoFreePageError` /
        :class:`NoFreeGroupError` when the KV pool or group pool
        cannot cover the admission)."""
        if not self._paged:
            if prefix_tokens is not None:
                raise ValueError(
                    "prefix_tokens needs paged=True — the dense layout "
                    "has no prefill program")
            return self._admit_dense(src, src_len)
        return self.admit_group(src, n=1, src_len=src_len,
                                prefix_tokens=prefix_tokens)[0]

    def _admit_dense(self, src, src_len):
        self._gate_admission(1)
        self._begin_op()
        try:
            return _retry.call(
                lambda: self._admit_dense_attempt(src, src_len),
                origin="serve.admit")
        finally:
            self._end_op()

    def _admit_dense_attempt(self, src, src_len):
        if not self._free:
            raise NoFreeSlotError(
                "all %d slots occupied; step() until one frees"
                % self._S)
        src = np.asarray(src, dtype="int64").reshape(1, self._T)
        length = self._T if src_len is None else int(np.ravel(src_len)[0])
        slot = self._take_slot()
        feed = {
            "src_word": src,
            "src_len": np.asarray([[length]], dtype="int64"),
            "slot_idx": np.asarray([slot], dtype="int64"),
        }
        try:
            if _chaos.ENABLED:
                _chaos.fault("serve.admit")
            self._run(self._admit_prog, feed, [])
        except BaseException:
            # a failed admission dispatch (transient OOM, chaos fault,
            # interrupt) must not leak the slot — and the restored pop
            # order means a classified retry re-admits into the SAME
            # slot, keeping (seed, slot, position) PRNG streams intact
            self._free.append(slot)
            raise
        trg = np.full(self._T, self._eos, dtype="int64")
        trg[0] = self._bos
        self._live[slot] = {"trg": trg, "pos": 0}
        _sequences_total.inc(event="admitted")
        _active_slots.set(len(self._live))
        return slot

    def admit_group(self, src, n=1, src_len=None, prefix_tokens=None):
        """Admit ``n`` sampled continuations of ONE source as a fork
        group (paged sessions): one encoder forward, one group-pooled
        set of cross-attention K/V rows shared by every member, and —
        with a forced prefix — one chunked prefill whose pages every
        member references until copy-on-write splits their tails.
        Members are admitted into consecutively popped slots, so a
        seeded sampled member is bit-identical to an unshared session
        admitting the same members solo (same slot => same
        ``(seed, slot, position)`` PRNG stream). Returns the member
        slot ids in admission order. Any mid-admission failure rolls
        the whole group back (table rows to the trash page FIRST, then
        references, slots, group and reservations)."""
        if not self._paged:
            raise ValueError(
                "admit_group needs paged=True — the dense layout has "
                "no shareable KV state")
        if self._beam_width > 1:
            raise ValueError(
                "this is a beam session (beam_width=%d): slots are "
                "lane-tiled — admissions go through admit_beam()"
                % self._beam_width)
        n = int(n)
        if n < 1:
            raise ValueError("admit_group needs n >= 1, got %d" % n)
        self._gate_admission(n)
        self._begin_op()
        try:
            # classified retry around the whole admission attempt: a
            # transient fault mid-admission rolls the group back (free
            # stacks restored in pop order), so the retried attempt
            # lands in the SAME slots/pages — bit-exact with a run that
            # never saw the fault. Typed rejects (NoFreeSlot/NoFreePage/
            # NoFreeGroup) are not transient and surface immediately.
            return _retry.call(
                lambda: self._admit_group_attempt(
                    src, n, src_len, prefix_tokens),
                origin="serve.admit")
        finally:
            self._end_op()

    def _admit_group_attempt(self, src, n, src_len, prefix_tokens,
                             slots_override=None):
        if slots_override is None and len(self._free) < n:
            raise NoFreeSlotError(
                "admit_group(n=%d): only %d of %d slots free; step() "
                "until more free" % (n, len(self._free), self._S))
        # beam admission hands the LANE's aligned slots in; the caller
        # already removed them from the free stack (and restores the
        # lane if this attempt rolls back)
        pending_slots = (deque(slots_override)
                         if slots_override is not None else None)
        beam = self._beam_width > 1
        if not self._free_groups:
            raise NoFreeGroupError(
                "all %d cross-K/V groups occupied; step() until a "
                "group's last member completes" % self._G)
        src = np.asarray(src, dtype="int64").reshape(1, self._T)
        length = self._T if src_len is None else int(np.ravel(src_len)[0])
        prefix = self._full_prefix(prefix_tokens)
        L = len(prefix)
        worst = self._pages_for(self._T, self._ps)
        capacity = self._P - 1 - self._leaked_pages
        if self._reserved_pages + n * worst > capacity:
            raise NoFreePageError(
                "KV pool cannot reserve %d pages for %d new "
                "sequence(s) (%d of %d already reserved); step() until "
                "a sequence completes"
                % (n * worst, n, self._reserved_pages, capacity))
        self._reserved_pages += n * worst
        gid = self._free_groups.pop()
        slots = []
        start_feed = {
            "group_idx": np.asarray([gid], dtype="int64"),
            "start_tok": np.asarray([[prefix[-1]]], dtype="int64"),
            "start_pos": np.asarray([[L - 1]], dtype="int64"),
        }
        # decode-ahead coverage for the first dispatch: prefill writes
        # positions [0, L-1), the first step() writes [L-1, L-1+steps)
        cover = min(L - 1 + self._steps, self._T)
        k_full = (L - 1) // self._ps  # prefix pages that end up FULL
        try:
            # -- member 0: encoder forward + (any) prefill ------------------
            slot0 = (pending_slots.popleft() if pending_slots is not None
                     else self._take_slot())
            slots.append(slot0)
            cached = []
            if self._prefix_cache is not None and L > 1:
                cached = self._prefix_cache.lookup(
                    self._src_fp(src, length), prefix)[:k_full]
            pages = []
            for pg in cached:
                self._pool.ref(pg)
                pages.append(pg)
            self._slot_pages[slot0] = pages
            self._slot_group[slot0] = gid
            self._provision(slot0, cover)
            feed = {
                "src_word": src,
                "src_len": np.asarray([[length]], dtype="int64"),
                "slot_idx": np.asarray([slot0], dtype="int64"),
                "page_row": self._page_row(pages),
            }
            feed.update(start_feed)
            if beam:
                # hypothesis 0 seeds the lane's lattice at score 0; the
                # rest ride at -1e9 (first-step duplicate suppression,
                # the dense beam convention)
                feed["start_score"] = np.asarray([[0.0]], "float32")
            if _chaos.ENABLED:
                # the serve.admit kill/fault point: slots popped, pages
                # provisioned, nothing dispatched — a fault here MUST
                # roll the whole group back (repoint-then-deref) and,
                # under classified retry, re-admit bit-identically
                _chaos.fault("serve.admit")
            self._run(self._admit_prog, feed, [])
            self._count_admit(1)
            self._note_cross(slot0, gid, length)
            write_from = len(cached) * self._ps
            if write_from:
                self._prefix_cache.tokens_saved += write_from
                _prefill_saved.inc(write_from)
            if write_from < L - 1:
                pw = np.full((1, self._T), self._eos, dtype="int64")
                pw[0, :L] = prefix
                self._run(self._prefill_prog, {
                    "prefix_word": pw,
                    "prefix_len": np.asarray([[L]], dtype="int64"),
                    "write_from": np.asarray([[write_from]],
                                             dtype="int64"),
                    "slot_idx": np.asarray([slot0], dtype="int64"),
                    "group_idx": np.asarray([gid], dtype="int64"),
                }, [])
            if (self._prefix_cache is not None
                    and k_full > len(cached)):
                # newly-full pages join the trie (one cache ref each);
                # insert only after the prefill landed their bits
                self._prefix_cache.insert(
                    self._src_fp(src, length), prefix, pages[:k_full])
            # -- members 1..n-1: fork by reference --------------------------
            # shared: exactly the pages holding PREFIX content (full
            # pages + the partial tail). Decode-ahead pages past the
            # prefix are private per member — sharing an empty page
            # would only buy a guaranteed COW copy.
            shared = pages[:self._pages_for(max(L - 1, 0), self._ps)]
            for _ in range(1, n):
                s = (pending_slots.popleft() if pending_slots is not None
                     else self._take_slot())
                slots.append(s)
                mpages = []
                for pg in shared:
                    self._pool.ref(pg)
                    mpages.append(pg)
                self._slot_pages[s] = mpages
                self._slot_group[s] = gid
                self._provision(s, cover)
                jfeed = {
                    "slot_idx": np.asarray([s], dtype="int64"),
                    "page_row": self._page_row(mpages),
                }
                jfeed.update(start_feed)
                if beam:
                    jfeed["start_score"] = np.asarray([[-1e9]],
                                                      "float32")
                self._run(self._join_prog, jfeed, [])
                self._note_cross(s, gid)
                if L > 1:
                    _prefill_saved.inc(L - 1)
        except BaseException:
            self._rollback_admission(slots, gid, n,
                                     restore_free=pending_slots is None)
            raise
        self._group_members[gid] = set(slots)
        for k, s in enumerate(slots):
            trg = np.full(self._T, self._eos, dtype="int64")
            trg[:L] = prefix
            self._live[s] = {"trg": trg, "pos": L - 1}
            if beam:
                self._live[s]["done"] = False
                self._live[s]["score"] = 0.0 if k == 0 else -1e9
            _sequences_total.inc(event="admitted")
        _active_slots.set(len(self._live))
        self._update_pool_gauges()
        return slots

    def _rollback_admission(self, slots, gid, n, restore_free=True):
        """A failed admission dispatch must leave NO device table row
        pointing at pages that return to the free list: repoint each
        admitted slot's row at the trash page FIRST (the same order
        ``_release_many`` uses, one dispatch for the group's members),
        THEN drop the page references — the
        admit dispatch may have committed device-side before the host
        raised (post-dispatch chaos fault, fetch failure), and a
        recycled page receiving a stale row's writes is silent
        corruption of whichever sequence owns it next. If even the
        repoint dispatch fails, the pages are deliberately LEAKED
        (``_repoint``: kept allocated, never freed, and subtracted from the
        reservation capacity so provisioning can still never fail):
        a smaller pool is recoverable, corruption is not."""
        self._cross_view = None  # the dispatch may or may not have landed
        self._repoint([s for s in slots if s in self._slot_pages])
        for s in slots:
            self._slot_group.pop(s, None)
            # last acquired first: the pool's free list is a stack, and
            # a retry must pop the same pages
            for pg in reversed(self._slot_pages.pop(s, ())):
                self._pool.deref(pg)
        # restore the free stack exactly (pop order == re-pop order, so
        # a retried admission lands in the same slots => same PRNG
        # streams). Beam-lane admissions own their slot bookkeeping
        # (restore_free=False): the caller returns the lane wholesale.
        if restore_free:
            for s in reversed(slots):
                self._free.append(s)
        self._free_groups.append(gid)
        self._reserved_pages -= n * self._pages_for(self._T, self._ps)
        self._update_pool_gauges()

    # -- beam decode ---------------------------------------------------------
    @property
    def beam_width(self):
        return self._beam_width

    @property
    def free_beams(self):
        """Unoccupied beam lanes (beam sessions)."""
        return len(self._free_lanes) if self._beam_width > 1 else 0

    @property
    def active_beams(self):
        """Lane ids currently decoding (beam sessions)."""
        return sorted(self._beam_live) if self._beam_width > 1 else []

    def beam_slots(self, beam_id):
        """The K aligned slots of one live beam lane, hypothesis
        order == slot order (top-k keeps survivors score-sorted)."""
        return list(self._beam_live[int(beam_id)]["slots"])

    def admit_beam(self, src, src_len=None, prefix_tokens=None):
        """Claim one beam LANE (``beam_width`` aligned slots) for one
        source: ONE encoder forward into a fresh cross-K/V group, one
        chunked prefill for any forced prefix (prefix-cache hits
        provision by reference, and all K hypotheses share the prefix
        pages — a beam's shared prefix costs ONE set of physical
        pages), hypothesis 0 seeded at score 0 and the rest at -1e9.
        Returns the beam id (the lane index). Raises
        :class:`NoFreeSlotError` when every lane is occupied, plus the
        page/group rejects of ``admit_group`` — all with full
        rollback. Admission is admit-or-reject (beams never ride the
        solo backlog: their K x worst-case reservation is too large to
        head-of-line park)."""
        if self._beam_width < 2:
            raise ValueError(
                "admit_beam needs a beam session — build with "
                "beam_width >= 2")
        K = self._beam_width
        self._gate_admission(K)
        self._begin_op()
        try:
            return _retry.call(
                lambda: self._admit_beam_attempt(src, src_len,
                                                 prefix_tokens),
                origin="serve.admit")
        finally:
            self._end_op()

    def _admit_beam_attempt(self, src, src_len, prefix_tokens):
        if not self._free_lanes:
            raise NoFreeSlotError(
                "all %d beam lanes occupied; step() until one "
                "finishes" % (self._S // self._beam_width))
        K = self._beam_width
        lane = self._free_lanes.pop()
        slots = [lane * K + k for k in range(K)]
        for s in slots:
            self._free.remove(s)
        try:
            self._admit_group_attempt(src, K, src_len, prefix_tokens,
                                      slots_override=slots)
        except BaseException:
            # _admit_group_attempt rolled the pages/group back but left
            # the slot stack alone (restore_free=False): the lane is
            # returned wholesale, slots re-enter the free mirror
            for s in reversed(slots):
                self._free.append(s)
            self._free_lanes.append(lane)
            raise
        self._beam_live[lane] = {"slots": slots}
        self._last_parents[lane] = list(range(K))
        _active_beams.set(len(self._beam_live))
        return lane

    def register_beam_owner(self, beam_id):
        """Attach a request id to a live beam (the wire front end's
        bank hook): when the beam finishes, its n-best lands in the
        beam result bank under this id — and both the binding and the
        bank ride the decode snapshot, so a preempted process's beams
        stay claimable. Returns the id (session-monotonic, the same
        counter solo requests draw from)."""
        lane = int(beam_id)
        if lane not in self._beam_live:
            raise ValueError("beam %d is not live" % lane)
        rid = self._next_req
        self._next_req += 1
        self._beam_owner[lane] = rid
        return rid

    def take_beam_result(self, request_id):
        """Claim (and remove) a finished beam's n-best by request id:
        ``{"tokens": [K, T] int64 (score-descending), "scores": [K]
        float32}`` — or None if unknown/unfinished. Banked results
        survive a preemption (they ride the decode snapshot) until
        taken. Safe on any session (a dense/sampler session simply has
        no beam bank) — the wire ``take_result`` probes both banks."""
        bank = getattr(self, "_beam_results", None)
        if not bank:
            return None
        return bank.pop(int(request_id), None)

    @property
    def last_beam_events(self):
        """Per-lane survivor info from the LAST step dispatch —
        ``{lane: {"parents", "tokens", "scores", "done"}}`` (what a
        streaming front end flushes per dispatch). Finished lanes
        appear in :attr:`last_finished_beams` instead."""
        return self._beam_events

    @property
    def last_finished_beams(self):
        """Beams the LAST step completed: ``{lane: {"tokens" [K, T],
        "scores" [K], "slots"}}`` in score-descending hypothesis
        order."""
        return self._last_finished_beams

    def _reorder_lane(self, slots, perm):
        """Execute one lane's parent permutation on the HOST side. The
        device already gathered the page-table rows in-graph; here the
        refcounts catch up: each survivor references its parent's
        pages, every pre-reorder list derefs. A pure permutation nets
        every refcount unchanged — zero pages move, zero pages free,
        zero copies; duplicated parents leave their pages shared until
        COW splits the write page. Under
        ``FLAGS_beam_reorder=reference`` the permutation is instead
        materialized the pre-paged way: every survivor with
        ``perm[k] != k`` COPIES its parent's resident pages into fresh
        private ones (one coalesced dispatch; bytes counted) — the
        copy-reorder baseline the bench A/Bs against, bit-identical by
        construction."""
        from paddle_tpu import flags as _flags

        K = len(slots)
        old_pages = [self._slot_pages[s] for s in slots]
        # ref new lists first, then deref old: no page transits 0
        new_pages = []
        for k in range(K):
            lst = list(old_pages[perm[k]])
            for pg in lst:
                self._pool.ref(pg)
            new_pages.append(lst)
        for lst in old_pages:
            for pg in lst:
                self._pool.deref(pg)
        for k, s in enumerate(slots):
            self._slot_pages[s] = new_pages[k]
        if _flags.get("beam_reorder") != "reference":
            return
        # the copy-reorder oracle: physically privatize every moved
        # hypothesis (the in-graph row gather already happened; these
        # copies + repoints overwrite the rows in one dispatch). Every
        # destination page is acquired BEFORE any slot's list mutates:
        # a NoFreePageError mid-plan must leave the rebound refcounts
        # exactly as they stand (pages just go back), never a slot
        # whose host row diverged from the device row.
        window = []
        fresh_lists = {}
        try:
            for k, s in enumerate(slots):
                if perm[k] == k:
                    continue
                fresh = []
                for pg in self._slot_pages[s]:
                    dst = self._acquire_page()
                    window.append((s, pg, dst))
                    fresh.append(dst)
                fresh_lists[s] = fresh
        except BaseException:
            for _s, _src, dst in window:
                self._pool.deref(dst)  # acquired at refcount 1
            raise
        for s, fresh in fresh_lists.items():
            self._slot_pages[s] = fresh
        if window:
            self._dispatch_cow(window)  # derefs the sources on success
            self.beam_reorder_pages += len(window)
            _beam_reorder_bytes.inc(len(window) * self._page_bytes())

    def _page_bytes(self):
        dh = self._D // self._n_head
        return 2 * self._n_layer * self._n_head * self._ps * dh * 4

    def _step_beam(self):
        # pre-dispatch COW/provisioning for LIVE hypotheses only: done
        # hypotheses' writes route to the trash page in-graph, so a
        # frozen slot never needs a private write page
        before_pairs = self.cow_pairs
        self._dispatch_cow(self._cow_window(
            [(s, st["pos"]) for s, st in self._live.items()
             if not st["done"]]))
        split = self.cow_pairs - before_pairs
        if split:
            # write-page splits charged to BEAM decode (duplicated
            # parents diverging at the write position); the oracle's
            # reorder copies are counted apart (beam_reorder_pages)
            self.beam_cow_copies += split
            _beam_cow.inc(split)
        self._update_pool_gauges()
        extras = list(getattr(self, "_extra_step_fetches", ()))
        t0 = time.perf_counter()
        out = self._run(
            self._step_prog, {},
            [self._beam_fetches["token"], self._beam_fetches["parent"],
             self._beam_fetches["score"]] + extras)
        elapsed = time.perf_counter() - t0
        toks, parents, scores = out[0], out[1], out[2]
        # test hook: extra fetch names (e.g. the step logits for the
        # offline-lattice parity test) ride the same dispatch
        self.last_extra_fetches = [np.asarray(x) for x in out[3:]]
        toks = np.asarray(toks).reshape(self._S)
        parents = np.asarray(parents).reshape(self._S)
        scores = np.asarray(scores).reshape(self._S)
        K = self._beam_width
        live_before = sum(1 for st in self._live.values()
                          if not st["done"])
        finished = {}
        self._beam_events = {}
        self._last_finished_beams = {}
        for lane in sorted(self._beam_live):
            slots = self._beam_live[lane]["slots"]
            perm = [int(parents[s]) - slots[0] for s in slots]
            old = [self._live[s] for s in slots]
            if perm != list(range(K)):
                self._reorder_lane(slots, perm)
            new_states = []
            for k, s in enumerate(slots):
                parent = old[perm[k]]
                tok = int(toks[s])
                sc = float(scores[s])
                if parent["done"]:
                    # frozen hypothesis carried forward untouched (its
                    # beam_step candidate was (eos, score))
                    st = {"trg": parent["trg"].copy(),
                          "pos": parent["pos"], "done": True,
                          "score": sc}
                else:
                    pos = min(parent["pos"] + 1, self._T - 1)
                    trg = parent["trg"].copy()
                    trg[pos] = tok
                    st = {"trg": trg, "pos": pos,
                          "done": (tok == self._eos
                                   or parent["pos"] + 1
                                   >= self._T - 1),
                          "score": sc}
                new_states.append(st)
            for k, s in enumerate(slots):
                self._live[s] = new_states[k]
            self._last_parents[lane] = perm
            if all(st["done"] for st in new_states):
                tokens = np.stack([st["trg"] for st in new_states])
                lane_scores = np.asarray(
                    [st["score"] for st in new_states], "float32")
                self._last_finished_beams[lane] = {
                    "tokens": tokens, "scores": lane_scores,
                    "slots": list(slots),
                    # the FINAL survivor chunk (a streaming front end
                    # flushes it before the n-best, so an incremental
                    # client's replay covers every step)
                    "parents": perm,
                    "step_tokens": [int(toks[s]) for s in slots],
                    "step_scores": [float(scores[s]) for s in slots],
                }
                for s in slots:
                    finished[s] = self._live[s]["trg"]
                    del self._live[s]
                    self._free.append(s)
                    _sequences_total.inc(event="completed")
                # the lane's rows in one table dispatch, before the next
                # lane's reorder can dispatch anything
                self._release_many(slots)
                del self._beam_live[lane]
                self._free_lanes.append(lane)
                self._last_parents.pop(lane, None)
                rid = self._beam_owner.pop(lane, None)
                if rid is not None:
                    self._beam_results[rid] = {
                        "tokens": tokens, "scores": lane_scores}
            else:
                self._beam_events[lane] = {
                    "parents": perm,
                    "tokens": [int(toks[s]) for s in slots],
                    "scores": [float(scores[s]) for s in slots],
                    "done": [bool(st["done"]) for st in new_states],
                }
        _active_slots.set(len(self._live))
        _active_beams.set(len(self._beam_live))
        if elapsed > 0:
            _decode_tps.set(live_before / elapsed)
        self._update_pool_gauges()
        return finished

    def generate_beam(self, src, src_len=None, prefix_tokens=None,
                      len_penalty=None):
        """Dedicated-session convenience: run ONE beam to completion
        and return ``(tokens [K, T] int64, scores [K] float32)`` in
        score-descending hypothesis order (bos-led, eos-padded rows).
        ``len_penalty`` (optional float) rescoring: the final n-best is
        reordered under the GNMT length penalty
        (``transformer.gnmt_rescore_nbest`` — the same formula the
        offline ``beam_generate`` applies via ``_pick_best_beam``) and
        the returned scores are the PENALIZED ones. Other lanes
        finishing meanwhile are returned to nobody — use
        :meth:`register_beam_owner` + :meth:`take_beam_result` for
        concurrent consumers."""
        lane = self.admit_beam(src, src_len=src_len,
                               prefix_tokens=prefix_tokens)
        rid = self.register_beam_owner(lane)
        while lane in self._beam_live:
            self.step()
        out = self.take_beam_result(rid)
        if len_penalty is None:
            return out["tokens"], out["scores"]
        from paddle_tpu.models import transformer

        _order, tokens, scores = transformer.gnmt_rescore_nbest(
            out["tokens"], out["scores"], self._eos,
            float(len_penalty))
        return tokens, scores

    def cancel(self, slot):
        """Abort one in-flight sequence — the disconnect/cancel
        teardown a network front end needs, the one-slot case of
        :meth:`cancel_many`: the slot frees, its page references drop
        (the table row is repointed at the trash page FIRST, the
        ``_release_many`` discipline, so recycled pages can never
        receive a stale row's writes), its group loses a member and any
        request ownership is dropped WITHOUT banking a result. Returns
        True when the slot was live. Call between dispatches (never
        mid-``step``); :attr:`pool_conserved` holds afterwards — a
        killed client costs capacity nothing.

        On a BEAM session a slot is one hypothesis of a lane, and a
        lane is one request: cancelling any member releases the WHOLE
        beam (every sibling slot, the lane, the owner binding — nothing
        banks)."""
        return bool(self.cancel_many([slot]))

    def cancel_many(self, slots):
        """Abort several in-flight sequences in ONE teardown (what a
        front end's worker pass hands over: every stream it found
        cancelled): all their table rows are repointed by one dispatch
        of the smallest rung that holds them (a lone slot runs
        ``table_prog``, as :meth:`cancel` always has), then the slots,
        pages and groups come back in the order given — exactly what
        cancelling them one by one leaves. A beam session releases the
        whole lane of every slot given, each lane once. Returns the
        slots of ``slots`` that were live (a lane's later siblings are
        not: the first released them)."""
        hit, release, lanes = [], [], []
        for slot in (int(s) for s in slots):
            if slot not in self._live or slot in release:
                continue
            if self._beam_width > 1:
                lane = slot // self._beam_width
                if lane not in self._beam_live:
                    continue
                lanes.append(lane)
                release.extend(s for s in self._beam_live[lane]["slots"]
                               if s in self._live)
            else:
                release.append(slot)
            hit.append(slot)
        if release:
            with _tracing.span("cancel"):
                self._cancel_slots(release, lanes)
        return hit

    def _cancel_slots(self, slots, lanes):
        if self._paged:
            before = (self.release_dispatches, self.release_pad_rows)
        self._begin_op()
        try:
            for slot in slots:
                del self._live[slot]
            if self._paged:
                # a repoint dispatch that fails leaks its slots' pages
                # (recorded, so ckpt_inspect --verify exempts them)
                # instead of freeing pages a stale row could write; the
                # group/reservation books still close, so the slots
                # re-admit cleanly. Same corruption-beats-capacity rule
                # as _rollback_admission.
                self._release_many(slots)
            for slot in slots:
                self._free.append(slot)
                # inside the op window: a quiesce snapshot at _end_op
                # must never bank a freed slot with a stale owner entry
                # (a later occupant of the slot would finish into the
                # cancelled request's result id)
                rid = self._owner.pop(slot, None)
                if self._slot_traces or self._trace_ids:
                    self._trace_cancel(slot, rid)
            for lane in lanes:
                del self._beam_live[lane]
                self._free_lanes.append(lane)
                self._last_parents.pop(lane, None)
                self._beam_events.pop(lane, None)
                self._beam_owner.pop(lane, None)  # cancelled, never banked
        finally:
            self._end_op()
        if _tracing.ENABLED:
            _tracing.round_count("cancel_rows", len(slots))
            if self._paged:
                _tracing.round_count(
                    "cancel_dispatches",
                    self.release_dispatches - before[0])
                _tracing.round_count(
                    "cancel_pad_rows", self.release_pad_rows - before[1])
        _sequences_total.inc(len(slots), event="cancelled")
        _active_slots.set(len(self._live))
        if lanes:
            _active_beams.set(len(self._beam_live))
        if self._paged:
            self._update_pool_gauges()

    def step(self):
        """Advance every in-flight sequence through the step
        executable — one token (dense layout) or ``steps`` tokens (one
        on-device scan dispatch, paged layout) — and return
        ``{slot: [T] int64 tokens}`` for the sequences that finished
        (their slots, and page references, are free again). No-op ({})
        when nothing is in flight."""
        if not self._live:
            return {}
        with _tracing.span("step"):
            return self._step()

    def _step(self):
        traced = bool(self._slot_traces) and _tracing.ENABLED
        if traced:
            t_step = time.time()
            pre_pos = {s: self._live[s]["pos"]
                       for s in self._slot_traces if s in self._live}
            pre_spec = self.spec_dispatches if self._spec_k else 0
            self._trace_cow.clear()
        self._begin_op()
        try:
            if _chaos.ENABLED:
                # the decode-side serving dispatch site: kill@step=N
                # SIGKILLs entering the Nth step dispatch (the SIGKILL
                # leg of the tests), io/compile faults exercise the
                # classified-retry shell the executor dispatch wears
                _chaos.fault("serve.dispatch", step=self.steps_done)
            if self._beam_width > 1:
                out = self._step_beam()
            else:
                out = (self._step_paged() if self._paged
                       else self._step_dense())
            self.steps_done += 1
        finally:
            self._end_op()
        if traced and pre_pos:
            self._trace_step(
                pre_pos, out, t_step, time.time(),
                bool(self._spec_k
                     and self.spec_dispatches > pre_spec))
        if self._monitor is not None:
            self._monitor.observe(self._health_load())
        return out

    def _step_dense(self):
        cur = np.full((self._S, 1), self._eos, dtype="int64")
        pos = np.zeros((self._S, 1), dtype="int64")
        pe = np.zeros((self._S, 1, self._D), dtype="float32")
        for slot, st in self._live.items():
            cur[slot, 0] = st["trg"][st["pos"]]
            pos[slot, 0] = st["pos"]
            pe[slot] = self._transformer.position_encoding_row(
                st["pos"], self._D)
        t0 = time.perf_counter()
        (toks,) = self._run(self._step_prog, {
            "cur_tok": cur, "pe_row": pe, "gen_pos": pos,
        }, [self._fetch_name])
        elapsed = time.perf_counter() - t0
        # [S, 1] device-selected token ids — the vocab-sized logits
        # never leave the device
        toks = np.asarray(toks).reshape(-1)
        live_before = len(self._live)
        finished = self._consume_tokens(toks[None, :, None])
        if elapsed > 0:
            _decode_tps.set(live_before / elapsed)
        return finished

    def _cow_window(self, slots_positions, span=None):
        """Assemble one dispatch window's COW pairs + growth rebinds
        for ``[(slot, write_pos)]``; the page lists are repointed here,
        the device catches up in ONE ``_dispatch_cow`` call. ``span``
        is the number of positions the dispatch will write per slot
        (default ``steps``; a speculative verify dispatch writes its
        whole k + 1 node tree)."""
        window = []
        span = self._steps if span is None else int(span)
        pending = {}  # src -> derefs planned by this window's pairs
        for slot, pos in slots_positions:
            grew = self._provision(slot, pos + span)
            copies = self._cow_copies(slot, pos, pending, span=span)
            for src_pg, dst_pg in copies:
                window.append((slot, src_pg, dst_pg))
            if grew and not copies:
                window.append((slot, 0, 0))  # rebind-only entry
        return window

    def _step_paged(self):
        if self._spec_k:
            from paddle_tpu import flags as _flags

            # the bit-exactness oracle: FLAGS_speculative=off routes
            # this very session through the plain sequential step —
            # both executables stay warm, the flag flips mid-stream
            if _flags.get("speculative") != "off":
                return self._step_speculative()
        # pre-provision every live slot for the whole dispatch: step j
        # writes K/V at position pos + j, so the table must cover
        # pos + steps resident tokens before the scan launches — and
        # any page the dispatch will WRITE that is still shared must be
        # copy-on-write split first (shared pages are read-only). All
        # of the window's pairs ride ONE coalesced dispatch.
        self._dispatch_cow(self._cow_window(
            [(slot, st["pos"]) for slot, st in self._live.items()]))
        self._update_pool_gauges()
        if _tracing.ENABLED:
            self._count_cross(self._n_layer * self._steps)
        t0 = time.perf_counter()
        with _tracing.span(".dispatch"):
            (toks,) = self._exe.run_multi_step(
                self._step_prog, self._steps, feed={},
                fetch_list=[self._fetch_name], scope=self._scope,
                stack_fetches=True, in_flight=self.in_flight)
        elapsed = time.perf_counter() - t0
        toks = np.asarray(toks)  # [K, S, 1]
        live_before = len(self._live)
        finished = self._consume_tokens(toks)
        if elapsed > 0:
            _decode_tps.set(live_before * self._steps / elapsed)
        self._update_pool_gauges()
        return finished

    def _step_speculative(self):
        """One draft-then-verify round: host drafting, ONE target
        dispatch scoring the anchor + k draft tokens as a tree in the
        slot's write pages, in-graph accept/commit, host bookkeeping
        honoring the per-slot accept length. Commits 1 to k + 1 tokens
        per live slot; token streams are bit-identical to the
        sequential ``FLAGS_speculative=off`` path."""
        # the verify dispatch writes the whole tree — storage positions
        # [pos, pos + N) — so COW/provisioning covers the full span
        # before any drafting touches the (shared) page tables
        self._dispatch_cow(self._cow_window(
            [(slot, st["pos"]) for slot, st in self._live.items()],
            span=self._spec_nodes))
        self._update_pool_gauges()
        draft = self._spec_drafter.propose(self._live)
        if _tracing.ENABLED:
            self._count_cross(self._n_layer)
        t0 = time.perf_counter()
        out = self._run(self._spec_prog, {
            "spec_draft": draft.astype("int64"),
            "spec_parent": self._spec_parent,
            "spec_anc": self._spec_anc,
        }, [self._spec_fetches["spec_token_seq"],
            self._spec_fetches["spec_accept_len"]])
        elapsed = time.perf_counter() - t0
        tok_seq = np.asarray(out[0]).reshape(self._S, self._spec_nodes)
        acc_len = np.asarray(out[1]).reshape(self._S)
        live_slots = list(self._live)
        committed = int(sum(int(acc_len[s]) for s in live_slots))
        accepted = int(sum(max(int(acc_len[s]) - 1, 0)
                           for s in live_slots))
        proposed = self._spec_k * len(live_slots)
        self.spec_proposed += proposed
        self.spec_accepted += accepted
        self.spec_dispatches += 1
        _spec_proposed.inc(proposed)
        _spec_accepted.inc(accepted)
        if self.spec_proposed:
            _spec_accept_rate.set(
                self.spec_accepted / float(self.spec_proposed))
        finished = self._consume_spec(tok_seq, acc_len)
        if elapsed > 0:
            _decode_tps.set(committed / elapsed)
        self._update_pool_gauges()
        return finished

    def _consume_spec(self, tok_seq, acc_len):
        """Apply one verify dispatch's commits to the live slots:
        exactly ``acc_len[slot]`` tokens per slot (entries past that
        are eos padding, NOT tokens — unlike ``_consume_tokens``'s
        per-step trajectory, where padding only follows a terminal
        token and is self-identifying)."""
        finished = {}
        for slot in list(self._live):
            st = self._live[slot]
            for j in range(int(acc_len[slot])):
                t = st["pos"]
                nxt = int(tok_seq[slot, j])
                st["trg"][t + 1] = nxt
                st["pos"] = t + 1
                if nxt == self._eos or t + 1 == self._T - 1:
                    finished[slot] = st["trg"]
                    del self._live[slot]
                    self._free.append(slot)
                    _sequences_total.inc(event="completed")
                    break
        if finished:
            self._release_many(list(finished))  # one table dispatch
        _active_slots.set(len(self._live))
        return finished

    def _consume_tokens(self, toks):
        """Apply a ``[K, S, 1]`` token trajectory to the live slots —
        the host mirror of the on-device loop: each live slot consumes
        one token per scan step until it finishes (eos or max length);
        post-finish steps for that slot are the device's forced-eos
        padding and are ignored."""
        finished = {}
        for j in range(toks.shape[0]):
            for slot in list(self._live):
                st = self._live[slot]
                t = st["pos"]
                nxt = int(toks[j, slot, 0])
                st["trg"][t + 1] = nxt
                st["pos"] = t + 1
                if nxt == self._eos or t + 1 == self._T - 1:
                    finished[slot] = st["trg"]
                    del self._live[slot]
                    self._free.append(slot)
                    _sequences_total.inc(event="completed")
        if finished and self._paged:
            # the trajectory's finishers together, in finishing order:
            # one table dispatch, before any other dispatch can run
            self._release_many(list(finished))
        _active_slots.set(len(self._live))
        return finished

    # -- request queue -------------------------------------------------------
    @property
    def pending_requests(self):
        """Queued request ids not yet admitted (the backlog a snapshot
        preserves)."""
        return [r["id"] for r in self._pending]

    def enqueue(self, src, src_len=None, prefix_tokens=None,
                trace_id=None):
        """Queue one request ([T] or [1, T] int ids) without admitting
        it; :meth:`pump` admits queued requests as capacity frees.
        Returns a request id (monotonic per session — a restored
        session continues the numbering, so ids name the same requests
        across a preemption). The queue is part of the decode snapshot:
        a preempted process restores with its backlog intact.
        ``trace_id`` binds the request to an in-flight request trace
        (observability/tracing.py); the binding rides the snapshot, so
        a restored backlog re-emits under its ORIGINAL ids."""
        if self._beam_width > 1:
            raise ValueError(
                "beam sessions are admit-or-reject (admit_beam): a "
                "beam's K x worst-case reservation is too large to "
                "head-of-line park in the solo backlog")
        rid = self._next_req
        self._next_req += 1
        src = np.asarray(src, dtype="int64").reshape(1, self._T)
        length = self._T if src_len is None else int(np.ravel(src_len)[0])
        entry = {
            "id": rid, "src": src, "len": length,
            "prefix": (None if prefix_tokens is None
                       else [int(t) for t in prefix_tokens]),
        }
        if trace_id:
            # t_enq feeds the queue-wait span at admission; the key is
            # runtime-only (a snapshot serializes the named keys), so a
            # restored entry's queue span starts at its re-admission
            self._trace_ids[rid] = str(trace_id)
            entry["t_enq"] = time.time()
            entry["round"] = _tracing.round_id()
        self._pending.append(entry)
        return rid

    def drop_pending(self, request_id):
        """Remove one not-yet-admitted request from the backlog (the
        disconnect path for a queued wire request). Returns True when
        it was still queued."""
        rid = int(request_id)
        for i, req in enumerate(self._pending):
            if req["id"] == rid:
                del self._pending[i]
                if self._trace_ids:
                    tid = self._trace_ids.pop(rid, None)
                    tr = (_tracing.inflight_get(tid) if tid is not None
                          else None)
                    if tr is not None and tr.origin == "session":
                        _tracing.finish(tr, outcome="dropped")
                return True
        return False

    def admit_pending(self):
        """The admission half of :meth:`pump`: admit queued requests in
        order while capacity allows (a pool/group reservation reject --
        or a degradation reject, when the monitor is armed — defers the
        request back to the FRONT; admission order is the service
        contract). On a paged session the head RUN of plain requests (no
        forced prefix) is admitted by ONE batched encoder dispatch
        (:meth:`_admit_run`), into the slots, groups and pages
        one-at-a-time admission would have given them; a request with a
        forced prefix ends the run and goes through :meth:`admit`. An
        admission dispatch that fails past the retry budget raises: its
        requests are rolled back and lost to the caller, the queue
        behind them is untouched (``DecoderOnlySession.admit_pending``'s
        contract). Returns ``{slot: request_id}`` for the requests
        admitted THIS call — what a streaming front end needs to map
        slots back to their wire streams before the next step
        dispatch."""
        admitted = {}
        while self._pending and self._free:
            with _tracing.span("admit"):
                if self._admit_rungs and not self._pending[0]["prefix"]:
                    got = self._admit_run()
                else:
                    slot = self._admit_next()
                    got = {} if slot is None else {slot: self._owner[slot]}
            if not got:
                break
            admitted.update(got)
        return admitted

    def _plain_run(self):
        """How many requests at the head of the queue ONE dispatch
        admits: plain ones, as many as free slots, free groups and the
        page reservation allow and the top rung holds; one alone where
        they would leave their rung nearly empty (``_ADMIT_MIN_FILL``);
        each let through the degradation gate as sequential admission
        would have been (its load counts the members before it). 0 when
        the head itself has to wait."""
        from paddle_tpu.serving.degradation import DegradedError

        worst = self._pages_for(self._T, self._ps)
        capacity = self._P - 1 - self._leaked_pages
        room = min(len(self._free), len(self._free_groups),
                   (capacity - self._reserved_pages) // worst,
                   self._admit_rungs[-1])
        run = 0
        for req in self._pending:
            if run >= room or req["prefix"]:
                break
            run += 1
        if run and run * _ADMIT_MIN_FILL < self._rung_of(run):
            run = 1
        for n in range(run):
            try:
                self._gate_admission(1, taking=n)
            except DegradedError:
                return n
        return run

    def _rung_of(self, rows):
        return next(r for r in self._rungs if r >= rows)

    def _admit_feed(self, members, rung):
        """The admission program's feed for ``members`` (``[(request,
        slot, group)]``) padded to ``rung`` rows. A row of padding
        encodes one token of id 0 and carries a slot and a group index
        past the pools' ends, so the scatter drops every write of it."""
        pad = rung - len(members)

        def col(vals, fill):
            return np.asarray(list(vals) + [fill] * pad, dtype="int64")

        return {
            "src_word": np.concatenate(
                [m[0]["src"] for m in members]
                + [np.zeros((pad, self._T), "int64")], axis=0),
            "src_len": col((m[0]["len"] for m in members), 1).reshape(
                rung, 1),
            "slot_idx": col((m[1] for m in members), self._S),
            "group_idx": col((m[2] for m in members), self._G),
            "page_row": np.concatenate(
                [self._page_row(self._slot_pages[m[1]]) for m in members]
                + [np.zeros((pad, self._npp), "int64")], axis=0),
            "start_tok": np.full((rung, 1), self._bos, "int64"),
            "start_pos": np.zeros((rung, 1), "int64"),
        }

    def _count_admit(self, rows, pad=0):
        """One encoder dispatch admitted ``rows`` sources beside ``pad``
        rows of padding."""
        self.admit_dispatches += 1
        self.admit_rows += rows
        if _tracing.ENABLED:
            _tracing.round_count("admit_dispatches", 1)
            _tracing.round_count("admit_rows", rows)
            _tracing.round_count("admit_pad_rows", pad)

    def _admit_run(self):
        """Admit the head run of plain requests through ONE batched
        dispatch; ``{slot: request_id}``, empty when the head has to
        wait (no group, no page reservation, or the monitor rejects it:
        the queue is left as it was)."""
        # pop -> dispatch -> owner-record of the whole batch is ONE
        # dispatch window: a quiesce-point snapshot sees each request in
        # _pending or in _owner, never in neither
        self._begin_op()
        try:
            n = self._plain_run()
            if not n:
                return {}
            reqs = [self._pending.popleft() for _ in range(n)]
            t_admit = time.time() if self._trace_ids else 0.0
            # classified retry around the whole batch attempt: a failed
            # attempt rolls every member back, so the retried one lands
            # in the SAME slots, groups and pages
            slots = _retry.call(lambda: self._admit_run_attempt(reqs),
                                origin="serve.admit")
            got = {}
            for req, slot in zip(reqs, slots):
                self._owner[slot] = got[slot] = req["id"]
                if req["id"] in self._trace_ids:
                    self._trace_admitted(req, slot, t_admit)
            return got
        finally:
            self._end_op()

    def _admit_run_attempt(self, reqs):
        """Slots, groups and pages for ``reqs`` taken in queue order —
        request by request what ``_admit_group_attempt`` takes for a solo
        admission, so every id is the one sequential admission gives —
        then one dispatch of the smallest rung that holds them."""
        worst = self._pages_for(self._T, self._ps)
        cover = min(self._steps, self._T)  # the first dispatch's writes
        rung = self._rung_of(len(reqs))
        members = []
        try:
            for req in reqs:
                self._reserved_pages += worst
                gid = self._free_groups.pop()
                slot = self._take_slot()
                members.append((req, slot, gid))
                self._slot_pages[slot] = []
                self._slot_group[slot] = gid
                self._provision(slot, cover)
            if _chaos.ENABLED:
                # slots popped, pages provisioned, nothing dispatched
                _chaos.fault("serve.admit")
            self._run(self._admit_progs[rung],
                      self._admit_feed(members, rung), [])
        except BaseException:
            # last taken first: the group stack and the page pool's free
            # list are LIFO, and a retry must pop the same ids
            for _req, slot, gid in reversed(members):
                self._rollback_admission([slot], gid, 1)
            raise
        self._count_admit(len(reqs), rung - len(reqs))
        for req, slot, gid in members:
            self._note_cross(slot, gid, req["len"])
            self._group_members[gid] = {slot}
            trg = np.full(self._T, self._eos, dtype="int64")
            trg[0] = self._bos
            self._live[slot] = {"trg": trg, "pos": 0}
            _sequences_total.inc(event="admitted")
        _active_slots.set(len(self._live))
        self._update_pool_gauges()
        return [m[1] for m in members]

    def _admit_next(self):
        """Admit the request at the head of the queue; its slot, or
        None when it was deferred back to the front."""
        from paddle_tpu.serving.degradation import DegradedError

        # the pop -> admit -> owner-record sequence is ONE dispatch
        # window: a quiesce-point snapshot (or deferred SIGTERM) firing
        # inside admit's own window would otherwise see the request in
        # neither _pending nor _owner — a request lost across the
        # restore
        self._begin_op()
        try:
            req = self._pending.popleft()
            traced = req["id"] in self._trace_ids
            t_admit = time.time() if traced else 0.0
            try:
                slot = self.admit(req["src"], req["len"],
                                  prefix_tokens=req["prefix"])
            except (NoFreePageError, NoFreeGroupError, DegradedError):
                # capacity/degradation reject: defer and let in-flight
                # sequences drain — guaranteed progress, since the
                # constructor requires the pool to cover one sequence
                # and a shed monitor relaxes as the pool empties
                self._pending.appendleft(req)
                return None
            self._owner[slot] = req["id"]
            if traced:
                self._trace_admitted(req, slot, t_admit)
            return slot
        finally:
            self._end_op()

    def pump(self):
        """One scheduler round: :meth:`admit_pending`, then one
        :meth:`step`. Returns ``{request_id: [T]
        tokens}`` for requests that finished this round; every finished
        result is ALSO banked until :meth:`take_result` claims it, so
        concurrent consumers (a ``generate()`` call draining the pool
        for its own rows while other requests ride along) never lose a
        request another consumer's pump happened to complete. Slots
        finished that no queued request owns are dropped
        (``generate_best_of``'s documented behavior). An IDLE session
        (nothing queued, nothing live) returns ``{}`` immediately — a
        caller looping "until request X finishes" should guard on
        ``pending_requests`` / ``active_slots``, or it will spin."""
        self.admit_pending()
        finished = {}
        for slot, tokens in self.step().items():
            rid = self._owner.pop(slot, None)
            if rid is not None:
                finished[rid] = tokens
                self._results[rid] = tokens
                self._trace_bank(rid)
        return finished

    def take_result(self, request_id):
        """Claim (and remove) a finished request's ``[T]`` tokens from
        the result bank, or None if it hasn't finished. Results stay
        banked — and ride the decode snapshot, so a completed-but-
        unclaimed request survives a preemption — until taken; a
        long-lived caller that consumes :meth:`pump`'s return directly
        should still take (or this bank grows one entry per request).
        Claiming retires the request's trace-id binding."""
        rid = int(request_id)
        out = self._results.pop(rid, None)
        if out is not None and self._trace_ids:
            self._trace_ids.pop(rid, None)
        return out

    # -- request tracing -----------------------------------------------------
    def _trace_admitted(self, req, slot, t_admit):
        """Admission-side trace hooks for a queued solo request: emit
        the queue-wait span and the prefill span (the admission IS the
        prefill in this design — encoder forward + chunked prefix
        prefill in one dispatch window) and bind slot -> trace id for
        the step loop. A restored backlog entry has a rid -> id binding
        but no in-flight trace: the ORIGINAL id is continued here as a
        session-origin trace, finished when the result banks."""
        rid = req["id"]
        tid = self._trace_ids.get(rid)
        if tid is None:
            return
        tr = _tracing.inflight_get(tid)
        if tr is None:
            tr = _tracing.start(tid, endpoint="generate",
                                origin="session")
        t_enq = req.get("t_enq")
        if t_enq is not None:
            tr.span("queue", t_enq, t_admit, rid=int(rid),
                    round=req.get("round"))
        hit_pages = (getattr(self._prefix_cache, "last_hit_pages", 0)
                     if self._paged and self._prefix_cache is not None
                     else 0)
        tr.span("prefill", t_admit, time.time(), kind="solo",
                slot=int(slot), rid=int(rid),
                prefix_hit_pages=int(hit_pages),
                round=_tracing.round_id())
        self._slot_traces[slot] = tr

    def _trace_bank(self, rid):
        """Close a session-origin continuation trace when its result
        banks (the restored-backlog / headless finish path). The
        rid -> trace-id binding stays until :meth:`take_result` claims
        the row, so the claim response can still name its trace."""
        if not self._trace_ids:
            return
        tid = self._trace_ids.get(int(rid))
        tr = _tracing.inflight_get(tid) if tid is not None else None
        if tr is not None and tr.origin == "session":
            _tracing.finish(tr, outcome="banked")

    def _trace_cancel(self, slot, rid):
        """Cancel-side trace teardown: unbind the slot, stop its page
        integration, retire the rid binding, and close session-origin
        traces — a cancelled request must never leave an open span in
        flight (the ring sweep in tests/test_tracing.py pins this)."""
        tr = self._slot_traces.pop(slot, None)
        tid = (self._trace_ids.pop(int(rid), None) if rid is not None
               else None)
        if (tr is None or tr.done) and tid is not None:
            tr = _tracing.inflight_get(tid)
        if tr is None or tr.done:
            return
        tr.sample_pages(0)
        if tr.origin == "session":
            _tracing.finish(tr, outcome="cancelled")

    def _tokens_past(self, trg, prev):
        """Tokens a finished row generated past position ``prev``
        (through its terminal eos, or the max-length cap)."""
        for idx in range(prev + 1, self._T):
            if int(trg[idx]) == self._eos:
                return idx - prev
        return self._T - 1 - prev

    def _trace_step(self, pre_pos, out, t0, t1, was_spec):
        """Post-dispatch span emission for every traced slot that was
        live when the step launched: one ``decode.step`` span per slot
        (tokens committed, COW copies coalesced for it, speculative or
        sequential, the worker's round), accumulator bumps for the
        derived stats, and a page-seconds sample per trace (summed
        across a group's slots). One pass over the bound slots, no
        lock: a slot is bound to its Trace at admission. Runs OUTSIDE
        the dispatch window — host-only bookkeeping."""
        round_id = _tracing.round_id()
        pages = {}   # Trace -> pages its still-bound slots hold
        for slot, prev in pre_pos.items():
            tr = self._slot_traces.get(slot)
            if tr is None:
                continue
            if tr.done:
                # finished by its handler while the slot decodes on: a
                # trace re-started under the id (a re-attached stream)
                # takes the slot's spans from here on; else the slot is
                # unbound, so this lookup is made once
                tr = _tracing.inflight_get(tr.id)
                if tr is None:
                    del self._slot_traces[slot]
                    continue
                self._slot_traces[slot] = tr
            finished_here = slot not in self._live
            if finished_here:
                trg = out.get(slot)
                delta = (self._tokens_past(trg, prev)
                         if trg is not None else 0)
            else:
                delta = self._live[slot]["pos"] - prev
            cow = self._trace_cow.pop(slot, 0)
            tr.span("decode.step", t0, t1, slot=int(slot),
                    tokens=int(delta), cow_copies=int(cow),
                    speculative=bool(was_spec), round=round_id)
            if delta > 0:
                tr.bump("tokens", int(delta))
                if was_spec:
                    # one token per verify dispatch is the anchor the
                    # sequential path would have produced anyway; the
                    # rest came from accepted draft tokens
                    tr.bump("tokens_from_spec", int(delta) - 1)
            if cow:
                tr.bump("cow_copies", int(cow))
            held = 0
            if finished_here:
                self._slot_traces.pop(slot, None)
            elif self._paged:
                held = len(self._slot_pages.get(slot, ()))
            pages[tr] = pages.get(tr, 0) + held
        for tr, npages in pages.items():
            tr.sample_pages(npages)

    def generate(self, src, src_len=None):
        """Batch convenience: run every row of ``src`` ([B, T] int ids,
        ``src_len`` [B] or [B, 1]) through the slot pool — admitting as
        slots free up, which exercises the continuous-batching path even
        for B > num_slots — and return the [B, T] token matrix
        (bos-led, eos-padded; greedy unless the session's sampler says
        otherwise). Requests are served strictly in row order through
        the session's persistent queue (:meth:`enqueue` +
        :meth:`pump`), so a snapshot taken mid-generate carries the
        backlog."""
        src = np.asarray(src, dtype="int64")
        lengths = (np.full(len(src), self._T, dtype="int64")
                   if src_len is None
                   else np.ravel(np.asarray(src_len, dtype="int64")))
        out = np.full((len(src), self._T), self._eos, dtype="int64")
        order = {self.enqueue(src[i], lengths[i]): i
                 for i in range(len(src))}
        want = set(order)
        while want:
            self.pump()
            # claim ONLY this call's rows from the result bank: a
            # request some other consumer enqueued stays claimable by
            # its owner instead of being consumed-and-dropped here
            for rid in list(want):
                tokens = self.take_result(rid)
                if tokens is not None:
                    out[order[rid]] = tokens
                    want.discard(rid)
        return out

    def generate_best_of(self, src, n, src_len=None, prefix_tokens=None):
        """Best-of-N convenience over ``admit_group``: decode ``n``
        continuations of ONE source ([T] or [1, T] ids) to completion
        and return them as an [n, T] matrix in member order. Intended
        for a dedicated session (it steps until the group drains;
        other in-flight slots finishing meanwhile are returned to
        nobody)."""
        slots = self.admit_group(src, n=n, src_len=src_len,
                                 prefix_tokens=prefix_tokens)
        order = {s: i for i, s in enumerate(slots)}
        out = np.full((int(n), self._T), self._eos, dtype="int64")
        remaining = set(slots)
        while remaining:
            for slot, tokens in self.step().items():
                if slot in remaining:
                    out[order[slot]] = tokens
                    remaining.discard(slot)
        return out
