"""DecoderOnlySession: continuous-batching decode of a decoder-only model
over the state its builder declares: page pools that grow with a slot's
sequence, page pools that hold a slot's last ``window`` positions only,
fixed-size arrays indexed by the slot itself, or any of them together.

The model is chosen from its description (``builder_for``, of
``models/decoder_programs.py``, which builds every family's programs on
one frame): a dict with
``mamba_d_state`` is the hybrid state-space decoder
(``models/hybrid_ssm_decoder.py``: recurrent state and a convolution
window a slot a state-space layer, K/V page pools for its few attention
layers), one with ``kv_lora_rank`` the latent-attention, routed-expert
decoder (``models/latent_moe_decoder.py``: one pool of latent rows a
layer and, with ``index_topk``, an indexer's NARROWER pool beside it in
the layers that have one: pools of two row widths under the one growing
page table), one with ``layer_types`` and a ``sliding_window`` the decoder of
window and full attention layers with routed experts
(``models/windowed_moe_decoder.py``: a ring of K/V pages a slot a window
layer beside a full layer's growing pools), one with ``linear_attn_config``
the decoder of gated delta-rule linear-attention layers with a few
grouped-query attention layers among them and routed experts
(``models/linear_attn_moe_decoder.py``: a float32 MATRIX state a head and
a convolution window a slot a linear layer, K/V page pools for the
attention layers, a held shard of the experts). The session knows no model:
the queue, the buckets, batched admission, the reservation, growth and
return of pages, results and the trace hooks are one code path, and a
builder's ``geometry["state"]`` names its ``page_pools`` and
``slot_arrays`` and, under ``windowed``, which pools are rings and of
what window. Fixed-size state costs the host
nothing: a prefill installs it for the slots it admits (after each
prompt's last real token, whatever its bucket was padded to; a reused slot
starts from its own prefill), and the live mask fed with every decode
dispatch keeps a dead slot's rows as they are.

A sibling of ``generation.SlotDecodeSession`` that the frontend's decode
worker drives in the same way (``enqueue``, ``admit_pending``, ``step``,
``cancel``, ``pump``, ``take_result``, ``free_slots``, ``active_slots``,
``pending_requests``, ``pool_conserved``, ``health``): the same
``ServingFrontend``, the same wire, the same ``ServingClient.generate(src,
src_len=)``. What differs is the model:

* **The prompt is the source, and admission IS its prefill.** ``src`` is
  the prompt's token ids (the first ``src_len`` of them). ``admit_pending``
  gathers the head of the queue, as many requests as slots are free, and
  prefills them SEVERAL A DISPATCH: one dispatch per length bucket and
  ``prompts_per_dispatch(bucket)`` prompts (a dispatch reads every
  weight of the model, every expert's too; a dispatch of one would pay
  that per prompt). A prefill program walks every token place of its
  shape, a row of padding like a prompt's: with ``prefill_rungs`` the
  builder gives a bucket one program a RUNG of prompt rows (1, 2, 4, ...
  up to the most a dispatch takes) and a dispatch runs the least rung
  that holds its prompts, so a loop that frees one slot at a time does
  not walk the whole budget for one prompt. The prefill writes the
  prompt's rows into the slot's pages, installs the slot's fixed-size
  state and samples the first token.
* **One page table for every KIND of pool.** The pools that grow with
  the sequence share one table (one pool of latent rows a layer, or a K
  and a V pool an attention layer); a builder's windowed pools share
  another, a RING of ``R`` columns a slot in which logical page ``j``
  (rows ``j * page_size`` and on) sits in column ``j % R``. Each kind has
  its own ``PagePool`` (refcount 1 a page: nothing is shared here), its
  own count of reserved pages and its own feeds. A slot's worst case is
  reserved at admission in every kind (prompt + new tokens; never more
  than ``R`` in a ring), pages are taken as the sequence grows, and a
  ring's page goes back to its free list the moment its last row is
  behind every query the next dispatch can hold. The tables and the live
  mask are the HOST's and are fed with every decode dispatch, so growing
  a slot, returning a page behind its window, finishing it or cancelling
  it costs no dispatch: ``cancel`` is bookkeeping.
* **EOS is not looked for**: a stream ends at ``max_new_tokens`` or when
  its client closes it (the wire's in-band cancel). Seeded weights never
  emit a meaningful EOS, and public serving benchmarks ignore it.

Not built for this session, and refused with a ``ServingError`` that says
so: forced prefixes and the prefix cache, fork groups (``n > 1``) and
copy-on-write, beam lanes, speculation, decode snapshots.

Tracing (``observability/tracing.py``): under the worker's round,
``admit`` > ``prefill`` > ``prefill.dispatch`` per bucket dispatch and
``step`` > ``step.dispatch``; the round counts ``prefill_prompts``,
``prefill_tokens``, ``prefill_dispatches``,
``prefill_pad_tokens`` (token places the dispatch walks for nothing: its
program's ``rows x bucket`` less the prompts' own tokens), ``pages_in_use``
(with windowed pools also by kind, ``full_pages_in_use`` and
``window_pages_in_use``, with ``window_pages_released``, the pages the
round gave back from behind its slots' windows, and ``full_rows_visible``
/ ``window_rows_visible``, the rows the decode dispatch's slots could see
in a full layer and in a window layer), ``state_slots_live`` (with a
model that has per-slot arrays: the slots whose rows the decode dispatch
had to update; ``state_bytes_live``, the bytes of those arrays a token step
reads and writes for them; ``kv_rows_visible``, the rows the dispatch's
slots could see in an attention layer's pools beside them), where the
builder's prefill walks a recurrence in chunks (``geometry
["prefill_chunk"]``) ``prefill_chunks`` / ``prefill_chunks_padded`` (the
chunks a prefill dispatch walked for real tokens, and those of its token
places that held only padding), under learned sparse attention ``latent_rows_resident``
(the rows the decode dispatch's slots hold a layer),
``latent_rows_selected`` (the sum over its slots of min(rows,
``index_topk``): what a layer's attention reads of them) and
``index_pages_in_use`` (the pages of an indexer's narrow pool: the latent
pools' own, one table) and, with routed experts,
``expert_max_over_mean`` (the most loaded expert's tokens over the mean,
over the dispatch's steps and expert layers, from the counts the expert
op returns WITH the step's tokens: no dispatch and no device sync of
their own) and, where the model holds a SHARD of its experts,
``experts_routed_tokens`` / ``experts_held_tokens`` (the (token, expert)
choices of the dispatch's steps and those that fell on a held expert)
and ``experts_held_hit`` (the held experts a step and layer that got a
token), from the same counts; where the router has zero-compute
(identity) experts among its outputs, ``experts_zero_tokens`` (the
choices that fell on one: the expert op's ``ZeroTokens``, fetched with the
same dispatch).
"""

import collections
import time

import numpy as np

from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels.paged_attention import pages_for
from paddle_tpu.models.decoder_programs import builder_for
from paddle_tpu.observability import explain as _explain
from paddle_tpu.observability import tracing as _tracing
from paddle_tpu.serving.generation import (
    NoFreeSlotError,
    SlotDecodeSession as _SlotSession,
    _active_slots,
    _pages_in_use,
    _sequences_total,
)
from paddle_tpu.serving.kv_pool import NoFreePageError, PagePool
from paddle_tpu.serving.server import ServingError

__all__ = ["DecoderOnlySession", "builder_for"]


class _PageKind(object):
    """The pages of one kind of pool: its ``PagePool``, the worst cases
    reserved in it, every slot's pages and the table fed for them.

    A slot holds the logical pages ``lo .. lo + len(pages) - 1`` (page
    ``j``: the rows of positions ``j * page_size`` and on). With no
    ``window`` ``lo`` stays 0 and the table row lists the pages in order,
    its tail aliased to the last one (the kernels' no-copy rule). With a
    ``window`` the row is a ring (page ``j`` in column ``j % cols``) and
    pages wholly before the first row a query can still see are given
    back."""

    def __init__(self, slots, page_size, cols, num_pages, limit, table_feed,
                 rows_feed, window=None):
        self.ps, self.cols, self.P = int(page_size), int(cols), \
            int(num_pages)
        self.limit = int(limit)       # logical pages of a whole sequence
        self.window = window
        self.table_feed, self.rows_feed = table_feed, rows_feed
        self.pool = PagePool(self.P)
        self.reserved = 0
        self.table = np.zeros((slots, self.cols), "int64")
        self.pages = {}               # slot -> [page ids], logical order
        self.lo = {}                  # slot -> its first logical page

    def worst_case(self, positions):
        return min(pages_for(positions, self.ps), self.cols)

    def hold(self, slot, write_at, rows_end):
        """Give ``slot`` the pages of the rows a dispatch whose first
        query stands at ``write_at`` can see, up to row ``rows_end - 1``
        (the last it writes). Returns the pages given back."""
        pages = self.pages.setdefault(slot, [])
        lo = self.lo.setdefault(slot, 0)
        released = 0
        if self.window:
            first = max(write_at - self.window + 1, 0) // self.ps
            while pages and lo < first:
                self.table[slot, lo % self.cols] = 0
                self.pool.deref(pages.pop(0))
                lo += 1
                released += 1
            self.lo[slot] = lo = lo if pages else first
        need = min(pages_for(rows_end, self.ps), self.limit) - lo
        if len(pages) < need:
            while len(pages) < need:
                pages.append(self.pool.acquire())
            self.write_row(slot, self.table[slot])
        return released

    def write_row(self, slot, row):
        """``slot``'s pages into ``row`` (its table row, or a prefill
        dispatch's)."""
        pages, lo = self.pages[slot], self.lo[slot]
        if self.window:
            row[(lo + np.arange(len(pages))) % self.cols] = pages
        else:
            row[:len(pages)] = pages
            row[len(pages):] = pages[-1]

    def drop(self, slot):
        for page in self.pages.pop(slot, ()):
            self.pool.deref(page)
        self.lo.pop(slot, None)
        self.table[slot] = 0

    @property
    def conserved(self):
        """free + allocated == pages - 1 (page 0 is the trash page), and
        every allocated page belongs to exactly one live slot."""
        held = sum(len(p) for p in self.pages.values())
        return (self.pool.free_count + self.pool.allocated_count
                == self.P - 1 and held == self.pool.allocated_count)


class _PagesOfSlot(object):
    """What the shared trace hooks sample (``_slot_pages.get(slot, ())``):
    every page ONE slot holds, of every kind; nothing is built for the
    slots nobody asks about."""

    def __init__(self, kinds):
        self._kinds = kinds

    def get(self, slot, default=()):
        if slot not in self._kinds[0].pages:
            return default
        return [p for k in self._kinds for p in k.pages[slot]]


class DecoderOnlySession(object):
    """Parameters
    ----------
    exe : Executor (or a stand-in with ``run``/``run_multi_step``).
    desc : dict of the model's config keys; ``builder_for`` chooses the
        builder from them.
    num_slots, max_prompt, max_new_tokens : the pool: a slot holds
        ``max_prompt + max_new_tokens`` positions.
    page_size : rows a page; every prefill bucket is a multiple of it.
    tokens_per_dispatch : decode tokens a slot a dispatch.
    prefill_buckets, prefill_token_budget : the builder's (defaults:
        powers of two of the page size; 2048 tokens a dispatch).
    prefill_rungs : ask the builder for a prefill program a rung of prompt
        rows under a bucket's most (module docstring): more programs to
        compile, for dispatches of few LONG prompts.
    num_pages : size of the pools that grow with the sequence, trash page
        included (default: full occupancy; a ring is always at full
        occupancy).
    probe_rows : the builder's (a check's fetch of a few slots' logits
        from the serving executable itself; 0: none). The slots are
        ``probe_slots`` (an array to write into), fed with every step.
    scope : must hold the parameters (``load_parameters``).

    ``admit_token_budget`` (an attribute, None at first: no bound) bounds
    the bucket token places ONE ``admit_pending`` call may dispatch: with
    long prompts the prefill of every free slot at once keeps the live
    streams' decode waiting for as many dispatches (96 empty slots of
    ~2800-token prompts: 17 s on one v5e); under a budget the rest of the
    queue is admitted by the next rounds, a decode dispatch between them.
    """

    def __init__(self, exe, desc, num_slots, max_prompt, max_new_tokens,
                 page_size, tokens_per_dispatch=4, prefill_buckets=None,
                 prefill_token_budget=2048, num_pages=None, sampler=None,
                 scope=None, dtype="bfloat16", probe_rows=0,
                 prefill_rungs=False):
        from paddle_tpu.executor import global_scope

        self._exe = exe
        self._scope = scope or global_scope()
        self._S = int(num_slots)
        self._max_prompt = int(max_prompt)
        self._max_new = int(max_new_tokens)
        self._K = int(tokens_per_dispatch)
        self._ps = int(page_size)
        positions = self._max_prompt + self._max_new
        if prefill_buckets is None:
            prefill_buckets, t = [], self._ps
            while t < self._max_prompt:
                prefill_buckets.append(t)
                t *= 2
            prefill_buckets.append(t)
        built = builder_for(desc)(
            desc, self._S, positions, self._ps, prefill_buckets,
            num_pages=num_pages,
            prefill_token_budget=prefill_token_budget, sampler=sampler,
            dtype=dtype, probe_rows=probe_rows,
            tokens_per_dispatch=self._K, prefill_rungs=prefill_rungs)
        self.geometry = geo = built["geometry"]
        self._buckets = geo["buckets"]
        if self._buckets[-1] < self._max_prompt:
            raise ValueError("the longest prefill bucket (%d) is shorter "
                             "than max_prompt %d: chunked prefill is not "
                             "built" % (self._buckets[-1], self._max_prompt))
        self._per_dispatch = geo["prompts_per_dispatch"]
        npp = geo["pages_per_slot"]
        if geo["num_pages"] - 1 < npp:
            raise ValueError("num_pages=%d cannot hold one full sequence "
                             "(%d pages)" % (geo["num_pages"], npp))
        # the pools that grow with the sequence first, then the rings
        self._kinds = [_PageKind(self._S, self._ps, npp, geo["num_pages"],
                                 npp, "page_table", "page_rows")]
        for ring in geo["state"].get("windowed", ()):
            self._kinds.append(_PageKind(
                self._S, self._ps, ring["pages_per_slot"],
                ring["num_pages"], npp, ring["table_feed"],
                ring["rows_feed"], window=int(ring["window"])))
        self._slot_pages = _PagesOfSlot(self._kinds)
        self._slot_state = bool(geo["state"]["slot_arrays"])
        # what one slot owns of the per-slot arrays, in bytes
        self._slot_state_bytes = sum(
            int(np.prod(a["shape"])) * np.dtype(np_dtype(a["dtype"])).itemsize
            for a in geo["state"]["slot_arrays"].values()) // self._S
        self._prefill_chunk = int(geo.get("prefill_chunk") or 0)
        # {bucket: {prompt rows: program}}
        self._prefill_progs = built["prefill_rungs"]
        self._step_prog = built["step"]
        self._fetch = built["fetches"]
        # what the frontend's worker reads of a session (its package-
        # internal contract): trg[0] is a placeholder, generated tokens
        # are trg[1..pos]; no token is ever equal to _eos
        self._T = self._max_new + 1
        self._bos, self._eos = 0, -1
        self._monitor = None
        self.beam_width = 1
        self._free = list(range(self._S - 1, -1, -1))
        self._live = {}          # slot -> {"pos", "n", "len", "trg"}
        self._live_mask = np.zeros((self._S, 1), "int64")
        self._pending = collections.deque()
        self._owner = {}
        self._results = {}
        self._next_req = 0
        self._trace_ids = {}
        self._slot_traces = {}
        self._trace_cow = {}     # the shared hooks' (never filled here)
        self.steps_done = 0
        # as SlotDecodeSession's: run between the decode dispatch's
        # launch and its wait
        self.in_flight = None
        self.prefill_dispatches = 0
        self.last_counters = {}
        # what the last admit_pending()/step() dispatched, for whoever
        # times them from outside: [(bucket, [prompt lengths])] and
        # (live slots, resident rows the dispatch's first step attends);
        # of those rows, the ones a window layer's queries could see
        self.last_prefills = []
        self.last_step = (0, 0)
        self.last_window_rows = 0
        # under learned sparse attention, the rows a layer's attention
        # reads of ``last_step``'s resident ones: index_topk a slot at most
        self._index_topk = int(geo.get("index_topk") or 0)
        self.last_selected_rows = 0
        self._released = 0       # ring pages given back since _count()
        self.probe_slots = np.zeros((int(probe_rows),), "int64")
        self.admit_token_budget = None
        # the pools' and the per-slot arrays' device allocations. The
        # builder's spans (``init``, ``prefill/<bucket>``, ``step``) are
        # roots: a span or a decorator around this whole constructor cost
        # the Jamba cell 4 s of its 21 s of IR building on the chip's host
        # (PERF.md section 6, PR 51)
        with _explain.setup_span("pools"):
            self._exe.run(built["init"], scope=self._scope)

    # -- what this session does not do ---------------------------------------
    _paged = True
    _prefix_cache = None

    def _unsupported(self, what):
        raise ServingError(
            "DecoderOnlySession does not support %s (forced prefixes and "
            "the prefix cache, fork groups and copy-on-write, beam and "
            "speculation are SlotDecodeSession's)" % what)

    def admit_group(self, src, n=1, src_len=None, prefix_tokens=None):
        if int(n) != 1 or prefix_tokens:
            self._unsupported("fork groups (n=%d) or forced prefixes" % n)
        return [self.admit(src, src_len)]

    def admit_beam(self, *a, **kw):
        self._unsupported("beam search")

    def take_beam_result(self, request_id):
        return None

    def prefix_cache_stats(self):
        return {}

    # -- tracing hooks: SlotDecodeSession's, as they are ---------------------
    _trace_admitted = _SlotSession._trace_admitted
    _trace_bank = _SlotSession._trace_bank
    _trace_cancel = _SlotSession._trace_cancel
    _trace_step = _SlotSession._trace_step
    _tokens_past = _SlotSession._tokens_past

    # -- introspection -------------------------------------------------------
    @property
    def step_program(self):
        return self._step_prog

    @property
    def health(self):
        from paddle_tpu.serving.degradation import HEALTHY

        return HEALTHY

    @property
    def free_slots(self):
        return len(self._free)

    @property
    def active_slots(self):
        return sorted(self._live)

    @property
    def pending_requests(self):
        return [r["id"] for r in self._pending]

    @property
    def free_pages(self):
        """Of the pools that grow with the sequence."""
        return self._kinds[0].pool.free_count

    @property
    def pages_in_use(self):
        """Over every kind of pool."""
        return sum(k.pool.allocated_count for k in self._kinds)

    @property
    def pages_in_use_by_kind(self):
        """``[pages]``: the growing pools' first, then each ring's."""
        return [k.pool.allocated_count for k in self._kinds]

    def slot_pages(self, slot):
        """``[(first logical page, [page ids])]`` of a live slot, a kind
        of pool each, in ``pages_in_use_by_kind``'s order."""
        return [(k.lo[slot], list(k.pages[slot])) for k in self._kinds]

    @property
    def pool_conserved(self):
        """In every kind of pool: free + allocated == pages - 1 (page 0
        is the trash page), and every allocated page belongs to exactly
        one live slot."""
        return all(k.conserved for k in self._kinds)

    def bucket_of(self, length):
        for t in self._buckets:
            if length <= t:
                return t
        raise ServingError("a prompt of %d tokens is longer than the "
                           "longest prefill bucket (%d)"
                           % (length, self._buckets[-1]))

    # -- the queue -----------------------------------------------------------
    def enqueue(self, src, src_len=None, prefix_tokens=None, trace_id=None):
        """Queue one request: ``src`` holds the prompt's ids, the first
        ``src_len`` of them (all when None). Returns its request id."""
        if prefix_tokens:
            self._unsupported("forced prefixes")
        ids = np.asarray(src, dtype="int64").ravel()
        n = len(ids) if src_len is None else int(np.ravel(src_len)[0])
        if not 1 <= n <= min(len(ids), self._max_prompt):
            raise ServingError(
                "prompt length %d is outside 1..%d (chunked prefill for "
                "longer prompts is not built)"
                % (n, min(len(ids), self._max_prompt)))
        rid = self._next_req
        self._next_req += 1
        entry = {"id": rid, "prompt": ids[:n].copy(), "len": n,
                 "prefix": None}
        if trace_id:
            self._trace_ids[rid] = str(trace_id)
            entry["t_enq"] = time.time()
            entry["round"] = _tracing.round_id()
        self._pending.append(entry)
        return rid

    def drop_pending(self, request_id):
        rid = int(request_id)
        for i, req in enumerate(self._pending):
            if req["id"] == rid:
                del self._pending[i]
                tid = self._trace_ids.pop(rid, None)
                tr = (_tracing.inflight_get(tid) if tid is not None
                      else None)
                if tr is not None and tr.origin == "session":
                    _tracing.finish(tr, outcome="dropped")
                return True
        return False

    # -- admission = batched prefill -----------------------------------------
    def _reserve(self, length, sign=1):
        """Reserve (``sign`` -1: give back) a prompt's worst case in
        every kind of pool."""
        for kind in self._kinds:
            kind.reserved += sign * kind.worst_case(length + self._max_new)

    def _fits(self, lengths):
        """Whether every kind of pool can reserve the worst cases of
        prompts of ``lengths`` beside what it has reserved."""
        return all(
            kind.reserved + sum(kind.worst_case(n + self._max_new)
                                for n in lengths) <= kind.P - 1
            for kind in self._kinds)

    def admit_pending(self):
        """Admit the head of the queue, as many requests as slots are
        free, the pool can reserve and ``admit_token_budget`` allows (one
        at least), in ONE prefill dispatch per length bucket (more when a
        bucket holds more prompts than a dispatch takes). Returns ``{slot:
        request_id}`` of this call's admissions."""
        take, places = [], 0
        self.last_prefills = []
        while self._pending and len(take) < len(self._free):
            if not self._fits([r["len"] for r in take]
                              + [self._pending[0]["len"]]):
                break      # a pool is reserved: wait for slots to end
            places += self.bucket_of(self._pending[0]["len"])
            if take and self.admit_token_budget \
                    and places > self.admit_token_budget:
                break      # the next round's, after a decode dispatch
            take.append(self._pending.popleft())
        if not take:
            return {}
        admitted = {}
        waiting = list(take)       # not dispatched yet, in queue order
        with _tracing.span("admit"):
            try:
                for bucket in self._buckets:
                    mine = [r for r in take
                            if self.bucket_of(r["len"]) == bucket]
                    per = self._per_dispatch[bucket]
                    for at in range(0, len(mine), per):
                        chunk = mine[at:at + per]
                        for r in chunk:
                            waiting.remove(r)
                        admitted.update(self._prefill(bucket, chunk))
            except BaseException:
                # the dispatch at fault was rolled back and its requests
                # are lost (the frontend tells their streams); the ones
                # not dispatched yet go back to the head, in order
                self._pending.extendleft(reversed(waiting))
                raise
        return admitted

    def _prefill(self, bucket, reqs):
        """One prefill dispatch of ``reqs`` (all of ``bucket``): slots
        and pages are taken first, the rows of padding point nowhere.
        It runs the least rung of rows that holds them."""
        T = bucket
        B = min(r for r in self._prefill_progs[T] if r >= len(reqs))
        ids = np.zeros((B, T), "int64")
        lens = np.zeros((B,), "int64")
        slot_idx = np.full((B,), self._S, "int64")
        page_rows = [np.zeros((B, k.cols), "int64") for k in self._kinds]
        last_idx = np.arange(B, dtype="int64") * T
        slots = []
        t_admit = time.time()
        try:
            for b, r in enumerate(reqs):
                slot = self._free.pop()
                slots.append(slot)
                n = r["len"]
                self._reserve(n)
                for kind, rows in zip(self._kinds, page_rows):
                    # the next query stands at position n
                    kind.hold(slot, n, n)
                    kind.write_row(slot, rows[b])
                ids[b, :n] = r["prompt"]
                lens[b], slot_idx[b] = n, slot
                last_idx[b] += n - 1
            with _tracing.span("prefill"):
                if _tracing.ENABLED:
                    _tracing.round_count("prefill_prompts", len(reqs))
                    _tracing.round_count("prefill_tokens", int(lens.sum()))
                    _tracing.round_count("prefill_pad_tokens",
                                         B * T - int(lens.sum()))
                    _tracing.round_count("prefill_dispatches", 1)
                    if self._prefill_chunk:
                        walked = int((-(-lens // self._prefill_chunk)).sum())
                        _tracing.round_count("prefill_chunks", walked)
                        _tracing.round_count(
                            "prefill_chunks_padded",
                            B * -(-T // self._prefill_chunk) - walked)
                feed = {"prompt_ids": ids.reshape(-1), "prompt_len": lens,
                        "slot_idx": slot_idx, "last_idx": last_idx}
                for kind, rows in zip(self._kinds, page_rows):
                    feed[kind.rows_feed] = rows
                with _tracing.span(".dispatch"):
                    (first,) = self._exe.run(
                        self._prefill_progs[T][B], feed=feed,
                        fetch_list=[self._fetch["first_token"]],
                        scope=self._scope)
            first = np.asarray(first).reshape(-1)
        except BaseException:
            for slot, r in zip(slots, reqs):
                for kind in self._kinds:
                    kind.drop(slot)
                self._reserve(r["len"], -1)
                self._free.append(slot)
            raise
        self.prefill_dispatches += 1
        self.last_prefills.append((T, [r["len"] for r in reqs]))
        out = {}
        for b, (slot, r) in enumerate(zip(slots, reqs)):
            trg = np.zeros((self._T + self._K,), "int64")
            trg[1] = first[b]
            # ``pos`` is what the streams have been shown: the first
            # token is shown with the first decode dispatch's
            self._live[slot] = {"pos": 0, "n": 1, "len": r["len"],
                                "trg": trg}
            self._live_mask[slot, 0] = 1
            self._owner[slot] = out[slot] = r["id"]
            if r["id"] in self._trace_ids:
                self._trace_admitted(r, slot, t_admit)
            _sequences_total.inc(event="admitted")
        _active_slots.set(len(self._live))
        _pages_in_use.set(self.pages_in_use)
        return out

    def admit(self, src, src_len=None, prefix_tokens=None):
        """Admit ONE prompt now (a prefill dispatch of its own); its
        slot. ``NoFreeSlotError``/``NoFreePageError`` when it cannot."""
        if not self._free:
            raise NoFreeSlotError("all %d slots are occupied" % self._S)
        self.enqueue(src, src_len, prefix_tokens=prefix_tokens)
        req = self._pending.pop()
        self.last_prefills = []
        if not self._fits([req["len"]]):
            raise NoFreePageError("the pool cannot reserve this prompt's "
                                  "worst case")
        with _tracing.span("admit"):
            (slot,) = self._prefill(self.bucket_of(req["len"]), [req])
        self._owner.pop(slot, None)   # a direct admission has no owner
        return slot

    # -- pages ---------------------------------------------------------------
    def _release(self, slot):
        st = self._live.pop(slot)
        for kind in self._kinds:
            kind.drop(slot)
        self._reserve(st["len"], -1)
        self._live_mask[slot, 0] = 0
        self._free.append(slot)
        return st

    # -- decode --------------------------------------------------------------
    def step(self):
        """``tokens_per_dispatch`` tokens for every live slot in one
        dispatch; ``{slot: trg}`` of the sequences that reached
        ``max_new_tokens`` (their slots and pages are free again)."""
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
        rows = ring_rows = chosen_rows = 0
        ring = self._kinds[-1].window or 0
        for slot, st in self._live.items():
            # step j writes the row of position len + n - 1 + j
            at = st["len"] + st["n"] - 1
            for kind in self._kinds:
                self._released += kind.hold(slot, at, at + self._K)
            rows += at + 1
            ring_rows += min(at + 1, ring)
            chosen_rows += min(at + 1, self._index_topk)
        self.last_step = (len(self._live), rows)
        self.last_window_rows = ring_rows
        self.last_selected_rows = chosen_rows
        fetch = [self._fetch["token"]]
        # the expert layers' counts come back with the tokens, in the
        # dispatch's own fetch: no dispatch and no sync of their own
        counted = [key for key in ("expert_tokens", "zero_tokens")
                   if self._fetch.get(key)]
        fetch += [self._fetch[key] for key in counted]
        feed = {"live": self._live_mask}
        for kind in self._kinds:
            feed[kind.table_feed] = kind.table
        if len(self.probe_slots):
            feed["probe_slots"] = self.probe_slots
        with _tracing.span(".dispatch"):
            out = self._exe.run_multi_step(
                self._step_prog, self._K, feed=feed, fetch_list=fetch,
                scope=self._scope, stack_fetches=True,
                in_flight=self.in_flight)
        toks = np.asarray(out[0])                       # [K, S, 1]
        self.steps_done += 1
        self._count(**dict(zip(counted, out[1:])))
        finished = {}
        for slot in list(self._live):
            st = self._live[slot]
            n = st["n"]
            st["trg"][n + 1:n + 1 + self._K] = toks[:, slot, 0]
            st["n"] = st["pos"] = min(n + self._K, self._max_new)
            if st["n"] >= self._max_new:
                finished[slot] = self._release(slot)["trg"][:self._T]
                _sequences_total.inc(event="completed")
        _active_slots.set(len(self._live))
        _pages_in_use.set(self.pages_in_use)
        if traced and pre_pos:
            self._trace_step(pre_pos, finished, t_step, time.time(), False)
        return finished

    def _count(self, expert_tokens=None, zero_tokens=None):
        """The round's counters, from what came back with the tokens."""
        counters = {"pages_in_use": self.pages_in_use}
        if len(self._kinds) > 1:
            full = self._kinds[0].pool.allocated_count
            counters.update(
                full_pages_in_use=full,
                window_pages_in_use=counters["pages_in_use"] - full,
                window_pages_released=self._released,
                full_rows_visible=self.last_step[1],
                window_rows_visible=self.last_window_rows)
            self._released = 0
        if self._slot_state:
            counters.update(
                state_slots_live=self.last_step[0],
                state_bytes_live=2 * self.last_step[0]
                * self._slot_state_bytes,
                kv_rows_visible=self.last_step[1])
        if self._index_topk:
            # the narrow pools share the latent pools' table: a page in
            # use is one page of every pool of either width
            counters.update(
                latent_rows_resident=self.last_step[1],
                latent_rows_selected=self.last_selected_rows,
                index_pages_in_use=self._kinds[0].pool.allocated_count)
        if expert_tokens is not None:
            c = np.asarray(expert_tokens, "float64")    # [K, layers, E]
            mean = c.mean(axis=-1)
            ratio = c.max(axis=-1)[mean > 0] / mean[mean > 0]
            if ratio.size:
                counters["expert_max_over_mean"] = float(ratio.mean())
            experts = self.geometry.get("experts")
            if experts and (experts["held"] < experts["of"]
                            or zero_tokens is not None):
                # a shard of the experts: the (token, expert) choices the
                # dispatch's steps made, those that fell on a held expert,
                # and the held experts a step and layer that got any
                counters.update(
                    experts_routed_tokens=int(
                        c.shape[0] * c.shape[1] * self.last_step[0]
                        * experts["top_k"]),
                    experts_held_tokens=int(c.sum()),
                    experts_held_hit=float((c > 0).sum(axis=-1).mean()))
            if zero_tokens is not None:
                # of those choices, the ones that fell on a zero-compute
                # (identity) expert: no expert's weights are read for them
                counters["experts_zero_tokens"] = int(
                    np.asarray(zero_tokens).sum())
        self.last_counters = counters
        if _tracing.ENABLED:
            for key, value in counters.items():
                _tracing.round_count(key, value)

    def cancel(self, slot):
        """Abort one live sequence: its slot and pages are free again,
        nothing is banked. Host bookkeeping only: the next dispatch is
        fed a table and a mask without it. True when it was live."""
        slot = int(slot)
        if slot not in self._live:
            return False
        with _tracing.span("cancel"):
            self._release(slot)
            rid = self._owner.pop(slot, None)
            if self._slot_traces or self._trace_ids:
                self._trace_cancel(slot, rid)
        if _tracing.ENABLED:
            _tracing.round_count("cancel_rows", 1)
        _sequences_total.inc(event="cancelled")
        _active_slots.set(len(self._live))
        _pages_in_use.set(self.pages_in_use)
        return True

    def cancel_many(self, slots):
        """:meth:`cancel` for each of ``slots`` (``SlotDecodeSession``'s
        method of the same name batches a table dispatch; here a cancel
        dispatches nothing, so there is nothing to batch). The slots
        that were live."""
        return [slot for slot in slots if self.cancel(slot)]

    def pump(self):
        """One scheduler round without a frontend: ``admit_pending``, one
        ``step``; ``{request_id: trg}`` of the requests that finished,
        which are also banked for ``take_result``."""
        self.admit_pending()
        finished = {}
        for slot, trg in self.step().items():
            rid = self._owner.pop(slot, None)
            if rid is not None:
                finished[rid] = self._results[rid] = trg
                self._trace_bank(rid)
        return finished

    def take_result(self, request_id):
        rid = int(request_id)
        out = self._results.pop(rid, None)
        if out is not None:
            self._trace_ids.pop(rid, None)
        return out

    def tokens_of(self, slot):
        """The tokens a live slot has generated so far (the first comes
        from its prefill)."""
        st = self._live[int(slot)]
        return st["trg"][1:st["n"] + 1].copy()
