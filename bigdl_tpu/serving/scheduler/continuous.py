"""Continuous batching for the transformer generate path.

``TransformerLM.generate`` is run-to-completion batching: one prompt
batch enters, ``lax.scan`` decodes until the LONGEST request finishes,
and every short request pads the batch until then — at mixed request
lengths most of the device work is wasted decode steps for sequences
that already finished.  This scheduler makes KV-cache capacity the
admission unit instead (the vLLM/Orca-style design, built directly on
the existing ``TransformerLM`` decode stack so the math stays on
device), in three compounding pieces:

* **Block-paged KV**: the cache is a
  pool of fixed-size pages behind a free-list
  :class:`~.paging.PageAllocator`; a slot owns a *page list* (a
  host-side page table row), so **capacity is tokens actually held**,
  not ``num_slots x max_len`` rows provisioned.  A request that can
  never fit the pool sheds typed (``SlotCapacityError``); one that
  merely cannot fit *right now* is held back and placed when pages
  free up.
* **Content-hash prefix cache** (``prefix_cache=True``, the default):
  full pages of a prompt are published refcounted + read-only under a
  chained token-content hash (:class:`~.paging.PrefixCache`), so a
  shared system prompt is prefilled ONCE and every later request
  attaches its pages and prefills only its suffix — the dominant cost
  at consumer traffic with long common heads.  Divergence is
  copy-on-write by construction: a reader's first write position is
  the end of its shared prefix, which lands in its own freshly
  allocated page; the shared page bytes are never touched.
* **Speculative decoding** (``draft_model=...``): a small resident
  draft (PR 9's packed int8 trees make one nearly free to hold)
  proposes ``spec_k`` tokens per chunk through ``decode_pages`` on a
  pool of its own (slot ``i`` owns pages ``i*Lp .. (i+1)*Lp - 1``: a
  fixed table, no allocator); the target model verifies all of them
  in ONE ``decode_pages`` pass and
  the host accepts the longest prefix that matches the target's own
  greedy picks, plus the target's correction token — so accepted
  output is exactly the target model's greedy path (the bit-equality
  PR 8 already proves), and a chunk emits up to ``spec_k + 1`` tokens
  for one target dispatch.

Around them: admit per decode chunk into free slots (prompt suffix
padded to a :class:`~.buckets.BucketLadder` rung), evict on finish,
per-chunk ``serve.slots``/``serve.pages`` occupancy records, and EAGER
capacity enforcement at ``submit()`` (an overrun write is additionally
redirected to the pool's trash page, so it cannot reach a neighbor's —
or a shared prefix's — page even if the host bookkeeping were wrong).

ONE cache layout and ONE decode program: the generator compiles a
prefill per bucket and a ``lax.scan`` of ``model.decode_pages`` over
``steps_per_sync`` steps.  Whether a read goes through the Pallas
paged-attention kernel or gathers the row's pages is decided inside
the attention layer from the backend it runs on
(``ops.attention.paged_attention_enabled``), not here.  A model is
servable when it has ``init_paged_cache`` and ``decode_pages``.

Right-padded prefill is safe by construction, as before: garbage K/V
beyond the real length is hidden by the validity predicate
(``l <= pos``) and overwritten the step it first becomes visible.  The
same argument covers speculative rejects: a rejected proposal's K/V
sit at positions beyond the accepted frontier, invisible until the
very chunk that overwrites them.

A model may DECLARE a second kind of state (``model.recurrent_state``: a
linear-attention or state-space layer keeps a fixed-size state per SLOT,
which no page carries).  Everything that differs then follows from that
declaration and not from an option: the cache is the model's own tree
(``{"pages": ..., "slots": ...}``, donated whole), the prefill names its
slot and always starts at position 0 (the model zeroes the slot's state
there, in-graph), inactive rows leave their state bit for bit, and what
moves or shares pages only — the prefix cache, a draft model's verify,
sessions and their parking — is declined or refused typed
(``RecurrentStateError``) instead of resuming from a state nobody saved.
A model may also declare ``decode_counters``: small integers its
``decode_pages`` returns beside the cache, reduced over a chunk's steps
in-graph and carried to the host by the sync that is there, onto the
``serve.decode`` and ``serve.prefill`` spans.
"""

from __future__ import annotations

import itertools
import logging
import os
import threading
import time
from concurrent.futures import Future
from typing import List, Optional, Sequence

import numpy as np

from bigdl_tpu.observability import ledger as run_ledger
from bigdl_tpu.observability import tracer
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.serving.errors import (DrainingError, InvalidRequestError,
                                      MemoryBudgetError, QueueFullError,
                                      RecurrentStateError,
                                      SlotCapacityError)
from bigdl_tpu.serving.scheduler.buckets import BucketLadder
from bigdl_tpu.serving.scheduler.paging import (HostOffloadTier,
                                                PageAllocator, PrefixCache)

logger = logging.getLogger("bigdl_tpu.serving")

_rids = itertools.count(1)


class Timeline:
    """One request's lifecycle on ``time.monotonic()``, always kept (the
    ledger may be off) and readable by the client as ``future.timeline``
    once the future resolved; the scheduler thread is its only writer.

    ``t_submit`` — ``submit()`` accepted it; ``t_admit`` — the scheduler
    took it out of the queue and began to place it (a held-back request
    keeps its first); ``t_first`` — the host holds its first token;
    ``t_last`` — the newest delivery of tokens to it.  A delivery is the
    prefill's first token or a decode chunk that emitted for it:
    ``n_chunks`` counts them, ``gaps_s`` lists the ``n_chunks - 1``
    intervals between consecutive ones (prefills of other requests
    included) and ``max_gap_s`` is their largest.  A stamp the request
    never reached stays None."""

    __slots__ = ("t_submit", "t_admit", "t_first", "t_last", "n_chunks",
                 "max_gap_s", "gaps_s")

    def __init__(self):
        self.t_submit = time.monotonic()
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        self.n_chunks = 0
        self.max_gap_s = 0.0
        self.gaps_s: List[float] = []

    def delivered(self, now: float) -> Optional[float]:
        """Note a delivery of tokens at ``now``; returns the gap since
        the one before it (None for the first)."""
        gap = None
        if self.t_last is None:
            self.t_first = now
        else:
            gap = now - self.t_last
            self.gaps_s.append(gap)
            if gap > self.max_gap_s:
                self.max_gap_s = gap
        self.t_last = now
        self.n_chunks += 1
        return gap

    def fields(self) -> dict:
        """What a ``serve.request`` record carries of it."""
        out = {"t_submit": self.t_submit, "n_chunks": self.n_chunks,
               "max_gap_s": self.max_gap_s}
        if self.t_admit is not None:
            out["queue_s"] = self.t_admit - self.t_submit
        if self.t_first is not None:
            out["ttft_s"] = self.t_first - self.t_submit
            out["gaps_s"] = list(self.gaps_s)
        return out


class GenRequest:
    """One admitted generation request: a 1-based prompt, a token
    budget, a future resolving to the generated 1-based ids
    (``np.ndarray``, length ``max_new`` — shorter only on ``eos_id``);
    the future carries the request's :class:`Timeline`."""

    __slots__ = ("rid", "prompt", "max_new", "future", "deadline",
                 "timeline", "slot", "tokens", "counted", "session")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 session: Optional[str] = None):
        self.rid = next(_rids)
        self.prompt = prompt
        self.max_new = int(max_new)
        self.future: Future = Future()
        self.timeline = self.future.timeline = Timeline()
        self.deadline = None            # AdmissionQueue duck contract
        self.slot: Optional[int] = None
        self.tokens: List[int] = []
        self.counted = False            # prefix census: count once even
                                        # if held back and re-placed
        self.session = session          # multi-turn session id (r20)

    @property
    def t_submit(self) -> float:
        return self.timeline.t_submit


class Session:
    """One multi-turn generation session (r20): the KV built by earlier
    turns stays live between turns, so a continuing turn prefills only
    ``tokens[kv_pos:] + new_prompt`` through the EXISTING shared-prefix
    prefill executable (``start = kv_pos``) — no new compiled programs,
    bit-equal to re-running the whole history by construction.

    States: ``new`` (no KV yet) → ``active`` (slot-bound, a turn is
    decoding) → ``resident`` (idle; private pages live on device) ⇄
    ``parked`` (idle; private pages D2H'd to the host offload tier,
    page ids freed).  Shared prefix pages are NEVER parked: the session
    keeps its prefix-chain refs in every state, so a page another
    reader may be attending into stays on device, refcount-pinned.

    ``row`` is the session's page-table prefix for positions
    ``[0, kv_pos)`` — shared head first, then private pages in logical
    order; ``pages`` is just the private tail of it (what park moves
    and close frees).  The cache never holds KV for the final emitted
    token (its KV is never written), hence ``kv_pos == len(tokens)-1``
    between turns.  All mutation happens on the scheduler thread; the
    submit thread only reads ``tokens`` and flips ``busy`` under the
    generator lock."""

    __slots__ = ("sid", "tokens", "kv_pos", "row", "pages", "keys",
                 "state", "busy", "last_used")

    def __init__(self, sid: str):
        self.sid = sid
        self.tokens: List[int] = []     # full logical history (1-based)
        self.kv_pos = 0                 # cache positions held
        self.row = np.zeros(0, np.int32)  # page ids for [0, kv_pos)
        self.pages: List[int] = []      # private page ids (device)
        self.keys: List[str] = []       # pinned prefix-chain keys
        self.state = "new"
        self.busy = False               # a turn is queued or decoding
        self.last_used = time.monotonic()

    @property
    def shared_pages(self) -> int:
        return len(self.keys)


class _Control:
    """A scheduler-thread command (park / close-session) riding the
    admission queue: FIFO with real work, wakes the idle block, and is
    always processed by the one thread that owns the page table."""

    __slots__ = ("op", "sid", "future", "deadline", "priority",
                 "t_submit")

    def __init__(self, op: str, sid: str):
        self.op = op
        self.sid = sid
        self.future: Future = Future()
        self.deadline = None            # AdmissionQueue duck contract
        self.priority = 0
        self.t_submit = time.monotonic()


class SlotManager:
    """KV-cache slots as the admission unit: allocation, release, and
    the EAGER capacity check that keeps over-length requests out of the
    decode loop entirely.  ``pool_tokens`` adds the
    token-pool bound: a request needing more cache tokens than the
    whole page pool holds can NEVER be placed and sheds typed."""

    def __init__(self, num_slots: int, max_len: int, max_prompt: int,
                 pool_tokens: Optional[int] = None):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.max_prompt = int(max_prompt)
        self.pool_tokens = None if pool_tokens is None else int(pool_tokens)
        self._free = list(range(num_slots - 1, -1, -1))  # pop() -> slot 0 first

    def check(self, prompt_len: int, max_new: int) -> None:
        """Typed shed for a request that can NEVER fit — the guard for
        ``TransformerLM.decode``'s silent clamp-and-corrupt overrun."""
        if prompt_len + max_new > self.max_len:
            raise SlotCapacityError(
                f"prompt {prompt_len} + max_new {max_new} exceeds the "
                f"KV-cache capacity {self.max_len}: admitting it would "
                "overrun the cache (decode clamps an overrun into the "
                "last slot and corrupts it) — shed eagerly instead")
        if prompt_len > self.max_prompt:
            raise SlotCapacityError(
                f"prompt {prompt_len} exceeds the largest prefill "
                f"bucket {self.max_prompt}")
        if self.pool_tokens is not None \
                and prompt_len + max_new - 1 > self.pool_tokens:
            raise SlotCapacityError(
                f"prompt {prompt_len} + max_new {max_new} needs "
                f"{prompt_len + max_new - 1} cache tokens but the page "
                f"pool holds {self.pool_tokens} in total — page "
                "exhaustion is certain, shed eagerly instead")

    def alloc(self) -> Optional[int]:
        return self._free.pop() if self._free else None

    def release(self, slot: int) -> None:
        self._free.append(slot)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def active_count(self) -> int:
        return self.num_slots - len(self._free)


def _row_bytes(tree) -> int:
    """Bytes of ONE row (page, slot) across every array of ``tree``."""
    import jax
    return int(sum(int(np.prod(a.shape[1:])) * np.dtype(a.dtype).itemsize
                   for a in jax.tree_util.tree_leaves(tree)))


class ContinuousGenerator:
    """Continuous-batching front for ``TransformerLM`` generation.

    ``submit(prompt, max_new=...)`` either raises a typed shed
    (``QueueFullError`` / ``DrainingError`` / ``SlotCapacityError`` /
    ``InvalidRequestError``) or returns a future resolving to the
    generated 1-based token ids.  Greedy by default; ``temperature > 0``
    samples (per-step keys split from ``rng``; note the key stream
    differs from ``TransformerLM.generate``'s, so sampled outputs match
    only distributionally).  Use as a context manager or call
    :meth:`drain`.
    """

    def __init__(self, model, params=None, state=None, *,
                 num_slots: int = 4,
                 max_len: Optional[int] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 steps_per_sync: int = 4,
                 temperature: float = 0.0,
                 rng=None,
                 eos_id: Optional[int] = None,
                 queue_capacity: int = 256,
                 cache_dtype=None,
                 warmup: bool = True,
                 quantize: Optional[str] = None,
                 donate_cache: Optional[bool] = None,
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 draft_model=None,
                 draft_params=None,
                 draft_state=None,
                 draft_quantize: Optional[str] = None,
                 spec_k: int = 4,
                 calibration_prompts=None,
                 ledger_tags: Optional[dict] = None,
                 budgeter=None,
                 budget_tenant: Optional[str] = None):
        """``quantize``: ``"w8"``/``"int8"`` serves prefill and decode
        from an int8-packed copy of the params (fused dequant-matmul in
        the qkv/ffn projections; ``mem.params`` ledger record for the
        residency win); ``"w4"``/``"int4"`` and ``"f8"``/``"fp8"`` are
        the r14 rungs on the same packed format — 0.25x / 0.5x int8's
        weight bytes, each behind its declared ``quant.RUNG_BUDGETS``
        accuracy budget (bench-tune gates them).  ``"w8a8"`` (r15, the
        r14 follow-up) additionally bakes CALIBRATED per-tensor
        activation scales into the packed leaves so prefill and every
        decode step run int8 x int8 through the fused kernels — it
        needs ``calibration_prompts``: a few representative token-id
        prompts run through the fp model once (eagerly) to fix the
        scales, exactly like ``DLClassifier(calibration_rows=...)``;
        the deployed scales are auditable via the ``quant.calibration``
        ledger record, and the rung serves under its declared
        ``quant.RUNG_BUDGETS["w8a8"]`` budget.

        ``ledger_tags``: extra fields merged into every ledger record
        this generator emits (``run.start``/``run.end``,
        ``serve.request``/``serve.shed``/``serve.slots``/…) — the
        fleet registry passes ``{"tenant": name}`` so a multi-tenant
        run directory stays attributable per tenant.

        ``donate_cache``: donate the KV-cache pytree
        into the prefill/decode-chunk executables so each chunk updates
        the cache IN PLACE instead of holding old+new generations live
        (the cache is the dominant HBM tenant at high slot counts).
        Default ``None`` = donate everywhere but the CPU backend (the
        allreduce.py platform gate); greedy output is bit-equal either
        way — regression-tested.

        ``budgeter``/``budget_tenant`` (r20): a
        :class:`~bigdl_tpu.serving.scheduler.membudget.MemoryBudgeter`
        every device page this generator allocates is charged to (class
        ``kv_pages``; publishes transfer to ``prefix_pages``; parks to
        ``host_offload``), under the tenant name ``budget_tenant``
        (default: the ``ledger_tags`` tenant, else ``"default"``).  A
        request whose worst-case KV bytes exceed the tenant budget
        sheds typed (``MemoryBudgetError``) at ``submit()``; placement
        pressure runs the degradation ladder — budgeter reclaimers
        (rung executables), prefix-cache leaves, then idle-session
        parking — before holding back or shedding.

        ``page_size``/``num_pages``: the page pool (module doc).
        ``num_pages`` defaults to a full table for every slot
        (``num_slots * ceil(max_len / page_size)``); smaller pools make
        capacity genuinely token-scarce.  ``prefix_cache`` (default: on)
        shares page-aligned prompt prefixes across requests.
        ``draft_model``/``draft_params``/``draft_state``/
        ``spec_k`` arm speculative decoding (greedy only; the draft
        must share the target's vocab); ``draft_quantize="w8"`` packs
        the draft int8 — the nearly-free-resident configuration."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.ops import quant

        self.model = model
        self.params = params if params is not None else model.params
        self.state = state if state is not None else model.state
        self._tags = dict(ledger_tags or {})
        # what the model declares (module doc): a per-slot recurrent
        # state, and counters its decode_pages returns
        self._recurrent = bool(getattr(model, "recurrent_state", False))
        self._counted = dict(getattr(model, "decode_counters", None) or {})
        if draft_model is not None and (
                self._recurrent
                or getattr(draft_model, "recurrent_state", False)):
            raise RecurrentStateError(
                "speculative decoding rolls rejected proposals back by "
                "position, which pages allow and a recurrent state does "
                "not: the draft's proposals and the verify pass would "
                "leave the slot's state past the accepted frontier")
        qmode = quant.normalize_mode(quantize)
        if qmode is not None:
            if qmode not in ("w8", "w8a8", "w4", "f8"):
                raise ValueError(
                    f"unsupported quantize mode {quantize!r} for "
                    "generation: use 'w8'/'int8', 'w8a8', 'w4'/'int4' "
                    "or 'f8'/'fp8'")
            calib = None
            if qmode == "w8a8":
                prompts = list(calibration_prompts or ())
                if not prompts:
                    raise ValueError(
                        "quantize='w8a8' needs calibration_prompts: a "
                        "few representative token-id prompts run "
                        "through the fp model once to fix the "
                        "per-tensor activation scales (weight-only "
                        "quantization is 'w8')")
                # one eager fp forward per prompt arms every quantized
                # matmul site's absmax observer (quant.calibrate); the
                # resulting scales are baked into the packed leaves as
                # "sx", so every decode step runs int8 x int8
                batches = [np.asarray(p, np.int32).reshape(1, -1)
                           for p in prompts]
                calib = quant.calibrate(model, self.params, self.state,
                                        batches)
            # extra_keys=("tok",): decode/decode_pages fully support a
            # packed tied embedding/head table (any r14 rung — the
            # gather and logit matmul dispatch on the leaf kind), and
            # it is the dominant residual tenant of a quantized LM —
            # leaving it fp would undercut the residency win
            self.params = quant.quantize_params(self.params, mode=qmode,
                                                calib=calib,
                                                extra_keys=("tok",))
            quant.emit_param_bytes(self.params,
                                   kind="ContinuousGenerator",
                                   mode=qmode, **self._tags)
        self.quantize = qmode
        if donate_cache is None:
            donate_cache = quant.donation_supported()
        self._donate = bool(donate_cache)
        self.max_len = int(max_len or model.max_len)
        if getattr(model, "position", None) == "learned" \
                and self.max_len > model.max_len:
            raise ValueError(
                f"cache length {self.max_len} exceeds the learned-"
                f"position table length {model.max_len}")
        self.seq_ladder = BucketLadder(
            seq_buckets if seq_buckets is not None else [self.max_len],
            name="seq")
        if self.seq_ladder.max > self.max_len:
            raise ValueError(
                f"largest seq bucket {self.seq_ladder.max} exceeds the "
                f"cache length {self.max_len}")
        self.steps_per_sync = int(steps_per_sync)
        if self.steps_per_sync < 1:
            raise ValueError("steps_per_sync must be >= 1")
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self._cache_dtype = cache_dtype or jnp.float32
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        # greedy mode never consumes the keys: reuse one constant batch
        # instead of paying two host dispatches per chunk splitting keys
        # nobody reads
        self._greedy_keys = None
        if self.temperature <= 0:
            self._greedy_keys = jax.random.split(
                jax.random.PRNGKey(0), max(int(steps_per_sync), 1))

        # -- paging ----------------------------------------------------------
        n = int(num_slots)
        ps = int(page_size)
        lp = -(-self.max_len // ps)          # page-table width
        if num_pages is None:
            num_pages = n * lp               # a full table for every slot
        self._alloc = PageAllocator(int(num_pages), ps)
        if prefix_cache is None:
            prefix_cache = True
        # a shared page carries the prefix's keys, not the recurrent
        # state after it: declined (counted below), not silently wrong
        prefix_declined = bool(prefix_cache) and self._recurrent
        self._prefix = PrefixCache(ps) \
            if prefix_cache and not self._recurrent else None
        self._lp = lp
        self._page_table = np.full((n, lp), self._alloc.trash, np.int32)
        self._slot_priv: List[List[int]] = [[] for _ in range(n)]
        self._slot_keys: List[List[str]] = [[] for _ in range(n)]
        self._slot_shared = [0] * n      # shared-prefix tokens/slot
        self._offload = HostOffloadTier()
        self._sessions: "dict[str, Session]" = {}
        # do reads go through the paged-attention kernel?  The layer
        # decides that from the backend; read once, for the spans'
        # walk counters and stats()
        from bigdl_tpu.ops.attention import (paged_attention_enabled,
                                             paged_block_pages)
        self._kernel_reads = paged_attention_enabled()
        self._walk_block = paged_block_pages(ps, lp)   # pages a block
        self._pending: Optional[GenRequest] = None

        self.slots = SlotManager(n, self.max_len, self.seq_ladder.max,
                                 pool_tokens=self._alloc.capacity_tokens)

        # -- speculative decoding --------------------------------------------
        self._draft = draft_model
        self.spec_k = int(spec_k)
        if self._draft is not None:
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            if self.temperature > 0:
                raise ValueError(
                    "speculative decoding is greedy-only: the accept "
                    "rule compares draft proposals against the target "
                    "model's argmax path")
            if getattr(self._draft, "vocab_size", None) \
                    != getattr(model, "vocab_size", None):
                raise ValueError(
                    f"draft vocab {getattr(self._draft, 'vocab_size', '?')}"
                    f" != target vocab {getattr(model, 'vocab_size', '?')}"
                    " — proposals would not be comparable")
            self._draft_params = (draft_params if draft_params is not None
                                  else self._draft.params)
            self._draft_state = (draft_state if draft_state is not None
                                 else self._draft.state)
            dq = quant.normalize_mode(draft_quantize)
            if dq is not None:
                if dq != "w8":
                    raise ValueError(f"unsupported draft_quantize "
                                     f"{draft_quantize!r}: use 'w8'")
                self._draft_params = quant.quantize_params(
                    self._draft_params, mode="w8", extra_keys=("tok",))
                quant.emit_param_bytes(self._draft_params,
                                       kind="ContinuousGenerator.draft",
                                       mode="w8")
            # the draft's own pool behind a FIXED table: slot i owns
            # pages i*Lp .. (i+1)*Lp - 1
            self._dtable = jnp.arange(n * lp, dtype=jnp.int32).reshape(n, lp)
            self._dcache = self._new_draft_cache()
        else:
            self._dcache = None

        self.metrics = Metrics()
        if prefix_declined:
            self.metrics.incr("serve.gen.prefix.declined")
        self._closed = False
        self._lock = threading.Lock()
        from bigdl_tpu.serving.queue import AdmissionQueue
        self.queue = AdmissionQueue(
            queue_capacity,
            on_depth=lambda d: self.metrics.set("serve.gen queue depth",
                                                d, unit="scalar"))

        # per-slot host state (the worker thread owns these)
        self._requests: List[Optional[GenRequest]] = [None] * n
        self._tokens = np.ones(n, np.int32)
        self._pos = np.zeros(n, np.int32)
        self._active = np.zeros(n, bool)
        self._limit = np.zeros(n, np.int32)
        # device-memory budgeter (r20): every page this generator
        # allocates is charged under the tenant name; the pool
        # reservation itself is REPORTED (stats) but not charged —
        # budgets size what is USED, and parking exists exactly so
        # use can exceed the pool
        self._budget = budgeter
        self._bt = budget_tenant or self._tags.get("tenant", "default")
        self._cache = self._new_paged_cache()
        # bytes of ONE page across every layer's pools
        pools = self._cache["pages"] if self._recurrent else self._cache
        self._page_bytes = _row_bytes(pools)
        from bigdl_tpu.ops.attention import paged_pool_dims
        # lanes of a token's row (padding included) in the first pool
        self._pool_width = int(paged_pool_dims(
            jax.tree_util.tree_leaves(pools)[0])[1])
        # recurrent state of ONE slot
        self._state_bytes = _row_bytes(self._cache["slots"]) \
            if self._recurrent else 0
        # the same two by kind of layer, where the model can tell (a
        # window layer's ring and a recurrent state are both a slot's)
        by_kind = getattr(model, "state_bytes", None)
        self._bytes_by_kind = by_kind(self._cache) if by_kind else None
        # a model that mixes window and full attention layers says how
        # many keys a window layer keeps
        self._window = getattr(model, "window", None) \
            if self._recurrent else None
        self._moe_pairs = 0
        self._moe_hit = 0
        self._chunks = 0
        self._emitted = 0
        self._completed = 0
        self._occupancy_sum = 0.0
        self._token_occupancy_sum = 0.0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._pages_walked = 0
        self._pages_table = 0
        # temp_size_in_bytes of each program compiled at warm-up
        self._program_temp: "dict[str, Optional[int]]" = {}

        self._build_programs()
        if warmup:
            self._warmup()
        self._worker = threading.Thread(target=self._loop,
                                        name="bigdl-tpu-generate",
                                        daemon=True)
        self._worker.start()

    def _new_paged_cache(self):
        """The model's paged cache, with its per-slot state where it
        declares one."""
        extra = {"num_slots": self.slots.num_slots} \
            if self._recurrent else {}
        return self.model.init_paged_cache(
            self._alloc.num_pages, self._alloc.page_size,
            self._cache_dtype, **extra)

    def _new_draft_cache(self):
        """The draft's page pool: a full table row for every slot."""
        return self._draft.init_paged_cache(
            self.slots.num_slots * self._lp, self._alloc.page_size,
            self._cache_dtype)

    # -- compiled programs ---------------------------------------------------

    def _build_programs(self) -> None:
        import jax
        import jax.numpy as jnp

        model = self.model
        temperature = self.temperature
        eos_id = self.eos_id

        def pick(logp, key):
            with jax.named_scope("sample"):
                if temperature <= 0:
                    return jnp.argmax(logp, axis=-1).astype(jnp.int32) + 1
                return jax.random.categorical(
                    key, logp / temperature,
                    axis=-1).astype(jnp.int32) + 1

        counted = self._counted
        def decode_pages(*args, **kw):
            # (log-probs, cache', counters): a model that declares no
            # counters returns none, and an empty dict adds no output to
            # the compiled program
            out = model.decode_pages(*args, **kw)
            return out if counted else (*out, {})

        def reduce_counts(counts):
            # over the chunk's steps, each as the model declares
            return {k: (jnp.max if counted[k] == "max" else jnp.sum)(v)
                    for k, v in counts.items()}

        def prefill_slot(params, state, tokens, ts, cache, pages, slot,
                         key):
            # a model with recurrent state: the prompt WHOLE (nothing
            # below it is shared or retained), from position 0, where
            # the model zeroes the state of `slot` in-graph; `ts`
            # keeps the right-padding out of the state and selects
            # the one row of log-probs that is computed
            lp, cache, counts = decode_pages(
                params, state, tokens, cache, pages,
                jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
                slots=jnp.asarray(slot, jnp.int32)[None],
                lengths=jnp.asarray(ts, jnp.int32)[None])
            return pick(lp[:, 0], key)[0], cache, counts

        def prefill(params, state, tokens, ts, cache, pages, start, key):
            # tokens (1, Tb): the prompt SUFFIX beyond the shared
            # prefix, right-padded to a seq rung; ts is its REAL
            # length and start the shared-prefix depth in tokens
            # (both traced, one executable per rung).  Writes land
            # in the slot's own pages via the page table — shared
            # prefix pages sit below `start` and are never indexed.
            pos = jnp.asarray(start, jnp.int32)[None]
            active = jnp.ones((1,), bool)
            lp, cache, counts = decode_pages(params, state, tokens,
                                             cache, pages, pos, active)
            last = jax.lax.dynamic_slice_in_dim(lp, ts - 1, 1,
                                                axis=1)[:, 0]
            first = pick(last, key)[0]
            return first, cache, counts

        def step_chunk_kernel(params, state, tokens, cache, pages,
                              pos, active, limit, keys):
            # THE decode program: one scanned span of steps_per_sync
            # ``decode_pages`` steps over ALL slots; admit/evict happens
            # host-side between chunks.  Each step's writes scatter
            # straight into the pool and its reads go through the page
            # table (the Pallas kernel, or the layer's gather where
            # there is none); a model with recurrent state updates its
            # slot state step by step beside the pool.  (The name is
            # what the trace readers find the program by.)
            def one(carry, key):
                tok, cache, pos, active = carry
                lp, cache, counts = decode_pages(params, state,
                                                 tok[:, None], cache,
                                                 pages, pos, active)
                nxt = pick(lp[:, -1], key)
                nxt = jnp.where(active, nxt, tok)
                pos = jnp.where(active, pos + 1, pos)
                emitted = active
                active = jnp.logical_and(active, pos < limit)
                if eos_id is not None:
                    active = jnp.logical_and(active, nxt != eos_id)
                return (nxt, cache, pos, active), (nxt, emitted, counts)

            (tok, cache, pos, active), (toks, emitted, counts) = \
                jax.lax.scan(one, (tokens, cache, pos, active), keys)
            return tok, cache, pos, active, toks, emitted, \
                reduce_counts(counts)

        # cache donation: the live cache enters each program exactly
        # once and is immediately rebound to the program's output, so
        # XLA may alias the update in place — peak HBM holds ONE cache
        # instead of old+new across every prefill/chunk.  Every call
        # site (including warmup) rebinds self._cache from the result;
        # the donated input is never touched again (graftlint:
        # use-after-donate)
        self._prefill_fn = jax.jit(
            prefill_slot if self._recurrent else prefill,
            donate_argnums=(4,) if self._donate else ())
        self._step_fn = jax.jit(
            step_chunk_kernel,
            donate_argnums=(3,) if self._donate else ())

        if self._draft is None:
            return
        draft = self._draft
        k = self.spec_k
        dcap = self.max_len
        dtable = self._dtable

        def draft_prefill(dparams, dstate, prompt, dcache, slot):
            # the draft ingests the FULL prompt from position 0 into
            # the slot's own row of its table (prefix pages are a
            # target-side economy)
            row = jax.lax.dynamic_slice_in_dim(dtable, slot, 1, axis=0)
            _, dcache = draft.decode_pages(
                dparams, dstate, prompt, dcache, row,
                jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool))
            return dcache

        def spec_chunk(params, state, dparams, dstate, cur,
                       tcache, dcache, pages, pos, active):
            # 1. the draft proposes k tokens autoregressively
            # through its own pool (write-gated past its capacity;
            # a position past the table goes to the trash page
            # anyway, so an overrun could only dent the accept
            # rate, never a neighbour's pages or correctness)
            def dstep(carry, _):
                tok, dc, p = carry
                lp, dc = draft.decode_pages(
                    dparams, dstate, tok[:, None], dc, dtable, p,
                    jnp.logical_and(active, p < dcap))
                nxt = jnp.argmax(
                    lp[:, -1], axis=-1).astype(jnp.int32) + 1
                nxt = jnp.where(active, nxt, tok)
                return (nxt, dc, p + 1), nxt

            # k+1 steps, k proposals used: the extra step
            # exists to WRITE d_k's K/V at pos+k, which a
            # full-accept round (pos advances by k+1) would
            # otherwise leave as a permanent zero hole in the
            # draft cache — every later proposal for the
            # request would attend a zero row at a valid
            # position and the accept rate would silently decay
            # (a self-draft must accept at exactly 1.0;
            # regression-tested at depth)
            (_, dcache, _), drafts = jax.lax.scan(
                dstep, (cur, dcache, pos), None, length=k + 1)
            drafts = jnp.transpose(drafts)[:, :k]   # (B, k)
            # 2. the target verifies cur + all k proposals in
            # ONE pass — ROW-EXPANDED: each verify token
            # becomes its own batch row at S=1, sharing the
            # slot's page table with per-row positions.  The
            # scatter lands before the gather inside
            # decode_pages, so row i reads rows < i's K/V
            # written this same pass (the layer-by-layer
            # dependency of sequential decode, satisfied
            # structurally); keeping S=1 keeps the per-token
            # float math the EXACT shape of the plain decode
            # path, so greedy[:, i] — the target's pick after
            # [prefix, cur, d_1..d_i] — is bit-identical to
            # what sequential decoding would produce (an
            # S=k+1 pass reduces in a different order and can
            # flip near-tie argmaxes)
            toks = jnp.concatenate([cur[:, None], drafts],
                                   axis=1)           # (B, k+1)
            b = cur.shape[0]
            lp, tcache = model.decode_pages(
                params, state, toks.reshape(b * (k + 1), 1),
                tcache, jnp.repeat(pages, k + 1, axis=0),
                (pos[:, None] + jnp.arange(k + 1)).reshape(-1),
                jnp.repeat(active, k + 1))
            greedy = jnp.argmax(
                lp[:, 0], axis=-1).astype(jnp.int32) + 1
            greedy = greedy.reshape(b, k + 1)        # (B, k+1)
            return drafts, greedy, tcache, dcache

        self._draft_prefill_fn = jax.jit(
            draft_prefill,
            donate_argnums=(3,) if self._donate else ())
        self._spec_fn = jax.jit(
            spec_chunk,
            donate_argnums=(5, 6) if self._donate else ())

    def _compile(self, name: str, fn, *args):
        """Compile ``fn`` for ``args`` ahead of its first call (which
        then reuses the executable: jit keeps one per lowering) and
        record its ``temp_size_in_bytes`` under ``name``: a temporary of
        the pool's size coming back into a program shows in
        ``stats()["pages"]["program_temp_bytes"]`` without a trace.  A
        backend without the analysis records ``None``: a gauge that is
        missing says so."""
        compiled = fn.lower(*args).compile()
        try:
            mem = compiled.memory_analysis()
        except NotImplementedError:
            mem = None
        self._program_temp[name] = None if mem is None \
            else int(mem.temp_size_in_bytes)
        return fn(*args)

    def _warmup(self) -> None:
        """Compile every prefill rung, the decode chunk and (armed) the
        speculative chunk before the first request.  Without donation
        the outputs are discarded (the programs are pure, the live
        cache untouched); with donation the input cache is CONSUMED, so
        every warmup call adopts the returned cache.  Warmup runs
        against an all-trash page table, so the dummy K/V never land in
        an allocatable page at all (the draft's land in slot 0's own
        pages, hidden from its first tenant by the right-padding
        argument in the module doc)."""
        import jax
        import jax.numpy as jnp
        with tracer.span("serve.warmup", buckets=list(self.seq_ladder),
                         slots=self.slots.num_slots):
            key = jax.random.PRNGKey(0)
            n = self.slots.num_slots
            trash_row = jnp.full((1, self._lp), self._alloc.trash,
                                 jnp.int32)
            for b in self.seq_ladder:
                dummy = jnp.ones((1, b), jnp.int32)
                # (the 0 is the shared depth, or slot 0 of a model
                # with recurrent state, whose first real tenant
                # starts from zero whatever this leaves there)
                first, new_cache, _ = self._compile(
                    f"prefill.{b}", self._prefill_fn,
                    self.params, self.state, dummy, 1, self._cache,
                    trash_row, 0, key)
                if self._donate:
                    self._cache = new_cache
                np.asarray(first)
                if self._draft is not None:
                    dcache = self._draft_prefill_fn(
                        self._draft_params, self._draft_state, dummy,
                        self._dcache, 0)
                    if self._donate:
                        self._dcache = dcache
            keys = jax.random.split(key, self.steps_per_sync)
            table = jnp.asarray(self._page_table)
            out = self._compile("step", self._step_fn,
                                self.params, self.state,
                                jnp.asarray(self._tokens),
                                self._cache, table,
                                jnp.asarray(self._pos),
                                jnp.asarray(self._active),
                                jnp.asarray(self._limit), keys)
            if self._donate:
                self._cache = out[1]
            np.asarray(out[0])
            if self._draft is not None:
                spec = self._compile("spec", self._spec_fn,
                                     self.params, self.state,
                                     self._draft_params,
                                     self._draft_state,
                                     jnp.asarray(self._tokens),
                                     self._cache, self._dcache, table,
                                     jnp.asarray(self._pos),
                                     jnp.asarray(self._active))
                if self._donate:
                    self._cache, self._dcache = spec[2], spec[3]
                np.asarray(spec[1])

    # -- lifecycle -----------------------------------------------------------

    def __enter__(self) -> "ContinuousGenerator":
        return self

    def __exit__(self, *exc) -> None:
        self.drain()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Stop admitting; finish every admitted request (queued ones
        are still prefilled and decoded — admitted means answered);
        join the worker.  Idempotent."""
        self._closed = True
        self.queue.close()
        self._worker.join(timeout)
        joined = not self._worker.is_alive()
        run_ledger.flush()
        return joined

    close = drain

    # -- admission -----------------------------------------------------------

    def _shed(self, exc) -> None:
        """Every synchronous rejection feeds the same shed census the
        pool server's does: per-reason counter + ledger event, so
        run-report's shed-by-reason figure sees over-capacity and
        invalid sheds too, not just queue ones."""
        self.metrics.incr(f"serve.shed.{exc.reason}")
        run_ledger.emit("event", kind="serve.shed", reason=exc.reason,
                        **self._tags)
        raise exc

    # -- memory budget plumbing (r20): no-ops without a budgeter ------------

    def _budget_add(self, cls: str, nbytes: int, **detail) -> None:
        if self._budget is not None and nbytes:
            self._budget.charge(self._bt, cls, nbytes, **detail)

    def _budget_sub(self, cls: str, nbytes: int, **detail) -> None:
        if self._budget is not None and nbytes:
            self._budget.discharge(self._bt, cls, nbytes, **detail)

    def _budget_move(self, src: str, dst: str, nbytes: int,
                     **detail) -> None:
        if self._budget is not None and nbytes:
            self._budget.transfer(self._bt, src, dst, nbytes, **detail)

    def submit(self, prompt, max_new: int, *,
               session: Optional[str] = None) -> Future:
        """Admit one generation request or raise a typed shed
        synchronously.

        ``session`` (r20) names a multi-turn session: the turn's KV is
        RETAINED when it finishes, and the next ``submit`` with the
        same id prefills only the new suffix against it (parked
        sessions are resumed transparently).  ``prompt`` is just the
        new turn's tokens — the generator prepends the session history
        itself.  One outstanding turn per session."""
        if self._closed:
            self._shed(DrainingError("generator is draining"))
        p = np.asarray(prompt, np.int32).reshape(-1)
        if p.size < 1:
            self._shed(InvalidRequestError("empty prompt"))
        if max_new < 1:
            self._shed(InvalidRequestError(
                f"max_new must be >= 1, got {max_new}"))
        if session is not None:
            return self._submit_session(p, int(max_new), str(session))
        # EAGER capacity guard: over-capacity work is shed typed at the
        # door, never admitted into the decode loop (see module doc)
        try:
            self.slots.check(p.size, max_new)
        except SlotCapacityError as e:
            self._shed(e)
        if self._budget is not None:
            need = self._alloc.pages_for(p.size + max_new - 1) \
                * self._page_bytes + self._state_bytes
            try:
                self._budget.require_possible(self._bt, need,
                                              what="request")
            except MemoryBudgetError as e:
                self._shed(e)
        req = GenRequest(p, max_new)
        try:
            self.queue.offer(req)
        except (QueueFullError, DrainingError) as e:
            self._shed(e)
        self.metrics.incr("serve.gen.submitted")
        return req.future

    def _submit_session(self, p: np.ndarray, max_new: int,
                        sid: str) -> Future:
        """The session half of :meth:`submit`: claim the session's
        turn latch, build the full logical prompt (history + new
        tokens) and run the capacity/budget guards against it."""
        if self._draft is not None:
            self._shed(InvalidRequestError(
                "sessions are not supported with speculative decoding "
                "(the draft's pool has no park/resume path)"))
        if self._recurrent:
            self._shed(RecurrentStateError(
                "a session keeps its PAGES between turns; the slot's "
                "recurrent state goes to the slot's next tenant, so the "
                "next turn could not resume from it"))
        with self._lock:
            sess = self._sessions.get(sid)
            created = sess is None
            if created:
                sess = Session(sid)
                self._sessions[sid] = sess
                busy = False
            else:
                busy = sess.busy
            if not busy:
                sess.busy = True
                history = list(sess.tokens)
                kv_pos = sess.kv_pos
        if busy:
            self._shed(InvalidRequestError(
                f"session {sid!r} already has an outstanding turn "
                "(one turn at a time per session)"))
        # the turn latch is ours: any shed below must release it (and
        # drop a session that never materialised)
        try:
            full = (np.concatenate([np.asarray(history, np.int32), p])
                    if history else p)
            total = int(full.size) + max_new
            ts = int(full.size) - kv_pos       # the prefill suffix
            try:
                if total > self.max_len:
                    raise SlotCapacityError(
                        f"session {sid!r}: history+prompt {full.size} "
                        f"+ max_new {max_new} exceeds the KV-cache "
                        f"capacity {self.max_len}")
                if ts > self.slots.max_prompt:
                    raise SlotCapacityError(
                        f"session {sid!r}: turn suffix {ts} exceeds "
                        f"the largest prefill bucket "
                        f"{self.slots.max_prompt}")
                if self.slots.pool_tokens is not None \
                        and total - 1 > self.slots.pool_tokens:
                    raise SlotCapacityError(
                        f"session {sid!r} needs {total - 1} cache "
                        "tokens at once but the page pool holds "
                        f"{self.slots.pool_tokens} in total")
            except SlotCapacityError as e:
                self._shed(e)
            if self._budget is not None:
                need = self._alloc.pages_for(total - 1) * self._page_bytes
                try:
                    self._budget.require_possible(
                        self._bt, need, what=f"session:{sid}")
                except MemoryBudgetError as e:
                    self._shed(e)
            req = GenRequest(full, max_new, session=sid)
            try:
                self.queue.offer(req)
            except (QueueFullError, DrainingError) as e:
                self._shed(e)
        except BaseException:
            with self._lock:
                live = self._sessions.get(sid)
                if live is sess:
                    sess.busy = False
                    if created and sess.state == "new":
                        del self._sessions[sid]
            raise
        self.metrics.incr("serve.gen.submitted")
        return req.future

    # -- session lifecycle (r20) ---------------------------------------------

    def park(self, sid: str) -> Future:
        """Ask the scheduler to park session ``sid`` to the host-RAM
        offload tier; resolves True when parked, False when the
        session was busy, unknown or already parked.  The command
        rides the admission queue, so the one thread that owns the
        page table executes it (parking mid-decode is impossible by
        construction — the concurrent park-vs-decode race resolves to
        'park after the turn retires, or not at all').  Pressure also
        parks idle sessions automatically; this is the explicit
        client-driven variant."""
        if self._recurrent:
            self._shed(RecurrentStateError(
                "parking moves a session's pages to the host; a model "
                "with recurrent state keeps no session to park"))
        cmd = _Control("park", str(sid))
        try:
            self.queue.offer(cmd)
        except (QueueFullError, DrainingError) as e:
            self._shed(e)
        return cmd.future

    def close_session(self, sid: str) -> Future:
        """Release session ``sid``'s retained KV (device pages or
        parked host copy, and its prefix-chain pins); resolves True
        when a session was closed, False when unknown or mid-turn."""
        cmd = _Control("close", str(sid))
        try:
            self.queue.offer(cmd)
        except (QueueFullError, DrainingError) as e:
            self._shed(e)
        return cmd.future

    def session_info(self, sid: str) -> Optional[dict]:
        """Best-effort snapshot of one session (None when unknown)."""
        with self._lock:
            sess = self._sessions.get(str(sid))
            if sess is None:
                return None
            return {"sid": sess.sid, "state": sess.state,
                    "busy": sess.busy, "kv_pos": sess.kv_pos,
                    "tokens": len(sess.tokens),
                    "private_pages": len(sess.pages),
                    "shared_pages": len(sess.keys)}

    def generate(self, prompts, max_new: int) -> List[np.ndarray]:
        """Submit every prompt and block for the ordered outputs — the
        continuous-batching analogue of ``TransformerLM.generate``."""
        futs = [self.submit(p, max_new) for p in prompts]
        return [f.result() for f in futs]

    # -- the scheduler loop --------------------------------------------------

    def _loop(self) -> None:
        if run_ledger.enabled():
            tracer.install_compile_hook()
            run_ledger.emit_clock()
            run_ledger.emit("run.start", kind="ContinuousGenerator",
                            pid=os.getpid(),
                            thread=threading.get_ident(),
                            trace=run_ledger.trace_id(),
                            slots=self.slots.num_slots,
                            max_len=self.max_len,
                            seq_buckets=list(self.seq_ladder),
                            steps_per_sync=self.steps_per_sync,
                            donate_cache=self._donate,
                            quantize=self.quantize,
                            page_size=self._alloc.page_size,
                            num_pages=self._alloc.num_pages,
                            prefix_cache=self._prefix is not None,
                            recurrent_state=self._recurrent,
                            speculative=self._draft is not None,
                            spec_k=(self.spec_k if self._draft is not None
                                    else None),
                            **self._tags)
        t0 = time.monotonic()
        while True:
            try:
                self._admit()
                if self.slots.active_count == 0:
                    if self._pending is not None:
                        # everything is idle: the only page pressure
                        # left is the prefix cache, which force-evicts
                        req, self._pending = self._pending, None
                        self._place(req, force=True)
                        continue
                    # idle: block for work (None == closed AND empty —
                    # with no active slots that is the drain exit)
                    req = self.queue.take(timeout=None)
                    if req is None:
                        break
                    if isinstance(req, _Control):
                        self._control(req)
                        continue
                    self._place(req)
                    continue
                self._decode_chunk()
            except BaseException:        # the scheduler must never die
                logger.exception("continuous generator: unexpected error")
                self._fail_all_and_recover()
        self._run_end(time.monotonic() - t0)

    def _fail_all_and_recover(self) -> None:
        """Fail every live slot typed rather than hang clients, then
        restore a servable cache.  Under donation a failed prefill/
        decode call may already have CONSUMED the live cache buffers —
        continuing to pass the deleted arrays would fail every future
        request while the generator looked healthy — so the donating
        path rebuilds a fresh cache (the tenants' prefixes died with
        the donated buffers; they were just failed typed anyway).  The
        prefix cache's pages died with the pool too, so its entries are
        evicted wholesale back to the allocator."""
        for j, r in enumerate(self._requests):
            if r is not None:
                self._evict(j, "failed")
        self._active[:] = False
        if self._donate:
            self._cache = self._new_paged_cache()
            # every retained session's KV died with the donated
            # pool (parked copies too — their shared heads are
            # gone, a resume could not be bit-faithful): close
            # them all, which also releases their prefix pins so
            # the wholesale evict below can actually drain; the
            # budget discharges ride along, keeping the budgeter
            # exact through the crash path
            for sid in list(self._sessions):
                self._destroy_session(self._sessions[sid])
            if self._prefix is not None:
                freed = self._prefix.evict_for(self._alloc.num_pages,
                                               self._alloc)
                self._budget_sub("prefix_pages", freed * self._page_bytes)
            if self._draft is not None:
                self._dcache = self._new_draft_cache()

    def _admit(self) -> None:
        """Fill free slots from the queue — the per-decode-step admit.
        A held-back request (admitted, but the page pool could not fit
        it at its last placement attempt) goes first: admission stays
        FIFO even under page pressure."""
        while self.slots.free_count > 0:
            if self._pending is not None:
                req, self._pending = self._pending, None
            else:
                req = self.queue.take(timeout=0.0)
                if req is None:
                    return
                if isinstance(req, _Control):
                    self._control(req)
                    continue
            if not self._place(req):
                return                    # held back again; stop admitting

    # -- session park / resume (scheduler thread only, r20) ------------------

    def _control(self, cmd: _Control) -> None:
        """Execute a park/close command on the scheduler thread."""
        try:
            if cmd.op == "park":
                out = self._park_session(cmd.sid)
            elif cmd.op == "close":
                out = self._close_session(cmd.sid)
            else:
                raise ValueError(f"unknown control op {cmd.op!r}")
            cmd.future.set_result(out)
        except Exception as e:
            try:
                cmd.future.set_exception(e)
            except Exception:        # client cancelled mid-flight
                pass

    def _park_session(self, sid: str) -> bool:
        sess = self._sessions.get(sid)
        if sess is None or sess.state != "resident" or sess.busy:
            return False            # mid-turn / unknown / already parked
        self._park(sess, reason="request")
        return True

    def _park(self, sess: Session, reason: str) -> None:
        """D2H-copy the session's PRIVATE pages to the offload tier and
        free their device page ids.  Shared prefix pages stay on device
        untouched — the session keeps its refcount pins, so a page
        another reader holds is never moved out from under it."""
        ids = sess.pages
        nbytes = len(ids) * self._page_bytes
        if ids:
            idx = np.asarray(ids, np.int32)
            payload = [{"k": np.asarray(l["k"][idx]),
                        "v": np.asarray(l["v"][idx])}
                       for l in self._cache]
        else:
            payload = []
        self._offload.park(sess.sid, payload, nbytes)
        if ids:
            self._alloc.free(ids)
        self._budget_move("kv_pages", "host_offload", nbytes,
                          sid=sess.sid)
        sess.pages = []
        sess.state = "parked"
        self.metrics.incr("serve.gen.parks")
        run_ledger.emit("mem.offload", action="park", sid=sess.sid,
                        pages=len(ids), bytes=nbytes, reason=reason,
                        kv_pos=sess.kv_pos, **self._tags)

    def _resume_into(self, sess: Session, ids: List[int]) -> None:
        """H2D-scatter the parked private pages into freshly allocated
        ids and re-point the session's page-table prefix at them.  The
        page CONTENTS are copied verbatim and re-addressed through the
        table, so the resumed session is bit-equal to one that never
        parked."""
        import jax.numpy as jnp

        payload = self._offload.resume(sess.sid)
        nbytes = len(ids) * self._page_bytes
        if ids:
            idx = jnp.asarray(np.asarray(ids, np.int32))
            self._cache = [
                {"k": l["k"].at[idx].set(jnp.asarray(pl["k"])),
                 "v": l["v"].at[idx].set(jnp.asarray(pl["v"]))}
                for l, pl in zip(self._cache, payload)]
        row = np.array(sess.row)
        row[len(sess.keys):] = ids
        sess.row = row
        sess.pages = list(ids)
        sess.state = "resident"
        sess.last_used = time.monotonic()
        self._budget_move("host_offload", "kv_pages", nbytes,
                          sid=sess.sid)
        self.metrics.incr("serve.gen.resumes")
        run_ledger.emit("mem.offload", action="resume", sid=sess.sid,
                        pages=len(ids), bytes=nbytes,
                        kv_pos=sess.kv_pos, **self._tags)

    def _close_session(self, sid: str) -> bool:
        sess = self._sessions.get(sid)
        if sess is None or sess.busy or sess.state == "active":
            return False
        self._destroy_session(sess)
        return True

    def _destroy_session(self, sess: Session) -> None:
        """Free everything a NON-slot-bound session holds: device
        pages or the parked host copy, plus its prefix-chain pins.
        Slot-bound (active) sessions are torn down through
        :meth:`_evict` instead — their pages live in the slot's
        private list and must not be freed twice."""
        with self._lock:
            self._sessions.pop(sess.sid, None)
        if sess.state == "parked":
            freed = self._offload.drop(sess.sid)
            self._budget_sub("host_offload", freed, sid=sess.sid)
        elif sess.pages:
            self._alloc.free(sess.pages)
            self._budget_sub("kv_pages",
                             len(sess.pages) * self._page_bytes,
                             sid=sess.sid)
        if sess.keys and self._prefix is not None:
            self._prefix.release(sess.keys)
        run_ledger.emit("mem.offload", action="close", sid=sess.sid,
                        kv_pos=sess.kv_pos, **self._tags)
        sess.pages, sess.keys = [], []
        sess.state, sess.busy = "closed", False

    def _session_abort(self, req: GenRequest) -> None:
        """A turn died before retention (shed, cancel): release the
        session's turn latch, and drop a session that never built KV."""
        if req.session is None:
            return
        with self._lock:
            sess = self._sessions.get(req.session)
            if sess is None:
                return
            sess.busy = False
            if sess.state == "new" and not sess.tokens:
                del self._sessions[req.session]

    def _make_room(self, pages_needed: int,
                   protect: Optional[Session] = None) -> None:
        """The degradation ladder (r20), pressure instead of crash, in
        order: (1) budgeter reclaimers — cold tenants' warmed rung
        executables, byte pressure only; (2) prefix-cache leaves (the
        r11 ``evict_for``, now budget-driven too — frees device pages
        AND charged bytes); (3) PARK idle sessions, LRU first (frees
        device pages; their bytes move to the host tier).  Runs until
        the free list can seat ``pages_needed`` and the tenant's byte
        headroom covers them, or the ladder is dry — the CALLER
        decides what a remaining deficit means (hold back vs typed
        shed).  ``protect`` exempts the session being placed right
        now."""
        alloc, prefix = self._alloc, self._prefix
        pb = self._page_bytes

        def page_deficit() -> int:
            return pages_needed - alloc.free_count

        def byte_deficit() -> int:
            if self._budget is None:
                return 0
            head = self._budget.headroom(self._bt)
            if head is None:
                return 0
            return pages_needed * pb - int(head)

        if byte_deficit() > 0:
            self._budget.reclaim(self._bt, byte_deficit())
        need = page_deficit()
        if pb and byte_deficit() > 0:
            need = max(need, -(-byte_deficit() // pb))
        if need > 0 and prefix is not None:
            freed = prefix.evict_for(need, alloc)
            if freed:
                self._budget_sub("prefix_pages", freed * pb)
                run_ledger.emit("serve.cache", event="evict",
                                pages=freed, **self._tags)
        while page_deficit() > 0 or byte_deficit() > 0:
            # any RESIDENT session is parkable — including one whose
            # next turn is already queued (``busy`` is the submit-time
            # turn latch, not device occupancy): its KV is idle on
            # device and placement resumes parked sessions
            # transparently, so a burst of continuations across many
            # sessions cannot deadlock the pool.  Only ``active``
            # (slot-bound) sessions are untouchable.
            victim: Optional[Session] = None
            for s in self._sessions.values():
                if s.state == "resident" and s is not protect:
                    if victim is None or s.last_used < victim.last_used:
                        victim = s
            if victim is None:
                break
            self._park(victim, reason="pressure")

    # -- placement -----------------------------------------------------------

    def _place(self, req: GenRequest, force: bool = False) -> bool:
        """Place one admitted request into a free slot.  Returns False
        when the page pool cannot fit it right now (the request is held
        back in ``self._pending``, untouched); True otherwise — placed,
        failed typed, or cancelled.  ``force`` (drain/idle path) sheds
        typed instead of holding back, so the loop can never wedge on a
        request the pool will never satisfy (belt-and-braces: the
        submit-time pool check already rejects those)."""
        tl = req.timeline
        if tl.t_admit is None:          # a held-back request keeps its first
            tl.t_admit = time.monotonic()
            self.metrics.observe("serve.gen.queue_wait_s",
                                 tl.t_admit - tl.t_submit)

        import jax
        import jax.numpy as jnp

        alloc, prefix = self._alloc, self._prefix
        sess: Optional[Session] = None
        if req.session is not None:
            sess = self._sessions.get(req.session)
            if sess is not None and sess.state in ("resident", "parked"):
                # a continuing turn: extend the retained KV instead of
                # prefilling from scratch
                return self._place_continuation(req, sess, force)
        tp = int(req.prompt.size)
        ps = alloc.page_size
        pages_total = alloc.pages_for(tp + req.max_new - 1)

        # prefix lookup: full pages only, capped so at least the LAST
        # prompt token is prefilled (its logits seed generation; a
        # fully-shared prompt still needs that one live forward)
        keys: List[str] = []
        depth, shared = 0, []
        if prefix is not None:
            keys = prefix.chain_keys(req.prompt)[:(tp - 1) // ps]
            if req.counted:
                # held-back retry: don't recount the census
                lk, hp = prefix.lookup_pages, prefix.hit_pages
                depth, shared = prefix.lookup(keys)
                prefix.lookup_pages, prefix.hit_pages = lk, hp
            else:
                depth, shared = prefix.lookup(keys)
                req.counted = True

        # pin the looked-up chain BEFORE any eviction: acquire makes it
        # un-evictable (and LRU-fresh), so the pressure loop below can
        # never cannibalize the very pages this request is about to
        # read — without the pin, evict_for's leaf-first LRU could
        # reclaim our own cold chain, inflate priv_needed, and shed a
        # request the pool can actually satisfy
        slot_keys = list(keys[:depth])
        if prefix is not None and depth:
            prefix.acquire(slot_keys)
        priv_needed = pages_total - depth
        if alloc.free_count < priv_needed \
                or (self._budget is not None
                    and self._budget.headroom(self._bt) is not None):
            # degradation ladder: rung executables -> prefix leaves ->
            # park idle sessions, for page AND byte pressure alike
            self._make_room(priv_needed, protect=sess)
        starved = False
        if self._budget is not None:
            head = self._budget.headroom(self._bt)
            starved = (head is not None
                       and priv_needed * self._page_bytes > head)
        if starved:
            if prefix is not None and slot_keys:
                prefix.release(slot_keys)
            if not force:
                self._pending = req      # placed later, FIFO preserved
                return False
            exc: Exception
            try:
                self._budget.admit(self._bt,
                                   priv_needed * self._page_bytes,
                                   what=f"rid:{req.rid}", reclaim=False)
                exc = MemoryBudgetError(
                    "byte-starved at placement (budget headroom "
                    "vanished under the check)")
            except MemoryBudgetError as e:
                exc = e
            self._session_abort(req)
            self._fail_typed(req, exc)
            return True
        priv = alloc.alloc(priv_needed)
        if priv is None:
            if prefix is not None and slot_keys:
                prefix.release(slot_keys)
            if not force:
                self._pending = req      # placed later, FIFO preserved
                return False
            self._session_abort(req)
            self._fail_typed(req, SlotCapacityError(
                f"page pool exhausted: request needs {priv_needed} "
                f"pages, {alloc.free_count} free and nothing evictable"))
            return True

        if not req.future.set_running_or_notify_cancel():
            alloc.free(priv)
            if prefix is not None and slot_keys:
                prefix.release(slot_keys)
            self._session_abort(req)
            self.metrics.incr("serve.gen.cancelled")
            self._emit_request(req, "cancelled")
            return True
        slot = self.slots.alloc()
        assert slot is not None, "placed with no free slot"
        self._budget_add("kv_pages", len(priv) * self._page_bytes,
                         rid=req.rid)
        self._budget_add("slot_state", self._state_bytes, rid=req.rid)

        # build the slot's page table row: shared prefix pages first,
        # then the private pages, trash beyond the allocation
        table_row = np.full(self._lp, alloc.trash, np.int32)
        table_row[:depth] = shared
        table_row[depth:pages_total] = priv

        start = depth * ps
        suffix = req.prompt[start:]
        ts = tp - start
        bucket = self.seq_ladder.pick(ts)
        padded = np.ones((1, bucket), np.int32)
        padded[0, :ts] = suffix
        # prep in its own recover scope: a failure here (H2D of the
        # prompt, key split) provably never consumed the donated cache,
        # so only THIS request fails — but its slot, pages and future
        # still get the same cleanup (a leak here would shrink capacity
        # forever and strand the client in future.result())
        try:
            suffix_dev = jnp.asarray(padded)
            table_dev = jnp.asarray(table_row[None])
            if self._greedy_keys is not None:
                key = self._greedy_keys[0]
            else:
                self._rng, key = jax.random.split(self._rng)
        except Exception as e:
            self._release_partial(req, slot, priv, slot_keys)
            self._prefill_failed(req, e, consumed_cache=False)
            return True
        try:
            with tracer.open_span(
                    "serve.prefill", slot=slot, bucket=bucket, tp=tp,
                    shared_tokens=start, rid=req.rid,
                    **self._walk_attrs(start + bucket - 1)) as sp:
                # a model with recurrent state is told its slot where an
                # attention-only one is told its shared depth (0 here:
                # nothing is shared below a recurrent state)
                first, self._cache, counts = self._prefill_fn(
                    self.params, self.state, suffix_dev, ts,
                    self._cache, table_dev,
                    slot if self._recurrent else start, key)
                if self._draft is not None:
                    fbucket = self.seq_ladder.pick(tp)
                    fpad = np.ones((1, fbucket), np.int32)
                    fpad[0, :tp] = req.prompt
                    self._dcache = self._draft_prefill_fn(
                        self._draft_params, self._draft_state,
                        jnp.asarray(fpad), self._dcache, slot)
                # the host fetch stays in scope: an async dispatch
                # failure surfaces here, after the cache was donated
                first, counts = jax.device_get((first, counts))
                first = int(first)
                self._first_token(req)
                if counts:
                    sp.set(**self._counter_attrs(counts))
        except Exception as e:
            self._release_partial(req, slot, priv, slot_keys)
            self._prefill_failed(req, e, consumed_cache=True)
            return True

        # publish the prompt's freshly-prefilled full pages (beyond the
        # shared depth) into the prefix cache: ownership transfers to
        # the cache, this slot stays attached as a reader
        n_full = len(keys)
        if prefix is not None and n_full > depth:
            prefix.insert(keys, table_row[:n_full].tolist(), depth)
            prefix.acquire(keys[depth:])
            published = table_row[depth:n_full].tolist()
            priv = [p for p in priv if p not in published]
            slot_keys = list(keys)
            # ownership of the published pages moved to the prefix
            # cache; their bytes move classes with them so evict_for
            # can discharge exactly what it frees
            self._budget_move("kv_pages", "prefix_pages",
                              len(published) * self._page_bytes)
        if prefix is not None:
            st = prefix.stats()
            self.metrics.set("serve.prefix hit rate", st["hit_rate"],
                             unit="scalar")
            run_ledger.emit("serve.cache", event="admit", rid=req.rid,
                            lookup_pages=len(keys), hit_pages=depth,
                            shared_tokens=start,
                            inserted=max(0, n_full - depth),
                            **self._tags)
            self.metrics.incr("serve.gen.prefix.lookup_pages", len(keys))
            self.metrics.incr("serve.gen.prefix.hit_pages", depth)

        self._page_table[slot] = table_row
        self._slot_priv[slot] = priv
        self._slot_keys[slot] = slot_keys
        # tokens living in cache-owned pages — the ATTACHED depth plus
        # anything this slot just PUBLISHED (the census counts those
        # through the prefix side, so the publisher must not also count
        # them as private)
        self._slot_shared[slot] = len(slot_keys) * ps
        if sess is not None:
            sess.state = "active"
            sess.last_used = time.monotonic()
        self._commit_placed(req, slot, tp, first, bucket)
        return True

    def _place_continuation(self, req: GenRequest, sess: "Session",
                            force: bool) -> bool:
        """Place a continuing session turn: the retained KV (resident
        pages, or parked pages resumed H2D first) is extended in place
        and only the SUFFIX beyond ``sess.kv_pos`` is prefilled —
        through the same shared-prefix prefill executable a fresh
        request uses with ``start=kv_pos``, which is what makes a
        resumed session bit-equal to one that never parked.  The
        session's partial last page is provably private (kv_pos lands
        strictly inside it past the shared-full-page head), so in-place
        extension can never write a page another reader holds."""
        import jax
        import jax.numpy as jnp

        alloc = self._alloc
        ps = alloc.page_size
        tp = int(req.prompt.size)
        kv_start = sess.kv_pos
        pages_total = alloc.pages_for(tp + req.max_new - 1)
        row_len = len(sess.row)
        new_needed = max(0, pages_total - row_len)
        resume_pages = (row_len - len(sess.keys)
                        if sess.state == "parked" else 0)
        pool_need = new_needed + resume_pages

        if alloc.free_count < pool_need \
                or (self._budget is not None
                    and self._budget.headroom(self._bt) is not None):
            self._make_room(pool_need, protect=sess)
        starved = False
        if self._budget is not None:
            head = self._budget.headroom(self._bt)
            # resume is a class TRANSFER (host_offload -> kv_pages),
            # so only the NEW pages are fresh device bytes
            starved = (head is not None
                       and new_needed * self._page_bytes > head)
        if starved:
            if not force:
                self._pending = req
                return False
            exc: Exception
            try:
                self._budget.admit(self._bt,
                                   new_needed * self._page_bytes,
                                   what=f"session:{sess.sid}",
                                   reclaim=False)
                exc = MemoryBudgetError(
                    "byte-starved at placement (budget headroom "
                    "vanished under the check)")
            except MemoryBudgetError as e:
                exc = e
            self._session_abort(req)
            self._fail_typed(req, exc)
            return True
        got = alloc.alloc(pool_need)
        if got is None:
            if not force:
                self._pending = req
                return False
            self._session_abort(req)
            self._fail_typed(req, SlotCapacityError(
                f"page pool exhausted: continuation needs {pool_need} "
                f"pages, {alloc.free_count} free and nothing "
                f"evictable"))
            return True

        if not req.future.set_running_or_notify_cancel():
            alloc.free(got)
            self._session_abort(req)
            self.metrics.incr("serve.gen.cancelled")
            self._emit_request(req, "cancelled")
            return True

        resumed, new_priv = got[:resume_pages], got[resume_pages:]
        if sess.state == "parked":
            try:
                self._resume_into(sess, resumed)
            except Exception as e:
                alloc.free(got)
                with self._lock:
                    self._sessions.pop(sess.sid, None)
                if sess.keys and self._prefix is not None:
                    self._prefix.release(sess.keys)
                if sess.sid not in self._offload:
                    # the payload was popped before the copy died
                    self._budget_sub("host_offload",
                                     resume_pages * self._page_bytes)
                sess.state = "closed"
                sess.busy = False
                self._prefill_failed(req, e, consumed_cache=False)
                return True
        self._budget_add("kv_pages", len(new_priv) * self._page_bytes,
                         rid=req.rid, sid=sess.sid)

        slot = self.slots.alloc()
        assert slot is not None, "placed with no free slot"
        table_row = np.full(self._lp, alloc.trash, np.int32)
        table_row[:row_len] = sess.row
        table_row[row_len:pages_total] = new_priv

        suffix = req.prompt[kv_start:]
        ts = tp - kv_start
        bucket = self.seq_ladder.pick(ts)
        padded = np.ones((1, bucket), np.int32)
        padded[0, :ts] = suffix
        try:
            suffix_dev = jnp.asarray(padded)
            table_dev = jnp.asarray(table_row[None])
            if self._greedy_keys is not None:
                key = self._greedy_keys[0]
            else:
                self._rng, key = jax.random.split(self._rng)
        except Exception as e:
            self.slots.release(slot)
            alloc.free(new_priv)
            self._budget_sub("kv_pages",
                             len(new_priv) * self._page_bytes)
            self._destroy_session(sess)
            self._prefill_failed(req, e, consumed_cache=False)
            return True
        try:
            with tracer.span("serve.prefill", slot=slot, bucket=bucket,
                             tp=tp, shared_tokens=kv_start,
                             rid=req.rid, sid=sess.sid,
                             **self._walk_attrs(kv_start + bucket - 1)):
                first, self._cache, _ = self._prefill_fn(
                    self.params, self.state, suffix_dev, ts,
                    self._cache, table_dev, kv_start, key)
                first = int(np.asarray(first))
                self._first_token(req)
        except Exception as e:
            self.slots.release(slot)
            alloc.free(new_priv)
            self._budget_sub("kv_pages",
                             len(new_priv) * self._page_bytes)
            self._destroy_session(sess)
            self._prefill_failed(req, e, consumed_cache=True)
            return True

        self._page_table[slot] = table_row
        self._slot_priv[slot] = list(sess.pages) + list(new_priv)
        self._slot_keys[slot] = list(sess.keys)
        self._slot_shared[slot] = len(sess.keys) * ps
        sess.state = "active"
        sess.last_used = time.monotonic()
        self.metrics.incr("serve.gen.continuations")
        self._commit_placed(req, slot, tp, first, bucket)
        return True

    def _first_token(self, req: GenRequest) -> None:
        """The host holds the request's first token: stamp it."""
        tl = req.timeline
        tl.delivered(time.monotonic())
        self.metrics.observe("serve.gen.ttft_s", tl.t_first - tl.t_submit)

    def _delivered(self, req: GenRequest, now: float) -> None:
        """A decode chunk that ended at ``now`` emitted for ``req``."""
        gap = req.timeline.delivered(now)
        if gap is not None:
            self.metrics.observe("serve.gen.chunk_gap_s", gap)

    def _decode_attrs(self, queries: int = 1) -> dict:
        """What a ``serve.decode`` span says of the work it covers
        (computed only while the ledger is on): ``ctx_tokens``, the sum
        of the active rows' positions, ``pages_mapped``, the pages their
        table rows map, and what the paged kernel walks of those tables
        at the chunk's first step (``queries`` kernel rows a slot: the
        speculative verify expands each slot into ``k + 1``)."""
        if not run_ledger.enabled():
            return {}
        act = self._active
        return {"ctx_tokens": int(self._pos[act].sum()),
                "pages_mapped": int(
                    (self._page_table[act] != self._alloc.trash).sum()),
                **self._walk_attrs(
                    self._pos[act][:, None] + np.arange(queries))}

    def _walk_attrs(self, last_visible) -> dict:
        """``pages_walked`` and ``pages_table`` of one call of the paged
        kernel (only while the ledger is on): of the ``Lp`` table slots
        of each kernel row, those up to the page of its last visible key
        (``last_visible``, one position a row) are walked, the rest move
        no data.  Their running ratio is the gauge ``serve.paged walk
        share``.  ``blocks_walked`` and ``blocks_table``: the same walk
        in the kernel's own steps, blocks of ``paged_block_pages``
        pages: the loop turns a row costs over those its table has."""
        if not (self._kernel_reads and run_ledger.enabled()):
            return {}
        last = np.asarray(last_visible).reshape(-1) // self._alloc.page_size
        last = np.clip(last, 0, self._lp - 1)
        block = self._walk_block
        walked = int((last + 1).sum())
        table = int(last.size * self._lp)
        self._pages_walked += walked
        self._pages_table += table
        if self._pages_table:
            self.metrics.set("serve.paged walk share",
                             self._pages_walked / self._pages_table,
                             unit="scalar")
        return {"pages_walked": walked, "pages_table": table,
                "blocks_walked": int((last // block + 1).sum()),
                "blocks_table": int(last.size * -(-self._lp // block))}

    def _state_attrs(self, emitted, pos) -> dict:
        """What a ``serve.decode`` span says of the per-slot state and
        the latent pool (a model with recurrent state, ledger on):
        ``state_rows``, the row-steps that updated a state, and
        ``latent_tokens``, the context tokens an attention layer read
        over them (a row at position p reads p + 1), from the positions
        the chunk started at (``pos`` is where it ended).  A model that
        declares a ``window`` gets the same sum by kind of layer:
        ``full_tokens`` (p + 1 a row-step, what a full layer read) and
        ``window_tokens`` (``min(p + 1, window)``, a window layer's)."""
        if not (self._recurrent and run_ledger.enabled()):
            return {}
        n = emitted.sum(axis=0).astype(np.int64)         # steps a row ran
        first = pos.astype(np.int64) - n                 # where a row began
        ctx = n * (first + 1) + n * (n - 1) // 2
        out = {"state_rows": int(n.sum()), "latent_tokens": int(ctx.sum())}
        if self._window:
            steps = first[None] + np.arange(emitted.shape[0])[:, None]
            seen = np.minimum(steps + 1, self._window)
            out.update(full_tokens=out["latent_tokens"],
                       window_tokens=int(seen[steps < pos[None]].sum()))
        return out

    def _counter_attrs(self, counts) -> dict:
        """The model's counters of one program run as span attributes;
        the expert layers' feed two running gauges: pairs per expert
        that had any, and the most loaded expert's pairs over that."""
        out = {k: int(v) for k, v in counts.items()}
        if out.get("experts_hit"):
            self._moe_pairs += out["expert_pairs"]
            self._moe_hit += out["experts_hit"]
            self.metrics.set("serve.moe pairs per hit expert",
                             self._moe_pairs / self._moe_hit, unit="scalar")
            self.metrics.set("serve.moe max over mean",
                             out["expert_pairs_max"] * out["experts_hit"]
                             / out["expert_pairs"], unit="scalar")
        return out

    def _commit_placed(self, req: GenRequest, slot: int, tp: int,
                       first: int, bucket: int) -> None:
        req.slot = slot
        req.tokens = [first]
        self._requests[slot] = req
        self._tokens[slot] = first
        self._pos[slot] = tp
        self._limit[slot] = tp + req.max_new - 1
        self._active[slot] = True
        self.metrics.incr("serve.gen.prefills")
        self.metrics.incr(f"serve.gen.bucket.{bucket}")
        self._emitted += 1
        if req.max_new == 1 or (self.eos_id is not None
                                and first == self.eos_id):
            self._active[slot] = False
            self._evict(slot, "ok")

    def _release_partial(self, req: GenRequest, slot: Optional[int],
                         priv: List[int],
                         slot_keys: Optional[List[str]]) -> None:
        """Undo a placement that failed before commit: slot row, fresh
        private pages and prefix refs all go back — a leak here would
        shrink capacity forever."""
        if slot is not None:
            self.slots.release(slot)
            self._budget_sub("slot_state", self._state_bytes)
        if priv:
            self._alloc.free(priv)
            self._budget_sub("kv_pages", len(priv) * self._page_bytes)
        if slot_keys and self._prefix is not None:
            self._prefix.release(slot_keys)

    def _fail_typed(self, req: GenRequest, exc: Exception) -> None:
        self.metrics.incr(f"serve.shed.{getattr(exc, 'reason', 'error')}")
        run_ledger.emit("event", kind="serve.shed",
                        reason=getattr(exc, "reason", "error"),
                        **self._tags)
        try:
            req.future.set_exception(exc)
        except Exception:                # client cancelled mid-flight
            pass
        self._emit_request(req, "failed", tokens=0)

    def _prefill_failed(self, req: GenRequest, e: Exception,
                        consumed_cache: bool) -> None:
        """A failed prefill must not leak its slot (active_count would
        stay >= 1 forever, turning the idle branch into a busy spin)
        nor strand the claimed future.  ``consumed_cache``: the failed
        call may have eaten the donated cache — fail the other tenants
        typed and rebuild (see :meth:`_fail_all_and_recover`); prep
        failures pass False and keep the blast radius to one
        request."""
        if consumed_cache and self._donate:
            self._fail_all_and_recover()
        self._session_abort(req)
        self.metrics.incr("serve.gen.failed")
        try:
            req.future.set_exception(RuntimeError(
                f"prefill failed: {type(e).__name__}: {e}"))
        except Exception:            # client cancelled mid-flight
            pass
        self._emit_request(req, "failed", tokens=0)

    # -- decode --------------------------------------------------------------

    def _decode_chunk(self) -> None:
        if self._draft is not None:
            self._spec_chunk()
        else:
            self._plain_chunk()

    def _plain_chunk(self) -> None:
        import jax

        n_active = int(self._active.sum())
        occ = n_active / self.slots.num_slots
        with tracer.open_span("serve.decode", chunk=self._chunks,
                              active=n_active, steps=self.steps_per_sync,
                              **self._decode_attrs()) as sp:
            if self._greedy_keys is not None:
                keys = self._greedy_keys
            else:
                self._rng, key = jax.random.split(self._rng)
                keys = jax.random.split(key, self.steps_per_sync)
            # the mirrors go up in ONE call and the results come back in
            # one: each separate transfer is a round trip to the device
            tokens, pos, active, limit, table = jax.device_put(
                [self._tokens, self._pos, self._active, self._limit,
                 self._page_table])
            tok, self._cache, pos, active, toks, emitted, counts = \
                self._step_fn(self.params, self.state, tokens, self._cache,
                              table, pos, active, limit, keys)
            tok, pos, new_active, toks, emitted, counts = jax.device_get(
                (tok, pos, active, toks, emitted, counts))
            # np.array (copy): what device_get returns may be a read-only
            # view, and _place mutates these mirrors on the next admit
            self._tokens = np.array(tok)
            self._pos = np.array(pos)        # toks, emitted: (steps, slots)
            sp.set(**self._state_attrs(emitted, pos),
                   **self._counter_attrs(counts))
        now = time.monotonic()      # the chunk's tokens reached the host
        chunk_tokens = int(emitted.sum())
        self._account_chunk(occ, n_active, chunk_tokens,
                            self.steps_per_sync)
        got = emitted.any(axis=0)
        for j, req in enumerate(self._requests):
            if req is None:
                continue
            if got[j]:
                self._delivered(req, now)
            for t in range(toks.shape[0]):
                if emitted[t, j]:
                    req.tokens.append(int(toks[t, j]))
            if not new_active[j]:
                self._active[j] = False
                self._evict(j, "ok")
            else:
                self._active[j] = True

    def _spec_chunk(self) -> None:
        """One speculative round: the draft proposes ``spec_k`` tokens,
        the target verifies them in one pass, the host accepts the
        matched prefix + the target's correction token — the accept
        rule that makes output exactly the target's greedy path."""
        import jax.numpy as jnp

        n_active = int(self._active.sum())
        occ = n_active / self.slots.num_slots
        k = self.spec_k
        with tracer.span("serve.decode", chunk=self._chunks,
                         active=n_active, steps=1, spec_k=k,
                         **self._decode_attrs(queries=k + 1)):
            drafts, greedy, self._cache, self._dcache = self._spec_fn(
                self.params, self.state, self._draft_params,
                self._draft_state, jnp.asarray(self._tokens),
                self._cache, self._dcache,
                jnp.asarray(self._page_table), jnp.asarray(self._pos),
                jnp.asarray(self._active))
            drafts = np.asarray(drafts)          # (slots, k)
            greedy = np.asarray(greedy)          # (slots, k + 1)
        now = time.monotonic()      # the round's tokens reached the host
        chunk_tokens = 0
        proposed = accepted = 0
        for j, req in enumerate(self._requests):
            if req is None or not self._active[j]:
                continue
            self._delivered(req, now)   # an active row emits every round
            n = 0
            while n < k and drafts[j, n] == greedy[j, n]:
                n += 1
            proposed += k
            accepted += n
            # emit matched prefix + correction (or the bonus token when
            # everything matched), replaying the sequential limit/eos
            # rule token by token
            for i in range(n + 1):
                t = int(greedy[j, i])
                req.tokens.append(t)
                self._tokens[j] = t
                self._pos[j] += 1
                chunk_tokens += 1
                alive = self._pos[j] < self._limit[j]
                if self.eos_id is not None and t == self.eos_id:
                    alive = False
                if not alive:
                    self._active[j] = False
                    self._evict(j, "ok")
                    break
        self._spec_proposed += proposed
        self._spec_accepted += accepted
        rate = (self._spec_accepted / self._spec_proposed
                if self._spec_proposed else 0.0)
        self.metrics.set("serve.draft accept rate", rate, unit="scalar")
        self.metrics.incr("serve.gen.spec.proposed", proposed)
        self.metrics.incr("serve.gen.spec.accepted", accepted)
        run_ledger.emit("serve.spec", chunk=self._chunks,
                        proposed=proposed, accepted=accepted,
                        emitted=chunk_tokens, **self._tags)
        self._account_chunk(occ, n_active, chunk_tokens, 1)

    def _account_chunk(self, occ: float, n_active: int,
                       chunk_tokens: int, steps: int) -> None:
        self._emitted += chunk_tokens
        self._chunks += 1
        self._occupancy_sum += occ
        self.metrics.incr("serve.gen.steps", steps)
        self.metrics.set("serve.slot occupancy", occ, unit="scalar")
        run_ledger.emit("serve.slots", chunk=self._chunks,
                        active=n_active, slots=self.slots.num_slots,
                        occupancy=occ, tokens=chunk_tokens,
                        **self._tags)
        # tokens actually held, counted ONCE: each slot's private
        # positions (pos minus its shared head) plus each DISTINCT
        # resident shared page — summing raw pos would count a
        # shared prefix once per reader and overstate (even past
        # 100%) under exactly the shared-head traffic paging is for
        held = int(sum(int(self._pos[j]) - self._slot_shared[j]
                       for j, r in enumerate(self._requests)
                       if r is not None))
        if self._prefix is not None:
            held += self._prefix.held_pages * self._alloc.page_size
        # idle RESIDENT sessions hold device tokens too (their
        # private positions; the shared head is already counted
        # through the prefix side)
        held += int(sum(s.kv_pos - len(s.keys) * self._alloc.page_size
                        for s in self._sessions.values()
                        if s.state == "resident"))
        cap = self._alloc.capacity_tokens
        tocc = held / cap if cap else 0.0
        self._token_occupancy_sum += tocc
        self.metrics.set("serve.token occupancy", tocc, unit="scalar")
        run_ledger.emit(
            "serve.pages", chunk=self._chunks, tokens_held=held,
            capacity_tokens=cap, token_occupancy=tocc,
            pages_used=self._alloc.used_count,
            pages_total=self._alloc.num_pages,
            prefix_pages=(self._prefix.held_pages
                          if self._prefix is not None else 0),
            **self._pool_gauges(), **self._tags)

    def _pool_gauges(self) -> dict:
        """What the pool's layout exists to remove, beside what it
        costs: each compiled program's temporaries (a relayouted copy of
        the pool would show as its size here), the lanes of a token's
        row and the bytes of the pools as they lie on the device (trash
        page and padding lanes included)."""
        out = {"program_temp_bytes": dict(self._program_temp),
               "pool_width": self._pool_width,
               "pool_padded_bytes":
                   (self._alloc.num_pages + 1) * self._page_bytes}
        if self._bytes_by_kind is not None:
            out["bytes_by_kind"] = self._bytes_by_kind
        return out

    def _evict(self, slot: int, status: str) -> None:
        """Finish the request in ``slot`` and free it for the next
        admit — the evict half of continuous batching.  Private pages
        go back to the allocator; shared prefix pages only drop a
        refcount (the cache keeps them warm for the next hit).  The
        K/V this slot wrote stay in place but are invisible to every
        other slot (per-row validity over its OWN page list) and are
        overwritten before the next tenant can see them."""
        req = self._requests[slot]
        self._requests[slot] = None
        self._active[slot] = False
        self.slots.release(slot)
        self._budget_sub("slot_state", self._state_bytes)
        sess = (self._sessions.get(req.session)
                if req.session is not None else None)
        if sess is not None and status == "ok":
            # session turn retired: RETAIN the KV up to kv_pos
            # (cache holds positions 0..kv_pos-1; the final emitted
            # token's KV was never written), trim the tail pages
            # that only existed for max_new headroom.  The prefix
            # pins move to the session so shared pages stay
            # refcount-protected across idle/park.
            kv_pos = int(self._pos[slot])
            keep_n = self._alloc.pages_for(kv_pos)
            nk = len(self._slot_keys[slot])
            priv = self._slot_priv[slot]
            keep = priv[:keep_n - nk]
            tail = priv[keep_n - nk:]
            if tail:
                self._alloc.free(tail)
                self._budget_sub("kv_pages", len(tail) * self._page_bytes)
            sess.tokens = req.prompt.tolist() + list(req.tokens)
            sess.kv_pos = kv_pos
            sess.row = np.array(self._page_table[slot][:keep_n])
            sess.pages = keep
            sess.keys = list(self._slot_keys[slot])
            sess.state = "resident"
            sess.last_used = time.monotonic()
            with self._lock:
                sess.busy = False
        else:
            if self._slot_keys[slot] and self._prefix is not None:
                self._prefix.release(self._slot_keys[slot])
            if self._slot_priv[slot]:
                self._alloc.free(self._slot_priv[slot])
                self._budget_sub(
                    "kv_pages",
                    len(self._slot_priv[slot]) * self._page_bytes)
            if sess is not None:
                # failed turn tears the session down with it — the
                # retained KV past kv_pos is unrecoverable
                with self._lock:
                    self._sessions.pop(sess.sid, None)
                    sess.busy = False
                sess.pages = []
                sess.keys = []
                sess.state = "closed"
        self._slot_keys[slot] = []
        self._slot_priv[slot] = []
        self._slot_shared[slot] = 0
        self._page_table[slot, :] = self._alloc.trash
        if status == "ok":
            out = np.asarray(req.tokens[:req.max_new], np.int32)
            try:
                req.future.set_result(out)
            except Exception:            # client cancelled mid-flight
                status = "cancelled"
            self._completed += 1
            self.metrics.incr("serve.gen.completed")
            self.metrics.incr("serve.gen.tokens", len(out))
        else:
            try:
                req.future.set_exception(RuntimeError(
                    "generation failed (see server log)"))
            except Exception:
                status = "cancelled"
            self.metrics.incr("serve.gen.failed")
        self._emit_request(req, status, tokens=len(req.tokens), slot=slot)

    def _emit_request(self, req: GenRequest, status: str, **fields) -> None:
        """The one ``serve.request`` record of a request, whatever became
        of it: how long it took and its timeline."""
        led = run_ledger.get_ledger()
        if led is None:
            return
        rec = {"type": "serve.request", "rid": req.rid, "status": status,
               "dur_s": time.monotonic() - req.t_submit}
        rec.update(req.timeline.fields())
        rec.update(fields)
        rec.update(self._tags)
        led.emit(rec)

    def _run_end(self, wall_s: float) -> None:
        led = run_ledger.get_ledger()
        if led is None:
            return
        run_ledger.emit(
            "run.end", kind="ContinuousGenerator", pid=os.getpid(),
            wall_s=wall_s, chunks=self._chunks,
            completed=self._completed, tokens=self._emitted,
            mean_occupancy=(self._occupancy_sum / self._chunks
                            if self._chunks else 0.0),
            mean_token_occupancy=(
                self._token_occupancy_sum / self._chunks
                if self._chunks else None),
            prefix_hit_rate=(self._prefix.stats()["hit_rate"]
                             if self._prefix is not None else None),
            draft_accept_rate=(
                self._spec_accepted / self._spec_proposed
                if self._spec_proposed else None),
            **self._tags)
        from bigdl_tpu.observability.prometheus import write_prometheus
        write_prometheus(self.metrics,
                         os.path.join(
                             led.dir,
                             f"metrics-generate-{os.getpid()}.prom"))
        led.flush()

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        local, _, _ = self.metrics.snapshot()
        out = {
            "counters": {name: v for name, (v, _p) in local.items()},
            "histograms": self.metrics.hist_snapshot(),
            "queue_depth": self.queue.depth,
            "slots": self.slots.num_slots,
            "active": int(self._active.sum()),
            "chunks": self._chunks,
            "completed": self._completed,
            "tokens": self._emitted,
            "mean_occupancy": (self._occupancy_sum / self._chunks
                               if self._chunks else 0.0),
            # facts, not options: benchmark/serve_cell.py indexes both
            "paged": True,
            "paged_kernel": self._kernel_reads,
        }
        out["pages"] = {
            "page_size": self._alloc.page_size,
            "total": self._alloc.num_pages,
            "free": self._alloc.free_count,
            "capacity_tokens": self._alloc.capacity_tokens,
            "page_bytes": self._page_bytes,
            "pool_bytes": self._alloc.num_pages * self._page_bytes,
            **self._pool_gauges(),
            "mean_token_occupancy": (
                self._token_occupancy_sum / self._chunks
                if self._chunks else 0.0),
        }
        if self._recurrent:
            out["state"] = {
                "bytes_per_slot": self._state_bytes,
                "bytes": self.slots.num_slots * self._state_bytes}
            if self._bytes_by_kind is not None:
                out["state"]["bytes_per_slot_by_kind"] = \
                    self._bytes_by_kind["slot"]
        out["prefix"] = (self._prefix.stats()
                         if self._prefix is not None else None)
        with self._lock:
            sessions = list(self._sessions.values())
        out["sessions"] = {
            "open": len(sessions),
            "active": sum(1 for s in sessions
                          if s.state == "active"),
            "resident": sum(1 for s in sessions
                            if s.state == "resident"),
            "parked": sum(1 for s in sessions
                          if s.state == "parked"),
            "device_tokens": int(sum(
                s.kv_pos for s in sessions
                if s.state in ("active", "resident"))),
            "parked_tokens": int(sum(
                s.kv_pos for s in sessions
                if s.state == "parked")),
            "total_tokens": int(sum(s.kv_pos for s in sessions)),
        }
        out["offload"] = self._offload.stats()
        if self._budget is not None:
            snap = self._budget.snapshot()
            out["budget"] = snap["tenants"].get(self._bt)
        if self._draft is not None:
            out["spec"] = {
                "k": self.spec_k,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "accept_rate": (self._spec_accepted / self._spec_proposed
                                if self._spec_proposed else 0.0),
            }
        return out
