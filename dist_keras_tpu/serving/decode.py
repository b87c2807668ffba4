"""Continuous-batching autoregressive decode engine over paged KV.

``ServingEngine`` packs fixed-shape classifier-style forward passes;
this engine serves the LLM-shaped workload the rest of the repo was
built for (causal ``models/transformer.py``, the flash/paged Pallas
kernels): token-level scheduling with per-sequence futures, no batch
barrier.

Design points (each mirrors an existing engine contract):

- **Continuous batching.**  A per-replica scheduler thread runs one
  decode iteration at a time over the replica's ACTIVE sequence set;
  between iterations it admits queued sequences into free slots and
  retires finished ones — a short sequence exits early and its slot
  refills on the very next iteration, never waiting for neighbours
  (the continuous-batching line of work in PAPERS.md).
- **Prefill / decode phase split, both ladder-bounded.**  A sequence's
  prompt runs ONCE through a fixed-shape prefill ladder (padded like
  the serving batch ladder); every subsequent token runs through a
  fixed ladder of decode SLOT counts.  Dispatched executable shapes
  are therefore bounded by ``len(prefill_ladder) + len(decode_ladder)``
  (x replica devices, inherent) — the same no-retrace contract
  ``ServingEngine.stats()["retrace_count"]`` verifies, reported the
  same way.
- **Paged KV, and per-sequence state beside it.**  Each replica owns
  the pools of its model's block family.  The family states each pool
  (its ``cache_pools``) as ``(layers it spans, "page" or "sequence", the
  entry's shape)``: ONE pool of ``v | k`` rows of ``2 x d_model`` values
  (every head's values, then every head's keys) over every layer for
  ``models/transformer.py``, ONE pool of ``latent +
  rope`` wide entries over every layer for ``models/mla_moe.py``, for
  ``models/lfm2_moe.py`` one paged pool of ``v | k`` rows over its
  attention layers only and one pool of convolution state, a row a
  SEQUENCE, over its convolution layers only, and for
  ``models/olmo_hybrid.py`` one paged pool of ``v | k`` rows over its
  attention layers and TWO per-sequence pools over its linear-attention
  layers (the convolutions' last inputs; the recurrent matrices).  **The
  "layers it spans" are the family's count of ENTRIES,** not of its
  layers of weights: ``models/ouro.py``, whose one stack of ``n_layers``
  layers runs ``ut_steps`` times a token on shared weights, states one
  paged pool over ``ut_steps x n_layers`` of them (a row a position for
  every (pass, layer), written and read through a traced index inside
  the loop its steps hold), and nothing here asks what an entry is.  A
  family may hold any number of pools of either kind: they are all
  addressed by the ONE page table and the ONE row a sequence has, and the
  steps take and return them in ``cache_pools``' order.  A paged pool is
  ``(layers, num_pages + 1, page_size, *entry)`` — page-major, so that
  the steps' scatters over (page, offset) update the donated pools in
  place and the attention reads whole pages with no copy of the pool or
  of a layer of it; a per-sequence pool is ``(layers, state_rows + 1,
  *entry)``.  One :class:`~dist_keras_tpu.serving.kv_cache.PagedKVCache`
  allocator hands out both: a sequence's row is reserved with its pages
  and returned with them, before its future resolves, so that a caller
  who answers a result with a request finds the row free.  How many rows
  a replica holds is ``state_rows``: by default one for every sequence the
  door can admit, which suits a row of kilobytes; where a row is megabytes
  (``olmo_hybrid``: 14 MB at the published widths) the count is part of
  sizing the replica, as pages are, and the door refuses typed when every
  row is held.  The family is named by the model's ``cfg`` and
  taken once, at construction (``models/families.py``, which states
  what a family is: its two step functions and its pools); the
  scheduler, the allocator and recovery's replay see no family.
  Admission reserves a sequence's WORST-CASE page count up front, so
  decode never stalls mid-sequence on KV: exhaustion is a typed
  ``Overloaded(reason="kv_exhausted")`` strictly at the door (rejected,
  not lost), and completion/cancel/error all reclaim through the one
  allocator path (zero leaked pages or rows — the chaos tests assert it).
- **Hot reload never drops a sequence.**  ``submit_generate`` pins the
  replica's CURRENT params reference into the sequence; a
  ``set_params`` (CheckpointWatcher promotion, blue/green cutover)
  swaps the replica reference only — in-flight sequences finish on the
  params they started with, decode iterations simply group active
  sequences by params generation (at most a couple in flight).
- **A decode step stays in flight.**  The worker launches step n+1
  while step n still runs, and fetches, emits and schedules behind the
  running step: the device always has its successor queued, and the
  host's part of an iteration (build, transfer, launch, the tokens'
  way back, callbacks, the locked scheduling pass) costs the loop only
  what does not fit under a step.  Step n+1's input tokens never visit
  the host: the compiled step takes its predecessor's output, still on
  the device, and the tokens view of the packed array says where each
  slot's token comes from (:func:`_step_views` states the encoding).
  Everything else a step needs the host knows without the token
  (``kv_len + 1`` for a sequence of the step in flight).  The canonical
  ``seq.tokens`` / ``kv_len`` advance only when a step LANDS, so
  recovery, retries and deadlines read what they always read.  A
  sequence that reaches its count with step n is left out of n+1; one
  that ends on ``eos_id`` is known a step late, and its slot of n+1 is
  computed and discarded (``decode.tokens_discarded``).  A prefill
  stays synchronous (its first token goes to the host) and is launched
  between two steps without draining them (:meth:`DecodeEngine._prefill`).
  No option and no second loop: with nothing in flight the same code launches on
  host-known tokens alone.  Why pages may be freed under a step that
  still names them: :meth:`DecodeEngine._step_group`.
- **Typed errors, never hangs.**  The ``decode.admit`` /
  ``decode.kv_alloc`` / ``decode.step`` / ``decode.recover`` fault
  points cover admission, page reservation, the step dispatch and the
  quarantine re-admission path; any failure lands typed on the
  affected sequences' futures with their pages reclaimed.
- **Sequence-level recovery.**  A replica worker crash (the
  :meth:`DecodeEngine.kill_replica` chaos seam, or a ``decode.step``
  fault past the in-place retry) QUARANTINES that replica: its KV
  pages free, its in-flight sequences re-admit onto surviving
  replicas and REPLAY — prefill over the prompt, then teacher-forced
  decode steps over the already-generated tokens, which rebuild a
  per-sequence state as they rebuild the KV (the canonical
  ``seq.tokens`` are kept; replayed predictions are discarded, so
  streaming callbacks resume exactly where they stopped and the
  final doc is bit-identical to an undisturbed greedy run).  Futures
  never see the failure; only when NO survivor can hold a sequence
  does it resolve typed (never a hang).  Whole-pod loss is out of
  scope: killing the last live replica is refused.
- **End-to-end deadlines.**  ``submit_generate(deadline_s=...)``
  rejects at the door (``Overloaded("deadline_infeasible")``) when
  the observed prefill/step EWMA says ``max_new_tokens`` cannot
  finish in time; a deadline expiring mid-decode frees the slot and
  its pages between steps and resolves the future with
  ``finish="deadline"`` and the tokens produced so far.
- **Brownout shedding.**  ``priority="batch"`` admissions are shed
  typed (``Overloaded("shed_batch")``) while ``slo.breaching()`` or
  KV occupancy sits above ``DK_DECODE_SHED_WATERMARK`` —
  ``interactive`` traffic keeps its SLO through the brownout.
  Sheds count ``decode.shed``, deliberately NOT ``decode.rejected``:
  the ``generate_tokens`` SLO reads ``rejected``, and shedding that
  burned the SLO would amplify itself.

Observability: ``decode_*`` events at every seam, ``decode.*``
registry metrics (TTFT and step-time histograms carry trace
exemplars), and with tracing on each request's trace gains
``serve.queue_wait`` + ``serve.prefill`` spans stamped from the
scheduler thread — time-to-first-token is attributable per request.
The ``generate_ttft`` / ``generate_tokens`` SLO objectives read these
surfaces (``observability/slo.py``).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from concurrent.futures import Future

import numpy as np

import jax
import jax.numpy as jnp

from dist_keras_tpu.models.families import DECODERS, family_of
from dist_keras_tpu.observability import events, metrics, perf, spans
from dist_keras_tpu.observability import slo as _slo
from dist_keras_tpu.resilience.faults import fault_point
from dist_keras_tpu.serving.engine import Overloaded
from dist_keras_tpu.serving.kv_cache import PagedKVCache, PagesExhausted
from dist_keras_tpu.utils import knobs
from dist_keras_tpu.utils.serialization import (
    deserialize_model,
    serialize_model,
)


class _ReplicaDead(Exception):
    """Internal scheduler signal: this replica must quarantine (worker
    crash, kill seam, or a step failure past the retry policy with a
    survivor available).  Never escapes the engine."""

    def __init__(self, cause):
        self.cause = cause
        super().__init__(str(cause))


class _Sequence:
    """One admitted generation: host-side state the scheduler owns."""

    __slots__ = ("sid", "tokens", "prompt_len", "max_new", "eos_id",
                 "future", "on_token", "t", "tw", "ctx", "params",
                 "params_host", "pages", "row", "kv_len", "steps",
                 "cancelled", "prefilled", "block", "block_steps",
                 "fixed_at", "passes",
                 "ttft_s", "t_first", "deadline", "priority",
                 "recoveries", "finished")

    def __init__(self, sid, tokens, max_new, eos_id, on_token, params,
                 params_host, pages, row, deadline=None,
                 priority="interactive"):
        self.sid = sid
        self.tokens = list(tokens)
        self.prompt_len = len(tokens)
        self.max_new = max_new
        self.eos_id = eos_id
        self.future = Future()
        self.on_token = on_token
        self.t = time.monotonic()
        self.tw = time.time()
        self.ctx = spans.capture()
        self.params = params      # pinned: reloads never touch us
        self.params_host = params_host  # host ref: re-pin on recovery
        self.pages = pages
        self.row = row            # its per-sequence state row, or None
        self.kv_len = 0           # KV positions written so far (committed)
        self.prefilled = False    # its prefill has run (reset by recovery)
        self.steps = 0            # decode iterations consumed
        # a family that generates in blocks: the open block's tokens as of
        # the last LANDED pass (the mask id where nothing is fixed yet),
        # the passes it has taken, the pass that fixed each position, and
        # for every generated token the pass of its block that fixed it
        self.block = None
        self.block_steps = 0
        self.fixed_at = None
        self.passes = None
        self.cancelled = False
        self.ttft_s = None
        self.t_first = None
        self.deadline = deadline  # absolute monotonic, or None
        self.priority = priority
        self.recoveries = 0       # quarantine re-admissions survived
        self.finished = False     # exit accounted (pages reclaimed)

    def generated(self):
        return self.tokens[self.prompt_len:]

    def result_doc(self, finish):
        doc = {
            "tokens": list(self.tokens),
            "generated": self.generated(),
            "prompt_len": self.prompt_len,
            "steps": self.steps,
            "ttft_s": self.ttft_s,
            "finish": finish,
            "recoveries": self.recoveries,
        }
        if self.passes is not None:
            # generation in blocks: for each generated token the pass of
            # its block that fixed it, counted from 0 (the trajectory)
            doc["passes"] = list(self.passes)
        return doc


class Generation:
    """Caller-side handle: a future plus a cancel seam (cancel reclaims
    the sequence's KV pages; the future resolves with
    ``finish="cancelled"`` and the tokens produced so far)."""

    def __init__(self, engine, seq):
        self._engine = engine
        self._seq = seq
        self.future = seq.future

    def result(self, timeout=None):
        return self.future.result(timeout=timeout)

    def cancel(self):
        return self._engine.cancel(self)

    def done(self):
        return self.future.done()


def _step_views(packed, pmax, state=False, width=1):
    """The six arrays of a decode step inside its ONE packed int32 array,
    in ``decode_step``'s order.  ``packed`` holds ``rung * (pmax + 5)``
    values: the page tables row by row (``pmax`` entries a slot), then
    the tokens, positions, write pages, write offsets and lengths, a rung
    of each.  The layout's one statement: on the host these are the
    writable views ``_step_group`` fills, inside the compiled step the
    same slices cut the device's copy apart.  Flat with the tables first
    because the compiled step is then no longer than with six arguments
    (one copy into fast memory, whole-tile slices; the latent kernel
    wants its tables flat anyway), and no slower on the chip: PERF.md,
    PR 31.  For a family that holds per-sequence ``state`` a seventh
    array follows, the slots' state rows (``rung * (pmax + 6)`` values);
    every other family is handed exactly the six.

    The tokens view names each slot's token SOURCE: an entry ``>= 0`` is
    the token itself, one the host knows (a prefill's first token, a
    recovered sequence's teacher-forced token, the first step with
    nothing in flight); an entry ``-(j + 1)`` means "slot ``j`` of the
    step in flight's output", which is still on the device.  The
    engine's compiled wrapper (``_packed_decode_fn``) resolves it before
    the family's step sees the tokens; no other view depends on a
    token.

    A family whose step computes ``width`` positions a slot (a BLOCK) is
    handed seven arrays (eight with ``state``): ``entries * (pmax + width
    + 5)`` values, the tables, then the entries' blocks (``width``
    values an entry, the mask id where nothing is fixed yet), then
    positions (where each block starts), write pages, write offsets,
    lengths (``start + width``) and how many masked positions the pass
    fixes (0: it commits the block).  An ENTRY is a block of a sequence,
    and a rung's pass holds more entries than sequences
    (``DecodeEngine._entries``): a sequence whose block commits may hold
    two, the commit and RIGHT BEHIND it the block that opens next (the
    same page table, ``start + width``, ``start + 2 * width`` rows read:
    layer by layer the first entry writes the committed rows the second
    reads, and never sees the second's).  A block's values name their
    source like a token's: ``-(j + 1)`` at block position ``b`` means
    "block position ``b`` of entry ``j`` of the pass in flight's output".
    With ``width`` 1 nothing of the above changes."""
    if width > 1:
        arrays = 6 + state
        rung = packed.shape[0] // (pmax + width + arrays - 1)
        tables = packed[:rung * pmax].reshape(rung, pmax)
        toks = packed[rung * pmax:rung * (pmax + width)].reshape(rung,
                                                                 width)
        positions, wpage, woff, lengths, fix, *rows = \
            packed[rung * (pmax + width):].reshape(arrays - 1, rung)
        return (toks, positions, tables, wpage, woff, lengths, fix, *rows)
    arrays = 6 if state else 5
    rung = packed.shape[0] // (pmax + arrays)
    tables = packed[:rung * pmax].reshape(rung, pmax)
    toks, positions, wpage, woff, lengths, *rows = \
        packed[rung * pmax:].reshape(arrays, rung)
    return (toks, positions, tables, wpage, woff, lengths, *rows)


def _prefill_views(packed, state=False):
    """The four arrays of a prefill inside its ONE packed int32 array, in
    ``prefill_step``'s order: ``3 * rung + 1`` values, the tokens, page
    indices and page offsets a rung each, then the prompt's length (on
    the host a copy, not a view: ``_prefill`` writes ``packed[-1]``).
    For a family that holds per-sequence ``state`` the sequence's state
    row follows the length (``3 * rung + 2`` values)."""
    if state:
        toks, page_idx, page_off = packed[:-2].reshape(3, -1)
        return toks, packed[-2], page_idx, page_off, packed[-1]
    toks, page_idx, page_off = packed[:-1].reshape(3, -1)
    return toks, packed[-1], page_idx, page_off


def _spare_entries(rung, passes):
    """Entries a pass of ``rung`` sequences holds beyond one a sequence,
    for the blocks that open behind a commit: a block takes ``passes``
    denoising passes, so in steady state ``rung / passes`` sequences
    commit a pass (rounded up; at least one, so that a sequence alone
    never waits a pass for its next block)."""
    return -(-rung // passes)


def _still_running(out):
    """Has the device NOT yet finished the step that puts ``out`` out?
    (0.2 us a call; a seam of its own so that a test can hold a step
    "running".)"""
    return not out.is_ready()


def _to_device(packed):
    """A dispatch's ONE transfer to the device, counted: ``perf.h2d_bytes``
    grows by the array's size and ``perf.h2d_s`` gains the enqueue's wall,
    which is the transfer's part of the dispatch region around it (the
    rest is the launch)."""
    t0 = time.perf_counter()
    on_device = jnp.asarray(packed)
    perf.h2d(packed.nbytes, time.perf_counter() - t0)
    return on_device


class _Flight:
    """One decode step launched and not yet landed: what its landing
    needs besides the host's canonical state, which it has not touched."""

    __slots__ = ("group", "entries", "slot", "rung", "out", "lengths",
                 "t0", "overlapped", "fed", "attempt", "fix")

    def __init__(self, group, entries, rung, out, lengths, t0, overlapped,
                 fed, attempt, fix=None):
        self.group = group            # the sequences, slot by slot
        # the sequence of each live ENTRY of the step, in the packed
        # array's order: the group's where a step is a token; in a pass
        # over blocks a sequence whose block commits may hold two, the
        # commit and right behind it the block that opens next
        self.entries = entries
        # where the next step finds a sequence's tokens in ``out``: its
        # entry, the LAST one where it holds two (its open block's)
        self.slot = {seq: i for i, seq in enumerate(self.entries)}
        self.rung = rung
        self.out = out                # device: tokens to the top rung, counts
        self.lengths = lengths        # host view, for the family's counts
        self.t0 = t0                  # perf_counter at the launch
        # launched before its predecessor was fetched (NOT "while the
        # device was busy": that is ``fed``)
        self.overlapped = overlapped
        # the predecessor was still running right after the launch: the
        # chip never ran dry (False: it had drained; None: no sample,
        # there was no predecessor or the pass ran a prefill)
        self.fed = fed
        self.attempt = attempt        # failures this step had before
        # a pass over blocks: the masked positions each entry's pass fixes
        # (0: it commits the entry's block); None where a step is a token
        self.fix = fix

    def folds(self, i):
        """Is entry ``i`` a commit whose sequence's NEXT block rides in
        the same pass (the entry right behind it)?"""
        return (0 <= i < len(self.entries) - 1
                and self.entries[i + 1] is self.entries[i])


class _DecodeReplica:
    """One replica: pinned device, params swap point, its KV pools, and
    the decode step it has in flight."""

    def __init__(self, index, device, params, cache, pools, no_tokens):
        self.index = index
        self.device = device
        self.params_host = params
        self.params = (jax.device_put(params, device)
                       if device is not None else params)
        self.cache = cache
        self.pools = tuple(pools)
        self.queue = collections.deque()
        self.active = []
        self.retiring = False
        self.killed = False       # crash requested (kill_replica seam)
        self.dead = False         # quarantined: out of service for good
        self.steps = 0
        self.flight = None        # the launched, unlanded step (worker's)
        self.no_tokens = no_tokens  # what a step carries with none in flight
        self.landed_at = 0.0      # perf_counter: the last tokens on the host
        self.attempt = 0          # failures of the step to launch next
        self.prefilling = False   # the worker's pass ran a prefill
        self._pinned = {}         # id(params_host) -> device params

    def put_params(self, params):
        self.params_host = params
        self.params = (jax.device_put(params, self.device)
                       if self.device is not None else params)

    def pin(self, params_host):
        """Device-resident params for a recovered sequence's pinned
        generation.  The common case (no reload since admission) reuses
        this replica's current params; an older generation device-puts
        once and caches (at most a couple of generations in flight —
        the same bound the step grouping relies on)."""
        if params_host is self.params_host:
            return self.params
        key = id(params_host)
        if key not in self._pinned:
            self._pinned[key] = (
                jax.device_put(params_host, self.device)
                if self.device is not None else params_host)
        return self._pinned[key]


class DecodeEngine:
    """Continuous-batching decode over a causal decoder.

    Args:
      keras_model: a decoder of a family in ``models/families.py`` (or
        anything the serialization layer round-trips to one); its ``cfg``
        names the block family.  A ``Transformer`` decodes with token in
        == logit out, so its config must have ``input_dim == n_classes``
        (the vocabulary) and dense feed-forwards (its Switch-MoE blocks
        drop tokens over capacity and have no decode step).
      replicas: replica count (default: one per visible device).
      prefill_ladder: ascending fixed PROMPT shapes; a prompt runs
        padded to the smallest rung that fits (``ValueError`` past the
        largest — the front end's 400).
      decode_ladder: ascending fixed SLOT counts for decode steps; the
        largest rung is the per-replica concurrency cap.  A rung counts
        SEQUENCES: the one program of a rung of a family that generates
        in blocks also holds the few spare entries in which a committing
        block's successor rides (:meth:`_entries`; derived, not set).
      page_size: KV positions per page.
      num_pages: pool pages per replica.  Default sizes the pool so a
        full slot set of maximum-length sequences fits.
      max_queue: admission bound on admitted-but-unresolved sequences.
      state_rows: per-sequence state rows per replica, for a family that
        keeps such state (ignored otherwise).  Default: one for every
        sequence the door can admit (``min(max_queue, num_pages)``), so
        that pages and the queue bound refuse before rows do; a family
        whose row is large is given fewer, and rows refuse first.
      max_new_default: ``max_new_tokens`` when a request omits it.
      eos_id: default stop token (None = length-only stopping).
      devices: explicit device list (default ``jax.devices()``).
      step_retries: in-place retries of a failed decode-step dispatch
        (safe: pools and ``kv_len`` only advance on success).  Past
        them the replica quarantines when a survivor exists, else the
        group fails typed.
      shed_watermark: KV occupancy fraction above which ``batch``
        admissions shed (default: ``DK_DECODE_SHED_WATERMARK``).
      self_check_interval_s: cadence of the scheduler's allocator
        reconciliation pass (``decode.kv_leaked``).
    """

    def __init__(self, keras_model, replicas=None,
                 prefill_ladder=(16, 64), decode_ladder=(1, 4, 8),
                 page_size=8, num_pages=None, max_queue=256,
                 max_new_default=16, eos_id=None, devices=None,
                 step_retries=1, shed_watermark=None,
                 self_check_interval_s=1.0, state_rows=None):
        self.serialized = serialize_model(keras_model)
        model = deserialize_model(self.serialized)
        cfg = getattr(model, "cfg", None)
        if cfg is None:
            raise ValueError(
                "DecodeEngine needs a decoder of a family in "
                f"models/families.py ({', '.join(DECODERS)}: the model "
                f"contract with a cfg dict); got {type(model).__name__}")
        self.cfg = cfg
        # the model's block family, looked up once: everything below
        # this line sees pools and steps, no family
        self._family = family_of(cfg)
        self.vocab = self._family.vocab(cfg)
        # positions a slot a step: 1, or the length of the blocks the
        # family generates in (then: the id of a position nothing is
        # fixed at yet, and the masked positions a pass fixes)
        self._width = int(self._family.step_width(cfg))
        self._mask_id, self._fix_a_pass = (
            self._family.step_fixes(cfg) if self._width > 1 else (None, 0))
        self.seq_len = int(cfg["seq_len"])
        self._host_params = model.params

        ladder = sorted(set(int(b) for b in prefill_ladder))
        if not ladder or ladder[0] < 1 or ladder[-1] > self.seq_len:
            raise ValueError(
                f"prefill_ladder {prefill_ladder!r} must hold positive "
                f"ints <= seq_len ({self.seq_len})")
        self.prefill_ladder = tuple(ladder)
        slots = sorted(set(int(b) for b in decode_ladder))
        if not slots or slots[0] < 1:
            raise ValueError(
                f"decode_ladder {decode_ladder!r} must hold positive "
                "ints")
        self.decode_ladder = tuple(slots)
        self.max_slots = slots[-1]
        self.max_queue = int(max_queue)
        self.max_new_default = int(max_new_default)
        self.eos_id = eos_id if eos_id is None else int(eos_id)
        self.page_size = int(page_size)
        if self.page_size % self._width or self.seq_len % self._width:
            raise ValueError(
                f"page_size={page_size} and the model's seq_len "
                f"({self.seq_len}) must be whole blocks of "
                f"{self._width} positions: a block never straddles a page")
        self.max_pages_per_seq = -(-self.seq_len // self.page_size)
        if num_pages is None:
            num_pages = self.max_slots * self.max_pages_per_seq
        self.num_pages = int(num_pages)
        self._pools = tuple(self._family.cache_pools(cfg))
        # a family with a pool of per-sequence rows: every sequence holds
        # one, and a dispatch names the rows in one more packed column
        self._state = any(rows == "sequence" for _, rows, _ in self._pools)
        if not self._state:
            state_rows = 0
        elif state_rows is None:
            state_rows = min(self.max_queue, self.num_pages)
        self.state_rows = int(state_rows)
        if self._state and self.state_rows < 1:
            raise ValueError(f"state_rows={state_rows} must be >= 1")

        # the width of a decode step's output, whatever rung ran it: the
        # tokens padded to the top rung, then the family's counts
        self._out_width = self._decode_out_width(model.params)

        # donation keeps the pool update in place: a dispatch consumes
        # the replica's pools and returns their successors (the output a
        # step carries from its predecessor is read, never donated)
        donated = tuple(range(1, 1 + len(self._pools)))
        self._prefill_jit = jax.jit(self._packed_prefill_fn,
                                    donate_argnums=donated)
        self._decode_jit = jax.jit(self._packed_decode_fn,
                                   donate_argnums=donated)

        if devices is None:
            devices = jax.devices()
        n = int(replicas) if replicas is not None else len(devices)
        if n < 1:
            raise ValueError(f"replicas={replicas} must be >= 1")
        self._devices = list(devices) if devices else []
        self._next_replica_index = n
        self._seq_ids = itertools.count()
        self._replicas = [self._make_replica(i) for i in range(n)]

        self._cond = threading.Condition()
        self._outstanding = 0
        self._draining = False
        self._stopped = False
        self._drained = threading.Event()
        self._rr = 0
        self._shapes = set()      # (phase, rung) dispatched
        self.reload_count = 0
        self.step_retries = int(step_retries)
        self._shed_watermark = float(
            shed_watermark if shed_watermark is not None
            else knobs.get("DK_DECODE_SHED_WATERMARK"))
        self._self_check_interval = float(self_check_interval_s)
        self._next_self_check = (time.monotonic()
                                 + self._self_check_interval)
        # recovered sequences waiting for survivor KV capacity: they
        # hold no pages while pending; every worker iteration tries to
        # place them (admission-identical worst-case reservation)
        self._orphans = []
        # observed wall EWMAs feeding deadline feasibility at the door
        self._ewma_prefill = None
        self._ewma_step = None

        # engine-local instruments + the shared process registry (the
        # same split ServingEngine documents: per-engine truths vs
        # process-wide aggregates)
        self._m_ttft = metrics.Histogram("decode.ttft_s")
        self._m_step = metrics.Histogram("decode.step_s")
        self._n_admitted = 0
        self._n_completed = 0
        self._n_rejected = 0
        self._n_errors = 0
        self._n_cancelled = 0
        self._n_tokens = 0
        self._n_steps = 0
        self._n_quarantines = 0
        self._n_recovered = 0
        self._n_shed = 0
        self._n_deadline_infeasible = 0
        self._n_deadline_expired = 0
        self._n_kv_leaked = 0
        self._reg_admitted = metrics.counter("decode.admitted")
        self._reg_completed = metrics.counter("decode.completed")
        self._reg_rejected = metrics.counter("decode.rejected")
        self._reg_errors = metrics.counter("decode.errors")
        self._reg_cancelled = metrics.counter("decode.cancelled")
        self._reg_tokens = metrics.counter("decode.tokens")
        self._reg_quarantines = metrics.counter("decode.quarantines")
        self._reg_recovered = metrics.counter("decode.recovered")
        self._reg_shed = metrics.counter("decode.shed")
        self._reg_deadline_infeasible = metrics.counter(
            "decode.deadline_infeasible")
        self._reg_deadline_expired = metrics.counter(
            "decode.deadline_expired")
        self._reg_kv_leaked = metrics.counter("decode.kv_leaked")
        self._reg_ttft = metrics.histogram("decode.ttft_s")
        self._reg_step = metrics.histogram("decode.step_s")
        self._reg_overlapped = metrics.histogram("decode.step_overlapped")
        self._reg_fed = metrics.histogram("decode.launch_fed")
        self._reg_discarded = metrics.counter("decode.tokens_discarded")
        self._reg_block_passes = metrics.histogram("decode.block.passes")
        self._reg_trimmed = metrics.counter("decode.block.tokens_trimmed")
        self._reg_prefill = metrics.histogram("decode.prefill_s")
        self._reg_queue_wait = metrics.histogram("decode.queue_wait_s")
        self._reg_active = metrics.gauge("decode.active")
        self._reg_kv = metrics.gauge("decode.kv_used_pages")
        self._reg_rows = metrics.gauge("decode.state_rows_used")
        perf.install()  # retrace listener: the ladder bound, verified
        perf.watch_stalls()  # until _shutdown_threads

        self._workers = [threading.Thread(
            target=self._worker_main, args=(rep,), daemon=True,
            name=f"dk-decode-worker-{rep.index}")
            for rep in self._replicas]
        for t in self._workers:
            t.start()

    # -- the family's model math (jitted once per ladder rung) ----------
    def _prefill_fn(self, params, *args):
        """``(params, *pools, tokens, length, page_idx, page_off)``: one
        padded prompt -> (int32 array, the first token in front, *updated
        pools).  The family's step with its four integer arrays apart (a
        fifth, the state row, for a family with per-sequence state);
        what is dispatched is :meth:`_packed_prefill_fn` around it."""
        return self._family.prefill_step(self.cfg, params, *args)

    def _decode_fn(self, params, *args):
        """``(params, *pools, tokens, positions, page_tables, write_page,
        write_off, lengths)``: one token step for a padded slot set ->
        (int32 array, the next tokens in front, *updated pools).  The
        family's step with its six integer arrays apart (a seventh, the
        state rows, for a family with per-sequence state); what is
        dispatched is :meth:`_packed_decode_fn` around it."""
        return self._family.decode_step(self.cfg, params, *args)

    # What crosses to the device a dispatch: ONE int32 array, cut back
    # into the step's arrays inside the compiled program (a transfer
    # costs the host about as much for 54 KB as for 128 B, and the device
    # waits through each).  The programs keep the family functions' names
    # in theirs: a trace's reader finds a step's program by ``_decode_fn``.
    def _packed_prefill_fn(self, params, *args):
        """``(params, *pools, packed)``: :meth:`_prefill_fn` on the four
        arrays :func:`_prefill_views` cuts out of ``packed``."""
        *pools, packed = args
        return self._prefill_fn(
            params, *pools, *_prefill_views(packed, self._state))

    def _packed_decode_fn(self, params, *args):
        """``(params, *pools, carried, packed)``: :meth:`_decode_fn` on
        the six arrays :func:`_step_views` cuts out of ``packed``, the
        tokens resolved first: a slot whose entry is ``-(j + 1)`` takes
        slot ``j`` of ``carried``, the previous step's output, which never
        left the device.  The output has ONE width whatever the rung (the
        tokens padded to the top rung, then the family's counts), so a
        step carries any rung's output into the one program of its own."""
        *pools, carried, packed = args
        if self._width > 1:
            return self._packed_pass_fn(params, pools, carried, packed)
        toks, *rest = _step_views(packed, self.max_pages_per_seq,
                                  self._state)
        top, rung = self.max_slots, toks.shape[0]
        with jax.named_scope("carried_tokens"):
            toks = jnp.where(
                toks < 0, carried[jnp.clip(-toks - 1, 0, top - 1)], toks)
        out, *pools = self._decode_fn(params, *pools, toks, *rest)
        if rung < top:
            out = jnp.concatenate(
                [out[:rung], jnp.zeros((top - rung,), out.dtype),
                 out[rung:]])
        return (out, *pools)

    def _packed_pass_fn(self, params, pools, carried, packed):
        """:meth:`_packed_decode_fn` where a step is a pass over blocks of
        ``width`` positions: the same resolution a block position at a
        time (entry ``-(j + 1)`` at position ``b`` takes position ``b``
        of ENTRY ``j``'s block in ``carried``), and an output that holds
        the entries' blocks padded to the top rung's entries
        (:meth:`_entries`), then the counts."""
        width = self._width
        toks, *rest = _step_views(packed, self.max_pages_per_seq,
                                  self._state, width)
        top, entries = self._entries(self.max_slots), toks.shape[0]
        with jax.named_scope("carried_tokens"):
            source = (jnp.clip(-toks - 1, 0, top - 1) * width
                      + jnp.arange(width, dtype=jnp.int32))
            toks = jnp.where(toks < 0, carried[source], toks)
        out, *pools = self._decode_fn(params, *pools, toks, *rest)
        if entries < top:
            out = jnp.concatenate(
                [out[:entries * width],
                 jnp.zeros(((top - entries) * width,), out.dtype),
                 out[entries * width:]])
        return (out, *pools)

    def _entries(self, rung):
        """The entries the ONE program of slot rung ``rung`` computes.  A
        token step: the rung.  A pass over blocks: the rung and the spare
        entries in which a committing sequence's NEXT block rides
        (:func:`_spare_entries`, from what the family states); an unused
        one is padding like any other (``length == 0``, the scratch page,
        no expert, no K/V read), so the fused and the plain pass are the
        same compiled program."""
        if self._width == 1:
            return rung
        return rung + _spare_entries(rung, self._width // self._fix_a_pass)

    def _decode_out_width(self, params):
        """How many int32 values a decode step of this family puts out,
        the tokens padded to the top rung's entries: known from the
        step's shapes alone (nothing runs), and what a step carries when
        none is in flight has to have it."""
        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        top = self._entries(self.max_slots)
        out, *_ = jax.eval_shape(
            self._decode_fn, params,
            *(jax.ShapeDtypeStruct(shape, jnp.float32)
              for shape in self.pool_shapes),
            ints(top, self._width) if self._width > 1 else ints(top),
            ints(top), ints(top, self.max_pages_per_seq),
            *(ints(top),) * (3 + (self._width > 1) + self._state))
        return out.shape[0]

    @property
    def pool_shapes(self):
        """The shape of each pool a replica holds (float32), from the
        family's ``cache_pools``: a paged pool is page-major, ``(layers,
        num_pages + 1, page_size, *entry)``, page index ``num_pages`` the
        scratch page; a per-sequence pool is ``(layers, state_rows + 1,
        *entry)``, row index ``state_rows`` the scratch row.  The one
        statement of the layout: replicas allocate from it and the
        jitted steps read everything else off the pools they are
        given."""
        return tuple(
            (layers,) + ((self.num_pages + 1, self.page_size)
                         if rows == "page" else (self.state_rows + 1,))
            + tuple(entry)
            for layers, rows, entry in self._pools)

    def _make_replica(self, index):
        devs = self._devices
        device = devs[index % len(devs)] if devs else None
        cache = PagedKVCache(self.num_pages, self.page_size,
                             self.state_rows)
        # allocated ON the replica's device: N pools staged through the
        # default device would cost it N pools of peak memory
        pools = [jnp.zeros(shape, jnp.float32, device=device)
                 for shape in self.pool_shapes]
        no_tokens = jnp.zeros((self._out_width,), jnp.int32, device=device)
        return _DecodeReplica(index, device, self._host_params, cache,
                              pools, no_tokens)

    # -- admission ------------------------------------------------------
    def _rung_for(self, n, ladder):
        for b in ladder:
            if n <= b:
                return b
        return None

    def _live_replicas_locked(self):
        return [r for r in self._replicas
                if not r.retiring and not r.dead and not r.killed]

    def _pick_replica(self, needed_pages):
        """Most free pages wins (KV is the scarce resource), round-robin
        on ties; retiring and quarantined replicas are out of rotation,
        and so is one whose state rows are all held (a family that keeps
        per-sequence state).  Caller holds the lock."""
        live = self._live_replicas_locked()
        if self._state:
            live = [r for r in live
                    if r.cache.used_rows() < r.cache.state_rows]
        if not live:
            return None, 0
        frees = [r.cache.stats()["free_pages"] for r in live]
        best = max(frees)
        order = range(self._rr, self._rr + len(live))
        for i in order:
            i %= len(live)
            if frees[i] == best:
                self._rr = (i + 1) % len(live)
                return (live[i] if best >= needed_pages else None), best
        return None, best  # pragma: no cover - unreachable

    def _should_shed_locked(self):
        """Brownout verdict for a ``batch`` admission: KV occupancy
        over the watermark, or any SLO objective firing.  Caller holds
        the lock ( ``slo.breaching`` takes only leaf locks)."""
        live = self._live_replicas_locked()
        total = used = 0
        for r in live:
            st = r.cache.stats()
            total += st["num_pages"]
            used += st["used_pages"]
        if total and used / total >= self._shed_watermark:
            return "kv_watermark"
        firing = _slo.breaching()
        if firing:
            return "slo:" + ",".join(firing)
        return None

    def submit_generate(self, tokens, max_new_tokens=None, eos_id=None,
                        on_token=None, deadline_s=None,
                        priority="interactive"):
        """Admit one prompt; -> :class:`Generation` whose future
        resolves to the result doc (tokens, ttft_s, finish reason).
        Raises :class:`Overloaded` at the door (``queue_full`` /
        ``kv_exhausted`` / ``draining`` / ``stopped`` /
        ``deadline_infeasible`` / ``shed_batch``) and ``ValueError``
        for malformed prompts — rejected, never lost.

        ``deadline_s`` is the caller's end-to-end budget: infeasible
        requests (per the observed prefill/step EWMAs) reject at the
        door instead of burning KV pages toward a 504; expiry
        mid-decode frees the slot between steps and resolves
        ``finish="deadline"``.  ``priority`` is ``interactive``
        (default) or ``batch``; ``batch`` sheds first in a brownout."""
        fault_point("decode.admit")
        toks = [int(t) for t in tokens]
        if not toks:
            raise ValueError("empty prompt")
        if any(t < 0 or t >= self.vocab for t in toks):
            raise ValueError(
                f"prompt tokens must be in [0, {self.vocab})")
        max_new = (self.max_new_default if max_new_tokens is None
                   else int(max_new_tokens))
        if max_new < 1:
            raise ValueError(f"max_new_tokens={max_new} must be >= 1")
        rung = self._rung_for(len(toks), self.prefill_ladder)
        if rung is None:
            raise ValueError(
                f"prompt length {len(toks)} exceeds the prefill "
                f"ladder (max {self.prefill_ladder[-1]})")
        total = self._positions_for(len(toks), max_new)
        if total > self.seq_len:
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds the "
                f"model's seq_len ({self.seq_len})")
        eos = self.eos_id if eos_id is None else int(eos_id)
        if priority not in ("interactive", "batch"):
            raise ValueError(
                f"priority={priority!r} must be 'interactive' or "
                "'batch'")
        if deadline_s is not None:
            deadline_s = float(deadline_s)
            if deadline_s <= 0:
                raise ValueError(
                    f"deadline_s={deadline_s} must be > 0")
        with self._cond:
            if self._draining or self._stopped:
                self._n_rejected += 1
                self._reg_rejected.inc()
                raise Overloaded(
                    "draining" if self._draining else "stopped")
            if self._outstanding >= self.max_queue:
                self._n_rejected += 1
                self._reg_rejected.inc()
                raise Overloaded("queue_full",
                                 pending=self._outstanding,
                                 capacity=self.max_queue)
            if priority == "batch":
                shed_why = self._should_shed_locked()
                if shed_why is not None:
                    # counted decode.shed, NOT decode.rejected: the
                    # generate_tokens SLO reads rejected, and a shed
                    # that burned the SLO would amplify itself
                    self._n_shed += 1
                    self._reg_shed.inc()
                    events.emit("decode_shed", reason=shed_why,
                                prompt_len=len(toks))
                    raise Overloaded("shed_batch",
                                     pending=self._outstanding,
                                     capacity=self.max_queue)
            if deadline_s is not None \
                    and self._ewma_prefill is not None \
                    and self._ewma_step is not None:
                est = self._ewma_prefill + self._ewma_step \
                    * self._steps_for(len(toks), max_new)
                if est > deadline_s:
                    self._n_rejected += 1
                    self._reg_rejected.inc()
                    self._n_deadline_infeasible += 1
                    self._reg_deadline_infeasible.inc()
                    events.emit("decode_deadline", phase="admission",
                                deadline_s=deadline_s,
                                estimate_s=round(est, 6))
                    raise Overloaded("deadline_infeasible")
            sid = next(self._seq_ids)
            needed = max(1, -(-total // self.page_size))
            rep, best_free = self._pick_replica(needed)
            if rep is None:
                self._n_rejected += 1
                self._reg_rejected.inc()
                raise Overloaded("kv_exhausted", pending=needed,
                                 capacity=best_free)
            # the allocator's own fault point (decode.kv_alloc) fires
            # inside; a raise here admits nothing and leaks nothing
            pages = rep.cache.alloc(sid, total)
            seq = _Sequence(
                sid, toks, max_new, eos, on_token, rep.params,
                rep.params_host, pages, self._row_of(rep, sid),
                deadline=(None if deadline_s is None
                          else time.monotonic() + deadline_s),
                priority=priority)
            rep.queue.append(seq)
            self._outstanding += 1
            self._n_admitted += 1
            self._reg_active.set(self._outstanding)
            if self._state:
                self._reg_rows.set(sum(r.cache.used_rows()
                                       for r in self._replicas))
            self._cond.notify_all()
        self._reg_admitted.inc()
        events.emit("decode_admit", sid=sid, prompt_len=len(toks),
                    max_new=max_new, replica=rep.index,
                    pages=len(pages))
        return Generation(self, seq)

    def _positions_for(self, prompt_len, max_new):
        """The positions a request may come to hold, its reservation: a
        family that generates in blocks computes (and reserves) whole
        blocks, whatever of the last one is returned."""
        total = prompt_len + max_new
        return -(-total // self._width) * self._width

    def _steps_for(self, prompt_len, max_new):
        """The decode steps a request takes after its prefill: one a token
        or, in blocks, the PASSES of its ``ceil((tail + max_new) /
        width)`` blocks: as many as fix the block's masks (fewer in the
        first block, which opens holding the prompt's tail), and one that
        commits the LAST block: every other block's commit rides in the
        pass that opens the next one (a pass with no spare entry makes
        such a commit ride alone, a pass more: the door does not know)."""
        width, fix = self._width, self._fix_a_pass
        if width == 1:
            return max_new
        tail = prompt_len % width
        blocks = -(-(tail + max_new) // width)
        return (-(-(width - tail) // fix) + (blocks - 1) * (width // fix)
                + 1)

    def _row_of(self, rep, sid):
        """The state row ``alloc`` reserved with a sequence's pages."""
        return rep.cache.state_row(sid) if self._state else None

    def generate(self, tokens, max_new_tokens=None, eos_id=None,
                 timeout_s=None):
        """Blocking convenience: submit one prompt, wait for the doc."""
        return self.submit_generate(
            tokens, max_new_tokens=max_new_tokens,
            eos_id=eos_id).result(timeout=timeout_s)

    def cancel(self, generation):
        """Cancel a generation: reclaim its pages and resolve its
        future with ``finish="cancelled"`` (tokens so far).  -> True if
        the cancel landed before completion."""
        seq = generation._seq
        dequeued = False
        with self._cond:
            if seq.future.done() or seq.cancelled or seq.finished:
                # ``finished`` closes the race against _sequence_done:
                # the scheduler already accounted the exit (pages
                # reclaimed) and is about to resolve the future —
                # nothing is left to cancel, and marking ``cancelled``
                # here would be a lie the next pass can't act on
                return False
            seq.cancelled = True
            # still queued on some replica? finish it here, never
            # occupying a slot
            for rep in self._replicas:
                if seq in rep.queue:
                    rep.queue.remove(seq)
                    self._finish_locked(rep, seq, "cancelled")
                    dequeued = True
                    break
            self._cond.notify_all()
        if dequeued:
            events.emit("decode_cancel", sid=seq.sid,
                        generated=len(seq.generated()))
            self._resolve(seq, "cancelled")
        return True  # active: the scheduler retires it next iteration

    # -- scheduler ------------------------------------------------------
    def _resolve(self, seq, finish, error=None):
        """Resolve a sequence's future OUTSIDE the lock."""
        if error is not None:
            seq.future.set_exception(error)
        else:
            seq.future.set_result(seq.result_doc(finish))

    def _finish_locked(self, rep, seq, finish):
        """Account one sequence's exit (caller holds the lock):
        reclaim pages, bump counters.  The single reclamation seam for
        complete/cancel/error/deadline — zero leaked pages by
        construction."""
        rep.cache.free(seq.sid)
        self._account_exit_locked(seq, finish)

    def _account_exit_locked(self, seq, finish):
        """The bookkeeping half of an exit — callers (quarantine)
        whose pages were already reclaimed on the dead replica use
        this directly."""
        seq.finished = True
        self._outstanding -= 1
        if finish == "error":
            self._n_errors += 1
            self._reg_errors.inc()
        elif finish == "cancelled":
            self._n_cancelled += 1
            self._reg_cancelled.inc()
        elif finish == "deadline":
            # a deadline expiry is the CALLER's budget running out —
            # the future resolves with the tokens produced so far
            # (graceful degradation), counted on its own meter
            self._n_deadline_expired += 1
            self._reg_deadline_expired.inc()
        elif finish == "stopped":
            # a close(drain=False) abort is a rejection, not a model
            # error — rejected-not-lost, same as the door
            self._n_rejected += 1
            self._reg_rejected.inc()
        else:
            self._n_completed += 1
            self._reg_completed.inc()
        self._reg_active.set(self._outstanding)
        self._reg_kv.set(sum(r.cache.used_pages()
                             for r in self._replicas))
        if self._state:
            self._reg_rows.set(sum(r.cache.used_rows()
                                   for r in self._replicas))
        self._cond.notify_all()

    def _emit_token(self, seq, token):
        seq.tokens.append(int(token))
        self._n_tokens += 1
        self._reg_tokens.inc()
        if seq.on_token is not None:
            try:
                seq.on_token(int(token))
            # dklint: ignore[broad-except] a caller's token callback must never kill the scheduler thread
            except Exception as e:
                events.emit("decode_error", sid=seq.sid,
                            where="on_token", error=type(e).__name__)

    def _sequence_done(self, seq, token):
        if seq.eos_id is not None and int(token) == seq.eos_id:
            return "eos"
        if len(seq.generated()) >= seq.max_new:
            return "length"
        return None

    def _first_token(self, seq):
        """``seq``'s first generated token is going out: its TTFT."""
        seq.ttft_s = time.monotonic() - seq.t
        seq.t_first = time.time()
        ex = ((seq.ctx.trace_id, seq.ctx.span_id)
              if seq.ctx is not None else None)
        self._m_ttft.observe(seq.ttft_s, exemplar=ex)
        self._reg_ttft.observe(seq.ttft_s, exemplar=ex)

    def _prefill_len(self, seq):
        """The tokens a sequence's prefill is shown: its prompt.  In
        blocks a RECOVERED sequence is shown its committed tokens too (one
        block-causal prefill rebuilds their K/V; committed blocks are
        whole, so only a prompt's tail is ever left to the open block),
        unless they pass the prefill ladder: then the prompt, and commit
        passes over the blocks the host knows catch up."""
        if self._width > 1 and self._rung_for(
                len(seq.tokens), self.prefill_ladder) is not None:
            return len(seq.tokens)
        return seq.prompt_len

    def _open_block(self, seq, start):
        """The block that opens at position ``start`` as the host knows
        it: the tokens it has there (the prompt's tail; a recovered
        sequence's committed tokens) and the mask id behind them."""
        known = seq.tokens[start:start + self._width]
        return known + [self._mask_id] * (self._width - len(known))

    def _prefill(self, rep, seq, rung, leads):
        """Run one admitted prompt through its prefill ``rung``; emits
        the first generated token (TTFT) or fails the sequence typed.

        One crossing each way: the prompt's four integer arrays (and
        the sequence's state row, where the family keeps such state) go
        to the device as ONE packed int32 array (:func:`_prefill_views`,
        cut apart inside the compiled step), the token comes back in the
        one array the wait region fetches.

        A prefill is synchronous: its first token goes to the host, and
        the sequence joins the first step launched after that, on a token
        the host knows.  The running sequences do not wait for it: the
        prefill's launch queues behind the step in flight, and before
        this thread blocks on the prefill's token it runs one pass of the
        loop's steps (:meth:`_advance`: the next step is launched behind
        the prefill, the step in flight lands and its tokens go out), so
        the device goes from step to prefill to step with nothing
        drained.  Only the prefill that ``leads`` a scheduling pass has a
        step launched behind it; the others of a burst only land what is
        in flight and then run back to back, so that a burst of
        admissions is decoding together after one step, not after one
        step each (the benchmark's warm-up counts on a rung's worth of
        short requests meeting in one step).  The ``decode.prefill``
        region closes around the launch and opens again around the wait
        when such a pass runs between (its regions lie there, under their
        own names), and ``decode.prefill_s`` runs from the later of the
        launch and the landed step's tokens on the host.

        A RECOVERED sequence (``seq.tokens`` longer than the prompt)
        replays the same prefill over the prompt only — its prediction
        is a token the stream already delivered, so it is discarded
        and the teacher-forced decode steps replay the rest.

        **In blocks** a prefill commits the K/V of the prompt's WHOLE
        blocks (``kv_len`` may stay 0: ``seq.prefilled`` says it ran),
        yields no token (the TTFT is taken when the first block commits,
        :meth:`_land`) and leaves the open block as the host knows it:
        the prompt's tail and mask ids (:meth:`_open_block`;
        :meth:`_prefill_len` for what a recovered sequence is shown)."""
        with contextlib.ExitStack() as region:
            region.enter_context(
                perf.phase("decode.prefill", sid=seq.sid, rung=rung))
            with perf.phase("decode.prefill.build"):
                ps = self.page_size
                shown = self._prefill_len(seq)
                # what the prefill commits: the whole prompt, or (in
                # blocks) its whole blocks; the tail is the open block's
                n = shown - shown % self._width
                packed = np.zeros((3 * rung + 1 + self._state,), np.int32)
                toks, _, page_idx, page_off, *_ = _prefill_views(
                    packed, self._state)
                toks[:shown] = seq.tokens[:shown]
                # position t goes to page t // ps; the padding to the
                # scratch
                page_idx[:n] = np.repeat(seq.pages, ps)[:n]
                page_idx[n:] = rep.cache.scratch_page
                page_off[:] = np.arange(rung) % ps
                if self._state:
                    packed[-2:] = n, seq.row
                else:
                    packed[-1] = n
            replay = len(seq.tokens) > seq.prompt_len
            t0 = time.perf_counter()
            tw0 = time.time()
            if not replay:
                self._reg_queue_wait.observe(time.monotonic() - seq.t,
                                             at=t0)
            if events.enabled():
                spans.span_at("serve.queue_wait", seq.ctx, seq.tw, tw0)
            try:
                perf.count_dispatch()
                with perf.phase("decode.prefill.dispatch"):
                    first, *rep.pools = self._prefill_jit(
                        seq.params, *rep.pools, _to_device(packed))
                if rep.flight is not None or (leads and any(
                        other.prefilled for other in rep.active)):
                    region.close()
                    self._advance(rep, launch=leads)
                    t0 = max(t0, rep.landed_at)
                    region.enter_context(perf.phase(
                        "decode.prefill", sid=seq.sid, rung=rung))
                with perf.phase("decode.prefill.wait"):
                    # the token (a prefill in blocks yields none), then
                    # whatever counts the family sends
                    counts = np.asarray(first).reshape(-1)
                    if self._width == 1:
                        first, counts = int(counts[0]), counts[1:]
            except _ReplicaDead:
                raise
            # dklint: ignore[broad-except] a failed prefill lands TYPED on its own future with pages reclaimed
            except Exception as e:
                with self._cond:
                    rep.active.remove(seq)
                    self._finish_locked(rep, seq, "error")
                events.emit("decode_error", sid=seq.sid, where="prefill",
                            error=type(e).__name__)
                self._resolve(seq, None, error=e)
                return
        dt = time.perf_counter() - t0
        self._reg_prefill.observe(dt, at=t0)
        if self._family.observe_step is not None:
            self._family.observe_step(counts, t0)
        with self._cond:
            self._shapes.add(("prefill", rung))
            self._ewma_prefill = (
                dt if self._ewma_prefill is None
                else 0.8 * self._ewma_prefill + 0.2 * dt)
        seq.kv_len, seq.prefilled = n, True
        if self._width > 1:
            seq.block = self._open_block(seq, n)
            seq.block_steps, seq.fixed_at = 0, [None] * self._width
            if seq.passes is None:
                seq.passes = []
        elif not replay:
            self._first_token(seq)
        if events.enabled():
            spans.span_at("serve.prefill", seq.ctx, tw0, time.time(),
                          rung=rung, replica=rep.index)
        events.emit("decode_prefill", sid=seq.sid, rung=rung,
                    replica=rep.index, duration_s=dt,
                    ttft_s=seq.ttft_s, replay=replay)
        if replay or self._width > 1:
            # the first generated token was emitted before the crash;
            # the replayed prediction is that same token (greedy,
            # pinned params) — discard it, the canonical seq.tokens
            # drive the teacher-forced catch-up steps.  A prefill in
            # blocks has no token to emit: its passes follow
            return
        self._emit_token(seq, first)
        finish = self._sequence_done(seq, first)
        if finish is not None:
            with self._cond:
                rep.active.remove(seq)
                self._finish_locked(rep, seq, finish)
            events.emit("decode_complete", sid=seq.sid, finish=finish,
                        generated=len(seq.generated()),
                        steps=seq.steps)
            self._resolve(seq, finish)

    def _step_group(self, rep, group):
        """Launch one decode step for ``group`` (same pinned params)
        behind the step in flight, THEN land that step: fetch its tokens,
        emit them, settle finishes.  The one primitive of the loop: an
        empty ``group`` only lands, and with nothing in flight the launch
        is all there is (its tokens all host-known), so the drained case
        is this code with nothing carried.

        What the launch reads is the host's state as it will be once the
        step in flight lands, which the host knows without its tokens: a
        sequence of that step sits at ``kv_len + 1``, and its input token
        is the step's output for its slot, still on the device
        (:func:`_step_views`: entry ``-(j + 1)``), unless a recovered
        sequence is still catching up on tokens the host has (its
        predictions are discarded until ``kv_len`` reaches the frontier,
        so streams never see a duplicate).  A sequence that reaches
        ``max_new`` with the step in flight is left out: no step is spent
        on it.  One that ends on ``eos_id`` is known only when its step
        lands, with its successor already launched: that slot's result is
        discarded at ITS landing (``seq.finished``; never appended, never
        streamed, counted on ``decode.tokens_discarded``), as is the
        slot of a sequence cancelled or expired under its step.

        **Why pages are freed at once.**  A sequence retired while a
        launched step still names its pages (or its state row) returns
        them to the allocator immediately, and the next admission may
        take them.  Every program that touches a replica's pools takes
        the pools its predecessor put out (they are donated), so the
        device runs them in launch order: a new owner's prefill, and all
        its later steps, write after the stale step's one write, and a
        sequence reads only positions it wrote itself since it got the
        page.  The stale write lands in a page nobody reads before
        rewriting; nothing waits for the landing.

        **Failures.**  ``seq.tokens`` / ``kv_len`` advance only when a
        step lands, so a failed step retries IN PLACE from them
        (``step_retries``).  ``fault_point("decode.step")`` fires once a
        launch; a device failure surfaces at the fetch, with the
        successor already launched on poisoned inputs: both are dropped
        and the failure counts once, against the step that was fetched.
        Past the retries the replica quarantines when a survivor exists
        (the sequences migrate and replay), else exactly the dropped
        steps' sequences fail, typed, pages reclaimed."""
        prev, rep.flight = rep.flight, None
        if prev is not None:
            group = [seq for seq in group
                     if not self._ends_with(prev, seq)]
        if not group and prev is None:
            return
        rung = (self._rung_for(len(group), self.decode_ladder) if group
                else prev.rung)
        err, blamed = None, ()
        with perf.phase("decode.step", n=len(group or prev.group),
                        rung=rung):
            if group:
                try:
                    rep.flight = self._launch(rep, group, rung, prev)
                # dklint: ignore[broad-except] a failed launch retries in place, then quarantines or lands TYPED
                except Exception as e:
                    err, blamed = e, group
                    rep.attempt += 1
            if prev is not None:
                try:
                    with perf.phase("decode.step.wait"):
                        out = np.asarray(prev.out)
                # dklint: ignore[broad-except] a failed fetch drops the step and its successor, then retries, quarantines or lands TYPED
                except Exception as e:
                    err = e
                    blamed = prev.group + [seq for seq in group
                                           if seq not in prev.slot]
                    rep.attempt = prev.attempt + 1
                    rep.flight = None
                else:
                    self._land(rep, prev, out)
        if err is not None:
            self._step_failed(rep, blamed, err)

    def _ends_with(self, flight, seq):
        """Will ``seq`` reach its ``max_new`` when ``flight`` lands?  Known
        from its count alone (the step's prediction is a NEW token unless
        a recovered sequence is still catching up).  In blocks: when the
        pass in flight COMMITS the block that holds its last token (such
        a commit rides alone: nothing opens behind a last block, so the
        sequence's entry of the pass is the commit)."""
        if seq not in flight.slot:
            return False
        if self._width > 1:
            return (flight.fix[flight.slot[seq]] == 0
                    and self._last_block(seq, seq.kv_len))
        return (seq.kv_len + 1 >= len(seq.tokens)
                and len(seq.tokens) + 1 - seq.prompt_len >= seq.max_new)

    def _last_block(self, seq, start):
        """Does the block at ``start`` hold ``seq``'s last token?"""
        return start + self._width - seq.prompt_len >= seq.max_new

    def _launch(self, rep, group, rung, prev):
        """Build and dispatch one step for ``group`` on ``prev``'s output
        (None: nothing in flight) -> its :class:`_Flight`.

        One crossing: the step's six integer arrays (tokens or their
        sources, positions, page tables, write pages, write offsets,
        lengths) are views of ONE packed int32 host array
        (:func:`_step_views`; 54 KB at 32 slots of 416 pages) that goes
        to the device in one transfer and is cut apart inside the
        compiled step; what the step carries is there already.

        A pass over blocks gives a sequence one ENTRY, its open block
        (:meth:`_next_pass`), and a sequence whose entry COMMITS a second
        one right behind it: the block that opens next, as the host
        knows it (:meth:`_open_block`: masks), with what its first
        denoising pass fixes.  The commit's products are all there, in
        the pass that would have run anyway.  No second entry where the
        commit ends the sequence, where the pass has no entry to spare
        (:meth:`_entries`; the commit then rides alone and the block
        opens a pass later, which also moves the sequence's phase off
        the crowded pass), or where the host already knows the next
        block whole (a recovered sequence catching up: that block's own
        commit follows)."""
        with perf.phase("decode.step.build"):
            ps = self.page_size
            pmax = self.max_pages_per_seq
            width = self._width
            wide = width > 1          # a pass over blocks, not a token
            room = self._entries(rung)
            packed = np.zeros(
                (room * (pmax + 5 + wide * width + self._state),), np.int32)
            toks, positions, tables, wpage, woff, lengths, *rows = \
                _step_views(packed, pmax, self._state, width)
            fix = rows.pop(0) if wide else None
            wpage[:] = rep.cache.scratch_page
            if self._state:
                # a padding slot's state goes to the scratch row
                rows[0][:] = rep.cache.scratch_row
            ahead = prev.slot if prev is not None else {}
            entries = []

            def enter(seq, at, source, fixes=None):
                i = len(entries)
                entries.append(seq)
                toks[i] = source
                positions[i] = at
                tables[i, :len(seq.pages)] = seq.pages
                wpage[i] = seq.pages[at // ps]
                woff[i] = at % ps
                lengths[i] = at + width
                if wide:
                    fix[i] = fixes
                if self._state:
                    rows[0][i] = seq.row

            for n, seq in enumerate(group):
                j = ahead.get(seq)
                if not wide:
                    at = seq.kv_len + (j is not None)
                    enter(seq, at, seq.tokens[at] if at < len(seq.tokens)
                          else -(j + 1))
                    continue
                at, block, masks = self._next_pass(seq, prev)
                enter(seq, at, -(j + 1) if block is None else block,
                      min(self._fix_a_pass, masks))
                if masks or self._last_block(seq, at) \
                        or len(entries) + len(group) - n > room:
                    continue    # no commit, the last one, or no entry spare
                block = self._open_block(seq, at + width)
                masks = block.count(self._mask_id)
                if masks:   # else the host knows it whole: its own commit
                    enter(seq, at + width, block,
                          min(self._fix_a_pass, masks))
        t0 = time.perf_counter()
        fault_point("decode.step")
        perf.count_dispatch()
        with perf.phase("decode.step.dispatch"):
            out, *rep.pools = self._decode_jit(
                group[0].params, *rep.pools,
                rep.no_tokens if prev is None else prev.out,
                _to_device(packed))
        # fed or drained, asked once the successor is queued: a
        # predecessor still running then never left the chip without work
        fed = (None if prev is None or rep.prefilling
               else _still_running(prev.out))
        flight = _Flight(group, entries, rung, out, lengths, t0,
                         prev is not None, fed, rep.attempt, fix)
        rep.attempt = 0
        return flight

    def _next_pass(self, seq, prev):
        """What ``seq``'s next pass computes, as the host knows without
        the tokens of the pass in flight (``prev``; None when there is
        none) -> (where its open block starts, the block's tokens or None
        when they are the output of its entry of the pass in flight, how
        many of its positions are still masked: none left means the pass
        COMMITS the block).  The schedule is static
        (``low_confidence_static``): a pass fixes a known NUMBER of
        positions, so the count of masks left is arithmetic; which
        positions, and with what, only the device knows until the pass
        lands.  The reckoning is from the host's state as it WILL be when
        the pass in flight has landed: behind a commit in flight the
        sequence stands a block further, its open block the one that
        commit opens, less what that block's own entry of the same pass
        fixes where the commit carried it along."""
        j = None if prev is None else prev.slot.get(seq)
        if j is None:
            return seq.kv_len, seq.block, seq.block.count(self._mask_id)
        flying = prev.fix[j]
        if flying and not prev.folds(j - 1):
            # a denoising pass of the block the host has
            return (seq.kv_len, None,
                    seq.block.count(self._mask_id) - flying)
        # the block in flight commits: the next one opens, on the host
        # (the commit rides alone) or in the same pass
        start = seq.kv_len + self._width
        block = self._open_block(seq, start)
        masks = block.count(self._mask_id) - flying
        return start, (None if flying else block), masks

    def _land(self, rep, flight, out):
        """``flight``'s output is on the host: account the step, emit its
        tokens, settle finishes.  ``decode.step_s`` reads what the step
        cost the loop: from the later of its launch and its
        predecessor's tokens on the host to its own (dispatch + wait with
        nothing in flight; under overlap the period between two steps'
        tokens), one sample a step, stamped with that start, as are
        ``decode.step_overlapped`` (launched before its predecessor was
        fetched), ``decode.launch_fed`` where the launch took a sample
        (that predecessor was still running on the device) and the
        family's counts."""
        now = time.perf_counter()
        t0 = max(flight.t0, rep.landed_at)
        dt = now - t0
        rep.landed_at = now
        rep.steps += 1
        self._n_steps += 1
        self._m_step.observe(dt, at=t0)
        self._reg_step.observe(dt, at=t0)
        self._reg_overlapped.observe(float(flight.overlapped), at=t0)
        if flight.fed is not None:
            self._reg_fed.observe(float(flight.fed), at=t0)
        if self._family.observe_step is not None:
            # the counts came off the device behind the tokens, in the
            # one array the wait already fetched
            self._family.observe_step(
                out[self._entries(self.max_slots) * self._width:], t0,
                lengths=flight.lengths, page_size=self.page_size,
                **({} if flight.fix is None else {
                    "fix": flight.fix,
                    "folded": len(flight.entries) - len(flight.group)}))
        with perf.phase("decode.step.emit"):
            with self._cond:
                self._shapes.add(("decode", flight.rung))
                self._ewma_step = (dt if self._ewma_step is None
                                   else 0.8 * self._ewma_step + 0.2 * dt)
            events.emit("decode_step", replica=rep.index, rung=flight.rung,
                        n=len(flight.group), duration_s=dt,
                        **({} if flight.fix is None
                           else {"fixed": int(flight.fix.sum())}))
            finished, discarded, took = [], 0, []
            for i, seq in enumerate(flight.entries):
                if seq.finished or (finished and finished[-1][0] is seq):
                    # ended under this step (an eos seen a step late, a
                    # cancel, a deadline; the block behind a commit that
                    # met its eos): computed and thrown away
                    discarded += 1
                    continue
                if flight.fix is not None:
                    # a pass, not an entry: a sequence that holds two
                    # entries of it spent one
                    seq.steps += not flight.folds(i - 1)
                    finish = self._land_pass(
                        seq, flight.fix[i],
                        out[i * self._width:(i + 1) * self._width],
                        took, not flight.folds(i))
                    if finish is not None:
                        finished.append((seq, finish))
                    continue
                seq.steps += 1
                seq.kv_len += 1
                if seq.kv_len < len(seq.tokens):
                    # replay catch-up: this prediction is a token the
                    # stream already delivered before the crash — discard
                    continue
                token = int(out[i])
                self._emit_token(seq, token)
                finish = self._sequence_done(seq, token)
                if finish is not None:
                    finished.append((seq, finish))
            if discarded:
                self._reg_discarded.inc(discarded)
            if took:
                # one sample a pass that committed blocks, their mean: a
                # sample a block would outrun a histogram's memory (19,600
                # blocks a 51 s window of the benchmark's cell)
                self._reg_block_passes.observe(sum(took) / len(took), at=t0)
            if finished:
                with self._cond:
                    for seq, finish in finished:
                        rep.active.remove(seq)
                        self._finish_locked(rep, seq, finish)
                for seq, finish in finished:
                    events.emit("decode_complete", sid=seq.sid,
                                finish=finish,
                                generated=len(seq.generated()),
                                steps=seq.steps)
                    self._resolve(seq, finish)

    def _land_pass(self, seq, fixes, after, took, alone):
        """One entry of a landed pass over blocks: ``after`` is ``seq``'s
        open block as the pass left it -> the sequence's finish, or None.

        A denoising pass (``fixes`` > 0) only moves the host's copy of
        the block on: the positions it fixed are those that held the mask
        id and hold a token now, and each remembers the pass that fixed
        it.  NOTHING is emitted: a block's tokens become final out of
        order, and a cancel or a deadline under an open block returns
        none of it.  The COMMIT (``fixes`` == 0; its rows in the pool are
        the block's final ones) advances ``kv_len`` by the block, emits
        the block's tokens in position order (those the host did not have
        already: the prompt's tail and a recovered sequence's committed
        tokens are not emitted again), each with the pass that fixed it,
        ends the sequence at ``max_new`` or on ``eos_id`` (what the block
        holds beyond is counted on ``decode.block.tokens_trimmed`` and
        not emitted), and opens the next block on the host.  Where that
        block rode in the same pass its entry lands NEXT, as the first
        denoising pass of the block just opened.  ``took`` gains the
        passes the committed block cost: its denoising passes, and one
        more where its commit rode ``alone`` (with the sequence's next
        block in the same pass, that block pays for the pass)."""
        width = self._width
        if fixes:
            for b in range(width):
                if seq.block[b] == self._mask_id \
                        and int(after[b]) != self._mask_id:
                    seq.block[b] = int(after[b])
                    seq.fixed_at[b] = seq.block_steps
            seq.block_steps += 1
            return None
        start = seq.kv_len
        seq.kv_len += width
        took.append(seq.block_steps + alone)
        finish, trimmed = None, 0
        for b in range(len(seq.tokens) - start, width):
            if finish is not None:
                trimmed += 1
                continue
            if seq.ttft_s is None:
                self._first_token(seq)
            seq.passes.append(seq.fixed_at[b])
            self._emit_token(seq, seq.block[b])
            finish = self._sequence_done(seq, seq.block[b])
        if trimmed:
            self._reg_trimmed.inc(trimmed)
        seq.block = self._open_block(seq, seq.kv_len)
        seq.block_steps, seq.fixed_at = 0, [None] * width
        return finish

    def _step_failed(self, rep, blamed, err):
        """A launch or a fetch failed and nothing is in flight any more:
        leave the retry to the loop's next pass, or past ``step_retries``
        quarantine (a peer can hold the work: migrate and replay, the
        futures never see the failure) or fail ``blamed`` typed."""
        if rep.attempt <= self.step_retries:
            events.emit("decode_error", where="step_retry",
                        n=len(blamed), replica=rep.index,
                        attempt=rep.attempt - 1,
                        error=type(err).__name__)
            return
        rep.attempt = 0
        with self._cond:
            survivors = [r for r in self._live_replicas_locked()
                         if r is not rep]
        if survivors:
            raise _ReplicaDead(err)
        blamed = [seq for seq in blamed if not seq.finished]
        with self._cond:
            for seq in blamed:
                rep.active.remove(seq)
                self._finish_locked(rep, seq, "error")
        events.emit("decode_error", where="step", n=len(blamed),
                    replica=rep.index, error=type(err).__name__)
        for seq in blamed:
            self._resolve(seq, None, error=err)

    def _worker_main(self, rep):
        """Thread body: the scheduler loop plus the crash boundary.
        ANY escape — the :class:`_ReplicaDead` signal (kill seam, step
        failure past retries) or an unexpected scheduler bug —
        quarantines the replica so its sequences migrate or resolve
        typed instead of hanging on a silently dead thread."""
        try:
            self._worker_loop(rep)
        except _ReplicaDead as e:
            self._quarantine(rep, e.cause)
        # dklint: ignore[broad-except] a crashed worker quarantines its replica; sequences migrate or land typed, never hang
        except Exception as e:
            self._quarantine(rep, e)

    def _worker_loop(self, rep):
        while True:
            dropped = []
            with self._cond:
                # pending orphans hold the park open: an idle replica
                # has its whole (homogeneous) pool free, so the next
                # placement pass below always lands them
                while (not rep.queue and not rep.active
                       and rep.flight is None and not self._orphans
                       and not self._stopped and not rep.retiring
                       and not rep.killed):
                    # the scheduler's idle park: deliberately unbounded
                    # — every admit, cancel and both lifecycle exits
                    # notify this cond, and the predicate re-checks
                    # stop/retire/kill on wake
                    with perf.phase("decode.park"):
                        # dklint: ignore[unbounded-wait] idle park; admission and lifecycle exits notify this cond
                        self._cond.wait()
                with perf.phase("decode.sched", queued=len(rep.queue),
                                active=len(rep.active)):
                    if self._stopped:
                        break
                    if rep.killed:
                        raise _ReplicaDead(Overloaded("replica_lost"))
                    if rep.retiring and not rep.queue \
                            and not rep.active:
                        break
                    o_migrated, o_dropped = \
                        self._try_place_orphans_locked()
                    # retire cancelled and deadline-expired actives,
                    # refill free slots — the continuous-batching seam:
                    # between iterations, never a batch barrier.  An
                    # expired deadline frees the slot HERE, between
                    # steps.
                    now = time.monotonic()
                    for seq in list(rep.active):
                        fin = ("cancelled" if seq.cancelled else
                               "deadline" if seq.deadline is not None
                               and now > seq.deadline else None)
                        if fin is not None:
                            rep.active.remove(seq)
                            self._finish_locked(rep, seq, fin)
                            dropped.append((seq, fin))
                    while rep.queue \
                            and len(rep.active) < self.max_slots:
                        seq = rep.queue.popleft()
                        fin = ("cancelled" if seq.cancelled else
                               "deadline" if seq.deadline is not None
                               and now > seq.deadline else None)
                        if fin is not None:
                            self._finish_locked(rep, seq, fin)
                            dropped.append((seq, fin))
                            continue
                        rep.active.append(seq)
                    # prefill candidates by state, not by admission
                    # order: a recovered sequence re-enters here with
                    # kv_len == 0 and replays exactly like a fresh
                    # admission
                    prefills = [s for s in rep.active if not s.prefilled]
            for seq, target in o_migrated:
                self._reg_recovered.inc()
                events.emit("decode_recover", sid=seq.sid, src=None,
                            dst=target.index,
                            generated=len(seq.generated()),
                            recoveries=seq.recoveries)
            dropped.extend(o_dropped)
            for seq, fin in dropped:
                if fin == "cancelled":
                    events.emit("decode_cancel", sid=seq.sid,
                                generated=len(seq.generated()))
                else:
                    events.emit("decode_deadline", sid=seq.sid,
                                phase="expiry",
                                generated=len(seq.generated()))
                self._resolve(seq, fin)
            # a pass that runs a prefill samples no ``decode.launch_fed``:
            # a step launched behind the leading prefill is fed by it
            # whatever the host did, and one launched after a prefill's
            # wait finds the chip drained by design
            rep.prefilling = bool(prefills)
            for i, seq in enumerate(prefills):
                self._prefill(rep, seq, self._rung_for(
                    self._prefill_len(seq), self.prefill_ladder),
                    leads=i == 0)
                if rep.killed:
                    raise _ReplicaDead(Overloaded("replica_lost"))
            self._advance(rep)
            self._maybe_self_check()
        # stopped or retired: nothing is launched any more, and the step
        # in flight lands (a retired replica's holds discarded slots only)
        self._step_group(rep, [])

    def _advance(self, rep, launch=True):
        """One pass of decode steps over the replica's prefilled
        sequences, grouped by pinned params generation (a hot reload
        means at most a couple of groups until old sequences drain):
        each group's step launches behind whatever is in flight and
        lands it, so two generations simply alternate; with no group
        left, or nothing to ``launch``, the last step still has to
        land."""
        groups = {}
        if launch:
            with self._cond:
                for seq in rep.active:
                    if not seq.prefilled:
                        continue  # not prefilled yet: it joins the next pass
                    groups.setdefault(id(seq.params), []).append(seq)
        for group in list(groups.values()) or [[]]:
            self._step_group(rep, group)
            if rep.killed:
                raise _ReplicaDead(Overloaded("replica_lost"))

    # -- survivability: quarantine + sequence-level recovery ------------
    def kill_replica(self, index):
        """Chaos seam: crash one replica worker (the thread analogue
        of SIGKILL on a replica process).  The worker observes the
        flag at its next seam, quarantines the replica — pages freed,
        in-flight sequences re-admitted onto survivors and replayed —
        and exits.  Refused (``ValueError``) for the LAST live
        replica: whole-pod loss is the job scheduler's problem, not a
        survivable event."""
        with self._cond:
            rep = next((r for r in self._replicas
                        if r.index == int(index)), None)
            if rep is None or rep.dead or rep.killed:
                raise ValueError(
                    f"kill_replica({index}): no such live replica")
            live = self._live_replicas_locked()
            if rep in live and len(live) <= 1:
                raise ValueError(
                    "kill_replica: refusing to kill the last live "
                    "replica (whole-pod loss is out of scope)")
            rep.killed = True
            self._cond.notify_all()
        return rep.index

    def _place_locked(self, seq):
        """Re-admission placement (caller holds the lock): the
        surviving replica with the most free pages that can hold the
        sequence's WORST-CASE reservation — the same door contract as
        submit_generate.  -> the replica, or None when nowhere fits."""
        total = self._positions_for(seq.prompt_len, seq.max_new)
        live = [r for r in self._live_replicas_locked()]
        live.sort(key=lambda r: -r.cache.stats()["free_pages"])
        for rep in live:
            try:
                seq.pages = rep.cache.alloc(seq.sid, total)
            except PagesExhausted:      # pages or state rows
                continue
            seq.row = self._row_of(rep, seq.sid)
            return rep
        return None

    def _fits_somewhere_locked(self, seq):
        """Could ANY live replica's whole pool hold this sequence's
        worst-case reservation?  If yes, a full-but-alive pool is a
        capacity wait, not a loss."""
        total = self._positions_for(seq.prompt_len, seq.max_new)
        return any(r.cache.pages_for(total) <= r.cache.num_pages
                   for r in self._live_replicas_locked())

    def _try_place_orphans_locked(self):
        """Place pending orphans — recovered sequences waiting for
        survivor capacity (caller holds the lock).  They hold NO
        pages while pending; placement reserves worst-case, exactly
        like admission.  -> (migrated, dropped) pairs for the caller
        to emit events / resolve futures OUTSIDE the lock."""
        migrated, dropped = [], []
        if not self._orphans:
            return migrated, dropped
        now = time.monotonic()
        still = []
        for seq in self._orphans:
            fin = ("cancelled" if seq.cancelled else
                   "deadline" if seq.deadline is not None
                   and now > seq.deadline else None)
            if fin is not None:
                self._account_exit_locked(seq, fin)
                dropped.append((seq, fin))
                continue
            target = self._place_locked(seq)
            if target is None:
                still.append(seq)
                continue
            seq.kv_len, seq.prefilled = 0, False  # replay regenerates the KV
            seq.recoveries += 1
            seq.params = target.pin(seq.params_host)
            target.queue.append(seq)
            self._n_recovered += 1
            migrated.append((seq, target))
        self._orphans[:] = still
        if migrated:
            self._cond.notify_all()
        return migrated, dropped

    def _quarantine(self, rep, cause):
        """Take a crashed replica out of service and carry its
        sequences over: free every page it held, re-admit each
        in-flight sequence onto a survivor (``kv_len`` reset — the
        replay machinery regenerates its KV from the canonical
        tokens), park what fits a survivor's pool but not its current
        free list (placed as capacity frees), and resolve typed only
        what no survivor could EVER hold.  Futures never hang; pages
        never leak."""
        with self._cond:
            rep.killed = True
            rep.dead = True
            rep.retiring = True     # out of _pick_replica rotation
            rep.flight = None       # dropped: the replay starts from what LANDED
            orphans = list(rep.active) + list(rep.queue)
            del rep.active[:]
            rep.queue.clear()
            for seq in orphans:
                rep.cache.free(seq.sid)
            self._n_quarantines += 1
            self._cond.notify_all()
        self._reg_quarantines.inc()
        events.emit("decode_quarantine", replica=rep.index,
                    orphans=len(orphans), cause=type(cause).__name__)
        recover_err = None
        try:
            fault_point("decode.recover")
        # dklint: ignore[broad-except] a failed recovery resolves every orphan TYPED — never a hang
        except Exception as e:
            recover_err = e
        migrated = []
        resolved = []
        with self._cond:
            for seq in orphans:
                if seq.cancelled:
                    self._account_exit_locked(seq, "cancelled")
                    resolved.append((seq, "cancelled", None))
                    continue
                target = (None if recover_err is not None
                          else self._place_locked(seq))
                if target is None:
                    if recover_err is None \
                            and self._fits_somewhere_locked(seq):
                        # survivors exist but are full RIGHT NOW: the
                        # sequence was already admitted (door contract
                        # honoured once), so it WAITS for capacity
                        # instead of failing — futures never see a
                        # survivable crash
                        self._orphans.append(seq)
                        continue
                    # no survivor can EVER hold it (or recovery itself
                    # is the injected failure): typed, never hung
                    err = recover_err if recover_err is not None \
                        else cause
                    if not isinstance(err, BaseException):
                        err = Overloaded("replica_lost")
                    self._account_exit_locked(seq, "error")
                    resolved.append((seq, None, err))
                    continue
                seq.kv_len, seq.prefilled = 0, False  # replay regenerates the KV
                seq.recoveries += 1
                seq.params = target.pin(seq.params_host)
                target.queue.append(seq)
                migrated.append((seq, target))
                self._n_recovered += 1
            self._cond.notify_all()
        for seq, target in migrated:
            self._reg_recovered.inc()
            events.emit("decode_recover", sid=seq.sid,
                        src=rep.index, dst=target.index,
                        generated=len(seq.generated()),
                        recoveries=seq.recoveries)
        for seq, fin, err in resolved:
            if fin == "cancelled":
                events.emit("decode_cancel", sid=seq.sid,
                            generated=len(seq.generated()))
            else:
                events.emit("decode_error", sid=seq.sid,
                            where="quarantine",
                            error=type(err).__name__)
            self._resolve(seq, fin, error=err)

    def _maybe_self_check(self):
        now = time.monotonic()
        with self._cond:
            if now < self._next_self_check:
                return
            self._next_self_check = now + self._self_check_interval
        self.self_check()

    def self_check(self):
        """Reconcile every allocator against the sequences the
        scheduler actually holds — the periodic backstop behind
        :meth:`assert_no_leaks`.  An allocation owned by NO queued or
        active sequence is a leak: reclaimed here, counted on
        ``decode.kv_leaked``, and reported so the gate fails loudly
        instead of the pool quietly shrinking.  -> pages reclaimed."""
        leaked = 0
        stale = []
        with self._cond:
            for rep in self._replicas:
                owned = {s.sid for s in rep.active}
                owned.update(s.sid for s in rep.queue)
                for sid in rep.cache.sequence_ids():
                    if sid not in owned:
                        n = rep.cache.free(sid)
                        leaked += n
                        stale.append((rep.index, sid, n))
            if leaked:
                self._n_kv_leaked += leaked
        for rep_index, sid, n in stale:
            self._reg_kv_leaked.inc(n)
            events.emit("decode_kv_leak", replica=rep_index, sid=sid,
                        pages=n)
        return leaked

    # -- hot reload -----------------------------------------------------
    def set_params(self, state, step=None):
        """Swap every replica's params reference.  In-flight sequences
        keep their pinned params (finish on what they started with);
        sequences admitted after this call see the new ones — zero
        dropped mid-decode sequences, the blue/green contract."""
        params = (state["params"]
                  if isinstance(state, dict) and "params" in state
                  else state)
        for rep in self._replicas:
            rep.put_params(params)
        self._host_params = params
        self.reload_count += 1
        metrics.counter("serve.reloads").inc()
        events.emit("serve_reload", step=step, role="decode",
                    replicas=len(self._replicas))

    # -- elastic replica set --------------------------------------------
    def resize(self, n):
        """Grow or shrink the replica set (the autoscaler's actuation
        seam).  Grow: fresh replicas with fresh KV pools on the
        construction device list.  Shrink: retired replicas stop
        admitting, finish every sequence they hold, then exit (nothing
        admitted is ever dropped).  -> the new live replica count."""
        n = int(n)
        if n < 1:
            raise ValueError(f"resize({n}): must keep >= 1 replica")
        started = []
        with self._cond:
            if self._stopped or self._draining:
                raise Overloaded(
                    "stopped" if self._stopped else "draining")
            live = [r for r in self._replicas if not r.retiring]
            cur = len(live)
            if n < cur:
                for rep in live[n:]:
                    rep.retiring = True
                self._rr = 0
                self._cond.notify_all()
            elif n > cur:
                for _ in range(n - cur):
                    idx = self._next_replica_index
                    self._next_replica_index += 1
                    rep = self._make_replica(idx)
                    self._replicas.append(rep)
                    t = threading.Thread(
                        target=self._worker_main, args=(rep,),
                        daemon=True, name=f"dk-decode-worker-{idx}")
                    self._workers.append(t)
                    started.append(t)
        for t in started:
            t.start()
        return n

    # -- lifecycle ------------------------------------------------------
    def drain(self, timeout_s=None):
        """Stop admission (typed rejection), let every admitted
        sequence decode to completion, then stop the schedulers.
        Nothing admitted is ever dropped.  -> delivery counts."""
        t0 = time.perf_counter()
        with self._cond:
            self._draining = True
            backlog = self._outstanding
            self._cond.notify_all()
        events.emit("serve_drain_begin", backlog=backlog,
                    role="decode")
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        with self._cond:
            while self._outstanding:
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(
                        f"drain: {self._outstanding} sequences still "
                        f"in flight after {timeout_s}s")
                self._cond.wait(remaining)
        self._shutdown_threads()
        out = {"delivered": self._n_completed,
               "errored": self._n_errors,
               "rejected": self._n_rejected,
               "cancelled": self._n_cancelled,
               "duration_s": time.perf_counter() - t0}
        events.emit("decode_drain", **out)
        return out

    def _shutdown_threads(self):
        with self._cond:
            first = not self._stopped
            self._stopped = True
            self._cond.notify_all()
        if not first:
            self._drained.wait(timeout=10)
            return
        for t in self._workers:
            if t is not threading.current_thread():
                t.join(timeout=10)
        perf.unwatch_stalls()
        self._drained.set()

    def close(self, drain=True, timeout_s=None):
        """Stop the engine.  ``drain=True`` finishes the backlog;
        ``drain=False`` fails unresolved sequences with a typed
        :class:`Overloaded` and reclaims their pages (never a silent
        drop, never a leaked page)."""
        if self._stopped:
            return
        if drain:
            self.drain(timeout_s=timeout_s)
            return
        with self._cond:
            self._draining = True
        self._shutdown_threads()
        orphans = []
        with self._cond:
            for rep in self._replicas:
                for seq in list(rep.queue) + list(rep.active):
                    orphans.append((rep, seq))
                rep.queue.clear()
                del rep.active[:]
            for rep, seq in orphans:
                self._finish_locked(rep, seq, "stopped")
            pending = list(self._orphans)
            self._orphans[:] = []
            for seq in pending:   # page-less: bookkeeping half only
                self._account_exit_locked(seq, "stopped")
        for _, seq in orphans:
            self._resolve(seq, None, error=Overloaded("stopped"))
        for seq in pending:
            self._resolve(seq, None, error=Overloaded("stopped"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    @property
    def draining(self):
        return self._draining

    @property
    def running(self):
        return not self._stopped

    def kv_stats(self):
        """Aggregate + per-replica page-pool accounting."""
        per = [r.cache.stats() for r in self._replicas
               if not r.retiring]
        total = sum(p["num_pages"] for p in per)
        used = sum(p["used_pages"] for p in per)
        return {
            "num_pages": total,
            "used_pages": used,
            "peak_pages": sum(p["peak_pages"] for p in per),
            "state_rows": sum(p["state_rows"] for p in per),
            "used_rows": sum(p["used_rows"] for p in per),
            "occupancy": (used / total) if total else 0.0,
            "sequences": sum(p["sequences"] for p in per),
            "replicas": per,
        }

    def assert_no_leaks(self):
        """Every replica's allocator balances and, when idle, holds
        zero pages — the chaos sweep / gate invariant."""
        for rep in self._replicas:
            rep.cache.assert_balanced()
        with self._cond:
            idle = self._outstanding == 0
        if idle:
            for rep in self._replicas:
                used, rows = rep.cache.used_pages(), rep.cache.used_rows()
                if used or rows:
                    raise AssertionError(
                        f"replica {rep.index} leaked {used} KV pages and "
                        f"{rows} state rows with no sequence outstanding")

    def stats(self):
        """JSON-ready engine counters — the ``/metricsz`` payload core
        (same retrace contract as ``ServingEngine.stats``)."""
        with self._cond:
            queued = sum(len(r.queue) for r in self._replicas)
            active = sum(len(r.active) for r in self._replicas)
            outstanding = self._outstanding
            shapes = sorted(self._shapes)
            live = len(self._live_replicas_locked())
            dead = sum(1 for r in self._replicas if r.dead)
        return {
            "replicas": live,
            "prefill_ladder": list(self.prefill_ladder),
            "decode_ladder": list(self.decode_ladder),
            "page_size": self.page_size,
            "queued": queued,
            "active": active,
            "pending": queued,
            "outstanding": outstanding,
            "admitted": self._n_admitted,
            "completed": self._n_completed,
            "rejected": self._n_rejected,
            "errors": self._n_errors,
            "cancelled": self._n_cancelled,
            "tokens": self._n_tokens,
            # decode steps landed: a token a slot each, or (generation in
            # blocks) PASSES, which fix 0 to a block of tokens a slot
            "steps": self._n_steps,
            "quarantines": self._n_quarantines,
            "recovered": self._n_recovered,
            "shed": self._n_shed,
            "deadline_infeasible": self._n_deadline_infeasible,
            "deadline_expired": self._n_deadline_expired,
            "kv_leaked": self._n_kv_leaked,
            "orphans_pending": len(self._orphans),
            "replicas_dead": dead,
            "reloads": self.reload_count,
            "shapes_dispatched": shapes,
            # the no-retrace bound: prefill rungs + decode rungs ever
            # dispatched (executables are shapes x replica devices on
            # top, both factors fixed)
            "retrace_count": len(shapes),
            "retrace_bound": (len(self.prefill_ladder)
                              + len(self.decode_ladder)),
            "draining": self._draining,
            "kv": self.kv_stats(),
            "ttft_s": self._m_ttft.summary(),
            "step_s": self._m_step.summary(),
        }
