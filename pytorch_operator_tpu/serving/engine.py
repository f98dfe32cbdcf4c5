"""Continuous-batching decode engine.

Reference analog: SURVEY §1's control flow — a long-running reconciled
workload — applied to inference. The training operator's reconciler
keeps a desired world running; this engine keeps a desired BATCH
decoding: a fixed set of cache slots, each slot independently holding a
request at its own depth, refilled the moment its occupant finishes.

TPU-first shape (every program's shapes static):

- The model is whatever ``cfg.serving_model()`` returns
  (models/serving.py: its cache constructor, its two forwards, its own
  device counters); the engine names no model family.
- ONE decode program: ``decode_block`` loops ``steps`` single-token
  steps (a traced count, so every length is the same compiled program)
  over the full [slots] batch through the model's per-row decode
  forward — every row at its own position. Attention reads each
  layer's cache in blocks, found inside the program from the positions
  (ops/cache_attention.py): each row's up to the block that holds that
  row's own position where the slabs are plain (a kernel with per-row
  lengths; ``ServingModel.decode_reads_per_row``), every row's up to the
  deepest row's where they are int8 (a loop with a traced trip count).
  Either way a step costs what the batch holds and not what the slabs
  reserve. Empty rows are parked at position 0 (they
  re-write position 0 of their own empty row, which the next admission's
  first chunk overwrites), so a slot's last occupant reads one block and
  never holds the loop's bound up; rows never see each other's cache. Everything the engine
  decides (admit, harvest, and the serve loop's poll and responses)
  happens between dispatches, so the engine chooses each dispatch's
  length from what it holds on the host (:func:`decode_steps`): to the
  step at which the next slot frees when all are taken, a quantum
  (:data:`QUANTUM`) while a slot is free for an arrival, never past the
  last row's budget, never more than ``block``, the most steps one
  dispatch may run. Each dispatch's span says which rule sized it
  (``BENCHMARK.json``'s chat and longprompt cells judge it).
- The prefill program, ``prefill_chunk``: fixed-size chunks through the
  model's chunked-prefill forward into one slot's row of the donated
  cache, in place (the model is told the slot; nothing row-sized is
  sliced out or written back), last chunk padded — the pad tokens write
  cache slots past the prompt that every later read either masks
  (col <= row) or overwrites (the next decode token lands exactly on the
  first padded slot before anything attends it). That holds for state
  that grows with position; a model whose state is a recurrence has no
  later mask, so the model is also told how many of the chunk's tokens
  are real (a traced scalar: still one program) and what it does with
  that is its own business (models/serving.py). A chunk attends the
  row's prefix up to its own last position, by the same bounded
  attention, and runs no head: a small program of its own,
  ``prefill_chunk_head``, takes the last chunk's hidden states to the
  logits of the prompt's last position, once a prompt
  (``prefill_head_chunks`` counts it) -- through the model's ``finish``
  where it has one (layers that write no state run there, for that one
  token, reading the slot's cache), else the head's product alone --
  samples the first token from them
  with the sampler the decode steps use and writes the row's token and
  position into the donated ``tok`` / ``pos`` that ``decode_block`` reads.
  Arbitrary prompt lengths therefore hit exactly these compiled programs,
  and a prompt longer than one program's activation budget prefills in
  bounded O(chunk · L) score memory.
- TWO widths of it where the model takes them (PR 47). A chunk pays a cost
  that does not grow with its tokens: its weights are read once a chunk,
  and at the configuration's ``chunk`` (the width that suits its SHORT
  prompts: a prompt pads to whole chunks) that read is not hidden behind
  the tokens' operations. So a model that says its ``prefill`` takes any
  width and its cache does not depend on it
  (``ServingModel.prefill_any_width``: the llama family; nothing else
  selects the path, no option and no model's name) also gets
  ``prefill_chunk_wide``, the same function at :func:`wide_chunk` tokens (the
  cache attention's block, 512), and each prompt's chunks are chosen from
  its length (:func:`chunk_schedule`): wide from position 0 while a wide
  chunk's worth of tokens remains, the rest narrow, or as one more wide
  chunk where the narrow ones would pad to its length anyway; a short
  prompt keeps the narrow schedule exactly, and every prompt pads to what
  it padded to. The wide program hands the head's ONE program the narrow
  window of its hidden states that holds the last real token. The wide
  program is compiled from shapes (or loaded from the compile cache) when
  the engine is made, in line: no warm-up request need reach it, and
  nothing compiles or loads inside a long prompt's admission.
  ``prefill_wide_chunks`` counts its dispatches beside ``prefill_chunks``
  (every chunk); a dispatch's span says its ``width``. Every other model
  has one width, its programs and schedule as before.
- An admission costs the device no round trip to the host: a boundary
  queues every admitted prompt's chunks and head, then ``decode_block``
  behind them (what it needs of a new row is the request's: its position
  and its budget), and only then reads each first token (4 bytes) and the
  decode tokens. A first token that ends its request (``eos_token``) is
  learnt one dispatch late: the row ran a dispatch whose tokens are
  dropped (``decode_behind_admit`` counts the dispatches queued so).
- A boundary queues at most :data:`ADMIT_TOKENS` prompt tokens of prefill
  (as whole chunks) in front of the decode dispatch: the rows that are
  decoding wait for a bounded stretch of prefill, and a wave of long
  prompts into an empty engine is admitted a round at a time, its first
  rows decoding meanwhile. A prompt LONGER than the budget: where the
  model's decode step can leave a row untouched
  (``ServingModel.holds``), its prefill stops at a chunk's end and goes
  on at the next boundary (``prefill_rounds`` counts the boundaries that
  queued chunks of a prompt: over ``admitted`` it is 1 until a prompt is
  split), so the budget bounds what stands in front of a decode dispatch
  whatever the prompt's length; the row's state, convolution tail and
  slabs live in its slot meanwhile, the row HELD at position -1 through
  the decode dispatches between, and it joins ``decode_block`` once its
  head has run. For every other model such a prompt is admitted whole,
  alone. A row part-way through its prompt is neither free nor active:
  no request is admitted into its slot; it is no row of a decode
  dispatch (``slot_blocks_occupied``, ``decode_row_steps`` and so
  ``slot_occupancy_pct`` count the rows that decode, and
  ``decode_attended_positions`` charges it nothing: like a parked row it
  costs the walk one block a step); :func:`decode_steps` sees its slot
  among the free ones, so the dispatch behind its part is a quantum and
  the next part is queued soon; ``abort_in_flight`` evicts it with the
  rest and returns its id.
- Slot L-1 of every row is a parking slot: rows that exhaust their
  budget clamp there, so admission requires prompt + new <= L-1 and
  no live stream ever attends a parked write.
- A model that DRAFTS (``ServingModel.drafter``, models/serving.py) gets
  a VERIFYING step in ``decode_block`` (:func:`_drafting_programs`): the
  row's state is its last accepted token ``tok`` at ``pos`` and a
  ``draft`` of the token after it; a step runs both through the main
  stack at ``pos`` and ``pos + 1``, takes the stack's own choice after
  the first, accepts the draft iff it IS that choice (then the choice
  after the second is a token too), lets the drafter leave the next
  draft, and advances the row by one or two -- all on the device inside
  the dispatch's loop, no host round trip a step. What is delivered is
  what the model delivers without its drafter, token for token; a
  position written under a rejected draft is written again by the row's
  next step before anything attends it. The dispatch returns each step's
  two tokens and how many of them it delivered; ``_accept_token`` is
  called once a delivered token and a row's surplus token past its budget
  is dropped; ``decode_steps`` sizes a dispatch for rows that need between
  half and all of their budget in steps; the counters that depend on
  where a row stood (``decode_live_positions``,
  ``decode_attended_positions``) take the dispatch's own tallies, at
  every row-step, and ``decode_yield_pct`` = tokens over row-steps reads
  up to 200. The head's program of such a model also runs the drafter on
  the prompt's last position (it writes the drafter's state, so it takes
  the cache donated) and leaves the first draft. Greedy only: a
  temperature is refused.

Latency accounting: TTFT per request (submit -> first sampled token,
measured on the host around the real dispatches) and its three parts
(claim wait, slot wait, prefill); per-token latency samples at dispatch
granularity (dispatch wall / tokens accepted in it) — what a client
experiences when tokens arrive a dispatch at a time, and the source for
the p50/p99 the bench reports. Greedy tokens are a function of the
model and the prompt, never of where the dispatches were cut.

Measurement lives where the work happens, always on (PERF.md section 3
lists every name beside the metric that reads it):

- spans through ``obs.span`` (file records under ``TPUJOB_TRACE_DIR``,
  ``TraceAnnotation`` s on the device trace's clock while a
  ``jax.profiler`` session records): ``engine.step`` around
  ``engine.admit`` (with ``engine.prefill_dispatch`` per chunk),
  ``engine.decode_dispatch``, ``engine.first_token`` (the fence on the
  boundary's first tokens), ``engine.decode_fence``, ``engine.accept``
  and ``engine.harvest``; the per-request hop spans ``slot_wait`` and
  ``decode`` from the engine's own timestamps. The dispatch is the unit
  of the trace: a dispatch's span carries what the dispatch was (a
  chunk's slot, prompt tokens and whether the head ran behind it; a
  decode dispatch's rows, steps and the rule that sized it; on its fence
  also the positions live and attended -- where the model drafts, the
  FLOOR of attended, as if no draft were accepted, and the dispatch's own
  ``attended`` with ``drafted`` and ``accepted`` on ``engine.accept``,
  which opens after the fence), each number known before the
  span opens, so a reader has the counters' increments of exactly the
  traced stretch, on the device's clock, and can pair every run of
  ``decode_block`` on the device with the dispatch that queued it;
- counters (integers and ``perf_counter`` sums, O(1) per iteration):
  blocks (dispatches) and their steps, occupied rows,
  row-steps and accepted tokens (slot occupancy, decode yield), the cache
  positions those tokens had live beside the positions attention read for
  them (``decode_attended_positions`` charges an active row the whole
  blocks up to its own position where the model's decode step reads per
  row, up to the deepest active row's where it does not; over
  ``decode_row_steps`` x ``max_decode_len`` it is the share of the slabs
  still read, over ``decode_live_positions`` what was read for each
  position live; ``prefill_attended_positions`` is the same over the
  chunks, each read to its own end), prefill chunks, those
  of them that ran the head, and pad tokens,
  admissions, the model's own counters (summed on the device inside the
  two programs, brought back at ``stats()``), and one clock that charges every
  second of the serving thread to a segment
  (:data:`GAP_SEGMENTS` while the device waits for the host,
  :data:`FENCE_SEGMENTS` while the host waits for the device,
  ``dispatch``, ``overlapped``, ``idle``);
- the record as it stood at the newest arrival (``submit`` copies the
  counters and the clock's sums: two small dicts a request), which
  ``stats()`` gives as ``fed_*`` beside the whole: the engine under
  arrivals apart from the engine emptying.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Callable, NamedTuple, Optional

import numpy as np

from .. import obs
from ..obs.trace import serve_span

# Segments of the serving thread's time (``ServingEngine.host_lap``).
# From a fence's return to the next dispatch the device has nothing to do
# and the host is what it waits for; those seconds are the host gap, by
# what the host did (``admit_prep``: up to a boundary's first dispatch, a
# prompt padded or the decode dispatch sized; ``accept``: the decode
# dispatch's tokens taken, row by row):
GAP_SEGMENTS = ("accept", "harvest", "respond", "report", "poll", "submit", "admit_prep")
# ... while the host blocks on the device's result:
FENCE_SEGMENTS = ("first_token", "decode_fence")
# ... ``dispatch`` in the dispatch calls and while it queues work behind work
# (a prompt's later chunks, the next prompt, the decode dispatch behind an
# admission), ``overlapped`` for the bookkeeping done while the decode
# dispatch is in flight (first tokens taken, the dispatch counted), and
# ``idle`` while there is no request anywhere (the serve loop's sleep).
SEGMENTS = GAP_SEGMENTS + FENCE_SEGMENTS + ("dispatch", "overlapped", "idle")


def host_key(segment: str) -> str:
    """The key of a segment's seconds in ``ServingEngine.stats()``."""
    return f"host_gap_{segment}_s" if segment in GAP_SEGMENTS else f"host_{segment}_s"


# What sized a decode dispatch (:func:`decode_steps`): ``sized_by`` on the
# dispatch's span.
SIZED_BY = ("budget", "quantum", "ceiling")
_COUNTERS = (
    "decode_blocks", "decode_steps", "slot_blocks_occupied", "decode_row_steps", "decode_tokens",
    "decode_live_positions", "decode_attended_positions", "prefill_attended_positions",
    "prefill_chunks", "prefill_wide_chunks", "prefill_head_chunks", "prefill_tokens", "prefill_pad_tokens",
    "admit_rounds", "decode_behind_admit", "admitted", "prefill_rounds",
)
SPAN_CAT = "engine"

# Decode steps per dispatch while a slot is free for an arrival, and the
# fewest a full batch runs to its next finishing row. Chosen from a chip
# sweep of 8 / 16 / 32 in both serving cells (PERF.md section 6, PR 25):
# at 7.4 ms a step on the v5e a boundary costs the host 2-3 ms, and 8 gave
# the shortest time to first token and the most tokens a second at the
# same time per output token as 16.
QUANTUM = 8


# Most prompt tokens (as whole chunks, pads counted) the admissions of ONE
# boundary may queue in front of the decode dispatch behind them; stated in
# tokens so that it means the same at any chunk size (128 chunks of 128, as
# it was first set: PERF.md section 6, PR 36). The rows that are decoding
# wait for every chunk of a round, so an empty engine that meets a hundred
# long prompts (a closed loop's first wave) would hold its first rows'
# second tokens back for the whole wave; with the bound they wait a second
# or so, and the rest of the wave is admitted a round at a time between
# dispatches of a quantum. A round's first prompt is always begun: whole
# where it fits or the model cannot hold a row (``ServingModel.holds``),
# else as many whole chunks of it as the budget takes, the rest at the next
# boundaries (PERF.md section 6, PR 46).
ADMIT_TOKENS = 16_384


def decode_steps(remaining, free_slots: int, block: int, per_step: int = 1) -> tuple[int, str]:
    """How many steps the next decode dispatch runs, and which rule sized
    it (one of :data:`SIZED_BY`). ``remaining`` are the active rows'
    remaining budgets (each >= 1; an upper bound on the row's life where
    an EOS token can end it sooner), ``free_slots`` the slots that hold no
    request — after admission, so a free slot means nothing is queued, or
    that the boundary's admissions reached :data:`ADMIT_TOKENS`, or that the
    slot's row is part-way through its prompt (its next part is queued at
    the next boundary).
    ``per_step`` is the most tokens a step yields a row (2 where the model
    drafts): a row with ``r`` tokens left then needs between ``ceil(r /
    per_step)`` and ``r`` steps, and the rules below take the FEWEST for
    "the step at which the next slot frees" (no slot can free sooner, and a
    dispatch that ends before the row does is followed by another, sized
    again from what is left) and the MOST for "a step no row can use".

    - a slot free: an arrival could be admitted at the next boundary, so
      at most a quantum;
    - all slots taken, whether or not a request is queued: to the
      shortest budget, the step at which the next slot frees and its
      answer can go out — but at least a quantum, so rows that finish a
      step apart do not make one-step dispatches;
    - never past the longest budget (a step no row can use), never more
      than ``block``.
    """
    shortest, longest = -(-min(remaining) // per_step), max(remaining)
    steps, sized_by = QUANTUM, "quantum"
    if not free_slots and shortest > QUANTUM:
        steps, sized_by = shortest, "budget"
    if longest < steps:
        steps, sized_by = longest, "budget"
    if block < steps:
        steps, sized_by = block, "ceiling"
    return steps, sized_by


def wide_chunk(model, chunk: int) -> Optional[int]:
    """The width of the wide prefill chunk, or None where there is none. The
    model says whether it takes one (``ServingModel.prefill_any_width``);
    the width is the cache attention's block: a chunk's scores against one
    block of its row's slab are ``[1, K, G, width, block]`` float32, and a
    chunk wider than that block was 3.2 x slower on the chip (PERF.md
    section 6, PR 46), so the one constant is the attention's own. None
    where the configuration's chunk is already that wide, does not divide
    it (the narrow chunks behind the wide ones start on a multiple of
    theirs), or the slab is no longer than it; and for a model that drafts
    (its chunks come with the token that follows them: no such model takes
    a wide chunk yet)."""
    from ..ops.cache_attention import BLOCK_MAX as wide

    fits = chunk < wide < model.cfg.max_decode_len and wide % chunk == 0
    return wide if model.prefill_any_width and model.drafter is None and fits else None


def chunk_schedule(p: int, chunk: int, wide: Optional[int]) -> list[tuple[int, int]]:
    """The chunks a prompt of ``p`` tokens is prefilled in, as ``(start,
    width)``: in order, covering ``[0, p)`` once, only the last padded.
    Without a wide width (:func:`wide_chunk`) every chunk is ``chunk`` wide.
    With one, wide chunks from position 0 while at least ``wide`` tokens
    remain; what is left after them in narrow chunks, unless those would pad
    to a whole wide chunk's length: then one more wide chunk runs the same
    padded tokens with one read of the weights for several (on the chip a
    chunk of 512 took 12.81 ms and four of 128 took 4 x 4.10: PERF.md
    section 6, PR 47). So a prompt pads to the length it padded to with
    narrow chunks alone, and one shorter than that tail keeps the narrow
    schedule exactly."""
    if wide is None:
        return [(start, chunk) for start in range(0, p, chunk)]
    body = p - p % wide
    out, tail = [(start, wide) for start in range(0, body, wide)], range(body, p, chunk)
    if len(tail) * chunk == wide:
        return out + [(body, wide)]
    return out + [(start, chunk) for start in tail]


def padded_len(chunks: list) -> int:
    """Where a schedule's last chunk ends: the prompt's length with its pad."""
    start, width = chunks[-1]
    return start + width


@dataclasses.dataclass
class Request:
    id: str
    prompt: np.ndarray  # [p] int32 token ids
    max_new_tokens: int
    submit_time: float  # client wall clock (time.time())
    claim_time: float = 0.0  # stamped by ServingEngine.submit


@dataclasses.dataclass
class RequestResult:
    id: str
    prompt_len: int
    tokens: list[int]  # generated tokens (EOS kept if hit)
    ttft_s: float  # submit -> first token out of prefill
    admit_wait_s: float  # submit -> admission (queueing component)
    tpot_s: Optional[float]  # (finish - first token) / (n - 1)
    finish_time: float
    # ttft_s in its three parts (they sum to it):
    claim_wait_s: float = 0.0  # client's submit -> handed to the engine
    slot_wait_s: float = 0.0  # handed to the engine -> admitted to a slot
    prefill_s: float = 0.0  # admitted -> first token sampled


@dataclasses.dataclass
class _Slot:
    request: Request
    admit_time: float
    first_token_time: float
    pos: int  # position of the last accepted token
    remaining: int
    tokens: list[int]
    done: bool = False
    # A row whose prompt is not all queued yet: the padded prompt, its chunks
    # (:func:`chunk_schedule`) and how many of them are queued (more than
    # none and fewer than all only for a row part-way through its prompt,
    # ``ServingModel.holds``); ``buf`` is None once its head is queued.
    buf: Optional[np.ndarray] = None
    chunks: list = dataclasses.field(default_factory=list)
    queued: int = 0


class Programs(NamedTuple):
    """The engine's compiled programs (:func:`programs`)."""

    prefill_chunk: Callable
    prefill_chunk_head: Callable
    decode_block: Callable
    # The chunk's program at the wide width, where the model takes one
    # (:func:`wide_chunk`); None for every other model.
    prefill_chunk_wide: Optional[Callable] = None


def programs(model, *, slots: int, chunk: int, block: int, sample) -> Programs:
    """The engine's compiled programs over ``model`` (a
    ``models.serving.ServingModel``). ``sample`` maps (float32 logits
    [rows, V], key) to tokens [rows] (ops/sampling.py). A function of its
    own so that a test can compile the very programs the engine runs, for
    a chip that is described and not attached, from shapes."""
    import jax
    import jax.numpy as jnp

    L = model.cfg.max_decode_len
    add = functools.partial(jax.tree.map, jnp.add)

    def chunk_forward(width, params, cache, counts, slot, chunk_toks, start, n_real):
        pos = (start + jnp.arange(width, dtype=jnp.int32))[None, :]
        hidden, cache, added = model.prefill(params, cache, slot, chunk_toks, pos, n_real)
        return hidden, cache, add(counts, added)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def prefill_chunk(params, cache, counts, slot, chunk_toks, start, n_real=chunk):
        """One [1, chunk] prefill chunk into row ``slot`` of the batch
        cache, of which the first ``n_real`` tokens are the prompt's
        (slot/start/n_real are traced scalars: one program). The model
        writes the chunk's keys and values into the donated slabs where
        they belong and reads the row's filled prefix where it lies:
        nothing row-sized is copied out or back. Returns the final-norm
        hidden states [1, chunk, D] and runs no head: nobody reads the
        logits of a prompt's earlier chunks (:func:`prefill_chunk_head`
        is for its last). ``counts`` are the model's counters so far, to
        which this call's are added."""
        return chunk_forward(chunk, params, cache, counts, slot, chunk_toks, start, n_real)

    wide, prefill_chunk_wide = wide_chunk(model, chunk), None
    if wide is not None:

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def prefill_chunk_wide(params, cache, counts, slot, chunk_toks, start, n_real=wide):
            """:func:`prefill_chunk` at ``[1, wide]`` (:func:`wide_chunk`),
            for the body of a long prompt: the weights are read once for
            ``wide`` tokens. Of the hidden states it returns the ``chunk``
            of them that hold the last real token (``wide`` is a multiple
            of ``chunk``, so they start on a multiple of ``chunk`` as a
            narrow chunk does): whichever program ran a prompt's last
            chunk, the head's one program takes ``[1, chunk, D]`` and finds
            the prompt's last position at ``(p - 1) % chunk``."""
            hidden, cache, counts = chunk_forward(wide, params, cache, counts, slot, chunk_toks, start, n_real)
            at = (n_real - 1) // chunk * chunk
            return jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(a, at, chunk, axis=1), hidden), cache, counts

    def finish(params, cache, slot, h, position):
        if model.finish is not None:
            return model.finish(params, cache, slot, h, position)
        with jax.named_scope("head"):
            return model.logits(params, h)

    @functools.partial(jax.jit, donate_argnums=(3, 4))
    def prefill_chunk_head(params, cache, hidden, tok, pos, slot, p, key):
        """The end of an admission, once a prompt: the head on the last
        real position ONLY of the prompt's last chunk (the full [chunk, V]
        product costs as much as several transformer layers), the first
        token sampled from those float32 logits as a decode step samples
        (greedy is ``sample`` at temperature 0), and row ``slot`` of the
        donated ``tok`` / ``pos`` [slots] set to it and to the prompt's
        length ``p``, where ``decode_block`` writes its keys and values
        before attending (as ``make_generate``'s first scan step does).
        ``hidden`` is whatever pytree the model's prefill returned; the
        model's ``finish`` takes that one position of it to the logits and
        may read row ``slot`` of ``cache`` (not donated) to do so: a model
        whose prefill ran every layer has none, the head's product is all
        that is left, and the cache is no input of its program.
        Returns ``tok``, ``pos``, the token as a scalar (all the host reads of an
        admission) and the next key. A program of its own and not a
        second form of the chunk's: a second copy of the whole chunk
        program cost every run 2.3 s of set-up to load (PERF.md section
        6, PR 31), for one product a prompt. (``prefill_chunk_wide`` IS a
        second copy, and pays for itself: a long prompt's body runs a
        quarter more tokens a second through it, and its load is paid
        for by what the llama family's programs no longer spend on
        tracing their layers one by one: PERF.md section 6, PR 47.) Its
        name keeps ``prefill_chunk`` in it, by which the benchmark finds
        the prefill's programs."""
        with jax.named_scope("head"):
            at = (p - 1) % chunk
            h = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(a, at, 1, axis=1)[:, 0], hidden)
        logits = finish(params, cache, slot, h, p - 1)
        key, sub = jax.random.split(key)
        first = sample(logits, sub)[0]
        return tok.at[slot].set(first), pos.at[slot].set(p), first, key

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def decode_block(params, cache, counts, tok, pos, active, rng, steps, held=None):
        """``steps`` (a traced int32, at most ``block``) decode steps
        over all slots: tok/pos [slots] are each row's last accepted
        token and its position; parked rows (active=False) stand at
        position 0 whatever their last occupant left, so they do not
        hold up the bound of the model's cache attention, and
        re-write position 0 of their own empty row. ``held`` [slots]
        (given only for a model that ``holds``: every other model's
        program is as it was) are the rows part-way through their
        prompt: they stand at position -1, where the model's step
        moves nothing of theirs. Returns the
        sampled tokens [slots, block], of which the first ``steps``
        columns are written."""
        pos = jnp.where(active, pos, 0 if held is None else jnp.where(held, -1, 0))

        def step(i, carry):
            cache, counts, tok, pos, rng, toks = carry
            logits, cache, added = model.decode(
                params, cache, tok[:, None], pos[:, None]
            )
            rng, k = jax.random.split(rng)
            nxt = sample(logits, k)
            nxt = jnp.where(active, nxt, tok)
            pos = jnp.where(
                active, jnp.minimum(pos + 1, L - 1), pos
            )
            toks = jax.lax.dynamic_update_index_in_dim(toks, nxt, i, 0)
            return cache, add(counts, added), nxt, pos, rng, toks

        toks = jnp.zeros((block, slots), tok.dtype)
        cache, counts, tok, pos, rng, toks = jax.lax.fori_loop(
            0, steps, step, (cache, counts, tok, pos, rng, toks)
        )
        return toks.swapaxes(0, 1), cache, counts, tok, pos, rng

    if model.drafter is None:
        return Programs(prefill_chunk, prefill_chunk_head, decode_block, prefill_chunk_wide)
    return Programs(prefill_chunk, *_drafting_programs(model, finish, slots=slots, chunk=chunk, block=block, sample=sample))


def _drafting_programs(model, finish, *, slots: int, chunk: int, block: int, sample):
    """``prefill_chunk_head`` and ``decode_block`` for a model that drafts
    (``ServingModel.drafter``; the same names, by which the benchmark finds
    the programs in a trace). The row's state gains ``draft [slots]``, the
    drafter's token for the position after the row's; ``sample`` is greedy
    (the engine refuses a drafting model any other)."""
    import jax
    import jax.numpy as jnp

    L, drafter = model.cfg.max_decode_len, model.drafter
    add = functools.partial(jax.tree.map, jnp.add)

    @functools.partial(jax.jit, donate_argnums=(1, 3, 4, 5))
    def prefill_chunk_head(params, cache, hidden, tok, pos, draft, slot, p, key):
        """The end of an admission as the other models' program has it (the
        head on the prompt's last position, the first token sampled, the
        row's ``tok`` / ``pos`` set), and then the drafter on that position
        with the first token: it writes its own state there, so this program
        takes the cache donated, and leaves the row's first draft in the
        donated ``draft``."""
        with jax.named_scope("head"):
            at = (p - 1) % chunk
            h = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(a, at, 1, axis=1)[:, 0], hidden)
        logits = finish(params, cache, slot, h, p - 1)
        key, sub, sub2 = jax.random.split(key, 3)
        first = sample(logits, sub)
        draft_logits, cache = drafter.first(params, cache, slot, h, p - 1, first)
        first = first[0]
        return (cache, tok.at[slot].set(first), pos.at[slot].set(p),
                draft.at[slot].set(sample(draft_logits, sub2)[0]), first, key)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def decode_block(params, cache, counts, tok, pos, draft, active, rng, steps):
        """``steps`` verifying steps over all slots, on the device from end
        to end: each runs the row's last accepted token and its draft (at
        ``pos`` and ``pos + 1``) through the main stack, takes the stack's
        own choice after the first, accepts the draft iff it IS that choice
        and then takes the choice after the second too, lets the drafter
        leave the next draft, and advances the row by one or two. Parked
        rows stand at position 0 as in the other models' program. Returns
        the tokens ``[slots, block, 2]`` with how many of a step's two were
        delivered ``[slots, block]`` (1 or 2; 0 for a parked row), of which
        the first ``steps`` steps are written. (No ``held`` rows: no model
        that drafts holds a row, and the engine refuses one that says so.)"""
        pos = jnp.where(active, pos, 0)

        def step(i, carry):
            cache, counts, tok, pos, draft, rng, toks, took = carry
            at = jnp.minimum(jnp.stack([pos, pos + 1], axis=1), L - 1)
            logits, hidden, cache, added = drafter.verify(params, cache, jnp.stack([tok, draft], axis=1), at)
            rng, k, k2 = jax.random.split(rng, 3)
            chosen = sample(logits.reshape(2 * slots, -1), k).reshape(slots, 2).astype(tok.dtype)
            accepted = active & (chosen[:, 0] == draft)
            draft_logits, cache, more = drafter.draft(params, cache, hidden, chosen, at, accepted, active)
            n = jnp.where(active, 1 + accepted.astype(jnp.int32), 0)
            tok = jnp.where(active, jnp.where(accepted, chosen[:, 1], chosen[:, 0]), tok)
            draft = jnp.where(active, sample(draft_logits, k2).astype(tok.dtype), draft)
            pos = jnp.where(active, jnp.minimum(pos + n, L - 1), pos)
            toks = jax.lax.dynamic_update_index_in_dim(toks, chosen, i, 0)
            took = jax.lax.dynamic_update_index_in_dim(took, n, i, 0)
            return cache, add(add(counts, added), more), tok, pos, draft, rng, toks, took

        toks = jnp.zeros((block, slots, 2), tok.dtype)
        took = jnp.zeros((block, slots), jnp.int32)
        cache, counts, tok, pos, draft, rng, toks, took = jax.lax.fori_loop(
            0, steps, step, (cache, counts, tok, pos, draft, rng, toks, took)
        )
        return toks.swapaxes(0, 1), took.swapaxes(0, 1), cache, counts, tok, pos, draft, rng

    return prefill_chunk_head, decode_block


class ServingEngine:
    """Slot-based continuous batching over a model's decode stack.

    ``cfg`` must be a decode config (``decode=True``) of a family that
    serves (``cfg.serving_model()``, models/serving.py); ``params`` is that
    model's parameter tree (for the llama family possibly a quantized one,
    ops/quantize.py).
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        slots: int = 8,
        chunk: int = 64,
        block: int = 16,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        eos_token: Optional[int] = None,
        seed: int = 0,
    ):
        import jax
        import jax.numpy as jnp

        from ..ops.cache_attention import attended
        from ..ops.sampling import make_sampler, validate_sampling

        if not cfg.decode:
            raise ValueError("ServingEngine needs a decode=True config")
        model = cfg.serving_model()
        if chunk < 1 or block < 1 or slots < 1:
            raise ValueError("slots, chunk and block must be >= 1")
        if cfg.max_decode_len < chunk + 1:
            raise ValueError(
                f"max_decode_len {cfg.max_decode_len} too small for "
                f"chunk {chunk} (+1 parking slot)"
            )
        validate_sampling(temperature, top_k, top_p)
        if model.drafter is not None and temperature > 0:
            raise ValueError(
                "this model drafts (models.serving.Drafter) and the engine verifies a draft greedily: "
                f"temperature {temperature} is not served (speculative sampling is not implemented); use 0"
            )
        if model.drafter is not None and model.holds:
            raise ValueError("this model drafts and says it holds a row: the verifying step takes no held rows")
        self.model = model
        self.cfg = model.cfg
        self.slots = slots
        self.chunk = chunk
        self.block = block
        self.eos_token = eos_token
        self._params = params
        self._rng = jax.random.key(seed)
        self._first_key = jax.random.key(seed + 1)
        self._prefill_chunk, self._prefill_chunk_head, self._decode_block, prefill_chunk_wide = programs(
            model, slots=slots, chunk=chunk, block=block,
            sample=make_sampler(temperature, top_k, top_p),
        )
        self.wide = wide_chunk(model, chunk)
        self._jnp = jnp
        self._jax = jax
        self._attended = attended  # the cache attention's own rounding, for the counters
        self._cache = model.init_cache(slots, chunk)
        self._gauges = model.gauges(self._cache)
        # The model's own counters: one running sum a program on the
        # device, drained to the host's totals at ``stats()``.
        self._counts = self._zero_counts()
        self._model_n = jax.tree.map(lambda a: np.zeros(a.shape, np.int64), self._counts)
        self._tok = jnp.zeros((slots,), jnp.int32)
        self._pos = jnp.zeros((slots,), jnp.int32)
        # A model that drafts: each row's draft of the token after ``tok``.
        self._draft = jnp.zeros((slots,), jnp.int32) if model.drafter is not None else None
        self._slots: list[Optional[_Slot]] = [None] * slots
        # Admissions whose first token is still on the device, in order.
        self._unread: list[tuple[_Slot, object]] = []
        self._round_queued = False  # something is queued at this boundary already
        self._queue: deque[tuple[Request, list]] = deque()  # each with its prompt's chunk_schedule
        self.last_steps = 0  # steps of the newest decode dispatch
        # Latency/throughput accounting.
        self.completed: list[RequestResult] = []
        self._tpot_samples: list[float] = []
        self._clear_record()
        self._prefill_chunk_wide = None
        if self.wide is not None:
            # No request that warms an engine up need be long enough to
            # reach the wide program, and it must not be traced or loaded
            # inside a long prompt's admission, so it is made here, in line,
            # from shapes: lowered (~0.2 s: the model's layers are one
            # traced function) and compiled, or loaded from the compile
            # cache (~1.4 s for 24 layers). In line because every way of
            # doing it beside the serving thread was measured and was worse
            # (PERF.md section 6, PR 47). The shapes carry an array's place
            # only where it is committed to it, as ``jit`` takes it: the
            # compiled program's results are then committed or not as the
            # jitted narrow one's are, and the head's and the decode program
            # see one kind of argument, not a second kind and a second
            # compile later. What is called is the compiled program itself
            # (a jitted function called after its ``lower().compile()``
            # traces and loads again).
            shaped = functools.partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=a.sharding if getattr(a, "committed", False) else None))
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            self._prefill_chunk_wide = prefill_chunk_wide.lower(
                *shaped((params, self._cache, self._counts["prefill"])), scalar,
                jax.ShapeDtypeStruct((1, self.wide), jnp.int32), scalar, scalar,
            ).compile()

    def _clear_record(self) -> None:
        self._decode_wall = 0.0
        self._n = dict.fromkeys(_COUNTERS, 0)
        self._host_s = dict.fromkeys(SEGMENTS, 0.0)
        self._mark = self._reset_at = time.perf_counter()
        # The record as it stood at the newest arrival (``submit``): the
        # time, the counters, the clock's sums and the decode wall.
        self._fed = (self._reset_at, dict(self._n), dict(self._host_s), 0.0)

    # ---- the serving thread's clock ----

    def host_lap(self, segment: str) -> None:
        """Charge the serving thread's time since the previous lap to
        ``segment`` (one of :data:`SEGMENTS`). The engine laps its own
        phases; the serve loop calls this after each of its own
        (``poll``, ``submit``, ``respond``, ``report``, and ``idle`` for
        a sleep or a poll that found an idle engine nothing), so there
        is one clock and every second goes to one segment."""
        now = time.perf_counter()
        self._host_s[segment] += now - self._mark
        self._mark = now

    def _lap_to_dispatch(self) -> None:
        """Lap at a prompt's first chunk and at the decode dispatch: up to a
        boundary's first dispatch the device waited for the host
        (``admit_prep``); behind an admission the host queues work behind
        work (``dispatch``)."""
        self.host_lap("dispatch" if self._round_queued else "admit_prep")
        self._round_queued = True

    # ---- admission ----

    def submit(self, request: Request) -> None:
        p = int(np.asarray(request.prompt).shape[0])
        L = self.cfg.max_decode_len
        if p < 1:
            raise ValueError(f"{request.id}: empty prompt")
        if request.max_new_tokens < 1:
            # Admission would still emit the prefill's first token, and
            # a negative budget weakens the cache-budget inequality.
            raise ValueError(
                f"{request.id}: max_new_tokens "
                f"{request.max_new_tokens} must be >= 1"
            )
        # Valid stream cap (L-1 reserves the parking slot) AND the
        # padded prefill tail must stay inside the cache.
        chunks = chunk_schedule(p, self.chunk, self.wide)
        if p + request.max_new_tokens > L - 1 or padded_len(chunks) > L:
            raise ValueError(
                f"{request.id}: prompt {p} + max_new "
                f"{request.max_new_tokens} exceeds the cache budget "
                f"(max_decode_len {L}, 1 slot reserved)"
            )
        if not self.busy:
            self.host_lap("idle")  # work arrives: the idle stretch ends here
        request.claim_time = time.time()
        self._queue.append((request, chunks))
        self._fed = (time.perf_counter(), dict(self._n), dict(self._host_s), self._decode_wall)

    def _free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _begin(self, request: Request, chunks: list, slot: int) -> _Slot:
        """A prompt's row in ``slot``, none of its ``chunks`` (its
        :func:`chunk_schedule`) queued yet: the row's budget and position
        after its first token are the request's, and the admission's
        counters are the whole prompt's."""
        L = self.cfg.max_decode_len
        prompt = np.asarray(request.prompt, np.int32)
        p = prompt.shape[0]
        padded = padded_len(chunks)
        # A model that drafts gets each chunk with the token that follows it.
        buf = np.zeros((padded + (self._draft is not None),), np.int32)
        buf[:p] = prompt
        self._n["admitted"] += 1
        self._n["prefill_chunks"] += len(chunks)
        self._n["prefill_wide_chunks"] += sum(width != self.chunk for _, width in chunks)
        self._n["prefill_tokens"] += p
        self._n["prefill_pad_tokens"] += padded - p
        # What the admission's attention reads of the row's slabs: each
        # chunk up to its own end, unless the model says otherwise.
        reads = np.array([start + width for start, width in chunks])
        if self.model.slab_reads is not None:
            reads = self.model.slab_reads(reads, p)
        self._n["prefill_attended_positions"] += int(self._attended(reads, L).sum())
        # decode_block writes the first token's k/v at position p before
        # attending, exactly as make_generate's first scan step does.
        st = _Slot(
            request=request,
            admit_time=time.time(),
            first_token_time=0.0,  # stamped as its fence returns (_take_first)
            pos=p,
            remaining=request.max_new_tokens - 1,
            tokens=[],
            buf=buf,
            chunks=chunks,
        )
        self._slots[slot] = st
        return st

    def _admit(self, st: _Slot, slot: int, room: int) -> int:
        """Queue chunks of the prompt in ``slot`` from where it stands, and
        its head behind the last; read nothing back. All that is left of it,
        or, where that is more than ``room`` tokens and the model's decode
        step can hold the row meanwhile (``ServingModel.holds``), the whole
        chunks that fit ``room`` (at least one): the rest goes on at the
        next boundary. The first token's value stays on the device
        (``self._unread``) until the decode dispatch is queued behind it.
        Returns the tokens queued, pads counted."""
        p = len(st.request.prompt)
        part = st.chunks[st.queued:]
        if self.model.holds:
            # The whole chunks that fit the room, from the next on.
            fits = int(np.searchsorted(np.cumsum([width for _, width in part]), room, side="right"))
            part = part[: max(1, fits)]
        last = st.queued + len(part) == len(st.chunks)
        self._n["prefill_rounds"] += 1
        # Host values throughout: the dispatch moves what its program reads
        # (a family whose state is keys and values never gets ``n_real``).
        slot_ = np.int32(slot)
        with obs.span(
            "engine.admit", SPAN_CAT, rid=st.request.id, slot=slot, prompt_len=p, chunks=len(st.chunks),
            resumed=st.queued > 0,
        ):
            for i, (start, width) in enumerate(part):
                first = self._chunk(st, slot_, start, width, p, head=last and i == len(part) - 1, lap=i == 0)
        self.host_lap("dispatch")
        st.queued += len(part)
        if last:
            self._n["prefill_head_chunks"] += 1
            st.buf = None  # the row joins the decode dispatches
            self._unread.append((st, first))
        return sum(width for _, width in part)

    def _chunk(self, st: _Slot, slot_, start: int, width: int, p: int, *, head: bool, lap: bool):
        """One chunk's dispatch, and behind a prompt's last chunk its head's:
        returns the first token (on the device) where the head ran."""
        ahead, first = 0 if self._draft is None else 1, None
        n_real = min(width, p - start)
        with obs.span(
            "engine.prefill_dispatch", SPAN_CAT, start=start, slot=int(slot_), n_real=n_real, width=width, head=head,
            resumed=st.queued > 0,
        ):
            if lap:
                self._lap_to_dispatch()
            run = self._prefill_chunk if width == self.chunk else self._prefill_chunk_wide
            hidden, self._cache, self._counts["prefill"] = run(
                self._params, self._cache, self._counts["prefill"], slot_,
                st.buf[None, start : start + width + ahead], np.int32(start), np.int32(n_real),
            )
            if head and self._draft is None:
                # The last chunk's last VALID position (not the padded
                # tail) feeds the first token, and the program that
                # samples it sets the row's state: the head runs once a
                # prompt, queued behind that chunk.
                self._tok, self._pos, first, self._first_key = self._prefill_chunk_head(
                    self._params, self._cache, hidden, self._tok, self._pos, slot_,
                    np.int32(p), self._first_key,
                )
            elif head:
                # ... and, where the model drafts, leaves the row's first
                # draft there too (the drafter writes its state: the
                # cache goes in donated).
                (self._cache, self._tok, self._pos, self._draft, first,
                 self._first_key) = self._prefill_chunk_head(
                    self._params, self._cache, hidden, self._tok, self._pos, self._draft, slot_,
                    np.int32(p), self._first_key,
                )
        return first

    def _attended_over(self, deepest) -> int:
        """Slab positions a dispatch's walks read, from ``deepest [rows,
        steps]``, each active row's deepest query at each step: whole blocks
        up to it where the model's decode step reads per row, up to the
        deepest row's where it does not."""
        L = self.cfg.max_decode_len
        deepest = np.minimum(deepest, L - 1)
        if not self.model.decode_reads_per_row:
            deepest = np.broadcast_to(deepest.max(axis=0), deepest.shape)
        return int(self._attended(deepest + 1, L).sum())

    def _take_first(self) -> None:
        """Read each admission's first token, in admission order: the one
        thing the host learns late is a token that ends its request."""
        if not self._unread:
            return
        with obs.span("engine.first_token", SPAN_CAT, n=len(self._unread)):
            for st, first in self._unread:
                token = int(first)  # the fence: every chunk of the prompt has run
                st.first_token_time = time.time()
                st.tokens.append(token)
                st.done = st.remaining <= 0 or token == self.eos_token
        self.host_lap("first_token")
        self._unread.clear()

    def _accept_token(self, st: _Slot, slot: int, token: int) -> None:
        st.tokens.append(int(token))
        st.pos += 1
        st.remaining -= 1
        if st.remaining <= 0 or (
            self.eos_token is not None and token == self.eos_token
        ):
            st.done = True

    # ---- the engine iteration ----

    def step(self) -> list[RequestResult]:
        """One engine iteration: admit into free slots at this dispatch
        boundary, run one decode dispatch, harvest finished requests.
        Returns the requests completed this iteration."""
        from .. import faults

        # Fault-injection site: a ``fail_engine_step`` plan entry makes
        # this iteration raise InjectedFault — the serve loop's recovery
        # (abort_in_flight + error responses) is what chaos tests pin.
        faults.engine_step_check()
        with obs.span("engine.step", SPAN_CAT):
            return self._step()

    def _step(self) -> list[RequestResult]:
        # 1. Admission: every prompt's chunks and head are queued, none read.
        # A prompt begun at an earlier boundary goes on first (at most one
        # row is part-way at a time: only a round's first prompt is split).
        room = ADMIT_TOKENS
        self._round_queued = False
        for slot, st in enumerate(self._slots):
            if st is not None and st.buf is not None:
                room -= self._admit(st, slot, room)
        for slot in self._free_slots():
            if not self._queue:
                break
            if padded_len(self._queue[0][1]) > room and self._round_queued:
                break  # the rest of the queue at the next boundary, behind a decode dispatch
            room -= self._admit(self._begin(*self._queue.popleft(), slot), slot, room)
        self._n["admit_rounds"] += bool(self._unread)
        # Rows with budget left (a request of one token is finished by its
        # first and stays parked).
        active_rows = [
            i for i, s in enumerate(self._slots) if s is not None and s.remaining > 0 and s.buf is None
        ]
        if not active_rows:
            self._take_first()
            return self._harvest()
        # 2. One decode dispatch over the full slot batch, as long as the
        # rows' budgets and the free slots say (decode_steps), queued
        # behind this boundary's admissions.
        active = np.zeros((self.slots,), bool)
        active[active_rows] = True
        drafting = self._draft is not None
        # Rows part-way through their prompt, for a model that can hold one
        # (no other model's program has the argument).
        held = ()
        if self.model.holds:
            held = (np.array([s is not None and s.buf is not None for s in self._slots]),)
        steps, sized_by = decode_steps(
            [self._slots[i].remaining for i in active_rows],
            self.slots - len(active_rows), self.block, 2 if drafting else 1,
        )
        t0 = time.time()
        rows = len(active_rows)
        with obs.span(
            "engine.decode_dispatch", SPAN_CAT, rows=rows, steps=steps, sized_by=sized_by
        ):
            self._lap_to_dispatch()
            if drafting:
                (toks, took, self._cache, self._counts["decode"], self._tok, self._pos, self._draft,
                 self._rng) = self._decode_block(
                    self._params, self._cache, self._counts["decode"], self._tok,
                    self._pos, self._draft, active, self._rng, np.int32(steps),
                )
            else:
                (toks, self._cache, self._counts["decode"], self._tok, self._pos,
                 self._rng) = self._decode_block(
                    self._params, self._cache, self._counts["decode"], self._tok,
                    self._pos, active, self._rng, np.int32(steps), *held,
                )
        self.host_lap("dispatch")
        # From here to the decode fence the device has the dispatch to run.
        if self._unread:
            self._n["decode_behind_admit"] += 1
            self._take_first()
            t0 = time.time()  # the dispatch starts where the last head ended
        # What each step's attention reads of an active row: the blocks up
        # to the row's own position where the model's decode step reads per
        # row, else up to the deepest active row's; the program finds either
        # from the same positions. Where the model drafts, a step's deepest
        # query stands one position further and the rows advance on the
        # device by one or two: what the host can say before the fence is
        # the FLOOR (no draft accepted), which is what the fence's span
        # carries; the counters take what the device did, after the fence.
        L = self.cfg.max_decode_len
        depths = np.array([self._slots[i].pos for i in active_rows])
        first_live = int(depths.sum()) + rows  # cache positions live at the dispatch's first step
        attended = self._attended_over(depths[:, None] + np.arange(steps) + drafting)
        if not drafting:
            self._n["decode_attended_positions"] += attended
        self.last_steps = steps
        self._n["decode_blocks"] += 1
        self._n["decode_steps"] += steps
        self._n["slot_blocks_occupied"] += rows
        self._n["decode_row_steps"] += rows * steps
        self.host_lap("overlapped")
        with obs.span(
            "engine.decode_fence", SPAN_CAT, rows=rows, steps=steps, live=first_live, attended=attended
        ):
            # Device fence: the dispatch is the unit.
            toks = np.asarray(toks)[:, :steps]
        self.host_lap("decode_fence")
        wall = time.time() - t0
        live = 0
        said = {}
        if drafting:
            # From the dispatch's own tallies: where each row stood at each
            # step, so what its two queries had live and what the walk read.
            took = np.asarray(took)[active_rows, :steps]
            deepest = np.minimum(depths[:, None] + np.cumsum(took, axis=1) - took + 1, L - 1)
            attended = self._attended_over(deepest)
            self._n["decode_attended_positions"] += attended
            self._n["decode_live_positions"] += int(deepest.sum()) + rows * steps
            said = dict(drafted=rows * steps, accepted=int((took == 2).sum()), attended=attended)
            # A step's tokens in order, those it delivered: [steps, 2] -> the row's stream.
            toks = [toks[i][np.arange(2) < took[r][:, None]] for r, i in enumerate(active_rows)]
        else:
            toks = toks[active_rows]
        with obs.span("engine.accept", SPAN_CAT, **said):
            for i, stream in zip(active_rows, toks):
                st = self._slots[i]
                accepted, live0 = 0, st.pos + 1  # cache positions live at the row's first step
                for t in stream:
                    if st.done:
                        break  # a token past the row's budget (a step's second) is dropped
                    self._accept_token(st, i, t)
                    accepted += 1
                if accepted:
                    # Per-REQUEST experienced latency: every occupied slot
                    # waited the whole dispatch's wall for its `accepted`
                    # tokens (concurrent slots don't divide a request's
                    # wait — aggregating wall/total_tokens would understate
                    # tpot by the concurrency factor).
                    self._tpot_samples.append(wall / accepted)
                    if not drafting:
                        self._n["decode_live_positions"] += (
                            accepted * live0 + accepted * (accepted - 1) // 2
                        )
                live += accepted
        if live:
            self._n["decode_tokens"] += live
            self._decode_wall += wall
        self.host_lap("accept")
        return self._harvest()

    def _harvest(self) -> list[RequestResult]:
        out = []
        with obs.span("engine.harvest", SPAN_CAT):
            for i, st in enumerate(self._slots):
                if st is None or not st.done:
                    continue
                now = time.time()
                n = len(st.tokens)
                req = st.request
                out.append(
                    RequestResult(
                        id=req.id,
                        prompt_len=int(np.asarray(req.prompt).shape[0]),
                        tokens=st.tokens,
                        ttft_s=st.first_token_time - req.submit_time,
                        admit_wait_s=st.admit_time - req.submit_time,
                        tpot_s=(
                            (now - st.first_token_time) / (n - 1)
                            if n > 1
                            else None
                        ),
                        finish_time=now,
                        claim_wait_s=req.claim_time - req.submit_time,
                        slot_wait_s=st.admit_time - req.claim_time,
                        prefill_s=st.first_token_time - st.admit_time,
                    )
                )
                # The request's hops, for `tpujob why` and `tpujob trace
                # --request` (file spans only: explicit endpoints).
                serve_span(
                    "slot_wait", req.claim_time,
                    st.admit_time - req.claim_time, rid=req.id,
                )
                serve_span(
                    "decode", st.admit_time, now - st.admit_time,
                    rid=req.id, tokens=n,
                )
                self._slots[i] = None  # the slot is free for the next admit
        self.completed.extend(out)
        self.host_lap("harvest")
        return out

    def abort_in_flight(self) -> list[str]:
        """Failure-path hardening: evict every occupied slot and return
        the aborted request ids (the serve loop answers each with an
        error response — exactly-once, never a silent drop). Queued
        requests stay queued. Safe without cache surgery: admission
        prefills a row in full before any decode reads it, so a freed
        slot's stale k/v can never leak into a later request. A row
        part-way through its prompt goes with the rest: the chunks it
        has queued run for nothing, and the slot's next occupant starts
        at position 0, from zero state."""
        aborted = []
        for i, st in enumerate(self._slots):
            if st is not None:
                aborted.append(st.request.id)
                self._slots[i] = None
        self._unread.clear()
        return aborted

    @property
    def queued(self) -> int:
        """Requests admitted to the engine but not yet in a slot."""
        return len(self._queue)

    @property
    def slots_free(self) -> int:
        """Unoccupied cache slots — the serve-plane load beat's
        headroom signal (rendezvous.report_serve)."""
        return sum(1 for s in self._slots if s is None)

    @property
    def busy(self) -> bool:
        return bool(self._queue) or any(
            s is not None for s in self._slots
        )

    def run_until_drained(self, max_iters: int = 10_000):
        """Drive step() until queue and slots are empty (test/bench
        helper; the serve workload loops step() itself to interleave
        spool polling)."""
        out = []
        for _ in range(max_iters):
            if not self.busy:
                return out
            out.extend(self.step())
        raise RuntimeError("engine did not drain")

    def _zero_counts(self) -> dict:
        zeros = lambda: self._jax.tree.map(self._jnp.zeros_like, self.model.counts)
        return {"prefill": zeros(), "decode": zeros()}

    def _drain_counts(self) -> None:
        """Move the model's device counters into the host's totals and
        start them again at zero (so the int32 sums on the device only
        ever hold what one reporting interval added). A model without
        counters costs nothing here."""
        if not self.model.counts:
            return
        got = self._jax.device_get(self._counts)
        self._model_n = self._jax.tree.map(
            lambda total, new: total + np.asarray(new, np.int64), self._model_n, got
        )
        self._counts = self._zero_counts()

    def reset_stats(self) -> None:
        """Clear the latency/throughput accumulators (benches call this
        after compile-warmup requests so percentiles reflect steady
        state, not XLA compilation)."""
        self._drain_counts()
        self._model_n = self._jax.tree.map(np.zeros_like, self._model_n)
        self.completed.clear()
        self._tpot_samples.clear()
        self._clear_record()

    def _derived(self, n: dict, host: dict, decode_wall: float) -> dict:
        """What ``stats()`` computes from a record's counters, its clock's
        sums and its decode wall: of the whole record, and of the record as
        it stood at the newest arrival."""

        def share(part, whole):
            return round(100.0 * part / whole, 3) if whole else None

        return {
            "decode_tokens_per_sec": round(n["decode_tokens"] / decode_wall, 1) if decode_wall else None,
            # Rows that held a request, of the rows the dispatches ran;
            # tokens accepted, of the steps those rows ran; and the mean
            # length of a dispatch.
            "slot_occupancy_pct": share(n["slot_blocks_occupied"], n["decode_blocks"] * self.slots),
            "decode_yield_pct": share(n["decode_tokens"], n["decode_row_steps"]),
            "decode_steps_per_block": round(n["decode_steps"] / n["decode_blocks"], 3)
            if n["decode_blocks"]
            else None,
            # Pad positions of each prompt's last chunk, of the positions
            # the prefill program ran.
            "prefill_pad_pct": share(
                n["prefill_pad_tokens"], n["prefill_tokens"] + n["prefill_pad_tokens"]
            ),
            # The host gap is the time the device waited for the host with
            # work queued.
            "host_gap_s": sum(host[k] for k in GAP_SEGMENTS),
        }

    def stats(self) -> dict:
        """Aggregate latency/throughput record (the bench block)."""
        done = self.completed
        ttft = sorted(r.ttft_s for r in done)
        tpot = sorted(self._tpot_samples)

        def pct(xs, q):
            if not xs:
                return None
            i = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
            return round(1000 * xs[i], 3)

        n, host = self._n, self._host_s
        self._drain_counts()
        plain = lambda v: int(v) if np.ndim(v) == 0 else [int(x) for x in v]
        model_n = self._jax.tree.map(np.add, self._model_n["prefill"], self._model_n["decode"])
        # The record as it stood at the newest arrival, by the same
        # expressions: while requests arrive it trails the whole by one
        # boundary; once they stop, the whole runs on through the emptying
        # of the slots and this one does not.
        fed_at, fed_n, fed_host, fed_wall = self._fed
        fed = self._derived(fed_n, fed_host, fed_wall)

        return {
            "requests": len(done),
            "generated_tokens": sum(len(r.tokens) for r in done),
            "ttft_ms_p50": pct(ttft, 0.50),
            "ttft_ms_p99": pct(ttft, 0.99),
            "tpot_ms_p50": pct(tpot, 0.50),
            "tpot_ms_p99": pct(tpot, 0.99),
            "slots": self.slots,
            "block": self.block,
            "chunk": self.chunk,
            **n,
            **self._derived(n, host, self._decode_wall),
            # The serving thread's seconds by segment: the gap's parts are
            # ``host_gap_<segment>_s``, so a reader finds them by the key
            # and keeps no list of its own.
            **{host_key(k): v for k, v in host.items()},
            "fed_s": fed_at - self._reset_at,
            **{f"fed_{k}": fed_n[k] for k in ("decode_blocks", "decode_steps", "decode_tokens")},
            **{f"fed_{k}": fed[k] for k in (
                "slot_occupancy_pct", "decode_yield_pct", "decode_tokens_per_sec", "host_gap_s")},
            # The model's own: its gauges, its counters over both programs
            # and over the decode program alone, and what it derives.
            **self._gauges,
            **{k: plain(v) for k, v in model_n.items()},
            **{f"decode_{k}": plain(v) for k, v in self._model_n["decode"].items()},
            **(self.model.derive(model_n) if self.model.counts else {}),
        }
