"""What the serving stack asks of a model, and the presets by name.

``serving/engine.py``, ``workloads/serve.py`` and
``workloads/generate.load_params`` talk to a model through
:class:`ServingModel` alone: its config, its seeded init, its cache
constructor and its two forwards. A config class states its family by
having ``serving_model()`` (``models.llama.LlamaConfig``,
``models.mimo_v2.MiMoV2Config`` (MiMo-V2.5 and K-EXAONE), ``models.nemotron_h.NemotronHConfig``,
``models.phi4_flash.Phi4FlashConfig``, ``models.jamba.JambaConfig``); a job's ``--config`` names a preset, and
:func:`preset` finds the family that has it. Nothing else selects a path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional


def _nothing(*_):
    return {}


@dataclasses.dataclass(frozen=True)
class Drafter:
    """What a model that DRAFTS gives the engine beside its forwards
    (``ServingModel.drafter``): a decode step then runs TWO positions a row
    through the main stack (the row's last accepted token and the draft of
    the next), accepts the draft iff it is the main stack's own choice,
    yields one or two tokens, and leaves the next draft in the row's state.
    What is delivered is, token for token, what ``decode`` alone delivers;
    greedy only. ``prefill`` of such a model gets each chunk's tokens with
    the ONE that follows them (``[1, chunk + 1]``; past the prompt's end a
    pad) and fills the drafter's own state from them; ``hidden`` is whatever
    ``finish`` and ``first`` take one token of."""

    # (params, cache, tokens [slots, 2], positions [slots, 2]) -> (float32
    # logits [slots, 2, V], hidden (any pytree of [slots, 2, ...] leaves),
    # cache, counts): the main stack, both positions written to its cache.
    verify: Callable
    # (params, cache, hidden, chosen [slots, 2] (the main stack's choice
    # after each position), positions [slots, 2], accepted [slots] bool,
    # live [slots] bool (rows that hold a request)) -> (float32 draft
    # logits [slots, V]: the token after next at the LAST POSITION KEPT, the
    # second where ``accepted``; cache; counts). A rejected position's state
    # is overwritten by the next step's write before anything reads it.
    draft: Callable
    # The end of an admission: (params, cache, slot, h (``hidden`` at the
    # prompt's last token), position, first [1] (the sampled first token))
    # -> (float32 draft logits [1, V], cache): the row's first draft.
    first: Callable


@dataclasses.dataclass(frozen=True)
class ServingModel:
    """A model as the engine sees it. ``cache`` is the model's own state
    object: any pytree whose every leaf leads with the slot axis; what the
    leaves are is the model's business. The engine donates the whole of it
    to both forwards and never takes a row out itself: ``prefill`` is told
    which slot its one row of tokens lives in and leaves every other
    slot's leaves as they were (the llama family writes the chunk's keys
    and values in place and reads that row's filled prefix where it lies;
    the layer-pattern family slices its small row out and writes it back,
    inside its own forward; the hybrid family keeps a constant-size
    recurrent state a row beside its slabs; the decoder-hybrid-decoder
    family has layers that own no leaf and read another layer's). ``counts`` are
    the model's own device counters at zero (a pytree of int32 arrays, ``{}``
    for none): each forward returns what one call adds, the engine's two
    programs sum them on the device, and ``ServingEngine.stats()`` brings
    them back."""

    cfg: Any  # vocab_size, max_decode_len, decode
    # key -> the serving parameter tree, on the device in the serving dtype.
    init_params: Callable
    # (slots, chunk) -> cache.
    init_cache: Callable
    # (params, cache, slot (a traced int32 scalar), tokens [1, chunk],
    # positions [1, chunk], n_real (a traced int32 scalar: the first
    # ``n_real`` tokens are the prompt's, the rest the last chunk's pad))
    # -> (hidden, cache, counts): one call shape for every family (a model
    # that drafts gets one token more: :class:`Drafter`).
    # ``hidden`` is the final-norm hidden [1, chunk, D], or any pytree of
    # [1, chunk, ...] leaves that the family's ``finish`` takes one token
    # of. State that grows with position (keys and values)
    # may take the pads in: every later read masks them by position or
    # overwrites them. State that is a recurrence must stop at the last
    # real token, and start from zero where the chunk stands at position 0
    # (the slot's last occupant left its own there).
    prefill: Callable
    # (params, cache, tokens [slots, 1], positions [slots, 1]), every row
    # at its own position -> (float32 logits [slots, V], cache, counts).
    decode: Callable
    # (params, hidden [n, D]) -> float32 logits [n, V].
    logits: Callable
    # The end of an admission, once a prompt: (params, cache (read only),
    # slot, h (``hidden`` at the prompt's last token: [1, ...] leaves),
    # position (that token's)) -> float32 logits [1, V]. None = ``logits
    # (params, h)``: every layer ran in ``prefill``. A family whose later
    # layers write no state runs them here, for the one token whose logits
    # anybody reads, against the slot's cache.
    finish: Optional[Callable] = None
    # None = a decode step yields one token a row. A model that drafts
    # states how here (:class:`Drafter`); nothing else selects the path.
    drafter: Optional[Drafter] = None
    # What an admission's programs attend of the full-length slabs, for the
    # engine's ``prefill_attended_positions``: (each chunk's last position
    # + 1 (an integer array), the prompt's length) -> the positions each
    # read needs, an integer array. None = each chunk reads up to its own
    # end.
    slab_reads: Optional[Callable] = None
    # Whether a decode step (one query a row, or a verifying step's two)
    # reads each row's slabs to that row's own depth or every row's to the
    # deepest row's, for the engine's ``decode_attended_positions``: what
    # ``ops.cache_attention.reads_per_row`` answers for the model's slabs
    # (the one statement of the condition is there).
    decode_reads_per_row: bool = False
    # Whether ``decode`` leaves a row whose position is NEGATIVE untouched:
    # no leaf of its cache moves (a recurrence's state and tail stay; keys
    # and values go to the parking position ``max_decode_len - 1``). Only
    # for such a model does the engine stop a prompt's prefill at a chunk's
    # end and go on at the next boundary, its row held at -1 through the
    # decode dispatches between (``serving/engine.py``); for every other a
    # boundary admits a prompt whole, whatever its length.
    holds: bool = False
    # Whether ``prefill`` takes a chunk of ANY width up to the engine's wide
    # width (``serving/engine.py:wide_chunk``: the cache attention's block)
    # at the cost a token it has at the configuration's ``chunk``, and
    # ``init_cache`` makes the same cache whatever ``chunk`` it is given.
    # Only for such a model does the engine build a second chunk program and
    # prefill a long prompt's body through it, the weights read once per
    # wide chunk; every other model's programs and schedules are what they
    # were. The llama family says so (its cache is slabs alone and its chunk
    # attends through ``cache_attention(..., slot=)``, general in the
    # chunk's width). What the others would need first: the ring families
    # (``mimo_v2``, ``phi4_flash``) size a window layer's ring ``window +
    # chunk``, so rings sized for the wide width (more memory, and a decode
    # step reads rings whole) or a chunk that wraps its ring in sub-chunks;
    # ``nemotron_h``'s ``scan_chunk`` is quadratic in the chunk (a ``[G, Hg,
    # S, S]`` decay in float32 products), so a scan that walks a wide chunk
    # in sub-chunks; ``jamba``'s cells state 512 already; a model that
    # drafts gets each chunk with the token that follows it, and the engine
    # builds no wide program for one (``wide_chunk``) until one asks.
    prefill_any_width: bool = False
    # a checkpoint's parameter tree (the trainer's form, host arrays) ->
    # the same leaves as ``init_params`` arranges them, which is how the
    # forwards read them fastest (``workloads.generate.load_params`` calls
    # it on what it restores). Plain indexing, no copy on the host.
    arrange: Callable = lambda params: params
    counts: Any = dataclasses.field(default_factory=dict)
    # cache -> {gauge: number}, read once (shapes, not values).
    gauges: Callable = _nothing
    # {counter: host total} -> {derived statistic: number}.
    derive: Callable = _nothing


def families() -> dict:
    """``preset name -> (model module, name of the function that makes its
    config)``, read at call time: a family's table is a module dict that a
    caller may add a preset to (the benchmark's ``bench``)."""
    from . import jamba, llama, mimo_v2, nemotron_h, phi4_flash

    return {
        name: (module, fn)
        for module in (llama, mimo_v2, nemotron_h, phi4_flash, jamba)
        for name, fn in module.CONFIGS.items()
    }


def preset(name: str, **over):
    """The config of preset ``name`` with ``over`` (the server's
    ``decode=True``, ``max_decode_len`` ...)."""
    table = families()
    if name not in table:
        raise ValueError(f"no preset {name!r} (has {sorted(table)})")
    module, fn = table[name]
    return getattr(module, fn)(**over)
