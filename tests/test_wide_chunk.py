"""PR 47: a long prompt's body is prefilled in wide chunks where the model
says it takes one (``ServingModel.prefill_any_width``: the llama family).

- the schedule (``serving.engine.chunk_schedule``) on both sides of every
  threshold, and the engine's counters and spans against it;
- the same tokens and the same logits, to float32 rounding, with the wide
  program and without, whichever program ran a prompt's last chunk;
- the wide program is ready before a prompt needs it: its first dispatch
  compiles and loads nothing;
- a family without the member has no wide program, its schedule is
  ``range(0, padded, chunk)`` and its three programs lower to the text they
  lowered to at the parent commit (``tests/lowerings.py``).

The tiny engines run a wide width of 32 over chunks of 8: the width is the
cache attention's ``BLOCK_MAX``, which the tests set as the chip sweep tool
does (``benchmark/tools/sweep_chunk_block.py``); a slab of 272 is read in
blocks of 34 either way.
"""

from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from pytorch_operator_tpu.models import llama as llama_lib
from pytorch_operator_tpu.obs import trace as obs_trace
from pytorch_operator_tpu.ops import cache_attention
from pytorch_operator_tpu.runtime import backend
from pytorch_operator_tpu.serving import Request, ServingEngine
from pytorch_operator_tpu.serving import engine as engine_lib
from pytorch_operator_tpu.serving.engine import chunk_schedule, padded_len, wide_chunk
from tests import lowerings

CHUNK, WIDE, LENGTH = 8, 32, 272
# Prompt lengths on both sides of every threshold of the tiny engine's schedule: one token; a narrow chunk; the wide
# width -1, +0, +1; a tail of three narrow chunks (kept narrow) and of one token more (four narrow chunks pad to a
# wide chunk's length: one wide chunk); three wide chunks and a padded fourth; the longest prompt the slab takes.
LENGTHS = [1, CHUNK, WIDE - 1, WIDE, WIDE + 1, WIDE + 3 * CHUNK, WIDE + 3 * CHUNK + 1, 3 * WIDE + 27, 8 * WIDE + 4]


def _checked(p, chunk, wide):
    """The schedule of a prompt, held to what every schedule must be."""
    chunks = chunk_schedule(p, chunk, wide)
    starts, widths = zip(*chunks)
    assert starts[0] == 0 and all(s + w == nxt for (s, w), nxt in zip(chunks, starts[1:]))  # in order, no gap, no overlap
    assert starts[-1] < p <= padded_len(chunks)  # pads in the last chunk alone
    assert set(widths) <= {chunk, wide} and all(s % w == 0 for s, w in chunks)
    # wide chunks first: a narrow chunk is followed by narrow chunks only
    assert list(widths) == sorted(widths, key=lambda w: w != wide)
    return chunks


@pytest.mark.parametrize("chunk, wide, limit", [(8, 32, 272), (128, 512, 4096), (64, 512, 2048), (256, 512, 4000)])
def test_a_schedule_covers_the_prompt_once_in_order_and_pads_only_its_last_chunk(chunk, wide, limit):
    crossover = wide // chunk  # the narrow chunks whose padded length is a wide chunk's
    edges = {1, chunk, chunk + 1, wide - 1, wide, wide + 1, wide + (crossover - 1) * chunk, wide + (crossover - 1) * chunk + 1,
             2 * wide, 5 * wide + 3, limit - wide + 1, limit - chunk, limit - 2}
    for p in sorted(e for e in edges if 0 < e < limit):
        chunks = _checked(p, chunk, wide)
        body, tail = p // wide, -(-(p % wide) // chunk)
        assert [w for _, w in chunks[:body]] == [wide] * body  # wide while a wide chunk's worth of real tokens remains
        assert len(chunks) == body + (1 if tail == crossover else tail), (p, chunks)
        # ... which pads what narrow chunks pad: a prompt fits the slab with a wide width if it did without; and
        # without one, today's schedule exactly.
        narrow = chunk_schedule(p, chunk, None)
        assert narrow == [(s, chunk) for s in range(0, -(-p // chunk) * chunk, chunk)] and padded_len(chunks) == padded_len(narrow)


def test_the_rule_of_the_tail_is_the_chips_at_the_cells_sizes():
    """InternLM2's cells (chunks of 128, wide 512): three narrow chunks cost less than one wide one, four do not
    (12.3 / 16.4 ms against 12.81 on the chip: PERF.md section 6, PR 47)."""
    assert chunk_schedule(512 + 384, 128, 512) == [(0, 512), (512, 128), (640, 128), (768, 128)]
    assert chunk_schedule(512 + 385, 128, 512) == [(0, 512), (512, 512)]
    assert chunk_schedule(511, 128, 512) == [(0, 512)] and chunk_schedule(384, 128, 512) == [(0, 128), (128, 128), (256, 128)]
    # chat's median prompt (256) and every reasoning cell's keep the narrow schedule
    assert chunk_schedule(256, 128, 512) == [(0, 128), (128, 128)]


@pytest.fixture(scope="module", params=["plain", "int8"])
def model(request):
    import flax.linen as nn
    import jax

    cfg = llama_lib.llama_tiny(decode=True, max_decode_len=LENGTH, kv_quantize=None if request.param == "plain" else "int8")
    params = nn.meta.unbox(
        llama_lib.Llama(dataclasses.replace(cfg, decode=False)).init(jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    )
    return cfg, params


@pytest.fixture
def wide(monkeypatch):
    monkeypatch.setattr(cache_attention, "BLOCK_MAX", WIDE)


@pytest.fixture
def kept(monkeypatch):
    """Every logits array the engine's programs sample from, in the device's order (as ``tests/test_jamba.py``)."""
    import jax
    import jax.numpy as jnp

    from pytorch_operator_tpu.ops import sampling

    seen = []

    def make_sampler(*_):
        def sample(logits, key):
            jax.debug.callback(lambda a: seen.append(np.asarray(a)), logits, ordered=True)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return sample

    monkeypatch.setattr(sampling, "make_sampler", make_sampler)
    return seen


def _prompt(p, seed=0):
    return np.random.default_rng(seed + p).integers(0, 256, (p,)).astype(np.int32)


def _serve(model, lengths, new=5, **engine):
    eng = ServingEngine(*model, **{"slots": 2, "chunk": CHUNK, "block": 4, **engine})
    for i, p in enumerate(lengths):
        eng.submit(Request(id=f"r{i}", prompt=_prompt(p), max_new_tokens=new, submit_time=time.time()))
    done = {r.id: r.tokens for r in eng.run_until_drained()}
    return [done[f"r{i}"] for i in range(len(lengths))], eng


@pytest.fixture
def traced_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(obs_trace.ENV_VAR, str(tmp_path / "trace"))
    obs_trace.reset_tracer()
    yield tmp_path / "trace"
    monkeypatch.delenv(obs_trace.ENV_VAR, raising=False)
    obs_trace.reset_tracer()


@pytest.mark.parametrize("p", LENGTHS)
def test_the_counters_and_the_spans_say_what_the_schedule_says(model, wide, traced_dir, p):
    chunks = _checked(p, CHUNK, WIDE)
    _, eng = _serve(model, [p], new=3)
    assert eng.wide == WIDE
    n = eng.stats()
    assert n["prefill_chunks"] == len(chunks) and n["prefill_wide_chunks"] == sum(w == WIDE for _, w in chunks)
    assert n["prefill_tokens"] == p and n["prefill_pad_tokens"] == padded_len(chunks) - p
    assert n["prefill_attended_positions"] == sum(int(cache_attention.attended(s + w, LENGTH)) for s, w in chunks)
    assert n["prefill_head_chunks"] == n["admitted"] == 1
    rec = obs_trace.tracer()
    rec.flush()
    spans = [e["args"] for e in obs_trace.load_span_file(rec.path) if e["ph"] == "X" and e["name"] == "engine.prefill_dispatch"]
    assert [(c["start"], c["width"]) for c in spans] == chunks
    assert [c["n_real"] for c in spans] == [min(w, p - s) for s, w in chunks] and [c["head"] for c in spans][-1] is True
    assert sum(c["width"] - c["n_real"] for c in spans) == n["prefill_pad_tokens"]
    if p == 8 * WIDE + 4:  # eight wide chunks and a narrow one, to the slab's last but one chunk
        assert chunks[-1] == (8 * WIDE, CHUNK) and padded_len(chunks) == 8 * WIDE + CHUNK <= LENGTH


# A prompt whose last chunk is wide and full, is narrow (behind wide ones), is a padded wide one (the head reads the
# window of its hidden states that holds the last real token) with the most pad and the least, and a short prompt.
LAST_CHUNKS = {"wide": 2 * WIDE, "narrow": 2 * WIDE + 5, "padded_wide": 2 * WIDE + 25, "padded_wide_late": 3 * WIDE - 1, "short": 7}


@pytest.mark.parametrize("last", sorted(LAST_CHUNKS))
def test_the_wide_chunk_serves_the_same_tokens_and_logits(model, monkeypatch, kept, last):
    """Greedy tokens equal; the first token's logits (the head on the prompt's last position, whichever chunk program
    made its hidden states) and every decode step's within the order of float32 sums: the tiny model computes in
    float32 (an int8 cache rounds keys and values alike on both sides, the same values written either way)."""
    import jax

    p = LAST_CHUNKS[last]
    want, narrow = _serve(model, [p])
    jax.effects_barrier()
    want_logits = [np.array(a) for a in kept]
    del kept[:]
    monkeypatch.setattr(cache_attention, "BLOCK_MAX", WIDE)
    got, eng = _serve(model, [p])
    jax.effects_barrier()
    assert narrow.wide is None and eng.wide == WIDE and got == want
    assert narrow.stats()["prefill_wide_chunks"] == 0 and (eng.stats()["prefill_wide_chunks"] > 0) == (p >= WIDE)
    assert len(kept) == len(want_logits) and kept[0].shape[0] == 1  # the head's [1, V] first
    for a, b in zip(kept, want_logits):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(1.0, float(np.abs(b).max())))


def test_rows_served_together_through_both_programs_get_their_own_tokens(model, wide):
    """Several prompts a boundary, wide and narrow chunks interleaved into different slots: each request's tokens are
    what it gets alone through narrow chunks (the wide program writes its own row of the donated cache only)."""
    lengths = [WIDE + 3, 5, 3 * WIDE, 2 * WIDE + CHUNK + 1, 9]
    together, eng = _serve(model, lengths, slots=3)
    assert 0 < eng.stats()["prefill_wide_chunks"] < eng.stats()["prefill_chunks"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cache_attention, "BLOCK_MAX", 512)
        alone = [_serve(model, [p])[0][0] for p in lengths]
    assert together == alone


def test_the_wide_program_is_ready_before_its_first_dispatch(model, wide):
    """The engine compiles (or loads) the wide program when it is made, from shapes; a short request then brings the
    other three in. From there the first long prompt, whose body goes through the wide program, compiles nothing and
    loads nothing: the compile cache is asked for no program, so the first wide dispatch costs what the later ones do,
    and no program met a second kind of argument (the compiled program hands on results of the kind the jitted ones
    do: uncommitted here, as a seeded init's weights are)."""
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()  # asked anew whether it is on: a compile test of this process had it off for a while
    eng = ServingEngine(*model, slots=2, chunk=CHUNK, block=4)
    assert "prefill_chunk_wide" in eng._prefill_chunk_wide.as_text()[:200]
    eng.submit(Request(id="short", prompt=_prompt(5), max_new_tokens=9, submit_time=time.time()))
    eng.run_until_drained()
    assert eng.stats()["prefill_wide_chunks"] == 0
    before = backend.compile_counts()
    eng.submit(Request(id="long", prompt=_prompt(3 * WIDE + 27), max_new_tokens=9, submit_time=time.time()))
    eng.submit(Request(id="short2", prompt=_prompt(7), max_new_tokens=3, submit_time=time.time()))
    assert [len(r.tokens) for r in eng.run_until_drained()] == [3, 9]
    assert eng.stats()["prefill_wide_chunks"] == 4 and backend.compile_counts() == before and sum(before.values()) > 0


def test_a_prompt_is_admitted_or_refused_by_the_budget_as_without_a_wide_width(model, wide):
    """A wide tail pads what its narrow chunks would, so the cache-budget check says what it said."""
    eng = ServingEngine(*model, slots=2, chunk=CHUNK, block=4)
    eng.submit(Request(id="fits", prompt=_prompt(8 * WIDE + 4), max_new_tokens=3, submit_time=time.time()))  # 264 <= 272
    with pytest.raises(ValueError, match="exceeds the cache budget"):
        eng.submit(Request(id="no", prompt=_prompt(LENGTH - 2), max_new_tokens=3, submit_time=time.time()))
    assert [len(r.tokens) for r in eng.run_until_drained()] == [3]


# ---- the families that take no wide chunk ----


def test_only_a_model_that_says_so_gets_a_wide_program(model, wide):
    from pytorch_operator_tpu.ops.sampling import make_sampler

    llama = model[0].serving_model()
    assert llama.prefill_any_width and wide_chunk(llama, CHUNK) == WIDE
    assert wide_chunk(llama, WIDE) is None and wide_chunk(llama, 12) is None  # already wide; does not divide it
    assert wide_chunk(dataclasses.replace(llama, prefill_any_width=False), CHUNK) is None
    short = dataclasses.replace(model[0], max_decode_len=WIDE).serving_model()
    assert wide_chunk(short, CHUNK) is None  # a slab no longer than the wide chunk
    progs = engine_lib.programs(dataclasses.replace(llama, prefill_any_width=False), slots=2, chunk=CHUNK, block=4,
                                sample=make_sampler(0.0, 0, 1.0))
    assert progs.prefill_chunk_wide is None


@pytest.fixture(scope="module")
def recorded():
    return json.loads((Path(__file__).parent / "data_lowerings_pr46.json").read_text())


@pytest.fixture(scope="module")
def lowered_now():
    """preset -> {program: hash}, each family lowered once (with the wide width as small as the tests make it: a
    family without the member never asks for it)."""
    done = {}

    def of(name):
        if name not in done:
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(cache_attention, "BLOCK_MAX", 512)
                done[name] = lowerings.lowered(name)
        return done[name]

    return of


@pytest.mark.parametrize("program", lowerings.PROGRAMS)
@pytest.mark.parametrize("family", lowerings.FAMILIES)
def test_a_family_without_the_member_lowers_to_the_parents_text(recorded, lowered_now, family, program):
    assert lowered_now(family)[program] == recorded[family][program]


@pytest.mark.parametrize("family", lowerings.FAMILIES)
def test_a_family_without_the_member_has_no_wide_program_and_todays_schedule(family):
    from pytorch_operator_tpu.models.serving import preset
    from pytorch_operator_tpu.ops.sampling import make_sampler

    model = preset(family, decode=True, max_decode_len=4096).serving_model()
    assert not model.prefill_any_width and wide_chunk(model, 128) is None
    progs = engine_lib.programs(model, slots=2, chunk=128, block=4, sample=make_sampler(0.0, 0, 1.0))
    assert progs.prefill_chunk_wide is None
    for p in (1, 128, 600, 3000):
        assert chunk_schedule(p, 128, wide_chunk(model, 128)) == [(s, 128) for s in range(0, p, 128)]


def test_why_prints_the_wide_chunks_beside_all_of_them():
    """``tpujob why`` reads the engine's last ``metrics`` record: its admissions line ends with wide / all."""
    from pytorch_operator_tpu.obs.analyze import render_report

    text = render_report({"job": "default/serve", "admit_rounds": {"master-0": [209, 209, 517, 3180, 2510]}})
    (line,) = [l for l in text.splitlines() if l.startswith("admits:")]
    assert "master-0 517 admitted in 209 round(s)" in line and line.endswith("; 2510 of 3180 prefill chunk(s) wide")
