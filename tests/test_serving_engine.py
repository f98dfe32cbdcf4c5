"""The continuous-batching serving stack (serving/engine.py + spool.py
+ workloads/serve.py).

The load-bearing property: a mixed-length request stream served through
shared cache slots produces EXACTLY the tokens each request would get
generated alone (greedy parity vs make_generate), while slots recycle
and latency accounting (TTFT, per-token samples) accrues.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from pytorch_operator_tpu.models import llama as llama_lib
from pytorch_operator_tpu.obs import trace as obs_trace
from pytorch_operator_tpu.serving import Request, ServingEngine, Spool
from pytorch_operator_tpu.serving.engine import QUANTUM, SIZED_BY, decode_steps


def _cfg_params(max_decode_len=48, **over):
    import jax
    import flax.linen as nn

    cfg = llama_lib.llama_tiny(decode=True, max_decode_len=max_decode_len, **over)
    params = nn.meta.unbox(
        llama_lib.Llama(dataclasses.replace(cfg, decode=False)).init(
            jax.random.key(0), np.zeros((1, 8), np.int32)
        )["params"]
    )
    return cfg, params


def _reference_rollout(cfg, params, prompt, new):
    """make_generate (B=1, uniform single-stream path) — the parity
    oracle for every engine rollout."""
    import jax
    import jax.numpy as jnp

    from pytorch_operator_tpu.workloads.generate import (
        init_cache,
        make_generate,
    )

    model = llama_lib.Llama(cfg)
    gen = make_generate(model, max_new_tokens=new)
    cache = init_cache(model, 1, len(prompt))
    toks, _ = gen(
        params, cache, jnp.asarray(prompt[None, :]), jax.random.key(0)
    )
    return [int(t) for t in np.asarray(toks)[0]]


def _req(rid, prompt, new):
    return Request(
        id=rid, prompt=prompt, max_new_tokens=new, submit_time=time.time()
    )


Q = QUANTUM


@pytest.mark.parametrize(
    "remaining, free_slots, block, want",
    [
        # A slot free: a quantum, so an arrival waits that long at most ...
        ([40, 100], 1, 64, (Q, "quantum")),
        ([40, 100], 6, 256, (Q, "quantum")),
        # ... but never past the last row's budget,
        ([3, Q - 1], 1, 64, (Q - 1, "budget")),
        ([1], 7, 64, (1, "budget")),
        # and the ceiling cuts a quantum like anything else.
        ([40, 100], 1, 4, (4, "ceiling")),
        ([40, 100], 1, 1, (1, "ceiling")),
        # All slots taken: to the step at which the next one frees,
        ([40, 100, 57], 0, 64, (40, "budget")),
        ([Q + 1, 300], 0, 64, (Q + 1, "budget")),
        # under the ceiling,
        ([100, 200], 0, 64, (64, "ceiling")),
        ([64, 200], 0, 64, (64, "budget")),
        # with a floor of a quantum (rows a step apart do not make one-step dispatches),
        ([1, 2, 3, 90], 0, 64, (Q, "quantum")),
        ([Q, 90], 0, 64, (Q, "quantum")),
        # which the longest budget still cuts.
        ([1, 2, 3], 0, 64, (3, "budget")),
        ([5], 0, 64, (5, "budget")),
        ([5], 0, 4, (4, "ceiling")),
    ],
)
def test_decode_steps_rule(remaining, free_slots, block, want):
    steps, sized_by = decode_steps(remaining, free_slots, block)
    assert (steps, sized_by) == want and sized_by in SIZED_BY
    assert 1 <= steps <= min(block, max(remaining))  # no step that no row can use


@pytest.fixture
def sized_by(tmp_path, monkeypatch):
    """What sized each decode dispatch so far, in order, as the dispatches'
    own spans say it (file records under ``TPUJOB_TRACE_DIR``)."""
    monkeypatch.setenv(obs_trace.ENV_VAR, str(tmp_path / "trace"))
    obs_trace.reset_tracer()

    def read() -> list:
        rec = obs_trace.tracer()
        rec.flush()
        return [e["args"]["sized_by"] for e in obs_trace.load_span_file(rec.path)
                if e["name"] == "engine.decode_dispatch"]

    yield read
    monkeypatch.delenv(obs_trace.ENV_VAR)
    obs_trace.reset_tracer()


PARITY_SHAPES = [(5, 20), (13, 30), (8, 3), (9, 18)]  # (prompt, new tokens): budgets on both sides of a quantum


@pytest.fixture(scope="module")
def parity_model():
    cfg, params = _cfg_params(max_decode_len=64)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, (p,)).astype(np.int32) for p, _ in PARITY_SHAPES]
    want = [_reference_rollout(cfg, params, prompt, n) for prompt, (_, n) in zip(prompts, PARITY_SHAPES)]
    return cfg, params, prompts, want


@pytest.mark.parametrize("block", [1, 4, 64])
@pytest.mark.parametrize("slots, occupancy", [(5, "free"), (4, "full"), (2, "queued")])
def test_greedy_tokens_do_not_depend_on_where_dispatches_are_cut(parity_model, block, slots, occupancy, sized_by):
    """The same four requests with a slot always free, with every slot
    taken, and with a queue waiting for slots, under ceilings of 1, 4 and 64
    steps: token for token ``make_generate``'s single-stream rollout."""
    cfg, params, prompts, want = parity_model
    eng = ServingEngine(cfg, params, slots=slots, chunk=8, block=block)
    for i, (prompt, (_, n)) in enumerate(zip(prompts, PARITY_SHAPES)):
        eng.submit(_req(f"r{i}", prompt, n))
    results, sizes = {}, []
    while eng.busy:
        for r in eng.step():
            results[r.id] = r.tokens
        sizes.append((eng.last_steps, eng.queued))  # the dispatch's steps, and who still waited for a slot
    assert [results[f"r{i}"] for i in range(len(want))] == want
    assert eng._decode_block._cache_size() == 1  # every length ran the one compiled program
    n = eng.stats()
    assert all(1 <= steps <= block for steps, _ in sizes)
    assert n["decode_steps"] <= block * n["decode_blocks"]
    reasons = sized_by()  # one a dispatch, on its span
    assert len(reasons) == n["decode_blocks"] and set(reasons) <= set(SIZED_BY)
    assert n["decode_tokens"] == sum(new - 1 for _, new in PARITY_SHAPES)
    if block < Q:
        assert "ceiling" in reasons and "quantum" not in reasons
    elif occupancy == "free":
        # First dispatch: budgets 19, 29, 2, 17 and a slot free -> a quantum.
        assert sizes[0][0] == Q and reasons[0] == "quantum" and "ceiling" not in reasons
    elif occupancy == "full":
        # All four slots taken, shortest budget 2 -> the floor of a quantum; nothing ever exceeds it here.
        assert sizes[0][0] == Q and max(steps for steps, _ in sizes) == Q
    else:
        # Two slots, two requests queued: 19 and 29 remain -> run to 19, when the next slot frees.
        assert sizes[0] == (19, 2) and reasons[0] == "budget"


def test_an_eos_row_is_harvested_at_the_next_boundary(parity_model, sized_by):
    """A budget is an upper bound on a row's life where an EOS token can end
    it: the dispatch is sized by the budget, the row ends inside it, and the
    same ``step()`` hands the answer out and frees the slot."""
    cfg, params, prompts, want = parity_model
    full = want[1]  # 30 tokens
    eos = full[3]
    cut = full.index(eos) + 1
    eng = ServingEngine(cfg, params, slots=1, chunk=8, block=64, eos_token=eos)
    eng.submit(_req("e0", prompts[1], 30))
    eng.submit(_req("e1", prompts[2], 3))
    (res,) = eng.step()
    assert res.id == "e0" and res.tokens == full[:cut]
    n = eng.stats()
    assert eng.last_steps == 29 and sized_by() == ["budget"]  # one slot, all taken: to its budget
    assert n["decode_row_steps"] == 29 and n["decode_tokens"] == cut - 1
    assert eng.slots_free == 1 and eng.queued == 1  # the next boundary admits the one that waited
    (nxt,) = eng.run_until_drained()
    assert nxt.id == "e1" and nxt.tokens == want[2]


# ---- what a prefill chunk is told: how many of its tokens are real ----


def _family_model(family):
    """(serving model, params) of a family's test-size preset."""
    import jax

    from pytorch_operator_tpu.models.serving import preset

    name = {"llama": "tiny", "mimo_v2": "mimo-tiny", "nemotron_h": "nemotron-h-tiny", "phi4_flash": "phi4-flash-tiny"}[family]
    model = preset(name, decode=True, max_decode_len=64).serving_model()
    return model, model.init_params(jax.random.key(0))


FAMILIES = ["llama", "mimo_v2", "nemotron_h", "phi4_flash"]
RECURRENT = ("nemotron_h", "phi4_flash")  # the families that keep a scan's state beside keys and values


@pytest.mark.parametrize("family", FAMILIES)
def test_the_count_of_real_tokens_changes_nothing_where_state_is_keys_and_values(family):
    """``prefill`` of a padded last chunk with the count of real tokens, and
    with the whole chunk called real: the families whose state is keys and
    values give the same bits (every later read masks a pad by position or
    overwrites it); those whose state is a recurrence give the same bits
    at the real positions and another state behind them."""
    import jax
    import jax.numpy as jnp

    model, params = _family_model(family)
    chunk, real = 8, 5
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 200, (1, chunk)), jnp.int32)
    pos = jnp.arange(chunk, dtype=jnp.int32)[None]
    told, cache_told, _ = model.prefill(params, model.init_cache(2, chunk), jnp.int32(1), toks, pos, jnp.int32(real))
    whole, cache_whole, _ = model.prefill(params, model.init_cache(2, chunk), jnp.int32(1), toks, pos, jnp.int32(chunk))
    for a, b in zip(jax.tree.leaves(told), jax.tree.leaves(whole)):  # ``hidden`` is the family's own pytree
        assert np.array_equal(np.asarray(a[0, :real], np.float32), np.asarray(b[0, :real], np.float32))
    same = all(np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
               for a, b in zip(jax.tree.leaves(cache_told), jax.tree.leaves(cache_whole)))
    assert same == (family not in RECURRENT)


@pytest.mark.parametrize("family", FAMILIES)
def test_the_engine_tells_every_chunk_how_many_of_its_tokens_are_real(family, monkeypatch):
    """A prompt of 19 in chunks of 8: the model's ``prefill`` is traced once
    (one program) and the three dispatches carry 8, 8 and 3."""
    from pytorch_operator_tpu.serving import engine as engine_lib

    model, params = _family_model(family)
    seen, real_programs = [], engine_lib.programs

    def spying(model, **kw):
        progs = real_programs(model, **kw)

        def prefill_chunk(params, cache, counts, slot, toks, start, n_real):
            seen.append((int(start), int(n_real)))
            return progs.prefill_chunk(params, cache, counts, slot, toks, start, n_real)

        return progs._replace(prefill_chunk=prefill_chunk)

    monkeypatch.setattr(engine_lib, "programs", spying)
    eng = ServingEngine(model.cfg, params, slots=2, chunk=8, block=4)
    eng.submit(_req("a", np.arange(1, 20, dtype=np.int32), 3))
    (res,) = eng.run_until_drained()
    assert len(res.tokens) == 3 and seen == [(0, 8), (8, 8), (16, 3)]
    assert eng.stats()["prefill_chunks"] == 3 and eng.stats()["prefill_pad_tokens"] == 5


# ---- an admission is dispatched whole: nothing is read back until the decode dispatch is queued (PR 35) ----


def _sampled_rollouts(model, params, prompts, news, *, chunk, seed, temperature, top_k):
    """What the engine must give when every prompt is admitted in its first
    round, one a slot, straight from the model's own forwards: each first
    token from the chain of ``key(seed + 1)`` in admission order, then one
    sample a step over all rows from the chain of ``key(seed)``."""
    import jax
    import jax.numpy as jnp

    from pytorch_operator_tpu.ops.sampling import make_sampler

    sample = make_sampler(temperature, top_k, 1.0)
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode)
    cache = model.init_cache(len(prompts), chunk)
    first_key, rng = jax.random.key(seed + 1), jax.random.key(seed)
    tok = []
    for slot, prompt in enumerate(prompts):
        p = len(prompt)
        buf = np.zeros((-(-p // chunk) * chunk,), np.int32)
        buf[:p] = prompt
        for start in range(0, len(buf), chunk):
            pos = (start + jnp.arange(chunk, dtype=jnp.int32))[None]
            hidden, cache, _ = prefill(params, cache, jnp.int32(slot), jnp.asarray(buf[None, start:start + chunk]),
                                       pos, jnp.int32(min(chunk, p - start)))
        first_key, sub = jax.random.split(first_key)
        h = jax.tree.map(lambda a: a[:, (p - 1) % chunk], hidden)
        logits = model.logits(params, h) if model.finish is None else model.finish(params, cache, jnp.int32(slot), h, jnp.int32(p - 1))
        tok.append(sample(logits, sub)[0])
    tok, pos = jnp.stack(tok), jnp.asarray([len(prompt) for prompt in prompts], jnp.int32)
    out = [[int(t)] for t in tok]
    for _ in range(max(news) - 1):
        logits, cache, _ = decode(params, cache, tok[:, None], pos[:, None])
        rng, k = jax.random.split(rng)
        tok, pos = sample(logits, k), pos + 1
        for row, t in zip(out, np.asarray(tok)):
            row.append(int(t))
    return [row[:n] for row, n in zip(out, news)]


ROUND_SHAPES = [(5, 7), (19, 12), (8, 4)]  # (prompt, new tokens): one, three and one chunks of 8


def _round_prompts():
    rng = np.random.default_rng(11)
    return [rng.integers(1, 250, (p,)).astype(np.int32) for p, _ in ROUND_SHAPES]


@pytest.mark.parametrize("family", ["llama", "nemotron_h", "phi4_flash"])
def test_seeded_sampling_with_several_prompts_admitted_in_one_round_is_the_models_own_rollout(family):
    """Temperature 1, top-k 8, three prompts into three slots at one
    boundary: the first tokens come from the first-token key split once an
    admission, in admission order, and the rest from the decode key, as
    before the head's program sampled them; a second engine with the same
    seed repeats them and greedy gives others."""
    model, params = _family_model(family)
    prompts, news = _round_prompts(), [n for _, n in ROUND_SHAPES]
    want = _sampled_rollouts(model, params, prompts, news, chunk=8, seed=3, temperature=1.0, top_k=8)

    def served(**sampling):
        eng = ServingEngine(model.cfg, params, slots=3, chunk=8, block=4, **sampling)
        for i, (prompt, n) in enumerate(zip(prompts, news)):
            eng.submit(_req(f"t{i}", prompt, n))
        got = {r.id: r.tokens for r in eng.run_until_drained()}
        assert eng.stats()["admit_rounds"] == eng.stats()["decode_behind_admit"] == 1
        return [got[f"t{i}"] for i in range(len(prompts))]

    assert served(temperature=1.0, top_k=8, seed=3) == want == served(temperature=1.0, top_k=8, seed=3)
    assert served() != want and served(temperature=1.0, top_k=8, seed=4) != want


class _Unread:
    """A program's output the host has not read yet: the read is logged."""

    def __init__(self, value, log, name):
        self.value, self.log, self.name = value, log, name

    def __int__(self):
        self.log.append(f"read:{self.name}")
        return int(self.value)

    def __array__(self, dtype=None, copy=None):
        self.log.append(f"read:{self.name}")
        return np.asarray(self.value, dtype)


def _logging_programs(monkeypatch, log):
    """The engine's own programs, each dispatch logged by its name, and the
    two outputs the host reads (an admission's first token, a dispatch's
    tokens) wrapped so that the read is logged too."""
    from pytorch_operator_tpu.serving import engine as engine_lib

    real_programs = engine_lib.programs

    def logging(model, **kw):
        progs = real_programs(model, **kw)

        def prefill_chunk(*args):
            log.append("prefill_chunk")
            return progs.prefill_chunk(*args)

        def prefill_chunk_head(*args):
            log.append("prefill_chunk_head")
            tok, pos, first, key = progs.prefill_chunk_head(*args)
            return tok, pos, _Unread(first, log, "first_token"), key

        def decode_block(*args):
            log.append("decode_block")
            toks, *rest = progs.decode_block(*args)
            return (_Unread(toks, log, "decode_tokens"), *rest)

        return engine_lib.Programs(prefill_chunk, prefill_chunk_head, decode_block)

    monkeypatch.setattr(engine_lib, "programs", logging)


def test_nothing_is_read_back_between_an_admissions_first_chunk_and_the_decode_dispatch_behind_it(parity_model, monkeypatch):
    """Two prompts admitted at one boundary (one and two chunks of 8): both
    prompts' chunks and heads are dispatched, then the decode block, and
    only then the two first tokens are read, in admission order, before the
    decode tokens; a boundary without an admission reads the decode tokens
    alone. (The CPU backend does not honour
    ``jax.transfer_guard_device_to_host``, so the order is recorded where
    the engine dispatches and reads.) The tokens are ``make_generate``'s."""
    cfg, params, prompts, want = parity_model
    log = []
    _logging_programs(monkeypatch, log)
    eng = ServingEngine(cfg, params, slots=2, chunk=8, block=4)
    eng.submit(_req("r0", prompts[0], 20))  # 5 tokens: one chunk
    eng.submit(_req("r1", prompts[1], 30))  # 13 tokens: two chunks
    assert eng.step() == []
    assert log == ["prefill_chunk", "prefill_chunk_head", "prefill_chunk", "prefill_chunk", "prefill_chunk_head",
                   "decode_block", "read:first_token", "read:first_token", "read:decode_tokens"]
    del log[:]
    eng.step()
    assert log == ["decode_block", "read:decode_tokens"]
    got = {r.id: r.tokens for r in eng.run_until_drained()}
    assert got == {"r0": want[0], "r1": want[1]}
    n = eng.stats()
    assert n["admit_rounds"] == n["decode_behind_admit"] == 1 and n["admitted"] == 2


@pytest.mark.parametrize("temperature", [0.0, 1.0], ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", FAMILIES)
def test_an_admission_compiles_no_program_beyond_the_engines_own(family, temperature):
    """Five requests through three slots, admissions at several boundaries:
    the process compiles (or takes from the compile cache) the engine's
    three programs and nothing else: no eager scatter sets a row's state,
    no sampler of its own takes the first token, and a model's ``finish``
    (or its absence) adds no program."""
    from pytorch_operator_tpu.runtime.backend import compile_counts

    model, params = _family_model(family)
    sampling = {"temperature": temperature, "top_k": 8, "seed": 1} if temperature else {}
    eng = ServingEngine(model.cfg, params, slots=3, chunk=8, block=4, **sampling)
    before = sum(compile_counts().values())
    rng = np.random.default_rng(0)
    for i, (p, n) in enumerate([(5, 7), (13, 9), (8, 1), (21, 5), (3, 12)]):
        eng.submit(_req(f"c{i}", rng.integers(0, 256, (p,)).astype(np.int32), n))
    assert len(eng.run_until_drained()) == 5
    assert sum(compile_counts().values()) - before == 3
    assert [f._cache_size() for f in (eng._prefill_chunk, eng._prefill_chunk_head, eng._decode_block)] == [1, 1, 1]
    assert not hasattr(eng, "_first_token") and eng.stats()["admit_rounds"] > 1


@pytest.mark.parametrize("family", FAMILIES)
def test_the_head_program_takes_the_cache_only_where_the_model_finishes_an_admission_itself(family):
    """``prefill_chunk_head`` is handed the cache (read, not donated), the
    slot and the position for the model's ``finish``. The three families
    whose prefill ran every layer have none: their first token is the head's
    product on the hidden state, as before, and the compiled program has no
    cache among its inputs (an unused argument is pruned). The
    decoder-hybrid-decoder's reads its slab and its cross-decoder's weights."""
    import jax
    import jax.numpy as jnp

    from pytorch_operator_tpu.ops.sampling import make_sampler
    from pytorch_operator_tpu.serving.engine import programs

    model, params = _family_model(family)
    chunk, p, slot = 8, 13, jnp.int32(2)
    progs = programs(model, slots=3, chunk=chunk, block=4, sample=make_sampler(0.0, 0, 1.0))
    zero = jax.tree.map(jnp.zeros_like, model.counts)
    prompt = np.random.default_rng(5).integers(1, 250, (2 * chunk,)).astype(np.int32)
    cache = model.init_cache(3, chunk)
    for start in (0, chunk):
        hidden, cache, zero = progs.prefill_chunk(params, cache, zero, slot, prompt[None, start:start + chunk],
                                                  jnp.int32(start), jnp.int32(min(chunk, p - start)))
    tok, pos, first, _ = progs.prefill_chunk_head(params, cache, hidden, jnp.zeros((3,), jnp.int32),
                                                  jnp.zeros((3,), jnp.int32), slot, jnp.int32(p), jax.random.key(0))
    h = jax.tree.map(lambda a: a[:, (p - 1) % chunk], hidden)
    bytes_of = lambda tree: sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    compiled = progs.prefill_chunk_head.lower(params, cache, hidden, tok, pos, slot, jnp.int32(p), jax.random.key(0)).compile()
    inputs = compiled.memory_analysis().argument_size_in_bytes
    if family == "phi4_flash":
        assert model.finish is not None
        want = model.finish(params, cache, slot, h, jnp.int32(p - 1))
        slab = cache[f"layer_{model.cfg.full_layer}"]
        assert inputs > bytes_of(slab) + bytes_of(params["layers"][model.cfg.full_layer:])
    else:
        assert model.finish is None and model.slab_reads is None
        want = model.logits(params, h)
        assert inputs < bytes_of(cache)  # the cache is not among the program's inputs
    assert int(first) == int(np.argmax(np.asarray(want[0]))) == int(tok[2]) and int(pos[2]) == p


def test_a_boundary_admits_a_bounded_stretch_of_prefill_and_always_one_prompt(parity_model, monkeypatch, sized_by):
    """Four prompts of 5 chunks into four free slots with the bound at 10
    chunks: two a boundary, a decode dispatch of a quantum behind each
    round (a slot is free, so no longer), the same greedy tokens as without
    the bound; a prompt longer than the bound is still admitted, alone."""
    from pytorch_operator_tpu.serving import engine as engine_lib

    cfg, params, _, _ = parity_model
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 250, (40,)).astype(np.int32) for _ in range(4)]

    def served(bound):
        monkeypatch.setattr(engine_lib, "ADMIT_TOKENS", bound * 8)  # stated in tokens: ``bound`` chunks of 8
        eng = ServingEngine(cfg, params, slots=4, chunk=8, block=16)
        for i, prompt in enumerate(prompts):
            eng.submit(_req(f"b{i}", prompt, 12))
        got = {r.id: r.tokens for r in eng.run_until_drained()}
        return [got[f"b{i}"] for i in range(4)], eng.stats()

    whole, n = served(engine_lib.ADMIT_TOKENS // 8)
    assert n["admit_rounds"] == 1 and n["prefill_chunks"] == 20
    before = len(sized_by())
    bounded, n = served(10)
    assert bounded == whole and n["admit_rounds"] == n["decode_behind_admit"] == 2 and "quantum" in sized_by()[before:]
    alone, n = served(3)
    assert alone == whole and n["admit_rounds"] == 4 and n["admitted"] == 4
    assert n["prefill_rounds"] == 4  # this family's decode step cannot hold a row: a prompt is never split


def test_a_first_token_that_ends_its_request_costs_one_dispatch_and_leaves_the_slot_clean(parity_model):
    """The host learns an admission's first token after the decode dispatch
    is queued: where it is ``eos_token`` the row ran that dispatch for
    nothing, its tokens are dropped, the request ends with its one token and
    the slot is free at the same boundary; the next occupant of the slot
    gets its own rollout, token for token."""
    cfg, params, prompts, want = parity_model
    eos = want[1][0]
    assert eos not in want[3]
    eng = ServingEngine(cfg, params, slots=1, chunk=8, block=4, eos_token=eos)
    eng.submit(_req("e0", prompts[1], 30))
    eng.submit(_req("e1", prompts[3], 18))
    (res,) = eng.step()
    assert res.id == "e0" and res.tokens == [eos] and res.tpot_s is None
    n = eng.stats()
    assert n["decode_blocks"] == n["decode_behind_admit"] == 1 and n["decode_tokens"] == 0
    assert n["decode_row_steps"] == eng.last_steps == 4 and eng.slots_free == 1 and eng.queued == 1
    (nxt,) = eng.run_until_drained()
    assert nxt.id == "e1" and nxt.tokens == want[3]
    assert eng.stats()["decode_tokens"] == 17


def test_a_request_of_one_token_never_enters_a_decode_dispatch(parity_model, monkeypatch):
    """``max_new_tokens`` 1 is finished by its first token: alone it runs no
    decode dispatch at all; beside a decoding row it stays parked (the
    dispatch runs one row) and is answered at that boundary."""
    cfg, params, prompts, want = parity_model
    log = []
    _logging_programs(monkeypatch, log)
    eng = ServingEngine(cfg, params, slots=3, chunk=8, block=4)
    eng.submit(_req("one", prompts[0], 1))
    (res,) = eng.step()
    assert res.tokens == want[0][:1] and not eng.busy
    assert log == ["prefill_chunk", "prefill_chunk_head", "read:first_token"]
    n = eng.stats()
    assert n["decode_blocks"] == 0 and (n["admit_rounds"], n["decode_behind_admit"]) == (1, 0)
    eng.submit(_req("long", prompts[1], 30))
    eng.submit(_req("one-more", prompts[2], 1))
    (res,) = eng.step()
    assert res.id == "one-more" and res.tokens == want[2][:1]
    n = eng.stats()
    assert n["decode_blocks"] == 1 and n["slot_blocks_occupied"] == 1 and n["decode_row_steps"] == eng.last_steps
    assert (n["admit_rounds"], n["decode_behind_admit"]) == (2, 1)
    (res,) = eng.run_until_drained()
    assert res.id == "long" and res.tokens == want[1]


@pytest.mark.parametrize("slots", [1, 2, 4])
def test_every_round_that_admits_a_decoding_row_queues_the_decode_dispatch_behind_it(parity_model, slots):
    cfg, params, prompts, want = parity_model
    eng = ServingEngine(cfg, params, slots=slots, chunk=8, block=4)
    for i, (prompt, (_, n)) in enumerate(zip(prompts, PARITY_SHAPES)):
        eng.submit(_req(f"r{i}", prompt, n))
    got = {r.id: r.tokens for r in eng.run_until_drained()}
    assert [got[f"r{i}"] for i in range(len(want))] == want
    n = eng.stats()
    assert n["decode_behind_admit"] == n["admit_rounds"] >= -(-len(want) // slots) and n["admitted"] == len(want)
    assert n["decode_behind_admit"] < n["decode_blocks"]
    eng.reset_stats()
    assert eng.stats()["decode_behind_admit"] == 0 and "host_overlapped_s" in eng.stats()


@pytest.mark.slow
class TestEngineParity:
    def test_mixed_lengths_match_single_stream(self):
        """Mixed prompt lengths and budgets through 3 shared slots: every
        request token-for-token equal to its single-stream rollout."""
        cfg, params = _cfg_params()
        eng = ServingEngine(cfg, params, slots=3, chunk=8, block=4)
        rng = np.random.default_rng(0)
        shapes = [(5, 7), (13, 9), (8, 3), (21, 5)]
        reqs = [
            _req(f"r{i}", rng.integers(0, 256, (p,)).astype(np.int32), n)
            for i, (p, n) in enumerate(shapes)
        ]
        for r in reqs:
            eng.submit(r)
        results = {r.id: r for r in eng.run_until_drained()}
        assert sorted(results) == [f"r{i}" for i in range(len(shapes))]
        for r in reqs:
            want = _reference_rollout(cfg, params, r.prompt, r.max_new_tokens)
            assert results[r.id].tokens == want, r.id

    def test_slot_reuse_preserves_parity(self):
        """More requests than slots: later requests land in RECYCLED
        slots whose caches hold a finished stream's leftovers — the
        write-before-read masking must keep them exact."""
        cfg, params = _cfg_params()
        eng = ServingEngine(cfg, params, slots=2, chunk=8, block=4)
        rng = np.random.default_rng(1)
        reqs = [
            _req(f"q{i}", rng.integers(0, 256, (p,)).astype(np.int32), n)
            for i, (p, n) in enumerate(
                [(6, 8), (11, 4), (4, 10), (17, 6), (9, 9)]
            )
        ]
        for r in reqs:
            eng.submit(r)
        results = {r.id: r for r in eng.run_until_drained()}
        assert len(results) == 5
        for r in reqs:
            want = _reference_rollout(cfg, params, r.prompt, r.max_new_tokens)
            assert results[r.id].tokens == want, r.id
        # All 5 went through 2 slots — reuse actually happened.
        assert eng.slots == 2

    def test_int8_stack_composes(self):
        """The serving stack's production config: int8 weights + int8
        KV through the engine, parity vs the single-stream rollout on
        the SAME quantized params."""
        import jax

        from pytorch_operator_tpu.ops.quantize import quantize_tree

        cfg, params = _cfg_params(kv_quantize="int8")
        cfg = dataclasses.replace(cfg, quantize="int8")
        qparams = jax.jit(quantize_tree)(params)
        eng = ServingEngine(cfg, qparams, slots=2, chunk=8, block=4)
        rng = np.random.default_rng(2)
        reqs = [
            _req(f"s{i}", rng.integers(0, 256, (p,)).astype(np.int32), n)
            for i, (p, n) in enumerate([(7, 6), (12, 8), (5, 4)])
        ]
        for r in reqs:
            eng.submit(r)
        results = {r.id: r for r in eng.run_until_drained()}
        for r in reqs:
            want = _reference_rollout(cfg, qparams, r.prompt, r.max_new_tokens)
            assert results[r.id].tokens == want, r.id

    def test_eos_frees_slot_early(self):
        """A request hitting EOS finishes before its budget and frees
        the slot; the emitted tokens stop at (and include) EOS."""
        cfg, params = _cfg_params()
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, 256, (6,)).astype(np.int32)
        # Find the greedy rollout, then declare its 3rd token EOS.
        full = _reference_rollout(cfg, params, prompt, 12)
        eos = full[2]
        eng = ServingEngine(
            cfg, params, slots=1, chunk=8, block=4, eos_token=eos
        )
        eng.submit(_req("e0", prompt, 12))
        (res,) = eng.run_until_drained()
        assert res.tokens == full[:3]
        assert res.tokens[-1] == eos

    def test_temperature_sampling_serves(self):
        """T>0 exercises the sampler in the head's program and in the
        decode blocks; tokens must be in-range and the full budget
        delivered."""
        cfg, params = _cfg_params()
        eng = ServingEngine(
            cfg, params, slots=2, chunk=8, block=4,
            temperature=1.0, top_k=8, seed=3,
        )
        rng = np.random.default_rng(5)
        for i in range(2):
            eng.submit(
                _req(f"t{i}", rng.integers(0, 256, (6,)).astype(np.int32), 5)
            )
        results = eng.run_until_drained()
        assert len(results) == 2
        for r in results:
            assert len(r.tokens) == 5
            assert all(0 <= t < cfg.vocab_size for t in r.tokens)

    def test_latency_accounting(self):
        cfg, params = _cfg_params()
        eng = ServingEngine(cfg, params, slots=2, chunk=8, block=4)
        rng = np.random.default_rng(4)
        for i in range(3):
            eng.submit(
                _req(f"m{i}", rng.integers(0, 256, (6,)).astype(np.int32), 6)
            )
        results = eng.run_until_drained()
        s = eng.stats()
        assert s["requests"] == 3 and s["generated_tokens"] == 18
        assert s["decode_tokens_per_sec"] > 0
        for k in ("ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50", "tpot_ms_p99"):
            assert s[k] is not None and s[k] > 0, k
        for r in results:
            assert r.ttft_s >= r.admit_wait_s >= 0
            assert r.tpot_s is None or r.tpot_s > 0


class TestEngineValidation:
    def test_budget_rejected_at_submit(self):
        cfg, params = _cfg_params(max_decode_len=32)
        eng = ServingEngine(cfg, params, slots=1, chunk=8, block=2)
        with pytest.raises(ValueError, match="cache budget"):
            eng.submit(
                _req("big", np.zeros((20,), np.int32), 12)  # 20+12 > 31
            )
        with pytest.raises(ValueError, match="empty"):
            eng.submit(_req("empty", np.zeros((0,), np.int32), 4))
        # A zero/negative budget would still emit the prefill's first
        # token (and weaken the cache-budget inequality).
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(_req("zero", np.zeros((4,), np.int32), 0))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(_req("neg", np.zeros((4,), np.int32), -5))

    def test_needs_decode_config(self):
        cfg, params = _cfg_params()
        with pytest.raises(ValueError, match="decode"):
            ServingEngine(
                dataclasses.replace(cfg, decode=False), params, slots=1
            )


class TestSpool:
    def test_submit_claim_respond_roundtrip(self, tmp_path):
        sp = Spool(tmp_path / "sp")
        a = sp.submit(prompt=[1, 2, 3], max_new_tokens=4)
        b = sp.submit(prompt_len=7, max_new_tokens=2)
        assert sp.pending_count() == 2
        recs = sp.claim(10)
        assert [r["id"] for r in recs] == [a, b]  # oldest first
        assert recs[0]["prompt"] == [1, 2, 3]
        assert recs[1]["prompt_len"] == 7
        assert sp.pending_count() == 0
        sp.respond(a, {"tokens": [9, 9]})
        assert sp.wait_response(a, timeout=5)["tokens"] == [9, 9]
        with pytest.raises(TimeoutError):
            sp.wait_response(b, timeout=0.1)

    def test_tmp_files_invisible_to_claim(self, tmp_path):
        sp = Spool(tmp_path / "sp")
        (sp.requests / ".partial.tmp").write_text("{not json")
        assert sp.claim(5) == []
        assert sp.pending_count() == 0

    def test_claim_limit(self, tmp_path):
        sp = Spool(tmp_path / "sp")
        for _ in range(4):
            sp.submit(prompt_len=3, max_new_tokens=1)
        assert len(sp.claim(2)) == 2
        assert sp.pending_count() == 2

    def test_recover_claimed_requeues_orphans(self, tmp_path):
        """A crashed engine's in-flight claims must become requests
        again on restart (the supervisor restart policy re-runs the
        job; orphaned clients would otherwise wait out their
        timeouts). Already-answered claims are NOT re-run."""
        sp = Spool(tmp_path / "sp")
        a = sp.submit(prompt_len=3, max_new_tokens=2)
        b = sp.submit(prompt_len=4, max_new_tokens=2)
        sp.claim(2)  # both in flight
        assert sp.pending_count() == 0
        # Simulate a crash AFTER b's response was written but before
        # its claim was unlinked.
        (sp.responses / f"{b}.json").write_text('{"tokens": []}')
        assert sp.recover_claimed() == 1
        assert sp.pending_count() == 1
        assert sp.recover_claimed() == 0  # nothing left to recover
        assert [r["id"] for r in sp.claim(5)] == [a]

    def test_submit_validates(self, tmp_path):
        sp = Spool(tmp_path / "sp")
        with pytest.raises(ValueError, match="exactly one"):
            sp.submit(prompt=[1], prompt_len=3)
        with pytest.raises(ValueError, match="exactly one"):
            sp.submit()


@pytest.mark.slow
def test_serve_job_under_supervisor(tmp_path):
    """The operator-analog serving journey end to end: a REAL serve job
    under the supervisor (subprocess, rendezvous env, progress surface),
    fed by a client through the spool, exiting cleanly after its request
    budget — the reconciled-workload lifecycle applied to inference."""
    import threading

    from pytorch_operator_tpu.api import (
        ProcessTemplate,
        ReplicaType,
        Resources,
    )
    from pytorch_operator_tpu.controller import Supervisor
    from tests.testutil import new_job

    spool_dir = tmp_path / "spool"
    sp = Spool(spool_dir)
    got = {}

    def client():
        ids = [
            sp.submit(prompt_len=5, max_new_tokens=6),
            sp.submit(prompt=[3, 1, 4, 1, 5], max_new_tokens=4),
        ]
        for rid in ids:
            got[rid] = sp.wait_response(rid, timeout=240)

    t = threading.Thread(target=client)
    t.start()
    sup = Supervisor(state_dir=tmp_path / "state", poll_interval=0.1)
    job = new_job(name="serve-e2e", workers=0)
    job.spec.port = None
    job.spec.replica_specs[ReplicaType.MASTER].template = ProcessTemplate(
        module="pytorch_operator_tpu.workloads.serve",
        args=[
            "--config", "tiny", "--spool", str(spool_dir),
            "--slots", "2", "--chunk", "8", "--block", "4",
            "--max-decode-len", "48", "--max-requests", "2",
            "--idle-timeout", "120", "--json",
        ],
        resources=Resources(cpu_devices=1),
    )
    done = sup.run(job, timeout=240)
    t.join(timeout=60)
    log = (
        tmp_path / "state" / "logs" / "default_serve-e2e-master-0.log"
    ).read_text()
    assert done.is_succeeded(), f"log:\n{log[-3000:]}"
    assert not t.is_alive()
    assert len(got) == 2
    for r in got.values():
        assert len(r["tokens"]) in (4, 6)
        assert r["ttft_ms"] > 0
    # The serving job reports through the same progress surface as
    # training jobs: the status stream carries a metrics record with
    # the latency percentiles.
    import json as _json

    from pytorch_operator_tpu.controller.progress import job_status_dir
    from pytorch_operator_tpu.controller.store import job_key

    status = (
        job_status_dir(tmp_path / "state" / "status", job_key(done))
        / "master-0.jsonl"
    ).read_text()
    metrics = [
        r for r in map(_json.loads, status.splitlines())
        if r.get("event") == "metrics" and "ttft_ms_p50" in r
    ]
    assert metrics and metrics[-1]["requests"] == 2, status[-1500:]
    sup.shutdown()


@pytest.mark.slow
class TestServeWorkload:
    def test_serve_loop_with_concurrent_client(self, tmp_path):
        """The workload surface: serve.run() against a spool a client
        thread feeds while the loop runs — mixed lengths, responses
        with latency fields, a bad request rejected with an error."""
        import threading

        from pytorch_operator_tpu.workloads import serve as serve_mod

        spool_dir = tmp_path / "spool"
        sp = Spool(spool_dir)
        ids = [sp.submit(prompt_len=5, max_new_tokens=6)]
        got = {}

        def client():
            time.sleep(3)
            ids.append(sp.submit(prompt=[1, 2, 3, 4], max_new_tokens=4))
            ids.append(
                sp.submit(prompt_len=30, max_new_tokens=40)
            )  # over budget at L=48 -> rejected
            for rid in list(ids):
                got[rid] = sp.wait_response(rid, timeout=240)

        t = threading.Thread(target=client)
        t.start()
        stats = serve_mod.run(
            config="tiny", spool_dir=str(spool_dir), slots=2, chunk=8,
            block=4, max_decode_len=48, max_requests=2, idle_timeout=60,
            log=lambda *_: None,
        )
        t.join(timeout=300)
        assert not t.is_alive()
        assert stats["served"] == 2 and stats["rejected"] == 1
        ok = [r for r in got.values() if "tokens" in r]
        bad = [r for r in got.values() if "error" in r]
        assert len(ok) == 2 and len(bad) == 1
        for r in ok:
            assert len(r["tokens"]) in (4, 6)
            assert r["ttft_ms"] > 0
        assert "budget" in bad[0]["error"]
        assert stats["ttft_ms_p50"] > 0 and stats["tpot_ms_p50"] > 0


class TestServeRequestCLI:
    """`tpujob serve-request` — the client half of the serving service
    as a first-class CLI surface (no server needed for these: the spool
    IS the contract)."""

    def _cli(self, *argv):
        from pytorch_operator_tpu.client.cli import main

        return main(list(argv))

    def test_no_wait_submits_a_claimable_request(self, tmp_path, capsys):
        spool = tmp_path / "sp"
        Spool(spool)  # the serve job owns spool creation
        rc = self._cli(
            "serve-request", "--spool", str(spool),
            "--prompt", "3,1,4,1,5", "--max-new-tokens", "7", "--no-wait",
        )
        assert rc == 0
        rid = capsys.readouterr().out.strip()
        (rec,) = Spool(spool).claim(5)
        assert rec["id"] == rid
        assert rec["prompt"] == [3, 1, 4, 1, 5]
        assert rec["max_new_tokens"] == 7

    def test_wait_returns_the_engine_response(self, tmp_path, capsys):
        import json
        import threading

        spool_dir = tmp_path / "sp"
        sp = Spool(spool_dir)

        def fake_engine():
            # Answer the first request that shows up.
            import time as _t

            deadline = _t.time() + 30
            while _t.time() < deadline:
                recs = sp.claim(1)
                if recs:
                    sp.respond(
                        recs[0]["id"],
                        {"tokens": [9, 8], "ttft_ms": 12.0, "tpot_ms": 3.0},
                    )
                    return
                _t.sleep(0.02)

        t = threading.Thread(target=fake_engine)
        t.start()
        rc = self._cli(
            "serve-request", "--spool", str(spool_dir),
            "--prompt-len", "5", "--max-new-tokens", "2", "--timeout", "30",
        )
        t.join()
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["tokens"] == [9, 8] and out["ttft_ms"] == 12.0

    def test_bad_args_and_timeout(self, tmp_path, capsys):
        spool = str(tmp_path / "sp")
        assert self._cli("serve-request", "--spool", spool) == 2
        assert (
            self._cli(
                "serve-request", "--spool", spool,
                "--prompt", "1,2", "--prompt-len", "3",
            )
            == 2
        )
        assert (
            self._cli(
                "serve-request", "--spool", spool,
                "--prompt", "not,ints",
            )
            == 2
        )
        # A prompt with no valid ids is rejected locally, not after a
        # guaranteed-error server round trip.
        assert (
            self._cli(
                "serve-request", "--spool", spool, "--prompt", ","
            )
            == 2
        )
        # Arg errors must NOT have created the spool as a side effect,
        # and a missing spool is a clear client-side error (rc 1), not
        # a 300s hang against directories nothing reads.
        import pathlib

        assert not pathlib.Path(spool).exists()
        assert (
            self._cli(
                "serve-request", "--spool", spool,
                "--prompt-len", "4", "--timeout", "0.2",
            )
            == 1
        )
        assert "does not exist" in capsys.readouterr().err
        # With a live spool but nothing serving: the wait times out, rc 1.
        Spool(spool)
        assert (
            self._cli(
                "serve-request", "--spool", spool,
                "--prompt-len", "4", "--timeout", "0.2",
            )
            == 1
        )


@pytest.mark.slow
def test_serve_loop_churn_under_threaded_clients(tmp_path, monkeypatch):
    """Stress the spool+engine+loop composition: 12 requests from 3
    client threads with jittered submit timing into 2 slots — every
    request answered exactly once, no response lost or duplicated
    (the serving analog of the control plane's test_stress.py)."""
    import collections
    import threading

    from pytorch_operator_tpu.workloads import serve as serve_mod

    spool_dir = tmp_path / "spool"
    sp = Spool(spool_dir)
    results = {}
    lock = threading.Lock()
    # Count engine-side respond() calls per id — the only place
    # duplication is actually observable (a double respond would
    # silently overwrite the same response file).
    respond_counts = collections.Counter()
    real_respond = Spool.respond

    def counting_respond(self, request_id, record):
        with lock:
            respond_counts[request_id] += 1
        return real_respond(self, request_id, record)

    monkeypatch.setattr(Spool, "respond", counting_respond)
    rng = np.random.default_rng(0)
    plans = [
        [(int(rng.integers(3, 20)), int(rng.integers(2, 10)))
         for _ in range(4)]
        for _ in range(3)
    ]

    def client(plan, jitter):
        for p, n in plan:
            time.sleep(jitter)
            rid = sp.submit(prompt_len=p, max_new_tokens=n)
            r = sp.wait_response(rid, timeout=240)
            with lock:
                results[rid] = (n, r)

    threads = [
        threading.Thread(target=client, args=(plan, 0.2 * i))
        for i, plan in enumerate(plans)
    ]
    for t in threads:
        t.start()
    stats = serve_mod.run(
        config="tiny", spool_dir=str(spool_dir), slots=2, chunk=8,
        block=4, max_decode_len=48, max_requests=12, idle_timeout=120,
        log=lambda *_: None,
    )
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert stats["served"] == 12 and stats["rejected"] == 0
    assert len(results) == 12
    # Exactly-once: every submitted id answered by exactly ONE engine
    # respond() call.
    assert sorted(respond_counts) == sorted(results)
    assert set(respond_counts.values()) == {1}, respond_counts
    for rid, (n, r) in results.items():
        assert len(r["tokens"]) == n, rid
        assert r["ttft_ms"] > 0
    # The spool drained completely: nothing claimed or pending.
    assert sp.pending_count() == 0
    assert list(sp.claimed.iterdir()) == []
