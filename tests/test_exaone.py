"""K-EXAONE in the layer-pattern family (models/mimo_v2.py with its options,
the multi-token-prediction block, and the engine's verifying step) against the
benchmark's plain reference (benchmark/families/exaone_moe/reference.py), at a
small size with the real structure: layer 0 window + dense, then window,
window, full, window with experts (16, top-4, 4 held, a shared one), window 8,
q/k norms, rotary in window layers only, and the block with its slab.

Program and reference start from the same seeded leaves, matrices rounded to
bfloat16 as the configuration states them, and both compute in float32 here:
what is left between them is the order of float32 sums, so the tolerances
below are 2e-4 (as tests/test_mimo_v2.py's). What is SERVED must not depend on
the drafter at all: the same tokens with the model's own block, with an
oracle (every draft right: every step yields two), with a drafter that is
always wrong, and with the drafter taken out.
"""

from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from benchmark import family
from pytorch_operator_tpu.models import layer_list, mimo_v2
from pytorch_operator_tpu.models.serving import Drafter, preset
from pytorch_operator_tpu.parallel.moe import moe_held
from pytorch_operator_tpu.serving import Request, ServingEngine
from pytorch_operator_tpu.serving.engine import decode_steps

ROOT = Path(__file__).resolve().parents[1]
TINY = json.loads((ROOT / "tests/zz_benchmark/data/cells/config.tiny-exaone.json").read_text())
REAL = json.loads((ROOT / "benchmark/configs/k-exaone-236b-serve-ep8.json").read_text())
TOL = 2e-4
WINDOW = TINY["sliding_window"]

S = family.load("exaone_moe", "shape")
W = family.load("exaone_moe", "weights")
R = family.load("exaone_moe", "reference")
INSTALL = family.load("exaone_moe", "install")
FLOPS = family.load("exaone_moe", "flops")


def _setup(model=TINY, seed=0, **over):
    """(dims, program config, seeded params, key): float32 compute over
    bfloat16-rounded matrices on both sides."""
    import jax
    import jax.numpy as jnp

    d = W.dims(model)
    cfg = mimo_v2.make_config(
        INSTALL.config_base(d),
        {"decode": True, "max_decode_len": 128, "dtype": jnp.float32, "param_dtype": jnp.bfloat16, **over},
    )
    key = jax.random.key(seed)
    return d, cfg, W.make_params(d, key, jnp.bfloat16), key


def _reference(d, key, tokens):
    """(main logits [S, V], draft logits [S, V], near the main stack's edge [S], near the block's [S])."""
    import jax.numpy as jnp

    with R.highest():
        logits, near, draft, near_draft = R.make_forward(d)(
            key, jnp.asarray(tokens, jnp.int32), with_near=True, with_draft=True)
    return np.asarray(logits), np.asarray(draft), np.asarray(near), np.asarray(near_draft)


def _prompt(n, seed=1):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], (n,)).astype(np.int32)


def _serve(cfg, params, jobs, model=None, **engine):
    """Tokens served for ``jobs`` (prompt, budget), and the engine; ``model`` replaces the config's serving model."""
    with pytest.MonkeyPatch.context() as patch:
        if model is not None:
            patch.setattr(mimo_v2.MiMoV2Config, "serving_model", lambda self: model)
        eng = ServingEngine(cfg, params, **{"slots": 3, "chunk": 16, "block": 4, **engine})
    for i, (prompt, new) in enumerate(jobs):
        eng.submit(Request(id=f"r{i}", prompt=prompt, max_new_tokens=new, submit_time=time.time()))
    done = {r.id: r.tokens for r in eng.run_until_drained()}
    return [done[f"r{i}"] for i in range(len(jobs))], eng


# ---- (a) prefill, then the verifying steps, against the reference's full forward ----

PROMPTS = [WINDOW - 3, WINDOW, 5 * WINDOW + 1]  # shorter than, equal to, several times the window (and two chunks)


@pytest.mark.parametrize("prompt_len", PROMPTS)
def test_engine_tokens_are_the_references_first_choice(prompt_len):
    """Through ``ServingEngine``, drafting: every served token's logit lies
    within TOL of the reference's best at its position (the benchmark's own
    measure), and drafts were accepted on the way."""
    d, cfg, params, key = _setup()
    prompt, new = _prompt(prompt_len), 40
    (tokens,), eng = _serve(cfg, params, [(prompt, new)])
    seq = np.concatenate([prompt, tokens])
    ref = _reference(d, key, seq)[0][prompt_len - 1 : prompt_len - 1 + new]
    gap = ref.max(-1) - ref[np.arange(new), np.asarray(tokens)]
    assert len(tokens) == new and gap.max() <= TOL, gap.max()
    stats = eng.stats()
    assert stats["mtp_drafts"] == stats["decode_row_steps"] > 0 and stats["mtp_accepted"] > 0


@pytest.mark.parametrize("prompt_len", PROMPTS)
def test_prefill_then_verifying_steps_give_the_references_main_and_draft_logits(prompt_len):
    """The forwards the engine's programs call, driven as it drives them
    (chunks of 16 with the token that follows each into one slot's row, the
    first draft, then verifying steps of two positions a row): the main
    stack's logits at BOTH positions of a step and the block's draft logits
    equal the reference's full forward, whatever was drafted; a position
    written under a wrong draft is rewritten by the next step."""
    import jax.numpy as jnp

    d, cfg, params, key = _setup()
    model, chunk, new = cfg.serving_model(), 16, 14
    seq = _prompt(prompt_len + new + 2, seed=2)
    ref, ref_draft, near, near_draft = _reference(d, key, seq)
    cache = model.init_cache(1, chunk)
    padded = -(-prompt_len // chunk) * chunk
    buf = np.zeros((padded + 1,), np.int32)
    buf[:prompt_len] = seq[:prompt_len]
    for start in range(0, padded, chunk):
        pos = (start + jnp.arange(chunk, dtype=jnp.int32))[None]
        hidden, cache, _ = model.prefill(
            params, cache, jnp.int32(0), jnp.asarray(buf[None, start : start + chunk + 1]), pos,
            jnp.int32(min(chunk, prompt_len - start)))
    h = hidden[:, (prompt_len - 1) % chunk]
    first = model.finish(params, cache, jnp.int32(0), h, jnp.int32(prompt_len - 1))
    assert np.abs(np.asarray(first[0]) - ref[prompt_len - 1]).max() <= TOL
    # The first draft: the block at the prompt's last position with the true next token.
    draft_logits, cache = model.drafter.first(
        params, cache, jnp.int32(0), h, jnp.int32(prompt_len - 1), jnp.asarray(seq[prompt_len : prompt_len + 1]))
    assert near_draft[prompt_len - 1] or np.abs(np.asarray(draft_logits[0]) - ref_draft[prompt_len - 1]).max() <= TOL
    # Verifying steps, teacher-forced on ``seq``: even steps carry the RIGHT draft and keep both positions, odd
    # steps a wrong one and keep the first only. The positions the block saw next tokens for are the sequence's.
    at, step = prompt_len, 0
    live = jnp.ones((1,), bool)
    while at + 2 < prompt_len + new:
        right = step % 2 == 0
        toks = jnp.asarray([[seq[at], seq[at + 1] if right else (seq[at + 1] + 1) % 256]], jnp.int32)
        positions = jnp.asarray([[at, at + 1]], jnp.int32)
        logits, hidden, cache, _ = model.drafter.verify(params, cache, toks, positions)
        keep = 2 if right else 1
        for s in range(keep):
            assert near[at + s] or np.abs(np.asarray(logits[0, s]) - ref[at + s]).max() <= TOL, (at, s)
        chosen = jnp.asarray([[seq[at + 1], seq[at + 2]]], jnp.int32)
        draft_logits, cache, counts = model.drafter.draft(
            params, cache, hidden, chosen, positions, jnp.asarray([right]), live)
        last = at + keep - 1
        assert near[last] or near_draft[last] or np.abs(np.asarray(draft_logits[0]) - ref_draft[last]).max() <= TOL, (at, keep)
        assert int(counts["mtp_drafts"]) == 1 and int(counts["mtp_accepted"]) == int(right)
        at, step = at + keep, step + 1
    assert step >= 6


# ---- (b) the chip's share of a sparse layer ----


def test_the_eight_shares_of_a_sparse_layer_with_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Experts [4 i, 4 i + 4) of the router's 16 for i in 0..3 (the tiny
    cut's eight-way share at its own size is four shares of four; eight of
    two each as well): the routed parts of all shares, plus the shared
    expert counted ONCE, equal the uncut reference layer."""
    import jax
    import jax.numpy as jnp

    d, cfg, _, key = _setup()
    x = jax.random.normal(jax.random.key(5), (24, d["D"]), jnp.float32)
    kind = d["kinds"][1]
    whole = R.stated(W.make_layer(d, key, jnp.int32(1), kind, jnp.bfloat16, held=(0, d["E"])))
    with R.highest():
        routed_whole, _ = R.routed(x, whole["moe"], d, (0, d["E"]))
        want = np.asarray(R.swiglu(x, whole["shared"]) + routed_whole)
    for n in (4, 2):  # four shares of four, eight shares of two
        total = np.asarray(R.swiglu(x, whole["shared"]))
        for first in range(0, d["E"], n):
            w = W.make_layer(d, key, jnp.int32(1), kind, jnp.bfloat16, held=(first, n))["moe"]
            with R.highest():
                y, _ = moe_held(jax.tree.map(lambda a: a.astype(jnp.float32), w), x, top_k=d["k"],
                                experts_held=(first, n), weight_scale=d["scale"])
            total = total + np.asarray(y)
        assert np.abs(total - want).max() <= TOL * max(1.0, np.abs(want).max()), n


# ---- (c) what is served does not depend on the drafter ----


def _oracle(model, wrong=False) -> Drafter:
    """A drafter that asks the main stack itself (its ``decode``, the cache it
    writes thrown away): every draft is the token the next step will choose;
    ``wrong``: never (the choice's logits negated)."""
    import jax.numpy as jnp

    L, sign = model.cfg.max_decode_len, -1.0 if wrong else 1.0

    def ask(params, cache, tok, pos):
        logits, _, _ = model.decode(params, cache, tok[:, None], jnp.minimum(pos, L - 1)[:, None])
        return sign * logits

    def draft(params, cache, hidden, chosen, positions, accepted, live):
        tok = jnp.where(accepted, chosen[:, 1], chosen[:, 0])
        pos = jnp.where(accepted, positions[:, 1], positions[:, 0]) + 1
        counts = {**model.counts, "mtp_drafts": jnp.sum(live, dtype=jnp.int32),
                  "mtp_accepted": jnp.sum(accepted & live, dtype=jnp.int32)}
        return ask(params, cache, tok, pos), cache, counts

    def first(params, cache, slot, h, position, first_token):
        slots = cache["mtp"]["k"].shape[0]
        tok = jnp.zeros((slots,), jnp.int32).at[slot].set(first_token[0])
        pos = jnp.zeros((slots,), jnp.int32).at[slot].set(position + 1)
        return ask(params, cache, tok, pos)[slot][None], cache

    return Drafter(verify=model.drafter.verify, draft=draft, first=first)


# Prompts that end inside a chunk, on its edge and past it, whose answers wrap the ring of 24 positions.
JOBS = [(5, 9), (16, 30), (3, 41), (33, 2), (17, 26), (40, 1), (9, 33)]


@pytest.fixture(scope="module")
def plain_tokens():
    """What the model serves with the drafter taken out: one token a step."""
    d, cfg, params, key = _setup()
    jobs = [(_prompt(p, seed=10 + i), n) for i, (p, n) in enumerate(JOBS)]
    model = dataclasses.replace(cfg.serving_model(), drafter=None)
    tokens, eng = _serve(cfg, params, jobs, model=model)
    stats = eng.stats()
    assert stats["decode_tokens"] <= stats["decode_row_steps"] and "mtp_accept_pct" in stats
    return cfg, params, jobs, tokens


@pytest.mark.parametrize("drafter", ["natural", "oracle", "always_wrong"])
def test_served_tokens_are_those_of_the_model_without_a_drafter(plain_tokens, drafter):
    cfg, params, jobs, want = plain_tokens
    model = cfg.serving_model()
    if drafter != "natural":
        model = dataclasses.replace(model, drafter=_oracle(model, wrong=drafter == "always_wrong"))
    got, eng = _serve(cfg, params, jobs, model=model)
    assert got == want
    stats = eng.stats()
    delivered = sum(n - 1 for _, n in JOBS)
    assert stats["decode_tokens"] == delivered and stats["mtp_drafts"] == stats["decode_row_steps"]
    if drafter == "oracle":
        # Every verified draft was right: each row-step a row could use yielded two (a budget's odd last token one).
        assert stats["mtp_accepted"] == stats["mtp_drafts"] and stats["mtp_accept_pct"] == 100.0
        assert stats["decode_yield_pct"] > 100.0
    elif drafter == "always_wrong":
        assert stats["mtp_accepted"] == 0 and stats["decode_yield_pct"] <= 100.0
    else:
        assert 0 < stats["mtp_accepted"] < stats["mtp_drafts"]


def test_a_budget_that_ends_on_an_accepted_pair_drops_the_surplus_token():
    """An oracle's every step yields two: a row with an EVEN number of
    tokens to decode after its first ends inside a pair, and the pair's
    second token is not delivered."""
    d, cfg, params, key = _setup()
    model = cfg.serving_model()
    oracle = dataclasses.replace(model, drafter=_oracle(model))
    jobs = [(_prompt(7, seed=3), 6), (_prompt(7, seed=3), 7), (_prompt(7, seed=3), 12)]
    (a, b, c), eng = _serve(cfg, params, jobs, model=oracle)
    assert (len(a), len(b), len(c)) == (6, 7, 12) and a == c[:6] and b == c[:7]


def test_the_engines_counters_are_what_the_device_did():
    """Positions advance on the device: the row-steps, the positions live and
    the blocks attended come from the dispatch's own tallies. With an oracle
    a row advances two a step, so its live positions grow twice as fast as
    with a drafter that is always wrong, over the same served tokens."""
    d, cfg, params, key = _setup()
    model = cfg.serving_model()
    job = [(_prompt(6, seed=4), 33)]
    stats = {}
    for name, wrong in (("oracle", False), ("wrong", True)):
        (tokens,), eng = _serve(cfg, params, job, model=dataclasses.replace(model, drafter=_oracle(model, wrong)), block=8)
        stats[name] = eng.stats()
        assert len(tokens) == 33
    fast, slow = stats["oracle"], stats["wrong"]
    assert fast["decode_tokens"] == slow["decode_tokens"] == 32
    assert fast["decode_row_steps"] < slow["decode_row_steps"] and fast["decode_steps"] * 2 >= 32
    # One row from position 6: a step at position p has its deepest query at p + 1, so p + 2 positions live.
    for got, per_step in ((fast, 2), (slow, 1)):
        steps = got["decode_row_steps"]
        want = sum(min(6 + per_step * s + 1, 127) + 1 for s in range(steps))
        assert got["decode_live_positions"] == want and got["decode_attended_positions"] >= want
        assert got["mtp_drafts"] == steps


def test_a_drafting_engine_compiles_its_three_programs_and_no_more():
    """Five requests through three slots, admissions at several boundaries:
    the chunk's program, the head's (which also leaves the first draft) and
    the verifying ``decode_block``, each one compiled form, and nothing eager
    beside them; the row's draft lives in a donated array of its own."""
    import jax

    from pytorch_operator_tpu.runtime.backend import compile_counts

    cfg = preset("k-exaone-tiny", decode=True, max_decode_len=64)
    eng = ServingEngine(cfg, mimo_v2.init_params(cfg, jax.random.key(0)), slots=3, chunk=8, block=4)
    before = sum(compile_counts().values())
    rng = np.random.default_rng(0)
    for i, (p, n) in enumerate([(5, 7), (13, 9), (8, 1), (21, 5), (3, 12)]):
        eng.submit(Request(id=f"c{i}", prompt=rng.integers(0, 256, (p,)).astype(np.int32), max_new_tokens=n, submit_time=time.time()))
    assert sorted(len(r.tokens) for r in eng.run_until_drained()) == [1, 5, 7, 9, 12]
    assert sum(compile_counts().values()) - before == 3
    assert [f._cache_size() for f in (eng._prefill_chunk, eng._prefill_chunk_head, eng._decode_block)] == [1, 1, 1]
    assert eng._draft.shape == (3,) and eng.stats()["admit_rounds"] > 1


def test_a_drafting_model_is_refused_a_temperature():
    d, cfg, params, key = _setup()
    with pytest.raises(ValueError, match="drafts.*temperature"):
        ServingEngine(cfg, params, slots=2, chunk=16, block=4, temperature=0.7)


def test_a_dispatch_is_sized_between_half_and_all_of_what_the_rows_have_left():
    """``decode_steps`` with two tokens a step at most: a full batch runs to
    the EARLIEST step at which its shortest row can end, never past the most
    steps its longest row can use."""
    assert decode_steps([40, 90], 0, 64, 2) == (20, "budget")
    assert decode_steps([40, 90], 0, 64) == (40, "budget")
    assert decode_steps([41, 90], 0, 64, 2) == (21, "budget")
    assert decode_steps([3, 5], 0, 64, 2) == (5, "budget")  # no row can use a sixth step
    assert decode_steps([200, 300], 1, 64, 2) == (8, "quantum")
    assert decode_steps([400, 300], 0, 64, 2) == (64, "ceiling")


# ---- (d) a ring under a position that is written and then given up ----


def test_a_rings_rejected_position_is_rewritten_before_anything_attends_it():
    """``write_positions`` with a verifying step's two positions a row, on a
    ring of window + chunk entries: after [p, p + 1] and then [p + 1, p + 2]
    (the draft at p + 1 was given up) the ring is, entry for entry, what
    writing p, p + 1, p + 2 once each with the kept values gives, and the
    entry the given-up write replaced held a position no later query sees."""
    import jax
    import jax.numpy as jnp

    window, chunk, rows = 8, 4, 3
    ring = window + chunk
    blank = {"k": jnp.zeros((rows, 2, ring, 16)), "v": jnp.zeros((rows, 2, ring, 16)),
             "pos": jnp.full((rows, ring), -1, jnp.int32)}
    draw = lambda i, s: jax.random.normal(jax.random.key(i), (rows, 2, s, 16))  # noqa: E731
    start = jnp.asarray([5, 22, 11], jnp.int32)  # row 1's ring of 12 has wrapped: 22, 23 -> entries 10, 11; 24 -> entry 0

    def filled(cache):  # every row's positions up to its start, one at a time
        for p in range(int(start.max())):
            at = jnp.minimum(p, start - 1)[:, None]
            cache = layer_list.write_positions(cache, draw(100 + p, 1), draw(200 + p, 1), at)
        return cache

    base = filled(blank)
    k_kept, v_kept, k_bad, v_bad = draw(1, 3), draw(2, 3), draw(3, 1), draw(4, 1)
    at = lambda o: start[:, None] + jnp.arange(o, o + 2)[None, :]  # noqa: E731
    # The step that drafts wrongly: position p kept, p + 1 written from the wrong draft ...
    given_up = layer_list.write_positions(
        base, jnp.concatenate([k_kept[:, :, :1], k_bad], 2), jnp.concatenate([v_kept[:, :, :1], v_bad], 2), at(0))
    # ... which replaced the entry of position p + 1 - ring, outside every later query's window.
    replaced = np.asarray(jnp.take_along_axis(base["pos"], (start[:, None] + 1) % ring, axis=1))[:, 0]
    assert all(r < 0 or int(s) - r >= window for r, s in zip(replaced, start)) and ring > window
    # The next step starts AT p + 1 and writes it again, with p + 2.
    after = layer_list.write_positions(given_up, k_kept[:, :, 1:], v_kept[:, :, 1:], at(1))
    once = base
    for s in range(3):
        once = layer_list.write_positions(once, k_kept[:, :, s : s + 1], v_kept[:, :, s : s + 1], start[:, None] + s)
    for name in ("k", "v", "pos"):
        assert np.array_equal(np.asarray(after[name]), np.asarray(once[name])), name


# ---- (e) the family's options leave the other model's trees alone ----


@pytest.mark.parametrize("name", ["mimo-tiny", "mimo-v2.5-ep16"])
def test_the_mimo_presets_build_the_parents_trees(name):
    """Parameter paths, shapes and dtypes, the cache's and the counters of the
    two MiMo presets, as the commit before K-EXAONE's options built them
    (tests/data_mimo_parent_trees.json, recorded from it)."""
    import jax

    want = json.loads((ROOT / "tests/data_mimo_parent_trees.json").read_text())[name]
    cfg = preset(name, decode=True)
    flat = lambda t: {jax.tree_util.keystr(p): [list(a.shape), str(a.dtype)]  # noqa: E731
                      for p, a in jax.tree_util.tree_leaves_with_path(t)}
    assert flat(jax.eval_shape(lambda k: mimo_v2.init_params(cfg, k), jax.random.key(0))) == want["params"]
    assert flat(jax.eval_shape(lambda: mimo_v2.init_cache(cfg, 4, 16))) == want["cache"]
    model = cfg.serving_model()
    assert sorted(model.counts) == want["counts"] and model.drafter is None and model.finish is None
    assert set(mimo_v2.cache_bytes(jax.eval_shape(lambda: mimo_v2.init_cache(cfg, 4, 16)))) == {
        "cache_full_bytes", "cache_window_bytes"}


# ---- (f) the served size, from shapes ----


def test_the_presets_are_the_configuration_files_and_their_bytes_are_the_shapes():
    import jax

    d = W.dims(REAL)
    cfg = preset("k-exaone-ep8", decode=True)
    assert cfg == mimo_v2.make_config(INSTALL.config_base(d), {"decode": True})
    assert preset("k-exaone-tiny", decode=True).layers == cfg.layers and cfg.serving_model().drafter is not None
    params = jax.eval_shape(lambda k: mimo_v2.init_params(cfg, k), jax.random.key(0))
    bench = jax.eval_shape(lambda k: W.make_params(d, k, cfg.param_dtype), jax.random.key(0))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)  # noqa: E731
    assert shapes(params) == shapes(bench)
    matrices = sum(a.size for a in jax.tree.leaves(params) if a.ndim >= 2)
    assert matrices == REAL["bytes"]["parameters"] == 4_543_217_664
    assert sum(S.parameters(d)["layers"]) + S.parameters(d)["mtp"] + S.parameters(d)["vocabulary"] == matrices
    sizes = mimo_v2.cache_bytes(jax.eval_shape(lambda: mimo_v2.init_cache(cfg, 96, 128)))
    assert sizes == {"cache_full_bytes": REAL["bytes"]["cache_full_bytes"], "cache_mtp_bytes": REAL["bytes"]["cache_mtp_bytes"],
                     "cache_window_bytes": REAL["bytes"]["cache_window_bytes"]}
    assert sum(sizes.values()) == 3_624_271_872
    # Every published width is the catalog's, and what is cut is what `reduced` names.
    row = next(json.loads(l) for l in open("/opt/skills/guides/model-configs/architectures.jsonl")
               if '"K-EXAONE-236B-A23B"' in l) if Path("/opt/skills/guides/model-configs/architectures.jsonl").is_file() else None
    if row is not None:
        changed = {k for k, v in row["config"].items() if REAL.get(k) != v}
        assert changed == set(REAL["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}


def test_the_least_bytes_of_a_step_count_every_weight_once_and_the_live_cache():
    """``flops.spec_step_bytes_min``: at no rows it is the weights of layers
    0-4, the block and the head's slice with the experts the counters say
    were touched; a row adds its live positions of two slabs and four rings."""
    d = W.dims(REAL)
    P = S.parameters(d)
    none = FLOPS.spec_step_bytes_min(REAL, slots=0, mean_positions=0, experts_touched=5 * 16)
    assert none == 2 * (sum(P["layers"]) + P["mtp"] + P["vocabulary"] // 2)
    row = FLOPS.spec_step_bytes_min(REAL, slots=1, mean_positions=1000, experts_touched=5 * 16) - none
    assert row == 2 * 2 * d["Hk"] * d["dh"] * (2 * 1000 + 4 * d["window"])
    fewer = FLOPS.spec_step_bytes_min(REAL, slots=0, mean_positions=0, experts_touched=5 * 15)
    assert none - fewer == 2 * 5 * 3 * d["D"] * d["Fe"]
    assert FLOPS.walk_step_bytes_min(REAL, slots=96, mean_positions=1000) == 96 * 2 * 2 * 2 * d["Hk"] * d["dh"] * 1000


# ---- the normal path: tpujob run -> supervisor -> workloads/serve.py -> ServingEngine ----


def test_tpujob_run_of_a_serve_job_with_the_preset_drafts_and_why_prints_the_acceptance(tmp_path):
    """``examples/serve-layer-pattern.yaml`` with ``k-exaone-tiny`` on a CPU
    device: the job answers its requests in full with verifying steps, its
    final record carries the drafts' counters and the block's gauge, ``tpujob
    why`` prints them, and a temperature is refused with a clear error."""
    import subprocess
    import sys
    import threading

    import yaml

    from pytorch_operator_tpu.serving import Spool

    job = yaml.safe_load((ROOT / "examples/serve-layer-pattern.yaml").read_text())
    job["metadata"]["name"] = "serve-drafting"
    template = job["spec"]["replica_specs"]["Master"]["template"]
    spool_dir = tmp_path / "spool"
    template["args"] = ["--config", "k-exaone-tiny", "--spool", str(spool_dir), "--slots", "2", "--chunk", "16",
                        "--block", "4", "--max-decode-len", "128", "--max-requests", "2", "--idle-timeout", "120",
                        "--json"]
    template["resources"] = {"cpu_devices": 1}
    (tmp_path / "job.yaml").write_text(yaml.safe_dump(job))
    sp, got = Spool(spool_dir), {}

    def client():
        for rid in [sp.submit(prompt_len=21, max_new_tokens=9), sp.submit(prompt=[3, 1, 4, 1, 5], max_new_tokens=12)]:
            got[rid] = sp.wait_response(rid, timeout=240)

    t = threading.Thread(target=client)
    t.start()
    cli = [sys.executable, "-m", "pytorch_operator_tpu.client.cli", "--state-dir", str(tmp_path / "state")]
    done = subprocess.run([*cli, "run", str(tmp_path / "job.yaml"), "--timeout", "240"], cwd=ROOT, capture_output=True,
                          text=True, timeout=300)
    t.join(timeout=60)
    log = "\n".join(p.read_text() for p in (tmp_path / "state" / "logs").glob("*.log"))
    assert done.returncode == 0 and not t.is_alive(), done.stdout[-1500:] + log[-3000:]
    assert sorted(len(r["tokens"]) for r in got.values()) == [9, 12]
    final = json.loads(log[log.index("[serve] done: ") + len("[serve] done: "):].splitlines()[0])
    assert final["config"] == "k-exaone-tiny" and final["mtp_drafts"] == final["decode_row_steps"] > 0
    assert final["cache_mtp_bytes"] > 0 and final["decode_tokens"] == 8 + 11 and "mtp_accept_pct" in final
    why = subprocess.run([*cli, "why", "serve-drafting"], cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert f"mtp_accepted {final['mtp_accepted']} of mtp_drafts {final['mtp_drafts']} row-step(s)" in why.stdout, why.stdout[-2000:]
    hot = subprocess.run(
        [sys.executable, "-m", "pytorch_operator_tpu.workloads.serve", "--config", "k-exaone-tiny", "--spool",
         str(tmp_path / "spool2"), "--slots", "2", "--chunk", "16", "--block", "4", "--max-decode-len", "128",
         "--temperature", "0.7", "--max-requests", "1"], cwd=ROOT, capture_output=True, text=True, timeout=240,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert hot.returncode != 0 and "drafts" in hot.stderr and "temperature" in hot.stderr, hot.stderr[-1500:]


def test_the_new_readers_count_a_record_import_no_jax_and_find_nothing_in_a_program_that_does_not_draft():
    """The four readers of the cell on a made-up record of a traced run,
    inside the harness (no JAX), and on the record of a program without the
    counters and scopes (the parent commit's): None, and nothing raised."""
    import subprocess
    import sys

    code = """
import json, sys
from benchmark import run, scope_reduce, mtp_reduce
scope_reduce.reduction = lambda ctx: {"busy_s": 3.8, "decode_steps": 150.0}
red = {"busy_s": 3.8, "scope_s": {"mtp": 0.38, "mtp_attn": 0.05, "mtp_moe": 0.3, "moe_shared": 0.2},
       "decode_scope_s": {"mtp": 0.3, "mtp_attn": 0.04, "mtp_moe": 0.24, "moe_shared": 0.15}, "decode_walk_s": 0.12, "decode_walk_events": 300.0}
mtp_reduce.reduction = lambda ctx: red
ctx = {"cell": {"name": "a-cell"}, "bench": run.BENCH, "device": {"device_kind": "TPU v5 lite"},
       "config": json.load(open("benchmark/configs/k-exaone-236b-serve-ep8.json")),
       "reports": [{"trace": {"program_s": {"prefill_chunk": 1.2, "decode_block": 2.6}}}],
       "final": {"decode_steps": 2000, "decode_row_steps": 180000, "decode_tokens": 250000, "decode_live_positions": 126000000,
                 "decode_attended_positions": 200000000, "decode_moe_experts_touched": 159800, "mtp_drafts": 180000,
                 "mtp_accepted": 84000, "mtp_accept_pct": 46.6667, "decode_yield_pct": 138.9}}
names = ("mtp_accept_pct", "mtp_share_pct", "spec_step_hbm_roofline_pct", "spec_walk_roofline_pct")
for name in names:
    print("VALUE", run.read_layer_metric(name + ".serve_tps", ctx))
red = {}
plain = {**ctx, "final": {"decode_steps": 2000, "decode_tokens": 180000, "decode_row_steps": 180000, "decode_live_positions": 126000000,
                          "decode_moe_experts_touched": 159800}}
for name in names:
    print("VALUE", run.read_layer_metric(name + ".serve_tps", plain))
assert "jax" not in sys.modules, "the harness imported JAX"
"""
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    values = [l.split(" ", 1)[1] for l in done.stdout.splitlines() if l.startswith("VALUE ")]
    assert values[4:] == ["None"] * 4
    accept, share, step, walk = (float(v) for v in values[:4])
    assert accept == 46.6667 and share == pytest.approx(10.0)
    # 90 rows at 700 live positions a row-step: 8.85 GB of weights + 0.65 GB of cache, 150 steps in 2.6 s
    d = W.dims(REAL)
    want = FLOPS.spec_step_bytes_min(REAL, slots=90, mean_positions=700, experts_touched=79.9)
    assert 9.3e9 < want < 9.7e9 and step == pytest.approx(100 * want * 150 / 2.6 / 819e9, rel=1e-6)
    assert walk == pytest.approx(100 * FLOPS.walk_step_bytes_min(REAL, slots=90, mean_positions=700) * 150 / 0.12 / 819e9, rel=1e-6)
    assert step < 100 and walk < 100 and d["V"] == 19200


def test_device_time_of_the_blocks_scopes_and_of_the_walks_kernel_inside_decode():
    """``mtp_reduce.reduce_ops`` on made-up events: the block's scopes over the
    window and inside ``decode_block``, the walk's kernel by its own name there
    (the main stack's and the block's; a chunk's program holds none), and
    nothing of the main stack's scopes charged to the block."""
    from benchmark.mtp_reduce import reduce_ops
    from benchmark.scope_reduce import reduce_ops as by_scope

    body = "jit(decode_block)/while/body"
    paths = {
        "a": f"{body}/attn_full/cache_attention_decode/pallas_call", "m": f"{body}/moe/dot",
        "ma": f"{body}/mtp/mtp_attn/cache_attention_decode/pallas_call", "mw": f"{body}/mtp/mtp_attn/cache_write/cache_write_rows",
        "mm": f"{body}/mtp/mtp_moe/moe_shared/dot", "mh": f"{body}/mtp/head/dot_general", "h": f"{body}/head/dot_general",
        "p": "jit(prefill_chunk)/mtp/mtp_moe/dot", "while.3": "jit(decode_block)/while",
    }
    ops, t = [], 0
    for _ in range(3):  # three steps
        for name, ns in (("a", 900), ("m", 6_000), ("ma", 1_100), ("mw", 50), ("mm", 2_000), ("mh", 300), ("h", 700)):
            ops.append((name, t, t + ns))
            t += ns + 100
    ops += [("p", t, t + 4_000), ("while.3", 0, t)]
    red = reduce_ops([ops], paths)
    assert red["scope_s"]["mtp"] == pytest.approx(3 * 3_450e-9 + 4_000e-9) and red["decode_scope_s"]["mtp"] == pytest.approx(3 * 3_450e-9)
    assert red["decode_scope_s"]["mtp_attn"] == pytest.approx(3 * 1_150e-9) and red["decode_scope_s"]["mtp_moe"] == pytest.approx(6_000e-9)
    assert red["decode_walk_s"] == pytest.approx(3 * 2_000e-9) and red["decode_walk_events"] == 6
    main = by_scope([ops], paths)
    assert main["scope_s"]["attn_full"] == pytest.approx(2_700e-9) and main["scope_s"]["moe"] == pytest.approx(18_000e-9)
    assert main["decode_steps"] == 3  # the two head products, one event a step each
