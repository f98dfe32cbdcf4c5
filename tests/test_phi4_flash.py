"""The decoder-hybrid-decoder serving model (models/phi4_flash.py) against the
benchmark's plain reference (benchmark/families/phi4_flash/reference.py), at a
small size with the real structure: 8 layers — two Mamba-1 layers and two
windows of 8, the Mamba layer that hands its scan output on, THE full layer,
a gated memory unit and a cross-attention layer that reads the full layer's
slab; 8 query and 4 key/value heads of 8 as pairs, differential attention
without positions, LayerNorm with bias, a tied head.

Program and reference start from the same seeded leaves, matrices rounded to
bfloat16 as the configuration states them, and both compute in float32 here:
what is left between them is the order of float32 sums (the blocked softmax
against the whole one, the products' accumulation),
so the tolerances below are 5e-4 on logits of unit size. A state that is not
reset, a stale slab, a wrong pair, lambda or window moves a logit by 0.05 or
more.
"""

from __future__ import annotations

import json
import re
import time
from pathlib import Path

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from benchmark import family
from pytorch_operator_tpu.models import phi4_flash, ssm
from pytorch_operator_tpu.models.serving import families, preset
from pytorch_operator_tpu.serving import Request, ServingEngine

ROOT = Path(__file__).resolve().parents[1]
TINY = json.loads((ROOT / "tests/zz_benchmark/data/cells/config.tiny-phi4-flash.json").read_text())
CELL = json.loads((ROOT / "benchmark/configs/phi4-mini-flash-serve.json").read_text())
TOL = 5e-4
CHUNK = 16

W = family.load("phi4_flash", "weights")
R = family.load("phi4_flash", "reference")
INSTALL = family.load("phi4_flash", "install")
FLOPS = family.load("phi4_flash", "flops")


def _setup(model=TINY, seed=0, **over):
    """(dims, program config, seeded params, key): float32 compute over
    bfloat16-rounded matrices on both sides."""
    import jax
    import jax.numpy as jnp

    d = W.dims(model)
    cfg = phi4_flash.make_config(
        INSTALL.config_base(d),
        {"decode": True, "max_decode_len": 128, "dtype": jnp.float32, "param_dtype": jnp.bfloat16, **over},
    )
    key = jax.random.key(seed)
    return d, cfg, W.make_params(d, key, jnp.bfloat16), key


def _reference_logits(d, key, tokens):
    import jax.numpy as jnp

    with R.highest():
        return np.asarray(R.make_forward(d)(key, jnp.asarray(tokens, jnp.int32)))


def _prompt(n, seed=1):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], (n,)).astype(np.int32)


def _serve(cfg, params, jobs, **engine):
    eng = ServingEngine(cfg, params, **{"slots": 3, "chunk": CHUNK, "block": 4, **engine})
    for i, (prompt, new) in enumerate(jobs):
        eng.submit(Request(id=f"r{i}", prompt=prompt, max_new_tokens=new, submit_time=time.time()))
    done = {r.id: r.tokens for r in eng.run_until_drained()}
    return [done[f"r{i}"] for i in range(len(jobs))], eng


_JITTED = {}


def _jit(model, name):
    """The model's forward ``name``, compiled once a configuration."""
    import jax

    if (model.cfg, name) not in _JITTED:
        _JITTED[model.cfg, name] = jax.jit(getattr(model, name))
    return _JITTED[model.cfg, name]


def _chunks(model, params, cache, slot, prompt, pad=0, prefill=None):
    """Prefill ``prompt`` into row ``slot`` as the engine does, the last
    chunk padded with ``pad``; returns (the last chunk's ``hidden``, cache)."""
    import jax.numpy as jnp

    p = len(prompt)
    padded = -(-p // CHUNK) * CHUNK
    buf = np.full((padded,), pad, np.int32)
    buf[:p] = prompt
    for start in range(0, padded, CHUNK):
        pos = (start + jnp.arange(CHUNK, dtype=jnp.int32))[None]
        hidden, cache, _ = (prefill or _jit(model, "prefill"))(
            params, cache, jnp.int32(slot), jnp.asarray(buf[None, start : start + CHUNK]), pos,
            jnp.int32(min(CHUNK, p - start)))
    return hidden, cache


def _finish(model, params, cache, slot, hidden, p):
    """The first token's logits as the head program computes them."""
    import jax
    import jax.numpy as jnp

    h = jax.tree.map(lambda a: a[:, (p - 1) % CHUNK], hidden)
    return np.asarray(_jit(model, "finish")(params, cache, jnp.int32(slot), h, jnp.int32(p - 1)))[0]


def _decode(model, params, cache, tokens, positions):
    """One decode step over the slots: row r's token at its position."""
    import jax.numpy as jnp

    logits, cache, _ = _jit(model, "decode")(
        params, cache, jnp.asarray(tokens, jnp.int32)[:, None], jnp.asarray(positions, jnp.int32)[:, None])
    return np.asarray(logits), cache


# ---- (a) chunked prefill with the self-decoder only, the finish, then decode, against the reference's full forward ----

PROMPTS = [1, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 5]


@pytest.mark.parametrize("prompt_len", PROMPTS)
def test_engine_tokens_are_the_references_first_choice(prompt_len):
    """Through ``ServingEngine``: every served token's logit lies within TOL
    of the reference's best at its position (the benchmark's own measure)."""
    d, cfg, params, key = _setup()
    prompt, new = _prompt(prompt_len), 20
    (tokens,), eng = _serve(cfg, params, [(prompt, new)])
    seq = np.concatenate([prompt, tokens])
    ref = _reference_logits(d, key, seq)[prompt_len - 1 : prompt_len - 1 + new]
    gap = ref.max(-1) - ref[np.arange(new), np.asarray(tokens)]
    assert len(tokens) == new and gap.max() <= TOL, gap.max()
    s = eng.stats()
    assert s["prefill_state_resets"] == s["prefill_cross_tokens"] == s["admitted"] == 1
    assert s["decode_prefill_state_resets"] == s["decode_prefill_cross_tokens"] == 0


@pytest.mark.parametrize("prompt_len", PROMPTS)
def test_prefill_finish_then_decode_logits_equal_the_full_forward_whatever_the_pad(prompt_len):
    """The logits themselves, the prompt's last position from ``finish`` and
    ten decode steps through the cache, against the reference's forward over
    the whole sequence; and another pad token gives the same bits."""
    d, cfg, params, key = _setup()
    model, new = cfg.serving_model(), 10
    prompt = _prompt(prompt_len, seed=2)
    got = {}
    for pad in (0, 99):
        hidden, cache = _chunks(model, params, model.init_cache(2, CHUNK), 1, prompt, pad=pad)
        logits = [_finish(model, params, cache, 1, hidden, prompt_len)]
        seq = list(prompt)
        for step in range(new):
            seq.append(int(logits[-1].argmax()))
            both, cache = _decode(model, params, cache, [0, seq[-1]], [0, prompt_len + step])
            logits.append(both[1])
        got[pad] = (np.stack(logits), seq)
    assert got[0][1] == got[99][1] and np.array_equal(got[0][0], got[99][0])
    ref = _reference_logits(d, key, got[0][1])[prompt_len - 1 :]
    assert np.abs(got[0][0] - ref).max() <= TOL, np.abs(got[0][0] - ref).max()


@pytest.mark.parametrize("prompt_len", [CHUNK - 1, 3 * CHUNK + 5])
def test_a_prefill_that_runs_the_cross_decoder_on_every_token_gives_the_skips_first_token_logits(prompt_len):
    """``forward`` in chunk form runs all the layers on every prompt token
    (twice the weights a chunk); the engine's prefill stops at the
    self-decoder and ``finish`` runs the rest for the last token alone. Same
    cache, and the same logits at the prompt's last position, at every
    position in fact: the cross-decoder writes nothing."""
    import jax

    d, cfg, params, key = _setup()
    model = cfg.serving_model()
    prompt = _prompt(prompt_len, seed=3)
    every = jax.jit(lambda params, cache, slot, toks, pos, n_real: phi4_flash.forward(
        cfg, params, cache, toks, pos, slot=slot, n_real=n_real))
    hidden, cache = _chunks(model, params, model.init_cache(2, CHUNK), 1, prompt)
    full_hidden, full_cache = _chunks(model, params, model.init_cache(2, CHUNK), 1, prompt, prefill=every)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(full_cache)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    skip = _finish(model, params, cache, 1, hidden, prompt_len)
    at = (prompt_len - 1) % CHUNK
    no_skip = np.asarray(model.logits(params, full_hidden[:, at]))[0]
    assert np.abs(skip - no_skip).max() <= 1e-5, np.abs(skip - no_skip).max()
    ref = _reference_logits(d, key, prompt)[-1]
    assert np.abs(no_skip - ref).max() <= TOL
    # what the counters say of each
    _, _, counts = every(params, model.init_cache(2, CHUNK), 1, np.zeros((1, CHUNK), np.int32),
                         np.arange(CHUNK, dtype=np.int32)[None], 5)
    assert int(counts["prefill_cross_tokens"]) == 1 + 5 and int(counts["prefill_state_resets"]) == 1


# ---- (b) the scan's two forms ----


def _scan_inputs(seed, S, dt_scale, C=24, N=8):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    dt = np.log1p(np.exp(f(S, C))) * dt_scale
    A = -np.exp(f(N, C))
    return f(S, C), f(S, N), f(S, N), dt.astype(np.float32), A.astype(np.float32), f(N, C)


@pytest.mark.parametrize("entry", ["zero", "nonzero"])
@pytest.mark.parametrize("dt_scale", [1e-4, 0.05, 30.0], ids=["decay-near-1", "trained-range", "decay-near-0"])
@pytest.mark.parametrize("S", [1, 16, 24, 128, 300])
def test_the_chunk_form_of_the_scan_equals_the_sequential_recurrence(S, dt_scale, entry, monkeypatch):
    """The chunk's kernel (8 tokens an iteration of its loop; a chunk of 1 or
    of 300 is padded with steps that move nothing, and 300 tokens are two of
    its grid steps, the state carried between them; 384 channels are three
    blocks of 128 where the tile is narrowed to that) against the recurrence
    written out in float64, from an entry state that
    is not zero, with decays ``exp(dt A)`` near 1 (nothing forgotten over
    the chunk), in a trained model's range, and near 0 (``dt A`` down to
    -1,000: the decay underflows to 0 and nothing overflows); and one
    ``scan_step`` over two rows is a chunk of one token for each."""
    import jax
    import jax.numpy as jnp

    u, Bm, Cm, dt, A, state = _scan_inputs(S, S, dt_scale, C=384 if S == 300 else 24)
    state = state if entry == "nonzero" else np.zeros_like(state)
    if S == 300:
        monkeypatch.setattr(ssm, "TILE", (256, 128))
    y, out = jax.jit(lambda *a: ssm.scan_chunk(*a))(*(jnp.asarray(a) for a in (u, Bm, Cm, dt, A, state)))
    s, want = state.astype(np.float64), []
    for t in range(S):
        s = np.exp(dt[t].astype(np.float64) * A) * s + (dt[t] * u[t])[None, :].astype(np.float64) * Bm[t][:, None]
        want.append((s * Cm[t][:, None]).sum(0))
    want = np.stack(want)
    assert bool(jnp.isfinite(y).all()) and np.abs(np.asarray(y) - want).max() <= 2e-5 * max(1.0, np.abs(want).max())
    assert np.abs(np.asarray(out) - s).max() <= 2e-5 * max(1.0, np.abs(s).max())
    both = lambda a: jnp.asarray(np.stack([a[0], a[-1]]))
    y2, s2 = phi4_flash.scan_step(both(u), both(Bm), both(Cm), both(dt), jnp.asarray(A), jnp.asarray(np.stack([state, state])))
    for row, t in ((0, 0), (1, S - 1)):
        y1, s1 = phi4_flash.scan_chunk(*(jnp.asarray(a[t : t + 1]) for a in (u, Bm, Cm, dt)), jnp.asarray(A), jnp.asarray(state))
        assert np.allclose(np.asarray(y2[row]), np.asarray(y1[0]), rtol=1e-6, atol=1e-6) and np.allclose(np.asarray(s2[row]), np.asarray(s1), rtol=1e-6, atol=1e-6)


def test_a_zero_step_size_freezes_the_state():
    import jax.numpy as jnp

    u, Bm, Cm, dt, A, state = (jnp.asarray(a) for a in _scan_inputs(5, 16, 0.05))
    _, moved = phi4_flash.scan_chunk(u, Bm, Cm, dt, A, state)
    _, frozen = phi4_flash.scan_chunk(u, Bm, Cm, dt.at[7:].set(0.0), A, state)
    _, seven = phi4_flash.scan_chunk(u[:7], Bm[:7], Cm[:7], dt[:7], A, state)
    # Seven tokens and then nine that move nothing, against seven tokens.
    assert np.abs(np.asarray(frozen) - np.asarray(seven)).max() <= 1e-6 < np.abs(np.asarray(moved) - np.asarray(frozen)).max()


def test_the_mamba_layer_equals_the_references_from_chunks_and_from_steps():
    """One Mamba-1 layer over 40 tokens: as 2.5 chunks of 16 through the
    cache (the last one padded), and as 40 decode steps, against the
    reference's sequential recurrence: the output and the memory ``m``."""
    import jax.numpy as jnp

    d, cfg, params, _ = _setup()
    w = params["layers"][0]["ssm"]
    x = jnp.asarray(np.random.default_rng(4).standard_normal((40, d["D"])), jnp.float32)
    with R.highest():
        want, want_m = (np.asarray(a) for a in R.mamba(x, R.stated(w), d))
    cache = phi4_flash.init_cache(cfg, 2, CHUNK)["layer_0"]
    outs, ms = [], []
    for start in range(0, 48, CHUNK):
        piece = jnp.zeros((1, CHUNK, d["D"]), jnp.float32).at[0, : min(CHUNK, 40 - start)].set(x[start : start + CHUNK])
        out, m, cache = phi4_flash.ssm_mixer(cfg, w, cache, piece, slot=jnp.int32(1), fresh=jnp.bool_(start == 0),
                                             n_real=jnp.int32(min(CHUNK, 40 - start)))
        outs.append(out[0]), ms.append(m[0])
    assert np.abs(np.concatenate(outs)[:40] - want).max() <= 1e-5 and np.abs(np.concatenate(ms)[:40] - want_m).max() <= 1e-5
    stepped = phi4_flash.init_cache(cfg, 2, CHUNK)["layer_0"]
    for t in range(40):
        out, m, stepped = phi4_flash.ssm_mixer(cfg, w, stepped, jnp.stack([x[t], x[t]])[:, None])
        assert np.abs(np.asarray(out[1, 0]) - want[t]).max() <= 1e-5
    # the chunks left the row where 40 steps leave it: the pad moved nothing
    for name in ("conv", "state"):
        assert np.abs(np.asarray(cache[name][1]) - np.asarray(stepped[name][1])).max() <= 1e-5
        assert not np.asarray(cache[name][0]).any()  # and touched no other row


# ---- (c) differential attention through the cache against whole score matrices ----


def _lambdas(w, sign):
    """The layer's four lambda vectors set so that ``lam`` comes out with
    ``sign``: exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init is about +1.1 or -0.9."""
    import jax.numpy as jnp

    d = w["lambda_q1"].shape[0]
    big, none = jnp.full((d,), (0.6 / d) ** 0.5), jnp.zeros((d,))
    first, second = (big, none) if sign > 0 else (none, 1.3 * big)
    return {**w, "lambda_q1": first, "lambda_k1": first, "lambda_q2": second, "lambda_k2": second}


@pytest.mark.parametrize("sign", [+1, -1], ids=["lambda-positive", "lambda-negative"])
@pytest.mark.parametrize("kind", ["window", "full", "cross"])
def test_differential_attention_through_the_cache_equals_whole_score_matrices(kind, sign):
    """A layer's attention over 40 positions, written into its ring (window
    8, shorter than a chunk) or the slab in chunks and attended through
    ``ring_attend`` / ``cache_attention`` with the widened queries, then one
    more position as a decode step: against the reference's two softmaxes
    over whole ``[S, S]`` score matrices. A cross layer brings queries only
    and reads the full layer's slab."""
    import jax.numpy as jnp

    d, cfg, params, _ = _setup()
    layer = {"window": 1, "full": cfg.full_layer, "cross": cfg.n_layers - 1}[kind]
    w = _lambdas(params["layers"][layer]["attn"], sign)
    owner = params["layers"][cfg.full_layer]["attn"] if kind == "cross" else w
    S = 41
    x = jnp.asarray(np.random.default_rng(5).standard_normal((S, d["D"])), jnp.float32)
    with R.highest():
        k, v = R.keys_values(x, R.stated(owner), d)
        want = np.asarray(R.differential_attention(x, R.stated(w), d, jnp.int32(layer), k, v,
                                                   d["window"] if kind == "window" else None))
    cache = phi4_flash.init_cache(cfg, 2, CHUNK)[f"layer_{layer if kind == 'window' else cfg.full_layer}"]

    def attend(cache, piece, positions, slot):
        pk, pv = phi4_flash.paired_kv(cfg, owner, piece)
        cache = phi4_flash.write_kv(cache, pk, pv, positions, slot)
        if kind == "window":
            seen = phi4_flash.ring_attend(cfg, phi4_flash.paired_queries(cfg, w, piece), positions, cache, slot)
            return phi4_flash.differential(cfg, w, layer, seen, piece.dtype), cache
        return phi4_flash.slab_attention(cfg, w, layer, cache, piece, positions, slot), cache

    got = []
    for start in range(0, 48, CHUNK):  # 40 positions as chunks of row 1, the last one padded
        piece = jnp.zeros((1, CHUNK, d["D"]), jnp.float32).at[0, : min(CHUNK, 40 - start)].set(x[start : min(start + CHUNK, 40)])
        out, cache = attend(cache, piece, (start + jnp.arange(CHUNK))[None], jnp.int32(1))
        got.append(np.asarray(out[0]))
    assert np.abs(np.concatenate(got)[:40] - want[:40]).max() <= 1e-5
    both = jnp.stack([x[0], x[40]])[:, None]  # position 40 as a decode step beside a parked row
    out, cache = attend(cache, both, jnp.asarray([[0], [40]]), None)
    assert np.abs(np.asarray(out[1, 0]) - want[40]).max() <= 1e-5
    lam_init = 0.8 - 0.6 * np.exp(-0.3 * layer)
    lam = np.exp(float(w["lambda_q1"] @ w["lambda_k1"])) - np.exp(float(w["lambda_q2"] @ w["lambda_k2"])) + lam_init
    assert np.sign(lam) == sign and abs(lam) > 0.5


def test_a_window_layer_sees_its_window_and_no_further():
    """The ring holds window + chunk positions; a key 8 or more positions
    back (window 8) moves nothing, one 7 back does."""
    import jax.numpy as jnp

    d, cfg, params, _ = _setup()
    assert phi4_flash.ring_len(cfg, CHUNK) == 32 and phi4_flash.ring_len(cfg, 6) == 18
    model = cfg.serving_model()
    base = _prompt(30, seed=6)
    far, near = base.copy(), base.copy()
    far[3], near[27] = (far[3] + 1) % 256, (near[27] + 1) % 256

    def window_out(prompt):
        x, _, _, _ = phi4_flash.self_decoder(
            cfg, params, model.init_cache(1, 32),
            jnp.asarray(np.pad(prompt, (0, 2))[None]), jnp.arange(32)[None], slot=jnp.int32(0), n_real=jnp.int32(30))
        return np.asarray(x[0, 29])

    # The Mamba layers carry every earlier token, so compare the window layer's own mixer instead of the stack.
    w = params["layers"][1]["attn"]
    x = jnp.asarray(np.random.default_rng(7).standard_normal((1, 32, d["D"])), jnp.float32)

    def mixer(x):
        cache = phi4_flash.init_cache(cfg, 1, 32)["layer_1"]
        k, v = phi4_flash.paired_kv(cfg, w, x)
        cache = phi4_flash.write_kv(cache, k, v, jnp.arange(32)[None], jnp.int32(0))
        seen = phi4_flash.ring_attend(cfg, phi4_flash.paired_queries(cfg, w, x), jnp.arange(32)[None], cache, jnp.int32(0))
        return np.asarray(seen[0, 29])

    assert np.array_equal(mixer(x), mixer(x.at[0, 21].add(1.0)))  # 29 - 21 = 8: outside
    assert not np.array_equal(mixer(x), mixer(x.at[0, 22].add(1.0)))  # 7 back: inside
    assert not np.array_equal(window_out(base), window_out(near)) and window_out(base).shape == (d["D"],)
    assert not np.array_equal(window_out(base), window_out(far))  # the scan remembers what the window forgot


# ---- (d) the slot's life: reuse, parked rows, what owns a leaf ----


def test_a_slot_reused_after_a_longer_request_equals_a_fresh_engine():
    """One slot serves a long request and then a short one: the second starts
    from zero state and sees none of the first one's ring or slab entries
    (its answers are those of an engine that never held the first)."""
    _, cfg, params, _ = _setup()
    first, second = (_prompt(70, seed=6), 40), (_prompt(11, seed=7), 30)
    (_, reused), eng = _serve(cfg, params, [first, second], slots=1)
    (fresh,), _ = _serve(cfg, params, [second], slots=1)
    s = eng.stats()
    assert s["admitted"] == s["prefill_state_resets"] == s["prefill_cross_tokens"] == 2 and reused == fresh


@pytest.mark.parametrize("broken", ["state", "slab"])
def test_a_state_that_is_not_reset_or_a_slab_that_is_not_written_changes_the_answer(monkeypatch, broken):
    """The same two requests through a forward that never zeroes a row's
    scan state, or whose prefill never writes the slab (so the cross layers
    attend the last occupant's row): the second request's tokens differ."""
    _, cfg, params, _ = _setup()
    first, second = (_prompt(70, seed=6), 40), (_prompt(11, seed=7), 30)
    (fresh,), _ = _serve(cfg, params, [second], slots=1)
    if broken == "state":
        mixer = phi4_flash.ssm_mixer
        monkeypatch.setattr(phi4_flash, "ssm_mixer", lambda *a, fresh=None, **k: mixer(*a, fresh=False, **k))
    else:
        write = phi4_flash.write_kv
        monkeypatch.setattr(phi4_flash, "write_kv", lambda cache, k, v, positions, slot: cache
                            if slot is not None and "pos" not in cache else write(cache, k, v, positions, slot))
    (_, stale), _ = _serve(cfg, params, [first, second], slots=1)
    assert stale != fresh


def test_parked_rows_leave_active_rows_logits_bit_identical():
    """Three slots, one active row: its decode logits are the same bits
    whether the other two rows stand empty at position 0 or hold the state,
    rings and slab rows a finished request left there; and the parked rows'
    state stays finite however long they step."""
    import jax
    import jax.numpy as jnp

    _, cfg, params, _ = _setup()
    model = cfg.serving_model()
    prompt = _prompt(21, seed=8)
    _, clean = _chunks(model, params, model.init_cache(3, CHUNK), 1, prompt)
    _, dirty = _chunks(model, params, model.init_cache(3, CHUNK), 0, _prompt(40, seed=9))
    _, dirty = _chunks(model, params, dirty, 2, _prompt(33, seed=10))
    _, dirty = _chunks(model, params, dirty, 1, prompt)
    tok = 7
    for step in range(40):
        a, clean = _decode(model, params, clean, [0, tok, 0], [0, 21 + step, 0])
        b, dirty = _decode(model, params, dirty, [5, tok, 9], [0, 21 + step, 0])
        assert np.array_equal(a[1], b[1]), step
        tok = int(a[1].argmax())
    assert all(bool(jnp.isfinite(leaf).all()) for leaf in jax.tree.leaves(dirty) if leaf.dtype != jnp.int32)


def test_one_slab_of_full_length_and_the_cross_decoder_owns_and_writes_no_leaf():
    """The cache tree: a leaf pair of ``max_decode_len`` positions under the
    full layer's name ONLY; the layers after it own nothing; ``finish`` (the
    cross-decoder on a token) returns logits and leaves every leaf as it was;
    a decode step changes nothing of the cross-decoder's, because there is
    nothing."""
    import jax

    _, cfg, params, _ = _setup()
    model = cfg.serving_model()
    cache = model.init_cache(3, CHUNK)
    kinds = dict(zip((f"layer_{i}" for i in range(cfg.n_layers)), cfg.layers))
    assert set(cache) == {n for n, k in kinds.items() if k not in (phi4_flash.GMU, phi4_flash.CROSS)}
    assert cfg.layers[cfg.full_layer] == phi4_flash.FULL and set(cfg.layers[cfg.full_layer + 1 :]) == {phi4_flash.GMU, phi4_flash.CROSS}
    long = [(name, leaf) for name, state in cache.items() for leaf in state.values()
            if leaf.ndim == 4 and leaf.shape[2] == cfg.max_decode_len]
    assert [name for name, _ in long] == [f"layer_{cfg.full_layer}"] * 2
    assert all(leaf.shape[0] == 3 for leaf in jax.tree.leaves(cache))  # every leaf leads with the slot axis
    hidden, cache = _chunks(model, params, cache, 1, _prompt(21, seed=8))
    before = jax.tree.map(np.asarray, cache)
    logits = _finish(model, params, cache, 1, hidden, 21)
    assert logits.shape == (cfg.vocab_size,)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(cache)):
        assert np.array_equal(a, np.asarray(b))
    assert set(hidden) == {"x", "m"} and hidden["m"].shape == (1, CHUNK, cfg.d_inner)
    big = phi4_flash.phi4_mini_flash(decode=True)
    assert big.layers.count(phi4_flash.MAMBA) == 8 and big.layers[16] == phi4_flash.MAMBA_MEMORY and big.layers[17] == phi4_flash.FULL
    assert big.layers.count(phi4_flash.WINDOW) == 8 and big.layers.count(phi4_flash.GMU) == 7 == big.layers.count(phi4_flash.CROSS)
    assert big.layers == W.dims(CELL)["kinds"] and big.full_readers == 8 and big.dt_rank == 160 and big.head_dim == 64


# ---- (e) the gauges, the counters, the interface ----


def test_cache_gauges_equal_the_configurations_arithmetic():
    import jax

    _, cfg, params, _ = _setup()
    eng = ServingEngine(cfg, params, slots=3, chunk=CHUNK, block=4)
    s, item = eng.stats(), 4  # float32 here
    assert s["cache_full_bytes"] == 3 * 2 * 2 * 128 * 16 * item  # slots x (k, v) x 2 pairs x 128 positions x 16
    assert s["cache_window_bytes"] == 2 * 3 * (2 * 2 * 32 * 16 * item + 32 * 4)  # 2 rings of 32 (+ their positions)
    assert s["cache_state_bytes"] == 3 * 3 * (8 * 128 * 4 + 3 * 128 * item) and s["cache_full_readers"] == 2
    big = phi4_flash.phi4_mini_flash(decode=True, max_decode_len=4096)
    sizes = phi4_flash.cache_gauges(big, jax.eval_shape(lambda: phi4_flash.init_cache(big, 96, 128)))
    assert sizes["cache_full_bytes"] == 96 * 4096 * 2560 * 2  # ONE slab: 2.01 GB
    assert sizes["cache_window_bytes"] == 96 * 8 * (640 * 2560 * 2 + 640 * 4)  # 2.52 GB
    assert sizes["cache_state_bytes"] == 96 * 9 * (16 * 5120 * 4 + 3 * 5120 * 2)  # 0.31 GB
    assert sizes == {**{k: CELL["bytes"][k] for k in ("cache_full_bytes", "cache_window_bytes", "cache_state_bytes")},
                     "cache_full_readers": 8}
    assert abs(sum(v for k, v in sizes.items() if k.endswith("_bytes")) / 1e9 - CELL["bytes"]["cache_gb"]) < 0.005


def test_the_engine_counts_what_an_admission_read_of_the_slab():
    """Three prompts of 5, 21 and 40 tokens: the chunks attend no slab, each
    finish attends its prompt's positions once a reader (2 here), rounded to
    the cache attention's blocks of 16 (an eighth of 128)."""
    _, cfg, params, _ = _setup()
    jobs = [(_prompt(p, seed=20 + p), 6) for p in (5, 21, 40)]
    _, eng = _serve(cfg, params, jobs)
    s = eng.stats()
    assert s["prefill_attended_positions"] == 2 * (16 + 32 + 48) and s["prefill_cross_tokens"] == s["admitted"] == 3
    assert s["prefill_tokens"] == 66 and s["prefill_head_chunks"] == 3 and s["prefill_chunks"] == 1 + 2 + 3
    eng.reset_stats()
    assert eng.stats()["prefill_cross_tokens"] == 0 and eng.stats()["prefill_state_resets"] == 0


def test_the_server_finds_the_family_by_its_presets():
    table = families()
    assert table["phi4-flash-tiny"][0] is phi4_flash and table["phi4-mini-flash"][0] is phi4_flash
    cfg = preset("phi4-mini-flash", decode=True, max_decode_len=4096, quantize=None, kv_quantize=None)
    assert (cfg.n_layers, cfg.d_model, cfg.d_inner, cfg.window, cfg.vocab_size) == (32, 2560, 5120, 512, 200_064)
    assert cfg.serving_model().finish is not None and cfg.serving_model().slab_reads is not None
    with pytest.raises(ValueError, match="unquantised"):
        preset("phi4-flash-tiny", decode=True, quantize="int8")
    with pytest.raises(ValueError, match="decode=True"):
        preset("phi4-flash-tiny").serving_model()
    with pytest.raises(ValueError, match="even count"):
        phi4_flash.phi4_flash_tiny(n_layers=7)


def test_weights_are_made_in_the_serving_dtype_and_count_what_the_configuration_states():
    """The program's own init and the benchmark's seeded leaves have the same
    paths, shapes and dtypes, at the tiny size as arrays and at the cell's
    size as shapes; the cell's tree counts the configuration file's
    parameters, and nothing is cut (``reduced`` empty, every catalog key as
    published)."""
    import jax
    import jax.numpy as jnp

    d, cfg, params, key = _setup(param_dtype=jnp.bfloat16)
    own = cfg.serving_model().init_params(key)
    spec = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
    assert spec(own) == spec(params)
    assert own["layers"][0]["ssm"]["in_proj"].dtype == jnp.bfloat16 and own["layers"][0]["ssm"]["A_log"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(own["layers"][0]["ssm"]["A_log"])[:, 0], np.log(np.arange(1, 9)), rtol=1e-6)
    big = phi4_flash.make_config(INSTALL.config_base(W.dims(CELL)), {"decode": True})
    shapes = jax.eval_shape(big.serving_model().init_params, key)
    seeded = jax.eval_shape(lambda k: W.make_params(W.dims(CELL), k, jnp.bfloat16), key)
    assert spec(shapes) == spec(seeded)
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == FLOPS.parameters(CELL) == CELL["bytes"]["parameters"] == 3_852_562_944
    assert abs(2 * n / 1e9 - CELL["bytes"]["weights_gb"]) < 0.01
    catalog = [json.loads(l) for l in open("/opt/skills/guides/model-configs/architectures.jsonl")
               if "Phi-4-mini-flash-reasoning" in l] if Path("/opt/skills/guides/model-configs/architectures.jsonl").is_file() else []
    for row in catalog:
        assert {k: CELL[k] for k in row["config"]} == row["config"] and CELL["source"] == row["source_url"]
    assert CELL["reduced"] == {} and set(CELL["assumed_sizes"]) == {"mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "head_dim"}


def test_decode_step_bytes_count_the_issues_arithmetic():
    """The family's least bytes of a decode step at the cell's size, 96 rows
    1,300 positions deep: 7.70 GB of weights, the one slab's live positions
    eight times over 5.11, the rings 2.01, the scan state read and written
    0.62: 15.4 GB, in the issue's shares."""
    shared = FLOPS.shared_kv_step_bytes_min(CELL, slots=96, mean_positions=1300)
    assert shared == 8 * 96 * 1300 * 5120 and abs(shared / 1e9 - 5.11) < 0.01
    ssm = FLOPS.ssm_step_bytes_min(CELL, slots=96)
    assert abs(ssm / 1e9 - (9 * 41.3e6 * 2 + 2 * 96 * 9 * (16 * 5120 * 4 + 3 * 5120 * 2)) / 1e9) < 0.01
    step = FLOPS.decode_step_bytes_min(CELL, slots=96, mean_positions=1300)
    assert abs(step / 1e9 - 15.45) < 0.05 and 0.32 < shared / step < 0.34 and 0.49 < 7.705e9 / step < 0.51
    assert FLOPS.decode_step_bytes_min(CELL, slots=96, mean_positions=300) < step - 4e9
    assert 7.0e9 < FLOPS.forward_flops_per_token(CELL, 1300) < 9e9


# ---- the reduction of a trace by this family's scopes ----


def test_device_time_of_the_cross_decoders_scopes_overall_and_inside_decode():
    from benchmark.xdec_reduce import reduce_ops

    paths = {"x": "jit(decode_block)/while/body/attn_cross/while/body/dot", "g": "jit(decode_block)/while/body/gmu/dot",
             "f": "jit(decode_block)/while/body/attn_full/while/body/dot", "w": "jit(prefill_chunk)/attn_full/dynamic_update_slice",
             "h": "jit(prefill_chunk_head)/attn_cross/while/body/dot", "while.2": "jit(decode_block)/while"}
    ops, t = [], 0
    for _ in range(4):
        for name, ns in (("f", 1_000), ("g", 2_000), ("x", 5_000)):
            ops.append((name, t, t + ns))
            t += ns + 100
    ops += [("w", t, t + 700), ("h", t + 800, t + 1_100), ("while.2", 0, t)]
    red = reduce_ops([ops], paths)
    assert red["scope_s"]["attn_cross"] == pytest.approx(20.3e-6) and red["scope_s"]["gmu"] == pytest.approx(8e-6)
    assert red["scope_s"]["attn_full"] == pytest.approx(4.7e-6)
    assert red["decode_scope_s"] == {"attn_cross": pytest.approx(20e-6), "gmu": pytest.approx(8e-6), "attn_full": pytest.approx(4e-6)}


def test_the_four_readers_count_a_record_and_import_no_jax():
    """The new metrics on a made-up record: 5.11 GB of the shared slab a step
    x 100 steps over 2.0 s of attn_full + attn_cross inside decode_block =
    255.6 GB/s of 819; 15.45 GB a step over 4.0 s of decode_block; the
    cross-decoder on 96 of 111,168 prompt tokens; a program without the
    scopes or counters (the parent's) reads nothing. The readers run inside
    the harness, which must not import JAX."""
    import subprocess
    import sys

    code = """
import json, sys
from benchmark import run, scope_reduce, xdec_reduce
scope_reduce.reduction = lambda ctx: {"busy_s": 6.0, "decode_steps": 100.0}
red = {"busy_s": 6.0, "scope_s": {"attn_cross": 1.8, "gmu": 0.3, "attn_full": 0.4},
       "decode_scope_s": {"attn_cross": 1.75, "gmu": 0.3, "attn_full": 0.25}}
xdec_reduce.reduction = lambda ctx: red
final = {"decode_steps": 2000, "decode_tokens": 192000, "decode_live_positions": 249600000, "cache_full_readers": 8,
         "prefill_cross_tokens": 96, "prefill_tokens": 111168, "admitted": 96}
ctx = {"cell": {"name": "a-cell"}, "bench": run.BENCH, "device": {"device_kind": "TPU v5 lite"},
       "config": json.load(open("benchmark/configs/phi4-mini-flash-serve.json")),
       "reports": [{"trace": {"busy_s": 6.0, "program_s": {"decode_block": 4.0}}}], "final": final}
names = ["shared_kv_roofline_pct.serve_tps", "decode_step_hbm_roofline_pct.serve_tps", "attn_cross_share_pct.serve_tps",
         "prefill_cross_skip_pct.serve_tps"]
got = [run.read_layer_metric(n, ctx) for n in names]
red = {}
ctx["final"] = {k: v for k, v in final.items() if k != "prefill_cross_tokens"}
ctx["reports"] = [{}]
got += [run.read_layer_metric(n, ctx) for n in names]
ctx["config"] = json.load(open("benchmark/configs/nemotron3-nano-serve-ep4.json"))
ctx["reports"], ctx["final"] = [{"trace": {"busy_s": 6.0, "program_s": {"decode_block": 4.0}}}], final
got.append(run.read_layer_metric("decode_step_hbm_roofline_pct.serve_tps", ctx))
print("GOT", json.dumps(got))
assert "jax" not in sys.modules, "the harness imported JAX"
"""
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    shared, step, share, skip, *nothing = json.loads(done.stdout.strip().splitlines()[-1].removeprefix("GOT "))
    assert shared == pytest.approx(100 * 5.1118e9 * 100 / 2.0 / 819e9, rel=1e-3)
    assert step == pytest.approx(100 * 15.4495e9 * 100 / 4.0 / 819e9, rel=1e-3)
    assert share == pytest.approx(30.0) and skip == pytest.approx(100 * (1 - 96 / 111168))
    assert nothing == [None] * 5  # the parent's program; and a family with experts keeps its own reader


def test_the_manifest_declares_the_cell_and_its_metrics_as_the_issue_names_them():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in manifest["workloads"] if w["name"] == "serve-phi4-mini-flash-reasoning")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("phi4-mini-flash-serve", "reasoning-ctx1k-closed-120", 1)
    # the sixth cell (later PRs append theirs behind it), and this cell is one chip
    assert manifest["workloads"][5] is cell and cell["chips"] == 1
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == [] and config["source"] == CELL["source"] and config["file"].endswith("phi4-mini-flash-serve.json")
    new = {"attn_cross_share_pct", "shared_kv_roofline_pct", "decode_step_hbm_roofline_pct", "prefill_cross_skip_pct"}
    for m in manifest["per_layer"]:
        stem, _, suffix = m["name"].partition(".")
        if stem in new:
            assert suffix == "serve_tps" and cell["name"] in m["workloads"] and m["moves"] == "serve_tokens_per_s"
            assert (ROOT / "benchmark/layer_metrics" / f"{m['name']}.py").is_file()
    reported = {m["name"] for m in manifest["per_layer"] if cell["name"] in m.get("workloads", [])}
    assert {f"{n}.serve_tps" for n in new} <= reported and "decode_hbm_roofline_pct.serve_tps" not in reported
    assert {"ssm_share_pct.serve_tps", "ssm_state_roofline_pct.serve_tps", "attn_full_share_pct.serve_tps",
            "attn_window_share_pct.serve_tps", "prefill_share_pct.serve_tps"} <= reported
    mix = json.loads((ROOT / "benchmark/traffic" / f"{cell['traffic']}.json").read_text())
    import random, math
    rng, table = random.Random(mix["drawn_from"]["table_seed"]), []
    for _ in range(128):
        p, a = rng.lognormvariate(math.log(1024), 0.5), rng.lognormvariate(math.log(256), 0.5)
        table.append([min(max(round(p), 256), 2048), min(max(round(a), 64), 512)])
    assert mix["lengths"] == table and mix["clients"] == 120 and mix["loop"] == "closed" and mix["cycle_entry"] == 0
    assert mix["check_pad_to"] == max(p + a for p, a in table) == 2560 and CELL["bench"]["engine"]["slots"] == 96


# ---- the normal path: tpujob run -> supervisor -> workloads/serve.py -> ServingEngine ----


def test_tpujob_run_of_a_serve_job_with_the_preset_answers_requests(tmp_path):
    """``examples/serve-decoder-hybrid-decoder.yaml`` with the test-size
    preset on a CPU device: the job answers its requests, its final record
    carries the model's counters and gauges beside the engine's, and ``tpujob
    why`` prints ``prefill_cross_tokens`` beside ``prefill_tokens``."""
    import subprocess
    import sys
    import threading

    import yaml

    from pytorch_operator_tpu.serving import Spool

    job = yaml.safe_load((ROOT / "examples/serve-decoder-hybrid-decoder.yaml").read_text())
    template = job["spec"]["replica_specs"]["Master"]["template"]
    assert template["module"] == "pytorch_operator_tpu.workloads.serve" and "phi4-mini-flash" in template["args"]
    spool_dir = tmp_path / "spool"
    template["args"] = ["--config", "phi4-flash-tiny", "--spool", str(spool_dir), "--slots", "2", "--chunk", "16",
                        "--block", "4", "--max-decode-len", "128", "--max-requests", "3", "--idle-timeout", "120",
                        "--json"]
    template["resources"] = {"cpu_devices": 1}
    (tmp_path / "job.yaml").write_text(yaml.safe_dump(job))
    sp, got = Spool(spool_dir), {}

    def client():
        rids = [sp.submit(prompt_len=21, max_new_tokens=9), sp.submit(prompt=[3, 1, 4, 1, 5], max_new_tokens=12),
                sp.submit(prompt_len=40, max_new_tokens=5)]
        for rid in rids:
            got[rid] = sp.wait_response(rid, timeout=240)

    t = threading.Thread(target=client)
    t.start()
    cli = [sys.executable, "-m", "pytorch_operator_tpu.client.cli", "--state-dir", str(tmp_path / "state")]
    done = subprocess.run([*cli, "run", str(tmp_path / "job.yaml"), "--timeout", "240"],
                          cwd=ROOT, capture_output=True, text=True, timeout=300)
    t.join(timeout=60)
    log = "\n".join(p.read_text() for p in (tmp_path / "state" / "logs").glob("*.log"))
    assert done.returncode == 0 and not t.is_alive(), done.stdout[-1500:] + log[-3000:]
    assert sorted(len(r["tokens"]) for r in got.values()) == [5, 9, 12]
    final = json.loads(log[log.index("[serve] done: ") + len("[serve] done: "):].splitlines()[0])
    assert final["config"] == "phi4-flash-tiny" and final["cache_full_readers"] == 2
    assert min(final[k] for k in ("cache_state_bytes", "cache_full_bytes", "cache_window_bytes")) > 0
    assert final["prefill_state_resets"] == final["prefill_cross_tokens"] == final["admitted"] == 3
    assert final["prefill_tokens"] == 66
    why = subprocess.run([*cli, "why", job["metadata"]["name"]], cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert "3 row(s) started from zero state for 3 admitted" in why.stdout, why.stdout[-2000:]
    assert re.search(r"prefill: +\S+ prefill_cross_tokens 3 beside prefill_tokens 66", why.stdout), why.stdout[-2000:]
