"""The hybrid state-space serving model (models/nemotron_h.py, parallel/moe.py's
held-experts layer in its relu^2 form) against the benchmark's plain reference
(benchmark/families/nemotron_h/reference.py), at a small size with the real
structure: ``MEM*EME`` — Mamba-2 layers of 8 heads of 8 on 2 groups of state
16 behind a convolution of 4 taps, attention without positions on 4 query and
2 key/value heads, 16 experts top-4 of which 4 are held beside a shared
expert, routing weights times 2.5, a seeded selection bias.

Program and reference start from the same seeded leaves, matrices rounded to
bfloat16 as the configuration states them, and both compute in float32 here:
what is left between them is the order of float32 sums (the chunked scan
against the sequential recurrence among them), so the tolerances below are
2e-4 on logits of unit size. A state that is not reset, a pad that runs
through it, a wrong decay, group or gate moves a logit by 0.05 or more.
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
from pytorch_operator_tpu.models import nemotron_h
from pytorch_operator_tpu.models.serving import families, preset
from pytorch_operator_tpu.parallel.moe import RELU2, moe_held
from pytorch_operator_tpu.serving import Request, ServingEngine

ROOT = Path(__file__).resolve().parents[1]
TINY = json.loads((ROOT / "tests/zz_benchmark/data/cells/config.tiny-nemotron.json").read_text())
CELL = json.loads((ROOT / "benchmark/configs/nemotron3-nano-serve-ep4.json").read_text())
TOL = 2e-4
CHUNK = 16

W = family.load("nemotron_h", "weights")
R = family.load("nemotron_h", "reference")
INSTALL = family.load("nemotron_h", "install")
FLOPS = family.load("nemotron_h", "flops")


def _setup(model=TINY, seed=0, **over):
    """(dims, program config, seeded params, key): float32 compute over
    bfloat16-rounded matrices on both sides."""
    import jax
    import jax.numpy as jnp

    d = W.dims(model)
    cfg = nemotron_h.make_config(
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


def _chunks(model, params, cache, slot, prompt, pad=0):
    """Prefill ``prompt`` into row ``slot`` as the engine does, the last
    chunk padded with ``pad``; returns (logits of every real position, cache)."""
    import jax.numpy as jnp

    p = len(prompt)
    padded = -(-p // CHUNK) * CHUNK
    buf = np.full((padded,), pad, np.int32)
    buf[:p] = prompt
    got = []
    for start in range(0, padded, CHUNK):
        pos = (start + jnp.arange(CHUNK, dtype=jnp.int32))[None]
        hidden, cache, _ = model.prefill(
            params, cache, jnp.int32(slot), jnp.asarray(buf[None, start : start + CHUNK]), pos,
            jnp.int32(min(CHUNK, p - start)))
        got.append(np.asarray(model.logits(params, hidden[0])))
    return np.concatenate(got)[:p], cache


def _decode(model, params, cache, rows, tokens, positions):
    """One decode step over ``rows`` slots: row r's token at its position."""
    import jax.numpy as jnp

    logits, cache, _ = model.decode(
        params, cache, jnp.asarray(tokens, jnp.int32)[:, None], jnp.asarray(positions, jnp.int32)[:, None])
    return np.asarray(logits), cache


# ---- (a) chunked prefill, then decode, against the reference's full forward ----

PROMPTS = [1, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 5]


@pytest.mark.parametrize("prompt_len", [5, CHUNK, 3 * CHUNK + 5])
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
    assert s["prefill_state_resets"] == s["admitted"] == 1 and s["decode_prefill_state_resets"] == 0


@pytest.mark.parametrize("prompt_len", PROMPTS)
def test_chunked_prefill_then_decode_logits_equal_the_full_forward_whatever_the_pad(prompt_len):
    """The two forwards the engine's programs call, driven as it drives them
    (chunks of 16 into one slot's row, the last one padded and the model told
    how many tokens are real; then one token a step), give the reference's
    logits at every position — and the same whether the pad is zeros or other
    tokens: the pads run through no state."""
    d, cfg, params, key = _setup()
    model, new = cfg.serving_model(), 10
    seq = _prompt(prompt_len + new, seed=2)
    ref = _reference_logits(d, key, seq)
    runs = []
    for pad in (0, 201):
        got, cache = _chunks(model, params, model.init_cache(2, CHUNK), 1, seq[:prompt_len], pad)
        assert np.abs(got - ref[:prompt_len]).max() <= TOL
        for p in range(prompt_len, prompt_len + new):
            logits, cache = _decode(model, params, cache, 2, [0, seq[p]], [0, p])
            got = np.concatenate([got, logits[1:]])
        assert np.abs(got - ref).max() <= TOL
        runs.append(got)
    assert np.array_equal(runs[0], runs[1])


# ---- (b) the scan's two forms ----


def _scan_inputs(seed, S, B=1):
    import jax
    import jax.numpy as jnp

    H, P, G, N = 8, 8, 2, 16
    ks = jax.random.split(jax.random.key(seed), 6)
    u = jax.random.normal(ks[0], (B, S, H, P))
    Bm, Cm = jax.random.normal(ks[1], (B, S, G, N)), jax.random.normal(ks[2], (B, S, G, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=2.77))  # -1 .. -16, as A_log is drawn
    state = jax.random.normal(ks[5], (B, H, P, N))
    return u, Bm, Cm, dt, A, state


@pytest.mark.parametrize("entry", ["zero", "nonzero"])
@pytest.mark.parametrize("S", [1, 16, 128])
def test_the_chunked_scan_equals_the_sequential_recurrence(entry, S):
    u, Bm, Cm, dt, A, state = _scan_inputs(3, S)
    state = state * (0.0 if entry == "zero" else 1.0)
    y, exit_ = nemotron_h.scan_chunk(u[0], Bm[0], Cm[0], dt[0], A, state[0])
    ys, s = [], state
    for t in range(S):
        y_t, s = nemotron_h.scan_step(u[:, t], Bm[:, t], Cm[:, t], dt[:, t], A, s)
        ys.append(y_t[0])
    scale = max(1.0, float(np.abs(np.asarray(ys)).max()))
    assert np.abs(np.asarray(y) - np.asarray(ys)).max() <= 2e-5 * scale
    assert np.abs(np.asarray(exit_) - np.asarray(s[0])).max() <= 2e-5 * scale


def test_a_zero_step_size_freezes_the_state():
    """Beyond the last real token dt = 0: the exit state is the state after
    the real tokens alone, whatever the later tokens hold."""
    import jax.numpy as jnp

    u, Bm, Cm, dt, A, state = _scan_inputs(4, 16)
    real = jnp.arange(16) < 11
    _, frozen = nemotron_h.scan_chunk(u[0], Bm[0], Cm[0], jnp.where(real[:, None], dt[0], 0.0), A, state[0])
    _, short = nemotron_h.scan_chunk(u[0, :11], Bm[0, :11], Cm[0, :11], dt[0, :11], A, state[0])
    assert np.abs(np.asarray(frozen) - np.asarray(short)).max() <= 1e-5


def test_the_mamba_layer_equals_the_references_from_chunks_and_from_steps():
    """One layer, 37 tokens: as chunks of 16 with the state carried in the
    cache, and as 37 single steps, against the reference's whole sequence."""
    import jax
    import jax.numpy as jnp

    d, cfg, _, _ = _setup()
    w = jax.tree.map(lambda a: a.astype(jnp.float32), W.make_layer(d, jax.random.key(5), 0, W.MAMBA, jnp.bfloat16)["ssm"])
    x = jax.random.normal(jax.random.key(6), (1, 48, d["D"]), jnp.float32)
    with R.highest():
        want = np.asarray(R.mamba(x[0, :37], w, d))
    cache = nemotron_h.init_cache(cfg, 2, CHUNK)["layer_0"]
    chunks = []
    for start in range(0, 48, CHUNK):
        y, cache = nemotron_h.ssm_mixer(cfg, w, cache, x[:, start : start + CHUNK], slot=jnp.int32(1),
                                        fresh=jnp.bool_(start == 0), n_real=jnp.int32(min(CHUNK, 37 - start)))
        chunks.append(np.asarray(y[0]))
    assert np.abs(np.concatenate(chunks)[:37] - want).max() <= TOL
    cache_b, steps = nemotron_h.init_cache(cfg, 2, CHUNK)["layer_0"], []
    for t in range(37):
        y, cache_b = nemotron_h.ssm_mixer(cfg, w, cache_b, jnp.stack([x[0, t : t + 1], x[0, 40:41]]))
        steps.append(np.asarray(y[0, 0]))
    assert np.abs(np.stack(steps) - want).max() <= TOL
    # Both ways leave the same state behind for row 1 / row 0: what the next step would start from.
    assert np.abs(np.asarray(cache["state"][1]) - np.asarray(cache_b["state"][0])).max() <= 1e-4
    assert np.abs(np.asarray(cache["conv"][1]) - np.asarray(cache_b["conv"][0])).max() <= 1e-6


# ---- (c) slots: reuse, and parked rows ----


def test_a_slot_reused_after_a_longer_request_equals_a_fresh_engine():
    """One slot serves a long request and then a short one: the second starts
    from zero state and sees none of the first one's keys (its answers are
    those of an engine that never held the first)."""
    _, cfg, params, _ = _setup()
    first, second = (_prompt(50, seed=6), 40), (_prompt(11, seed=7), 30)
    (_, reused), eng = _serve(cfg, params, [first, second], slots=1)
    (fresh,), _ = _serve(cfg, params, [second], slots=1)
    assert eng.stats()["admitted"] == eng.stats()["prefill_state_resets"] == 2 and reused == fresh


def test_a_state_that_is_not_reset_changes_the_answer(monkeypatch):
    """The same two requests through a forward that never zeroes a row: the
    second request's tokens differ (what the reset is for)."""
    _, cfg, params, _ = _setup()
    first, second = (_prompt(50, seed=6), 40), (_prompt(11, seed=7), 30)
    (fresh,), _ = _serve(cfg, params, [second], slots=1)
    mixer = nemotron_h.ssm_mixer
    monkeypatch.setattr(nemotron_h, "ssm_mixer", lambda *a, fresh=None, **k: mixer(*a, fresh=False, **k))
    (_, stale), _ = _serve(cfg, params, [first, second], slots=1)
    assert stale != fresh


def test_parked_rows_leave_active_rows_logits_bit_identical():
    """Three slots, one active row: its decode logits are the same bits
    whether the other two rows stand empty at position 0 or hold the state
    and keys a finished request left there; and the parked rows' state stays
    finite however long they step."""
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
        a, clean = _decode(model, params, clean, 3, [0, tok, 0], [0, 21 + step, 0])
        b, dirty = _decode(model, params, dirty, 3, [5, tok, 9], [0, 21 + step, 0])
        assert np.array_equal(a[1], b[1]), step
        tok = int(a[1].argmax())
    assert all(bool(jnp.isfinite(leaf).all()) for leaf in jax.tree.leaves(dirty))


# ---- (d) the expert layer: its shares, the shared expert, the scale ----


@pytest.mark.parametrize("tokens", [5, 64])
def test_the_expert_layers_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(tokens):
    """Four chips, each holding 4 of the 16 experts: the routed parts their
    layers compute for the same tokens, plus the shared expert counted once,
    sum to what the reference gives for the whole layer (held = all 16)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(3)
    whole = W.dims({**TINY, "experts_held": [0, 16]})
    x = jax.random.normal(jax.random.key(4), (tokens, whole["D"]), jnp.float32)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    with R.highest():
        uncut = f32(W.make_layer(whole, key, 1, W.EXPERTS, jnp.bfloat16))
        want = R.experts(x, uncut["moe"], whole) + R.shared_expert(x, uncut["shared"])
        parts, pairs = [], 0
        for first in range(0, 16, 4):
            d = W.dims({**TINY, "experts_held": [first, 4]})
            w = f32(W.make_layer(d, key, 1, W.EXPERTS, jnp.bfloat16))
            y, counts = moe_held(w["moe"], x, top_k=d["k"], experts_held=d["held"], form=RELU2, weight_scale=d["scale"])
            parts.append(np.asarray(y))
            pairs += int(counts["moe_local_pairs"])
            assert np.abs(np.asarray(R.experts(x, w["moe"], d)) - parts[-1]).max() <= TOL
            assert np.array_equal(np.asarray(w["shared"]["up_proj"]), np.asarray(uncut["shared"]["up_proj"]))
        shared = np.asarray(nemotron_h.shared_expert(uncut["shared"], x))
    assert pairs == tokens * whole["k"]  # every selected expert is held by exactly one share
    assert np.abs(sum(parts) + shared - np.asarray(want)).max() <= TOL
    assert max(np.abs(p).max() for p in parts) > 0.01 and np.abs(shared).max() > 0.01


def test_the_routed_weights_sum_to_the_scaling_factor():
    import jax.numpy as jnp

    d = W.dims(TINY)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((50, d["D"])), jnp.float32)
    w = {"router": jnp.asarray(np.random.default_rng(1).standard_normal((d["D"], 16)) / 8, jnp.float32),
         "e_bias": jnp.zeros((16,))}
    with R.highest():
        _, wt, margin = R.route(x, w, d)
    assert np.allclose(np.asarray(wt).sum(-1), 2.5, atol=1e-5) and (np.asarray(margin) >= 0).all()


def test_the_swiglu_form_is_the_layer_it_was():
    """``moe_swiglu_held`` is ``moe_held`` with its defaults: the same bits."""
    import jax
    import jax.numpy as jnp

    from pytorch_operator_tpu.parallel.moe import moe_swiglu_held

    ks = jax.random.split(jax.random.key(0), 6)
    w = {"router": jax.random.normal(ks[0], (32, 8)) / 6, "e_bias": 0.1 * jax.random.normal(ks[1], (8,)),
         "w_gate": jax.random.normal(ks[2], (4, 32, 16)) / 6, "w_up": jax.random.normal(ks[3], (4, 32, 16)) / 6,
         "w_down": jax.random.normal(ks[4], (4, 16, 32)) / 4}
    x = jax.random.normal(ks[5], (20, 32)).astype(jnp.bfloat16)
    w = jax.tree.map(lambda a: a.astype(jnp.bfloat16), w) | {"e_bias": w["e_bias"]}
    a, _ = moe_swiglu_held(w, x, top_k=2, experts_held=(2, 4))
    b, _ = moe_held(w, x, top_k=2, experts_held=(2, 4), form="swiglu", weight_scale=1.0)
    assert np.array_equal(np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32)))
    with pytest.raises(ValueError, match="form"):
        moe_held(w, x, top_k=2, experts_held=(2, 4), form="gelu")


# ---- (e) the gauges, the counters, the interface ----


def test_cache_gauges_equal_the_configurations_arithmetic():
    import jax

    _, cfg, params, _ = _setup()
    eng = ServingEngine(cfg, params, slots=3, chunk=CHUNK, block=4)
    s, item = eng.stats(), 4  # float32 here
    assert s["cache_full_bytes"] == 1 * 3 * 2 * 2 * 128 * 16 * item  # 1 layer x slots x (k, v) x 2 heads x 128 x 16
    assert s["cache_state_bytes"] == 3 * 3 * (8 * 8 * 16 * 4 + 3 * 128 * item)  # 3 layers x slots x (state + tail)
    big = nemotron_h.nemotron3_nano_ep4(decode=True, max_decode_len=4096)
    sizes = nemotron_h.cache_bytes(jax.eval_shape(lambda: nemotron_h.init_cache(big, 128, 128)))
    assert sizes["cache_full_bytes"] == 128 * 2 * 2 * 2 * 4096 * 128 * 2  # 1.074 GB
    assert sizes["cache_state_bytes"] == 128 * 7 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)  # 1.912 GB
    assert sizes == {"cache_full_bytes": CELL["bytes"]["cache_full_bytes"], "cache_state_bytes": CELL["bytes"]["cache_state_bytes"]}


def test_expert_counters_follow_every_token_through_the_expert_layers():
    _, cfg, params, _ = _setup()
    jobs = [(_prompt(40 + 7 * i, seed=20 + i), 30) for i in range(4)]
    _, eng = _serve(cfg, params, jobs)
    s = eng.stats()
    assert s["moe_tokens"] >= 3 * 300 and s["moe_tokens"] % 3 == 0  # every token visits the three expert layers
    assert sum(s["moe_expert_tokens"]) == s["moe_local_pairs"]
    assert abs(s["expert_local_hit_pct"] - 25.0) <= 8.0, s["expert_local_hit_pct"]
    assert 0 < s["decode_moe_tokens"] < s["moe_tokens"] and s["prefill_state_resets"] == s["admitted"] == 4
    eng.reset_stats()
    assert eng.stats()["moe_tokens"] == 0 and eng.stats()["prefill_state_resets"] == 0


def test_the_server_finds_the_family_by_its_presets():
    table = families()
    assert table["nemotron-h-tiny"][0] is nemotron_h and table["nemotron3-nano-ep4"][0] is nemotron_h
    cfg = preset("nemotron3-nano-ep4", decode=True, max_decode_len=4096, quantize=None, kv_quantize=None)
    assert cfg.pattern == "MEMEM*EMEMEM*EME" == CELL["hybrid_override_pattern"][:16]
    assert (cfg.pattern.count("M"), cfg.pattern.count("E"), cfg.pattern.count("*")) == (7, 7, 2)
    assert cfg.experts_held == (0, 32) and cfg.router_width == 128 and cfg.top_k == 6 and cfg.routed_scale == 2.5
    # the preset is the configuration file's model, field for field
    assert dataclass_fields(cfg) == dataclass_fields(nemotron_h.make_config(
        INSTALL.config_base(W.dims(CELL)), {"decode": True, "max_decode_len": 4096}))
    with pytest.raises(ValueError, match="unquantised"):
        preset("nemotron-h-tiny", decode=True, quantize="int8")
    with pytest.raises(ValueError, match="pattern"):
        nemotron_h.NemotronHConfig(pattern="M-E")


def dataclass_fields(cfg):
    import dataclasses

    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def test_weights_are_made_in_the_serving_dtype_and_count_what_the_configuration_states():
    import jax
    import jax.numpy as jnp

    cfg = nemotron_h.nemotron_h_tiny(decode=True, param_dtype=jnp.bfloat16)
    params = cfg.serving_model().init_params(jax.random.key(0))
    assert [sorted(layer) for layer in params["layers"]] == [
        sorted(["norm", {"M": "ssm", "*": "attn"}.get(k, "moe")] + (["shared"] if k == "E" else [])) for k in cfg.pattern]
    small = {"scale", "e_bias", "conv_w", "conv_b", "A_log", "dt_bias", "D", "norm_scale"}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        assert leaf.dtype == (jnp.float32 if path[-1].key in small else jnp.bfloat16), (path, leaf.dtype)
    ssm = params["layers"][0]["ssm"]
    decay = np.exp(np.asarray(jax.nn.softplus(ssm["dt_bias"])) * -np.exp(np.asarray(ssm["A_log"])))
    assert (decay > 0.15).all() and (decay < 1.0).all() and decay.max() - decay.min() > 0.05  # decays that differ by head
    # the real size, by shapes alone, from the program and from the benchmark's weights alike
    big = nemotron_h.nemotron3_nano_ep4(decode=True)
    for shapes in (jax.eval_shape(lambda k: nemotron_h.init_params(big, k), jax.random.key(0)),
                   jax.eval_shape(lambda k: W.make_params(W.dims(CELL), k, jnp.bfloat16), jax.random.key(0))):
        sizes = [(a.size, a.dtype.itemsize) for a in jax.tree.leaves(shapes)]
        assert sum(n for n, _ in sizes) == CELL["bytes"]["parameters"]
        assert abs(sum(n * b for n, b in sizes) / 1e9 - CELL["bytes"]["weights_gb"]) < 0.01


def test_decode_step_bytes_count_the_issues_arithmetic():
    """The family's least bytes of a decode step at the cell's size: all 224
    held experts touched and every slab full: 0.54 + 3.82 of state-space
    weights and state (read and written), 0.09 attention, 4.47 experts, 0.28
    shared, 0.70 head and 1.07 of slabs, in GB; fewer experts and live
    positions give less."""
    ssm = FLOPS.ssm_step_bytes_min(CELL, slots=128)
    assert abs(ssm / 1e9 - 4.367) < 0.01, ssm
    most = FLOPS.decode_step_bytes_min(CELL, slots=128, mean_positions=4096, experts_touched=224)
    assert abs(most / 1e9 - 10.99) < 0.05, most
    some = FLOPS.decode_step_bytes_min(CELL, slots=128, mean_positions=600, experts_touched=215)
    assert 9.0e9 < some < most - 0.9e9 and 0.38 < ssm / some < 0.46  # the state-space layers' two fifths
    assert 1.0e9 < FLOPS.forward_flops_per_token(CELL, 600) < 5e9


# ---- the reduction of a trace by this family's scopes ----


def test_device_time_of_the_state_space_scope_overall_and_inside_decode():
    from benchmark.ssm_reduce import reduce_ops

    paths = {"c": "jit(decode_block)/while/body/ssm/ssm_conv/add", "s": "jit(decode_block)/while/body/ssm/ssm_scan/mul",
             "m": "jit(decode_block)/while/body/moe/moe_shared/dot", "p": "jit(prefill_chunk)/ssm/ssm_scan/dot",
             "while.2": "jit(decode_block)/while"}
    ops, t = [], 0
    for _ in range(4):
        for name, ns in (("c", 1_000), ("s", 5_000), ("m", 3_000)):
            ops.append((name, t, t + ns))
            t += ns + 100
    ops += [("p", t, t + 7_000), ("while.2", 0, t)]
    red = reduce_ops([ops], paths)
    assert red["scope_s"]["ssm"] == pytest.approx(31e-6) and red["scope_s"]["ssm_scan"] == pytest.approx(27e-6)
    assert red["scope_s"]["moe_shared"] == pytest.approx(12e-6)
    assert red["decode_scope_s"]["ssm"] == pytest.approx(24e-6) and red["decode_scope_s"]["ssm_conv"] == pytest.approx(4e-6)


def test_the_state_roofline_reader_counts_a_record_and_imports_no_jax():
    """``ssm_state_roofline_pct.serve_tps`` on a made-up record: 4.367 GB a
    step at 128 rows x 100 steps over 1.0 s of the ``ssm`` scope inside
    ``decode_block`` = 436.7 GB/s of 819; a program without the scope reads
    nothing. The reader runs inside the harness, which must not import JAX."""
    import subprocess
    import sys

    code = """
import json, sys
from benchmark import run, scope_reduce, ssm_reduce
scope_reduce.reduction = lambda ctx: {"busy_s": 3.0, "decode_steps": 100.0}
red = {"busy_s": 3.0, "scope_s": {"ssm": 1.2}, "decode_scope_s": {"ssm": 1.0}}
ssm_reduce.reduction = lambda ctx: red
ctx = {"cell": {"name": "a-cell"}, "bench": run.BENCH, "device": {"device_kind": "TPU v5 lite"},
       "config": json.load(open("benchmark/configs/nemotron3-nano-serve-ep4.json")),
       "reports": [{"trace": {"busy_s": 3.0}}], "final": {"decode_steps": 2000, "decode_tokens": 256000}}
got = [run.read_layer_metric("ssm_state_roofline_pct.serve_tps", ctx), run.read_layer_metric("ssm_share_pct.serve_tps", ctx)]
red = {}
got += [run.read_layer_metric("ssm_state_roofline_pct.serve_tps", ctx), run.read_layer_metric("ssm_share_pct.serve_tps", ctx)]
print("GOT", json.dumps(got))
assert "jax" not in sys.modules, "the harness imported JAX"
"""
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    roofline, share, *nothing = json.loads(done.stdout.strip().splitlines()[-1].removeprefix("GOT "))
    assert roofline == pytest.approx(53.31, abs=0.05) and share == pytest.approx(40.0)
    assert nothing == [None, None]  # the parent's program has no such scope


# ---- the reference's check ----


def test_a_held_expert_near_the_selections_edge_gives_a_small_margin():
    """Top 4 of 16, experts 0-3 held: the margin is the distance of the
    nearest held expert from the edge, from either side; experts that are not
    held do not count."""
    import jax.numpy as jnp

    d = W.dims(TINY)
    top = {12: 0.9, 13: 0.8, 14: 0.7}  # three experts safely in, none of them held
    rows = [
        {**top, 7: 0.600, 2: 0.5995},  # a held outsider 0.0005 under the 4th
        {**top, 1: 0.600, 9: 0.5992},  # a held insider 0.0008 over the 5th
        {**top, 7: 0.600, 9: 0.5995},  # a tie between experts that are not held
        {**top, 7: 0.600, 2: 0.590},   # the held outsider 0.01 off
    ]
    scores = np.full((len(rows), 16), 0.1, np.float32)
    for t, row in enumerate(rows):
        for e, s in row.items():
            scores[t, e] = s
    x = np.eye(d["D"], dtype=np.float32)[: len(rows)]
    router = np.zeros((d["D"], 16), np.float32)
    router[: len(rows)] = np.log(scores / (1 - scores))
    with R.highest():
        idx, _, margin = R.route(jnp.asarray(x), {"router": jnp.asarray(router), "e_bias": jnp.zeros((16,))}, d)
    assert np.allclose(np.asarray(margin), [0.0005, 0.0008, 0.5, 0.01], atol=2e-5)
    assert sorted(idx[0].tolist()) == [7, 12, 13, 14] and (np.asarray(margin) < CELL["check"]["edge"]).tolist() == [True, True, False, False]


def test_the_check_counts_the_positions_it_leaves_out_and_profiles_the_margin():
    d = W.dims(TINY)
    rng = np.random.default_rng(30)
    reqs = [{"prompt": rng.integers(0, d["V"], (p,)).tolist(), "tokens": rng.integers(0, d["V"], (n,)).tolist()}
            for p, n in ((9, 20), (30, 12))]
    out = R.serve_check({"config": TINY, "seed": 5, "pad_to": 64, "width": 20, "requests": reqs}, control=True)
    assert out["positions"] + out["positions_near_edge"] == 32 and out["positions"] > 0 and out["edge"] == TINY["check"]["edge"]
    assert out["gap_max_all_positions"] >= out["gap_max"] > 0  # made-up tokens: far from the reference's choice
    kept = [n for n, _ in out["edge_profile"].values()]
    assert kept == sorted(kept) and kept[-1] <= 32 and out["control_gap_max"] >= 0


# ---- the normal path: tpujob run -> supervisor -> workloads/serve.py -> ServingEngine ----


def test_tpujob_run_of_a_serve_job_with_the_preset_answers_requests(tmp_path):
    """``examples/serve-hybrid-state.yaml`` with the test-size preset on a CPU
    device: the job answers its requests, its final record carries the
    model's counters and gauges beside the engine's, and ``tpujob why``
    prints the rows started from zero state beside the admissions."""
    import subprocess
    import sys
    import threading

    import yaml

    from pytorch_operator_tpu.serving import Spool

    job = yaml.safe_load((ROOT / "examples/serve-hybrid-state.yaml").read_text())
    template = job["spec"]["replica_specs"]["Master"]["template"]
    assert template["module"] == "pytorch_operator_tpu.workloads.serve" and "nemotron3-nano-ep4" in template["args"]
    spool_dir = tmp_path / "spool"
    template["args"] = ["--config", "nemotron-h-tiny", "--spool", str(spool_dir), "--slots", "2", "--chunk", "16",
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
    assert final["config"] == "nemotron-h-tiny" and final["cache_state_bytes"] > 0 and final["cache_full_bytes"] > 0
    assert final["prefill_state_resets"] == final["admitted"] == 3 and final["moe_tokens"] % 3 == 0
    why = subprocess.run([*cli, "why", job["metadata"]["name"]], cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert "3 row(s) started from zero state for 3 admitted" in why.stdout, why.stdout[-2000:]
    # ... and the rounds that admitted beside the decode dispatches queued behind one (PR 35): every row decodes.
    assert final["decode_behind_admit"] == final["admit_rounds"] >= 2 and "host_overlapped_s" in final
    assert re.search(rf"admits: +\S+ 3 admitted in {final['admit_rounds']} round\(s\), the decode dispatch queued "
                     rf"behind {final['decode_behind_admit']} of them", why.stdout), why.stdout[-2000:]
    # ... and what the decode steps read of the slabs over what was live (PR 38): each row to its own depth.
    ratio = final["decode_attended_positions"] / final["decode_live_positions"]
    assert re.search(rf"slabs: +\S+ decode_attended_positions {final['decode_attended_positions']} over "
                     rf"decode_live_positions {final['decode_live_positions']} = {ratio:.2f}", why.stdout), why.stdout[-2000:]
