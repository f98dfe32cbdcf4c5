"""The layer-pattern serving model (models/mimo_v2.py, parallel/moe.py's
held-experts layer) against the benchmark's plain reference
(benchmark/families/mimo_v2/reference.py), at a small size with the real
structure: layer 0 full + dense, then four window, one full and one window
layer with experts; window 8, q/k 24 beside v 16, rotary on 8, 2 and 4
key/value heads, 16 experts top-4 of which 4 are held, seeded sinks and
selection bias.

Program and reference start from the same seeded leaves, matrices rounded
to bfloat16 as the configuration states them, and both compute in float32
here: what is left between them is the order of float32 sums (1e-5 on
logits of unit size), so the tolerances below are 2e-4. A wrong mask, rotary
pairing, sink, value scale or routing moves a logit by 0.05 or more.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from benchmark import family
from pytorch_operator_tpu.models import mimo_v2
from pytorch_operator_tpu.models.serving import families, preset
from pytorch_operator_tpu.parallel.moe import moe_swiglu_held, route_sigmoid_topk
from pytorch_operator_tpu.serving import Request, ServingEngine

ROOT = Path(__file__).resolve().parents[1]
TINY = json.loads((ROOT / "tests/zz_benchmark/data/cells/config.tiny-mimo.json").read_text())
TOL = 2e-4
WINDOW = TINY["sliding_window"]

W = family.load("mimo_v2", "weights")
R = family.load("mimo_v2", "reference")
INSTALL = family.load("mimo_v2", "install")
FLOPS = family.load("mimo_v2", "flops")


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


def _reference_logits(d, key, tokens):
    import jax.numpy as jnp

    with R.highest():
        return np.asarray(R.make_forward(d)(key, jnp.asarray(tokens, jnp.int32)))


def _prompt(n, seed=1):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], (n,)).astype(np.int32)


def _serve(cfg, params, jobs, **engine):
    eng = ServingEngine(cfg, params, **{"slots": 3, "chunk": 16, "block": 4, **engine})
    for i, (prompt, new) in enumerate(jobs):
        eng.submit(Request(id=f"r{i}", prompt=prompt, max_new_tokens=new, submit_time=time.time()))
    done = {r.id: r.tokens for r in eng.run_until_drained()}
    return [done[f"r{i}"] for i in range(len(jobs))], eng


# ---- (a) chunked prefill, then decode, against the reference's full forward ----

PROMPTS = [WINDOW - 3, WINDOW, 5 * WINDOW + 1]  # shorter than, equal to, several times the window


@pytest.mark.parametrize("prompt_len", PROMPTS)
def test_engine_tokens_are_the_references_first_choice(prompt_len):
    """Through ``ServingEngine``: every served token's logit lies within TOL
    of the reference's best at its position (the benchmark's own measure)."""
    d, cfg, params, key = _setup()
    prompt, new = _prompt(prompt_len), 20
    (tokens,), _ = _serve(cfg, params, [(prompt, new)])
    seq = np.concatenate([prompt, tokens])
    ref = _reference_logits(d, key, seq)[prompt_len - 1 : prompt_len - 1 + new]
    gap = ref.max(-1) - ref[np.arange(new), np.asarray(tokens)]
    assert len(tokens) == new and gap.max() <= TOL, gap.max()


@pytest.mark.parametrize("prompt_len", PROMPTS)
def test_chunked_prefill_then_decode_logits_equal_the_full_forward(prompt_len):
    """The two forwards the engine's programs call, driven as it drives
    them (chunks of 16 into one slot's row, then one token a step at the
    row's own position), give the reference's logits at every position."""
    import jax.numpy as jnp

    d, cfg, params, key = _setup()
    model, chunk, new = cfg.serving_model(), 16, 12
    seq = _prompt(prompt_len + new, seed=2)
    ref = _reference_logits(d, key, seq)
    cache = model.init_cache(1, chunk)
    padded = -(-prompt_len // chunk) * chunk
    buf = np.zeros((padded,), np.int32)
    buf[:prompt_len] = seq[:prompt_len]
    got = []
    for start in range(0, padded, chunk):
        pos = (start + jnp.arange(chunk, dtype=jnp.int32))[None]
        hidden, cache, _ = model.prefill(params, cache, jnp.int32(0), jnp.asarray(buf[None, start : start + chunk]), pos)
        got.append(np.asarray(model.logits(params, hidden[0])))
    got = np.concatenate(got)[:prompt_len]
    assert np.abs(got - ref[:prompt_len]).max() <= TOL
    for p in range(prompt_len, prompt_len + new):
        logits, cache, _ = model.decode(params, cache, jnp.asarray(seq[None, p : p + 1]), jnp.full((1, 1), p, jnp.int32))
        assert np.abs(np.asarray(logits[0]) - ref[p]).max() <= TOL, p


# ---- (b) the shares add up ----


@pytest.mark.parametrize("tokens", [5, 64])
def test_the_expert_layers_shares_add_up_to_the_uncut_layer(tokens):
    """Four chips, each holding 4 of the 16 experts: the parts their layers
    compute for the same tokens sum to what the reference gives for the
    whole layer (held = all 16)."""
    import jax
    import jax.numpy as jnp

    key, kind = jax.random.key(3), (W.WINDOW, W.MOE)
    whole = W.dims({**TINY, "experts_held": [0, 16]})
    x = jax.random.normal(jax.random.key(4), (tokens, whole["D"]), jnp.float32)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    with R.highest():
        want = R.experts(x, f32(W.make_layer(whole, key, 2, kind, jnp.bfloat16)["moe"]), whole)
        parts, pairs = [], 0
        for first in range(0, 16, 4):
            d = W.dims({**TINY, "experts_held": [first, 4]})
            w = W.make_layer(d, key, 2, kind, jnp.bfloat16)["moe"]
            y, counts = moe_swiglu_held(f32(w), x, top_k=d["k"], experts_held=d["held"])
            parts.append(np.asarray(y))
            pairs += int(counts["moe_local_pairs"])
            assert np.abs(np.asarray(R.experts(x, f32(w), d)) - parts[-1]).max() <= TOL
    assert pairs == tokens * whole["k"]  # every selected expert is held by exactly one share
    assert np.abs(sum(parts) - np.asarray(want)).max() <= TOL
    assert max(np.abs(p).max() for p in parts) > 0.01


# ---- (c) the ring, and a slot's second request ----


def test_a_ring_that_wrapped_many_times_equals_the_masked_full_length_computation():
    """Ring of 8 + 16 = 24 positions, a stream of 127: it wraps 5 times; the
    tokens served are the full-length reference's own, position by position."""
    d, cfg, params, key = _setup()
    prompt, new = _prompt(27, seed=5), 100
    assert mimo_v2.ring_len(cfg, 16) == 24 and (27 + new) // 24 >= 5
    (tokens,), _ = _serve(cfg, params, [(prompt, new)], slots=1)
    ref = _reference_logits(d, key, np.concatenate([prompt, tokens]))[26 : 26 + new]
    gap = ref.max(-1) - ref[np.arange(new), np.asarray(tokens)]
    assert gap.max() <= TOL, gap.max()


def test_a_slot_taken_by_a_second_request_equals_a_fresh_engine():
    """One slot serves a long request and then a short one: the second sees
    none of the first one's keys (its answers are those of an engine that
    never held the first), in window and full layers alike."""
    _, cfg, params, _ = _setup()
    first, second = (_prompt(50, seed=6), 40), (_prompt(11, seed=7), 30)
    (_, reused), eng = _serve(cfg, params, [first, second], slots=1)
    (fresh,), _ = _serve(cfg, params, [second], slots=1)
    assert eng.stats()["admitted"] == 2 and reused == fresh


# ---- (d) the sink ----


def _attend(cfg, kind, sink):
    """One layer's attention over a prompt of 12, with the sink set."""
    import jax
    import jax.numpy as jnp

    d = W.dims(TINY)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), W.make_layer(d, jax.random.key(8), 1, (kind, W.MOE), jnp.bfloat16)["attn"])
    if sink is not None:
        w["sink"] = jnp.full((d["H"],), sink, jnp.float32)
    x = jax.random.normal(jax.random.key(9), (1, 12, d["D"]), jnp.float32)
    state = mimo_v2.init_cache(cfg, 1, 16)["layer_1" if kind == W.WINDOW else "layer_0"]
    out, _ = mimo_v2.attention(cfg, kind, w, state, x, jnp.arange(12, dtype=jnp.int32)[None])
    with R.highest():
        ref = R.attention(x[0], w, {**d, "sink": {**d["sink"], kind: sink is not None}}, kind)
    return np.asarray(out[0]), np.asarray(ref)


def test_the_sink_takes_mass_and_adds_no_value():
    """A large sink logit shrinks a window layer's output toward zero (the
    weights of the visible keys no longer sum to one) and the program
    follows the reference's formula."""
    _, cfg, _, _ = _setup()
    plain, _ = _attend(cfg, W.WINDOW, -1e9)
    small, ref_small = _attend(cfg, W.WINDOW, 0.0)
    large, ref_large = _attend(cfg, W.WINDOW, 6.0)
    assert np.abs(small - ref_small).max() <= TOL and np.abs(large - ref_large).max() <= TOL
    assert np.abs(large).mean() < 0.5 * np.abs(small).mean() < 0.5 * np.abs(plain).mean() * 1.01


def test_full_layers_have_no_sink():
    _, cfg, params, _ = _setup()
    kinds = [kind for kind, _ in cfg.layers]
    assert ["sink" in layer["attn"] for layer in params["layers"]] == [k == W.WINDOW for k in kinds]
    out, ref = _attend(cfg, W.FULL, None)
    assert np.abs(out - ref).max() <= TOL


def test_a_sink_at_minus_infinity_is_plain_softmax():
    _, cfg, _, _ = _setup()
    out, _ = _attend(cfg, W.WINDOW, -1e9)
    d = W.dims(TINY)
    _, ref_plain = _attend(cfg, W.WINDOW, None)  # the reference told the kind has no sink
    assert d["sink"][W.WINDOW] and np.abs(out - ref_plain).max() <= TOL


# ---- (e) routing ----


def test_the_bias_changes_selection_and_not_weights():
    import jax
    import jax.numpy as jnp

    router = jax.random.normal(jax.random.key(10), (64, 16), jnp.float32) / 8.0
    x = jax.random.normal(jax.random.key(11), (200, 64), jnp.float32)
    bias = 0.05 * jax.random.normal(jax.random.key(12), (16,), jnp.float32)
    idx0, w0 = route_sigmoid_topk(router, jnp.zeros((16,)), x, 4)
    idx1, w1 = route_sigmoid_topk(router, bias, x, 4)
    changed = np.mean(np.sort(np.asarray(idx0), -1) != np.sort(np.asarray(idx1), -1))
    assert changed > 0.1  # the seeded bias moves at least a tenth of the selections
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    picked = np.take_along_axis(scores, np.asarray(idx1), -1)
    assert np.allclose(np.asarray(w1), picked / picked.sum(-1, keepdims=True), atol=1e-6)
    assert np.allclose(np.asarray(w1).sum(-1), 1.0, atol=1e-6)


def test_every_token_to_one_expert_drops_none():
    """A bias that sends all 64 tokens to the same four experts, two of them
    held: no capacity, so every token gets both held experts' part."""
    import jax
    import jax.numpy as jnp

    d = W.dims(TINY)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), W.make_layer(d, jax.random.key(13), 2, (W.WINDOW, W.MOE), jnp.bfloat16)["moe"])
    w["e_bias"] = jnp.zeros((16,)).at[jnp.asarray([1, 3, 9, 12])].set(10.0)
    x = jax.random.normal(jax.random.key(14), (64, d["D"]), jnp.float32)
    y, counts = moe_swiglu_held(w, x, top_k=4, experts_held=(0, 4))
    assert [int(t) for t in counts["moe_expert_tokens"]] == [0, 64, 0, 64]
    assert int(counts["moe_local_pairs"]) == 128 and int(counts["moe_experts_touched"]) == 2
    with R.highest():
        assert np.abs(np.asarray(R.experts(x, w, d)) - np.asarray(y)).max() <= TOL
    assert (np.abs(np.asarray(y)).max(-1) > 0).all()


# ---- (f) the gauges, (g) the counters ----


def test_cache_gauges_equal_the_configurations_arithmetic():
    _, cfg, params, _ = _setup()
    eng = ServingEngine(cfg, params, slots=3, chunk=16, block=4)
    s, item = eng.stats(), 4  # float32 here
    full = 2 * 3 * 2 * 128 * (24 + 16) * item  # 2 full layers x slots x 2 heads x max_decode_len x (q/k 24 + v 16)
    ring = 5 * 3 * (4 * 24 * (24 + 16) * item + 24 * 4)  # 5 window layers x slots x (4 heads x ring 24 x 40 + the ring's positions)
    assert (s["cache_full_bytes"], s["cache_window_bytes"]) == (full, ring)
    big = mimo_v2.mimo_v2_5_ep16(decode=True, max_decode_len=4096)
    import jax

    shapes = jax.eval_shape(lambda: mimo_v2.init_cache(big, 64, 128))
    sizes = mimo_v2.cache_bytes(shapes)
    assert sizes["cache_full_bytes"] == 64 * 2 * 4096 * 4 * 320 * 2  # 1.342 GB
    assert sizes["cache_window_bytes"] == 64 * 5 * (256 * 8 * 320 * 2 + 256 * 4)  # 0.420 GB


def test_expert_local_hit_share_is_the_held_share_of_the_router():
    """4 of 16 held: a quarter of the selected experts are local, within the
    noise of some 9,000 selections (the seeded bias favours some experts)."""
    _, cfg, params, _ = _setup()
    jobs = [(_prompt(40 + 7 * i, seed=20 + i), 30) for i in range(4)]
    _, eng = _serve(cfg, params, jobs)
    s = eng.stats()
    assert s["moe_tokens"] >= 6 * 300 and s["moe_tokens"] % 6 == 0  # every token visits the six expert layers
    assert sum(s["moe_expert_tokens"]) == s["moe_local_pairs"]
    assert abs(s["expert_local_hit_pct"] - 25.0) <= 6.0, s["expert_local_hit_pct"]
    assert 1.0 <= s["expert_load_max_over_mean"] <= 2.5
    assert 0 < s["decode_moe_tokens"] < s["moe_tokens"] and s["decode_live_positions"] > 0
    eng.reset_stats()
    assert eng.stats()["moe_tokens"] == 0


# ---- the interface: presets, weights, bytes ----


def test_presets_name_their_family_and_the_server_finds_both():
    table = families()
    assert table["mimo-tiny"][0] is mimo_v2 and table["tiny"][0].__name__.endswith("models.llama")
    cfg = preset("mimo-v2.5-ep16", decode=True, max_decode_len=4096, quantize=None, kv_quantize=None)
    assert cfg.layers[0] == ("full", "dense") and [k for k, _ in cfg.layers].count("window") == 5
    assert cfg.experts_held == (0, 16) and cfg.router_width == 256 and cfg.top_k == 8
    assert preset("tiny", decode=True).serving_model().cfg.n_layers == 2
    with pytest.raises(ValueError, match="no preset"):
        preset("no-such-model")


def test_the_llama_presets_are_one_table_wherever_they_are_named(monkeypatch):
    """The table lives with the model; the trainer's name for it is the same
    dict, so the preset the benchmark adds through ``llama_train.CONFIGS`` is
    the server's too."""
    from pytorch_operator_tpu.models import llama
    from pytorch_operator_tpu.workloads import llama_train

    assert llama_train.CONFIGS is llama.CONFIGS
    monkeypatch.setitem(llama_train.CONFIGS, "bench", "llama_tiny")
    assert families()["bench"] == (llama, "llama_tiny")
    assert preset("bench", decode=True).n_layers == llama.llama_tiny().n_layers


@pytest.mark.parametrize("knob", ["quantize", "kv_quantize"])
def test_the_family_refuses_what_it_does_not_serve(knob):
    with pytest.raises(ValueError, match="unquantised"):
        preset("mimo-tiny", decode=True, **{knob: "int8"})


def test_weights_are_made_in_the_serving_dtype_a_layer_at_a_time():
    import jax
    import jax.numpy as jnp

    cfg = mimo_v2.mimo_v2_tiny(decode=True, param_dtype=jnp.bfloat16)
    params = cfg.serving_model().init_params(jax.random.key(0))
    assert len(params["layers"]) == 7 and "mlp" in params["layers"][0] and "moe" in params["layers"][1]
    small = {"scale", "sink", "e_bias"}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = path[-1].key
        assert leaf.dtype == (jnp.float32 if name in small else jnp.bfloat16), (path, leaf.dtype)
    # the real size, by shapes alone: 4.52 B parameters, 9.05 GB in bfloat16
    big = mimo_v2.mimo_v2_5_ep16(decode=True)
    shapes = jax.eval_shape(lambda k: mimo_v2.init_params(big, k), jax.random.key(0))
    sizes = [(a.size, a.dtype.itemsize) for a in jax.tree.leaves(shapes)]
    assert sum(n for n, _ in sizes) == 4_523_620_160  # norm scales, sinks and biases among them
    assert abs(sum(n * b for n, b in sizes) / 1e9 - 9.047) < 0.01


def test_decode_step_bytes_count_the_issue_s_arithmetic():
    """The family's least bytes of a decode step at the cell's size: all 96
    held experts touched and every slab full gives the count of ISSUE 28
    (1.30 attention + 0.40 dense + 4.83 experts + 1.25 head + 0.01 routers +
    1.34 of full slabs, in GB) but for the window layers, of which a step
    must read the 128 live positions (0.21) and not the ring's 256 (0.42);
    fewer experts and live positions give less."""
    model = json.loads((ROOT / "benchmark/configs/mimo-v2.5-serve-ep16.json").read_text())
    most = FLOPS.decode_step_bytes_min(model, slots=64, mean_positions=4096, experts_touched=96)
    assert abs(most / 1e9 - 9.35) < 0.02, most
    some = FLOPS.decode_step_bytes_min(model, slots=64, mean_positions=800, experts_touched=83)
    assert 7.0e9 < some < most - 1.5e9
    assert 1.0e9 < FLOPS.forward_flops_per_token(model, 800) < 2.5e10


# ---- the reduction of a trace by this family's scopes ----


def test_scope_paths_lose_their_wrappers():
    from benchmark.scope_reduce import segments

    path = "jit(decode_block)/while/body/attn_window/transpose(jvp(moe))/moe_router/dot_general:"
    assert {"attn_window", "moe", "moe_router"} <= segments(path) and "attn_full" not in segments(path)


def test_device_time_by_scope_and_the_steps_of_a_window():
    from benchmark.scope_reduce import reduce_ops

    ops, paths = [], {"a": "jit(decode_block)/while/body/attn_full/dot", "m": "jit(decode_block)/while/body/moe/moe_router/dot",
                      "s": "jit(decode_block)/while/body/closed_call/head/dot_general:", "p": "jit(prefill_chunk)/moe/dot",
                      "h": "jit(prefill_chunk)/head/dot_general:", "while.1": ""}
    t = 0
    for step in range(5):
        for name, ns in (("a", 2_000), ("m", 3_000), ("s", 1_000)):
            ops.append((name, t, t + ns))
            t += ns + 500
    ops += [("p", t, t + 4_000), ("h", t + 4_000, t + 5_000), ("while.1", 0, t)]  # a prefill chunk's head is no decode step
    red = reduce_ops([ops], paths)
    assert red["decode_steps"] == 5
    assert red["scope_s"]["attn_full"] == pytest.approx(10e-6) and red["scope_s"]["moe"] == pytest.approx(19e-6)
    assert red["scope_s"]["moe_router"] == pytest.approx(15e-6) and red["scope_s"]["dense_mlp"] == 0.0


# ---- the normal path: tpujob run -> supervisor -> workloads/serve.py -> ServingEngine ----


def test_tpujob_run_of_a_serve_job_with_the_preset_answers_requests(tmp_path):
    """``examples/serve-layer-pattern.yaml`` with the test-size preset on a
    CPU device: the job answers its requests and its final record carries
    the model's counters beside the engine's."""
    import subprocess
    import sys
    import threading

    import yaml

    from pytorch_operator_tpu.serving import Spool

    job = yaml.safe_load((ROOT / "examples/serve-layer-pattern.yaml").read_text())
    template = job["spec"]["replica_specs"]["Master"]["template"]
    assert template["module"] == "pytorch_operator_tpu.workloads.serve" and "mimo-v2.5-ep16" in template["args"]
    spool_dir = tmp_path / "spool"
    template["args"] = ["--config", "mimo-tiny", "--spool", str(spool_dir), "--slots", "2", "--chunk", "16",
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
    done = subprocess.run(
        [sys.executable, "-m", "pytorch_operator_tpu.client.cli", "--state-dir", str(tmp_path / "state"), "run",
         str(tmp_path / "job.yaml"), "--timeout", "240"], cwd=ROOT, capture_output=True, text=True, timeout=300)
    t.join(timeout=60)
    log = "\n".join(p.read_text() for p in (tmp_path / "state" / "logs").glob("*.log"))
    assert done.returncode == 0 and not t.is_alive(), done.stdout[-1500:] + log[-3000:]
    assert sorted(len(r["tokens"]) for r in got.values()) == [9, 12] and all(r["ttft_ms"] > 0 for r in got.values())
    final = json.loads(log[log.index("[serve] done: ") + len("[serve] done: "):].splitlines()[0])
    assert final["config"] == "mimo-tiny" and final["moe_tokens"] > 0 and final["cache_window_bytes"] > 0
    assert final["moe_tokens"] % 6 == 0 and len(final["moe_expert_tokens"]) == 4


def test_the_roofline_reader_counts_a_chip_runs_record_and_imports_no_jax():
    """``decode_hbm_roofline_pct.serve_tps`` on the record of a traced chip
    run of the cell (PR 28, seed 2147730002): 7.39 GB a step at the least x
    186 steps over 3.371 s of ``decode_block`` = 407.8 GB/s of 819. The
    reader runs inside the harness, which must not import JAX: the family's
    ``flops.py`` takes its sizes from ``shape.py``, not from ``weights.py``."""
    import subprocess
    import sys

    code = """
import json, sys
from benchmark import run, scope_reduce
scope_reduce.reduction = lambda ctx: {"busy_s": 3.839051078, "decode_steps": 186.0}
ctx = {"cell": {"name": "a-cell"}, "bench": run.BENCH, "device": {"device_kind": "TPU v5 lite"},
       "config": json.load(open("benchmark/configs/mimo-v2.5-serve-ep16.json")),
       "reports": [{"trace": {"program_s": {"prefill_chunk": 0.467650753, "decode_block": 3.371366566}}}],
       "final": {"decode_steps": 2588, "decode_tokens": 134560, "decode_live_positions": 84563293,
                 "decode_moe_experts_touched": 210220}}
print(run.read_layer_metric("decode_hbm_roofline_pct.serve_tps", ctx))
print(run.read_layer_metric("decode_hbm_roofline_pct.serve_tps", {**ctx, "final": {"decode_steps": 2588}}))
assert "jax" not in sys.modules, "the harness imported JAX"
"""
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    *_, value, nothing = done.stdout.strip().splitlines()
    assert float(value) == pytest.approx(49.79, abs=0.02) and nothing == "None"  # the parent's program has no such counters


# ---- the reference's check leaves out what no precision decides ----


def test_a_held_expert_within_bf16s_resolution_of_the_edge_marks_the_token():
    """Top 4 of 16, experts 0-3 held. The reference marks a token where a
    held expert lies within ``EDGE`` = 2^-8 of the selection's edge, from
    either side; experts that are not held, or a held one farther off, do not."""
    import jax.numpy as jnp

    d = W.dims(TINY)
    top = {12: 0.9, 13: 0.8, 14: 0.7}  # three experts safely in, none of them held
    rows = [
        {**top, 7: 0.600, 2: 0.599},   # a held outsider 0.001 under the 4th: near
        {**top, 1: 0.600, 9: 0.598},   # a held insider 0.002 over the 5th: near
        {**top, 7: 0.600, 9: 0.599},   # a tie between experts that are not held: not near
        {**top, 7: 0.600, 2: 0.590},   # the held outsider 0.01 off: not near
    ]
    scores = np.full((len(rows), 16), 0.1, np.float32)
    for t, row in enumerate(rows):
        for e, s in row.items():
            scores[t, e] = s
    logit = np.log(scores / (1 - scores))
    x = np.eye(d["D"], dtype=np.float32)[: len(rows)]
    router = np.zeros((d["D"], 16), np.float32)
    router[: len(rows)] = logit
    with R.highest():
        idx, wt, near = R.route(jnp.asarray(x), {"router": jnp.asarray(router), "e_bias": jnp.zeros((16,))}, d)
    assert R.EDGE == 2.0 ** -8 and near.tolist() == [True, True, False, False]
    assert sorted(idx[0].tolist()) == [7, 12, 13, 14] and np.allclose(np.asarray(wt).sum(-1), 1.0, atol=1e-6)


def test_the_check_counts_the_positions_it_leaves_out():
    d = W.dims(TINY)
    rng = np.random.default_rng(30)
    reqs = [{"prompt": rng.integers(0, d["V"], (p,)).tolist(), "tokens": rng.integers(0, d["V"], (n,)).tolist()}
            for p, n in ((9, 20), (30, 12))]
    out = R.serve_check({"config": TINY, "seed": 5, "pad_to": 64, "width": 20, "requests": reqs}, control=False)
    assert out["positions"] + out["positions_near_edge"] == 32 and out["positions"] > 0
    assert out["gap_max_all_positions"] >= out["gap_max"] > 0  # made-up tokens: far from the reference's choice
