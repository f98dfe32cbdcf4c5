"""What PR 31 changed in the prefill chunk, pinned on the CPU at tiny sizes:

- ``ServingModel.prefill(params, cache, slot, tokens, positions)`` writes one
  slot's row of a many-slot cache and nothing else, and that row is what the
  row-at-a-time form gave (both families);
- the engine's ``prefill_chunk`` runs no head, and ``prefill_chunk_head`` on
  a prompt's last chunk samples from the logits the one program gave and
  (PR 35) sets the row's token and position in the donated row state;
- an int8 weight's product with the scale behind it
  (``QuantizedTensor.project``) is ``dequantize()``-then-product to rounding,
  and no further from float32;
- ``prefill_head_chunks`` counts one a request;
- the llama family's serving arrangement, a tree a layer
  (``models.llama.per_layer_params``: the family's ``init_params`` makes
  them so and ``load_params`` arranges a checkpoint's), gives the
  scan-stacked tree's results to the bit.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from pytorch_operator_tpu.models import llama as llama_lib
from pytorch_operator_tpu.models import mimo_v2
from pytorch_operator_tpu.ops.quantize import QuantizedTensor, quantize, quantize_tree
from pytorch_operator_tpu.serving import Request, ServingEngine
from pytorch_operator_tpu.serving.engine import programs

SLOTS, CHUNK = 3, 8


def _llama(**over):
    import flax.linen as nn
    import jax

    cfg = llama_lib.llama_tiny(decode=True, max_decode_len=32, **over)
    params = nn.meta.unbox(
        llama_lib.Llama(dataclasses.replace(cfg, decode=False, quantize=None)).init(
            jax.random.key(0), np.zeros((1, 8), np.int32)
        )["params"]
    )
    return cfg, quantize_tree(params) if cfg.quantize else params


def _mimo():
    import jax

    cfg = mimo_v2.mimo_v2_tiny(decode=True, max_decode_len=32)
    return cfg, mimo_v2.init_params(cfg, jax.random.key(0))


FAMILIES = {
    "llama": lambda: _llama(),
    "llama-int8": lambda: _llama(quantize="int8", kv_quantize="int8"),
    "mimo": _mimo,
}


def _filled(cache, seed):
    """The cache with every leaf drawn at random, so that a slot the
    prefill should not touch has something to lose."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)

    def draw(a):
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jnp.asarray(rng.integers(-5, 6, a.shape), a.dtype)
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)

    return jax.tree.map(draw, cache)


@pytest.mark.parametrize("slot", [0, 2])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_prefill_writes_its_own_slot_and_no_other(family, slot):
    """Two chunks into ``slot`` of a three-slot cache: the other slots'
    leaves stay bit-identical, and slot ``slot`` and the hidden states equal
    what the same two chunks give on that row alone (a one-slot cache)."""
    import jax
    import jax.numpy as jnp

    cfg, params = FAMILIES[family]()
    model = cfg.serving_model()
    before = _filled(model.init_cache(SLOTS, CHUNK), seed=3)
    row = jax.tree.map(lambda a: a[slot : slot + 1], before)
    prefill = jax.jit(model.prefill)
    toks = np.random.default_rng(1).integers(1, cfg.vocab_size, (2, 1, CHUNK)).astype(np.int32)
    cache = before
    for c in range(2):
        pos = (c * CHUNK + jnp.arange(CHUNK, dtype=jnp.int32))[None]
        hidden, cache, _ = prefill(params, cache, jnp.int32(slot), jnp.asarray(toks[c]), pos)
        want_hidden, row, _ = prefill(params, row, jnp.int32(0), jnp.asarray(toks[c]), pos)
        np.testing.assert_array_equal(np.asarray(hidden, np.float32), np.asarray(want_hidden, np.float32))
    for got, was, want in zip(jax.tree.leaves(cache), jax.tree.leaves(before), jax.tree.leaves(row)):
        got, was = np.asarray(got), np.asarray(was)
        others = [s for s in range(SLOTS) if s != slot]
        np.testing.assert_array_equal(got[others], was[others])
        np.testing.assert_array_equal(got[slot], np.asarray(want)[0])
    assert any((np.asarray(g)[slot] != np.asarray(w)[slot]).any()
               for g, w in zip(jax.tree.leaves(cache), jax.tree.leaves(before)))


@pytest.mark.parametrize("temperature", [0.0, 0.9], ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_chunk_program_runs_no_head_and_the_head_program_samples_from_the_one_programs_logits(family, temperature):
    """A chunk returns its hidden states and the cache the model's own
    prefill gives; ``prefill_chunk_head`` on a prompt's last chunk samples
    the first token from ``model.logits`` of the hidden state at the
    prompt's last position (what the one program computed on every chunk)
    with the key's second half, sets row ``slot`` of ``tok`` to it and of
    ``pos`` to the prompt's length, touches no other row, and hands back
    the key's first half."""
    import jax
    import jax.numpy as jnp

    from pytorch_operator_tpu.ops.sampling import make_sampler

    cfg, params = FAMILIES[family]()
    model = cfg.serving_model()
    sample = make_sampler(temperature, 0, 1.0)
    progs = programs(model, slots=SLOTS, chunk=CHUNK, block=4, sample=sample)
    toks = np.random.default_rng(2).integers(1, cfg.vocab_size, (2, 1, CHUNK)).astype(np.int32)
    zero = lambda: jax.tree.map(jnp.zeros_like, model.counts)
    cache, counts, slot, p = model.init_cache(SLOTS, CHUNK), zero(), jnp.int32(1), CHUNK + 6
    _, cache, counts = progs.prefill_chunk(params, cache, counts, slot, jnp.asarray(toks[0]), jnp.int32(0))
    before = jax.tree.map(jnp.copy, cache)
    hidden, cache, counts = progs.prefill_chunk(params, cache, counts, slot, jnp.asarray(toks[1]), jnp.int32(CHUNK))
    pos = (CHUNK + jnp.arange(CHUNK, dtype=jnp.int32))[None]
    want_hidden, want_cache, _ = jax.jit(model.prefill)(params, before, slot, jnp.asarray(toks[1]), pos)
    np.testing.assert_array_equal(np.asarray(hidden, np.float32), np.asarray(want_hidden, np.float32))
    for got, w in zip(jax.tree.leaves(cache), jax.tree.leaves(want_cache)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
    key = jax.random.key(11)
    next_key, sub = jax.random.split(key)
    logits = model.logits(params, want_hidden[:, (p - 1) % CHUNK])
    assert logits.shape == (1, cfg.vocab_size) and logits.dtype == jnp.float32
    want = int(sample(logits, sub)[0])
    if temperature == 0.0:
        assert want == int(np.argmax(np.asarray(logits[0])))  # what the host's argmax gave before PR 35
    row_tok, row_pos = jnp.asarray([7, 8, 9], jnp.int32), jnp.asarray([70, 80, 90], jnp.int32)
    row_tok, row_pos, first, got_key = progs.prefill_chunk_head(
        params, cache, hidden, row_tok, row_pos, np.int32(1), np.int32(p), key)
    assert first.shape == () and int(first) == want
    assert np.asarray(row_tok).tolist() == [7, want, 9] and np.asarray(row_pos).tolist() == [70, p, 90]
    assert (jax.random.key_data(got_key) == jax.random.key_data(next_key)).all()
    # Both are the prefill's programs by name (the benchmark finds them so).
    assert "prefill_chunk" in progs.prefill_chunk_head.__name__ and "prefill_chunk" in progs.prefill_chunk.__name__


@pytest.mark.parametrize("shape", [(64, 96), (64, 4, 24)], ids=["2d", "3d"])
def test_the_scale_behind_the_product_is_the_dequantized_product_and_no_worse(shape):
    """``(x @ q) * scale`` with float32 accumulation against
    ``x @ dequantize()``: equal to bfloat16 rounding, and not further from
    the float32 product of the same quantized weight."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal(shape) * rng.uniform(0.1, 3.0, shape[1:]), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 16, shape[0])), jnp.bfloat16)
    qt = quantize(w, axis=0)
    dims = (((2,), (0,)), ((), ()))
    exact = jax.lax.dot_general(
        x.astype(jnp.float32), qt.dequantize(jnp.float32), dims, precision="highest")
    new = jax.jit(qt.project)(x)
    old = jax.lax.dot_general(x, qt.dequantize(jnp.bfloat16), dims)
    assert new.dtype == jnp.bfloat16 and new.shape == (2, 16, *shape[1:])
    err = lambda y: float(jnp.abs(y.astype(jnp.float32) - exact).max())
    scale = float(jnp.abs(exact).max())
    assert err(new) <= 2.0**-8 * scale  # one bfloat16 rounding of the result
    assert err(new) <= err(old) + 1e-6 * scale
    np.testing.assert_allclose(
        np.asarray(new, np.float32), np.asarray(old, np.float32), atol=2.0**-6 * scale)


def test_project_refuses_a_scale_that_is_not_one_per_output_channel():
    import jax.numpy as jnp

    w = jnp.ones((8, 4), jnp.float32)
    per_row = quantize(w, axis=-1)  # the embedding's rule: one scale per row
    with pytest.raises(ValueError, match="one scale per output channel"):
        per_row.project(jnp.ones((2, 8), jnp.bfloat16))


def test_a_quantized_layer_reaches_its_projections_as_int8():
    """``decode_forward`` hands a layer's ``QuantizedTensor`` leaves to its
    modules as they are: every one of a block's seven products takes an
    int8 operand converted in place (no dequantised weight is an
    intermediate of its own shape in the traced program)."""
    import jax
    import jax.numpy as jnp

    cfg, params = _llama(quantize="int8", kv_quantize="int8")
    model = cfg.serving_model()
    cache = model.init_cache(SLOTS, CHUNK)
    pos = jnp.arange(CHUNK, dtype=jnp.int32)[None]
    jaxpr = jax.make_jaxpr(model.prefill)(params, cache, jnp.int32(0), jnp.zeros((1, CHUNK), jnp.int32), pos)
    kernels = {leaf.q.shape[1:] for leaf in jax.tree.leaves(
        params["layers"], is_leaf=lambda t: isinstance(t, QuantizedTensor)) if isinstance(leaf, QuantizedTensor)}
    # The layers are ONE traced function (PR 47): the program calls it once a layer, and its equations are a layer's.
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "jit" and e.params["name"] == "layer"]
    assert len(calls) == cfg.n_layers and len({id(e.params["jaxpr"]) for e in calls}) == 1
    layer = calls[0].params["jaxpr"].jaxpr.eqns
    dots = [e for e in layer if e.primitive.name == "dot_general"]
    weight_dots = [e for e in dots if e.invars[1].aval.shape in kernels]
    assert len(weight_dots) == 7
    assert all(e.outvars[0].aval.dtype == jnp.float32 for e in weight_dots)
    # ... and nothing multiplies a kernel-shaped array by its scale before the product.
    muls = [e for e in [*layer, *jaxpr.jaxpr.eqns] if e.primitive.name == "mul" and e.outvars[0].aval.shape in kernels]
    assert not muls


@pytest.mark.parametrize("family", ["llama-int8", "mimo"])
def test_prefill_head_chunks_counts_one_a_request(family):
    cfg, params = FAMILIES[family]()
    eng = ServingEngine(cfg, params, slots=2, chunk=CHUNK, block=4)
    rng = np.random.default_rng(5)
    lengths = [3, CHUNK, CHUNK + 1, 2 * CHUNK + 3]
    for i, p in enumerate(lengths):
        eng.submit(Request(id=f"r{i}", prompt=rng.integers(1, cfg.vocab_size, p).astype(np.int32),
                           max_new_tokens=2, submit_time=time.time()))
    eng.run_until_drained()
    stats = eng.stats()
    assert stats["admitted"] == stats["prefill_head_chunks"] == len(lengths)
    assert stats["prefill_chunks"] == sum(-(-p // CHUNK) for p in lengths) == 7
    eng.reset_stats()
    assert eng.stats()["prefill_head_chunks"] == 0


# ---- the serving arrangement: a tree a layer (models.llama.per_layer_params) ----


@pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
def test_layers_held_a_tree_each_give_the_stacked_trees_results_to_the_bit(quantized):
    import jax
    import jax.numpy as jnp

    cfg, stacked = _llama(**({"quantize": "int8", "kv_quantize": "int8"} if quantized else {}))
    model = cfg.serving_model()
    keep, params = stacked, model.arrange(stacked)
    assert isinstance(params["layers"], list) and len(params["layers"]) == cfg.n_layers
    assert jax.tree.structure(params["layers"][0]) == jax.tree.structure(keep["layers"])
    assert model.arrange(params) is params
    # The model's own init makes them so, in the program that makes the weights.
    born = jax.eval_shape(model.init_params, jax.random.key(0))
    assert isinstance(born["layers"], list) and len(born["layers"]) == cfg.n_layers
    toks = jnp.asarray(np.random.default_rng(4).integers(1, cfg.vocab_size, (1, CHUNK)), jnp.int32)
    pos = jnp.arange(CHUNK, dtype=jnp.int32)[None]
    run = lambda p: jax.jit(model.prefill)(p, model.init_cache(SLOTS, CHUNK), jnp.int32(1), toks, pos)
    (h_list, c_list, _), (h_stack, c_stack, _) = run(params), run(keep)
    np.testing.assert_array_equal(np.asarray(h_list, np.float32), np.asarray(h_stack, np.float32))
    for a, b in zip(jax.tree.leaves(c_list), jax.tree.leaves(c_stack)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    step = lambda p: model.decode(p, c_list, jnp.ones((SLOTS, 1), jnp.int32), jnp.full((SLOTS, 1), CHUNK, jnp.int32))[0]
    np.testing.assert_array_equal(np.asarray(step(params)), np.asarray(step(keep)))


def test_quantizing_a_tree_held_a_layer_each_is_quantizing_the_stack():
    import jax

    cfg, stacked = _llama()
    want = llama_lib.per_layer_params(quantize_tree(stacked))
    got = quantize_tree(llama_lib.per_layer_params(stacked))
    assert jax.tree.structure(got, is_leaf=lambda t: isinstance(t, QuantizedTensor)) == jax.tree.structure(
        want, is_leaf=lambda t: isinstance(t, QuantizedTensor))
    kernels = [t for t in jax.tree.leaves(got["layers"], is_leaf=lambda t: isinstance(t, QuantizedTensor))
               if isinstance(t, QuantizedTensor)]
    assert len(kernels) == 7 * cfg.n_layers
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_load_params_hands_the_llama_family_its_layers_a_tree_each(quantize):
    from pytorch_operator_tpu.workloads.generate import load_params

    cfg = llama_lib.llama_tiny(decode=True, max_decode_len=32, quantize=quantize)
    params, _, n_params, _, _ = load_params(cfg, config="tiny", quantize=quantize, log=lambda *_: None)
    assert isinstance(params["layers"], list) and len(params["layers"]) == cfg.n_layers
    assert n_params > 0
    kernel = params["layers"][0]["attn"]["q_proj"]["kernel"]
    assert isinstance(kernel, QuantizedTensor) == (quantize == "int8")
    assert kernel.shape == (cfg.d_model, cfg.n_heads, cfg.head_dim)


def test_the_trainer_runs_none_of_it(monkeypatch):
    """The training model's projections are ``Dense``, which is
    ``nn.DenseGeneral`` unless handed an int8 kernel: a training step
    (forward, backward, update; the parameters' init too) lowers to the same
    text, locations aside, with either class."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    cfg = llama_lib.llama_tiny()
    tokens = jnp.zeros((2, 16), jnp.int32)

    def lowered():
        model = llama_lib.Llama(cfg)
        tx = optax.adafactor(1e-3)

        def init(key):
            params = nn.meta.unbox(model.init(key, tokens)["params"])
            return params, tx.init(params)

        def step(params, opt_state, tokens):
            def loss(p):
                logits = model.apply({"params": p}, tokens).astype(jnp.float32)
                return optax.softmax_cross_entropy_with_integer_labels(logits[:, :-1], tokens[:, 1:]).mean()

            value, grads = jax.value_and_grad(loss)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, value

        key = jax.random.key(0)
        state = jax.eval_shape(init, key)
        return jax.jit(init).lower(key).as_text(), jax.jit(step).lower(*state, tokens).as_text()

    changed = lowered()
    monkeypatch.setattr(llama_lib, "Dense", nn.DenseGeneral)
    assert lowered() == changed
    assert "dot_general" in changed[1]
