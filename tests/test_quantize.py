"""Weight-only int8 quantization (ops/quantize.py) and its decode-path
integration (workloads/generate.py --quantize int8).

Load-bearing properties:
- per-channel symmetric quantization honors its error bound (|w - deq|
  <= scale/2 per element);
- the name→contraction-axis rule lands on the right axes of every
  llama param family (incl. scan-stacked leading ``layers`` axes and
  MoE expert banks) and leaves precision-sensitive leaves (norm scales,
  MoE router) untouched;
- generate() fed QuantizedTensor leaves is BIT-IDENTICAL to generate()
  fed the eagerly-dequantized tree — quantization changes where the
  weights live (int8 in HBM, dequant fused in-program), never the math
  downstream of dequantization.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from pytorch_operator_tpu.models import llama as llama_lib
from pytorch_operator_tpu.ops.quantize import (
    QuantizedTensor,
    contract_axis,
    dequantize_tree,
    quantize,
    quantize_tree,
    tree_bytes,
)
from pytorch_operator_tpu.workloads.generate import init_cache, make_generate


def _tiny_params(**cfg_over):
    import jax

    cfg = llama_lib.llama_tiny(**cfg_over)
    model = llama_lib.Llama(cfg)
    import flax.linen as nn

    params = nn.meta.unbox(
        model.init(jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    )
    return cfg, model, params


class TestQuantize:
    def test_roundtrip_error_bound(self):
        import jax

        w = jax.random.normal(jax.random.key(1), (64, 48), jnp_dtype())
        qt = quantize(w, axis=-2)
        assert qt.q.dtype == np.int8
        assert qt.scale.shape == (1, 48)
        err = np.abs(np.asarray(qt.dequantize()) - np.asarray(w))
        bound = np.asarray(qt.scale) / 2 + 1e-7
        assert (err <= bound).all()
        # Scales really are per-channel maxima / 127.
        np.testing.assert_allclose(
            np.asarray(qt.scale[0]),
            np.abs(np.asarray(w)).max(axis=0) / 127.0,
            rtol=1e-6,
        )

    def test_zero_and_extreme_channels(self):
        import jax.numpy as jnp

        w = jnp.stack(
            [jnp.zeros((8,)), jnp.full((8,), 1e30), jnp.full((8,), -3.0)],
            axis=1,
        )
        qt = quantize(w, axis=-2)
        deq = np.asarray(qt.dequantize())
        np.testing.assert_array_equal(deq[:, 0], 0.0)
        np.testing.assert_allclose(deq[:, 1], 1e30, rtol=1e-6)
        np.testing.assert_allclose(deq[:, 2], -3.0, rtol=1e-6)

    def test_rule_axes_on_llama_tree(self):
        cfg, _, params = _tiny_params()
        qtree = quantize_tree(params)
        layers = qtree["layers"]

        def scale_shape(leaf):
            assert isinstance(leaf, QuantizedTensor)
            return leaf.scale.shape

        L, D, H, K, Dh = (
            cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim,
        )
        # q/k/v: [L, D, heads, Dh] quantized over the embed axis (-3) —
        # per-layer, per-(head, head_dim) channels.
        assert scale_shape(layers["attn"]["q_proj"]["kernel"]) == (L, 1, H, Dh)
        assert scale_shape(layers["attn"]["k_proj"]["kernel"]) == (L, 1, K, Dh)
        # o_proj [L, H*Dh, D] and MLP [L, in, out]: contraction -2.
        assert scale_shape(layers["attn"]["o_proj"]["kernel"]) == (L, 1, D)
        assert scale_shape(layers["mlp"]["gate_proj"]["kernel"]) == (
            L, 1, cfg.d_ff,
        )
        assert scale_shape(layers["mlp"]["down_proj"]["kernel"]) == (L, 1, D)
        # Embed rows; head columns.
        assert scale_shape(qtree["embed"]["embedding"]) == (cfg.vocab_size, 1)
        assert scale_shape(qtree["lm_head"]["kernel"]) == (1, cfg.vocab_size)
        # Norm scales stay full-precision arrays.
        assert not isinstance(
            layers["attn_norm"]["scale"], QuantizedTensor
        )
        assert not isinstance(qtree["final_norm"]["scale"], QuantizedTensor)

    def test_moe_banks_quantized_router_kept(self):
        cfg, _, params = _tiny_params(n_experts=4, moe_aux_weight=1e-2)
        qtree = quantize_tree(params)
        moe = qtree["layers"]["moe_mlp"]
        assert isinstance(moe["w_in"], QuantizedTensor)
        assert moe["w_in"].scale.shape == (
            cfg.n_layers, cfg.n_experts, 1, cfg.d_ff,
        )
        assert isinstance(moe["w_out"], QuantizedTensor)
        # The router's argmax is precision-sensitive — never quantized.
        assert not isinstance(moe["gate"], QuantizedTensor)

    def test_unknown_quantize_mode_rejected_at_config(self):
        import pytest

        with pytest.raises(ValueError, match="quantize"):
            llama_lib.llama_tiny(quantize="int4")

    def test_rule_skips_low_rank_leaves(self):
        import jax.numpy as jnp

        assert contract_axis(("anything", "kernel"), jnp.zeros((4,))) is None
        assert contract_axis(("x", "scale"), jnp.zeros((4, 4))) is None

    def test_tree_bytes_quarter_of_f32(self):
        _, _, params = _tiny_params()
        import jax

        f32 = sum(p.size * 4 for p in jax.tree.leaves(params))
        q = tree_bytes(quantize_tree(params))
        # int8 payload + scales + the unquantized norm leaves: well under
        # half, approaching a quarter.
        assert q < 0.30 * f32

    def test_dequantize_tree_identity_on_plain_trees(self):
        _, _, params = _tiny_params()
        out = dequantize_tree(params)
        import jax

        assert jax.tree.structure(out) == jax.tree.structure(params)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(params)):
            assert a is b

    def test_forward_logits_survive_quantization(self):
        """End-to-end accuracy proxy: full-forward logits through the
        quantized weights stay close (normalized RMS) to the original's
        — per-channel int8 at 127 levels is a sub-percent weight error."""
        cfg, model, params = _tiny_params()
        toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8))
        toks = toks.astype(np.int32)
        ref = np.asarray(model.apply({"params": params}, toks))
        deq = dequantize_tree(quantize_tree(params))
        got = np.asarray(model.apply({"params": deq}, toks))
        rms = np.sqrt(((got - ref) ** 2).mean()) / np.sqrt((ref**2).mean())
        assert rms < 0.02, rms


class TestQuantizedGenerate:
    def test_quantized_generate_bit_identical_to_eager_dequant(self):
        """THE integration invariant: a quantize-mode model fed
        QuantizedTensor leaves (dequant inside the scan body, int8 in
        HBM) produces exactly the tokens of the same program fed the
        eagerly-dequantized tree — same math, different residency.
        (map_variables' trans_in is identity on plain arrays, so one
        jitted program serves both sides of the A/B.)"""
        import jax

        new = 8
        cfg = llama_lib.llama_tiny(
            decode=True, max_decode_len=16, quantize="int8"
        )
        decode_model = llama_lib.Llama(cfg)
        _, _, params = _tiny_params()
        qparams = jax.jit(quantize_tree)(params)

        gen = make_generate(decode_model, max_new_tokens=new)
        prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 8))
        import jax.numpy as jnp

        prompt = jnp.asarray(prompt, jnp.int32)

        cache = init_cache(decode_model, 2, 8)
        t_q, _ = gen(qparams, cache, prompt, jax.random.key(0))
        cache = init_cache(decode_model, 2, 8)
        t_e, _ = gen(dequantize_tree(qparams), cache, prompt, jax.random.key(0))
        np.testing.assert_array_equal(np.asarray(t_q), np.asarray(t_e))

    def test_quantize_mode_full_forward_matches_plain_model(self):
        """Llama(quantize='int8').apply on the quantized tree ==
        plain Llama.apply on the eagerly dequantized tree, exactly —
        the in-module map_variables hook rearranges residency, not
        numerics. Also: a quantize-mode model refuses to init."""
        import jax
        import pytest

        cfg, model, params = _tiny_params()
        qcfg = dataclasses.replace(cfg, quantize="int8")
        qmodel = llama_lib.Llama(qcfg)
        qparams = quantize_tree(params)
        toks = np.random.default_rng(3).integers(
            0, cfg.vocab_size, (2, 8)
        ).astype(np.int32)
        got = qmodel.apply({"params": qparams}, toks)
        ref = model.apply({"params": dequantize_tree(qparams)}, toks)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        with pytest.raises(ValueError, match="quantize-mode"):
            qmodel.init(jax.random.key(0), toks)

    def test_run_quantized_smoke(self, tmp_path):
        """The workload path end to end on CPU (chip runs ride this exact
        entry)."""
        from pytorch_operator_tpu.workloads import generate as gen_mod

        result = gen_mod.run(
            config="tiny", batch_size=2, prompt_len=8, max_new_tokens=4,
            quantize="int8", log=lambda *a: None,
        )
        assert result["quantize"] == "int8"
        assert result["value"] > 0
        assert result["weight_mb"] > 0

    def test_init_host_requires_quantize(self):
        import pytest

        from pytorch_operator_tpu.workloads import generate as gen_mod

        with pytest.raises(ValueError, match="init_host"):
            gen_mod.run(config="tiny", init_host=True, log=lambda *a: None)
        with pytest.raises(ValueError, match="compare_unquantized"):
            gen_mod.run(
                config="tiny", quantize="int8", init_host=True,
                compare_unquantized=True, log=lambda *a: None,
            )

    def test_compare_unquantized_reports_control(self):
        from pytorch_operator_tpu.workloads import generate as gen_mod

        result = gen_mod.run(
            config="tiny", batch_size=2, prompt_len=8, max_new_tokens=4,
            quantize="int8", compare_unquantized=True, log=lambda *a: None,
        )
        assert result["tokens_per_sec_per_chip_unquantized"] > 0
        assert result["int8_speedup"] > 0

    @pytest.mark.slow
    def test_init_host_path_runs(self):
        """Host-init + host-quantize + device_put (the 8B-on-one-chip
        path) — on CPU the 'transfer' is trivial but the code path and
        tree plumbing are identical."""
        from pytorch_operator_tpu.workloads import generate as gen_mod

        result = gen_mod.run(
            config="tiny", batch_size=2, prompt_len=8, max_new_tokens=4,
            quantize="int8", init_host=True, log=lambda *a: None,
        )
        assert result["quantize"] == "int8"


class TestKVQuantize:
    def _decode_models(self):
        cfg = llama_lib.llama_tiny(decode=True, max_decode_len=16)
        q_cfg = dataclasses.replace(cfg, kv_quantize="int8")
        return llama_lib.Llama(cfg), llama_lib.Llama(q_cfg)

    def test_cache_layout_int8_with_scales(self):
        _, qmodel = self._decode_models()
        cache = init_cache(qmodel, 2, 8)
        assert set(cache) == {
            f"layer_{i}" for i in range(qmodel.cfg.n_layers)
        }
        layer = cache["layer_0"]["attn"]
        assert layer["cached_key"].dtype == np.int8
        assert layer["cached_value"].dtype == np.int8
        # Heads-major slabs, per-(token, kv-head) f32 scales: one per
        # head_dim payload row.
        assert layer["key_scale"].shape == (
            2, qmodel.cfg.n_kv_heads, 16, 1,
        )
        assert layer["key_scale"].dtype == np.float32

    @pytest.mark.slow
    def test_decode_forward_matches_flax_apply(self):
        """The unrolled serving path (decode_forward — flat per-layer
        cache, token-slice writes) is numerically IDENTICAL to the flax
        scan-lifted decode apply, with and without the int8 cache."""
        import jax
        import jax.numpy as jnp

        from pytorch_operator_tpu.models.llama import (
            decode_forward,
            init_decode_cache,
        )

        _, _, params = _tiny_params()
        toks = jnp.asarray(
            np.random.default_rng(7).integers(0, 256, (2, 8)), jnp.int32
        )
        for kv in (None, "int8"):
            cfg = llama_lib.llama_tiny(
                decode=True, max_decode_len=16, kv_quantize=kv
            )
            model = llama_lib.Llama(cfg)
            flax_cache = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(
                    lambda k: model.init(k, np.zeros((2, 8), np.int32)),
                    jax.random.key(0),
                )["cache"],
            )
            nxt = jnp.full((2, 1), 3, jnp.int32)
            pos = jnp.full((2, 1), 8, jnp.int32)
            # Flax path: prefill then one decode step.
            ref_h, upd = model.apply(
                {"params": params, "cache": flax_cache},
                toks,
                return_hidden=True,
                mutable=["cache"],
            )
            ref_h2, _ = model.apply(
                {"params": params, "cache": upd["cache"]},
                nxt,
                pos,
                return_hidden=True,
                mutable=["cache"],
            )
            # Functional path, same inputs. Tolerance, not bit-identity:
            # the flax path executes the layer stack as one compiled
            # lax.scan while this path unrolls it, and XLA's fusion
            # boundaries differ — last-ulp reassociation only (the
            # greedy-rollout gold test pins token-level equality).
            cache = init_decode_cache(cfg, 2)
            got_h, cache = decode_forward(model, params, cache, toks)
            got_h2, _ = decode_forward(model, params, cache, nxt, pos)
            np.testing.assert_allclose(
                np.asarray(got_h), np.asarray(ref_h), rtol=2e-5, atol=2e-6
            )
            np.testing.assert_allclose(
                np.asarray(got_h2), np.asarray(ref_h2), rtol=2e-5, atol=2e-6
            )

    def test_prefill_outputs_close_to_fp_cache(self):
        """The int8 cache changes K/V by at most scale/2 per element —
        prefill hidden states must track the fp-cache path within the
        quantization error, not diverge structurally."""
        import jax

        model, qmodel = self._decode_models()
        _, _, params = _tiny_params()
        toks = np.random.default_rng(5).integers(0, 256, (2, 8))
        toks = toks.astype(np.int32)

        def prefill(m):
            # No cache passed: the flax path zero-initializes its own
            # (scan-stacked) cache under mutable — init_cache's flat
            # decode_forward layout would be silently ignored here.
            out, _ = m.apply(
                {"params": params},
                toks,
                return_hidden=True,
                mutable=["cache"],
            )
            return np.asarray(jax.block_until_ready(out))

        ref, got = prefill(model), prefill(qmodel)
        rms = np.sqrt(((got - ref) ** 2).mean()) / np.sqrt((ref**2).mean())
        assert rms < 0.02, rms

    @pytest.mark.slow
    def test_generate_runs_and_tracks_fp_rollout(self):
        """End to end through make_generate: the int8-cache rollout is
        valid tokens; on this tiny model the greedy path stays within
        the fp rollout for at least the first steps (argmax margins at
        random init are far wider than the cache quantization error)."""
        import jax
        import jax.numpy as jnp

        model, qmodel = self._decode_models()
        _, _, params = _tiny_params()
        prompt = jnp.asarray(
            np.random.default_rng(6).integers(0, 256, (2, 8)), jnp.int32
        )
        new = 6
        t_fp, _ = make_generate(model, max_new_tokens=new)(
            params, init_cache(model, 2, 8), prompt, jax.random.key(0)
        )
        t_q, _ = make_generate(qmodel, max_new_tokens=new)(
            params, init_cache(qmodel, 2, 8), prompt, jax.random.key(0)
        )
        assert t_q.shape == (2, new)
        assert ((0 <= np.asarray(t_q)) & (np.asarray(t_q) < 256)).all()
        np.testing.assert_array_equal(
            np.asarray(t_q)[:, :2], np.asarray(t_fp)[:, :2]
        )

    @pytest.mark.slow
    def test_moe_decode_forward_matches_flax_apply(self):
        """The unrolled serving path must also carry MoE blocks (router
        + expert banks slice per layer like any stacked leaf)."""
        import jax.numpy as jnp

        from pytorch_operator_tpu.models.llama import (
            decode_forward,
            init_decode_cache,
        )

        _, _, params = _tiny_params(n_experts=4, moe_top_k=2)
        cfg = llama_lib.llama_tiny(
            decode=True, max_decode_len=16, n_experts=4, moe_top_k=2
        )
        model = llama_lib.Llama(cfg)
        toks = jnp.asarray(
            np.random.default_rng(8).integers(0, 256, (2, 8)), jnp.int32
        )
        ref, _ = model.apply(
            {"params": params},
            toks,
            return_hidden=True,
            mutable=["cache"],
        )
        got, _ = decode_forward(
            model, params, init_decode_cache(cfg, 2), toks
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-6
        )

    @pytest.mark.slow
    def test_quantized_moe_decode_runs(self):
        """Quantized expert banks (w_in/w_out QuantizedTensors) slice
        and dequantize per layer through the serving path."""
        import jax.numpy as jnp

        from pytorch_operator_tpu.models.llama import (
            decode_forward,
            init_decode_cache,
        )

        _, _, params = _tiny_params(n_experts=4, moe_top_k=2)
        qparams = quantize_tree(params)
        cfg = llama_lib.llama_tiny(
            decode=True, max_decode_len=16, n_experts=4, moe_top_k=2,
            quantize="int8",
        )
        model = llama_lib.Llama(cfg)
        toks = jnp.asarray(
            np.random.default_rng(9).integers(0, 256, (2, 8)), jnp.int32
        )
        got_q, _ = decode_forward(
            model, qparams, init_decode_cache(cfg, 2), toks
        )
        ref, _ = decode_forward(
            model, dequantize_tree(qparams), init_decode_cache(cfg, 2), toks
        )
        # The attention's projections scale behind their product since PR
        # 31 (float32 throughout here), the banks dequantize first: equal
        # to rounding, no longer to the bit.
        np.testing.assert_allclose(np.asarray(got_q), np.asarray(ref), rtol=1e-4, atol=1e-5)

    @pytest.mark.slow
    def test_decode_forward_tp_sharded_matches_unsharded(self):
        """Distributed serving: decode_forward under a dp×fsdp×tp mesh
        with born-sharded params (logical rules: heads/mlp/vocab over
        tp, embed over fsdp, batch over dp) produces the unsharded
        path's hidden states — SPMD partitioning changes collectives,
        not semantics."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from pytorch_operator_tpu.models.llama import (
            decode_forward,
            init_decode_cache,
        )
        from pytorch_operator_tpu.parallel import make_mesh
        from pytorch_operator_tpu.parallel.logical import init_sharded

        cfg = llama_lib.llama_tiny(decode=True, max_decode_len=16)
        model = llama_lib.Llama(cfg)
        train_model = llama_lib.Llama(
            dataclasses.replace(cfg, decode=False)
        )

        def init_fn(key):
            return train_model.init(key, np.zeros((1, 8), np.int32))[
                "params"
            ]

        mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
        sh_params, _ = init_sharded(init_fn, mesh, jax.random.key(0))
        _, _, ref_params = _tiny_params()  # same seed, unsharded

        toks = jnp.asarray(
            np.random.default_rng(11).integers(0, 256, (2, 8)), jnp.int32
        )
        ref_h, _ = decode_forward(
            model, ref_params, init_decode_cache(cfg, 2), toks
        )
        got_h, _ = jax.jit(
            lambda p, c, t: decode_forward(model, p, c, t)
        )(sh_params, init_decode_cache(cfg, 2), toks)
        np.testing.assert_allclose(
            np.asarray(got_h), np.asarray(ref_h), rtol=2e-4, atol=2e-5
        )

    def test_unknown_kv_mode_rejected(self):
        import pytest

        with pytest.raises(ValueError, match="kv_quantize"):
            llama_lib.llama_tiny(kv_quantize="fp8")

    def test_run_kv_quantized_smoke(self):
        from pytorch_operator_tpu.workloads import generate as gen_mod

        result = gen_mod.run(
            config="tiny", batch_size=2, prompt_len=8, max_new_tokens=4,
            kv_quantize="int8", max_decode_len=32, log=lambda *a: None,
        )
        assert result["kv_quantize"] == "int8"
        assert result["max_decode_len"] == 32
        assert result["value"] > 0


def jnp_dtype():
    import jax.numpy as jnp

    return jnp.float32
