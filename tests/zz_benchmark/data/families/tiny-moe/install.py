"""Where the made-up ``tiny-moe`` family reaches into the program, by itself:

- ``models.llama.Llama.init`` is wrapped to return this family's seeded
  leaves (the expert layer's among them);
- ``workloads.llama_train.CONFIGS`` gains the preset ``bench``, which states
  the configuration file's sizes through ``models.llama.llama3_8b``, the
  expert layer's beside the block's (``n_experts``, ``moe_top_k``, dense
  dispatch): the file states them once, and no argument of the job repeats them.
"""

from __future__ import annotations

from . import weights as W


def install(model: dict) -> None:
    import flax.linen as nn

    from pytorch_operator_tpu.models import llama as llama_lib
    from pytorch_operator_tpu.workloads import llama_train

    d = W.dims(model)
    llama_lib.bench_config = lambda **over: llama_lib.llama3_8b(**{
        "vocab_size": d["V"], "d_model": d["D"], "n_layers": d["L"], "n_heads": d["H"], "n_kv_heads": d["K"],
        "head_dim": d["hd"], "d_ff": d["F"], "rope_theta": d["theta"], "rms_eps": d["eps"],
        "n_experts": d["E"], "moe_top_k": d["k"], "moe_dispatch": "dense", **over})
    llama_train.CONFIGS["bench"] = "bench_config"
    flax_init = llama_lib.Llama.init

    def seeded_init(self, rngs, *args, **kwargs):
        variables = flax_init(self, rngs, *args, **kwargs)
        key = rngs["params"] if isinstance(rngs, dict) else rngs
        mine = W.make_params(W.dims(model | {"num_hidden_layers": self.cfg.n_layers}), key, self.cfg.param_dtype)
        return {**variables, "params": nn.meta.replace_boxed(variables["params"], mine)}

    llama_lib.Llama.init = seeded_init
