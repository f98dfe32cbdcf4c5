"""Where the ``llama`` family reaches into the program, and nowhere else:

- ``models.llama.Llama.init`` is wrapped to return the benchmark's seeded
  weights (same tree, same sharding metadata);
- ``workloads.llama_train.CONFIGS`` gains the preset ``bench``, which the
  server's ``--config`` reads too, stating the configuration file's sizes
  through ``models.llama.llama3_8b``.

The three other points are the entry modules' and every family's:
``llama_train.synthetic_bigram_batch``, ``trainer.throughput_loop``
(``entry_train.py``) and ``ServingEngine.submit`` (``entry_serve.py``).
"""

from __future__ import annotations

from . import weights as W


def install(model: dict) -> None:
    import flax.linen as nn

    from pytorch_operator_tpu.models import llama as llama_lib
    from pytorch_operator_tpu.workloads import llama_train

    d = W.dims(model)

    def bench_config(**over):
        return llama_lib.llama3_8b(**{
            "vocab_size": d["V"], "d_model": d["D"], "n_layers": d["L"],
            "n_heads": d["H"], "n_kv_heads": d["K"], "head_dim": d["hd"],
            "d_ff": d["F"], "rope_theta": d["theta"], "rms_eps": d["eps"], **over,
        })

    llama_lib.bench_config = bench_config
    llama_train.CONFIGS["bench"] = "bench_config"
    flax_init = llama_lib.Llama.init

    def seeded_init(self, rngs, *args, **kwargs):
        variables = flax_init(self, rngs, *args, **kwargs)
        key = rngs["params"] if isinstance(rngs, dict) else rngs
        mine = W.make_params(W.dims(model | {"num_hidden_layers": self.cfg.n_layers}),
                             key, self.cfg.param_dtype)
        return {**variables, "params": nn.meta.replace_boxed(variables["params"], mine)}

    llama_lib.Llama.init = seeded_init
