"""Where the ``mimo_v2`` family reaches into the program, and nowhere else:

- ``models.mimo_v2.CONFIGS`` gains the preset ``bench`` (``models.serving``
  reads that table when the server's ``--config bench`` is resolved), made by
  ``models.mimo_v2.make_config`` from the configuration file's sizes: every
  width, the layer kinds of its ``num_hidden_layers`` first layers, the
  router's width and the experts held;
- ``models.mimo_v2.init_layer`` and ``models.mimo_v2.init_outer``, the two
  functions ``init_params`` makes the tree with, a layer at a time and in the
  serving dtype, are replaced by ones that return the benchmark's seeded
  leaves (same paths, shapes and dtypes).

The one other point is the entry module's and every family's:
``ServingEngine.submit`` (``entry_serve.py``).
"""

from __future__ import annotations

from . import weights as W


def config_base(d: dict) -> dict:
    """``weights.dims`` of a configuration file as the fields of the
    program's ``MiMoV2Config``."""
    if d["sink"] != {W.FULL: False, W.WINDOW: True}:
        raise ValueError("the program gives window layers a sink and full layers none; the configuration differs")
    return dict(
        vocab_size=d["V"], d_model=d["D"], n_heads=d["H"], qk_head_dim=d["dqk"], v_head_dim=d["dv"],
        rotary_dim=d["rot"], n_kv_heads_full=d["Hk"][W.FULL], n_kv_heads_window=d["Hk"][W.WINDOW],
        rope_theta=d["theta"][W.FULL], window_rope_theta=d["theta"][W.WINDOW], window=d["window"],
        value_scale=d["vscale"], d_ff=d["F"], d_expert=d["Fe"], router_width=d["E"], experts_held=d["held"],
        top_k=d["k"], layers=d["kinds"], rms_eps=d["eps"],
    )


def install(model: dict) -> None:
    from pytorch_operator_tpu.models import mimo_v2

    d = W.dims(model)
    base = config_base(d)
    mimo_v2.bench_config = lambda **over: mimo_v2.make_config(base, over)
    mimo_v2.CONFIGS["bench"] = "bench_config"
    mimo_v2.init_layer = lambda cfg, kind, key, layer: W.make_layer(d, key, layer, kind, cfg.param_dtype)
    mimo_v2.init_outer = lambda cfg, key: W.make_outer(d, key, cfg.param_dtype)
