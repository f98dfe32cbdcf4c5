"""Where the ``exaone_moe`` family reaches into the program, and nowhere else:

- ``models.mimo_v2.CONFIGS`` gains the preset ``bench`` (``models.serving``
  reads that table when the server's ``--config bench`` is resolved), made by
  ``models.mimo_v2.make_config`` from the configuration file's sizes and
  K-EXAONE's options of the layer-pattern family (no sink, q/k norms, rotary
  in window layers only, the shared expert, the routed scale, the
  multi-token-prediction block): a program without those options (the parent
  commit's) refuses the preset when it is made, before anything runs;
- ``models.mimo_v2.init_layer``, ``models.mimo_v2.init_outer`` and
  ``models.mimo_v2.init_mtp``, the functions ``init_params`` makes the tree
  with, a layer at a time and in the serving dtype, are replaced by ones that
  return the benchmark's seeded leaves (same paths, shapes and dtypes).

The one other point is the entry module's and every family's:
``ServingEngine.submit`` (``entry_serve.py``).
"""

from __future__ import annotations

from . import weights as W


def config_base(d: dict) -> dict:
    """``weights.dims`` of a configuration file as the fields of the
    program's ``MiMoV2Config``."""
    return dict(
        vocab_size=d["V"], d_model=d["D"], n_heads=d["H"], qk_head_dim=d["dh"], v_head_dim=d["dh"],
        rotary_dim=d["dh"], n_kv_heads_full=d["Hk"], n_kv_heads_window=d["Hk"], rope_theta=d["theta"],
        window_rope_theta=d["theta"], window=d["window"], value_scale=1.0, d_ff=d["F"], d_expert=d["Fe"],
        d_shared=d["Fs"], router_width=d["E"], experts_held=d["held"], top_k=d["k"], layers=d["kinds"],
        rms_eps=d["eps"], routed_scale=d["scale"], window_sink=False, qk_norm=True, rope_full=False, mtp=True,
    )


def install(model: dict) -> None:
    from pytorch_operator_tpu.models import mimo_v2

    d = W.dims(model)
    base = config_base(d)
    mimo_v2.make_config(base, {})  # a program that lacks an option fails here
    mimo_v2.bench_config = lambda **over: mimo_v2.make_config(base, over)
    mimo_v2.CONFIGS["bench"] = "bench_config"
    mimo_v2.init_layer = lambda cfg, kind, key, layer: W.make_layer(d, key, layer, kind, cfg.param_dtype)
    mimo_v2.init_outer = lambda cfg, key: W.make_outer(d, key, cfg.param_dtype)
    mimo_v2.init_mtp = lambda cfg, key: W.make_mtp(d, key, cfg.param_dtype)
