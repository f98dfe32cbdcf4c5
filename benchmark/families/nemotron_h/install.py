"""Where the ``nemotron_h`` family reaches into the program, and nowhere else:

- ``models.nemotron_h.CONFIGS`` gains the preset ``bench`` (``models.serving``
  reads that table when the server's ``--config bench`` is resolved), made by
  ``models.nemotron_h.make_config`` from the configuration file's sizes: every
  width, the mixers of its ``num_hidden_layers`` first layers, the router's
  width and the experts held;
- ``models.nemotron_h.init_layer`` and ``models.nemotron_h.init_outer``, the
  two functions ``init_params`` makes the tree with, a layer at a time and in
  the serving dtype, are replaced by ones that return the benchmark's seeded
  leaves (same paths, shapes and dtypes).

The one other point is the entry module's and every family's:
``ServingEngine.submit`` (``entry_serve.py``).
"""

from __future__ import annotations

from . import weights as W


def config_base(d: dict) -> dict:
    """``weights.dims`` of a configuration file as the fields of the
    program's ``NemotronHConfig``."""
    return dict(
        vocab_size=d["V"], d_model=d["D"], n_heads=d["H"], n_kv_heads=d["Hk"], head_dim=d["dh"],
        ssm_heads=d["mH"], ssm_head_dim=d["mP"], ssm_state=d["N"], ssm_groups=d["G"], conv_kernel=d["K"],
        d_expert=d["Fe"], d_shared=d["Fs"], router_width=d["E"], experts_held=d["held"], top_k=d["k"],
        routed_scale=d["scale"], pattern="".join(d["kinds"]), rms_eps=d["eps"],
    )


def install(model: dict) -> None:
    from pytorch_operator_tpu.models import nemotron_h

    d = W.dims(model)
    base = config_base(d)
    nemotron_h.bench_config = lambda **over: nemotron_h.make_config(base, over)
    nemotron_h.CONFIGS["bench"] = "bench_config"
    nemotron_h.init_layer = lambda cfg, kind, key, layer: W.make_layer(d, key, layer, kind, cfg.param_dtype)
    nemotron_h.init_outer = lambda cfg, key: W.make_outer(d, key, cfg.param_dtype)
