"""Where the ``phi4_flash`` family reaches into the program, and nowhere else:

- ``models.phi4_flash.CONFIGS`` gains the preset ``bench`` (``models.serving``
  reads that table when the server's ``--config bench`` is resolved), made by
  ``models.phi4_flash.make_config`` from the configuration file's sizes;
- ``models.phi4_flash.init_layer`` and ``models.phi4_flash.init_outer``, the
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
    program's ``Phi4FlashConfig`` (its head size and ``dt_rank`` follow from
    the width, as ``shape.dims`` checked)."""
    return dict(
        vocab_size=d["V"], d_model=d["D"], n_layers=d["L"], n_heads=d["H"], n_kv_heads=d["Hk"], d_ff=d["F"],
        window=d["window"], d_state=d["N"], d_conv=d["K"], expand=d["di"] // d["D"], ln_eps=d["eps"],
    )


def install(model: dict) -> None:
    from pytorch_operator_tpu.models import phi4_flash

    d = W.dims(model)
    base = config_base(d)
    if phi4_flash.make_config(base, {}).layers != d["kinds"]:
        raise ValueError("the program orders the layers' kinds otherwise than the configuration file's family")
    phi4_flash.bench_config = lambda **over: phi4_flash.make_config(base, over)
    phi4_flash.CONFIGS["bench"] = "bench_config"
    phi4_flash.init_layer = lambda cfg, kind, key, layer: W.make_layer(d, key, layer, kind, cfg.param_dtype)
    phi4_flash.init_outer = lambda cfg, key: W.make_outer(d, key, cfg.param_dtype)
