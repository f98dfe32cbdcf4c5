"""Where the ``jamba`` family reaches into the program, and nowhere else:

- ``models.jamba.CONFIGS`` gains the preset ``bench`` (``models.serving``
  reads that table when the server's ``--config bench`` is resolved), made by
  ``models.jamba.make_config`` from the configuration file's sizes;
- ``models.jamba.init_layer`` and ``models.jamba.init_outer``, the two
  functions ``init_params`` makes the tree with, a layer at a time and in the
  serving dtype, are replaced by ones that return the benchmark's seeded
  leaves (same paths, shapes and dtypes).

The one other point is the entry module's and every family's:
``ServingEngine.submit`` (``entry_serve.py``). A program that has no
``models/jamba.py`` (the parent of the PR that brought this family) fails
here, at the import, before it touches the chip.
"""

from __future__ import annotations

from . import weights as W


def config_base(model: dict, d: dict) -> dict:
    """``weights.dims`` of a configuration file as the fields of the
    program's ``JambaConfig`` (its head size follows from the width, as
    ``shape.dims`` checked)."""
    return dict(
        vocab_size=d["V"], d_model=d["D"], n_layers=d["L"], n_heads=d["H"], n_kv_heads=d["Hk"], d_ff=d["F"],
        attn_offset=int(model["attn_layer_offset"]), attn_period=int(model["attn_layer_period"]),
        d_state=d["N"], d_conv=d["K"], expand=d["di"] // d["D"], dt_rank=d["R"], rms_eps=d["eps"],
    )


def install(model: dict) -> None:
    from pytorch_operator_tpu.models import jamba

    d = W.dims(model)
    base = config_base(model, d)
    if jamba.make_config(base, {}).layers != d["kinds"]:
        raise ValueError("the program orders the layers' kinds otherwise than the configuration file's family")
    jamba.bench_config = lambda **over: jamba.make_config(base, over)
    jamba.CONFIGS["bench"] = "bench_config"
    jamba.init_layer = lambda cfg, kind, key, layer: W.make_layer(d, key, layer, kind, cfg.param_dtype)
    jamba.init_outer = lambda cfg, key: W.make_outer(d, key, cfg.param_dtype)
