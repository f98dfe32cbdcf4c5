"""The StableHLO text the engine's programs lower to for a model, at a tiny
size on the CPU: what ``tests/test_wide_chunk.py`` holds the families that
take no wide chunk to (``tests/data_lowerings_pr46.json``, recorded from the
parent of PR 47 by running this file there:

    JAX_PLATFORMS=cpu python -m tests.lowerings > tests/data_lowerings_pr46.json

). The text carries no source location, so it moves only when a program
does, or when jax lowers the same program to other text: a failure of every
family at once after a jax upgrade means record the file again, and so does
a PR that changes a family's programs on purpose. The digests are evidence
for PR 47 (the families it did not mean to touch were not touched); what
lasts is beside them in ``tests/test_wide_chunk.py``: no wide program, and
the schedule ``range(0, padded, chunk)``.
"""

from __future__ import annotations

import hashlib
import json

FAMILIES = ("mimo-tiny", "k-exaone-tiny", "nemotron-h-tiny", "phi4-flash-tiny", "jamba-tiny")
PROGRAMS = ("prefill_chunk", "prefill_chunk_head", "decode_block")
SLOTS, CHUNK, BLOCK, LENGTH = 3, 8, 4, 128


def lowered(preset_name: str) -> dict:
    """``program name -> sha256 of its lowered text`` for the preset, through
    ``serving.engine.programs`` as the engine builds them, from shapes."""
    import jax
    import jax.numpy as jnp

    from pytorch_operator_tpu.models.serving import preset
    from pytorch_operator_tpu.ops.sampling import make_sampler
    from pytorch_operator_tpu.serving.engine import programs

    model = preset(preset_name, decode=True, max_decode_len=LENGTH).serving_model()
    progs = programs(model, slots=SLOTS, chunk=CHUNK, block=BLOCK, sample=make_sampler(0.0, 0, 1.0))
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    cache = jax.eval_shape(lambda: model.init_cache(SLOTS, CHUNK))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    key = jax.eval_shape(lambda: jax.random.key(0))
    drafts = model.drafter is not None
    chunk = (params, cache, model.counts, ints(), ints(1, CHUNK + drafts), ints(), ints())
    hidden = jax.eval_shape(progs.prefill_chunk, *chunk)[0]
    rows = (ints(SLOTS),) * (3 if drafts else 2)  # tok, pos and, where the model drafts, draft
    active = jax.ShapeDtypeStruct((SLOTS,), jnp.bool_)
    args = {
        "prefill_chunk": chunk,
        "prefill_chunk_head": (params, cache, hidden, *rows, ints(), ints(), key),
        "decode_block": (params, cache, model.counts, *rows, active, key, ints(), *((active,) if model.holds else ())),
    }
    return {name: hashlib.sha256(getattr(progs, name).lower(*args[name]).as_text().encode()).hexdigest()
            for name in PROGRAMS}


if __name__ == "__main__":
    import tests.jaxenv  # noqa: F401

    print(json.dumps({name: lowered(name) for name in FAMILIES}, indent=1))
