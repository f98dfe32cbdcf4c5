"""The serving entry with a verifying step that leaves a given-up position's
keys visible: where a window layer's ring already records the position a
step's first token stands at (the step before wrote it under a draft that was
then rejected, or a prompt's pad did), the write keeps the entry as it is and
does not put the true token's keys and values there, so every later query
inside the window attends the rejected draft's."""

import sys

import jax.numpy as jnp

from pytorch_operator_tpu.models import mimo_v2

from benchmark import entry_serve

write = mimo_v2.write_positions


def keep_what_claims_the_position(cache, k, v, positions):
    if "pos" not in cache or positions.shape[1] != 2:
        return write(cache, k, v, positions)
    at = positions[:, :1] % cache["k"].shape[2]
    claimed = (jnp.take_along_axis(cache["pos"], at, axis=1) == positions[:, :1])[:, None, :, None]
    old = lambda leaf: jnp.take_along_axis(leaf, at[:, None, :, None], axis=2)  # noqa: E731
    k = k.at[:, :, :1].set(jnp.where(claimed, old(cache["k"]), k[:, :, :1]))
    v = v.at[:, :, :1].set(jnp.where(claimed, old(cache["v"]), v[:, :, :1]))
    return write(cache, k, v, positions)


mimo_v2.write_positions = keep_what_claims_the_position

if __name__ == "__main__":
    sys.exit(entry_serve.main())
