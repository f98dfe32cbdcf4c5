"""The serving entry with a verifying step that accepts EVERY draft: the main
stack's logits after a row's first position are overridden to choose the
draft, so each step delivers the draft and the choice after it whether or not
the draft was the model's own choice."""

import sys

import jax

from pytorch_operator_tpu.models import mimo_v2

from benchmark import entry_serve

verify = mimo_v2._verify


def accept_all(cfg, params, cache, tokens, positions):
    logits, hidden, cache, counts = verify(cfg, params, cache, tokens, positions)
    return logits.at[:, 0].set(1e9 * jax.nn.one_hot(tokens[:, 1], logits.shape[-1])), hidden, cache, counts


mimo_v2._verify = accept_all

if __name__ == "__main__":
    sys.exit(entry_serve.main())
