"""The serving entry with the timed path broken underneath: every fifth
token is altered where the engine produces it."""

import sys

from pytorch_operator_tpu.serving.engine import ServingEngine

from benchmark import entry_serve

accept = ServingEngine._accept_token


def altered(self, st, slot, token):
    if len(st.tokens) % 5 == 4:
        token = (int(token) + 1) % self.cfg.vocab_size
    return accept(self, st, slot, token)


ServingEngine._accept_token = altered

if __name__ == "__main__":
    sys.exit(entry_serve.main())
