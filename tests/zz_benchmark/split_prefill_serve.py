"""The serving entry with a boundary's budget at 16 prompt tokens (one chunk
of the tiny cells): every prompt longer than a chunk is prefilled over
several boundaries, the rows that decode running a dispatch between its
parts."""

import sys

from pytorch_operator_tpu.serving import engine

from benchmark import entry_serve

engine.ADMIT_TOKENS = 16

if __name__ == "__main__":
    sys.exit(entry_serve.main())
