"""The serving entry of ``split_prefill_serve.py`` (every chunk of a prompt
queued at a boundary of its own) whose resumed parts start their row from
ZERO state: what a split prefill does if the row's scan state and
convolution tail do not live in its slot between the parts."""

import sys

from pytorch_operator_tpu.models import ssm
from pytorch_operator_tpu.serving import engine

from benchmark import entry_serve

engine.ADMIT_TOKENS = 16
mixer = ssm.mamba1_mixer


def every_part_fresh(*args, slot=None, fresh=None, **kwargs):
    return mixer(*args, slot=slot, fresh=fresh if slot is None else True, **kwargs)


ssm.mamba1_mixer = every_part_fresh

if __name__ == "__main__":
    sys.exit(entry_serve.main())
