"""The serving entry with the decoder-hybrid-decoder's state reset left out:
a request's first chunk enters every Mamba-1 layer's scan with whatever its
slot's last occupant left there."""

import sys

from pytorch_operator_tpu.models import phi4_flash

from benchmark import entry_serve

mixer = phi4_flash.ssm_mixer


def never_fresh(*args, fresh=None, **kwargs):
    return mixer(*args, fresh=False, **kwargs)


phi4_flash.ssm_mixer = never_fresh

if __name__ == "__main__":
    sys.exit(entry_serve.main())
