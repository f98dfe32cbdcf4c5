"""The serving entry with the hybrid family's state reset left out: a
request's first chunk enters the scan with whatever its slot's last occupant
left there."""

import sys

from pytorch_operator_tpu.models import nemotron_h

from benchmark import entry_serve

mixer = nemotron_h.ssm_mixer


def never_fresh(*args, fresh=None, **kwargs):
    return mixer(*args, fresh=False, **kwargs)


nemotron_h.ssm_mixer = never_fresh

if __name__ == "__main__":
    sys.exit(entry_serve.main())
