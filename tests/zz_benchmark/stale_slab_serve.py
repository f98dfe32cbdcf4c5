"""The serving entry with the decoder-hybrid-decoder's slab write left out of
the prefill: a prompt's keys and values never reach the one full-attention
slab, so the full layer and the seven-fold readers after it attend what the
slot's last occupant left in that row."""

import sys

from pytorch_operator_tpu.models import phi4_flash

from benchmark import entry_serve

write_kv = phi4_flash.write_kv


def rings_only(cache, k, v, positions, slot):
    if slot is not None and "pos" not in cache:
        return cache  # a chunk's write to the slab: dropped
    return write_kv(cache, k, v, positions, slot)


phi4_flash.write_kv = rings_only

if __name__ == "__main__":
    sys.exit(entry_serve.main())
