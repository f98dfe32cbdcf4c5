"""Hand-written TPU kernels (pallas) for the hot ops.

The reference ships no kernels — its numerical layer is whatever PyTorch
the user containers bring (SURVEY.md §2: "no C++/Rust/CUDA components in
the reference"). The rebuild's compute path is JAX/XLA; these pallas
kernels cover the few spots where fusing beyond XLA pays: attention's
O(S^2) score materialization (flash_attention.py), a decode step's walk of
each row's cache to that row's own depth (cache_attention.py) and its write
of one new position a row (cache_write.py).
"""

from .flash_attention import flash_attention  # noqa: F401
from .quantize import (  # noqa: F401
    QuantizedTensor,
    dequantize_tree,
    quantize_tree,
)
