"""Import this FIRST in any test module that uses jax in-process.

Runs the replica's own backend setup (runtime/backend.py) pinned to the
CPU. Kept out of conftest so pure control-plane test runs never pay the
jax import.
"""

from pytorch_operator_tpu.runtime.backend import setup_backend

setup_backend("cpu")
