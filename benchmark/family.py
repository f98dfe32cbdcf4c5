"""A configuration's family: the files that know the shape of its block.

A configuration file's ``bench.family`` names a directory
``families/<family>/`` with four files, found by that name the way
``layer_metrics/<name>.py`` and ``limits/<cell>.json`` are (by path: no
registry, and no line of the harness says what a family is called):

- ``reference.py`` — the plain float32 ``jax.numpy`` block, importing nothing
  of the program, with the two drivers ``reference_run.py`` calls (their
  contract is stated there);
- ``weights.py`` — ``dims(model)``, and seeded leaves one layer at a time
  (``make_layer``, ``make_outer``) and stacked (``make_params(d, key, dtype)``);
- ``install.py`` — ``install(model)``: the one place that reaches into the
  program; states the model as the workloads' ``bench`` preset and makes the
  model's ``init`` return the seeded tree. Its docstring lists every point of
  the program it touches;
- ``flops.py`` — the operations (and, for roofline readers, bytes) a token
  or a kernel call requires: ``train_flops_per_token(model, seq_len)``.

A family's files import one another relatively (``from . import weights``)
and no other family's; what no block's shape decides (the precisions, RMSNorm,
the plain Adafactor, seeded draws by path) they may take from
``families/_common.py``.
"""

from __future__ import annotations

import importlib
import sys
import types
import zlib
from pathlib import Path

BENCH = Path(__file__).resolve().parent
PARTS = ("reference", "weights", "install", "flops")


def load(family: str, part: str, bench: Path = BENCH):
    """``<bench>/families/<family>/<part>.py`` as a module."""
    folder = (Path(bench) / "families" / family).resolve()
    if not (folder / f"{part}.py").is_file():
        raise FileNotFoundError(f"family {family!r}: no file {folder / (part + '.py')}")
    # The directory as a package of its own, so that its files find one
    # another; named from its path, so a copy of the benchmark elsewhere
    # (the tests make one) is another package.
    package = f"benchmark_family_{zlib.crc32(str(folder).encode()):08x}"
    if package not in sys.modules:
        module = types.ModuleType(package)
        module.__path__ = [str(folder)]
        sys.modules[package] = module
    return importlib.import_module(f"{package}.{part}")


def of(config: dict, part: str, bench: Path = BENCH):
    """The ``part`` of the family a configuration file names."""
    return load(config["bench"]["family"], part, bench)
