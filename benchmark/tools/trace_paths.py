#!/usr/bin/env python3
"""Look at one trace by hand: the device's time by the scope path of its
operations (the ``tf_op`` stat of their metadata), the operations that carry
no path, and the program's spans on the host plane. Not part of a run.

    JAX_PLATFORMS=cpu python benchmark/tools/trace_paths.py TRACE_DIR [DEPTH]
"""

from __future__ import annotations

import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import span_reduce as S  # noqa: E402


def main(trace_dir: str, depth: int = 6) -> int:
    path = S.find_xplane(trace_dir)
    if not path:
        print(f"no trace under {trace_dir}")
        return 1
    paths = S.op_paths(path)
    devices, spans = S.read_trace(path)
    by_path, unnamed = Counter(), Counter()
    for name, start, end in (op for ops in devices for op in ops):
        if S.short_name(name).startswith(("while", "conditional")):
            continue
        if name in paths:
            by_path["/".join(paths[name].split(":")[0].split("/")[:depth])] += (end - start) / 1e9
        else:
            unnamed[S.short_name(name)] += (end - start) / 1e9
    print("device s by path (depth", depth, "):")
    for k, v in by_path.most_common(60):
        print(f"  {v:9.6f}  {k}")
    print("device s without a path:")
    for k, v in unnamed.most_common(15):
        print(f"  {v:9.6f}  {k}")
    print("program spans on the host plane: count")
    for k, v in sorted(Counter(name for name, _, _ in spans).items()):
        print(f"  {v:6d}  {k}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 6))
