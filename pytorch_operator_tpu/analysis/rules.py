"""The invariant rules.

Each rule mechanizes one contract that previously lived only in
ARCHITECTURE.md prose and review comments. Rules are HEURISTIC on
purpose — they pattern-match the idioms this codebase actually uses
(tmp+``os.replace``, ``O_EXCL`` markers, the shared ``backoff``
schedule, ``events.warning`` emission) and accept that a site the
heuristic cannot prove safe must either be rewritten in the idiom,
carry an inline ``# invariant: waived — reason`` tag, or be justified
in ``analysis/baseline.json``. A checker that guesses too generously
enforces nothing.

Per-module rules (subclass :class:`Rule`):

- ``atomic-state-write``   bare ``open(.., "w")`` / ``write_text`` /
                           ``write_bytes`` / creat-without-``O_EXCL``
                           in the state-bearing planes (controller/,
                           serving/, checkpoint/, obs/). Exempt: tmp-
                           named targets (the tmp+rename discipline),
                           append modes, ``O_EXCL``/``O_APPEND`` opens,
                           and functions that ``flock`` (locked
                           in-place rewrite).
- ``swallowed-exception``  ``except Exception``/``BaseException``/bare
                           handlers that neither re-raise nor call
                           anything that looks like an event/log
                           emission.
- ``retry-discipline``     ``time.sleep`` inside an exception handler
                           inside a loop — a retry loop not on the
                           shared ``backoff.py`` schedule.
- ``clock-discipline``     ``time.time()`` (directly or through a
                           local) in arithmetic/comparison against
                           TTL/deadline/timeout-shaped names — interval
                           math belongs on ``time.monotonic()``.

Project-wide rules (subclass :class:`ProjectRule`, see also
:mod:`.locks`):

- ``fenced-store-write``   job-state persistence reachable from the
                           sharded supervisor path that bypasses the
                           lease-fenced JobStore API, and any cross-
                           module call of JobStore persistence
                           internals.
- ``remediation-discipline`` actuator writes reachable from the
                           remediation engine that bypass the fenced
                           commit: store mutations outside the commit/
                           adopt pair, fleet actuations outside the
                           post-commit effectors, and cross-module
                           calls of engine-private decision internals.
- ``layer-direction``      a module of the compute layer (models/, ops/,
                           parallel/) importing from a layer that drives
                           it (workloads/, serving/, controller/,
                           client/), function-level imports included.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, List, Optional

from . import callgraph
from .findings import RawFinding

# ---------------------------------------------------------------------------
# infrastructure


class Rule:
    """Per-module rule: ``run(mod)`` yields RawFindings."""

    id: str = ""
    summary: str = ""

    def scope(self, relpath: str) -> bool:
        return True

    def run(self, mod) -> Iterator[RawFinding]:  # pragma: no cover
        raise NotImplementedError


class ProjectRule:
    """Whole-program rule: ``run(mods)`` yields (mod, RawFinding)."""

    id: str = ""
    summary: str = ""

    def run(self, mods) -> Iterator[tuple]:  # pragma: no cover
        raise NotImplementedError


def _src(mod, node: ast.AST) -> str:
    """Best-effort source text of a node (falls back to unparse)."""
    try:
        seg = ast.get_source_segment(mod.text, node)
        if seg is not None:
            return seg
    except Exception:  # invariant: waived — source-segment is cosmetic
        pass
    try:
        return ast.unparse(node)
    except Exception:  # invariant: waived — source-segment is cosmetic
        return ""


def _call_name(node: ast.Call) -> str:
    """Dotted-ish name of the called thing: ``open``, ``os.replace``,
    ``self.events.warning`` -> "self.events.warning"."""
    parts: List[str] = []
    f = node.func
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
    return ".".join(reversed(parts))


def iter_functions(tree: ast.Module):
    """Yield (qualname, function node) for every def, nested included."""

    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                yield qual, child
                yield from walk(child, f"{qual}.")
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, f"{prefix}{child.name}.")
            else:
                yield from walk(child, prefix)

    yield from walk(tree, "")


# ---------------------------------------------------------------------------
# atomic-state-write

_PLANES = ("controller/", "serving/", "checkpoint/", "obs/")
_WRITE_MODES = re.compile(r"^[wx]")  # "w", "wb", "w+", "x" (x is O_EXCL-like)


class AtomicStateWrite(Rule):
    id = "atomic-state-write"
    summary = (
        "file writes under the state/artifact root must be atomic: "
        "tmp + os.replace/rename, O_EXCL create, or os.link publication"
    )

    def scope(self, relpath: str) -> bool:
        return relpath.startswith(_PLANES)

    def run(self, mod) -> Iterator[RawFinding]:
        flocky_spans = [
            (fn.lineno, fn.end_lineno)
            for _, fn in iter_functions(mod.tree)
            if any(
                isinstance(n, ast.Call) and _call_name(n).endswith("flock")
                for n in ast.walk(fn)
            )
        ]

        def in_flock_fn(line: int) -> bool:
            return any(a <= line <= b for a, b in flocky_spans)

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            target: Optional[ast.AST] = None
            how = ""
            if name == "open" and node.args:
                mode = self._mode_of(node)
                if mode is None or not _WRITE_MODES.match(mode):
                    continue
                if mode.startswith("x"):
                    continue  # exclusive-create is the atomic idiom
                target, how = node.args[0], f'open(.., "{mode}")'
            elif name == "os.open" and len(node.args) >= 2:
                flags = _src(mod, node.args[1])
                if "O_WRONLY" not in flags and "O_RDWR" not in flags:
                    continue
                if "O_EXCL" in flags or "O_APPEND" in flags:
                    continue
                if in_flock_fn(node.lineno):
                    continue  # locked in-place rewrite (LeaderLease)
                target, how = node.args[0], "os.open without O_EXCL"
            elif isinstance(node.func, ast.Attribute) and node.func.attr in (
                "write_text",
                "write_bytes",
            ):
                target, how = node.func.value, node.func.attr
            else:
                continue
            tsrc = _src(mod, target).lower()
            if "tmp" in tsrc:
                continue  # tmp+rename discipline, first half
            yield RawFinding(
                node.lineno,
                f"bare {how} on {_src(mod, target)!r} — state files must "
                "land via tmp + os.replace, an O_EXCL create, or os.link "
                "(torn/partial content must never be readable at the "
                "real path)",
            )

    @staticmethod
    def _mode_of(node: ast.Call) -> Optional[str]:
        if len(node.args) >= 2 and isinstance(node.args[1], ast.Constant):
            v = node.args[1].value
            return v if isinstance(v, str) else None
        for kw in node.keywords:
            if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                v = kw.value.value
                return v if isinstance(v, str) else None
        if len(node.args) < 2:
            return "r"  # default mode: not a write
        return None  # dynamic mode: give it the benefit of the doubt


# ---------------------------------------------------------------------------
# swallowed-exception

_BROAD = {"Exception", "BaseException"}
_EMIT_HINTS = (
    "log",
    "warn",
    "error",
    "exception",
    "print",
    "emit",
    "event",
    "record",
    "report",
    "fail",
    "abort",
)


class SwallowedException(Rule):
    id = "swallowed-exception"
    summary = (
        "broad except handlers must emit an event/log, re-raise, or "
        "carry an explicit waiver"
    )

    @staticmethod
    def _is_broad(handler: ast.ExceptHandler) -> bool:
        t = handler.type
        if t is None:
            return True  # bare except:
        names = []
        for n in [t] if not isinstance(t, ast.Tuple) else t.elts:
            if isinstance(n, ast.Name):
                names.append(n.id)
            elif isinstance(n, ast.Attribute):
                names.append(n.attr)
        return any(n in _BROAD for n in names)

    @staticmethod
    def _emits(handler: ast.ExceptHandler) -> bool:
        for n in ast.walk(handler):
            if isinstance(n, (ast.Raise, ast.Assert)):
                return True
            if isinstance(n, ast.Call):
                name = _call_name(n).lower()
                if any(h in name for h in _EMIT_HINTS):
                    return True
        return False

    def run(self, mod) -> Iterator[RawFinding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Try):
                continue
            for handler in node.handlers:
                if not self._is_broad(handler):
                    continue
                if self._emits(handler):
                    continue
                yield RawFinding(
                    handler.lineno,
                    "broad exception handler swallows the failure "
                    "silently — emit an event/log line, re-raise, or tag "
                    "the site '# invariant: waived — <reason>'",
                    span=(handler.lineno, handler.end_lineno or handler.lineno),
                )


# ---------------------------------------------------------------------------
# retry-discipline


class RetryDiscipline(Rule):
    id = "retry-discipline"
    summary = (
        "retry loops must sleep on the shared backoff.py schedule, "
        "never a bare fixed-interval time.sleep"
    )

    def scope(self, relpath: str) -> bool:
        return relpath != "backoff.py"

    def run(self, mod) -> Iterator[RawFinding]:
        # A sleep is a RETRY sleep when it sits inside an except handler
        # that itself sits inside a loop: the canonical
        # ``while: try: ... except: sleep(FIXED)`` shape that
        # synchronizes a gang into a thundering herd.
        stack: List[ast.AST] = []

        def visit(node):
            if (
                isinstance(node, ast.Call)
                and _call_name(node) in ("time.sleep", "sleep")
                and any(isinstance(a, ast.ExceptHandler) for a in stack)
            ):
                # the handler must be inside a loop
                for i, anc in enumerate(stack):
                    if isinstance(anc, (ast.While, ast.For)) and any(
                        isinstance(b, ast.ExceptHandler)
                        for b in stack[i + 1 :]
                    ):
                        yield RawFinding(
                            node.lineno,
                            "bare time.sleep in a retry loop — use "
                            "backoff.Backoff/retry_call so the schedule "
                            "is jittered, capped, and fault-plan "
                            "deterministic",
                        )
                        break
            stack.append(node)
            for child in ast.iter_child_nodes(node):
                yield from visit(child)
            stack.pop()

        yield from visit(mod.tree)


# ---------------------------------------------------------------------------
# clock-discipline

_SUSPECT = re.compile(
    r"(ttl|deadline|timeout|expir|for_s|clear_s|holdoff|not_before"
    r"|_age|age_|lease|heartbeat|delay)",
    re.IGNORECASE,
)


def _contains_wallclock(node: ast.AST) -> bool:
    return any(
        isinstance(n, ast.Call) and _call_name(n) == "time.time"
        for n in ast.walk(node)
    )


class ClockDiscipline(Rule):
    id = "clock-discipline"
    summary = (
        "TTL/deadline/age math must use time.monotonic(); time.time() "
        "is for cross-process timestamps only"
    )

    def run(self, mod) -> Iterator[RawFinding]:
        for qual, fn in iter_functions(mod.tree):
            yield from self._scan_scope(mod, fn)
        yield from self._scan_scope(mod, mod.tree, module_scope=True)

    def _scan_scope(self, mod, scope, module_scope=False) -> Iterator[RawFinding]:
        # Names assigned (anywhere in this scope) from an expression
        # containing time.time() — one-level local dataflow.
        tainted: set = set()
        for node in self._own_nodes(scope, module_scope):
            if isinstance(node, ast.Assign) and _contains_wallclock(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        tainted.add(tgt.id)
        seen_lines: set = set()
        for node in self._own_nodes(scope, module_scope):
            sides: List[ast.AST] = []
            if isinstance(node, ast.Compare):
                sides = [node.left, *node.comparators]
            elif isinstance(node, ast.BinOp):
                sides = [node.left, node.right]
            elif isinstance(node, ast.Assign):
                # deadline = time.time() + x  (suspect TARGET name)
                if _contains_wallclock(node.value) and any(
                    isinstance(t, ast.Name) and _SUSPECT.search(t.id)
                    for t in node.targets
                ) and node.lineno not in seen_lines:
                    seen_lines.add(node.lineno)
                    yield self._finding(mod, node)
                continue
            else:
                continue
            def is_clocky(side: ast.AST) -> bool:
                if _contains_wallclock(side):
                    return True
                return isinstance(side, ast.Name) and side.id in tainted

            def is_suspect(side: ast.AST) -> bool:
                return bool(_SUSPECT.search(_src(mod, side)))

            if node.lineno in seen_lines:
                continue
            if any(is_clocky(s) for s in sides) and any(
                is_suspect(s) and not is_clocky(s) for s in sides
            ):
                seen_lines.add(node.lineno)
                yield self._finding(mod, node)

    @staticmethod
    def _own_nodes(scope, module_scope: bool):
        """Walk a scope WITHOUT descending into nested defs (each gets
        its own taint set); module scope skips all defs."""
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            n = stack.pop()
            if isinstance(
                n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ) or (module_scope and isinstance(n, ast.ClassDef)):
                continue
            yield n
            stack.extend(ast.iter_child_nodes(n))

    def _finding(self, mod, node) -> RawFinding:
        return RawFinding(
            node.lineno,
            f"wall-clock time.time() in duration/deadline math "
            f"({_src(mod, node)[:60]!r}) — a clock step (NTP) stretches "
            "or collapses the interval; use time.monotonic(), or waive "
            "if the value crosses a process boundary",
        )


# ---------------------------------------------------------------------------
# fenced-store-write (project rule)

_STORE_PRIVATE = {
    "_persist",
    "_persist_inner",
    "_atomic_write",
    "_load_all",
    "_rescan_inner",
    "_sweep_stale_tmp",
}
_RAW_PATH_HINTS = ("persist_dir", "_path_for")


class FencedStoreWrite(ProjectRule):
    id = "fenced-store-write"
    summary = (
        "job-state mutations on the supervisor path must go through "
        "the lease-fenced JobStore API, never raw persistence"
    )

    def run(self, mods) -> Iterator[tuple]:
        in_scope = [
            m
            for m in mods
            if m.relpath.startswith(("controller/", "client/"))
        ]
        by_rel = {m.relpath: m for m in in_scope}
        # 1) JobStore persistence internals are store.py-private.
        for mod in in_scope:
            if mod.relpath.endswith("store.py"):
                continue
            for node in ast.walk(mod.tree):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _STORE_PRIVATE
                ):
                    yield mod, RawFinding(
                        node.lineno,
                        f"call of JobStore-private {node.func.attr}() "
                        "outside store.py — job persistence must route "
                        "through the fenced API (update/add/delete/"
                        "mark_*)",
                    )
        # 2) Raw writes on the supervisor-reachable path.
        prog = callgraph.build_program(in_scope)
        seeds = [
            fi
            for ci in prog.classes.get("Supervisor", ())
            for name, fi in ci.methods.items()
            if name in ("sync_once", "sync_forever", "_shard_tick")
        ]
        if not seeds:
            return
        reach = callgraph.reachable_from(seeds, prog)
        for (module, qualname) in sorted(reach):
            mod = by_rel.get(module)
            if mod is None or module.endswith("store.py"):
                continue
            fi = prog.functions[(module, qualname)]
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                name = _call_name(node)
                target = None
                if name == "open" and node.args:
                    mode = AtomicStateWrite._mode_of(node)
                    if mode is None or not _WRITE_MODES.match(mode):
                        continue
                    target = node.args[0]
                elif isinstance(
                    node.func, ast.Attribute
                ) and node.func.attr in ("write_text", "write_bytes"):
                    target = node.func.value
                else:
                    continue
                tsrc = _src(mod, target)
                if any(h in tsrc for h in _RAW_PATH_HINTS):
                    yield mod, RawFinding(
                        node.lineno,
                        f"raw write to a job-store path ({tsrc!r}) on "
                        f"the supervisor path ({qualname}) — only the "
                        "lease-fenced JobStore API may persist job "
                        "state",
                    )


# ---------------------------------------------------------------------------
# remediation-discipline (project rule)

# The only methods allowed to mutate persisted job state from the
# remediation engine: _commit (the single fenced write an action rides)
# and _adopt (failover healing, which must re-derive — never re-decide).
_REMEDIATION_COMMITTERS = {"_commit", "_adopt"}
# The only methods allowed to touch the fleet: the post-commit effectors.
_REMEDIATION_EFFECTORS = {"_delete_excess_workers", "_deliver"}
# Fleet-mutating calls on the runner/reconciler. list_for_job & friends
# are read-only and deliberately absent.
_FLEET_MUTATORS = {
    "create",
    "delete",
    "delete_many",
    "inject_preempt",
    "inject_kill",
    "restart_world",
    "preempt_world",
}
# Engine-private decision/commit internals: calling these from outside
# the engine would let another module actuate without the audit trail.
_REMEDIATION_PRIVATE = {"_commit", "_append", "_act", "_apply", "_plan", "_adopt"}


class RemediationDiscipline(ProjectRule):
    id = "remediation-discipline"
    summary = (
        "remediation actions must commit through the single lease-"
        "fenced store write before any fleet side effect; actuator "
        "writes that bypass that path break exactly-once"
    )

    def run(self, mods) -> Iterator[tuple]:
        rem = None
        for mod in mods:
            if mod.relpath.endswith("controller/remediation.py"):
                rem = mod
                continue
            # (c) engine-private internals are remediation.py-private:
            # a cross-module call of _commit/_act/... on a remediation
            # receiver is an actuation without the engine's audit path.
            for node in ast.walk(mod.tree):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _REMEDIATION_PRIVATE
                    and "remediation" in _src(mod, node.func.value).lower()
                ):
                    yield mod, RawFinding(
                        node.lineno,
                        f"call of remediation-private {node.func.attr}() "
                        "outside controller/remediation.py — remediation "
                        "must act through evaluate() so every action "
                        "rides the fenced commit + audit trail",
                    )
        if rem is None:
            return
        spans = sorted(
            ((fn.lineno, fn.end_lineno or fn.lineno, qual) for qual, fn in iter_functions(rem.tree)),
            key=lambda t: t[1] - t[0],
        )

        def owner(line: int) -> str:
            # innermost enclosing def (spans sorted narrowest-first)
            for a, b, qual in spans:
                if a <= line <= b:
                    return qual.rsplit(".", 1)[-1]
            return ""

        for node in ast.walk(rem.tree):
            # (a) persisted-state mutations outside the commit/adopt pair
            # — a second store write would give supervisor failover a
            # window to replay the action (exactly-once broken).
            mutation = None
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                name = _call_name(node)
                attr = node.func.attr
                if attr == "touch" or (".store." in f".{name}" and attr in ("update", "add", "delete")):
                    mutation = f"{name}()"
                # (b) fleet actuations outside the post-commit effectors
                # — a pre-commit side effect is unfenced: a deposed
                # supervisor could actuate after losing its lease.
                elif attr in _FLEET_MUTATORS and (
                    "runner" in name or "reconciler" in name
                ):
                    fn = owner(node.lineno)
                    if fn in _REMEDIATION_EFFECTORS or fn.startswith("_effect_"):
                        continue
                    yield rem, RawFinding(
                        node.lineno,
                        f"fleet actuation {name}() outside a post-commit "
                        "effector (_effect_*/_delete_excess_workers/"
                        "_deliver) — side effects must run strictly "
                        "after the fenced commit",
                    )
                    continue
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign) else [node.target]
                )
                if any(
                    isinstance(t, ast.Attribute)
                    and t.attr == "remediation_generation"
                    for t in targets
                ):
                    mutation = "remediation_generation write"
            if mutation is None:
                continue
            fn = owner(node.lineno)
            if fn in _REMEDIATION_COMMITTERS:
                continue
            yield rem, RawFinding(
                node.lineno,
                f"persisted-state mutation ({mutation}) outside "
                "_commit/_adopt — every remediation must ride the one "
                "lease-fenced store write that bumps the generation",
            )


# ---------------------------------------------------------------------------
# layer-direction

_COMPUTE_LAYERS = ("models/", "ops/", "parallel/")
_DRIVING_LAYERS = frozenset({"workloads", "serving", "controller", "client"})


class LayerDirection(ProjectRule):
    id = "layer-direction"
    summary = (
        "the compute layer (models/, ops/, parallel/) is imported by "
        "workloads/, serving/, controller/ and client/ and imports "
        "none of them"
    )

    def run(self, mods) -> Iterator[tuple]:
        for mod in mods:
            if not mod.relpath.startswith(_COMPUTE_LAYERS):
                continue
            here = mod.relpath.split("/")[:-1]
            package = mod.path.parents[len(here)].name
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Import):
                    dotted = [a.name.split(".") for a in node.names]
                    targets = [d[1:] for d in dotted if d[0] == package]
                elif isinstance(node, ast.ImportFrom):
                    path = node.module.split(".") if node.module else []
                    if node.level:
                        base = here[: len(here) - (node.level - 1)] + path
                    elif path[:1] == [package]:
                        base = path[1:]
                    else:
                        continue
                    # ``from .. import workloads`` names the layer in
                    # the imported names, not in the module path.
                    targets = [base + [a.name] for a in node.names]
                else:
                    continue
                for target in targets:
                    if target and target[0] in _DRIVING_LAYERS:
                        yield mod, RawFinding(
                            node.lineno,
                            f"{mod.relpath} imports {'.'.join(target)}: "
                            "the compute layer must not know what drives "
                            "it — move the shared piece down, or pass it "
                            "in",
                        )


def module_rules() -> List[Rule]:
    return [
        AtomicStateWrite(),
        SwallowedException(),
        RetryDiscipline(),
        ClockDiscipline(),
    ]


def project_rules() -> List[ProjectRule]:
    from .locks import LockOrder

    return [
        FencedStoreWrite(),
        LockOrder(),
        RemediationDiscipline(),
        LayerDirection(),
    ]
