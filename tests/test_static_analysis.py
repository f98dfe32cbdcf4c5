"""The invariant checker (``tpujob verify-invariants``, analysis/).

Tier-1 lanes in here:

- firing + clean fixture per rule (all six), driven through the real
  engine against tmp-dir fixture packages;
- waiver tag syntax (accepted forms, reason required, placement);
- baseline round-trip: add -> suppress -> stale-entry warning, and
  load-time rejection of unjustified entries;
- the whole-repo gate: ZERO unsuppressed findings against the
  committed ``analysis/baseline.json``, no stale entries, every entry
  justified;
- CLI surface (``--json``, exit codes);
- regression tests for the clock-discipline bugs this analyzer
  surfaced (supervisor.wait, standby crash-loop holdoff, spool
  wait_response survive an NTP step);
- bench_smoke pin: the analyzer is read-only — zero writes, zero
  state-dir I/O.
"""

import json
import textwrap
import time

import pytest

from pytorch_operator_tpu import analysis
from pytorch_operator_tpu.analysis import findings as findings_mod
from pytorch_operator_tpu.analysis.baseline import Baseline, BaselineError
from pytorch_operator_tpu.client.cli import main
from pathlib import Path

PKG_ROOT = Path(analysis.__file__).resolve().parent.parent
REPO_BASELINE = PKG_ROOT / "analysis" / "baseline.json"


def write_fixture(root: Path, files: dict) -> Path:
    for rel, body in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    return root


def rule_findings(report, rule):
    return [f for f in report.findings if f.rule == rule and not f.waived]


def analyze_fixture(tmp_path, files: dict):
    return analysis.analyze(write_fixture(tmp_path / "fix", files))


# ---------------------------------------------------------------------------
# rule 1: atomic-state-write


class TestAtomicStateWrite:
    def test_bare_writes_in_state_planes_fire(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/thing.py": """
                def save(path, text):
                    with open(path, "w") as f:
                        f.write(text)

                def save2(path, text):
                    path.write_text(text)
            """,
        })
        got = rule_findings(rep, "atomic-state-write")
        assert len(got) == 2
        assert {f.line for f in got} == {3, 7}
        assert {f.qualname for f in got} == {"save", "save2"}

    def test_atomic_idioms_and_out_of_plane_are_clean(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/good.py": """
                import os

                def save(path, text):
                    tmp = path.with_suffix(".tmp")
                    tmp.write_text(text)
                    os.replace(tmp, path)

                def once(path, text):
                    with open(path, "x") as f:
                        f.write(text)

                def excl(path, data):
                    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_EXCL)
                    os.write(fd, data)

                def append(path, line):
                    with open(path, "a") as f:
                        f.write(line)

                def read(path):
                    with open(path) as f:
                        return f.read()
            """,
            # same bare write OUTSIDE the state planes: out of scope
            "api/helper.py": """
                def save(path, text):
                    path.write_text(text)
            """,
        })
        assert rule_findings(rep, "atomic-state-write") == []


# ---------------------------------------------------------------------------
# rule 2: fenced-store-write


class TestFencedStoreWrite:
    def test_private_persistence_call_outside_store_fires(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/helper.py": """
                def flush(store):
                    store._persist()
            """,
        })
        got = rule_findings(rep, "fenced-store-write")
        assert len(got) == 1
        assert "_persist" in got[0].message

    def test_raw_write_on_supervisor_path_fires(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/supervisor.py": """
                import json

                class Supervisor:
                    def __init__(self, persist_dir):
                        self.persist_dir = persist_dir

                    def sync_once(self):
                        self._dump({"phase": "Running"})

                    def _dump(self, status):
                        (self.persist_dir / "job.json").write_text(
                            json.dumps(status)
                        )
            """,
        })
        # NB: sees both the reachability finding and (separately) the
        # atomic-state-write one; assert the fenced rule specifically.
        got = rule_findings(rep, "fenced-store-write")
        assert len(got) == 1
        assert "persist_dir" in got[0].message

    def test_fenced_api_is_clean(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/supervisor.py": """
                class Supervisor:
                    def __init__(self, store):
                        self.store = store

                    def sync_once(self):
                        self.store.update("k", lambda j: j)
            """,
        })
        assert rule_findings(rep, "fenced-store-write") == []


# ---------------------------------------------------------------------------
# rule 3: lock-order


class TestLockOrder:
    def test_opposite_nesting_orders_fire_as_cycle(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/locks.py": """
                import threading

                class M:
                    def __init__(self):
                        self._a_lock = threading.Lock()
                        self._b_lock = threading.Lock()

                    def one(self):
                        with self._a_lock:
                            with self._b_lock:
                                return 1

                    def two(self):
                        with self._b_lock:
                            with self._a_lock:
                                return 2
            """,
        })
        got = rule_findings(rep, "lock-order")
        assert any("cyclic" in f.message for f in got)

    def test_blocking_call_under_lock_fires(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/spawny.py": """
                import subprocess
                import threading

                class R:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def spawn(self, argv):
                        with self._lock:
                            return subprocess.Popen(argv)
            """,
        })
        got = rule_findings(rep, "lock-order")
        assert len(got) == 1
        assert "Popen" in got[0].message and "R._lock" in got[0].message

    def test_consistent_order_and_pure_compute_are_clean(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/locks_ok.py": """
                import threading

                class M:
                    def __init__(self):
                        self._a_lock = threading.Lock()
                        self._b_lock = threading.Lock()
                        self.n = 0

                    def one(self):
                        with self._a_lock:
                            with self._b_lock:
                                self.n += 1

                    def two(self):
                        with self._a_lock:
                            with self._b_lock:
                                self.n -= 1
            """,
        })
        assert rule_findings(rep, "lock-order") == []


# ---------------------------------------------------------------------------
# rule 4: swallowed-exception


class TestSwallowedException:
    def test_silent_broad_handler_fires(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/oops.py": """
                def f():
                    try:
                        risky()
                    except Exception:
                        pass
            """,
        })
        got = rule_findings(rep, "swallowed-exception")
        assert len(got) == 1
        assert got[0].qualname == "f"

    def test_emitting_reraising_narrow_and_waived_are_clean(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/fine.py": """
                def logs(events):
                    try:
                        risky()
                    except Exception as e:
                        events.warning("k", "Oops", str(e))

                def reraises():
                    try:
                        risky()
                    except Exception:
                        raise

                def narrow():
                    try:
                        risky()
                    except OSError:
                        pass

                def waived():
                    try:
                        risky()
                    except Exception:
                        # invariant: waived — best-effort teardown
                        pass
            """,
        })
        assert rule_findings(rep, "swallowed-exception") == []
        assert any(
            f.rule == "swallowed-exception" and f.waived
            for f in rep.findings
        )


# ---------------------------------------------------------------------------
# rule 5: retry-discipline


class TestRetryDiscipline:
    def test_fixed_sleep_retry_loop_fires(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/poller.py": """
                import time

                def fetch(read):
                    while True:
                        try:
                            return read()
                        except OSError:
                            time.sleep(1.0)
            """,
        })
        got = rule_findings(rep, "retry-discipline")
        assert len(got) == 1
        assert "backoff" in got[0].message

    def test_backoff_schedule_and_pacing_sleeps_are_clean(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/paced.py": """
                import time
                from pytorch_operator_tpu.backoff import Backoff, retry_call

                def fetch(read):
                    return retry_call(
                        read, backoff=Backoff(base_s=0.05), attempts=5
                    )

                def poll(done):
                    while not done():
                        time.sleep(0.05)  # pacing, not a retry
            """,
        })
        assert rule_findings(rep, "retry-discipline") == []


# ---------------------------------------------------------------------------
# rule 6: clock-discipline


class TestClockDiscipline:
    def test_wall_clock_deadline_math_fires(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/clocky.py": """
                import time

                def wait(ttl):
                    deadline = time.time() + ttl
                    while time.time() < deadline:
                        pass

                def expired(lease_expires):
                    return time.time() >= lease_expires
            """,
        })
        got = rule_findings(rep, "clock-discipline")
        # the suspect-named assignment and the direct compare; the
        # tainted `time.time() < deadline` compare is folded into the
        # assignment finding (both operands are wall clock there).
        assert len(got) == 2
        assert {f.qualname for f in got} == {"wait", "expired"}

    def test_monotonic_and_timestamp_records_are_clean(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/clocks_ok.py": """
                import time

                def wait(ttl):
                    deadline = time.monotonic() + ttl
                    while time.monotonic() < deadline:
                        pass

                def stamp(record):
                    # wall clock AS a timestamp (no interval math): fine
                    record["created_at"] = time.time()
                    return record
            """,
        })
        assert rule_findings(rep, "clock-discipline") == []


# ---------------------------------------------------------------------------
# rule 7: remediation-discipline


class TestRemediationDiscipline:
    def test_mutation_and_actuation_outside_commit_path_fire(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/remediation.py": """
                class RemediationEngine:
                    def __init__(self, store, runner):
                        self.store = store
                        self.runner = runner

                    def _plan(self, key, job):
                        # actuation BEFORE the commit: unfenced
                        self.runner.inject_preempt(key)
                        # store write outside _commit/_adopt: a second
                        # fenced write = a replay window
                        job.status.remediation_generation += 1
                        self.store.update(job)

                    def _commit(self, key, job):
                        job.status.remediation_generation += 1
                        self.store.update(job)

                    def _effect_preempt(self, name):
                        self.runner.inject_preempt(name)
            """,
            "controller/other.py": """
                def poke(sup, key, job):
                    # engine-private internals are remediation.py-private
                    sup.remediation._commit(key, job)
            """,
        })
        got = rule_findings(rep, "remediation-discipline")
        msgs = " | ".join(f.message for f in got)
        assert len(got) == 4, msgs
        assert "inject_preempt" in msgs
        assert "remediation_generation" in msgs
        assert "_commit()" in msgs

    def test_commit_adopt_and_effectors_are_clean(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "controller/remediation.py": """
                class RemediationEngine:
                    def __init__(self, store, runner):
                        self.store = store
                        self.runner = runner

                    def _commit(self, key, job):
                        job.status.remediation_generation += 1
                        self.store.update(job)

                    def _adopt(self, key, job):
                        job.status.remediation_generation += 0
                        self.store.update(job)

                    def _effect_preempt(self, name):
                        self.runner.inject_preempt(name)

                    def _delete_excess_workers(self, key, job):
                        self.runner.delete(key)
            """,
        })
        assert rule_findings(rep, "remediation-discipline") == []


# ---------------------------------------------------------------------------
# rule 8: layer-direction


class TestLayerDirection:
    def test_upward_imports_from_the_compute_layer_fire(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            "models/serving.py": """
                import fix.controller.store

                def families():
                    # function-level imports count
                    from ..workloads.llama_train import CONFIGS
                    return CONFIGS
            """,
            "ops/attend.py": """
                from .. import serving
            """,
            "parallel/nested/mesh.py": """
                from fix.client import cli
            """,
        })
        got = rule_findings(rep, "layer-direction")
        msgs = " | ".join(f.message for f in got)
        assert len(got) == 4, msgs
        for target in (
            "controller.store", "workloads.llama_train.CONFIGS",
            "imports serving", "client.cli",
        ):
            assert target in msgs

    def test_downward_and_sideways_imports_are_clean(self, tmp_path):
        rep = analyze_fixture(tmp_path, {
            # the compute layer among itself, and a module of it that
            # merely shares a driving layer's name
            "models/serving.py": """
                import jax
                import workloads

                from . import llama
                from ..ops import attend
                from ..parallel.moe import moe_swiglu_held
                from fix.runtime import backend
            """,
            # what drives the compute layer imports it freely
            "workloads/llama_train.py": """
                from ..models.llama import CONFIGS
                from ..serving import engine
            """,
            "serving/engine.py": """
                from ..models import serving
            """,
        })
        assert rule_findings(rep, "layer-direction") == []

    def test_the_repo_compute_layer_imports_nothing_that_drives_it(
        self, repo_report
    ):
        got = [f for f in repo_report.findings if f.rule == "layer-direction"]
        assert got == [], repo_report.render_text()


# ---------------------------------------------------------------------------
# waiver syntax


class TestWaiverSyntax:
    @pytest.mark.parametrize("dash", ["—", "–", "--", "-"])
    def test_dash_variants_accepted(self, dash):
        got = findings_mod.scan_waivers(
            [f"x = 1  # invariant: waived {dash} reason here"]
        )
        assert got == {1: "reason here"}

    def test_reason_is_required(self):
        assert findings_mod.scan_waivers(["x  # invariant: waived —"]) == {}
        assert findings_mod.scan_waivers(["x  # invariant: waived"]) == {}

    def test_placement_line_above_and_span(self):
        waivers = {5: "why"}
        assert findings_mod.find_waiver(waivers, 5) == "why"
        assert findings_mod.find_waiver(waivers, 6) == "why"  # line above
        assert findings_mod.find_waiver(waivers, 9) is None
        assert findings_mod.find_waiver(waivers, 2, span=(2, 7)) == "why"


# ---------------------------------------------------------------------------
# baseline round-trip


FIRING = {
    "controller/bad.py": """
        def f():
            try:
                risky()
            except Exception:
                pass
    """,
}


class TestBaselineRoundTrip:
    def test_add_suppress_then_stale(self, tmp_path):
        root = write_fixture(tmp_path / "fix", FIRING)
        bl_path = tmp_path / "baseline.json"

        # 1) finding is unsuppressed with no baseline
        rep = analysis.run_verify(root, bl_path)
        assert len(rep.unsuppressed) == 1
        assert rep.exit_code() == 1

        # 2) accept it -> suppressed, exit 0
        Baseline.from_findings(
            rep.unsuppressed, justification="known; tracked in #1"
        ).save(bl_path)
        rep2 = analysis.run_verify(root, bl_path)
        assert rep2.unsuppressed == []
        assert rep2.exit_code() == 0
        assert len(rep2.result.suppressed) == 1
        assert rep2.stale_entries == []

        # 3) fix the code -> the entry goes stale (and is reported)
        (root / "controller/bad.py").write_text(
            "def f():\n    risky()\n"
        )
        rep3 = analysis.run_verify(root, bl_path)
        assert rep3.unsuppressed == []
        assert len(rep3.stale_entries) == 1
        assert "STALE" in rep3.render_text()

    def test_unjustified_entries_are_rejected_at_load(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps({
            "version": 1,
            "entries": [{"fingerprint": "abc123", "justification": "  "}],
        }))
        with pytest.raises(BaselineError, match="justification"):
            Baseline.load(p)

    def test_fingerprint_survives_unrelated_edits(self, tmp_path):
        root = write_fixture(tmp_path / "fix", FIRING)
        fp1 = analysis.analyze(root).findings[0].fingerprint
        # prepend an unrelated function: the site moves down 4 lines
        src = (root / "controller/bad.py").read_text()
        (root / "controller/bad.py").write_text(
            "def unrelated():\n    return 1\n\n" + src
        )
        fp2 = analysis.analyze(root).findings[0].fingerprint
        assert fp1 == fp2

    def test_identical_sites_get_distinct_fingerprints(self, tmp_path):
        root = write_fixture(tmp_path / "fix", {
            "controller/twins.py": """
                def f(p, t):
                    p.write_text(t)
                    p.write_text(t)
            """,
        })
        rep = analysis.analyze(root)
        fps = [f.fingerprint for f in rep.findings]
        assert len(fps) == 2 and len(set(fps)) == 2


# ---------------------------------------------------------------------------
# the whole-repo gate (tier-1)


@pytest.fixture(scope="module")
def repo_report():
    """ONE whole-repo verify pass shared by the gate assertions (the
    pass is ~3s; re-running it per assertion would blow the <10s lane
    budget)."""
    return analysis.run_verify(PKG_ROOT, REPO_BASELINE)


class TestRepoGate:
    def test_repo_has_zero_unsuppressed_findings(self, repo_report):
        assert repo_report.modules_scanned > 50
        assert repo_report.unsuppressed == [], repo_report.render_text()

    def test_no_stale_baseline_entries(self, repo_report):
        assert repo_report.stale_entries == [], repo_report.render_text()

    def test_every_baseline_entry_is_justified(self):
        bl = Baseline.load(REPO_BASELINE)  # load() enforces; belt+braces
        assert bl.entries, "repo baseline unexpectedly empty"
        for e in bl.entries:
            assert len(e.justification) > 20, e.location

    def test_every_inline_waiver_carries_a_reason(self, repo_report):
        waived = [f for f in repo_report.findings if f.waived]
        assert waived, "expected inline-waived sites in the repo"
        for f in waived:
            assert f.waive_reason.strip(), f.location()


# ---------------------------------------------------------------------------
# CLI surface


class TestCli:
    def test_json_report_and_exit_codes(self, tmp_path, capsys):
        root = write_fixture(tmp_path / "fix", FIRING)
        rc = main([
            "verify-invariants", "--json", "--root", str(root),
            "--baseline", str(tmp_path / "baseline.json"),
        ])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert len(out["unsuppressed"]) == 1
        f = out["unsuppressed"][0]
        assert f["rule"] == "swallowed-exception"
        assert f["path"] == "controller/bad.py"
        assert f["fingerprint"]

    def test_default_baseline_path_resolves_under_root(self, tmp_path, capsys):
        # no --baseline: <root>/analysis/baseline.json (absent here, so
        # the finding stays unsuppressed — proving the default resolved
        # under --root rather than crashing or reading the repo's).
        root = write_fixture(tmp_path / "fix", FIRING)
        rc = main(["verify-invariants", "--root", str(root)])
        capsys.readouterr()
        assert rc == 1

    def test_write_baseline_then_clean(self, tmp_path, capsys):
        root = write_fixture(tmp_path / "fix", FIRING)
        bl = tmp_path / "baseline.json"
        rc = main([
            "verify-invariants", "--root", str(root),
            "--baseline", str(bl), "--write-baseline",
        ])
        assert rc == 0 and bl.exists()
        capsys.readouterr()
        rc = main([
            "verify-invariants", "--root", str(root), "--baseline", str(bl),
        ])
        capsys.readouterr()
        assert rc == 0


# ---------------------------------------------------------------------------
# regression: the clock-discipline bugs the analyzer surfaced
# (wall-clock deadlines stretched/collapsed by an NTP step)


def _jump_wall_clock(monkeypatch, offset=1e9):
    real = time.time
    monkeypatch.setattr(time, "time", lambda: real() + offset)


class TestClockRegressions:
    def test_supervisor_wait_timeout_survives_clock_jump(
        self, tmp_path, monkeypatch
    ):
        """An NTP jump of +1e9s mid-wait must NOT collapse the timeout:
        the deadline is monotonic now. (Before the fix this raised
        TimeoutError on the first pass.)"""
        from pytorch_operator_tpu.api.types import ProcessTemplate, ReplicaType
        from pytorch_operator_tpu.controller import Supervisor
        from tests.testutil import new_job

        sup = Supervisor(state_dir=tmp_path / "state", poll_interval=0.02)
        job = new_job(name="clock-jump", workers=0)
        job.spec.replica_specs[ReplicaType.MASTER].template = ProcessTemplate(
            command=["sh", "-c", "sleep 30"]
        )
        key = sup.submit(job)
        try:
            _jump_wall_clock(monkeypatch)
            t0 = time.monotonic()
            with pytest.raises(TimeoutError):
                sup.wait(key, timeout=0.3)
            # wall-clock deadline would have fired instantly
            assert time.monotonic() - t0 >= 0.3
        finally:
            monkeypatch.undo()
            sup.delete_job(key)
            sup.reconciler.sync(key)
            sup.shutdown()

    def test_standby_holdoff_survives_clock_jump(self, tmp_path, monkeypatch):
        """The crash-loop holdoff must hold through a forward wall-clock
        jump (before the fix, the jump collapsed it into a respawn
        storm)."""
        from pytorch_operator_tpu.controller.standby import StandbyPool

        pool = StandbyPool(tmp_path / "state", size=1)
        pool._fail_streak = 3
        pool._not_before = time.monotonic() + 60.0
        spawned = []
        monkeypatch.setattr(
            pool, "_spawn_one", lambda: spawned.append(1) or True
        )
        _jump_wall_clock(monkeypatch)
        pool.replenish()
        assert spawned == []

    def test_spool_wait_response_survives_clock_jump(
        self, tmp_path, monkeypatch
    ):
        """wait_response's poll budget is monotonic: a +1e9s wall jump
        neither times it out early nor (backward jump) pins it open."""
        from pytorch_operator_tpu.serving.spool import Spool

        spool = Spool(tmp_path / "spool")
        _jump_wall_clock(monkeypatch)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            spool.wait_response("nope", timeout=0.25)
        assert time.monotonic() - t0 >= 0.25


# ---------------------------------------------------------------------------
# bench_smoke pin: the analyzer is read-only


@pytest.mark.bench_smoke
class TestAnalyzerIsReadOnly:
    def test_zero_writes_zero_state_dir_io(self, tmp_path, monkeypatch):
        """The verify pass must be pure read: no file writes anywhere,
        no state-dir traffic (it analyzes SOURCES, it does not open
        supervisor state). Pinned two ways: the engine's own I/O
        counters, and a filesystem snapshot of a decoy state dir."""
        state = tmp_path / "state"
        state.mkdir()
        monkeypatch.setenv("TPUJOB_STATE_DIR", str(state))
        before = set(PKG_ROOT.rglob("*"))
        rep = analysis.run_verify(PKG_ROOT, REPO_BASELINE)
        assert rep.io.files_written == 0
        assert rep.io.state_dir_touches == 0
        assert rep.io.files_read >= rep.modules_scanned
        assert list(state.iterdir()) == []
        assert set(PKG_ROOT.rglob("*")) == before
