#!/usr/bin/env python3
"""What a PR that adds or changes a cell measures on the chip, in one place.
Not part of a run: ``benchmark/run.py`` never imports this file.

    python benchmark/tools/measure.py sets   WORKLOAD SECONDS SEED [SEED ...]
    python benchmark/tools/measure.py limits WORKLOAD SECONDS CONTROLS SEED [SEED ...]
    python benchmark/tools/measure.py sweep  WORKLOAD SECONDS SEED[,SEED...] RATE [RATE ...]

``sets``: the contract's two sets of runs of the committed command, the
same seeds in both, each run a new process; for each metric the medians and
the spread of each set, (Q3 - Q1) / median by ``statistics.quantiles``.
A bound is about five times the widest spread.

``limits``: what a cell's limits file is set from: the numbers the check
compares over the seeds' sound runs at the cell's own size and load, and,
on the first CONTROLS seeds, the same numbers with the reference computed
in the next precision down. A limit lies above the sound runs' largest and
below the control's smallest.

``sweep``: an open-loop cell's knee, once: the cell at each arrival rate (a
copy of its traffic file with another ``rate_per_s``), one window a seed,
with the time to first token over the window's thirds and the backlog at
its end. A window climbs where its last third's mean lies above both
earlier thirds' and at least 1.3 times the first's; the knee is the highest
rate up to which no window of any rate climbs. The cell runs at four fifths
of it, written into the traffic file with these lines.
"""

from __future__ import annotations

import json
import shutil
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import metrics as M  # noqa: E402
from benchmark import run  # noqa: E402


def show(**row) -> None:
    print(json.dumps(row), flush=True)


def values(res: dict) -> dict:
    return {n: v["value"] for n, v in res["metrics"].items()}


def spread(xs) -> float:
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)


def sets(workload: str, seconds: str, seeds: list) -> None:
    command = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
    per_set = []
    for k in (1, 2):
        rows = []
        for seed in seeds:
            out = subprocess.run(
                [*command, "--workload", workload, "--seed", seed, "--seconds", seconds, "--trace", "0"],
                cwd=ROOT, capture_output=True, text=True)
            lines = out.stdout.strip().splitlines()
            if out.returncode != 0 or not lines or not lines[-1].startswith("{"):
                print(f"set {k} seed {seed}: rc {out.returncode}\n{out.stdout[-1500:]}\n{out.stderr[-1500:]}", flush=True)
                continue
            res = json.loads(lines[-1])
            show(set=k, seed=seed, correct=res["correct"], attempted=res["attempted"], failed=res["failed"],
                 memory_peak_bytes=res["device"]["memory_peak_bytes"], **values(res),
                 lines=[ln for ln in lines if ln.startswith(("compared", "generator", "answers seen", "time to first"))])
            rows.append(values(res))
            answers = sorted((ROOT / ".benchrun" / workload / "spool" / "responses").glob("r*.json"))
            if answers:  # a serving cell: each request's time to first token, as the run's spool still holds it
                show(set=k, seed=seed, ttft_ms=[json.loads(p.read_text()).get("ttft_ms") for p in answers])
        per_set.append(rows)
    for name in sorted(per_set[0][0]) if per_set[0] else []:
        xs = [[r[name] for r in rows if name in r] for rows in per_set]
        show(metric=name, medians=[statistics.median(v) for v in xs if v],
             spreads=[spread(v) for v in xs if len(v) >= 2],
             after_first_run=[spread(v[1:]) for v in xs if len(v) >= 3])


def limits(workload: str, seconds: str, controls: str, seeds: list) -> None:
    sound, control = [], []
    compare, control_numbers = run.compare, run.control_numbers
    run.compare = lambda numbers, lim: sound.append(dict(numbers)) or compare(numbers, lim)
    run.control_numbers = lambda role, ref: control.append(control_numbers(role, ref)) or control[-1]
    for k, seed in enumerate(seeds):
        try:
            res = run.run_cell(workload, int(seed), float(seconds), False, control=k < int(controls))
            show(seed=seed, correct=res["correct"], failed=res["failed"], **values(res))
        except run.BenchFailure as e:
            print(f"seed {seed}: no result: {e}", flush=True)
    for n in sorted({n for row in sound for n in row}):
        s, c = [row[n] for row in sound if n in row], [row[n] for row in control if n in row]
        show(number=n, sound_largest=max(s), sound_all=s, control_smallest=min(c) if c else None, control_all=c)


def climbs(thirds) -> bool:
    return thirds[2] > max(thirds[0], thirds[1]) and thirds[2] >= 1.3 * thirds[0]


def sweep(workload: str, seconds: str, seeds: str, rates: list) -> None:
    copy = ROOT / ".benchrun" / "sweep-copy"
    climbed = {}
    for rate in rates:
        shutil.rmtree(copy, ignore_errors=True)
        shutil.copytree(ROOT / "benchmark", copy / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", copy / "BENCHMARK.json")
        cell = run.load_cell(workload, copy / "benchmark")
        mix_path = copy / "benchmark" / "traffic" / f"{cell['cell']['traffic']}.json"
        mix_path.write_text(json.dumps({**cell["traffic"], "rate_per_s": float(rate)}))
        for seed in seeds.split(","):
            seen = {}
            judge = run.judge_answers
            run.judge_answers = lambda load: seen.update(load=load) or judge(load)
            try:
                res = run.run_cell(workload, int(seed), float(seconds), False, bench=copy / "benchmark")
            except run.BenchFailure as e:
                print(f"rate {rate} seed {seed}: no result: {e}", flush=True)
                continue
            finally:
                run.judge_answers = judge
            load = seen["load"]
            rows = sorted((r["due"], load["answers"][r["id"]]) for r in load["sent"] if r["id"] in load["answers"])
            third = len(rows) // 3 or 1
            thirds = [M.mean(a["ttft_ms"] for _, a in rows[i * third:(i + 1) * third]) for i in range(3)]
            climbed[float(rate)] = climbed.get(float(rate), False) or climbs(thirds)
            show(rate_per_s=float(rate), seed=seed, requests=len(load["sent"]), ttft_mean_ms_by_third=thirds,
                 climbs=climbs(thirds), ttft_mean_ms=M.mean(a["ttft_ms"] for _, a in rows),
                 unanswered_at_window_end=sum(1 for r in load["sent"] if load["seen_at"].get(r["id"], 1e18) > load["end"]),
                 stalls_s=sum(load["stalls"]), correct=res["correct"], failed=res["failed"], **values(res))
    flat = [r for r in sorted(climbed) if not any(climbed[q] for q in climbed if q <= r)]
    show(knee=max(flat) if flat else None, climbed=climbed)


def main(argv) -> int:
    modes = {"sets": lambda a: sets(a[0], a[1], a[2:]), "limits": lambda a: limits(a[0], a[1], a[2], a[3:]),
             "sweep": lambda a: sweep(a[0], a[1], a[2], a[3:])}
    if len(argv) < 4 or argv[0] not in modes:
        print(__doc__, file=sys.stderr)
        return 2
    modes[argv[0]](argv[1:])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
