"""The traffic generator: the same seed gives the same schedule, every seed
the same work (lengths and gaps as multisets) in another order, and the
stated lengths and rate hold: of every serving mix of the real tree, and of
a copy with cells appended (``rules.py``)."""

from __future__ import annotations

import copy

import pytest

from benchmark import traffic as T
from tests.zz_benchmark.rules import REAL, Tree, appended, copy_cases, over  # noqa: F401


@over("mix_name", lambda tree: tree.serve_mixes)
def test_same_seed_same_schedule_other_seed_same_work(mix_name, tree=REAL):
    mix = tree.mixes[mix_name]
    a, b = T.schedule(mix, 12345, 20.0, 1000), T.schedule(mix, 12345, 20.0, 1000)
    assert a == b
    c = T.schedule(mix, 2**31 + 77, 20.0, 1000)  # the driver's seeds are large
    assert [r["prompt"] for r in c] != [r["prompt"] for r in a]
    pairs = lambda s: sorted((r["prompt_len"], r["max_new_tokens"]) for r in s)
    assert pairs(a) == pairs(c)
    assert all(len(r["prompt"]) == r["prompt_len"] and all(0 <= t < 1000 for t in r["prompt"]) for r in c)


@over("mix_name", lambda tree: tree.serve_mixes)
def test_lengths_are_the_tables_and_within_the_stated_range(mix_name, tree=REAL):
    """The longest prompt + answer fits the check's padding, and the cache of every cell that sends the mix
    (``bench.engine.max_decode_len`` of its configuration, less the margin 4,095 kept against 4,096); a mix no
    cell sends, the shallowest cache any served configuration states."""
    mix = tree.mixes[mix_name]
    table = {tuple(p) for p in mix["lengths"]}
    sched = T.schedule(mix, 3, 30.0, 50)
    assert {(r["prompt_len"], r["max_new_tokens"]) for r in sched} <= table
    longest = max(p + a for p, a in table)
    assert longest <= mix["check_pad_to"]
    depth = {w["name"]: bench["engine"]["max_decode_len"] for w in tree.manifest["workloads"]
             for bench in [tree.config_of(w)["bench"]] if "engine" in bench}  # of every served cell
    sent_by = [w["name"] for w in tree.manifest["workloads"] if w["traffic"] == mix_name]
    caches = {cell: depth[cell] for cell in sent_by or depth}
    assert caches and longest < min(caches.values()) - 1, f"{mix_name}: {longest} positions against max_decode_len {caches}"


def test_open_loop_rate_and_gaps():
    mix = {"name": "m", "loop": "open", "rate_per_s": 5.0, "lengths": [[10, 3], [20, 4]]}
    for seconds in (10.0, 50.0):
        due = T.arrival_times(mix, 9, seconds)
        assert len(due) == round(5.0 * seconds)
        assert due == sorted(due) and 0 < due[0] and due[-1] < seconds
    gaps = lambda d: sorted(round(y - x, 9) for x, y in zip([0.0] + d[:-1], d))
    assert gaps(T.arrival_times(mix, 1, 50.0)) == gaps(T.arrival_times(mix, 2, 50.0))
    assert T.arrival_times(mix, 1, 50.0) != T.arrival_times(mix, 2, 50.0)
    # Exponential gaps: the coefficient of variation of a Poisson stream is 1.
    g = gaps(T.arrival_times(mix, 1, 50.0))
    mean = sum(g) / len(g)
    cv = (sum((x - mean) ** 2 for x in g) / len(g)) ** 0.5 / mean
    assert 0.85 < cv < 1.1


def test_closed_loop_supply_and_unknown_loop():
    mix = {"name": "m", "loop": "closed", "clients": 2, "supply": 40, "lengths": [[10, 3], [20, 4], [30, 5]]}
    sched = T.schedule(mix, 5, 10.0, 100)
    assert len(sched) == 40 and all(r["due"] is None for r in sched)
    assert len({r["id"] for r in sched}) == 40
    with pytest.raises(ValueError):
        T.schedule({**mix, "loop": "spiral"}, 5, 10.0, 100)
    with pytest.raises(FileNotFoundError):
        T.load("no-such-mix")


def test_chat_file_records_its_sweep(tree=REAL):
    mix = tree.mixes["chat-poisson"]
    assert mix["swept"], "the swept rates and what each gave belong in the traffic file"
    assert any(abs(row["rate_per_s"] * 0.8 - mix["rate_per_s"]) < 0.26 for row in mix["swept"] if row.get("knee"))


def _cycle(mix, seed):
    s = T.schedule(mix, seed, 50.0, 100)
    due = [r["due"] for r in s]
    gaps = [round(b - a, 9) for a, b in zip([0.0] + due[:-1], due)]
    return list(zip(gaps, [(r["prompt_len"], r["max_new_tokens"]) for r in s])), [r["prompt"] for r in s]


@pytest.mark.parametrize("entry", ["seed", 0, 7])
def test_the_seed_enters_one_fixed_cycle(entry):
    """Which length meets which gap never changes: the seed only moves the
    point at which the cycle is entered, or, in a file whose ``cycle_entry``
    is a number, nothing but the token values."""
    mix = {**T.load("chat-poisson"), "cycle_entry": entry}
    n = round(mix["rate_per_s"] * 50.0)
    base, _ = _cycle({**mix, "cycle_entry": 0}, 0)
    (a, tokens_a), (b, tokens_b) = _cycle(mix, 0), _cycle(mix, 2**31 + 12345)
    k = (2**31 + 12345) % n if entry == "seed" else entry
    assert len(a) == n and b == base[k:] + base[:k] and tokens_a != tokens_b
    assert (b != a) == (entry == "seed")


def test_the_chat_cell_enters_its_cycle_at_one_row():
    """PR 26: its seeds lay apart and its repeats together (PERF.md), so the seed chooses token values alone."""
    assert T.load("chat-poisson")["cycle_entry"] == 0 and "cycle_entry" not in T.load("longprompt-closed")


# ---- the same rules, on a copy with cells appended ----


@pytest.mark.parametrize("rule, item", copy_cases(globals()))
def test_the_rule_holds_on_a_copy_with_cells_appended(rule, item, appended):
    rule(*item, tree=appended)


@pytest.mark.parametrize("config", [
    pytest.param("internlm2-1.8b-serve", id="under-a-cache-of-4096"), pytest.param(None, id="sent-by-no-cell")])
def test_a_mix_deeper_than_the_cache_it_meets_fails_the_rule(appended, config):
    """The copy's deep mix (6,000 positions) holds the rule under its own configuration (8192, the test above).
    Under a configuration whose cache holds 4,096 it does not, nor where no cell sends it and the copy's
    shallowest cache judges it."""
    manifest = copy.deepcopy(appended.manifest)
    (deep,) = [w for w in manifest["workloads"] if w["name"] == "tiny-deep"]
    if config:
        deep["config"] = config
    else:
        manifest["workloads"].remove(deep)
    with pytest.raises(AssertionError, match="tiny-deep-closed: 6000 positions against max_decode_len"):
        test_lengths_are_the_tables_and_within_the_stated_range("tiny-deep-closed", tree=Tree(appended.root, manifest))
