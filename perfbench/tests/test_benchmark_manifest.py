"""What holds of ``BENCHMARK.json`` and ``perfbench/metrics/`` from ISSUE 63
on, every cell a case: a cell's entry, its end-to-end names and the per-layer
names it prints, in order, are what they were at PR 63 with only more behind
them; every name has a reader. Then the table as a whole: it only appends
(against the snapshot ``fixtures/benchmark_at_pr63.json``, since a PR cannot
know its own hash), it has room, every entry has a file and every file an
entry, no cell stands twice in one ``workloads``, and no NEW file borrows
another's ``read``: a cell that an existing reader can read joins that
entry's ``workloads`` (PERF.md, section 4).

The 46 twins of PR 63 are still there, because the tier-1 tests under
``tests/`` pin the table to seven parents' hashes and call four twins by
name, and a ``benchmark`` PR may not edit ``tests/`` (PERF.md, section 7,
first entry). ``tools/fold_aliases.py`` is the fold, held here to what
ISSUE 63 asks of it, for the ``benchmark`` PR that may apply it;
``fixtures/fold_pairs_pr63.jsonl`` holds the last lines of one traced run a
side of two cells on a TPU v5e (my chip runs, PR 63: the parent, and a copy
with the fold applied), whole.
"""
import json
import os

import pytest

from perfbench import manifest as mf
from perfbench.tools import fold_aliases as fa

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "fixtures", "benchmark_at_pr63.json")) as f:
    SNAPSHOT = json.load(f)
WAS = SNAPSHOT["manifest"]
MAN = mf.load_manifest()
CELLS = [cell["name"] for cell in MAN["workloads"]]
HAD = {cell["name"] for cell in WAS["workloads"]}
GROUPS = ("configs", "workloads", "end_to_end", "per_layer")
# What the cells' own tests said of their `why` (tests/test_*_cell.py).
WHY_SAYS = {
    "serve.granite-4.0-h-small.rag-closed": [
        "stage 1 of 4: host, idle ~4x", "8.9 of 17.8 rows an expert"],
    "serve.nemotron-3-nano-30b-a3b.reason-closed": [
        "stage 1 of 4: host, idle ~4x"],
    "serve.solar-open2-250b.doc-closed": ["stage 1 of 12: host, idle ~12x"],
}
# ISSUE 63's table: the cells a surviving entry lists once its twins fold.
FOLDED_CELLS = {
    "decode_round_ms.batch": 10, "decode_wait_ms_round.batch": 9,
    "host_gap_ms_round.batch": 9, "batch_occupancy.batch": 9,
    "paged_decode_ms_round.batch": 8, "prefill_share.batch": 5,
    "experts_touched_share.longgen": 7, "expert_load_max_over_mean.agent": 6,
    "ssm_update_ms_round.chat": 3, "paged_latent_ms_round.longgen": 2,
    "itl_p50_ms.batch": 2, "ttft_p50_ms.batch": 2, "queue_wait_ms.batch": 2,
}


def names(manifest, cell, group):
    return [m["name"] for m in mf.cell_metrics(manifest, cell, group)]


def without_later_cells(entry):
    """`entry` as the snapshot could have held it: the cells that later PRs
    appended to its ``workloads`` taken off, after a look that they stand
    behind the others."""
    entry = dict(entry)
    if "workloads" in entry:
        later = [w for w in entry["workloads"] if w not in HAD]
        assert entry["workloads"][len(entry["workloads"]) - len(later):] \
            == later, entry["name"]
        entry["workloads"] = [w for w in entry["workloads"] if w in HAD]
    return entry


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_cell_lists_what_it_did_and_every_name_has_a_reader(cell_name):
    cell = mf.find_cell(MAN, cell_name)
    assert cell["chips"] in (1, 4) and 0 < len(cell["why"]) <= 200
    config, = (c for c in MAN["configs"] if c["name"] == cell["config"])
    assert 0 < len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert os.path.exists(os.path.join(mf.ROOT, config["file"]))
    assert mf.load_traffic(cell)["runner"]
    end_to_end = names(MAN, cell_name, "end_to_end")
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    per_layer = names(MAN, cell_name, "per_layer")
    assert per_layer and len(set(per_layer)) == len(per_layer)
    for name in per_layer:
        assert mf.load_reader(name) is not None, name
    for phrase in WHY_SAYS.get(cell_name, []):
        assert phrase in cell["why"]
    if cell_name not in HAD:
        return      # a later PR's cell: its own test says what it lists
    assert cell == mf.find_cell(WAS, cell_name)
    assert end_to_end == names(WAS, cell_name, "end_to_end")
    # a later entry may list this cell too: behind what it printed at PR 63
    had = names(WAS, cell_name, "per_layer")
    assert per_layer[:len(had)] == had


def test_the_benchmark_only_appends_to_what_it_had_at_pr_63():
    for key in ("command", "paths", "run_seconds"):
        assert MAN[key] == WAS[key], key
    for group in GROUPS:
        assert len(MAN[group]) >= len(WAS[group]), group
        for old, new in zip(WAS[group], MAN[group]):
            assert old == without_later_cells(new), old["name"]
    assert sum(cell["chips"] == 4 for cell in MAN["workloads"]) == 1
    assert os.path.getsize(os.path.join(mf.ROOT, "BENCHMARK.json")) < 64 << 10


def test_the_table_has_room_and_says_how_much():
    taken = len(MAN["per_layer"])
    assert taken <= 128, f"{taken} of 128 per-layer metrics"
    assert len(MAN["workloads"]) <= 24 and len(MAN["configs"]) <= 24


def test_every_entry_has_a_reader_file_and_every_file_an_entry():
    listed = [m["name"] for m in MAN["per_layer"]]
    assert len(set(listed)) == len(listed)
    assert sorted(listed) == fa.reader_files()


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_no_cell_stands_twice_in_one_workloads(group):
    for metric in MAN[group]:
        cells = metric.get("workloads", [])
        assert len(set(cells)) == len(cells), metric["name"]
        assert set(cells) <= set(CELLS), metric["name"]


def test_no_new_file_borrows_another_files_read():
    """Those of PR 63 wait for their fold; a new one is refused here: append
    the cell to the reader's entry (since PR 54 a PR may)."""
    new = {twin: target for twin, target in fa.twins().items()
           if twin not in SNAPSHOT["twins"]}
    assert not new, f"one reader under two names: {new}"


def test_the_fold_frees_the_twins_places_and_moves_no_reading():
    """On the table as it stands, so that cells and entries which later PRs
    append are folded with the rest."""
    twins = fa.twins()
    folded = fa.fold(MAN, twins)
    assert len(folded["per_layer"]) == len(MAN["per_layer"]) - len(twins)
    assert fa.readings(folded) == fa.readings(MAN)
    for key in MAN:
        if key != "per_layer":
            assert folded[key] == MAN[key], key
    was = {m["name"]: m for m in MAN["per_layer"]}
    targets = set(twins.values())
    assert not targets & set(twins)
    for entry in folded["per_layer"]:
        if entry["name"] not in targets:
            assert entry == was[entry["name"]]
            continue
        mine = [t for t in twins if twins[t] == entry["name"]]
        want = set(was[entry["name"]]["workloads"]).union(
            *(was[t]["workloads"] for t in mine))
        assert entry["workloads"] == [c for c in CELLS if c in want]
        assert {k: v for k, v in entry.items() if k != "workloads"} == {
            k: v for k, v in was[entry["name"]].items() if k != "workloads"}
    # every cell prints each reader's number once, under one name
    for cell in CELLS:
        readers = [mf.load_reader(n) for n in names(folded, cell, "per_layer")]
        assert len(set(map(id, readers))) == len(readers), cell


def test_the_fold_of_pr_63s_table_is_issue_63s():
    assert len(SNAPSHOT["twins"]) == 46 and len(WAS["per_layer"]) == 128
    folded = fa.fold(WAS, SNAPSHOT["twins"])
    assert len(folded["per_layer"]) == 82
    assert {m["name"]: len(m["workloads"]) for m in folded["per_layer"]
            if m["name"] in FOLDED_CELLS} == FOLDED_CELLS
    assert set(SNAPSHOT["twins"].values()) == set(FOLDED_CELLS)
    assert fa.readings(folded) == fa.readings(WAS) == [
        10, 25, 12, 22, 27, 31, 28, 29, 31, 18, 32, 33, 28]
    assert len(json.dumps(folded, indent=1)) < 41_000


def test_the_chips_lines_hold_the_same_readings_under_the_surviving_names():
    """31 and 33 per-layer readings on both sides, none null, the nine and
    the eight twins' under their targets' names."""
    with open(os.path.join(HERE, "fixtures", "fold_pairs_pr63.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    twins, renamed = SNAPSHOT["twins"], {}
    for parent, change in zip(rows[::2], rows[1::2]):
        assert (parent["side"], change["side"]) == ("parent", "change")
        assert parent["workload"] == change["workload"]
        assert parent["line"]["correct"] and change["line"]["correct"]
        was, now = parent["line"]["metrics"], change["line"]["metrics"]
        assert all(m["value"] is not None for m in [*was.values(),
                                                    *now.values()])
        assert sorted(now) == sorted(twins.get(name, name) for name in was)
        assert sorted(set(was) - set(names(WAS, parent["workload"],
                                           "end_to_end"))) == sorted(
            names(WAS, parent["workload"], "per_layer"))
        renamed[parent["workload"]] = sum(name in twins for name in was)
    assert renamed == {"serve.evabyte-6.5b.bytegen-closed": 9,
                       "serve.nemotron-3-nano-30b-a3b.reason-closed": 8}
