"""The admission path's per-layer readers on a hand-built run
(perfbench/tests/test_admission_spans.py, whose cases run here so that the
tier-1 run holds them)."""
import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _readers():
    spec = importlib.util.spec_from_file_location(
        "perfbench_tests_admission_spans", os.path.join(
            ROOT, "perfbench", "tests", "test_admission_spans.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


globals().update({name: case for name, case in vars(_readers()).items()
                  if name.startswith("test_")})


def test_the_eleven_entries_list_the_serving_cells_that_can_read_them():
    """perfbench/tests/test_admission_spans.py's case of this name, for a
    BENCHMARK.json that later PRs append to (that file pins the eleven
    entries to the END of `per_layer` and their `workloads` to the seven
    cells of PR 50; it may not be edited outside a `benchmark` PR): the
    eleven entries stand together in their order, each lists PR 50's cells
    first and in order, and every cell behind them is a serving cell whose
    runner is not the dense one's."""
    import json
    mod = _readers()
    mf, adm = mod.mf, mod.adm
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(adm.METRICS[0])
    entries = manifest["per_layer"][first:first + 11]
    assert [m["name"] for m in entries] == list(adm.METRICS)
    assert sorted(adm.METRICS) == sorted(mod.NEW + mod.OLD_TOO)
    cells = {c["name"]: c for c in manifest["workloads"]}
    for m in entries:
        assert mf.load_reader(m["name"]) is not None
        # the dense cell's runner keeps no attributes
        was = mod.CELLS[m["name"] in mod.ATTRIBUTED:]
        assert m["workloads"][:len(was)] == was
        assert m["moves"] == "serve_tok_s"
        assert m["layer"] == ("device" if m["name"].startswith("idle_")
                              else "serving engine")
        for c in m["workloads"]:
            runner = mf.load_traffic(cells[c])["runner"]
            assert runner.startswith("serve")
            assert (runner == "serve_closed") == (c == mod.CELLS[0])

