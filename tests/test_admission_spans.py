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
