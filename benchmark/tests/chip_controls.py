"""One run of a cell as ``benchmark/run.py`` makes it, with the check's
controls run on its record afterwards (each has to read false); the
arguments are ``run.py``'s.  For the chip: how the controls were read at
the cell's own size (PERF.md section 2)."""

import importlib.util
import sys
from pathlib import Path

spec = importlib.util.spec_from_file_location(
    "bench_run", Path(__file__).resolve().parents[1] / "run.py")
run_py = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run_py)
sys.exit(run_py.main(sys.argv[1:], controls=True))
