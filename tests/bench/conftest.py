"""The benchmark's own tests import it as ``bench`` from the checkout root
and their helpers from ``bench_helpers`` beside this file."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(pathlib.Path(__file__).resolve().parent), str(ROOT / "src"),
           str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
