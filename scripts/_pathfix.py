"""Make the repo root importable: nothing is pip-installed, and
`python scripts/x.py` puts scripts/ on sys.path, not the root.
Import for its side effect: ``import _pathfix``."""
import sys
from pathlib import Path

_root = str(Path(__file__).resolve().parent.parent)
if _root not in sys.path:
    sys.path.insert(0, _root)
