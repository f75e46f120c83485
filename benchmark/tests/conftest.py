"""Run by hand: ``python3 -m pytest benchmark/tests -q`` (not part of the
repo's tier-1 tests).  Puts ``benchmark/`` on the path, as ``run.py``
does."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
