"""The benchmark's tests import it as the package ``chipbench`` from the
root of the checkout, beside the program under ``src``."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
