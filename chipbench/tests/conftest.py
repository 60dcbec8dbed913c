import sys
from pathlib import Path

# the program is imported from the checkout's src/ (no install)
_SRC = str(Path(__file__).resolve().parents[2] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
