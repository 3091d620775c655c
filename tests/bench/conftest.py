import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
