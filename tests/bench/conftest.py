"""The benchmark's tests import it as ``bench`` from the checkout's root."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


import copy  # noqa: E402

import pytest  # noqa: E402

#: A crawl small enough for the interpreted kernels: the hottest keywords'
#: lists (about 3,000 postings) pass the window, so its bound binds.
TINY = {"n_docs": 3000, "vocab_size": 400, "n_sites": 8}
TINY_WINDOW = 2048


@pytest.fixture
def tiny_cell():
    """``tiny_cell(name, **traffic)``: the cell at a CPU-sized crawl and
    window, its traffic parameters overridden."""
    from bench import spec

    def make(name: str, root=None, **traffic):
        cell = spec.load_cell(name) if root is None else spec.load_cell(name, root)
        cell = copy.deepcopy(cell)
        cell.config.update(TINY)
        cell.config["service"]["window"] = TINY_WINDOW
        cell.traffic.update(traffic)
        return cell

    return make
