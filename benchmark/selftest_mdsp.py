#!/usr/bin/env python3
"""``selftest_kg4.py`` with ``mdsp`` cut to toy rows too: ``python3 benchmark/selftest_mdsp.py``.

``selftest.py`` cuts configurations to toy sizes by a dict of the names
it knows (``TOY``); a configuration it does not know is rehearsed at full
size (4.8e7 rows and sixteen planes for ``mdsp``). This file takes
``selftest_kg4``'s entry and its four virtual CPU devices, adds
``mdsp``'s (four datasets of 12,000 rows, as ``mds``), and then runs
``selftest``'s own checks unchanged: every cell of ``BENCHMARK.json``,
``mdsp.samples`` among them, at toy sizes. Until a ``benchmark`` issue
moves the toy cut into the configuration files, run this one and neither
plain ``selftest.py`` nor ``selftest_kg4.py``.
"""

from __future__ import annotations

import os
import sys

import selftest_kg4  # noqa: F401  (kg4's toy entry, the four devices)
import selftest

selftest.TOY["mdsp"] = {
    "rows_per_dataset": 12_000, "datasets": 4, "control": {"stale_rows_every": 2},
}

if __name__ == "__main__":
    rc = selftest.main()
    sys.stdout.flush()
    os._exit(rc)
