#!/usr/bin/env python3
"""``selftest.py`` with ``kg4`` cut to toy rows: ``python3 benchmark/selftest_kg4.py``.

``selftest.py`` cuts configurations to toy sizes by a dict of the names
it knows (``TOY``); a configuration it does not know is rehearsed at full
size (8e7 rows for ``kg4``). This file adds ``kg4``'s entry and four
virtual CPU devices, so that the four datasets of ``kg4.samples`` get four
owner chips, and then runs ``selftest``'s own checks unchanged: every cell
of ``BENCHMARK.json``, ``kg4.samples`` and ``kg1.samples-desc`` among
them, at toy sizes. Until a ``benchmark`` issue moves the toy cut into the
configuration files, run this one and not plain ``selftest.py``.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import selftest  # noqa: E402

selftest.TOY["kg4"] = {"rows_per_dataset": 60_000, "control": {"stale_rows_every": 2}}

if __name__ == "__main__":
    rc = selftest.main()
    sys.stdout.flush()
    os._exit(rc)
