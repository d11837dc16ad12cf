#!/usr/bin/env python3
"""``selftest_mds4.py`` with ``ukb1`` cut to toy size too: ``python3 benchmark/selftest_ukb1.py``.

``selftest.py`` cuts configurations to toy sizes by a dict of the names
it knows (``TOY``); a configuration it does not know is rehearsed at full
size (9.1 GB of plane words and 1.82e6 metadata documents for ``ukb1``).
This file takes ``selftest_mds4``'s entries, its four virtual CPU devices
and its checks, and adds ``ukb1``'s cut: 4,000 rows of chromosome 22 and
41,333 samples, a plane row of 1,292 words resident as ELEVEN lane rows
(the published 454,787 are 112), 1,653 or 1,654 samples a term. So
``selftest``'s own checks run ``ukb1.samples`` with its controls through
the real files, wider than any cell before it. After them it runs
``ukb1.samples`` and ``kg1.samples`` once more with ``--trace 1`` and
holds the lines to what the two widths are there to show side by side:
the three metrics this configuration brought read in both, the
selection's size is the term's, a launch is one slot and gathers whole
blocks of eight rows of the cell's own width, and the trace readers that
find no device plane on the CPU leave their metrics out. The published
width itself runs on the chip (``chiprun -- python3 benchmark/run.py
--workload ukb1.samples ...``) and, for the host path, here with
``--rehearsal`` and a bench root whose rows alone are cut (PERF.md 6).
Until a ``benchmark`` issue moves the toy cut into the configuration
files, run this one and none of the other ``selftest*.py``.
"""

from __future__ import annotations

import os
import sys

import selftest_mds4  # noqa: F401  (kg4's, mdsp's and mds4's toy entries, the four devices)
import selftest
from selftest import check

TOY_SAMPLES = 41_333
selftest.TOY["ukb1"] = {
    "rows_per_dataset": 4_000, "n_samples": TOY_SAMPLES,
    "control": {"stale_rows_every": 2},
}

every_cell_and_mds4 = selftest.test_command


def traced(tmp, workload: str, seed: int) -> dict:
    rc, lines = selftest.run_cell(tmp / "root", workload, seed, trace=1)
    check(rc == 0 and lines[-1]["correct"] is True, f"{workload} traced: ran and correct")
    return {k: v["value"] for k, v in lines[-1]["metrics"].items()}


def test_command(tmp) -> None:
    every_cell_and_mds4(tmp)
    print("ukb1.samples and kg1.samples traced, at toy size (rehearsals, never a chip result)")
    wide = traced(tmp, "ukb1.samples", 2**31 + 431)
    narrow = traced(tmp, "kg1.samples", 2**31 + 432)
    new = {"select_ms", "selected_samples_per_query", "plane_gather_mb_per_launch"}
    check(new <= set(wide) and new <= set(narrow),
          "both widths report the selection's stage, its size and the bytes a launch gathers")
    # the snapshots bracket the 1 s ramp before the 3 s window: up to 4 / 3 and the warm-up's tail
    term = TOY_SAMPLES / 25
    check(0.99 * term <= wide["selected_samples_per_query"] <= 1.6 * term,
          f"ukb1.samples selects a term's samples a request: {wide['selected_samples_per_query']:.0f} "
          f"against {term:.0f}")
    check(99 <= narrow["selected_samples_per_query"] <= 165,
          f"kg1.samples a hundred: {narrow['selected_samples_per_query']:.0f}")
    # blocks of eight rows: 11 lane rows of 512 B a row here, one at 2504 samples
    block_mb = 8 * 11 * 512 / 1e6
    check(0 < wide["plane_gather_mb_per_launch"] and
          abs(wide["plane_gather_mb_per_launch"] / narrow["plane_gather_mb_per_launch"] - 11) < 4,
          f"a launch gathers whole blocks of its cell's rows: {wide['plane_gather_mb_per_launch']:.4f} MB "
          f"(a block is {block_mb:.4f}) against {narrow['plane_gather_mb_per_launch']:.4f} at one lane row")
    check(wide["select_ms"] >= 0 and wide.get("descendants_ms") is not None
          and wide.get("resolve_memo_hit_share", 0) > 50,
          f"the selection is resolved inside engine.plan: {wide['select_ms']:.3f} ms a request; "
          "the term's closure and the memo read as in kg1.samples-desc")
    # (the plane launches pass no batcher: batch_mean_size reads the canary's few, or nothing)
    check(0.99 <= wide["launches_per_query"] <= 1.6 and wide.get("batch_mean_size", 1.0) == 1.0,
          f"one match+planes launch a request: {wide['launches_per_query']:.2f}")
    check(not {"plane_kernel_ms", "plane_roofline", "device_idle_share"} & set(wide),
          "no device plane on the CPU: the trace's readers leave their metrics out")
    check(wide.get("span_coverage", 0) >= 70, f"span_coverage {wide.get('span_coverage'):.1f} %")
    # the cell holds the most of any on its chip: the placement and start-up layers list it
    plane_gb = 4_000 * 11 * 512 / 1e9
    check(wide.get("chip_resident_gb_max", 0) >= plane_gb and wide.get("pack_upload_s", 0) > 0,
          f"the chip's resident bytes and the upload's seconds are read: {wide.get('chip_resident_gb_max')} GB "
          f"(the plane alone is {plane_gb:.4f}), {wide.get('pack_upload_s')} s")


selftest.test_command = test_command

if __name__ == "__main__":
    selftest_mds4.test_trace_ops()
    rc = selftest.main()
    sys.stdout.flush()
    os._exit(rc)
