#!/usr/bin/env python3
"""``selftest_mdsp.py`` with ``mds4`` cut to toy rows too: ``python3 benchmark/selftest_mds4.py``.

``selftest.py`` cuts configurations to toy sizes by a dict of the names
it knows (``TOY``); a configuration it does not know is rehearsed at full
size (2.559e8 rows for ``mds4``). This file takes ``selftest_mdsp``'s
entries and its four virtual CPU devices, adds ``mds4``'s (eight datasets
of 12,000 rows: two a device, so the engine's mesh stack serves
``mds4.fanout`` as it does on the chips), and then runs ``selftest``'s own
checks unchanged: every cell of ``BENCHMARK.json`` at toy sizes, each with
its controls. After them it runs ``mds4.fanout`` once more with
``--trace 1`` and holds the line to what the cell is there for: every
request took the stack (``mesh_launches_per_query``) and no other
family launched, its stages read, and the trace readers that find no
device plane on the CPU leave their metrics out and do not raise. It also checks
``readers/trace_ops.py`` on a hand-made event list. Until a ``benchmark``
issue moves the toy cut into the configuration files, run this one and
none of ``selftest.py``, ``selftest_kg4.py``, ``selftest_mdsp.py``.
"""

from __future__ import annotations

import os
import sys

import selftest_mdsp  # noqa: F401  (kg4's and mdsp's toy entries, the four devices)
import selftest
from selftest import check

selftest.TOY["mds4"] = {
    "rows_per_dataset": 12_000, "datasets": 8, "control": {"stale_rows_every": 2},
}


def test_trace_ops() -> None:
    import run as bench_run

    print("readers/trace_ops.py on a hand-made event list")
    reader = bench_run.load_module(selftest.HERE / "readers" / "trace_ops.py")
    program = "jit__local_query(12)"
    events = [
        ["/host:CPU", "main", "bench.traced_window", 0, 10_000],
        ["/device:TPU:0", "XLA Modules", program, 100, 1000],
        ["/device:TPU:0", "XLA Ops", "%all-reduce.1 = (s32[1]{0}, s32[1]{0}) all-reduce(...)", 200, 50],
        ["/device:TPU:0", "XLA Ops", "%fusion.4 = s32[32,1,1024]{2,1,0} fusion(...)", 300, 500],
        ["/device:TPU:1", "XLA Modules", program, 120, 1000],
        ["/device:TPU:1", "XLA Ops", "%all-reduce.1 = (s32[1]{0}, s32[1]{0}) all-reduce(...)", 220, 150],
        ["/device:TPU:1", "XLA Ops", "%all-reduce.7 = s32[8]{0} all-reduce(...)", 5000, 70],
    ]
    modules, ops = [r"^jit__local_query$"], ["^%?all-reduce"]
    check(abs(reader.ms_per_launch(events, modules, ops) - 200 / 1e6 / 2) < 1e-12,
          "all-reduce time inside the family's launches over its launches, summed over "
          "chips; an all-reduce outside any launch of the family is not counted")
    check(reader.ms_per_launch(events, [r"^jit_none$"], ops) is None
          and reader.ms_per_launch(events, modules, ["^%?all-gather"]) is None
          and reader.read({"what": "ms_per_launch", "family": "mesh", "ops": ops},
                          {"trace": {"devices": 0}}) is None,
          "no launch of the family, no such operation, no device plane: nothing, and no error")


rehearse_every_cell = selftest.test_command


def test_command(tmp) -> None:
    rehearse_every_cell(tmp)
    print("mds4.fanout traced, at toy size (a rehearsal, never a chip result)")
    rc, lines = selftest.run_cell(tmp / "root", "mds4.fanout", 2**31 + 77, trace=1)
    got = {k: v["value"] for k, v in lines[-1]["metrics"].items()}
    check(rc == 0 and lines[-1]["correct"] is True, "mds4.fanout traced: ran and correct")
    # the snapshots bracket the 1 s ramp before the 3 s window: 4 / 3 of the window's own
    check(0.99 <= got.get("mesh_launches_per_query", 0) <= 1.5,
          f"every request took the mesh stack: {got.get('mesh_launches_per_query'):.2f} launches "
          "a request, none of another family")
    check(abs(got.get("launches_per_query", -1) - got["mesh_launches_per_query"]) < 1e-9,
          "no launch of another family in the window")
    check(got.get("kernel_dispatch_ms", 0) > 0 and got.get("kernel_readback_ms", 0) > 0
          and got.get("fetched_kb_per_query", 0) > 0 and "materialize_ms" in got,
          "the mesh launch passes the kernel stages and its responses engine.materialize: "
          f"{got.get('fetched_kb_per_query'):.1f} kB fetched a request")
    check(not {"fanout_wait_ms", "fanout_pool_wait_ms", "fanout_targets_per_query"} & set(got),
          "a boolean's or a count's responses are built on the request's thread: no pool task")
    check(not {"mesh_kernel_ms", "mesh_roofline", "mesh_collective_ms"} & set(got),
          "no device plane on the CPU: the trace's readers leave their metrics out")
    check(got.get("span_coverage", 0) >= 70, f"span_coverage {got.get('span_coverage'):.1f} %")


selftest.test_command = test_command

if __name__ == "__main__":
    test_trace_ops()
    rc = selftest.main()
    sys.stdout.flush()
    os._exit(rc)
