#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from, on the chip, at the
cell's own size, many seeds in ONE process (set-up is long).

    python3 benchmark/tools/limits.py <cell> <control seeds> <seed> [<seed> ...]

Per seed: the program's set-up calls against the plain reference (the
LOWER reading).  For the first ``<control seeds>`` seeds also the control
(the reference in the program's place, matmul operands in float8_e4m3fn)
and the planted fault "half of the batch left out" against the reference
(the UPPER readings).  One JSON line per seed; nothing here is run by the
benchmark's own runs.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402
from benchmark.harness import check, spec  # noqa: E402


def main(argv) -> int:
    cell = spec.load_cell(argv[0])
    n_control, seeds = int(argv[1]), [int(s) for s in argv[2:]]
    bench.require_chips(int(cell["chips"]))
    bench.place_compile_cache()
    reference = spec.load_reference(cell["config_file"], cell["root"])
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        su = bench.drive_setup(cell, seed, reference)
        followed, call_data = su["followed"], su["call_data"]
        del su
        gc.collect()
        ref = bench.follow_reference(cell, seed, reference, call_data)
        line = {"cell": cell["name"], "seed": seed,
                "program": check.compare(followed, ref),
                "program_losses": [f["losses"] for f in followed],
                "reference_losses": [r["losses"] for r in ref],
                "reference_rare": [float(r["norms"].get("wte.rare", 0)) for r in ref]}
        if i < n_control:
            for name, kw in (("control_fp8", {"precision": "fp8"}),
                             ("fault_half_batch", {"rows": "half"})):
                other = bench.follow_reference(cell, seed, reference, call_data, **kw)
                line[name] = check.compare(other, ref)
                line[name + "_losses"] = [o["losses"] for o in other]
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
