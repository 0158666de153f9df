"""Record the small chip trace that ``test_bench_trace.py`` reads: one
traced run of the tiny cell (``bench_tiny``) on the TPU this process
finds, its ``.xplane.pb`` kept gzipped as ``tests/bench/data/tiny.xplane.pb.gz``.

    python3 tests/bench/record_trace.py
"""
from __future__ import annotations

import gzip
import json
import shutil
import sys
import types

import bench_tiny
from bench import run, tracefile

OUT = bench_tiny.ROOT / "tests" / "bench" / "data" / "tiny.xplane.pb.gz"


def main() -> int:
    run.use_cache()

    def keep_then_remove(path, **kw):
        OUT.parent.mkdir(exist_ok=True)
        with open(tracefile.find_xplane(path), "rb") as f, \
                gzip.open(OUT, "wb") as g:
            g.write(f.read())
        shutil.rmtree(path, **kw)

    # run_cell removes its trace directory once read: keep a copy first
    run.shutil = types.SimpleNamespace(rmtree=keep_then_remove)
    res = run.run_cell(bench_tiny.cell("backlog"), 2 ** 31 + 5, 2.0, True)
    print(json.dumps({"bytes": OUT.stat().st_size, "device": res["device"],
                      "correct": res["correct"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
