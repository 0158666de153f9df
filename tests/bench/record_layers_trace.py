"""Record the small chip trace that ``test_xspace.py`` reads: the traced
run of the tiny cell that ``record_trace.py`` makes, kept gzipped as
``tests/bench/data/layers.xplane.pb.gz``.  A program with model scopes
and ``engine.*`` annotations writes both into it.

    python3 tests/bench/record_layers_trace.py
"""
from __future__ import annotations

import sys

import record_trace

# sorts before tiny.xplane.pb.gz, which test_bench_trace.py finds last
record_trace.OUT = record_trace.OUT.with_name("layers.xplane.pb.gz")

if __name__ == "__main__":
    sys.exit(record_trace.main())
