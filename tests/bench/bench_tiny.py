"""A tiny cell for the benchmark's CPU tests: the harness's own data
shapes at a size the CPU runs in seconds."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

ARCH = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 32, "d_ff": 256, "vocab": 512, "rope_frac": 0.5,
        "rope_theta": 10000.0, "norm_eps": 1e-5}


def config(policy="kv=int8@32:ocp", limit=0.05, mean_limit=0.005):
    return {"name": "tiny", "arch": dict(ARCH), "policy": policy,
            "attn_impl": "flash" if "kv_value" in policy else "dense",
            "engine": {"max_slots": 4, "page_size": 16, "max_len": 256,
                       "sync_every": 4, "prefill_bucket": 32,
                       "num_pages": 64, "max_queued": 2},
            "check": {"sample": 3, "max_logit_gap": limit,
                      "mean_logit_gap": mean_limit},
            "control": "fp8_e4m3"}


def mix(kind="open_loop"):
    m = {"name": kind, "kind": kind,
         "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                        "min": 8, "max": 64},
         "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                        "min": 4, "max": 16},
         "set_size": 16}
    if kind == "open_loop":
        m.update(arrivals={"process": "poisson", "rate_per_s": 4.0},
                 ramp_s=0.5, drain_s=30)
    else:
        m.update(full_steps=4)
    return m


def cell(kind="open_loop", **kw):
    from bench import cells
    spec = cells.load_benchmark()
    e2e = [cells.Metric(m["name"], m["unit"], m["better"], m["source"], m)
           for m in spec["end_to_end"]]
    return cells.Cell(f"tiny.{kind}", 1, config(**kw), mix(kind), e2e, [])
