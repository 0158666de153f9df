"""The benchmark as data: every entry of BENCHMARK.json resolves to its
files by name, mixes are deterministic under the seed, the tail counts
every request from when it was due, and a run without a TPU fails."""
from __future__ import annotations

import json
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench_tiny import ROOT, mix

from bench import cells, run, serving, stats  # noqa: E402
from bench.arrivals import Traffic  # noqa: E402
from bench.flops import peaks  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SPEC = cells.load_benchmark()


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][1] == "bench/run.py"
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_resolves_by_name(w):
    cell = cells.load_cell(w["name"])
    assert cell.chips in (1, 4)
    names = {m.name for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m.spec["moves"] in names
        assert callable(cells.metric_reader(m.name))
    assert cell.traffic["kind"] in ("open_loop", "backlog")
    for key in ("arch", "policy", "engine", "check", "control"):
        assert key in cell.config


def test_names_units_and_sources():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for c in SPEC["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]


def test_a_new_mix_config_and_metric_are_files_and_entries_only(tmp_path):
    """A later PR adds a cell by adding files and entries: the harness
    finds them by name with no edit to its code."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "busy.json").write_text(
        json.dumps(dict(mix(), arrivals={"process": "poisson",
                                         "rate_per_s": 9.0})))
    (tmp_path / "bench" / "metrics" / "requests_judged.busy.py") \
        .write_text("def read(run):\n    return len(run.window.judged)\n")
    spec["workloads"].append({"name": "tiny.busy", "config": "tiny",
                              "traffic": "busy", "chips": 1,
                              "why": "test"})
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        (ROOT / "bench" / "configs" / "chatglm3-6b-int8kv.json")
        .read_text())
    spec["per_layer"].append({"name": "requests_judged.busy",
                              "unit": "requests", "better": "higher",
                              "source": "program_span", "layer": "engine",
                              "moves": "setup_s",
                              "workloads": ["tiny.busy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.load_cell("tiny.busy", root=tmp_path)
    assert cell.traffic["arrivals"]["rate_per_s"] == 9.0
    assert [m.name for m in cell.per_layer] == ["requests_judged.busy"]
    read = cells.metric_reader("requests_judged.busy",
                               bench=tmp_path / "bench")
    assert read(type("R", (), {"window": type("W", (), {
        "judged": [1, 2, 3]})})()) == 3


@pytest.mark.parametrize("kind", ["open_loop", "backlog"])
def test_mix_is_deterministic_under_the_seed(kind):
    seed = 2 ** 31 + 977          # wider than 32 signed bits
    a = Traffic(mix(kind), seed, 512).take(40)
    b = Traffic(mix(kind), seed, 512).take(40)
    c = Traffic(mix(kind), seed + 1, 512).take(40)
    key = lambda rs: [(r.due_s, r.max_new_tokens, r.prompt.tolist())
                      for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # every seed offers the same sizes in each pass over the set; an
    # open-loop pass begins where the ramp ends
    m = mix(kind)
    n = m["set_size"]
    pass_of = lambda rs: rs[:n] if kind == "backlog" else [
        r for r in rs if m["ramp_s"] <= r.due_s
        < m["ramp_s"] + n / m["arrivals"]["rate_per_s"]]
    assert len(pass_of(a)) == len(pass_of(c)) == n
    sizes = lambda rs: sorted(len(r.prompt) for r in pass_of(rs))
    outs = lambda rs: sorted(r.max_new_tokens for r in pass_of(rs))
    assert sizes(a) == sizes(c) and outs(a) == outs(c)
    if kind == "open_loop":
        def gaps(rs):              # the pass's gaps, its first one too
            i = pass_of(rs)[0].index
            return sorted(np.round(np.diff([r.due_s
                                            for r in rs[i - 1:i + n]]), 9))
        assert gaps(a) == gaps(c)
        assert np.isclose(sum(gaps(a)), n / m["arrivals"]["rate_per_s"])


@pytest.mark.parametrize("pairing", ["seed", "fixed"])
def test_fixed_pairing_gives_every_seed_the_same_requests(pairing):
    """With ``pairing: fixed`` a window of one pass judges the same
    (prompt, output) sizes under every seed; by default each pass pairs
    them afresh."""
    m = dict(mix(), pairing=pairing, set_size=32)
    pass_s = 32 / m["arrivals"]["rate_per_s"]
    pairs = []
    for seed in (5, 2 ** 31 + 11, 77):
        rs = Traffic(m, seed, 512).until(m["ramp_s"] + pass_s)
        judged = [r for r in rs if m["ramp_s"] <= r.due_s]
        assert len(judged) == 32
        pairs.append(sorted((len(r.prompt), r.max_new_tokens)
                            for r in judged))
    same = pairs[0] == pairs[1] == pairs[2]
    assert same == (pairing == "fixed")


def test_lognormal_sizes_follow_the_mix():
    m = dict(mix("backlog"), set_size=1001)
    rs = Traffic(m, 3, 512).take(1001)
    lens = sorted(len(r.prompt) for r in rs)
    assert lens[0] >= 8 and lens[-1] <= 64
    assert lens[500] == 20              # the median is the mix's median


def _req(due, first, last, n):
    r = serving.ReqRec(0, np.zeros(4, np.int32), n, due)
    r.sent = due + 5.0                  # sent late: must not count
    stamps = list(np.linspace(first, last, n))
    r.request = type("Q", (), {"t_tokens": stamps, "t_admitted": first})()
    r.tokens = np.zeros(n, np.int32)
    return r


def test_tail_counts_every_request_timed_from_due():
    judged = [_req(float(i), i + 0.1 * (i + 1), i + 10.0, 11)
              for i in range(20)]
    win = serving.Window(0.0, 0.0, 20.0, judged, judged)
    cell = type("C", (), {"traffic": {"kind": "open_loop"}})()
    rec = type("R", (), {"in_window": []})()
    out = run.end_to_end(cell, win, rec, 1.5)
    # ttft_i = 0.1 (i + 1): nearest-rank p90 of 20 is the 18th smallest
    assert out["ttft_p90_ms"] == pytest.approx(1800.0)
    tpots = sorted((10.0 - 0.1 * (i + 1)) / 10 for i in range(20))
    assert out["tpot_p90_ms"] == pytest.approx(1e3 * tpots[17])
    assert out["setup_s"] == 1.5
    assert stats.percentile([5.0], 90) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_rate_counts_whole_steps_only():
    steps = [serving.StepRec(t0=float(i), t1=i + 0.9, emitted=10)
             for i in range(10)]
    rec = type("R", (), {"in_window": serving.window_steps(
        steps, 2.5, 8.95)})()
    cell = type("C", (), {"traffic": {"kind": "backlog"}})()
    win = serving.Window(0.0, 2.5, 8.95, [], [])
    out = run.end_to_end(cell, win, rec, 0.0)
    # steps 3..8 lie wholly inside: 60 tokens over 8.9 - 3.0 seconds
    assert out["out_tokens_per_s"] == pytest.approx(60 / 5.9)


def test_unknown_device_kind_is_an_error():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


def test_cpu_backend_is_refused():
    with pytest.raises(run.NoChip):
        run.find_devices(1)


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_alone_in_a_checkout_of_the_benchmark_files_fails(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    cannot run the program, so the run fails without a result."""
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300, cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
