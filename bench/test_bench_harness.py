"""The harness without a chip: files found by name, the contract's
character rules, FLOP and byte counts by hand, the trace reducer, and a
run that finds no TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import common, flops, trace
from bench.readers import Run

ROOT = common.ROOT
BENCH = common.benchmark()
TESTDATA = Path(__file__).resolve().parent / "testdata"


def test_every_file_loads_by_name():
    for c in BENCH["configs"]:
        cfg = common.load_config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert len(cfg["source"]) <= 200
        assert set(c["reduced"]) <= set(cfg) and set(cfg["reduced"]) == \
            set(c["reduced"])
    for w in BENCH["workloads"]:
        wl = common.load_workload(w["name"])
        assert wl["config"] == w["config"] and wl["chips"] == w["chips"]
        assert (ROOT / "bench" / "drivers" / f"{wl['driver']}.py").is_file()
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    for m in BENCH["per_layer"]:
        reader = common.load_metric(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
            (m["layer"], m["unit"], m["moves"])


@pytest.mark.parametrize("kind", ["configs", "workloads", "metrics"])
def test_every_file_under_bench_loads(kind):
    """Files a later cell can name: each loads by its name, and its name
    keeps the character rule."""
    load = {"configs": common.load_config, "workloads": common.load_workload,
            "metrics": common.load_metric}[kind]
    files = sorted((ROOT / "bench" / kind).iterdir())
    names = [p.name[:-len(p.suffix)] for p in files
             if p.suffix in (".json", ".py")]
    assert names
    for n in names:
        assert load(common.check_name(n))


def test_names_units_and_keys_keep_the_rules():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"]: w for w in BENCH["workloads"]}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in cells.values()]:
        common.check_name(n)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        common.check_unit(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            reports = e2e[m["moves"]].get("workloads", cells)
            assert w in cells and w in reports
    for w in cells.values():
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert os.path.getsize(ROOT / "BENCHMARK.json") <= 64 * 1024


@pytest.mark.parametrize("name,ok", [
    ("flock191-fedbuff-tfm.m10", True), ("a_b.c-d", True),
    ("has space", False), ("a/b", False), ("a,b", False), (".lead", False),
    ("x" * 65, False), ("µs", False)])
def test_name_rule(name, ok):
    if ok:
        assert common.check_name(name) == name
    else:
        with pytest.raises(common.BenchError):
            common.check_name(name)


def test_a_new_entry_is_found_by_name_without_editing(tmp_path):
    """A configuration, a cell and a metric added as new files plus new
    `BENCHMARK.json` entries load; no file that was there changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((ROOT / "bench/configs/flock191-fedbuff-tfm.json")
                     .read_text())
    cfg["scheduler"]["params"]["M"] = 96
    (tmp_path / "bench/configs/toy-cfg.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/workloads/toy-cfg.m96.json").write_text(json.dumps(
        {"config": "toy-cfg", "driver": "federation", "chips": 1}))
    (tmp_path / "bench/metrics/toy.events.py").write_text(
        'LAYER = "fl.engine"\nUNIT = "count"\n'
        'MOVES = "sim_windows_per_s"\n\n\n'
        'def read(run):\n    return len(run.record["events"]) or None\n')
    bench["configs"].append({"name": "toy-cfg", "source": "x",
                             "file": "bench/configs/toy-cfg.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "toy-cfg.m96", "config": "toy-cfg",
                               "traffic": "m96", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "toy.events", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": "fl.engine",
                               "moves": "sim_windows_per_s",
                               "workloads": ["toy-cfg.m96"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert common.load_workload("toy-cfg.m96", tmp_path)["config"] == \
        "toy-cfg"
    assert common.load_config("toy-cfg", tmp_path)["scheduler"][
        "params"]["M"] == 96
    names = [m["name"] for m in
             common.metrics_for("toy-cfg.m96", "per_layer", tmp_path)]
    assert names == ["toy.events"]
    reader = common.load_metric("toy.events", tmp_path)
    run = Run("toy-cfg.m96", {"events": [3, 4]}, None, {})
    assert reader.read(run) == 2
    for p, data in before.items():
        assert p.read_bytes() == data


def test_peaks_are_keyed_by_device_kind():
    p = common.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(common.BenchError):
        common.peaks("cpu")


def test_payload_flops_by_hand():
    """A payload of 8 features as 2 tokens of 4, d 4, 1 layer, 2 heads of
    2 over 1 kv head, d_ff 6, 3 classes."""
    P = flops.Payload(features=8, seq=2, d_model=4, layers=1, heads=2,
                      kv_heads=1, d_ff=6, classes=3)
    embed = 2 * 2 * 4 * 4                  # (2 tokens x 4) @ (4 x 4)
    q = 2 * 2 * 4 * 4                      # (2 x 4) @ (4 x 2 heads * 2)
    kv = 2 * (2 * 2 * 4 * 2)               # k and v: (2 x 4) @ (4 x 2)
    o = 2 * 2 * 4 * 4                      # (2 x 4) @ (4 x 4)
    attn = 2 * (2 * 3 * 2 * 2)             # scores and values: 3 pairs
    ffn = 3 * (2 * 2 * 4 * 6)              # gate, up, down
    head = 2 * 4 * 3                       # last position only
    fwd = embed + q + kv + o + attn + ffn + head
    assert P.forward_flops() == fwd
    assert P.train_flops(5) == 3 * 5 * fwd
    f, b = P.flash_forward(batch=3)
    assert f == 3 * 2 * 2 * 3 * 2 * 2
    assert b == 4 * 3 * 2 * 2 * (2 * 2 + 2 * 1)


def test_agg_kernel_counts_by_hand():
    assert flops.agg_call(3, 10) == (60, 4 * (30 + 20 + 3))
    assert flops.agg_event(2, [5, 7]) == (2 * 2 * 12,
                                          4 * (10 + 10 + 2 + 14 + 14 + 2))
    share, bound = flops.roofline_share(1e9, 819e6, 2e-3, 197e12, 819e9)
    assert bound == "memory" and share == pytest.approx(50.0)


def test_reducer_reads_the_recorded_trace():
    t = trace.load(str(TESTDATA / "cpu_window.xplane.pb"))
    names = [n for n, _, _ in t.spans]
    assert names.count("data.gather") == 2
    assert names.count("client.train") == 2
    assert t.window_s > 0 and t.window == max(
        ((s, e) for n, s, e in t.spans if n == "window"),
        key=lambda w: w[1] - w[0])
    assert t.devices == [] and t.busy_s() == 0.0


def test_reducer_interval_arithmetic():
    t = trace.Trace(window=(0.0, 10.0),
                    ops={0: [("a", 1.0, 3.0), ("b", 2.0, 4.0),
                             ("a", 9.0, 12.0)]},
                    modules={0: [("jit_update_many", 1.0, 4.0)]},
                    spans=[("data.gather", 4.0, 6.0),
                           ("client.train", 5.0, 9.0),
                           ("window", 0.0, 10.0)])
    assert t.busy_s() == pytest.approx(4.0)          # [1, 4] and [9, 10]
    assert t.op_seconds(lambda n: n == "a") == pytest.approx(3.0)
    assert t.module_seconds(lambda n: "update_many" in n) == \
        pytest.approx(3.0)
    assert t.top_ops() == [["a", pytest.approx(3.0)],
                           ["b", pytest.approx(2.0)]]
    gaps = dict(t.idle_gaps())
    # idle [0, 1] and [4, 9]; on [5, 6] both spans cover the gap and the
    # shorter (innermost) one takes it
    assert gaps == {"host": pytest.approx(1.0),
                    "data.gather": pytest.approx(2.0),
                    "client.train": pytest.approx(3.0)}


def test_readers_return_nothing_without_a_trace():
    run = Run("flock191-fedbuff-tfm.m10",
              {"events": [10], "window": (0.0, 1.0), "spans": [],
               "leaf_sizes": [5]}, None, common.peaks("TPU v5 lite"))
    for name in ("device_idle.fl", "agg_roofline", "flash_attention_roofline",
                 "client_train.device_share", "data.gather_share"):
        assert common.load_metric(name).read(run) is None


def _run_cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_a_run_without_a_tpu_prints_no_result():
    p = _run_cli(ROOT, "--workload", "flock191-fedspace-tfm.replan",
                 "--seed", "2147483659", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path, "--workload", "flock191-fedbuff-tfm.m10",
                 "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
