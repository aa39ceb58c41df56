"""Tests of the benchmark's own code.  They run on the CPU backend:
``python -m pytest benchmarks/tests -q``.  Nothing here is a device
number."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import check, manifest, readers, reference, roofline, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


# -- the manifest and the loader ---------------------------------------------


def test_manifest_meets_the_contract_shape():
    m = manifest.load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert any(e["name"] == "setup_s" for e in m["end_to_end"])
    for w in m["workloads"]:
        cell = manifest.load_cell(w["name"])
        names = {e["name"] for e in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for spec in cell.per_layer:
            assert spec["reader"] in readers.READERS
            assert spec["moves"] in names


def test_loader_finds_config_mix_and_metric_by_name():
    m = manifest.load_manifest()
    assert manifest.load_config(m, "c1m-5k")["cluster"]["nodes"] == 5000
    assert manifest.load_traffic("stream")["loop"] == "open"
    assert manifest.load_metric("encode_ms_per_batch.tput")["reader"] \
        == "sample_mean"
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell("no-such.cell")


def test_a_new_cell_is_files_only(tmp_path):
    """A deployment, a mix and a metric over an existing sink key are
    added as files and manifest entries; no existing file is edited."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    m = manifest.load_manifest()
    cfg = manifest.load_config(m, "mock-10k")
    cfg["name"] = "mock-2k"
    cfg["cluster"]["nodes"] = 2000
    (root / "benchmarks/configs/mock-2k.json").write_text(json.dumps(cfg))
    mix = manifest.load_traffic("stream")
    mix["rate_per_s"] = 3.0
    (root / "benchmarks/traffic/trickle.json").write_text(json.dumps(mix))
    (root / "benchmarks/metrics/raft_apply_mean_ms.lat.json").write_text(
        json.dumps({"reader": "sample_mean", "key": "nomad.raft.apply"}))
    m["configs"].append({"name": "mock-2k", "source": "x",
                         "file": "benchmarks/configs/mock-2k.json",
                         "reduced": [], "why": "y"})
    m["workloads"].append({"name": "mock-2k.trickle", "config": "mock-2k",
                           "traffic": "trickle", "chips": 1, "why": "z"})
    for e in m["end_to_end"]:
        if "workloads" in e and "mock-10k.stream" in e["workloads"]:
            e["workloads"].append("mock-2k.trickle")
    m["per_layer"].append({
        "name": "raft_apply_mean_ms.lat", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "raft and WAL",
        "moves": "submit_to_placed_p50_ms", "workloads": ["mock-2k.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    cell = manifest.load_cell("mock-2k.trickle", root, root / "benchmarks")
    assert cell.config["cluster"]["nodes"] == 2000
    assert cell.traffic["rate_per_s"] == 3.0
    assert [s["name"] for s in cell.per_layer] == ["raft_apply_mean_ms.lat"]
    ctx = {"sink0": {"SampleTotals": {"nomad.raft.apply": (2, 4.0)}},
           "sink1": {"SampleTotals": {"nomad.raft.apply": (6, 16.0)}}}
    assert readers.read(ctx, cell.per_layer[0]) == pytest.approx(3.0)


# -- readers ------------------------------------------------------------------


def test_a_reader_with_nothing_to_read_returns_nothing():
    ctx = {"sink0": {"SampleTotals": {}, "CounterTotals": {}},
           "sink1": {"SampleTotals": {}, "CounterTotals": {}, "Gauges": {}},
           "client": {}, "harness": {}, "trace": None,
           "shapes": {"nodes": 10, "device_kind": "TPU v5 lite"}}
    for name in ("sample_mean", "counter_per_sample", "client_percentile",
                 "trace_busy_per_batch", "trace_roofline"):
        spec = {"reader": name, "key": "k", "per": "p", "field": "f",
                "q": 0.5, "program": "fused"}
        assert readers.read(ctx, spec) is None
    assert readers.percentile([1, 2, 3, 4], 0.5) == 2
    assert readers.percentile(list(range(1, 101)), 0.95) == 95


# -- the roofline ---------------------------------------------------------------


def test_roofline_on_known_shapes():
    work = roofline.placement_work(nodes=5000, specs=64, asks=64000)
    assert work["bytes"] == 64 * 5000 * 2 * 16 + 64000 * (8 + 32)
    assert work["ops"] == 64 * 5000 * roofline.OPS_PER_NODE_SCORE
    least = roofline.least_seconds(work, "TPU v5 lite")
    assert least["bound_by"] == "bytes"
    assert least["seconds"] == pytest.approx(work["bytes"] / 819e9)
    twice = roofline.placement_work(nodes=5000, specs=64, asks=64000, rounds=2)
    assert twice["ops"] == 2 * work["ops"]
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


# -- the trace reduction ------------------------------------------------------------


def test_trace_reduction_on_a_small_recorded_trace():
    rec = json.loads((DATA / "trace_small.json").read_text())
    loaded = {"devices": {p: {k: [tuple(e) for e in v] for k, v in d.items()}
                          for p, d in rec["devices"].items()}}
    red = trace.reduce(loaded, rec["t0"], rec["t1"],
                       [tuple(s) for s in rec["spans"]],
                       [tuple(o) for o in rec["outer"]])
    want = rec["expect"]
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    assert red["window_s"] == pytest.approx(rec["t1"] - rec["t0"])
    assert red["device_ops"][0][0] == want["top_op"]
    gaps = dict(red["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    for name, secs in want["gaps"].items():
        assert gaps[name] == pytest.approx(secs)
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


# -- the reference, its control and the comparison ---------------------------------


def _small_config(nodes=300):
    cfg = manifest.load_config(manifest.load_manifest(), "mock-10k")
    cfg["cluster"]["nodes"] = nodes
    return cfg


def _served_by(cfg, placer, n_jobs=40, count=10):
    cap = check.capacity(cfg)
    ask = check.ask_of(cfg)
    nodes = placer(cap, [ask] * n_jobs, [count] * n_jobs)
    return check.Served(jobs=[reference.PlacedJob(f"j{i}", ask, n)
                              for i, n in enumerate(nodes)])


def test_reference_placements_compare_correct():
    cfg = _small_config()
    served = _served_by(cfg, reference.greedy)
    compared = check.compare(served, cfg)
    assert compared["score_gap"]["value"] == 0.0
    assert compared["score_sum_rel"]["value"] == 0.0
    assert check.correct(compared)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_comes_out_not_correct(seed):
    """Upstream's candidate sampling in the reference's place: the widest
    score gap has to pass the limit."""
    cfg = _small_config()
    served = _served_by(cfg, reference.greedy)
    served.jobs = check.control_jobs(cfg, served.jobs, seed)
    compared = check.compare(served, cfg)
    assert compared["score_gap"]["value"] > 3 * compared["score_gap"]["limit"]
    assert not check.correct(compared)


def test_one_by_one_twin_agrees_with_the_rounds():
    cfg = _small_config(nodes=12)
    cap, ask = check.capacity(cfg), check.ask_of(cfg)
    fast = reference.greedy(cap, [ask] * 3, [10] * 3)
    used = np.zeros_like(cap)
    for nodes in fast:
        slow = reference._greedy_one_by_one(cap, used, ask, 10)
        assert sorted(slow.tolist()) == sorted(nodes.tolist())
        np.add.at(used, nodes, ask)


def test_exact_checks_catch_capacity_and_counts():
    cfg = _small_config(nodes=20)
    ask = check.ask_of(cfg)
    crowd = reference.PlacedJob("crowd", ask, np.zeros(8, dtype=np.int64))
    served = check.Served(jobs=[crowd], wrong_count=1)
    compared = check.compare(served, cfg)
    assert compared["nodes_over_capacity"]["value"] == 1
    assert compared["job_mates_on_one_node"]["value"] == 7
    assert compared["evals_wrong_count"]["value"] == 1
    assert not check.correct(compared)


# -- a whole run at a tiny size, on the CPU backend ------------------------------------


def _dry(workload, *extra, env=None):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload",
         workload, "--seed", "2147483659", "--seconds", "3",
         "--dry-run-cpu", *extra],
        cwd=str(ROOT), capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


@pytest.mark.parametrize("workload", ["c1m-5k.bulk", "mock-10k.stream"])
def test_dry_run_never_prints_device_metrics(workload):
    proc = _dry(workload)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == ""          # no result line off the chip
    assert "correct: True" in proc.stderr
    assert "NOT a chip result" in proc.stderr
    for word in ("busy_s", "placed_per_s", "roofline"):
        assert word not in proc.stderr


def test_run_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload",
         "c1m-5k.bulk", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=str(ROOT), capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("workload", ["c1m-5k.bulk", "mock-10k.stream"])
@pytest.mark.parametrize("fault,caught_by", [
    ("answer_altered", "score_gap"),
    ("half_left_out", "evals_wrong_count"),
    ("state_unchanged", "evals_wrong_count"),
])
def test_a_broken_timed_path_comes_out_not_correct(fault, caught_by, workload):
    """The rest of a run with the timed path broken underneath (the plan
    each eval submits is altered on its way to the plan queue), in each
    cell: a standing backlog of full batches and a stream of small ones."""
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve().parent / "faulty_run.py"),
         fault, workload], cwd=str(ROOT), capture_output=True, text=True,
        timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 1, proc.stderr[-3000:]
    assert "correct: False" in proc.stderr
    over = [ln for ln in proc.stderr.splitlines() if "<-- OVER" in ln]
    assert any(caught_by in ln for ln in over), over
