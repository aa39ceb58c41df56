"""Tests of the benchmark's own code.  They run on the CPU backend:
``python -m pytest benchmarks/tests -q``.  Nothing here is a device
number."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import check, manifest, readers, reference, roofline, trace  # noqa: E402
from benchmarks.deployments import uniform  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import files_only  # noqa: E402

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


def test_every_configuration_has_a_whole_deployment_module():
    m = manifest.load_manifest()
    for entry in m["configs"]:
        cfg = manifest.load_config(m, entry["name"])
        assert "deployment" not in cfg            # the standing two: uniform
        assert manifest.load_deployment(cfg) is uniform
    for name in manifest.DEPLOYMENT_API:
        assert callable(getattr(uniform, name))


@pytest.mark.parametrize("workload,config,named,says", [
    ("c1m-5k.bulk", "c1m-5k", "nowhere", "no .*deployments/nowhere.py"),
    ("mock-10k.stream", "mock-10k", "partial", "lacks .*compare"),
])
def test_a_deployment_that_is_not_there_or_not_whole_is_refused_at_once(
        tmp_path, workload, config, named, says):
    """By ``manifest.load_cell``, before JAX starts and before any agent is
    up: a commit that cannot run a configuration fails at once, it never
    hangs."""
    with pytest.raises(manifest.ManifestError, match="no-such"):
        manifest.load_deployment({"name": "x", "deployment": "no-such"})
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    whole = (root / "benchmarks/deployments/uniform.py").read_text()
    (root / "benchmarks/deployments/partial.py").write_text(
        whole.replace("def compare(", "def compare_(")
        .replace("def shrink(", "def shrink_("))
    m = manifest.load_manifest()
    cfg = manifest.load_config(m, config)
    cfg["deployment"] = named
    (root / f"benchmarks/configs/{config}.json").write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(root / "benchmarks/run.py"), "--workload",
         workload, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=str(root), capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=str(ROOT)))       # the program, for run.py's import
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert re.search(says, proc.stderr), proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "device:" not in proc.stderr           # no backend was started
    assert time.monotonic() - t0 < 20.0


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


def _ask_of(cfg):
    t = cfg["jobs"]["task"]
    return np.asarray([t["cpu"], t["memory_mb"], t["ephemeral_disk_mb"]],
                      dtype=np.float64)


def _served_by(cfg, placer, n_jobs=40, count=10):
    cap = uniform.capacity(cfg)
    ask = _ask_of(cfg)
    nodes = placer(cap, [ask] * n_jobs, [count] * n_jobs)
    return check.Served(jobs=[reference.PlacedJob(f"j{i}", ask, n)
                              for i, n in enumerate(nodes)])


def test_reference_placements_compare_correct():
    cfg = _small_config()
    served = _served_by(cfg, reference.greedy)
    compared = uniform.compare(served, cfg)
    assert compared["score_gap"]["value"] == 0.0
    assert compared["score_sum_rel"]["value"] == 0.0
    assert check.correct(compared)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_comes_out_not_correct(seed):
    """Upstream's candidate sampling in the reference's place: the widest
    score gap has to pass the limit."""
    cfg = _small_config()
    served = _served_by(cfg, reference.greedy)
    served.jobs = uniform.control_jobs(cfg, served.jobs, seed)
    compared = uniform.compare(served, cfg)
    assert compared["score_gap"]["value"] > 3 * compared["score_gap"]["limit"]
    assert not check.correct(compared)


def test_one_by_one_twin_agrees_with_the_rounds():
    cfg = _small_config(nodes=12)
    cap, ask = uniform.capacity(cfg), _ask_of(cfg)
    fast = reference.greedy(cap, [ask] * 3, [10] * 3)
    used = np.zeros_like(cap)
    for nodes in fast:
        slow = reference._greedy_one_by_one(cap, used, ask, 10)
        assert sorted(slow.tolist()) == sorted(nodes.tolist())
        np.add.at(used, nodes, ask)


def test_exact_checks_catch_capacity_and_counts():
    cfg = _small_config(nodes=20)
    ask = _ask_of(cfg)
    crowd = reference.PlacedJob("crowd", ask, np.zeros(8, dtype=np.int64))
    served = check.Served(jobs=[crowd], wrong_count=1)
    compared = uniform.compare(served, cfg)
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


# -- a deployment that is not one node shape x one job shape ------------------------


def _frozen_reference():
    """The parent's ``reference.py``, kept with the tests, not in the
    harness: what the rows must leave bit for bit where no job has one."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "reference_parent", DATA / "reference_parent.py")
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def _random_fleet(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 120))
    shapes = np.asarray([[3900.0, 7936.0, 98304.0], [15900.0, 65280.0, 405504.0],
                         [7900.0, 16128.0, 200704.0]])
    cap = shapes[rng.integers(0, 3, size=n)] if seed % 2 else \
        np.tile(shapes[0], (n, 1))
    n_jobs = int(rng.integers(5, 40))
    asks = [np.asarray([rng.integers(1, 12) * 50.0, rng.integers(1, 12) * 64.0,
                        rng.integers(1, 8) * 50.0]) for _ in range(n_jobs)]
    # Counts on both sides of the fleet's size: the rounds and the
    # one-by-one path, nodes that fill up and jobs that stay short.
    counts = [int(rng.integers(1, 2 * n)) for _ in range(n_jobs)]
    return cap, asks, counts


@pytest.mark.parametrize("seed", range(1, 9))
def test_without_rows_the_reference_is_the_parents_bit_for_bit(seed):
    old = _frozen_reference()
    cap, asks, counts = _random_fleet(seed)
    none = [None] * len(asks)
    placed = reference.greedy(cap, asks, counts, feasible=none, distinct=none)
    was = old.greedy(cap, asks, counts)
    assert len(placed) == len(was)
    for a, b in zip(placed, was):
        assert a.dtype == b.dtype and a.tolist() == b.tolist()
    limit = reference.candidate_limit(cap.shape[0])
    assert limit == old.candidate_limit(cap.shape[0])
    sampled = reference.greedy(cap, asks, counts, candidates=limit, seed=seed,
                               feasible=none, distinct=none)
    for a, b in zip(sampled, old.greedy(cap, asks, counts, candidates=limit,
                                        seed=seed)):
        assert a.tolist() == b.tolist()
    for nodes in (placed, sampled):
        new = reference.replay(cap, [reference.PlacedJob(str(i), a, n) for i, (a, n)
                                     in enumerate(zip(asks, nodes))])
        ref = old.replay(cap, [old.PlacedJob(str(i), a, n) for i, (a, n)
                               in enumerate(zip(asks, nodes))])
        assert (new.widest_gap, new.worst_job, new.infeasible, new.repeated) \
            == (ref.widest_gap, ref.worst_job, ref.infeasible, ref.repeated)
        assert new.used.tobytes() == ref.used.tobytes() and new.shared == 0
        assert reference.scorefit_sum(new.used, cap) \
            == old.scorefit_sum(ref.used, cap)


def test_the_uniform_control_is_the_parents_bit_for_bit():
    old = _frozen_reference()
    cfg = _small_config()
    served = _served_by(cfg, reference.greedy)
    cap = uniform.capacity(cfg)
    was = old.greedy(cap, [j.ask for j in served.jobs],
                     [len(j.nodes) for j in served.jobs], seed=5,
                     candidates=old.candidate_limit(cap.shape[0]))
    now = uniform.control_jobs(cfg, served.jobs, 5)
    assert [j.nodes.tolist() for j in now] == [n.tolist() for n in was]
    assert [j.key for j in now] == [j.key for j in served.jobs]


def test_a_feasibility_row_and_a_distinct_row_bind():
    cap = np.tile(np.asarray([1000.0, 1000.0, 1000.0]), (8, 1))
    ask = np.asarray([100.0, 100.0, 100.0])
    used0 = np.zeros_like(cap)
    used0[:, :2] = np.arange(8)[:, None] * 100.0      # node 7 scores best
    row = np.asarray([True] * 6 + [False] * 2)        # ... and is excluded
    racks = np.asarray([0, 0, 1, 1, 2, 2, 3, 3])
    hosts = np.arange(8)
    # The feasibility row: the best admitted nodes, never an excluded one.
    (nodes,) = reference.greedy(cap, [ask], [3], used0=used0, feasible=[row])
    assert sorted(nodes.tolist()) == [3, 4, 5]
    job = reference.PlacedJob("j", ask, np.asarray([7, 4, 5]), feasible=row)
    assert reference.replay(cap, [job], used0=used0).infeasible == 1
    # distinct_property: the best node of every rack, the best racks first;
    # a count beyond the racks is placed as far as they reach.
    (nodes,) = reference.greedy(cap, [ask], [3], used0=used0, distinct=[racks])
    assert nodes.tolist() == [7, 5, 3]
    (nodes,) = reference.greedy(cap, [ask], [6], used0=used0, distinct=[racks])
    assert nodes.tolist() == [7, 5, 3, 1]
    good = reference.PlacedJob("j", ask, np.asarray([7, 5, 3]), distinct=racks)
    rep = reference.replay(cap, [good], used0=used0)
    assert (rep.widest_gap, rep.shared, rep.repeated) == (0.0, 0, 0)
    worse = reference.PlacedJob("j", ask, np.asarray([6, 5, 3]), distinct=racks)
    assert reference.replay(cap, [worse], used0=used0).widest_gap > 0.1
    mates = reference.PlacedJob("j", ask, np.asarray([7, 6, 3]), distinct=racks)
    rep = reference.replay(cap, [mates], used0=used0)
    assert (rep.shared, rep.repeated) == (1, 0)
    # Hard distinct_hosts: a second job-mate on a node counts even when
    # fresh nodes have run out, and the twin stops at the nodes there are.
    (nodes,) = reference.greedy(cap, [ask], [10], used0=used0, distinct=[hosts])
    assert sorted(nodes.tolist()) == list(range(8))
    crowd = reference.PlacedJob("j", ask, np.asarray(list(range(8)) + [7, 7]),
                                distinct=hosts)
    rep = reference.replay(cap, [crowd], used0=used0)
    assert (rep.repeated, rep.shared) == (2, 2)
    soft = reference.PlacedJob("j", ask, np.asarray(list(range(8)) + [7, 7]))
    assert reference.replay(cap, [soft], used0=used0).repeated == 0
    # The control keeps to the rows too.
    for seed in range(5):
        (nodes,) = reference.greedy(cap, [ask], [4], used0=used0, seed=seed,
                                    candidates=2, feasible=[row],
                                    distinct=[racks])
        assert row[nodes].all() and len(set(racks[nodes])) == len(nodes) == 3
    # With the rows of distinct_hosts and room for all, the choice is the
    # one the soft penalty makes: the same nodes, the same gap.
    (a,) = reference.greedy(cap, [ask], [5], used0=used0, distinct=[hosts])
    (b,) = reference.greedy(cap, [ask], [5], used0=used0)
    assert a.tolist() == b.tolist()


def test_reserved_counts_as_used_where_nodes_differ():
    """Upstream's ScoreFit: on one small node and one large, which of them
    a small ask fills better depends on what each holds back."""
    cap = np.asarray([[900.0, 900.0, 1e5], [3600.0, 3600.0, 1e5]])
    held = np.asarray([[100.0, 100.0, 0.0], [1800.0, 1800.0, 0.0]])
    ask = np.asarray([90.0, 90.0, 10.0])
    (plain,) = reference.greedy(cap, [ask], [1])
    (with_it,) = reference.greedy(cap, [ask], [1], reserved=held)
    assert plain.tolist() == [0] and with_it.tolist() == [1]
    job = reference.PlacedJob("j", ask, plain)
    assert reference.replay(cap, [job]).widest_gap == 0.0
    assert reference.replay(cap, [job], reserved=held).widest_gap > 1.0
    same = np.tile(cap[:1], (4, 1))
    used0 = np.zeros_like(same)
    used0[:, :2] = np.asarray([0.0, 300.0, 100.0, 200.0])[:, None]
    (a,) = reference.greedy(same, [ask], [4], used0=used0)
    (b,) = reference.greedy(same, [ask], [4], used0=used0,
                            reserved=np.tile(held[:1], (4, 1)))
    assert a.tolist() == b.tolist() == [1, 3, 2, 0]


@pytest.fixture(scope="module")
def tree_with_pools3(tmp_path_factory):
    """A copy of the tree to which the test deployment was added as a later
    PR adds one: files and manifest entries only.  Its configuration file
    says one evaluation to a batch: the size at which the sequential
    replay is a sound reference for ``distinct_property`` (PERF.md
    section 7 row 0)."""
    root = tmp_path_factory.mktemp("files_only") / "repo"
    before = files_only.build(root, batch_size=1)
    return root, before


def _in_tree(root, *argv):
    return subprocess.run(
        [sys.executable, *argv], cwd=str(root), capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_a_new_deployment_is_files_only(tree_with_pools3):
    """Three node pools that differ in capacity, class, attributes and
    meta; three job templates with asks and counts of their own: one
    unconstrained, one with a ``version`` constraint and
    ``distinct_hosts``, one with ``distinct_property``.  It goes through
    ``run.py`` in a copy of the tree in which no file that was there has
    changed."""
    root, before = tree_with_pools3
    after = files_only.digests(root)
    assert {p: after.get(p) for p in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmarks/configs/pools3.json", "benchmarks/deployments/pools3.py"]
    was = manifest.load_manifest()
    now = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(was[key], now[key]):      # entries added, none edited
            old, new = dict(old), dict(new)
            assert new.pop("workloads", [])[:len(old.get("workloads", []))] \
                == old.pop("workloads", [])
            assert old == new
    proc = _in_tree(root, str(root / "benchmarks/run.py"), "--workload",
                    files_only.CELL, "--seed", "2147483659", "--seconds", "3",
                    "--dry-run-cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "correct: True" in proc.stderr
    compared = dict(re.findall(r"^compared (\w+): (\S+) ", proc.stderr, re.M))
    assert float(compared["score_gap"]) == 0.0
    assert compared["job_mates_in_one_distinct_group"] == "0"
    assert float(compared["oracle_routed"]) == 0.0
    assert "score_sum_rel" not in compared         # the module's own compare
    counts = json.loads(re.search(r"^counts: (.*)$", proc.stderr, re.M).group(1))
    assert counts["jobs_checked"] == 24 and counts["drained"]
    # Each eval counts for what its own job wants: 14 plain x 30 + 5
    # versioned x 12 + 5 racked x 8, less the two or three set-up saw done.
    assert counts["attempted"] in (21, 22)
    assert counts["placed"] in range(520 - 3 * 30, 520 - 2 * 8 + 1)
    assert {p: files_only.digests(root).get(p) for p in before} == before


@pytest.mark.parametrize("fault,caught_by", [
    ("constraint_broken", "infeasible_allocs"),
    ("mates_on_one_node", "job_mates_on_one_node"),
])
def test_a_planted_fault_on_the_new_deployment_is_caught(
        tree_with_pools3, fault, caught_by):
    """An allocation moved to a node its ``version`` constraint excludes;
    two job-mates on one node under ``distinct_hosts``."""
    root, _ = tree_with_pools3
    proc = _in_tree(root, str(root / "benchmarks/tests/faulty_run.py"), fault,
                    files_only.CELL)
    assert proc.returncode == 1, proc.stderr[-3000:]
    assert "correct: False" in proc.stderr
    over = [ln for ln in proc.stderr.splitlines() if "<-- OVER" in ln]
    assert any(caught_by in ln for ln in over), over


def test_the_new_deployment_at_a_cells_batch_size_is_legal_not_yet_correct(
        tmp_path):
    """The same deployment with its configuration file as it lies under
    ``data`` (``batch_size`` 64, which the dry run caps at 4).  Every
    exact check reads 0 on every run.  ``score_gap`` reads 0.0 or 2 to 5
    by how the batches fell (seed 14: over in 2 runs of 2, seed 13 in 1 of
    2): the fused pass is round-major, so a batch that holds a
    multi-round ``distinct_property`` spec is not placed in the order the
    sequential reference replays.  An expected failure until PERF.md
    section 7 row 0 is settled; it proves the plumbing, not the
    reference, at more than one evaluation to a batch."""
    root = tmp_path / "repo"
    files_only.build(root)
    proc = _in_tree(root, str(root / "benchmarks/run.py"), "--workload",
                    files_only.CELL, "--seed", "14", "--seconds", "3",
                    "--dry-run-cpu")
    assert proc.returncode in (0, 1), proc.stderr[-3000:]
    over = [ln.split()[1] for ln in proc.stderr.splitlines()
            if "<-- OVER" in ln]
    assert set(over) <= {"score_gap:"}, over        # legal placements, always
    if over:
        assert "correct: False" in proc.stderr
        pytest.xfail("PERF.md section 7 row 0: round-major batches against "
                     "a sequential reference")
    assert "correct: True" in proc.stderr


def test_a_shrink_that_changes_how_the_server_runs_is_refused(tmp_path):
    """A dry run cuts the fleet and the jobs; ``server`` is the
    configuration file's to say.  Refused by ``manifest.shrunk`` before
    JAX starts."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    whole = (root / "benchmarks/deployments/uniform.py").read_text()
    (root / "benchmarks/deployments/meddles.py").write_text(whole.replace(
        '    return config\n',
        '    config["server"]["batch_size"] = 1\n    return config\n'))
    m = manifest.load_manifest()
    cfg = manifest.load_config(m, "c1m-5k")
    cfg["deployment"] = "meddles"
    (root / "benchmarks/configs/c1m-5k.json").write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    proc = subprocess.run(
        [sys.executable, str(root / "benchmarks/run.py"), "--workload",
         "c1m-5k.bulk", "--seed", "1", "--seconds", "1", "--dry-run-cpu"],
        cwd=str(root), capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT)))
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "shrink changed `server`" in proc.stderr
    assert proc.stdout.strip() == "" and "device:" not in proc.stderr

