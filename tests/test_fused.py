"""Fused on-device score-and-commit tests (PR 6 tentpole).

The fused single-dispatch program (kernels.fused_pass) and the CPU
GenericScheduler oracle must agree on per-job placement counts with no
node overcommitted, across randomized clusters/jobs under a pinned
tie-break seed (NOMAD_TPU_RNG_SEED); scores stay within the
quantization bound, which is 0: quantization is exact-or-absent.
Plus: the single-transfer contract (exactly one
``batch.fetch`` span per fused batch), the narrow-dtype xfer codec, the
quantizer's exactness guarantees, and the chaos path — a corrupted
fused result buffer trips the breaker, the oracle carries the batch,
and a clean half-open probe restores the fused path.
"""
import random

import numpy as np
import pytest

from nomad_tpu import fault, mock
from nomad_tpu.ops import encode, resident, xfer
from nomad_tpu.ops.batch_sched import TPUBatchScheduler
from nomad_tpu.ops.breaker import KernelCircuitBreaker
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.generic import GenericScheduler
from nomad_tpu.structs import structs as s
from nomad_tpu.utils import tracing


def make_node(rng=None):
    node = mock.node()
    node.resources.networks = []
    node.reserved.networks = []
    if rng is not None:
        node.resources.cpu = rng.choice([2000, 4000, 8000])
        node.resources.memory_mb = rng.choice([4096, 8192, 16384])
    node.compute_class()
    return node


def make_job(count, rng=None):
    job = mock.job()
    job.task_groups[0].count = count
    for tg in job.task_groups:
        for t in tg.tasks:
            t.resources.networks = []
            if rng is not None:
                t.resources.cpu = rng.choice([100, 250, 500])
                t.resources.memory_mb = rng.choice([64, 256, 512])
    return job


def reg_eval(job):
    return s.Evaluation(
        id=s.generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
        status=s.EVAL_STATUS_PENDING)


def build_twin_problem(seed, n_nodes=24, n_jobs=4):
    """Two harnesses over identical fleets + identical jobs (shared job
    objects are immutable snapshots by store convention)."""
    rng = random.Random(seed)
    nodes = [make_node(rng) for _ in range(n_nodes)]
    jobs = [make_job(rng.randint(1, 4), rng) for _ in range(n_jobs)]
    harnesses = []
    for _ in range(2):
        h = Harness()
        for node in nodes:
            h.state.upsert_node(h.next_index(), node.copy())
        for job in jobs:
            h.state.upsert_job(h.next_index(), job)
        harnesses.append(h)
    return harnesses[0], harnesses[1], jobs


def placements_by_spec(h, jobs):
    """(job, tg) → sorted node ids of live allocs (the bit-identity
    comparison basis: same kernel ⇒ same multiset of slots)."""
    out = {}
    for job in jobs:
        for a in h.state.allocs_by_job(None, job.id, True):
            if a.terminal_status():
                continue
            out.setdefault((job.id, a.task_group), []).append(a.node_id)
    return {k: sorted(v) for k, v in out.items()}


def node_usage(h):
    used = {}
    for node in h.state.nodes(None):
        cpu = mem = 0
        for a in h.state.allocs_by_node(None, node.id):
            if a.terminal_status():
                continue
            if a.resources is not None:
                cpu += a.resources.cpu
                mem += a.resources.memory_mb
            else:
                cpu += sum(t.cpu for t in a.task_resources.values())
                mem += sum(t.memory_mb for t in a.task_resources.values())
        used[node.id] = (cpu, mem, node.resources.cpu,
                         node.resources.memory_mb)
    return used


def run_batch(h, jobs, monkeypatch, seed=1234, breaker=None):
    monkeypatch.setenv("NOMAD_TPU_RNG_SEED", str(seed))
    for j in jobs:
        if h.state.job_by_id(None, j.id) is None:
            h.state.upsert_job(h.next_index(), j)
    kw = {"breaker": breaker} if breaker is not None else {}
    sched = TPUBatchScheduler(h.logger, h.snapshot(), h, **kw)
    return sched.schedule_batch([reg_eval(j) for j in jobs])


# -- xfer narrow dtypes -------------------------------------------------------

class TestXferNarrowDtypes:
    def test_host_roundtrip(self):
        arrays = {
            "a16": np.arange(-6, 6, dtype=np.int16).reshape(3, 4),
            "u16": np.array([0, 1, 65535], dtype=np.uint16),
            "a8": np.arange(-8, 8, dtype=np.int8),
            "mix32": np.arange(5, dtype=np.int32),
            "f": np.linspace(0, 1, 7, dtype=np.float32),
        }
        buf, meta = xfer.pack_host(arrays)
        out = xfer.unpack_host(buf, meta)
        for name, arr in arrays.items():
            np.testing.assert_array_equal(out[name], arr)

    def test_device_unpack_matches_host(self):
        import jax
        import jax.numpy as jnp

        arrays = {
            "q": np.array([[1, -2], [32767, -32768]], dtype=np.int16),
            "b": np.array([7, 250], dtype=np.uint16),
            "s": np.array([-128, 127, 3], dtype=np.int8),
        }
        buf, meta = xfer.pack_host(arrays)
        dev = jax.jit(
            lambda b: tuple(xfer.unpack_device(b, meta).values()))(
                jnp.asarray(buf))
        names = [m[0] for m in meta]
        for name, arr in zip(names, dev):
            np.testing.assert_array_equal(np.asarray(arr), arrays[name])
            assert np.asarray(arr).dtype == arrays[name].dtype

    def test_device_pack_roundtrip(self):
        import jax
        import jax.numpy as jnp

        arrays = {
            "slots": np.arange(12, dtype=np.uint16).reshape(2, 6),
            "sum": np.array([3, 9], dtype=np.int32),
        }

        @jax.jit
        def pack():
            buf, _ = xfer.pack_device(
                {k: jnp.asarray(v) for k, v in arrays.items()})
            return buf

        meta = xfer.layout({k: (xfer._tag(v.dtype), v.shape)
                            for k, v in arrays.items()})
        out = xfer.unpack_host(np.asarray(pack()), meta)
        for name, arr in arrays.items():
            np.testing.assert_array_equal(out[name], arr)


# -- quantizer ----------------------------------------------------------------

class TestQuantizeResourceRows:
    def test_int8_via_per_dim_scales(self):
        # ISSUE 13: every dimension here divides down into the int8
        # range (4000/32, 8192/128, 102400/1024, 150/2), so BOTH
        # matrices ship int8 under per-matrix, per-dimension scales —
        # this exact shape used to ride int16 under the shared codebook.
        cap = np.tile(np.array([4000, 8192, 102400, 150]), (16, 1))
        used = np.tile(np.array([120, 512, 0, 0]), (16, 1))
        q = encode.quantize_resource_rows(cap, used)
        assert q is not None and q.cap_tag == "i8" and q.used_tag == "i8"
        assert q.tag == "i8"
        assert q.scale.shape == (2, 4)
        assert q.scale[0].tolist() == [32, 128, 1024, 2]
        np.testing.assert_array_equal(
            encode.dequantize_rows(q.cap_q, q.scale[0]), cap)
        np.testing.assert_array_equal(
            encode.dequantize_rows(q.used_q, q.scale[1]), used)

    def test_int16_when_int8_divisibility_fails(self):
        # disk (102404) divides by 4 (int16 range) but not by the 1024
        # the int8 range needs → that dimension stays int16-scaled and
        # the capacity matrix ships int16; the all-zero used matrix
        # still rides int8 independently (per-matrix dtypes).
        cap = np.tile(np.array([4000, 8192, 102404, 150]), (16, 1))
        used = np.zeros((16, 4), dtype=np.int64)
        q = encode.quantize_resource_rows(cap, used)
        assert q is not None and q.cap_tag == "i16" and q.used_tag == "i8"
        assert q.tag == "i16"
        assert q.scale[0].tolist() == [32, 128, 4, 2]
        np.testing.assert_array_equal(
            encode.dequantize_rows(q.cap_q, q.scale[0]), cap)
        np.testing.assert_array_equal(
            encode.dequantize_rows(q.used_q, q.scale[1]), used)

    def test_int8_when_ranges_allow(self):
        cap = np.tile(np.array([100, 120, 64, 50]), (4, 1))
        used = np.zeros((4, 4), dtype=np.int64)
        q = encode.quantize_resource_rows(cap, used)
        assert q is not None and q.tag == "i8"
        np.testing.assert_array_equal(
            encode.dequantize_rows(q.cap_q, q.scale[0]), cap)

    def test_non_divisible_refuses(self):
        # 100001 needs scale 4 but is odd — exactness impossible, so the
        # quantizer must refuse rather than round.
        cap = np.tile(np.array([4000, 8192, 100001, 150]), (4, 1))
        used = np.zeros((4, 4), dtype=np.int64)
        assert encode.quantize_resource_rows(cap, used) is None

    def test_roundtrip_guard_catches_corruption(self):
        resident.reset_counters()
        cap = np.tile(np.array([4000, 8192, 102400, 150]), (8, 1))
        q = encode.quantize_resource_rows(cap, np.zeros_like(cap))
        brk = KernelCircuitBreaker(threshold=0.9, window=8, min_checks=1,
                                   cooldown=3600.0)
        assert resident.check_quant_roundtrip(cap, q.cap_q, q.scale[0],
                                              breaker=brk)
        bad = np.array(q.cap_q)
        bad[2, 1] += 3
        assert not resident.check_quant_roundtrip(cap, bad, q.scale[0],
                                                  breaker=brk)
        assert resident.QUANT_MISMATCHES == 1
        assert brk.agreement() < 1.0
        resident.reset_counters()


# -- fused vs oracle ----------------------------------------------------------

class TestFusedParity:
    @pytest.mark.parametrize("seed,n_nodes,n_jobs", [
        (11, 16, 3), (12, 16, 3), (13, 16, 3),
        (0, 24, 4), (1, 24, 4), (2, 24, 4), (3, 24, 4), (7, 24, 4)])
    def test_fused_vs_cpu_oracle_fuzz(self, seed, n_nodes, n_jobs,
                                      monkeypatch):
        """Oracle parity: per-job placed counts equal, nothing
        overcommitted on either side (scores are within the
        quantization bound by construction — the bound is 0)."""
        h_f, h_o, jobs = build_twin_problem(seed, n_nodes=n_nodes,
                                            n_jobs=n_jobs)
        stats = run_batch(h_f, jobs, monkeypatch=monkeypatch)
        assert stats.fused == 1
        for job in jobs:
            GenericScheduler(h_o.logger, h_o.snapshot(), h_o,
                             batch=False).process(reg_eval(job))
        for job in jobs:
            live_f = [a for a in h_f.state.allocs_by_job(None, job.id,
                                                         True)
                      if not a.terminal_status()]
            live_o = [a for a in h_o.state.allocs_by_job(None, job.id,
                                                         True)
                      if not a.terminal_status()]
            assert len(live_f) == len(live_o), job.id
        for h in (h_f, h_o):
            for nid, (cpu, mem, cap_cpu, cap_mem) in node_usage(h).items():
                assert cpu <= cap_cpu and mem <= cap_mem, nid

    def test_multi_round_same_node_scores_stay_bounded(self, monkeypatch):
        """A spec committing to the SAME node across several capacity-
        feedback rounds (1-node cluster, count 3) must keep ONE binpack
        metric entry per node with the last commit's score — per-alloc
        slot entries must not SUM into a >18 pseudo-score."""
        h = Harness()
        node = make_node()
        h.state.upsert_node(h.next_index(), node)
        job = make_job(3)
        stats = run_batch(h, [job], monkeypatch=monkeypatch)
        live = [a for a in h.state.allocs_by_job(None, job.id, True)
                if not a.terminal_status()]
        assert len(live) == 3 and stats.rounds == 3
        scores = live[0].metrics.scores
        binpack = scores.get(f"{node.id}.binpack")
        assert binpack is not None and 0.0 <= binpack <= 18.0, scores

    def test_quant_kill_switch_beats_memo(self, monkeypatch):
        """NOMAD_TPU_QUANT=0 must take effect immediately even when the
        cached static encode memoized quantized rows while it was on."""
        h = Harness()
        for _ in range(8):
            h.state.upsert_node(h.next_index(), make_node())
        monkeypatch.setenv("NOMAD_TPU_QUANT", "1")
        st1 = run_batch(h, [make_job(1)], monkeypatch=monkeypatch)
        assert st1.quantized == 1
        monkeypatch.setenv("NOMAD_TPU_QUANT", "0")
        st2 = run_batch(h, [make_job(1)], monkeypatch=monkeypatch)
        assert st2.quantized == 0

    def test_quantized_rows_active_and_exact(self, monkeypatch):
        """The mock fleet's resource rows quantize (disk needs a scale),
        the batch reports it, and placements still match the unquantized
        run bit-for-bit."""
        h_q, h_x, jobs = build_twin_problem(21)
        monkeypatch.setenv("NOMAD_TPU_QUANT", "1")
        st_q = run_batch(h_q, jobs, monkeypatch=monkeypatch)
        monkeypatch.setenv("NOMAD_TPU_QUANT", "0")
        st_x = run_batch(h_x, jobs, monkeypatch=monkeypatch)
        assert st_q.quantized == 1 and st_x.quantized == 0
        assert placements_by_spec(h_q, jobs) == placements_by_spec(
            h_x, jobs)


# -- the single-transfer contract --------------------------------------------

class TestSingleFetch:
    def test_exactly_one_fetch_span_per_fused_batch(self):
        h_f, _h, jobs = build_twin_problem(31)
        tracing.enable()
        try:
            sched = TPUBatchScheduler(h_f.logger, h_f.snapshot(), h_f)
            evals = [reg_eval(j) for j in jobs]
            stats = sched.schedule_batch(evals)
            spans = tracing.trace_for_eval(evals[0].id)
        finally:
            tracing.disable()
        assert stats.fused == 1
        fetches = [sp for sp in spans if sp["Name"] == "batch.fetch"]
        assert len(fetches) == 1, [sp["Name"] for sp in spans]
        assert fetches[0]["Attrs"].get("fused") == 1
        # A fully-placed batch needs no forensics fetch either.
        assert not [sp for sp in spans
                    if sp["Name"] == "batch.fetch_forensics"]
        assert stats.fetch_bytes > 0

    def test_window_overflow_falls_back_to_slot_record(self, monkeypatch):
        """A payload window smaller than nnz triggers the overflow path
        (slot-record fetch + host decode) — placements must still be
        bit-identical to the run whose window held them all."""
        from nomad_tpu.ops import kernels

        h_f, h_t, jobs = build_twin_problem(51)
        monkeypatch.setattr(kernels, "FUSED_WINDOW_BYTES", 64)
        st_f = run_batch(h_f, jobs, monkeypatch=monkeypatch)
        monkeypatch.setattr(kernels, "FUSED_WINDOW_BYTES",
                            8 << 20)
        run_batch(h_t, jobs, monkeypatch=monkeypatch)
        assert st_f.fused == 1
        assert placements_by_spec(h_f, jobs) == placements_by_spec(
            h_t, jobs)

    def test_failed_specs_add_at_most_one_forensics_fetch(self):
        """Overcommitted asks (capacity exhaustion at full feasibility)
        still fetch only the fused result buffer; a spec with a
        constraint filter adds exactly ONE batched forensics fetch."""
        h = Harness()
        for _ in range(4):
            h.state.upsert_node(h.next_index(), make_node())
        job = make_job(2)
        tg = job.task_groups[0]
        tg.constraints = list(tg.constraints) + [
            s.Constraint("${attr.kernel.name}", "plan9", "=")]
        h.state.upsert_job(h.next_index(), job)
        tracing.enable()
        try:
            ev = reg_eval(job)
            TPUBatchScheduler(h.logger, h.snapshot(), h).schedule_batch(
                [ev])
            spans = tracing.trace_for_eval(ev.id)
        finally:
            tracing.disable()
        assert len([sp for sp in spans
                    if sp["Name"] == "batch.fetch"]) == 1
        assert len([sp for sp in spans
                    if sp["Name"] == "batch.fetch_forensics"]) == 1


# -- chaos: corrupted fused buffer -------------------------------------------

@pytest.mark.chaos
class TestFusedCorruption:
    def test_corrupt_fused_buffer_breaker_and_probe_recovery(self):
        """ops.kernel_result corrupts the FUSED result buffer: the batch
        is rejected, the breaker trips, the oracle places everything;
        after the cooldown a clean half-open probe (still fused)
        restores the device path."""
        clock = [0.0]
        brk = KernelCircuitBreaker(threshold=0.9, window=8, min_checks=1,
                                   cooldown=5.0, clock=lambda: clock[0])
        h = Harness()
        for _ in range(8):
            h.state.upsert_node(h.next_index(), make_node())

        def batch():
            jobs = [make_job(2) for _ in range(2)]
            for j in jobs:
                h.state.upsert_job(h.next_index(), j)
            sched = TPUBatchScheduler(h.logger, h.snapshot(), h,
                                      breaker=brk)
            stats = sched.schedule_batch([reg_eval(j) for j in jobs])
            placed = all(len([
                a for a in h.state.allocs_by_job(None, j.id, True)
                if not a.terminal_status()]) == 2 for j in jobs)
            return stats, placed

        with fault.scenario({"seed": 5, "faults": [
                {"point": "ops.kernel_result", "action": "corrupt",
                 "times": 1}]}):
            st1, placed1 = batch()
            fired = fault.trace()
        assert fired == [("ops.kernel_result", 0, "corrupt")]
        assert st1.kernel_rejects == 1 and placed1
        assert brk.state == "open"

        st2, placed2 = batch()              # open: oracle carries
        assert st2.oracle_routed == 2 and placed2

        clock[0] += 6.0                     # past cooldown: probe
        st3, placed3 = batch()
        assert st3.oracle_routed == 0 and st3.fused == 1 and placed3
        assert brk.state == "closed"


# -- packed-result decode twins (ISSUE 13) -----------------------------------

class TestNativeDecode:
    """native/decode.cc vs the numpy/python twins on seeded COO shapes
    (the conftest pins NOMAD_TPU_DECODE_GUARD_EVERY=1, so every guarded
    call in the batch path is ALSO twin-verified; these pin the module
    directly, including twin-only edge shapes)."""

    def _corpus(self, seed, n_specs=13, n_real=97):
        import random
        rng = random.Random(seed)
        rows, cols, cnts, scs, cos = [], [], [], [], []
        for u in range(n_specs):
            for _ in range(rng.randrange(0, 7)):
                rows.append(u)
                cols.append(rng.randrange(n_real))
                cnts.append(rng.randrange(1, 5))
                scs.append(rng.random() * 18.0)
                cos.append(rng.randrange(0, 3))
        return (np.array(rows, np.int32), np.array(cols, np.int32),
                np.array(cnts, np.int32), np.array(scs, np.float32),
                np.array(cos, np.int32), n_specs, n_real)

    @pytest.mark.parametrize("seed", [0, 1, 2, 9])
    def test_expand_matches_twin(self, seed):
        from nomad_tpu.ops import decode
        decode.reset_counters()
        rows, cols, cnts, _, _, n_specs, n_real = self._corpus(seed)
        off, exp = decode.expand_coo(rows, cols, cnts, n_specs, n_real,
                                     int(cnts.sum()))
        ref_off, ref_exp = decode._expand_twin(rows, cols, cnts,
                                               n_specs, n_real)
        np.testing.assert_array_equal(off, ref_off)
        np.testing.assert_array_equal(exp, ref_exp)
        assert decode.GUARD_MISMATCHES == 0
        decode.reset_counters()

    @pytest.mark.parametrize("seed", [0, 3, 7])
    def test_last_scores_matches_twin(self, seed):
        from nomad_tpu.ops import decode
        decode.reset_counters()
        rows, cols, cnts, scs, cos, n_specs, n_real = self._corpus(seed)
        out = decode.last_scores(rows, cols, scs, cos, n_specs, n_real)
        ref = decode._last_scores_twin(rows, cols, scs, cos, n_specs,
                                       n_real)
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(a, b)
        assert decode.GUARD_MISMATCHES == 0
        decode.reset_counters()

    def test_empty_and_all_invalid(self):
        from nomad_tpu.ops import decode
        rows = np.array([-1, -1], np.int32)
        cols = np.array([5, 6], np.int32)
        cnts = np.array([1, 1], np.int32)
        off, exp = decode.expand_coo(rows, cols, cnts, 4, 10, 2)
        assert off.tolist() == [0, 0, 0, 0, 0] and len(exp) == 0
        off2, exp2 = decode.expand_coo(
            np.zeros(0, np.int32), np.zeros(0, np.int32),
            np.zeros(0, np.int32), 3, 10, 0)
        assert off2.tolist() == [0, 0, 0, 0] and len(exp2) == 0


# -- compile-cache audit (ISSUE 13) ------------------------------------------

class TestCompileAudit:
    def test_same_shape_stream_compiles_once(self):
        """A stream of same-shape batches must add NO new placement-
        program signatures after the first (the `batch.compiles` gauge
        rides this counter)."""
        from nomad_tpu.ops import kernels

        # A clean audit: with bigger plans on record from this process's
        # earlier batches, the first PLAN_REUSE_LIMIT of these would run
        # a bigger compiled program and the next would earn its own
        # (kernels.choose_plan) — one signature, but not at a fixed batch.
        kernels.reset_compile_signatures()
        h = Harness()
        for _ in range(8):
            h.state.upsert_node(h.next_index(), make_node())

        def one_batch():
            job = make_job(2)
            h.state.upsert_job(h.next_index(), job)
            sched = TPUBatchScheduler(h.logger, h.snapshot(), h)
            sched.schedule_batch([reg_eval(job)])

        one_batch()
        one_batch()   # resident-hit shape (no u_rows in the dyn pack)
        base = kernels.compile_signatures()
        for _ in range(4):
            one_batch()
        assert kernels.compile_signatures() == base, (
            "steady same-shape batches must not mint new program "
            "signatures")
