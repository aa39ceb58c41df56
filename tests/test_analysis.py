"""Invariant analysis plane tests (ISSUE 15).

- Seeded known-bad fixture snippets asserting each rule family fires,
  including regression fixtures reproducing the PR 9 fsync-under-lock
  and PR 10 drain-under-lock shapes.
- The tree itself ships green: ``run_checks()`` returns zero
  unsuppressed violations.
- Runtime lockcheck units: a seeded inversion is caught with a witness
  cycle, the Condition protocol tracks manual release windows, and the
  disarmed state costs one module-global load (nothing patched).
- The sanitized native corpus leg (slow tier).
"""
from __future__ import annotations

import ast
import os
import threading
import time

import pytest

from nomad_tpu.analysis import (SourceFile, Allowlist, iter_source_files,
                                repo_root, run_checks)
from nomad_tpu.analysis import guardrules, jaxrules, knobrules, lockrules
from nomad_tpu.utils import knobs, lockcheck

pytestmark = pytest.mark.analysis

ROOT = repo_root()


def _sf(path: str, source: str) -> SourceFile:
    return SourceFile(path=path, abspath=os.path.join("/fake", path),
                      source=source, tree=ast.parse(source))


def _rules(violations):
    return sorted({v.rule for v in violations})


# ---------------------------------------------------------------------------
# rule family 1: lock discipline
# ---------------------------------------------------------------------------


class TestLockRules:
    def test_pr9_fsync_under_lock_fires(self):
        # The PR 9 regression shape: the WAL append fsyncs while the
        # raft log lock is held — group commit structurally impossible.
        src = (
            "import os\n"
            "import threading\n"
            "class RaftLog:\n"
            "    def __init__(self):\n"
            "        self._l = threading.Lock()\n"
            "    def apply(self, entry):\n"
            "        with self._l:\n"
            "            self._fh.write(entry)\n"
            "            os.fsync(self._fh.fileno())\n"
        )
        out = lockrules.check(ROOT, [_sf("nomad_tpu/server/fake_raft.py",
                                         src)])
        assert any(v.rule == "lock-blocking" and "fsync" in v.detail
                   for v in out), out

    def test_pr10_drain_under_lock_fires(self):
        # The PR 10 regression shape: the snapshot path drains the
        # apply sequencer (a sleep-poll loop) while the log lock is
        # held — flagged through the one-level helper propagation.
        src = (
            "import threading\n"
            "import time\n"
            "class FileLog:\n"
            "    def __init__(self):\n"
            "        self._l = threading.RLock()\n"
            "    def _drain_appliers(self):\n"
            "        while self._inflight:\n"
            "            time.sleep(0.01)\n"
            "    def snapshot(self):\n"
            "        with self._l:\n"
            "            self._drain_appliers()\n"
        )
        out = lockrules.check(ROOT, [_sf("nomad_tpu/server/fake_log.py",
                                         src)])
        assert any(v.rule == "lock-blocking"
                   and "_drain_appliers" in v.detail for v in out), out

    def test_lock_order_cycle_fires_with_witness(self):
        src = (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def fwd(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "    def rev(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n"
        )
        out = lockrules.check(ROOT, [_sf("nomad_tpu/server/fake_cyc.py",
                                         src)])
        cyc = [v for v in out if v.rule == "lock-order"]
        assert cyc and "_a" in cyc[0].message and "_b" in cyc[0].message

    def test_condition_wait_not_blocking(self):
        src = (
            "import threading\n"
            "class Broker:\n"
            "    def __init__(self):\n"
            "        self._l = threading.RLock()\n"
            "        self._cond = threading.Condition(self._l)\n"
            "    def dequeue(self):\n"
            "        with self._l:\n"
            "            while not self._ready:\n"
            "                self._cond.wait(1.0)\n"
        )
        out = lockrules.check(ROOT, [_sf("nomad_tpu/server/fake_bk.py",
                                         src)])
        assert not [v for v in out if v.rule == "lock-blocking"], out

    def test_clean_region_silent(self):
        src = (
            "import os\n"
            "import threading\n"
            "class Log:\n"
            "    def __init__(self):\n"
            "        self._l = threading.Lock()\n"
            "    def apply(self, entry):\n"
            "        with self._l:\n"
            "            seq = self._wal.write(entry)\n"
            "        os.fsync(self._fh.fileno())\n"
        )
        out = lockrules.check(ROOT, [_sf("nomad_tpu/server/fake_ok.py",
                                         src)])
        assert out == []


# ---------------------------------------------------------------------------
# rule family 2: jax discipline
# ---------------------------------------------------------------------------


class TestJaxRules:
    def test_donated_reuse_fires(self):
        src = (
            "import jax\n"
            "_apply = jax.jit(_impl, donate_argnums=(0,))\n"
            "def step(buf, delta):\n"
            "    out = _apply(buf, delta)\n"
            "    return buf.sum()\n"  # use-after-donation
        )
        out = jaxrules.check(ROOT, [_sf("nomad_tpu/ops/fake_don.py",
                                        src)])
        assert any(v.rule == "jax-donated-reuse" for v in out), out

    def test_donated_rebind_ok_and_args_not_reuse(self):
        src = (
            "import jax\n"
            "_apply = jax.jit(_impl, donate_argnums=(0,))\n"
            "def step(buf, delta):\n"
            "    buf = _apply(buf, delta)\n"
            "    return buf.sum()\n"  # rebound: the aliased result
        )
        out = jaxrules.check(ROOT, [_sf("nomad_tpu/ops/fake_ok.py",
                                        src)])
        assert not [v for v in out if v.rule == "jax-donated-reuse"], out

    def test_host_sync_fires_in_hot_path_only(self):
        src = (
            "import jax\n"
            "def fetch(buf):\n"
            "    return jax.device_get(buf)\n"
        )
        hot = jaxrules.check(ROOT, [_sf("nomad_tpu/ops/fake_sync.py",
                                        src)])
        assert any(v.rule == "jax-host-sync" for v in hot)
        cold = jaxrules.check(ROOT, [_sf("nomad_tpu/server/fake.py",
                                         src)])
        assert cold == []

    def test_note_signature_escape_fires(self):
        src = (
            "import jax\n"
            "_fn = jax.jit(_impl, static_argnames=('n',))\n"
        )
        out = jaxrules.check(ROOT, [_sf("nomad_tpu/ops/fake_jit.py",
                                        src)])
        assert any(v.rule == "jax-note-signature" for v in out), out
        src_ok = src + (
            "def run(x):\n"
            "    note_signature('fake', (1,))\n"
            "    return _fn(x)\n"
        )
        out = jaxrules.check(ROOT, [_sf("nomad_tpu/ops/fake_jit2.py",
                                        src_ok)])
        assert not [v for v in out if v.rule == "jax-note-signature"]


# ---------------------------------------------------------------------------
# rule families 3+4 against the real tree, plus seeded negatives
# ---------------------------------------------------------------------------


class TestGuardAndKnobRules:
    def test_real_tree_guard_coverage_clean(self):
        from nomad_tpu.analysis import load_tree

        files = load_tree(ROOT)
        assert guardrules.check(ROOT, files) == []

    def test_unclaimed_native_source_fires(self, tmp_path):
        # A fake root with one .cc and an empty registry.
        (tmp_path / "nomad_tpu" / "native").mkdir(parents=True)
        (tmp_path / "nomad_tpu" / "ops").mkdir(parents=True)
        (tmp_path / "nomad_tpu" / "utils").mkdir(parents=True)
        (tmp_path / "nomad_tpu" / "native" / "rogue.cc").write_text(
            "// unguarded native code\n")
        (tmp_path / "nomad_tpu" / "ops" / "guards.py").write_text(
            "REGISTRY = []\n"
            "def native_sources():\n"
            "    return []\n")
        knobs_src = open(os.path.join(
            ROOT, "nomad_tpu/utils/knobs.py")).read()
        (tmp_path / "nomad_tpu" / "utils" / "knobs.py").write_text(
            knobs_src)
        out = guardrules.check(str(tmp_path), [])
        assert any("unclaimed-native-source" in v.detail for v in out)

    def test_adhoc_env_read_fires(self):
        src = (
            "import os\n"
            "def enabled():\n"
            "    return os.environ.get('NOMAD_TPU_QUANT') == '1'\n"
        )
        out = knobrules.check(ROOT, [_sf("nomad_tpu/fake_knob.py", src)])
        mine = [v for v in out if v.path == "nomad_tpu/fake_knob.py"]
        assert any(v.rule == "knob-env-read" for v in mine), out

    def test_env_read_through_module_constant_fires(self):
        src = (
            "import os\n"
            "CHILD = 'NOMAD_TPU_LG_PROFILE'\n"
            "def is_child():\n"
            "    return os.environ.get(CHILD) == '1'\n"
        )
        out = knobrules.check(ROOT, [_sf("nomad_tpu/fake_knob2.py",
                                         src)])
        mine = [v for v in out if v.path == "nomad_tpu/fake_knob2.py"]
        assert any(v.rule == "knob-env-read" for v in mine), out

    def test_unregistered_knob_token_fires(self):
        src = "FLAG = 'NOMAD_TPU_TOTALLY_NEW_KNOB'\n"
        out = knobrules.check(ROOT, [_sf("nomad_tpu/fake_knob3.py",
                                         src)])
        mine = [v for v in out if v.path == "nomad_tpu/fake_knob3.py"]
        assert any(v.rule == "knob-unregistered" for v in mine), out

    def test_env_write_is_legal(self):
        src = (
            "import os\n"
            "def arm():\n"
            "    os.environ['NOMAD_TPU_QUANT'] = '0'\n"
            "    os.environ.pop('NOMAD_TPU_RESIDENT', None)\n"
        )
        out = knobrules.check(ROOT, [_sf("nomad_tpu/fake_knob4.py",
                                         src)])
        mine = [v for v in out
                if v.path == "nomad_tpu/fake_knob4.py"
                and v.rule == "knob-env-read"]
        assert mine == []

    def test_knob_accessors(self, monkeypatch):
        with pytest.raises(knobs.UnknownKnobError):
            knobs.get_bool("NOMAD_TPU_NOT_A_KNOB")
        monkeypatch.setenv("NOMAD_TPU_QUANT", "off")
        assert knobs.get_bool("NOMAD_TPU_QUANT") is False
        monkeypatch.setenv("NOMAD_TPU_QUANT", "")
        assert knobs.get_bool("NOMAD_TPU_QUANT") is True  # default
        monkeypatch.setenv("NOMAD_TPU_PLAN_PIPELINE", "garbage")
        assert knobs.get_int("NOMAD_TPU_PLAN_PIPELINE") == 8  # default
        monkeypatch.setenv("NOMAD_TPU_RNG_SEED", "123")
        assert knobs.get_int("NOMAD_TPU_RNG_SEED") == 123
        monkeypatch.delenv("NOMAD_TPU_RNG_SEED")
        assert knobs.get_int("NOMAD_TPU_RNG_SEED") is None
        assert knobs.raw("NOMAD_TPU_RNG_SEED") is None

    def test_readme_table_in_sync(self):
        text = open(os.path.join(ROOT, "README.md")).read()
        start = text.index(knobs.TABLE_BEGIN)
        stop = text.index(knobs.TABLE_END) + len(knobs.TABLE_END)
        assert text[start:stop] == knobs.render_readme_table()


# ---------------------------------------------------------------------------
# the allowlist mechanism
# ---------------------------------------------------------------------------


class TestAllowlist:
    def test_stale_entry_fails(self, tmp_path):
        allow = tmp_path / "allow.txt"
        allow.write_text("lock-blocking nomad_tpu/nope.py::f::x  "
                         "# covers nothing\n")
        active, _sup = run_checks(ROOT, allowlist_path=str(allow))
        assert any(v.rule == "allowlist" and "stale" in v.detail
                   for v in active)

    def test_entry_without_reason_fails(self, tmp_path):
        allow = tmp_path / "allow.txt"
        allow.write_text("lock-blocking nomad_tpu/x.py::f::y\n")
        active, _sup = run_checks(ROOT, allowlist_path=str(allow))
        assert any(v.rule == "allowlist" and "malformed" in v.detail
                   for v in active)


# ---------------------------------------------------------------------------
# the acceptance gate: the tree ships green
# ---------------------------------------------------------------------------


class TestTreeShipsGreen:
    def test_whole_tree_zero_unsuppressed_violations(self):
        active, suppressed = run_checks(ROOT)
        assert active == [], "\n".join(v.render() for v in active)
        # The allowlist is genuinely exercised (the justified shapes).
        assert len(suppressed) >= 10

    def test_every_source_file_scanned(self):
        paths = iter_source_files(ROOT)
        assert "nomad_tpu/server/raft.py" in paths
        assert "__graft_entry__.py" in paths
        assert not any(p.startswith("tests/") for p in paths)


# ---------------------------------------------------------------------------
# runtime lockcheck
# ---------------------------------------------------------------------------


class TestLockcheck:
    def setup_method(self):
        assert not lockcheck.armed()

    def teardown_method(self):
        lockcheck.disarm()

    def test_seeded_inversion_caught_with_witness(self):
        lockcheck.arm()
        a = lockcheck.make_tracked("t:a")
        b = lockcheck.make_tracked("t:b")
        with a:
            with b:
                pass
        assert lockcheck.find_cycle() is None
        done = []

        def invert():
            with b:
                with a:
                    done.append(True)

        t = threading.Thread(target=invert)
        t.start()
        t.join(5)
        assert done
        with pytest.raises(lockcheck.LockOrderError) as exc:
            lockcheck.assert_acyclic()
        msg = str(exc.value)
        assert "t:a" in msg and "t:b" in msg

    def test_disarmed_is_unpatched_and_one_load(self):
        # Disarmed: the real primitives are in place...
        assert threading.Lock is lockcheck._REAL_LOCK
        assert threading.RLock is lockcheck._REAL_RLOCK
        assert time.sleep is lockcheck._REAL_SLEEP
        assert os.fsync is lockcheck._REAL_FSYNC
        # ...and a live wrapper's entire disarmed cost is the single
        # module-global load (_STATE is None short-circuits before any
        # tracking structure is touched).
        lk = lockcheck.make_tracked("t:disarmed")
        assert lockcheck._STATE is None
        with lk:
            assert lockcheck.held_tracked() == []
        lockcheck.arm()
        assert threading.Lock is not lockcheck._REAL_LOCK
        with lk:
            assert lockcheck.held_tracked() == ["t:disarmed"]
        lockcheck.disarm()
        assert threading.Lock is lockcheck._REAL_LOCK

    def test_armed_wraps_nomad_locks_only(self):
        lockcheck.arm()
        # A lock created from a nomad_tpu frame is wrapped: fake the
        # creation site by compiling with a nomad_tpu filename.
        fake = os.path.join(ROOT, "nomad_tpu", "_lockfixture.py")
        ns = {"threading": threading}
        exec(compile("def mk():\n    return threading.Lock()\n",
                     fake, "exec"), ns)
        assert isinstance(ns["mk"](), lockcheck.TrackedLock)
        # A lock created from foreign code (this test file) is real.
        assert not isinstance(threading.Lock(), lockcheck.TrackedLock)

    def test_rlock_reentry_no_self_edge(self):
        lockcheck.arm()
        r = lockcheck.make_tracked("t:r", rlock=True)
        with r:
            with r:
                pass
        assert lockcheck.edges() == {}
        assert lockcheck.held_tracked() == []

    def test_condition_wait_releases_held(self):
        lockcheck.arm()
        r = lockcheck.make_tracked("t:cv", rlock=True)
        cond = threading.Condition(r)
        observed = []

        def waiter():
            with cond:
                cond.wait(timeout=5)
                observed.append("woke")

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.2)
        # The waiter released t:cv inside wait(): we can take it.
        got = r.acquire(timeout=2)
        assert got
        cond.notify_all()
        r.release()
        t.join(5)
        assert observed == ["woke"]

    def test_blocking_call_under_lock_recorded(self):
        lockcheck.arm()
        lk = lockcheck.make_tracked("t:hold")
        with lk:
            time.sleep(0)
        rec = lockcheck.blocking_calls()
        assert any(name == "t:hold" and kind == "time.sleep"
                   for name, kind, _site in rec), rec

    def test_maybe_arm_from_env(self, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_LOCKCHECK", "1")
        assert lockcheck.maybe_arm_from_env() is True
        assert lockcheck.armed()
        lockcheck.disarm()
        monkeypatch.setenv("NOMAD_TPU_LOCKCHECK", "0")
        assert lockcheck.maybe_arm_from_env() is False
        assert not lockcheck.armed()


# ---------------------------------------------------------------------------
# sanitized native corpus (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestSanitizedCorpus:
    def test_asan_corpus_clean(self):
        from nomad_tpu.native.__main__ import run_sanitized

        verdict = run_sanitized(seed=0, log=lambda *a: None)
        assert verdict in ("ok", "skip"), verdict
