"""Client runtime tests: restart tracker, task env, drivers, task/alloc
runners, allocdir, getter, GC (reference: client/*_test.go)."""
import os
import signal
import sys
import tempfile
import time

import pytest

from nomad_tpu.structs import structs as s
from nomad_tpu import mock
from nomad_tpu.client import (
    AllocRunner,
    ClientConfig,
    RestartTracker,
    TaskRunner,
    get_client_status,
)
from nomad_tpu.client.allocdir import AllocDir
from nomad_tpu.client.driver import env as envmod
from nomad_tpu.client.driver.driver import (
    DriverError,
    RecoverableError,
    WaitResult,
)
from nomad_tpu.client.gc import AllocGarbageCollector
from nomad_tpu.client.getter import ArtifactError, get_artifact
from nomad_tpu.client.restarts import (
    REASON_NO_RESTARTS_ALLOWED,
    REASON_UNRECOVERABLE,
    REASON_WITHIN_POLICY,
)


def wait_until(pred, timeout=10.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# RestartTracker (client/restarts_test.go)


def policy(attempts=2, interval=60.0, delay=0.01, mode=s.RESTART_POLICY_MODE_DELAY):
    return s.RestartPolicy(attempts=attempts, interval=interval, delay=delay,
                           mode=mode)


class TestRestartTracker:
    def test_service_restarts_on_success(self):
        rt = RestartTracker(policy(), s.JOB_TYPE_SERVICE)
        rt.set_wait_result(WaitResult(exit_code=0))
        state, _ = rt.get_state()
        assert state == s.TASK_RESTARTING
        assert rt.get_reason() == REASON_WITHIN_POLICY

    def test_batch_terminates_on_success(self):
        rt = RestartTracker(policy(), s.JOB_TYPE_BATCH)
        rt.set_wait_result(WaitResult(exit_code=0))
        state, _ = rt.get_state()
        assert state == s.TASK_TERMINATED

    def test_zero_attempts(self):
        rt = RestartTracker(policy(attempts=0), s.JOB_TYPE_SERVICE)
        rt.set_wait_result(WaitResult(exit_code=1))
        state, _ = rt.get_state()
        assert state == s.TASK_NOT_RESTARTING
        assert rt.get_reason() == REASON_NO_RESTARTS_ALLOWED

    def test_fail_mode_exhausts(self):
        rt = RestartTracker(policy(attempts=1, mode=s.RESTART_POLICY_MODE_FAIL),
                            s.JOB_TYPE_SERVICE)
        rt.set_wait_result(WaitResult(exit_code=1))
        assert rt.get_state()[0] == s.TASK_RESTARTING
        rt.set_wait_result(WaitResult(exit_code=1))
        assert rt.get_state()[0] == s.TASK_NOT_RESTARTING

    def test_delay_mode_waits_out_interval(self):
        rt = RestartTracker(policy(attempts=1, interval=5.0), s.JOB_TYPE_SERVICE)
        rt.set_wait_result(WaitResult(exit_code=1))
        rt.get_state()
        rt.set_wait_result(WaitResult(exit_code=1))
        state, delay = rt.get_state()
        assert state == s.TASK_RESTARTING
        assert delay > 1.0  # remainder of the 5s interval

    def test_unrecoverable_start_error(self):
        rt = RestartTracker(policy(), s.JOB_TYPE_SERVICE)
        rt.set_start_error(DriverError("bad config"))
        state, _ = rt.get_state()
        assert state == s.TASK_NOT_RESTARTING
        assert rt.get_reason() == REASON_UNRECOVERABLE

    def test_recoverable_start_error_restarts(self):
        rt = RestartTracker(policy(), s.JOB_TYPE_SERVICE)
        rt.set_start_error(RecoverableError("transient"))
        state, _ = rt.get_state()
        assert state == s.TASK_RESTARTING

    def test_restart_triggered(self):
        rt = RestartTracker(policy(attempts=0), s.JOB_TYPE_SERVICE)
        rt.set_restart_triggered()
        state, delay = rt.get_state()
        assert state == s.TASK_RESTARTING and delay == 0.0

    def test_interval_reset(self):
        rt = RestartTracker(policy(attempts=1, interval=0.05), s.JOB_TYPE_SERVICE)
        rt.set_wait_result(WaitResult(exit_code=1))
        assert rt.get_state()[0] == s.TASK_RESTARTING
        time.sleep(0.06)
        rt.set_wait_result(WaitResult(exit_code=1))
        assert rt.get_state()[0] == s.TASK_RESTARTING  # budget reset


# ---------------------------------------------------------------------------
# Task env builder (client/driver/env/env_test.go)


class TestTaskEnv:
    def build_env(self):
        alloc = mock.alloc()
        task = alloc.job.task_groups[0].tasks[0]
        task.env = {"CUSTOM": "x-${NOMAD_TASK_NAME}", "NODE_DC": "${node.datacenter}"}
        node = mock.node()
        b = envmod.Builder()
        b.set_task(task).set_alloc(alloc).set_node(node).set_region("global")
        b.set_dirs("/a/alloc", "/a/web/local", "/a/web/secrets")
        return b.build(), alloc, task, node

    def test_standard_vars(self):
        env, alloc, task, node = self.build_env()
        m = env.env()
        assert m["NOMAD_ALLOC_DIR"] == "/a/alloc"
        assert m["NOMAD_TASK_DIR"] == "/a/web/local"
        assert m["NOMAD_SECRETS_DIR"] == "/a/web/secrets"
        assert m["NOMAD_ALLOC_ID"] == alloc.id
        assert m["NOMAD_TASK_NAME"] == task.name
        assert m["NOMAD_JOB_NAME"] == alloc.job.name
        assert m["NOMAD_DC"] == node.datacenter
        assert m["NOMAD_REGION"] == "global"
        assert m["NOMAD_CPU_LIMIT"] == str(task.resources.cpu)
        assert m["NOMAD_MEMORY_LIMIT"] == str(task.resources.memory_mb)

    def test_task_env_interpolation(self):
        env, _, task, node = self.build_env()
        m = env.env()
        assert m["CUSTOM"] == f"x-{task.name}"
        assert m["NODE_DC"] == node.datacenter

    def test_replace_env(self):
        env, _, _, node = self.build_env()
        assert env.replace_env("${node.datacenter}-suffix") == \
            f"{node.datacenter}-suffix"
        assert env.replace_env("${missing.var}") == ""

    def test_alloc_index(self):
        env, alloc, _, _ = self.build_env()
        # mock alloc name is "web[0]"-ish; index parsed from the name
        if "[" in alloc.name:
            want = alloc.name.rsplit("[", 1)[1].rstrip("]")
            assert env.env()["NOMAD_ALLOC_INDEX"] == want

    def test_port_env(self):
        alloc = mock.alloc()
        task = alloc.job.task_groups[0].tasks[0]
        res = (alloc.task_resources or {}).get(task.name)
        if res is None or not res.networks:
            pytest.skip("mock alloc has no task networks")
        b = envmod.Builder()
        b.set_task(task).set_alloc(alloc)
        m = b.build().env()
        net = res.networks[0]
        for label, port in net.port_labels().items():
            assert m[f"NOMAD_PORT_{label}"] == str(port)
            assert m[f"NOMAD_ADDR_{label}"] == f"{net.ip}:{port}"


# ---------------------------------------------------------------------------
# Alloc dir


class TestAllocDir:
    def test_build_layout(self, tmp_path):
        ad = AllocDir(str(tmp_path / "a1"))
        ad.build()
        td = ad.new_task_dir("web")
        td.build()
        assert os.path.isdir(os.path.join(ad.shared_dir, "data"))
        assert os.path.isdir(os.path.join(ad.shared_dir, "logs"))
        assert os.path.isdir(td.local_dir)
        assert os.path.isdir(td.secrets_dir)

    def test_move_sticky(self, tmp_path):
        old = AllocDir(str(tmp_path / "old"))
        old.build()
        old.new_task_dir("web").build()
        with open(os.path.join(old.shared_dir, "data", "state.bin"), "w") as f:
            f.write("persisted")
        with open(os.path.join(old.task_dirs["web"].local_dir, "cache"), "w") as f:
            f.write("warm")

        new = AllocDir(str(tmp_path / "new"))
        new.build()
        new.new_task_dir("web").build()
        new.move(old, ["web"])
        assert open(os.path.join(new.shared_dir, "data", "state.bin")).read() \
            == "persisted"
        assert open(os.path.join(new.task_dirs["web"].local_dir, "cache")).read() \
            == "warm"

    def test_snapshot_restore(self, tmp_path):
        src = AllocDir(str(tmp_path / "src"))
        src.build()
        src.new_task_dir("web").build()
        with open(os.path.join(src.shared_dir, "data", "f"), "w") as f:
            f.write("snap")
        blob = src.snapshot()

        dst = AllocDir(str(tmp_path / "dst"))
        dst.build()
        dst.new_task_dir("web").build()
        dst.restore_snapshot(blob)
        assert open(os.path.join(dst.shared_dir, "data", "f")).read() == "snap"

    def test_path_escape_rejected(self, tmp_path):
        ad = AllocDir(str(tmp_path / "a"))
        ad.build()
        with pytest.raises(PermissionError):
            ad.read_at("../../etc/passwd", 0, 10)


# ---------------------------------------------------------------------------
# Artifact getter


class TestGetter:
    def test_file_artifact(self, tmp_path):
        src = tmp_path / "artifact.txt"
        src.write_text("payload")
        task_dir = tmp_path / "task"
        task_dir.mkdir()
        art = s.TaskArtifact(getter_source=f"file://{src}", relative_dest="local/")
        env = envmod.TaskEnv()
        dest = get_artifact(env, art, str(task_dir))
        assert open(dest).read() == "payload"

    def test_checksum_mismatch(self, tmp_path):
        src = tmp_path / "artifact.txt"
        src.write_text("payload")
        task_dir = tmp_path / "task"
        task_dir.mkdir()
        art = s.TaskArtifact(getter_source=str(src), relative_dest="local/",
                             getter_options={"checksum": "sha256:" + "0" * 64})
        with pytest.raises(ArtifactError):
            get_artifact(envmod.TaskEnv(), art, str(task_dir))

    def test_interpolated_source(self, tmp_path):
        src = tmp_path / "artifact.txt"
        src.write_text("x")
        task_dir = tmp_path / "task"
        task_dir.mkdir()
        env = envmod.TaskEnv(env_map={"SRC": str(src)})
        art = s.TaskArtifact(getter_source="${SRC}", relative_dest="local/")
        assert os.path.exists(get_artifact(env, art, str(task_dir)))

    def test_s3_artifact_anonymous_and_signed(self, tmp_path, monkeypatch):
        """s3:: endpoint form against a local fake bucket: anonymous GET,
        then a SigV4-signed GET once AWS creds are in the environment
        (getter.go s3 support)."""
        import http.server
        import threading

        seen = {}

        class FakeS3(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                seen["path"] = self.path
                seen["auth"] = self.headers.get("Authorization", "")
                seen["sha"] = self.headers.get("x-amz-content-sha256", "")
                body = b"s3-object-bytes"
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), FakeS3)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        port = httpd.server_address[1]
        try:
            task_dir = tmp_path / "task"
            task_dir.mkdir()
            art = s.TaskArtifact(
                getter_source=f"s3::http://127.0.0.1:{port}/bkt/obj.bin",
                relative_dest="local/")
            dest = get_artifact(envmod.TaskEnv(), art, str(task_dir))
            assert open(dest, "rb").read() == b"s3-object-bytes"
            assert seen["path"] == "/bkt/obj.bin"
            assert seen["auth"] == ""  # anonymous without creds

            monkeypatch.setenv("AWS_ACCESS_KEY_ID", "AKIDEXAMPLE")
            monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "secret")
            dest = get_artifact(envmod.TaskEnv(), art, str(task_dir))
            assert seen["auth"].startswith("AWS4-HMAC-SHA256 Credential="
                                           "AKIDEXAMPLE/")
            assert "SignedHeaders=host;x-amz-content-sha256;x-amz-date" \
                in seen["auth"]
            assert seen["sha"] == (
                "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b"
                "7852b855")  # sha256 of empty body
        finally:
            httpd.shutdown()

    def test_s3_checksum_verified(self, tmp_path):
        import hashlib as hl
        import http.server
        import threading

        body = b"data-123"

        class FakeS3(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), FakeS3)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        port = httpd.server_address[1]
        try:
            task_dir = tmp_path / "task"
            task_dir.mkdir()
            good = hl.sha256(body).hexdigest()
            art = s.TaskArtifact(
                getter_source=f"s3::http://127.0.0.1:{port}/b/k.bin",
                relative_dest="local/",
                getter_options={"checksum": f"sha256:{good}"})
            assert os.path.exists(
                get_artifact(envmod.TaskEnv(), art, str(task_dir)))
            art.getter_options = {"checksum": "sha256:" + "0" * 64}
            with pytest.raises(ArtifactError):
                get_artifact(envmod.TaskEnv(), art, str(task_dir))
        finally:
            httpd.shutdown()


# ---------------------------------------------------------------------------
# Task runner + mock driver (client/task_runner_test.go)


def make_task_runner(tmp_path, config_overrides=None, job_type=s.JOB_TYPE_BATCH,
                     restart=None):
    alloc = mock.alloc()
    alloc.job.type = job_type
    tg = alloc.job.task_groups[0]
    tg.restart_policy = restart or s.RestartPolicy(
        attempts=0, mode=s.RESTART_POLICY_MODE_FAIL)
    task = tg.tasks[0]
    task.driver = "mock_driver"
    task.config = dict(config_overrides or {"run_for": "50ms"})

    ad = AllocDir(str(tmp_path / alloc.id))
    ad.build()
    td = ad.new_task_dir(task.name)
    td.build()

    updates = []

    def updater(name, state, event):
        updates.append((name, state, event))

    cfg = ClientConfig(alloc_dir=str(tmp_path))
    tr = TaskRunner(config=cfg, alloc=alloc, task=task, task_dir=td,
                    updater=updater, node=mock.node())
    return tr, updates


class TestTaskRunner:
    def test_simple_run_to_completion(self, tmp_path):
        tr, updates = make_task_runner(tmp_path)
        tr.run()
        assert tr.done.wait(5.0)
        states = [u[1] for u in updates if u[1]]
        assert states[0] == s.TASK_STATE_PENDING
        assert s.TASK_STATE_RUNNING in states
        assert states[-1] == s.TASK_STATE_DEAD
        events = [u[2].type for u in updates if u[2] is not None]
        assert s.TASK_RECEIVED in events
        assert s.TASK_STARTED in events
        assert s.TASK_TERMINATED in events

    def test_failed_exit_marks_failed(self, tmp_path):
        tr, updates = make_task_runner(
            tmp_path, {"run_for": "10ms", "exit_code": 1})
        tr.run()
        assert tr.done.wait(5.0)
        events = [u[2] for u in updates if u[2] is not None]
        assert any(e.type == s.TASK_NOT_RESTARTING and e.failed for e in events)

    def test_start_error(self, tmp_path):
        tr, updates = make_task_runner(tmp_path, {"start_error": "boom"})
        tr.run()
        assert tr.done.wait(5.0)
        events = [u[2].type for u in updates if u[2] is not None]
        assert s.TASK_DRIVER_FAILURE in events

    def test_restart_within_policy(self, tmp_path):
        tr, updates = make_task_runner(
            tmp_path, {"run_for": "10ms", "exit_code": 1},
            restart=s.RestartPolicy(attempts=1, interval=60.0, delay=0.01,
                                    mode=s.RESTART_POLICY_MODE_FAIL))
        tr.run()
        assert tr.done.wait(5.0)
        events = [u[2].type for u in updates if u[2] is not None]
        assert events.count(s.TASK_STARTED) == 2
        assert s.TASK_RESTARTING in events

    def test_destroy_kills(self, tmp_path):
        tr, updates = make_task_runner(tmp_path, {"run_for": "60s"})
        tr.run()
        assert wait_until(lambda: any(
            u[2] is not None and u[2].type == s.TASK_STARTED for u in updates))
        tr.destroy(s.TaskEvent(type=s.TASK_KILLED))
        assert tr.done.wait(5.0)
        events = [u[2].type for u in updates if u[2] is not None]
        assert s.TASK_KILLED in events


# ---------------------------------------------------------------------------
# Raw exec driver — real process

@pytest.mark.skipif(sys.platform != "linux", reason="linux-only")
class TestRawExec:
    def test_real_process(self, tmp_path):
        alloc = mock.alloc()
        alloc.job.type = s.JOB_TYPE_BATCH
        tg = alloc.job.task_groups[0]
        tg.restart_policy = s.RestartPolicy(attempts=0,
                                            mode=s.RESTART_POLICY_MODE_FAIL)
        task = tg.tasks[0]
        task.driver = "raw_exec"
        task.config = {
            "command": sys.executable,
            "args": ["-c", "print('hello from ${NOMAD_TASK_NAME}')"],
        }
        ad = AllocDir(str(tmp_path / alloc.id))
        ad.build()
        td = ad.new_task_dir(task.name)
        td.build()

        updates = []
        cfg = ClientConfig(alloc_dir=str(tmp_path),
                           options={"driver.raw_exec.enable": "1"})
        tr = TaskRunner(config=cfg, alloc=alloc, task=task, task_dir=td,
                        updater=lambda n, st, ev: updates.append((n, st, ev)),
                        node=mock.node())
        tr.run()
        # Liveness bound, not a perf assertion: two python subprocesses
        # (supervisor + task) start up, which under full-suite load can
        # exceed 10s.
        assert tr.done.wait(30.0)
        events = [u[2] for u in updates if u[2] is not None]
        term = [e for e in events if e.type == s.TASK_TERMINATED]
        assert term and term[0].exit_code == 0
        # stdout landed in the log dir with rotation naming
        logs = os.listdir(td.log_dir)
        stdout_logs = [f for f in logs if ".stdout." in f]
        assert stdout_logs
        content = open(os.path.join(td.log_dir, stdout_logs[0])).read()
        assert f"hello from {task.name}" in content


# ---------------------------------------------------------------------------
# Alloc runner (client/alloc_runner_test.go)


def make_alloc_runner(tmp_path, task_configs, job_type=s.JOB_TYPE_BATCH):
    """task_configs: dict task_name → mock driver config."""
    alloc = mock.alloc()
    alloc.job.type = job_type
    tg = alloc.job.task_groups[0]
    tg.restart_policy = s.RestartPolicy(attempts=0,
                                        mode=s.RESTART_POLICY_MODE_FAIL)
    base_task = tg.tasks[0]
    tg.tasks = []
    for name, cfg in task_configs.items():
        t = base_task.copy()
        t.name = name
        t.driver = "mock_driver"
        t.config = cfg
        tg.tasks.append(t)

    updates = []
    cfg = ClientConfig(alloc_dir=str(tmp_path))
    ar = AllocRunner(config=cfg, alloc=alloc,
                     updater=lambda a: updates.append(a), node=mock.node())
    return ar, updates


class TestAllocRunner:
    def test_single_task_complete(self, tmp_path):
        ar, updates = make_alloc_runner(tmp_path, {"web": {"run_for": "50ms"}})
        ar.run()
        assert ar.wait(5.0)
        assert wait_until(lambda: updates and updates[-1].client_status ==
                          s.ALLOC_CLIENT_STATUS_COMPLETE)

    def test_multi_task_running(self, tmp_path):
        ar, updates = make_alloc_runner(
            tmp_path, {"a": {"run_for": "30s"}, "b": {"run_for": "30s"}})
        ar.run()
        assert wait_until(lambda: updates and updates[-1].client_status ==
                          s.ALLOC_CLIENT_STATUS_RUNNING)
        ar.destroy()
        assert ar.wait(5.0)

    def test_failed_task_fails_alloc_and_kills_sibling(self, tmp_path):
        ar, updates = make_alloc_runner(
            tmp_path,
            {"bad": {"run_for": "10ms", "exit_code": 1},
             "good": {"run_for": "60s"}})
        ar.run()
        assert ar.wait(10.0)
        assert wait_until(lambda: updates and updates[-1].client_status ==
                          s.ALLOC_CLIENT_STATUS_FAILED)
        final = updates[-1]
        sibling_events = [e.type for e in final.task_states["good"].events]
        assert s.TASK_SIBLING_FAILED in sibling_events

    def test_get_client_status(self):
        ts = {"a": s.TaskState(state=s.TASK_STATE_RUNNING)}
        assert get_client_status(ts) == s.ALLOC_CLIENT_STATUS_RUNNING
        ts["b"] = s.TaskState(state=s.TASK_STATE_DEAD, failed=True)
        assert get_client_status(ts) == s.ALLOC_CLIENT_STATUS_FAILED
        assert get_client_status(
            {"a": s.TaskState(state=s.TASK_STATE_DEAD)}) == \
            s.ALLOC_CLIENT_STATUS_COMPLETE


# ---------------------------------------------------------------------------
# GC


class TestGC:
    def _terminal_runner(self, tmp_path, name):
        ar, _ = make_alloc_runner(tmp_path / name, {"t": {"run_for": "1ms"}})
        ar.run()
        ar.wait(5.0)
        return ar

    def test_make_room_for_evicts(self, tmp_path):
        cfg = ClientConfig(alloc_dir=str(tmp_path), gc_max_allocs=2)
        gc = AllocGarbageCollector(cfg, stats_path=str(tmp_path))
        r1 = self._terminal_runner(tmp_path, "a1")
        gc.mark_for_collection(r1)
        assert gc.count() == 1
        gc.make_room_for(0, total_live_allocs=2)
        assert gc.count() == 0

    def test_collect_all(self, tmp_path):
        cfg = ClientConfig(alloc_dir=str(tmp_path))
        gc = AllocGarbageCollector(cfg, stats_path=str(tmp_path))
        for n in ("a", "b"):
            gc.mark_for_collection(self._terminal_runner(tmp_path, n))
        assert gc.collect_all() == 2
        assert gc.count() == 0


class TestGitGetter:
    def test_git_clone_artifact(self, tmp_path):
        """go-getter git:: support (client/getter wraps go-getter)."""
        import subprocess

        from nomad_tpu.client.getter import get_artifact
        from nomad_tpu.client.driver.env import TaskEnv
        from nomad_tpu.structs import structs as s

        src_repo = tmp_path / "srcrepo"
        src_repo.mkdir()
        subprocess.run(["git", "init", "-q", str(src_repo)], check=True)
        (src_repo / "hello.txt").write_text("from git")
        env = {"GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
               "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}
        import os as _os
        subprocess.run(["git", "-C", str(src_repo), "add", "."], check=True)
        subprocess.run(["git", "-C", str(src_repo), "commit", "-q", "-m", "x"],
                       check=True, env={**_os.environ, **env})

        task_dir = tmp_path / "task"
        task_dir.mkdir()
        art = s.TaskArtifact(getter_source=f"git::file://{src_repo}",
                             relative_dest="local/")
        dest = get_artifact(TaskEnv(), art, str(task_dir))
        assert (pathlib_path := __import__("pathlib").Path(dest) / "hello.txt").exists()
        assert pathlib_path.read_text() == "from git"


class TestDriverFieldSchemas:
    """helper/fields FieldData.Validate role: typed driver-config
    validation through the shared schema."""

    def test_schema_validation(self):
        from nomad_tpu.client.driver.fields import FieldSchema, validate_fields

        schema = {"command": FieldSchema("string", required=True),
                  "args": FieldSchema("list"),
                  "count": FieldSchema("int"),
                  "verbose": FieldSchema("bool")}
        assert validate_fields({"command": "/bin/x"}, schema) == []
        assert "missing required field 'command'" in \
            validate_fields({}, schema)[0]
        probs = validate_fields({"command": 5, "args": "no",
                                 "count": "x"}, schema)
        assert len(probs) == 3
        assert validate_fields({"command": "x", "bogus": 1}, schema,
                               strict=True) != []

    def test_driver_validates_config(self):
        from nomad_tpu.client.driver.driver import validate_driver_config
        import pytest as _pytest

        validate_driver_config("exec", {"command": "/bin/true"})
        with _pytest.raises(ValueError):
            validate_driver_config("exec", {})
        with _pytest.raises(ValueError):
            validate_driver_config("exec", {"command": 123})
        with _pytest.raises(ValueError):
            validate_driver_config("qemu", {})
        validate_driver_config("java", {"jar_path": "a.jar"})
        with _pytest.raises(ValueError):
            validate_driver_config("java", {})

    def test_invalid_config_fails_task_cleanly(self, tmp_path):
        """An invalid driver config must surface as a driver failure
        event, not a crash."""
        import time

        from nomad_tpu import mock
        from nomad_tpu.client import Client, ClientConfig
        from nomad_tpu.server import Server, ServerConfig
        from nomad_tpu.structs import structs as s

        srv = Server(ServerConfig(num_schedulers=1))
        srv.start()
        client = None
        try:
            client = Client(ClientConfig(
                alloc_dir=str(tmp_path / "allocs")), rpc=srv)
            client.start()
            deadline = time.time() + 20
            while time.time() < deadline:
                n = srv.node_get(client.node.id)
                if n is not None and n.status == "ready":
                    break
                time.sleep(0.05)
            job = mock.job()
            tg = job.task_groups[0]
            tg.count = 1
            tg.restart_policy = s.RestartPolicy(attempts=0, mode="fail")
            for t in tg.tasks:
                t.driver = "mock_driver"
                t.config = {"exit_code": "not-an-int"}  # schema violation
                t.resources.networks = []
                t.services = []
            srv.job_register(job)
            deadline = time.time() + 20
            failed = False
            while time.time() < deadline and not failed:
                for a in srv.job_allocations(job.id):
                    st = (a.task_states or {}).get("web")
                    if st and any("exit_code" in (e.message or "")
                                  and "int" in (e.message or "")
                                  for e in st.events):
                        failed = True
                time.sleep(0.1)
            assert failed, "schema violation never surfaced in task events"
        finally:
            if client is not None:
                client.shutdown()
            srv.shutdown()


class TestCgroupIsolation:
    """executor_linux.go cgroup isolation: exec-family tasks land in a
    per-task cgroup with memory/cpu limits, destroyed with the task."""

    def test_exec_task_runs_in_cgroup(self, tmp_path):
        import subprocess
        import time as _time

        from nomad_tpu.client.driver import cgroups
        from nomad_tpu.client.driver.executor import ExecCommand, Executor

        if not cgroups.available():
            import pytest as _pytest
            _pytest.skip("cgroups not writable on this host")

        cmd = ExecCommand(
            cmd="/bin/sh", args=["-c", "sleep 5"],
            cwd=str(tmp_path), task_name="cg-test",
            memory_limit_mb=64, cpu_limit=100,
            use_cgroups=True, cgroup_name="test-cg-task")
        ex = Executor(cmd)
        pid = ex.launch()
        try:
            assert ex.cgroup is not None and ex.cgroup.paths
            deadline = _time.time() + 5
            while _time.time() < deadline and pid not in ex.cgroup.pids():
                _time.sleep(0.05)
            assert pid in ex.cgroup.pids(), "pid never joined the cgroup"
            mem_path = ex.cgroup.paths[0]
            import os as _os
            if _os.path.exists(_os.path.join(mem_path,
                                             "memory.limit_in_bytes")):
                limit = int(open(_os.path.join(
                    mem_path, "memory.limit_in_bytes")).read())
            else:
                limit = int(open(_os.path.join(mem_path,
                                               "memory.max")).read())
            assert limit == 64 * 1024 * 1024
        finally:
            ex.shutdown(grace=0.2)
            ex.exited.wait(10)
        # group destroyed with the task
        assert ex.cgroup is None

    def test_cgroup_destroy_reaps_stragglers(self, tmp_path):
        import time as _time

        from nomad_tpu.client.driver import cgroups

        if not cgroups.available():
            import pytest as _pytest
            _pytest.skip("cgroups not writable on this host")

        import subprocess
        cg = cgroups.TaskCgroup("straggler-test", memory_mb=32)
        assert cg.create()
        proc = subprocess.Popen(["sleep", "30"])
        cg.add_pid(proc.pid)
        assert proc.pid in cg.pids()
        cg.destroy()
        deadline = _time.time() + 5
        while _time.time() < deadline and proc.poll() is None:
            _time.sleep(0.05)
        assert proc.poll() is not None, "straggler survived cgroup destroy"
        proc.wait()
