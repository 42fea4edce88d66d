"""Job submission + CLI tests.

Reference test models: ``dashboard/modules/job/tests/test_job_manager.py``
(lifecycle: submit/status/logs/stop) and the `ray job submit` CLI flow —
here driven end-to-end against a real head daemon OS process."""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private.worker import global_worker
from ray_tpu.job_submission import JobManager, JobStatus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


class TestJobManager:
    @pytest.fixture
    def jm(self, ray_start_regular):
        manager = JobManager(global_worker().cluster)
        yield manager
        manager.shutdown()

    def test_submit_and_succeed(self, jm, tmp_path):
        script = tmp_path / "ok.py"
        script.write_text("print('hello from job')\n")
        job_id = jm.submit_job(f"{sys.executable} {script}")
        assert jm.wait_job(job_id, timeout=60) == JobStatus.SUCCEEDED
        assert "hello from job" in jm.get_job_logs(job_id)

    def test_failure_reported(self, jm, tmp_path):
        script = tmp_path / "bad.py"
        script.write_text("raise SystemExit(3)\n")
        job_id = jm.submit_job(f"{sys.executable} {script}")
        assert jm.wait_job(job_id, timeout=60) == JobStatus.FAILED
        assert "exited with code 3" in jm.get_job_info(job_id).message

    def test_stop_job(self, jm, tmp_path):
        script = tmp_path / "spin.py"
        script.write_text("import time\ntime.sleep(120)\n")
        job_id = jm.submit_job(f"{sys.executable} {script}")
        deadline = time.monotonic() + 10
        while jm.get_job_status(job_id) != JobStatus.RUNNING and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        assert jm.stop_job(job_id)
        assert jm.wait_job(job_id, timeout=30) == JobStatus.STOPPED

    def test_runtime_env_working_dir_and_env_vars(self, jm, tmp_path):
        wd = tmp_path / "proj"
        wd.mkdir()
        (wd / "cfg.txt").write_text("42")
        (wd / "main.py").write_text(
            "import os\n"
            "print('CFG', open('cfg.txt').read())\n"
            "print('VAR', os.environ['JOB_FLAVOR'])\n")
        job_id = jm.submit_job(
            f"{sys.executable} main.py",
            runtime_env={"working_dir": str(wd),
                         "env_vars": {"JOB_FLAVOR": "salty"}})
        assert jm.wait_job(job_id, timeout=60) == JobStatus.SUCCEEDED
        logs = jm.get_job_logs(job_id)
        assert "CFG 42" in logs and "VAR salty" in logs

    def test_list_jobs(self, jm, tmp_path):
        script = tmp_path / "noop.py"
        script.write_text("pass\n")
        ids = {jm.submit_job(f"{sys.executable} {script}")
               for _ in range(3)}
        for job_id in ids:
            jm.wait_job(job_id, timeout=60)
        assert ids <= {j.submission_id for j in jm.list_jobs()}


@pytest.fixture(scope="class")
def head_daemon(tmp_path_factory):
    """A real head daemon OS process with the wire + job surface up."""
    tmp = tmp_path_factory.mktemp("head")
    address_file = str(tmp / "head_address")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_main",
         "--num-cpus", "2", "--address-file", address_file,
         "--system-config",
         '{"scheduler_backend": "native"}'],
        env=_env())
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and not os.path.exists(address_file):
        assert proc.poll() is None, "head daemon died on startup"
        time.sleep(0.1)
    with open(address_file) as f:
        address = f.read().strip()
    yield {"address": address, "address_file": address_file, "proc": proc}
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


class TestCliAgainstRunningHead:
    def _cli(self, head, *args, timeout=120):
        return subprocess.run(
            [sys.executable, "-m", "ray_tpu",
             *args],
            env=_env(), capture_output=True, text=True, timeout=timeout)

    def test_status(self, head_daemon):
        out = self._cli(head_daemon, "status",
                        "--address", head_daemon["address"])
        assert out.returncode == 0, out.stderr
        assert "ALIVE" in out.stdout
        assert "CPU" in out.stdout

    def test_submit_working_dir_end_to_end(self, head_daemon, tmp_path):
        """The acceptance line: `submit --working-dir . script.py`
        runs end-to-end against a running head."""
        wd = tmp_path / "app"
        wd.mkdir()
        (wd / "app.py").write_text(
            "import data\n"
            "print('RESULT', data.VALUE * 2)\n")
        (wd / "data.py").write_text("VALUE = 21\n")
        out = self._cli(head_daemon, "submit",
                        "--address", head_daemon["address"],
                        "--working-dir", str(wd),
                        "--env", "EXTRA=yes",
                        "--", sys.executable, "app.py")
        assert out.returncode == 0, out.stdout + out.stderr
        assert "RESULT 42" in out.stdout
        assert "SUCCEEDED" in out.stdout

    def test_jobs_listing_and_logs(self, head_daemon, tmp_path):
        wd = tmp_path / "app2"
        wd.mkdir()
        (wd / "go.py").write_text("print('from-job-two')\n")
        sub = self._cli(head_daemon, "submit",
                        "--address", head_daemon["address"],
                        "--working-dir", str(wd),
                        "--submission-id", "job-two",
                        "--", sys.executable, "go.py")
        assert sub.returncode == 0, sub.stdout + sub.stderr
        listing = self._cli(head_daemon, "jobs",
                            "--address", head_daemon["address"])
        assert "job-two" in listing.stdout
        logs = self._cli(head_daemon, "logs", "job-two",
                         "--address", head_daemon["address"])
        assert "from-job-two" in logs.stdout

    def test_worker_host_join_via_cli(self, head_daemon):
        out = self._cli(head_daemon, "start",
                        "--address", head_daemon["address"],
                        "--num-cpus", "1",
                        "--resources", '{"joined": 1}',
                        "--name", "cli-joined")
        assert out.returncode == 0, out.stderr
        deadline = time.monotonic() + 30
        seen = False
        while time.monotonic() < deadline and not seen:
            status = self._cli(head_daemon, "status",
                               "--address", head_daemon["address"])
            seen = "cli-joined" in status.stdout
            time.sleep(0.3)
        assert seen, "CLI-started worker host never appeared in status"


class TestCliMemoryTimelineUp:
    def _cli(self, *args, timeout=120):
        return subprocess.run(
            [sys.executable, "-m", "ray_tpu", *args],
            env=_env(), capture_output=True, text=True, timeout=timeout)

    def test_memory_and_timeline(self, head_daemon, tmp_path):
        out = self._cli("memory", "--address", head_daemon["address"])
        assert out.returncode == 0, out.stderr
        assert "OBJECTS" in out.stdout and "CAPACITY" in out.stdout
        dump = tmp_path / "tl.json"
        out = self._cli("timeline", "--address", head_daemon["address"],
                        "-o", str(dump))
        assert out.returncode == 0, out.stderr
        import json as json_mod
        assert isinstance(json_mod.loads(dump.read_text()), list)

    def test_latency_verb(self, head_daemon):
        """`ray-tpu latency`: dispatch-latency decomposition served by
        the head (table + json)."""
        out = self._cli("latency", "--address", head_daemon["address"])
        assert out.returncode == 0, out.stderr
        assert "STAGE" in out.stdout and "P99_MS" in out.stdout
        out = self._cli("latency", "--address", head_daemon["address"],
                        "--output", "json")
        assert out.returncode == 0, out.stderr
        import json as json_mod
        stages = json_mod.loads(out.stdout)
        assert isinstance(stages, dict)
        # Stage rows appear once any task ran through the head's GCS;
        # rows that do exist must be shaped right.
        for row in stages.values():
            assert {"count", "p50_s", "p99_s"} <= set(row)

    def test_up_launches_local_cluster(self, tmp_path):
        """`up` from a YAML config: head + 2 worker-hosts, visible in
        `status`, stopped by `down` (reference cluster launcher shape,
        local provider)."""
        cfg = tmp_path / "cluster.yaml"
        cfg.write_text(
            "head:\n"
            "  num_cpus: 1\n"
            "workers:\n"
            "  - count: 2\n"
            "    resources:\n"
            "      CPU: 1\n"
            "      spoke: 2\n")
        addr_file = str(tmp_path / "addr.txt")
        out = self._cli("up", str(cfg), "--address-file", addr_file,
                        timeout=180)
        assert out.returncode == 0, out.stdout + out.stderr
        address = open(addr_file).read().strip()
        try:
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                st = self._cli("status", "--address", address)
                if st.returncode == 0 and \
                        st.stdout.count("ALIVE") >= 3:
                    break
                time.sleep(1.0)
            assert st.stdout.count("ALIVE") >= 3, st.stdout
            assert "spoke" in st.stdout
        finally:
            self._cli("down", "--address", address)
