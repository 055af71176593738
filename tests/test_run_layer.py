"""Launch-layer tests (reference test strategy: run/ services are exercised
end-to-end in test_spark.py:51-110; here we unit-test the pieces plus a real
local hvdrun launch)."""

import base64
import io
import os
import subprocess
import sys
import time

import pytest

from horovod_tpu.run import cache as cache_mod
from horovod_tpu.run import exec_util, hosts, network, secret, services
from horovod_tpu.run.cli import run_command_on_hosts
from horovod_tpu.run.settings import Settings, Timeout, TimeoutException


class TestWire:
    def test_roundtrip(self):
        key = secret.make_secret_key()
        wire = network.Wire(key)
        buf = io.BytesIO()
        wire.write({"hello": [1, 2, 3]}, buf)
        buf.seek(0)
        assert wire.read(buf) == {"hello": [1, 2, 3]}

    def test_tampered_payload_rejected(self):
        key = secret.make_secret_key()
        wire = network.Wire(key)
        buf = io.BytesIO()
        wire.write("payload", buf)
        raw = bytearray(buf.getvalue())
        raw[-1] ^= 0xFF
        with pytest.raises(RuntimeError, match="Security error"):
            wire.read(io.BytesIO(bytes(raw)))

    def test_wrong_key_rejected(self):
        w1 = network.Wire(secret.make_secret_key())
        w2 = network.Wire(secret.make_secret_key())
        buf = io.BytesIO()
        w1.write("x", buf)
        buf.seek(0)
        with pytest.raises(RuntimeError, match="Security error"):
            w2.read(buf)


class TestServices:
    def test_ping_and_register(self):
        key = secret.make_secret_key()
        driver = services.LaunchDriverService(num_tasks=2, key=key)
        try:
            addrs = {"lo": [("127.0.0.1", driver.port)]}
            client = services.LaunchDriverClient(addrs, key)
            client.register_task(0, {"lo": [("127.0.0.1", 1)]}, "h0")
            client.register_task(1, {"lo": [("127.0.0.1", 2)]}, "h1")
            driver.wait_for_initial_registration(
                Timeout(5, "registration timed out"))
            assert client.all_task_addresses(1) == {"lo": [("127.0.0.1", 2)]}
            assert driver.task_host_hashes() == {0: "h0", 1: "h1"}
        finally:
            driver.shutdown()

    def test_wrong_key_cannot_connect(self):
        key = secret.make_secret_key()
        driver = services.LaunchDriverService(num_tasks=1, key=key)
        try:
            addrs = {"lo": [("127.0.0.1", driver.port)]}
            with pytest.raises(network.NoValidAddressesFound):
                services.LaunchDriverClient(addrs, secret.make_secret_key(),
                                            probe_timeout=0.5)
        finally:
            driver.shutdown()

    def test_common_interfaces_intersection(self):
        key = secret.make_secret_key()
        driver = services.LaunchDriverService(num_tasks=2, key=key)
        try:
            client = services.LaunchDriverClient(
                {"lo": [("127.0.0.1", driver.port)]}, key)
            client.register_task_to_task_addresses(
                0, {"eth0": [("10.0.0.1", 1)], "ib0": [("10.1.0.1", 1)]})
            client.register_task_to_task_addresses(
                1, {"eth0": [("10.0.0.2", 1)]})
            driver.wait_for_task_to_task_addresses(Timeout(5, "t"))
            assert driver.common_interfaces() == {"eth0"}
        finally:
            driver.shutdown()

    def test_task_service_runs_command(self, tmp_path):
        key = secret.make_secret_key()
        task = services.LaunchTaskService(0, key)
        try:
            client = services.LaunchTaskClient(
                0, {"lo": [("127.0.0.1", task.port)]}, key)
            marker = tmp_path / "ran"
            client.run_command(
                [sys.executable, "-c",
                 f"open({str(marker)!r}, 'w').write('ok')"])
            deadline = time.time() + 10
            while time.time() < deadline:
                terminated, code = client.command_exit_code()
                if terminated:
                    break
                time.sleep(0.1)
            assert terminated and code == 0
            assert marker.read_text() == "ok"
        finally:
            task.shutdown()


class TestHosts:
    def test_parse(self):
        hs = hosts.parse_hosts("a:2,b:4,c")
        assert [(h.hostname, h.slots) for h in hs] == \
            [("a", 2), ("b", 4), ("c", 1)]

    def test_parse_empty_raises(self):
        with pytest.raises(ValueError):
            hosts.parse_hosts(" , ")

    def test_expand_slots(self):
        hs = hosts.parse_hosts("a:2,b:1")
        expanded = hosts.expand_slots(hs)
        assert [(r, h.hostname, lr) for r, h, lr in expanded] == \
            [(0, "a", 0), (1, "a", 1), (2, "b", 0)]

    def test_localhost_is_local(self):
        assert hosts.is_local("localhost")
        assert hosts.is_local("127.0.0.1")
        assert not hosts.is_local("definitely-not-this-host.example")

    def test_host_hash_stable(self):
        assert hosts.host_hash() == hosts.host_hash()


class TestExecUtil:
    def test_env_filter(self):
        env = exec_util.filtered_env({"HVD_PROCESS_ID": 3})
        assert env["HVD_PROCESS_ID"] == "3"
        assert "OLDPWD" not in env

    def test_forwarded_flags(self):
        flags = exec_util.forwarded_env_flags(
            {"HOROVOD_FUSION_THRESHOLD": "1", "HOME": "/x", "OLDPWD": "/y"})
        assert flags == ["HOROVOD_FUSION_THRESHOLD=1"]

    def test_safe_execute_and_terminate(self):
        proc = exec_util.safe_execute([sys.executable, "-c",
                                       "import time; time.sleep(60)"])
        assert proc.poll() is None
        exec_util.terminate_tree(proc, grace_s=2.0)
        assert proc.wait(timeout=5) != 0


class TestCacheAndTimeout:
    def test_cache_roundtrip_and_ttl(self, tmp_path):
        c = cache_mod.Cache(cache_dir=str(tmp_path), ttl_s=1000)
        assert c.get(("ssh", "h")) is None
        c.put(("ssh", "h"), True)
        assert c.get(("ssh", "h")) is True
        # persisted across instances
        c2 = cache_mod.Cache(cache_dir=str(tmp_path), ttl_s=1000)
        assert c2.get(("ssh", "h")) is True
        # expired
        c3 = cache_mod.Cache(cache_dir=str(tmp_path), ttl_s=0)
        assert c3.get(("ssh", "h")) is None

    def test_timeout(self):
        t = Timeout(0.0, "boom")
        time.sleep(0.01)
        with pytest.raises(TimeoutException, match="boom"):
            t.check()


class TestLocalLaunch:
    """End-to-end: run_command_on_hosts spawns N local workers with correct
    rank env and propagates failures (reference run/run.py:458-481 parity,
    minus mpirun)."""

    def test_two_local_workers_env(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(
            "import os\n"
            "out = os.path.join(os.environ['OUT'], "
            "'r' + os.environ['HVD_PROCESS_ID'])\n"
            "open(out, 'w').write('|'.join([\n"
            "    os.environ['HVD_NUM_PROC'], os.environ['HVD_LOCAL_RANK'],\n"
            "    os.environ['HVD_COORDINATOR_ADDR']]))\n")
        os.environ["OUT"] = str(tmp_path)
        try:
            rc = run_command_on_hosts(
                hosts.parse_hosts("localhost:2"),
                [sys.executable, str(script)],
                "127.0.0.1:12345", Settings())
        finally:
            del os.environ["OUT"]
        assert rc == 0
        assert (tmp_path / "r0").read_text() == "2|0|127.0.0.1:12345"
        assert (tmp_path / "r1").read_text() == "2|1|127.0.0.1:12345"

    def test_tpu_slot_envs_one_chip_per_rank(self, monkeypatch):
        """A single local host whose chip count equals -np: every rank
        gets libtpu's per-process variables for exactly one chip
        (docs/tpus.md). Anything else — CPU-pinned ranks, a rank count
        that is not the chip count, several hosts — gets none."""
        from horovod_tpu.run import cli
        chips = [f"/dev/vfio/{i}" for i in range(4)]
        monkeypatch.setattr(
            cli.glob, "glob",
            lambda pat: chips if pat == "/dev/vfio/[0-9]*" else [])
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        four = hosts.parse_hosts("localhost:4")
        envs = cli._tpu_slot_envs(four, None)
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == [0, 1, 2, 3]
        assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
        assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
        assert all(e["TPU_PROCESS_ADDRESSES"].count("localhost:") == 4
                   for e in envs)
        assert cli._tpu_slot_envs(four, {"JAX_PLATFORMS": "cpu"}) is None
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert cli._tpu_slot_envs(four, None) is not None
        assert cli._tpu_slot_envs(hosts.parse_hosts("localhost:2"),
                                  None) is None
        assert cli._tpu_slot_envs(
            hosts.parse_hosts("localhost:4,otherhost:4"), None) is None

    def test_failure_propagates(self):
        rc = run_command_on_hosts(
            hosts.parse_hosts("localhost:2"),
            [sys.executable, "-c", "import sys; sys.exit(7)"],
            "127.0.0.1:1", Settings())
        assert rc == 7

    def test_hvdrun_cli_module(self, tmp_path):
        """The installed entry point parses and launches."""
        res = subprocess.run(
            [sys.executable, "-c",
             "from horovod_tpu.run.cli import main; main()",
             "-np", "1", sys.executable, "-c", "print('worker-ok')"],
            capture_output=True, text=True, timeout=120,
            cwd="/root/repo")
        assert res.returncode == 0, res.stderr

    def test_cli_exports_secret_to_workers(self, monkeypatch):
        """The per-job secret must reach every worker's env: the
        negotiated eager control plane derives its HMAC key from it
        (ops/negotiation.py control_key). Regression pin for the
        round-5 fix — without it, hvdrun jobs silently fell back to
        the strict same-order contract."""
        import signal

        import pytest as _pytest

        from horovod_tpu.run import cli, secret

        captured = {}

        def fake_run(host_list, command, coordinator_addr, settings,
                     output_dir=None, extra_env=None, cancel_event=None):
            captured["extra_env"] = extra_env
            return 0

        monkeypatch.setattr(cli, "run_command_on_hosts", fake_run)
        prev = signal.getsignal(signal.SIGTERM)
        try:
            with _pytest.raises(SystemExit) as e:
                cli.main(["-np", "1", "true"])
        finally:
            signal.signal(signal.SIGTERM, prev)  # main() installs one
        assert e.value.code == 0
        assert captured["extra_env"] is not None
        assert secret.HVD_SECRET_KEY in captured["extra_env"]

    def test_terminate_trees_kills_sigterm_ignoring_group(self, tmp_path):
        """terminate_trees must reach its SIGKILL pass promptly even
        when the process ignores SIGTERM (jax's preemption notifier
        swallows it) — the leak mode behind the round-5 elastic-drill
        fix."""
        import time as _time

        from horovod_tpu.run import exec_util

        script = tmp_path / "stubborn.py"
        script.write_text(
            "import signal, time\n"
            "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
            "print('ready', flush=True)\n"
            "time.sleep(60)\n")
        procs = [exec_util.safe_execute(
            [sys.executable, str(script)], stdout=subprocess.PIPE)
            for _ in range(2)]
        for p in procs:
            assert p.stdout.readline().strip() == b"ready"
        t0 = _time.monotonic()
        exec_util.terminate_trees(procs, grace_s=0.5)
        dt = _time.monotonic() - t0
        for p in procs:
            assert p.poll() is not None, "stubborn worker survived"
        # one SHARED grace window, not one per proc
        assert dt < 5.0, dt
