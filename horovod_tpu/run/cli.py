"""``hvdrun`` — the launch CLI (reference bin/horovodrun → run/run.py).

Where the reference discovers routable NICs and then execs ``mpirun`` with
interface flags and ``env -x`` forwarding (run/run.py:458-481), hvdrun uses
the same discovery machinery to choose a coordinator address and then
spawns every worker process itself — locally via subprocess, remotely via
ssh — with the rendezvous exported through environment variables:

    HVD_COORDINATOR_ADDR  host:port of the jax.distributed coordinator
    HVD_NUM_PROC          total worker count (== -np)
    HVD_PROCESS_ID        this worker's global rank
    HVD_LOCAL_RANK/SIZE   rank/size within the host
    HVD_CROSS_RANK/SIZE   host index / host count (GLOBAL/LOCAL/CROSS
                          communicator parity, reference mpi_context.h:40-49)

``hvd.init()`` reads these to call jax.distributed.initialize, the TPU
analogue of MPI_Init inside the background thread (operations.cc:869-888).
"""

import argparse
import base64
import glob
import os
import signal
import socket
import sys
import time

from . import cache as cache_mod
from . import exec_util, hosts, secret, services, task_fn
from .settings import Settings, Timeout


from .network import free_port as _free_port  # shared socket idiom


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu training job.",
        usage="hvdrun -np N [-H hosts] command...")
    p.add_argument("-np", "--num-proc", type=int, required=True,
                   help="Total number of worker processes.")
    p.add_argument("-H", "--hosts", default=None,
                   help="Comma-separated host:slots list "
                        "(default: localhost:np).")
    p.add_argument("-p", "--ssh-port", type=int, default=None,
                   help="SSH port for remote hosts.")
    p.add_argument("--start-timeout", type=int,
                   default=int(os.environ.get("HOROVOD_START_TIMEOUT", 600)),
                   help="Seconds to wait for all workers to start.")
    p.add_argument("--disable-cache", action="store_true",
                   help="Do not reuse cached ssh/interface check results.")
    p.add_argument("--verbose", "-v", action="count", default=0)
    p.add_argument("--output-dir", default=None,
                   help="Redirect each rank's stdout/stderr to "
                        "<dir>/rank.<i>.{out,err}.")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="Training command, e.g. python train.py")
    args = p.parse_args(argv)
    if not args.command:
        p.error("no command given")
    if args.command[0] == "--":
        args.command = args.command[1:]
    return args


def _discover_coordinator_ip(host_list, settings):
    """Find an IP every host can route to (reference run/run.py:188-257).

    Starts the driver service, ssh-launches one probe task per remote
    host, waits for ring-probe results, intersects interfaces, and returns
    the launcher's address on one common interface.
    """
    driver = services.LaunchDriverService(len(host_list), settings.key)
    procs = []
    try:
        addrs_b64 = task_fn.codec_dumps(driver.addresses())
        key_b64 = base64.b64encode(settings.key).decode("ascii")
        for i, h in enumerate(host_list):
            cmd = [sys.executable, "-m", "horovod_tpu.run.task_fn",
                   str(i), str(len(host_list)), addrs_b64]
            if hosts.is_local(h.hostname):
                env = exec_util.filtered_env(
                    {secret.HVD_SECRET_KEY: key_b64})
                procs.append(exec_util.safe_execute(cmd, env=env))
            else:
                ssh = ["ssh"] + hosts.SSH_OPTS
                if settings.ssh_port:
                    ssh += ["-p", str(settings.ssh_port)]
                remote = ["env", f"{secret.HVD_SECRET_KEY}={key_b64}"] + \
                    exec_util.forwarded_env_flags(quote=True) + \
                    exec_util.quote_argv(cmd)
                procs.append(exec_util.safe_execute(
                    ssh + [h.hostname] + remote))
        timeout = Timeout(settings.start_timeout_s,
                          "Timed out waiting for launch probe tasks. "
                          "Check ssh connectivity and firewalls.")
        driver.wait_for_initial_registration(timeout)
        driver.wait_for_task_to_task_addresses(timeout)
        common = driver.common_interfaces()
        if settings.verbose:
            print(f"hvdrun: common interfaces: {sorted(common)}")
        # Tell probes to exit.
        for i in range(len(host_list)):
            try:
                services.LaunchTaskClient(
                    i, driver.task_addresses(i), settings.key).shutdown_task()
            # hvdlint: disable=HVD006(best-effort farewell to probe tasks already being torn down)
            except Exception:
                pass
        # jax.distributed has process 0 BIND the coordinator socket, so the
        # address must belong to the host that runs rank 0 (host_list[0]),
        # not the launcher — horovodrun may be invoked from a machine
        # outside the host list. Task 0's registration gives us its IP on
        # a commonly-routable interface.
        rank0_addrs = driver.task_addresses(0)
        for iface in sorted(common):
            if iface in rank0_addrs:
                return rank0_addrs[iface][0][0]
        raise RuntimeError(
            f"Rank-0 host {host_list[0].hostname} has no address on common "
            f"interfaces {common}")
    finally:
        for proc in procs:
            exec_util.terminate_tree(proc, grace_s=1.0)
        driver.shutdown()


def _rank_env(rank, local_rank, host_index, h, n_proc, n_hosts,
              coordinator_addr):
    return {
        "HVD_COORDINATOR_ADDR": coordinator_addr,
        "HVD_NUM_PROC": n_proc,
        "HVD_PROCESS_ID": rank,
        "HVD_LOCAL_RANK": local_rank,
        "HVD_LOCAL_SIZE": h.slots,
        "HVD_CROSS_RANK": host_index,
        "HVD_CROSS_SIZE": n_hosts,
    }


# TPU_PROCESS_BOUNDS for one process per chip, by the host's chip count
# (the single-host topologies: v5e-4 is a 2x2, v5e-8 a 2x4)
_TPU_PROCESS_BOUNDS = {4: "2,2,1", 8: "2,4,1"}
_TPU_PROCESS_PORT = 8476  # libtpu's conventional base port


def _tpu_slot_envs(host_list, extra_env):
    """libtpu's own per-process variables, one dict per local rank, that
    give every rank of a single local host ONE of its TPU chips — the
    reference's one-rank-one-accelerator model (docs/tpus.md, "Chips vs
    processes"). Without them each rank tries to open every chip and all
    but the first fail. None when that does not apply: several hosts,
    ranks pinned off the TPU by JAX_PLATFORMS, or a rank count that is
    not the host's chip count. Chips are counted by their device files:
    a launcher that asked JAX would hold the chips its children need."""
    if len(host_list) != 1 or not hosts.is_local(host_list[0].hostname):
        return None
    platforms = (extra_env or {}).get("JAX_PLATFORMS",
                                      os.environ.get("JAX_PLATFORMS", ""))
    if platforms and "tpu" not in platforms.split(","):
        return None
    n = host_list[0].slots
    chips = len(glob.glob("/dev/vfio/[0-9]*"))  # one file per v5e chip
    if n < 2 or chips != n or n not in _TPU_PROCESS_BOUNDS:
        return None
    addresses = ",".join(f"localhost:{_TPU_PROCESS_PORT + i}"
                         for i in range(n))
    return [{"TPU_VISIBLE_CHIPS": i,
             "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
             "TPU_PROCESS_BOUNDS": _TPU_PROCESS_BOUNDS[n],
             "TPU_PROCESS_ADDRESSES": addresses,
             "TPU_PROCESS_PORT": _TPU_PROCESS_PORT + i,
             "CLOUD_TPU_TASK_ID": i} for i in range(n)]


def run_command_on_hosts(host_list, command, coordinator_addr, settings,
                         output_dir=None, extra_env=None, cancel_event=None):
    """Spawn every worker, wait, propagate first failure. Returns exit
    code. Setting cancel_event terminates all workers (exit 130)."""
    n_proc = sum(h.slots for h in host_list)
    tpu_slots = _tpu_slot_envs(host_list, extra_env)
    procs = []
    files = []
    exit_code = 0
    try:
        rank = 0
        for host_index, h in enumerate(host_list):
            for local_rank in range(h.slots):
                env_over = _rank_env(rank, local_rank, host_index, h, n_proc,
                                     len(host_list), coordinator_addr)
                if tpu_slots:
                    env_over.update(tpu_slots[local_rank])
                if extra_env:
                    env_over.update(extra_env)
                stdout = stderr = None
                if output_dir:
                    os.makedirs(output_dir, exist_ok=True)
                    stdout = open(os.path.join(output_dir,
                                               f"rank.{rank}.out"), "wb")
                    stderr = open(os.path.join(output_dir,
                                               f"rank.{rank}.err"), "wb")
                    files += [stdout, stderr]
                if hosts.is_local(h.hostname):
                    env = exec_util.filtered_env(env_over)
                    procs.append(exec_util.safe_execute(
                        command, env=env, stdout=stdout, stderr=stderr))
                else:
                    ssh = ["ssh"] + hosts.SSH_OPTS
                    if settings.ssh_port:
                        ssh += ["-p", str(settings.ssh_port)]
                    remote = ["env"] + \
                        exec_util.quote_argv(
                            f"{k}={v}" for k, v in env_over.items()) + \
                        exec_util.forwarded_env_flags(quote=True) + \
                        exec_util.quote_argv(command)
                    procs.append(exec_util.safe_execute(
                        ssh + [h.hostname] + remote,
                        stdout=stdout, stderr=stderr))
                rank += 1

        pending = set(range(len(procs)))
        while pending:
            if cancel_event is not None and cancel_event.is_set():
                exec_util.terminate_trees([procs[j] for j in sorted(pending)])
                exit_code = exit_code or 130
                break
            for i in sorted(pending):
                rc = procs[i].poll()
                if rc is None:
                    continue
                pending.discard(i)
                if rc != 0 and exit_code == 0:
                    exit_code = rc
                    # One failed worker aborts the job, as an MPI abort
                    # would (reference semantics of mpirun).
                    exec_util.terminate_trees(
                        [procs[j] for j in sorted(pending)])
                    pending.clear()
                    break
            time.sleep(0.2)
    except BaseException:
        # Spawn failure mid-loop, Ctrl-C, or a supervisor's SIGTERM
        # (rerouted to SystemExit in main): never leak already-started
        # workers — parallel group kill, so the whole cleanup fits
        # inside any reasonable supervisor kill-grace window.
        exec_util.terminate_trees(procs)
        if isinstance(sys.exc_info()[1], KeyboardInterrupt):
            exit_code = 130
        else:
            raise
    finally:
        for f in files:
            f.close()
    return exit_code


def main(argv=None):
    args = parse_args(argv)
    host_list = (hosts.parse_hosts(args.hosts) if args.hosts
                 else [hosts.HostSlots("localhost", args.num_proc)])
    n_slots = sum(h.slots for h in host_list)
    if n_slots < args.num_proc:
        sys.exit(f"hvdrun: -np {args.num_proc} but only {n_slots} slots in "
                 f"host list")

    key_env = os.environ.get("HOROVOD_SECRET_KEY") or \
        os.environ.get("HVD_SECRET_KEY")
    settings = Settings(
        num_proc=args.num_proc, hosts=host_list, command=args.command,
        key=(base64.b64decode(key_env) if key_env
             else secret.make_secret_key()),
        start_timeout_s=args.start_timeout, ssh_port=args.ssh_port,
        verbose=args.verbose)

    remote = [h.hostname for h in host_list
              if not hosts.is_local(h.hostname)]
    if remote:
        fn_cache = None if args.disable_cache else cache_mod.Cache()
        hosts.check_all_hosts_ssh_successful(remote, fn_cache=fn_cache)
        coordinator_ip = _discover_coordinator_ip(host_list, settings)
    else:
        coordinator_ip = "127.0.0.1"

    # The coordinator socket is bound by rank 0 (on host_list[0]); probing
    # a free port is only meaningful when that host is this machine.
    if hosts.is_local(host_list[0].hostname):
        coordinator_port = _free_port()
    else:
        import random
        coordinator_port = random.randrange(30000, 60000)
    coordinator_addr = f"{coordinator_ip}:{coordinator_port}"
    if args.verbose:
        print(f"hvdrun: launching {args.num_proc} processes on "
              f"{len(host_list)} host(s); coordinator {coordinator_addr}")
    # Workers run in their OWN process groups (exec_util.safe_execute
    # start_new_session), so a SIGTERM to hvdrun alone would strand them
    # training headless — exactly how a supervisor (run/elastic.py) or a
    # scheduler stops a job. Convert it to SystemExit so
    # run_command_on_hosts' cleanup path terminates every worker tree
    # before exiting. Main-thread only; library callers (launch.run)
    # drive cancellation via cancel_event instead.
    try:
        signal.signal(signal.SIGTERM,
                      lambda signum, frame: sys.exit(143))
    except ValueError:
        pass  # not the main thread
    # Export the per-job secret to every worker: the negotiated eager
    # control plane derives its HMAC key from it (ops/negotiation.py
    # control_key) — without it workers fall back to the strict
    # same-order contract (launch.py run() exports it the same way).
    key_b64 = base64.b64encode(settings.key).decode("ascii")
    sys.exit(run_command_on_hosts(host_list, args.command, coordinator_addr,
                                  settings, output_dir=args.output_dir,
                                  extra_env={secret.HVD_SECRET_KEY:
                                             key_b64}))


if __name__ == "__main__":
    main()
