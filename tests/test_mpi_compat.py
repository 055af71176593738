"""mpirun migration path (run/mpi.py): `mpirun -np N python train.py`
must work with ZERO extra env — rank 0 publishes the jax.distributed
rendezvous through the filesystem, keyed by the launcher's job id
(reference parity: run/run.py:458-481 jobs need nothing beyond mpirun's
own environment). mpirun is emulated by exporting the exact env it sets
(OMPI_COMM_WORLD_*), which is all the code under test reads."""

import json
import os
import subprocess
import sys
import time

import pytest

_WORKER = r"""
import numpy as np
import horovod_tpu as hvd
hvd.init()
out = hvd.allreduce(np.full((3,), float(hvd.process_rank()) + 1.0,
                            np.float32), average=False)
print("RESULT", hvd.process_rank(), hvd.process_count(),
      float(np.asarray(out)[0]), flush=True)
hvd.shutdown()
"""


class TestMpirunAutoRendezvous:
    def test_two_ranks_zero_extra_env(self, tmp_path):
        """Two processes with only mpirun's own env (no HVD_*) must form
        the job and allreduce correctly."""
        env_base = {k: v for k, v in os.environ.items()
                    if not k.startswith(("HVD_", "OMPI_", "PMI_"))}
        env_base.update({
            "JAX_PLATFORMS": "cpu",
            "HVD_RENDEZVOUS_DIR": str(tmp_path),
            # per-job id mpirun exports to every rank
            "OMPI_MCA_orte_hnp_uri": "666.0;tcp://10.0.0.1:12345",
            "OMPI_COMM_WORLD_SIZE": "2",
        })
        procs = []
        for rank in range(2):
            env = dict(env_base)
            env["OMPI_COMM_WORLD_RANK"] = str(rank)
            # mpirun also always exports these (jax's OMPI cluster
            # detection reads LOCAL_RANK)
            env["OMPI_COMM_WORLD_LOCAL_RANK"] = str(rank)
            env["OMPI_COMM_WORLD_LOCAL_SIZE"] = "2"
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _WORKER],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
                cwd=os.path.dirname(os.path.dirname(__file__))))
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=180)
            assert p.returncode == 0, out
            outs.append(out)
        for rank, out in enumerate(outs):
            line = [l for l in out.splitlines()
                    if l.startswith("RESULT")][0].split()
            assert line[1:] == [str(rank), "2", "3.0"], out
        # rank 0 cleaned its rendezvous file up at exit
        time.sleep(0.2)
        assert not [f for f in os.listdir(tmp_path)
                    if f.startswith("hvd_mpi_rdzv_")]

    def test_detect_and_key(self, monkeypatch):
        from horovod_tpu.run import mpi as mpi_compat
        for k in ("OMPI_COMM_WORLD_SIZE", "OMPI_COMM_WORLD_RANK",
                  "PMI_SIZE", "PMI_RANK", "SLURM_NTASKS",
                  "SLURM_STEP_NUM_TASKS", "SLURM_PROCID",
                  "OMPI_MCA_orte_hnp_uri", "PMIX_NAMESPACE", "PMI_JOBID",
                  "SLURM_JOB_ID"):
            monkeypatch.delenv(k, raising=False)
        assert mpi_compat.detect_mpi_world() is None
        # sbatch exports SLURM_NTASKS even to a single batch-script
        # process (no srun): must NOT be treated as a multi-rank launch
        monkeypatch.setenv("SLURM_NTASKS", "4")
        monkeypatch.setenv("SLURM_PROCID", "0")
        assert mpi_compat.detect_mpi_world() is None
        # srun sets the per-step task count: that IS a multi-rank launch
        monkeypatch.setenv("SLURM_STEP_NUM_TASKS", "4")
        monkeypatch.setenv("SLURM_PROCID", "3")
        assert mpi_compat.detect_mpi_world() == (4, 3)
        monkeypatch.delenv("SLURM_STEP_NUM_TASKS")
        monkeypatch.delenv("SLURM_NTASKS")
        monkeypatch.setenv("PMI_SIZE", "4")
        monkeypatch.setenv("PMI_RANK", "3")
        assert mpi_compat.detect_mpi_world() == (4, 3)
        # no job-id env: fallback key, flagged non-unique
        key, unique = mpi_compat._job_key()
        assert not unique
        monkeypatch.setenv("SLURM_JOB_ID", "1234")
        key2, unique2 = mpi_compat._job_key()
        assert unique2 and key2 != key

    def test_stale_rendezvous_file_rejected(self, tmp_path, monkeypatch):
        """A leftover file from a crashed previous run (same key, same
        size, old timestamp) must not be trusted."""
        from horovod_tpu.run import mpi as mpi_compat
        monkeypatch.setenv("HVD_RENDEZVOUS_DIR", str(tmp_path))
        monkeypatch.setenv("SLURM_JOB_ID", "zzz")
        key, _ = mpi_compat._job_key()
        stale = {"addr": "10.9.9.9:1", "size": 2,
                 "created": time.time() - 3600}
        with open(mpi_compat._rendezvous_path(key), "w") as f:
            json.dump(stale, f)
        with pytest.raises(RuntimeError, match="no published"):
            mpi_compat.auto_rendezvous(2, 1, timeout_s=1.0)
