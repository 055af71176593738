"""Torch on the native collective plane (torch/native.py over
libhvd_plane.so — the factored TCP-ring plane of _native/src/plane.h;
role of the reference's C torch binding, torch/mpi_ops_v2.cc:52-130).

Multi-process cases spawn real workers via run.launch.run: plane
bootstrap, ring collectives on torch storage (GIL released), fallback
and error surfaces.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")

from horovod_tpu.run.launch import run  # noqa: E402

_ENV = {"JAX_PLATFORMS": "cpu"}


def _plane_available():
    from horovod_tpu.torch import native
    return native.available()


class TestTorchNativePlane:
    def test_hook_driven_optimizer_rides_native_plane(self):
        """The DistributedOptimizer's post-accumulate-grad hooks must go
        through the plane (no eager-core crossing) and still converge to
        the same averaged-gradient update."""
        def fn():
            import os
            import numpy as np
            import torch
            import horovod_tpu.torch as hvd
            from horovod_tpu.torch import native

            hvd.init()
            if not native.available():
                return "unavailable"
            r = int(os.environ["HVD_PROCESS_ID"])
            model = torch.nn.Linear(4, 1, bias=False)
            with torch.no_grad():
                model.weight.fill_(1.0)
            opt = hvd.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=1.0),
                named_parameters=model.named_parameters())
            core_calls = []
            from horovod_tpu.torch import mpi_ops as tops
            orig = tops._core.allreduce_async

            def spy(t, **kw):
                core_calls.append(kw.get("name"))
                return orig(t, **kw)

            tops._core.allreduce_async = spy
            x = torch.full((2, 4), float(r + 1))
            loss = model(x).sum()
            loss.backward()
            opt.step()
            tops._core.allreduce_async = orig
            w = model.weight.detach().numpy().copy()
            plane_up = native._state["plane_up"]
            hvd.shutdown()
            return w.tolist(), len(core_calls), bool(plane_up)

        results = run(fn, num_proc=2, env=_ENV)
        if results[0] == "unavailable":
            pytest.skip("libhvd_plane.so unavailable in workers")
        for w, n_core, plane_up in results:
            # grad = x summed over batch = 2*(r+1) per input feature;
            # averaged over ranks: (2 + 4)/2 = 3; w = 1 - 3
            np.testing.assert_allclose(np.asarray(w), -2.0)
            assert plane_up, "native plane did not come up"
            assert n_core == 0, "gradients crossed into the eager core"

    def test_matches_bridge_path_numerics(self):
        """Native route and the numpy bridge must produce identical
        results for the same submissions (fp32 and bf16)."""
        def fn():
            import os
            import torch
            import horovod_tpu.torch as hvd
            from horovod_tpu.torch import native

            hvd.init()
            if (os.environ.get("HVD_TORCH_NATIVE") != "0"
                    and not native.available()):
                return "unavailable"
            r = int(os.environ["HVD_PROCESS_ID"])
            res = {}
            t = torch.arange(64, dtype=torch.float32) * (r + 1)
            res["f32"] = hvd.allreduce(t, average=True,
                                       name="ab.f32").tolist()
            b = torch.arange(16, dtype=torch.bfloat16) * (r + 1)
            res["bf16"] = hvd.allreduce(
                b, average=False, name="ab.bf16").float().tolist()
            res["native"] = bool(native._state["plane_up"])
            hvd.shutdown()
            return res

        native_env = dict(_ENV)
        bridge_env = dict(_ENV, HVD_TORCH_NATIVE="0")
        nat = run(fn, num_proc=2, env=native_env)
        if nat[0] == "unavailable":
            pytest.skip("libhvd_plane.so unavailable in workers")
        bri = run(fn, num_proc=2, env=bridge_env)
        assert nat[0]["native"] and not bri[0]["native"]
        for k in ("f32", "bf16"):
            assert nat[0][k] == bri[0][k] == nat[1][k] == bri[1][k]

    def test_allgatherv_native(self):
        """Variable-first-dim allgather over the plane: each rank
        contributes a different number of rows; every rank gets the
        concatenation in rank order (the reference's allgatherv,
        mpi_operations.cc:86-173)."""
        def fn():
            import os
            import torch
            import horovod_tpu.torch as hvd
            from horovod_tpu.torch import native

            hvd.init()
            if not native.available():
                return "unavailable"
            r = int(os.environ["HVD_PROCESS_ID"])
            # rank 0: 1 row, rank 1: 2 rows — rows carry the rank
            t = torch.full((r + 1, 3), float(r), dtype=torch.float32)
            out = hvd.allgather(t, name="agv")
            core_free = not any(
                isinstance(k, int) for k in
                __import__("horovod_tpu.torch.mpi_ops",
                           fromlist=["_handle_map"])._handle_map)
            sc = hvd.allgather(torch.tensor(float(r)), name="agv.scalar")
            hvd.shutdown()
            return (out.tolist(), list(out.shape), sc.tolist(),
                    bool(native._state["plane_up"]), core_free)

        results = run(fn, num_proc=2, env=_ENV)
        if results[0] == "unavailable":
            pytest.skip("libhvd_plane.so unavailable in workers")
        want = [[0.0, 0.0, 0.0], [1.0, 1.0, 1.0], [1.0, 1.0, 1.0]]
        for out, shape, sc, plane_up, core_free in results:
            assert out == want
            assert shape == [3, 3]
            assert sc == [0.0, 1.0]
            assert plane_up
            # the gathers really rode the plane: no eager-core handles
            assert core_free, "allgather fell back to the numpy bridge"

    def test_shape_mismatch_errors(self):
        """Same name, same byte count, different shapes across ranks:
        the shape digest must reject it (plane.h note_ready)."""
        def fn():
            import os
            import torch
            import horovod_tpu.torch as hvd
            from horovod_tpu.torch import native

            hvd.init()
            if not native.available():
                return "unavailable"
            r = int(os.environ["HVD_PROCESS_ID"])
            got = None
            try:
                t = torch.zeros((2, 3) if r == 0 else (3, 2))
                hvd.allreduce_(t, name="clash.shape")
            except RuntimeError as e:
                got = "mismatched" in str(e)
            # the plane survives for a well-formed collective
            ok = hvd.allreduce(torch.ones(4), average=False,
                               name="after.clash")
            hvd.shutdown()
            return got, float(ok[0])

        results = run(fn, num_proc=2, env=_ENV)
        if results[0] == "unavailable":
            pytest.skip("libhvd_plane.so unavailable in workers")
        for got, after in results:
            assert got, "shape mismatch did not raise"
            assert after == 2.0

    def test_poll_completes_without_releasing_handle(self):
        """hvd.poll on a native handle reports completion truthfully and
        leaves the handle joinable (reference poll/synchronize contract,
        torch/mpi_ops.py:406-438)."""
        def fn():
            import time
            import torch
            import horovod_tpu.torch as hvd
            from horovod_tpu.torch import native

            hvd.init()
            if not native.available():
                return "unavailable"
            h = hvd.allreduce_async_(torch.ones(64), average=False,
                                     name="poll.t")
            deadline = time.monotonic() + 30
            while not hvd.poll(h):
                if time.monotonic() > deadline:
                    hvd.shutdown()
                    return "poll-timeout"
                time.sleep(0.005)
            out = hvd.synchronize(h)  # still joinable after poll=True
            hvd.shutdown()
            return float(out[0])

        results = run(fn, num_proc=2, env=_ENV)
        if results[0] == "unavailable":
            pytest.skip("libhvd_plane.so unavailable in workers")
        assert results == [2.0, 2.0], results

    def test_disabled_env_uses_bridge(self):
        def fn():
            import torch
            import horovod_tpu.torch as hvd
            from horovod_tpu.torch import native

            hvd.init()
            out = hvd.allreduce(torch.ones(8), average=False, name="br")
            up = native._state["plane_up"]
            hvd.shutdown()
            return float(out[0]), bool(up)

        results = run(fn, num_proc=2,
                      env=dict(_ENV, HVD_TORCH_NATIVE="0"))
        for v, up in results:
            assert v == 2.0 and not up
