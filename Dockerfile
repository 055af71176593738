# Dev + CI image for horovod_tpu (role of the reference's Dockerfile /
# Dockerfile.test.cpu, /root/reference/Dockerfile:1-70 — there a
# CUDA+MPI build box; here a CPU box that runs the full suite on the
# virtual 8-device mesh. On a TPU VM, install the matching libtpu jax
# wheel instead of the CPU one and the same image serves for real-chip
# runs.)
#
#   docker build -t horovod-tpu .                      # dev image (default:
#   docker run --rm horovod-tpu                        #  the LAST stage)
#   docker run --rm horovod-tpu python -m pytest tests/ -q
#
# Integration stage — the real pyspark frontend (reference CI runs real
# pyspark, docker-compose.test.yml:1-60; the dev image verifies it
# against a duck-type stand-in only — docs/testing.md). There is no
# real-mxnet stage: mxnet 1.9.1 (its final release) is frozen at
# numpy<1.24, which the one supported installation (current jax) cannot
# meet, so that frontend is covered by its stand-in suite alone.
#
#   docker build --target integration-spark -t hvd-int-spark . && docker run --rm hvd-int-spark

# -- pyspark integration: modern stack + JRE ---------------------------------
FROM python:3.12-slim AS integration-spark
RUN apt-get update && apt-get install -y --no-install-recommends \
        default-jre-headless && rm -rf /var/lib/apt/lists/*
RUN pip install --no-cache-dir \
        "jax[cpu]" flax optax chex einops numpy pytest "pyspark==3.5.1"
WORKDIR /workspace/horovod_tpu
COPY . .
CMD ["python", "-m", "pytest", "tests/integration/test_real_spark.py", "-m", "integration", "-q", "-rs"]

# -- dev/CI image (LAST stage: the default `docker build .` target) ----------
FROM python:3.12-slim AS dev

RUN apt-get update && apt-get install -y --no-install-recommends \
        build-essential g++ make git openssh-client \
    && rm -rf /var/lib/apt/lists/*

# jax[cpu]: tests force the virtual CPU mesh; swap for jax[tpu] on TPU VMs
RUN pip install --no-cache-dir \
        "jax[cpu]" flax optax orbax-checkpoint chex einops numpy pytest \
        tensorflow-cpu keras torch --index-url https://pypi.org/simple

WORKDIR /workspace/horovod_tpu
COPY . .

# build the native core (planner/cache/timeline/autotuner C++)
RUN python setup.py build_native

CMD ["ci/run_tests.sh"]
