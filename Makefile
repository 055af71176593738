# Developer entry points (reference: setup.py + .buildkite/gen-pipeline.sh).

PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu
CPU_MESH = $(CPU_ENV) XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: lint lint-concurrency test native examples ci clean

# distributed-correctness static analysis (tools/hvdlint, docs/hvdlint.md);
# cheapest gate, so it leads the ci chain
lint:
	$(PY) -m tools.hvdlint horovod_tpu tools examples
	$(PY) -m tools.hvdlint --check-envdoc

# whole-program lock-discipline pass (docs/concurrency.md): guarded_by
# annotations + LOCK_RANKS order, HVD021/HVD022
lint-concurrency:
	$(PY) -m tools.hvdlint --selftest
	$(PY) -m tools.hvdlint --concurrency

native:
	$(PY) setup.py build_native

test:
	$(PY) -m pytest tests/ -q

# example smoke runs on the virtual 8-worker CPU mesh — the reference CI
# runs its example scripts as integration tests after pytest
# (gen-pipeline.sh:101-128)
examples:
	$(CPU_MESH) $(PY) examples/mnist.py --epochs 1 --steps-per-epoch 4
	$(CPU_MESH) $(PY) examples/mnist_eager.py --steps 20
	$(CPU_MESH) $(PY) examples/word2vec.py --steps 30 --batch-size 32
	$(CPU_MESH) $(PY) examples/imagenet_resnet50.py --epochs 1 \
	    --steps-per-epoch 2 --batch-size 2 --image-size 32 --val-steps 1 \
	    --checkpoint-dir /tmp/hvd-ci-imagenet-ckpt
	$(CPU_MESH) $(PY) examples/transformer_lm.py --size tiny --steps 3 \
	    --dp 2 --tp 2 --sp 2 --attention ring
	$(CPU_MESH) $(PY) examples/serve_lm.py --requests 12 --slots 2 \
	    --max-len 64 --baseline
	$(CPU_MESH) $(PY) examples/route_lm.py --requests 12 --replicas 2 \
	    --slots 2 --max-len 64 --compare
	$(CPU_MESH) $(PY) examples/synthetic_benchmark.py --model resnet18 \
	    --batch-size 1 --image-size 32 --num-warmup-batches 1 \
	    --num-iters 1 --num-batches-per-iter 2
	$(CPU_MESH) $(PY) examples/scaling_benchmark.py --model resnet18 \
	    --batch-size 1 --image-size 32 --device-counts 1,2 \
	    --num-warmup-batches 1 --num-iters 1 --num-batches-per-iter 2
	$(CPU_ENV) $(PY) examples/pytorch_mnist.py \
	    --epochs 1 --steps-per-epoch 4 --checkpoint-dir /tmp/hvd-ci-torch-ckpt
	$(CPU_ENV) $(PY) examples/keras_mnist.py \
	    --epochs 1 --steps-per-epoch 4 --checkpoint-dir /tmp/hvd-ci-keras-ckpt
	# 2-process launch: LearningRateWarmupCallback's ramp is identity at
	# size 1, so the warmup/schedule recipe is exercised across ranks
	$(CPU_ENV) PYTHONPATH=. $(PY) bin/hvdrun -np 2 $(PY) \
	    examples/keras_mnist_advanced.py --epochs 3 --steps-per-epoch 3 \
	    --val-steps 1 --warmup-epochs 2 \
	    --checkpoint-dir /tmp/hvd-ci-keras-adv-ckpt
	$(CPU_ENV) $(PY) examples/mxnet_mnist.py --epochs 1 --steps-per-epoch 4
	$(CPU_MESH) $(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

ci: lint lint-concurrency native test examples

clean:
	rm -rf build dist *.egg-info /tmp/hvd-ci-imagenet-ckpt \
	    /tmp/hvd-ci-torch-ckpt /tmp/hvd-ci-keras-ckpt \
	    /tmp/hvd-ci-keras-adv-ckpt
