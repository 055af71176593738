"""Distributed ImageNet ResNet-50 training.

Capability parity with the reference's examples/pytorch_imagenet_resnet50.py
and keras_imagenet_resnet50.py: per-worker batch sharding, LR = base_lr x
world size with gradual warmup over the first epochs (Goyal et al., the
LearningRateWarmupCallback semantics incl. momentum correction), step decay
at epochs 30/60/80, weight decay, optional fp16/bf16 gradient compression
(--fp16-allreduce), gradient accumulation (--batches-per-allreduce),
validation-accuracy averaging across workers (MetricAverageCallback), and
rank-0 checkpoint/resume per epoch.

Runs on real ImageNet if a directory of .npz shard files is given
(--train-dir), otherwise on synthetic ImageNet-shaped data (this container
has no dataset), which exercises every distributed code path at the real
tensor shapes.

Usage:
    python examples/imagenet_resnet50.py --epochs 2 --steps-per-epoch 10
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/imagenet_resnet50.py --epochs 2 --steps-per-epoch 4 \
        --batch-size 4 --image-size 64
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import callbacks as cb
from horovod_tpu import trainer
from horovod_tpu.models import resnet
from horovod_tpu.utils import checkpoint, compile_cache


def parse_args():
    p = argparse.ArgumentParser(description="horovod_tpu ImageNet ResNet-50")
    p.add_argument("--model", default="resnet50", choices=sorted(resnet.MODELS))
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-worker batch size")
    p.add_argument("--epochs", type=int, default=90)
    p.add_argument("--base-lr", type=float, default=0.0125,
                   help="per-worker LR; scaled by world size")
    p.add_argument("--warmup-epochs", type=float, default=5)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=0.00005)
    p.add_argument("--fp16-allreduce", action="store_true",
                   help="compress gradients to bf16 on the wire")
    p.add_argument("--batches-per-allreduce", type=int, default=1)
    p.add_argument("--checkpoint-dir", default="./imagenet-ckpt")
    p.add_argument("--train-dir", default=None,
                   help="directory of npz shards with images/labels arrays")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--steps-per-epoch", type=int, default=None)
    p.add_argument("--val-steps", type=int, default=2)
    p.add_argument("--seed", type=int, default=42)
    return p.parse_args()


def synthetic_batch(rng, n, size):
    imgs = rng.rand(n, size, size, 3).astype(np.float32)
    labels = rng.randint(0, 1000, n).astype(np.int32)
    return imgs, labels


def load_train_dir(path):
    """Concatenate every .npz shard (arrays 'images' [N,H,W,3] float or
    uint8, 'labels' [N]) under ``path``."""
    shards = sorted(f for f in os.listdir(path) if f.endswith(".npz"))
    if not shards:
        raise SystemExit(f"--train-dir {path}: no .npz shards found")
    imgs, labels = [], []
    for f in shards:
        with np.load(os.path.join(path, f)) as d:
            imgs.append(d["images"].astype(np.float32))
            labels.append(d["labels"].astype(np.int32))
    imgs = np.concatenate(imgs)
    if imgs.max() > 1.5:        # uint8-ranged pixels
        imgs /= 255.0
    return imgs, np.concatenate(labels)


def data_batch(data, rng, n):
    imgs, labels = data
    idx = rng.randint(0, len(imgs), n)
    return imgs[idx], labels[idx]


def main():
    args = parse_args()
    compile_cache.configure()
    hvd.init()
    world = hvd.size()
    global_batch = args.batch_size * world
    verbose = hvd.process_rank() == 0
    if verbose:
        print(f"workers={world} global_batch={global_batch} "
              f"platform={jax.devices()[0].platform}")

    model = resnet.MODELS[args.model](num_classes=1000, dtype=jnp.bfloat16)
    variables = model.init(
        jax.random.PRNGKey(args.seed),
        jnp.zeros((2, args.image_size, args.image_size, 3)), train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]

    compression = (hvd.Compression.bf16 if args.fp16_allreduce
                   else hvd.Compression.none)
    # inject_hyperparams exposes learning_rate to the LR callbacks, the
    # same knob the reference callbacks mutate on the Keras optimizer.
    tx = hvd.DistributedOptimizer(
        optax.inject_hyperparams(optax.sgd)(
            learning_rate=args.base_lr * world, momentum=args.momentum),
        compression=compression,
        backward_passes_per_step=args.batches_per_allreduce)
    opt_state = trainer.init_opt_state(tx, params, hvd.mesh())

    start_epoch = 0
    if checkpoint.exists(args.checkpoint_dir):
        (params, batch_stats, opt_state), start_epoch = checkpoint.restore(
            args.checkpoint_dir, like=(params, batch_stats, opt_state))
        if verbose:
            print(f"resumed from epoch {start_epoch}")

    axis = hvd.mesh().axis_names[0]

    def train_step(params, batch_stats, opt_state, batch):
        imgs, labels = batch

        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": batch_stats}, imgs,
                train=True, mutable=["batch_stats"])
            ce = trainer.softmax_cross_entropy(logits, labels)
            l2 = 0.5 * sum(jnp.sum(jnp.square(w))
                           for w in jax.tree_util.tree_leaves(p))
            return ce + args.wd * l2, mut["batch_stats"]

        # grads must be per-worker when they reach the DistributedOptimizer
        # (replicated params would make autodiff pre-sum them — see
        # hvd.ensure_varying)
        vparams = jax.tree_util.tree_map(
            lambda p: hvd.ensure_varying(p, axis), params)
        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(vparams)
        updates, new_opt = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        # keep BN statistics identical across replicas (the reference
        # broadcasts them with broadcast_parameters; averaging per step is
        # the sync-BN-statistics variant)
        new_bs = jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x, axis), new_bs)
        return new_params, new_bs, new_opt, jax.lax.pmean(loss, axis)

    def eval_step(params, batch_stats, batch):
        imgs, labels = batch
        logits = model.apply({"params": params, "batch_stats": batch_stats},
                             imgs, train=False)
        acc = jnp.mean(jnp.argmax(logits, -1) == labels)
        return jax.lax.pmean(acc, axis)

    mesh = hvd.mesh()
    jtrain = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P(), (P(axis), P(axis))),
        out_specs=(P(), P(), P(), P())))
    jeval = jax.jit(jax.shard_map(
        eval_step, mesh=mesh, in_specs=(P(), P(), (P(axis), P(axis))),
        out_specs=P()))
    sharding = NamedSharding(mesh, P(axis))

    steps = args.steps_per_epoch or max(1, 1281167 // global_batch)
    loop = cb.LoopState(params=params, opt_state=opt_state,
                        steps_per_epoch=steps)
    callbacks = cb.CallbackList([
        cb.BroadcastGlobalVariablesCallback(0),
        cb.LearningRateWarmupCallback(warmup_epochs=args.warmup_epochs,
                                      verbose=verbose),
        # reference pytorch_imagenet_resnet50 step decay: /10 at 30/60/80
        cb.LearningRateScheduleCallback(multiplier=0.1, start_epoch=30,
                                        end_epoch=60),
        cb.LearningRateScheduleCallback(multiplier=0.01, start_epoch=60,
                                        end_epoch=80),
        cb.LearningRateScheduleCallback(multiplier=0.001, start_epoch=80),
        cb.MetricAverageCallback(),
    ], loop)
    callbacks.on_train_begin()
    batch_stats = hvd.broadcast_parameters(batch_stats)

    rng = np.random.RandomState(args.seed + hvd.process_rank())
    data = load_train_dir(args.train_dir) if args.train_dir else None
    for epoch in range(start_epoch, args.epochs):
        callbacks.on_epoch_begin(epoch)
        t0 = time.time()
        losses = []
        for i in range(steps):
            callbacks.on_batch_begin(i)
            imgs, labels = (data_batch(data, rng, global_batch) if data else
                            synthetic_batch(rng, global_batch,
                                            args.image_size))
            imgs = jax.device_put(jnp.asarray(imgs), sharding)
            labels = jax.device_put(jnp.asarray(labels), sharding)
            loop.params, batch_stats, loop.opt_state, loss = jtrain(
                loop.params, batch_stats, loop.opt_state, (imgs, labels))
            losses.append(float(loss))
            callbacks.on_batch_end(i)

        accs = []
        for _ in range(args.val_steps):
            imgs, labels = (data_batch(data, rng, global_batch) if data else
                            synthetic_batch(rng, global_batch,
                                            args.image_size))
            accs.append(float(jeval(
                loop.params, batch_stats,
                (jax.device_put(jnp.asarray(imgs), sharding),
                 jax.device_put(jnp.asarray(labels), sharding)))))

        loop.logs = {"loss": np.mean(losses), "val_acc": np.mean(accs)}
        callbacks.on_epoch_end(epoch, loop.logs)
        if verbose:
            lr = cb.get_hyperparam(loop.opt_state, "learning_rate")
            print(f"epoch {epoch}: loss={loop.logs['loss']:.4f} "
                  f"val_acc={loop.logs['val_acc']:.4f} lr={float(lr):.4f} "
                  f"({time.time() - t0:.1f}s)")
            checkpoint.save(args.checkpoint_dir,
                            (loop.params, batch_stats, loop.opt_state),
                            step=epoch + 1)
    callbacks.on_train_end()


if __name__ == "__main__":
    main()
