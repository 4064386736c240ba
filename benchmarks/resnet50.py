"""ResNet-50 training throughput — the driver's image north-star metric
(BASELINE.json: ResNet-50 ImageNet images/sec/chip; config parity:
benchmark/paddle/image/resnet.py layer_num=50, batch 64, 224x224x3).

bf16 compute (MXU native) with f32 params/optimizer — the TPU-idiomatic mixed
precision.

Methodology (honest-bench notes):
* TRAIN-mode batch norm: per-batch statistics are computed and the running
  stats are updated and merged back every step (`nn.apply_stat_updates`), so
  the measured step includes all BN-stat work.
* Four distinct input batches are staged on device and rotated through the
  loop, so BN statistics do real, different work each step. (In deployment the
  host->HBM infeed overlaps compute via data/prefetch.py DoubleBuffer; staging
  keeps the host->device transfer out of the timed region while preserving
  per-step data variation.)
* Timing: N chained steps in one on-device ``fori_loop`` dispatch with
  short/long differencing, as in lstm_textcls.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

BATCH = 64
IMAGE = 224
CLASSES = 1000
NBUF = 4          # distinct staged batches rotated through the loop


def build(batch: int = BATCH, bf16: bool = True):
    from paddle_tpu import nn
    from paddle_tpu.models import ResNet
    from paddle_tpu.optimizer import Momentum

    model = ResNet(depth=50, classes=CLASSES)
    params = model.init(jax.random.PRNGKey(0))
    opt = Momentum(0.1, momentum=0.9)
    state = opt.init(params)

    def loss_fn(params, x, y):
        mut = {}
        if bf16:
            p16 = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16)
                if a.dtype == jnp.float32 else a, params)
            logits = model(p16, x.astype(jnp.bfloat16), train=True,
                           mutable=mut).astype(jnp.float32)
        else:
            logits = model(params, x, train=True, mutable=mut)
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
        return loss, mut

    def step_fn(params, state, x, y):
        (loss, mut), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, x, y)
        params, state = opt.update(grads, state, params)
        # merge the train-mode BN running-stat updates back (f32 master copy)
        mut = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), mut)
        params = nn.apply_stat_updates(params, mut)
        return params, state, loss

    @jax.jit
    def run_n(params, state, xs, ys, n):
        def body(i, carry):
            params, state, _ = carry
            j = i % NBUF
            x = jax.lax.dynamic_index_in_dim(xs, j, 0, keepdims=False)
            y = jax.lax.dynamic_index_in_dim(ys, j, 0, keepdims=False)
            return step_fn(params, state, x, y)
        return jax.lax.fori_loop(0, n, body, (params, state, jnp.float32(0)))

    rs = np.random.RandomState(0)
    xs = jnp.asarray(rs.rand(NBUF, batch, IMAGE, IMAGE, 3), jnp.float32)
    ys = jnp.asarray(rs.randint(0, CLASSES, (NBUF, batch)), jnp.int32)
    return run_n, step_fn, params, state, (xs, ys)


def run(iters: int = 20, repeats: int = 2, batch: int = BATCH):
    from benchmarks.mfu import attach_mfu, step_flops
    from benchmarks.timing import chained_ms_per_step

    run_n, step_fn, params, state, b = build(batch)
    sec = chained_ms_per_step(run_n, (params, state) + b, iters,
                              repeats) / 1e3
    ips = batch / sec
    flops = step_flops(step_fn, params, state, b[0][0], b[1][0])
    # key carries train-mode-BN semantics (r1 measured inference-mode BN)
    return attach_mfu(
        {"metric": f"resnet50_train_images_per_sec_bs{batch}_224_trainbn",
         "value": round(ips, 2), "unit": "images/sec",
         "vs_baseline": None,  # no published reference ResNet number
         "note": "train-mode BN with stat updates, 4 distinct rotating batches"},
        flops, sec)


def run_with_infeed(steps: int = 24, batch: int = BATCH):
    """images/sec INCLUDING host->HBM infeed, via the data/prefetch.py
    DoubleBuffer (the DataProvider.h:249 capability): a worker thread
    device_puts batches while the previous step computes; dispatch is async
    so transfer and compute overlap.

    The feed is uint8 pixels normalized ON DEVICE (x/255 in bf16) — the
    production image pipeline's wire format (JPEG decode yields uint8), and
    4x fewer transfer bytes than f32. Reports the end-to-end rate, the
    overlap ratio vs the compute-only number (1.0 == infeed fully hidden),
    and the achieved host->device MB/s — the MB/s line shows whether the
    link or the framework is the binding constraint on the machine the row
    ran on.
    """
    from paddle_tpu.data.prefetch import DoubleBuffer

    run_n, step_fn, params, state, b = build(batch)

    def step_u8(params, state, x_u8, y):
        # on-device normalize: uint8 -> bf16 in [0, 1]
        x = x_u8.astype(jnp.bfloat16) * jnp.bfloat16(1.0 / 255.0)
        return step_fn(params, state, x, y)

    step = jax.jit(step_u8, donate_argnums=(0, 1))

    rs = np.random.RandomState(1)
    host_batches = [(rs.randint(0, 256, (batch, IMAGE, IMAGE, 3),
                                np.uint8),
                     rs.randint(0, CLASSES, (batch,)).astype(np.int32))
                    for _ in range(NBUF)]
    batch_bytes = host_batches[0][0].nbytes + host_batches[0][1].nbytes

    total = steps + 4                       # warmup + pipeline depth; the
                                            # worker exits when exhausted
                                            # (no leaked thread / pinned HBM)
    def gen():
        for i in range(total):
            yield host_batches[i % NBUF]

    def to_device(hb):
        x, y = hb
        return jax.device_put(x), jax.device_put(y)

    db = iter(DoubleBuffer(gen, depth=2, transform=to_device))
    for _ in range(2):                      # warm: compile + fill pipeline
        x, y = next(db)
        params, state, loss = step(params, state, x, y)
    float(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        x, y = next(db)
        params, state, loss = step(params, state, x, y)
    float(loss)                             # drain the async queue
    e2e = (time.perf_counter() - t0) / steps

    # compute-only rate for the overlap ratio (same method as run())
    from benchmarks.timing import chained_ms_per_step
    staged = (jnp.asarray(np.stack([hb[0] for hb in host_batches])),
              jnp.asarray(np.stack([hb[1] for hb in host_batches])))
    compute = chained_ms_per_step(run_n, (params, state) + staged, 12,
                                  2) / 1e3
    from benchmarks.mfu import attach_mfu, step_flops
    flops = step_flops(step_fn, params, state,
                       staged[0][0].astype(jnp.bfloat16) / 255.0,
                       staged[1][0])
    # e2e time: mfu here reads "fraction of peak sustained INCLUDING the
    # infeed stall", pairing with overlap_ratio (bench-row schema:
    # every *_train_* row carries its mfu column)
    return attach_mfu(
        {"metric": f"resnet50_train_images_per_sec_bs{batch}_incl_infeed",
         "value": round(batch / e2e, 2), "unit": "images/sec",
         "vs_baseline": None,
         "compute_only_images_per_sec": round(batch / compute, 2),
         "overlap_ratio": round(compute / e2e, 3),
         "infeed_mb_per_sec": round(batch_bytes / e2e / 1e6, 1),
         "note": "DoubleBuffer uint8 host->HBM feed (on-device "
                 "normalize) overlapped with compute; infeed_mb_per_sec "
                 "is the host link as measured"},
        flops, e2e)


if __name__ == "__main__":
    import json
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    print(json.dumps(run()))
    print(json.dumps(run_with_infeed()))
