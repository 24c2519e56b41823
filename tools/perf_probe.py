"""On-chip perf probes: one big matmul, single convs in both layouts, one
ResNet-50 step (docs/PERF_NOTES.md).

Protocol: each probe times a DATA-DEPENDENT chain of iterations (the output
carries into the next step), finishes with a host fetch, and removes the
per-dispatch overhead by differencing two chain lengths. Percent-of-peak is
against the chip's entry in ``analysis.cost_model.DEVICE_PEAKS``; a device
the table does not list (the CPU included) raises.

Run on TPU:  python tools/perf_probe.py [micro|resnet|all]
"""
from __future__ import annotations

import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

RNG = np.random.RandomState(0)


def peak_tflops() -> float:
    from paddle_tpu.analysis.cost_model import device_peak

    return device_peak(jax.devices()[0].device_kind).bf16_tflops


def rnd(shape, dtype=jnp.bfloat16):
    return jax.device_put(RNG.randn(*shape).astype(np.float32)).astype(dtype)


def chain_time(make_fn, k_short=4, k_long=16, iters=3):
    """Median per-iteration seconds of make_fn(k)'s chained body, RTT
    removed by (T_long - T_short) / (k_long - k_short)."""
    def run(k):
        f = make_fn(k)
        float(f())            # compile + warm
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            float(f())
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    return (run(k_long) - run(k_short)) / (k_long - k_short)


def probe_matmul(n=4096):
    a, b = rnd((n, n)), rnd((n, n))

    def make(k):
        @jax.jit
        def f():
            x = a
            for _ in range(k):
                x = x @ b * (1.0 / n)
            return x.astype(jnp.float32).sum()
        return f

    dt = chain_time(make, 20, 200)
    tf = 2 * n ** 3 / dt / 1e12
    print(f"matmul {n}^3 bf16: {dt*1e3:.3f} ms, {tf:.1f} TF/s "
          f"({100*tf/peak_tflops():.0f}% peak)")


def probe_conv_train(tag, B, C, HW, k, layout):
    """fwd+bwd of one CxC kxk conv at BxHWxHW, chained through a dummy
    SGD update so iterations serialize."""
    pad = k // 2
    if layout == "NCHW":
        x = rnd((B, C, HW, HW))
        w0 = rnd((C, C, k, k), jnp.float32)
        dn = ("NCHW", "OIHW", "NCHW")
    else:
        x = rnd((B, HW, HW, C))
        w0 = rnd((k, k, C, C), jnp.float32)
        dn = ("NHWC", "HWIO", "NHWC")

    def loss(w):
        y = jax.lax.conv_general_dilated(
            x, w.astype(jnp.bfloat16), (1, 1), [(pad, pad), (pad, pad)],
            dimension_numbers=dn)
        return jnp.sum(y.astype(jnp.float32) ** 2) * 1e-12

    def make(kk):
        @jax.jit
        def f():
            def body(w, _):
                g = jax.grad(loss)(w)
                return w - 1e-20 * g, None
            w, _ = jax.lax.scan(body, w0, None, length=kk)
            return w.sum()
        return f

    dt = chain_time(make, 2, 10)
    flops = 3 * 2 * B * HW * HW * C * C * k * k
    tf = flops / dt / 1e12
    print(f"{tag} fwd+bwd {layout}: {dt*1e3:.2f} ms, ~{tf:.1f} TF/s "
          f"({100*tf/peak_tflops():.0f}% peak)")


def probe_resnet_step(nhwc: str, iters=10):
    from paddle_tpu import flags

    flags.set_flags({"FLAGS_conv_use_nhwc": nhwc})
    import paddle_tpu as fluid
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.resnet import build_resnet

    with un.guard():
        model = build_resnet(depth=50, class_num=1000, amp=True)
        exe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.Scope()
        dev = fluid.TPUPlace().jax_device()
        feed = {"img": jax.device_put(
                    RNG.rand(128, 3, 224, 224).astype(np.float32), dev),
                "label": jax.device_put(
                    RNG.randint(0, 1000, (128, 1)).astype(np.int64), dev)}
        with fluid.scope_guard(scope):
            exe.run(model["startup"])

            def step():
                return exe.run(model["main"], feed=feed,
                               fetch_list=[model["loss"]],
                               return_numpy=False)

            # warm + hard sync (host fetch) so timing starts quiescent
            out = step()
            float(np.asarray(out[0]).reshape(-1)[0])
            t0 = time.perf_counter()
            for _ in range(iters):
                out = step()   # state donation chains the iterations
            float(np.asarray(out[0]).reshape(-1)[0])
            dt = (time.perf_counter() - t0) / iters
    tf = 128 * 3 * 4.1e9 / dt / 1e12
    print(f"resnet50 bf16 train bs=128 [nhwc={nhwc}]: {dt*1e3:.1f} ms "
          f"({128/dt:.0f} img/s, ~{tf:.1f} TF/s, {100*tf/peak_tflops():.0f}% peak)")
    flags.set_flags({"FLAGS_conv_use_nhwc": "auto"})


if __name__ == "__main__":
    d = jax.devices()[0]
    print(f"device: {d.platform} {d.device_kind} x{len(jax.devices())}, "
          f"peak {peak_tflops()} bf16 TF/s")
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("all", "micro"):
        probe_matmul()
        for layout in ("NCHW", "NHWC"):
            probe_conv_train("stage1 3x3 64ch @56", 128, 64, 56, 3, layout)
            probe_conv_train("stage3 3x3 256ch @14", 128, 256, 14, 3, layout)
            probe_conv_train("stage4 3x3 512ch @7", 128, 512, 7, 3, layout)
    if which in ("all", "resnet"):
        probe_resnet_step("never")
        probe_resnet_step("always")
