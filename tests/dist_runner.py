"""Runnable distributed-training script (reference test_dist_base.py model
scripts: dist_mnist.py subclassing TestDistRunnerBase:61). Trains a fixed MLP
regression on deterministic synthetic data; under the launcher each rank
feeds its slice of the SAME global batch, standalone feeds the full batch —
losses must match bit-for-bit up to float tolerance. Rank 0 prints the loss
series as one JSON line prefixed with LOSSES."""
import json
import os
import sys

import numpy as np

GLOBAL_BATCH = 8
STEPS = 10
DIM = 16


def _say(line: str) -> None:
    """One whole line in ONE write: the ranks share the launcher's stdout,
    and ``print`` under ``-u`` writes a line and its end separately, which
    lets the other rank's line land between them."""
    sys.stdout.flush()
    os.write(sys.stdout.fileno(), (line + "\n").encode())


def main():
    nranks = int(os.getenv("PADDLE_TRAINERS_NUM", "1"))
    rank = int(os.getenv("PADDLE_TRAINER_ID", "0"))
    if nranks > 1:
        from paddle_tpu import distributed as dist

        dist.init_parallel_env()
    else:
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 2)

    import paddle_tpu as fluid

    if os.getenv("DIST_MODEL") == "deepfm":
        from paddle_tpu.models.deepfm import build_deepfm

        m = build_deepfm(vocab=64, num_fields=4, emb_dim=4, lr=0.05,
                         sharded=True)
        m["main"].random_seed = 31
        main_p, startup, loss = m["main"], m["startup"], m["loss"]
        rng = np.random.RandomState(42)
        ids = rng.randint(0, 64, (GLOBAL_BATCH, 4)).astype(np.int64)
        feeds = {"feat_ids": ids,
                 "label": (ids.sum(1) % 2).astype(np.float32).reshape(-1, 1)}
    else:
        main_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup):
            x = fluid.layers.data("x", shape=[DIM], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, 32, act="relu", name="d_fc1")
            pred = fluid.layers.fc(h, 1, name="d_fc2")
            loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        rng = np.random.RandomState(42)
        w_true = np.linspace(-1, 1, DIM).astype(np.float32).reshape(DIM, 1)
        xb = rng.rand(GLOBAL_BATCH, DIM).astype(np.float32)
        feeds = {"x": xb, "y": np.tanh(xb @ w_true).astype(np.float32)}
        with fluid.program_guard(main_p, startup):
            if os.getenv("DIST_OPT") == "adam":
                fluid.optimizer.Adam(learning_rate=0.02).minimize(loss)
            else:
                fluid.optimizer.SGD(learning_rate=0.02).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    local = GLOBAL_BATCH // nranks
    losses = []
    if os.getenv("DIST_LOCALSGD"):
        # LocalSGD: plain per-rank program, parameter averaging every k
        from paddle_tpu.incubate.fleet.collective import LocalSGDSync

        k = int(os.getenv("DIST_LOCALSGD"))
        sync = LocalSGDSync(main_p, k_steps=k)
        import paddle_tpu.executor as _ex

        scope = _ex.global_scope()
        for step in range(STEPS):
            sl = slice(rank * local, (rank + 1) * local) if nranks > 1 \
                else slice(None)
            lv = exe.run(main_p, feed={kk: v[sl] for kk, v in feeds.items()},
                         fetch_list=[loss])[0]
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
            sync.step(scope)
        w = np.asarray(scope.find_var("d_fc1.w_0")).ravel()[:6].tolist()
        _say(f"PARAMS{rank} " + json.dumps(w))
    else:
        bs = fluid.BuildStrategy()
        if os.getenv("DIST_REDUCE") == "1":
            bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
        compiled = fluid.CompiledProgram(main_p).with_data_parallel(
            loss_name=loss.name, build_strategy=bs)
        for step in range(STEPS):
            sl = slice(rank * local, (rank + 1) * local) if nranks > 1 \
                else slice(None)
            lv = exe.run(compiled, feed={k: v[sl] for k, v in feeds.items()},
                         fetch_list=[loss])[0]
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    if rank == 0:
        _say("LOSSES " + json.dumps(losses))
    if nranks > 1:
        # hard-exit teardown: this jax build's gloo transport double-frees
        # nondeterministically when interpreter teardown (or even
        # jax.distributed.shutdown) runs its destructors against the XLA
        # CPU client. The ranks are already synchronized by the final
        # training collective; skip every destructor and leave the
        # coordination sockets to die with the process.
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
