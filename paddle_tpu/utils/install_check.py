"""Install verification (ref: /root/reference/python/paddle/fluid/
install_check.py run_check — train a tiny linear model eagerly and
under the parallel executor, report success/diagnostics).

TPU adaptation: verifies (1) the backend initializes and reports its
platform/devices, (2) a jitted train step runs and the loss decreases,
(3) when >1 device is visible, the same step runs sharded over a dp
mesh — the three failure classes operators actually hit (a backend
that fails to initialise, broken compile cache, bad mesh/sharding
install).
"""

from __future__ import annotations

__all__ = ["run_check"]


def run_check(verbose: bool = True) -> bool:
    import numpy as np

    def say(msg):
        if verbose:
            print(f"[paddle_tpu] {msg}", flush=True)

    say("Running install check ...")
    try:
        import jax
        backend = jax.default_backend()
        devices = jax.devices()
        say(f"backend={backend} devices={len(devices)} "
            f"({devices[0].platform})")
    except Exception as e:  # noqa: BLE001
        say(f"FAIL: backend initialization raised: {e!r}")
        say("Hint: on a TPU host a hang/failure here usually means the "
            "accelerator runtime is unreachable; try JAX_PLATFORMS=cpu "
            "to confirm the CPU path.")
        return False

    import paddle_tpu as pt
    from paddle_tpu.static import TrainStep

    pt.seed(0)
    model = pt.nn.Linear(4, 3)
    opt = pt.optimizer.SGD(learning_rate=0.1)
    step = TrainStep(model, opt,
                     lambda out, y: pt.nn.functional.mse_loss(out, y))
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (8, 4)).astype(np.float32)
    y = rng.normal(0, 1, (8, 3)).astype(np.float32)
    try:
        first = float(step(x, labels=y)["loss"])
        for _ in range(10):
            last = float(step(x, labels=y)["loss"])
    except Exception as e:  # noqa: BLE001
        say(f"FAIL: jitted train step raised: {e!r}")
        return False
    if not (np.isfinite(last) and last < first):
        say(f"FAIL: loss did not decrease ({first} -> {last})")
        return False
    say(f"single-device train step OK (loss {first:.4f} -> {last:.4f})")

    if len(devices) > 1:
        try:
            from jax.sharding import PartitionSpec as P

            from paddle_tpu.parallel import (ShardedTrainStep,
                                             data_parallel_mesh)
            mesh = data_parallel_mesh()
            pt.seed(0)
            m2 = pt.nn.Linear(4, 3)
            s2 = ShardedTrainStep(
                m2, pt.optimizer.SGD(learning_rate=0.1),
                lambda out, yy: pt.nn.functional.mse_loss(out, yy),
                mesh=mesh, batch_spec=P("dp"))
            n = mesh.shape["dp"] * 2
            reps = -(-n // len(x))  # ceil-divide: tile to >= n rows
            l0 = float(s2(np.tile(x, (reps, 1))[:n],
                          labels=np.tile(y, (reps, 1))[:n])["loss"])
            if not np.isfinite(l0):
                say(f"FAIL: sharded step produced non-finite loss "
                    f"({l0}) — miswired collective/sharding")
                return False
            say(f"{len(devices)}-device sharded step OK (loss {l0:.4f})")
        except Exception as e:  # noqa: BLE001
            say(f"FAIL: sharded step over {len(devices)} devices "
                f"raised: {e!r}")
            return False
    say("paddle_tpu is installed and working.")
    return True
