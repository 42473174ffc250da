#!/usr/bin/env python3
"""Controls: what the comparison that decides ``correct`` must refuse.
Each control plants one fault, or computes one precision down, and goes
through the cell's own comparison (the runner's ``check_parity`` or
``check_parity_on_mesh``, the configuration file's limits); the drill
passes when every control ends ``correct: false``. A limit's upper
reading in ``PERF.md`` is what a control read here on the chip.

    python3 benchmarks/control_drill.py --workload <cell> --seed <n> \\
        [--controls a,b] [--out chiprun_out/controls_<cell>]

One process, set-up as the runner's up to the comparison, no window.
``--rehearsal`` walks the same flow at tiny widths on the CPU (the
tests import the faults from here).

``lm_train_step`` cells: ``lower_precision`` (the plain reference itself
in bfloat16 at default matmul precision, scan state and loss too, in
the system's place; only its loss gives it away, by bfloat16's grid,
which is why the comparison takes every sequence of a batch),
``softmax_router`` (softmax for sigmoid),
``dropped_choice`` (every token's last chosen expert adds nothing).
``sharded_train_step`` cells, faults that exist only between chips:
``summed_twice_over_mp`` (an all-reduce over ``mp`` of what every chip
already holds whole), ``not_summed_over_dp`` (a weight gradient each
``dp`` rank computes from its own rows and nobody adds up).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402 — JAX is imported later
from benchmarks.harness import log  # noqa: E402


# -- lm_train_step -----------------------------------------------------------

def lower_precision(run, model, leaves):
    """The reference one precision down, as ``check_parity``'s system
    side: a second copy of the reference's module with its two
    constants changed."""
    import jax
    import jax.numpy as jnp

    spec = importlib.util.find_spec(
        f"benchmarks.references.{run.cell['config']}")
    low = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(low)
    low.DTYPE, low.PRECISION = jnp.bfloat16, "default"
    cfg = run.config["model"]

    def system(p, b, ids, labels):
        def loss_of(watched):
            return low.loss({**p, **watched}, cfg, ids, labels, b)
        loss, g = jax.value_and_grad(loss_of)({k: p[k] for k in leaves})
        return loss, g, jnp.zeros((), jnp.int32)
    return contextlib.nullcontext(system)


@contextlib.contextmanager
def _route_replaced(route: Callable):
    from paddle_tpu.nn.layers import moe
    kept = moe.DroplessMoE.route
    moe.DroplessMoE.route = route
    try:
        yield None          # the system stays the model's timed path
    finally:
        moe.DroplessMoE.route = kept


def softmax_router(run, model, leaves):
    import jax
    import jax.numpy as jnp

    def route(self, tokens):
        s = jax.nn.softmax(tokens.astype(jnp.float32)
                           @ self.router_weight.astype(jnp.float32), -1)
        _, chosen = jax.lax.top_k(s + self.e_score_correction_bias,
                                  self.top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        return chosen, w * self.routed_scaling_factor
    return _route_replaced(route)


def dropped_choice(run, model, leaves):
    from paddle_tpu.nn.layers import moe
    kept = moe.DroplessMoE.route

    def route(self, tokens):
        chosen, w = kept(self, tokens)
        return chosen, w.at[:, -1].set(0.0)
    return _route_replaced(route)


def lm_controls(run, names: List[str]) -> Dict[str, Dict[str, Any]]:
    from benchmarks import lm_generator
    from benchmarks.runners import lm_train_step as runner

    model = runner.build_model(run)
    batch = lm_generator.next_token_batches(
        run.mix, run.config["model"]["vocab_size"],
        int(run.mix["batch_per_chip"]) * run.chips, run.seed)[0]
    runner.fit_router_bias(run, model, batch[0])
    leaves = list(run.config["tolerances"]["grad_rel_l2"])
    out = {}
    for name in names:
        with LM[name](run, model, leaves) as system:
            out[name] = judged(run, lambda: runner.check_parity(
                run, model, batch, system=system))
        gc.collect()
    return out


# -- sharded_train_step -------------------------------------------------------

def summed_twice_over_mp(step, model) -> None:
    import jax
    from jax.sharding import PartitionSpec as P

    def twice(x):
        return jax.shard_map(lambda b: jax.lax.psum(b, "mp"),
                             mesh=step.mesh, in_specs=P("dp"),
                             out_specs=P("dp"), check_vma=False)(x)

    layer = model.bert.encoder.layers[1]
    forward = layer.forward
    layer.forward = lambda src, src_mask=None: twice(forward(src, src_mask))


def not_summed_over_dp(step, model) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    @jax.custom_vjp
    def linear(x, w, b):
        return x @ w + b

    def backward(saved, g):
        x, w = saved
        partial = jax.shard_map(
            lambda xb, gb: jnp.einsum("bsi,bso->io", xb, gb),
            mesh=step.mesh, in_specs=(P("dp"), P("dp")), out_specs=P(),
            check_vma=False)(x, g)
        return g @ w.T, partial.astype(w.dtype), g.sum((0, 1))

    linear.defvjp(lambda x, w, b: (linear(x, w, b), (x, w)), backward)
    layer = model.cls.transform
    layer.forward = lambda x: linear(x, layer.weight, layer.bias)


def mesh_controls(run, names: List[str]) -> Dict[str, Dict[str, Any]]:
    from benchmarks import generator
    from benchmarks.runners import sharded_train_step as runner, train_step

    out = {}
    for name in names:
        model, opt = train_step.build_model(run)
        batch = generator.pretraining_batches(
            dict(run.mix, pool_batches=1),
            run.config["model"]["vocab_size"],
            int(run.mix["batch_per_chip"]) * run.chips, run.seed)[0]
        step = runner.build_step(run, model, opt)
        MESH[name](step, model)
        out[name] = judged(run, lambda: runner.check_parity_on_mesh(
            run, step, model, batch))
        del model, opt, step
        gc.collect()
    return out


LM = {"lower_precision": lower_precision, "softmax_router": softmax_router,
      "dropped_choice": dropped_choice}
MESH = {"summed_twice_over_mp": summed_twice_over_mp,
        "not_summed_over_dp": not_summed_over_dp}
KINDS = {"lm_train_step": (LM, lm_controls),
         "sharded_train_step": (MESH, mesh_controls)}


def judged(run, compare: Callable[[], None]) -> Dict[str, Any]:
    """``compare`` under a sweep's rules (a failed check is recorded and
    the comparison goes on), so that every margin is read."""
    run.failures, run.margins = [], {}
    t0 = time.perf_counter()
    compare()
    return {"correct": not run.failures, "failed": list(run.failures),
            "margins": dict(run.margins),
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 77)
    ap.add_argument("--controls", default="",
                    help="comma-separated; default: all of the kind's")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    run = harness.Run(harness.parse_args(
        ["--workload", args.workload, "--seed", str(args.seed),
         "--seconds", "1"] + (["--rehearsal"] if args.rehearsal else [])),
        time.perf_counter())
    run.sweeping = True
    known, controls_of = KINDS[run.mix["kind"]]
    names = [n for n in args.controls.split(",") if n] or list(known)
    harness.prepare_backend(run.rehearsal, run.chips)
    device = harness.find_devices(run)
    harness.enable_cache()
    log(f"controls {names} of {args.workload} seed {args.seed} on {device}")
    results = controls_of(run, names)
    run.cleanup()
    for name, r in results.items():
        log(f"control {name}: correct={r['correct']} failed={r['failed']} "
            f"margins={json.dumps(r['margins'])}")
    passed = all(not r["correct"] for r in results.values())
    report = {"workload": args.workload, "seed": args.seed,
              "device": device, "controls": results, "passed": passed}
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "controls.json"), "w") as f:
            json.dump(report, f, indent=1)
    print(json.dumps(report), flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
