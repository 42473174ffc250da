#!/usr/bin/env python3
"""Controls of the ``bd_train_step`` cells: what the comparison that
decides ``correct`` must refuse. Each control plants one fault in the
system, or computes the reference one precision down, and goes through
the cell's own comparison (``runners.bd_train_step.check_parity``, the
configuration file's limits); the drill passes when every control ends
``correct: false``. A limit's upper reading in ``PERF.md`` is what a
control read here on the chip. ``benchmarks/control_drill.py`` is the
same drill for the kinds that came before (its ``judged`` is used here).

    python3 benchmarks/bd_control_drill.py --workload <cell> --seed <n> \\
        [--controls a,b] [--out chiprun_out/controls_<cell>]

One process, set-up as the runner's up to the comparison, no window.
``--rehearsal`` walks the same flow at tiny widths on the CPU (the tests
import the faults from here).

``lower_precision``: the plain reference itself in bfloat16 at default
matmul precision in the system's place. ``causal_mask``: a causal mask
in the rule's place, a noisy query seeing the clean copy of its own
block too (``<=`` for ``<``); over thousands of keys four more move
nothing the whole sequence's comparison can tell from rounding, and the
comparison over the first blocks refuses it (``check_parity``).
``unshared_positions``: position ``i`` in the place of ``i mod L``, the
clean copy turned by ``L`` further positions. ``sigmoid_router``:
sigmoid for softmax. ``no_inverse_t``: a masked position's ``1 / t``
left out of the loss.

One more fault is the CPU tests' alone (``FAULTS``; it runs here by
name and does not count towards the drill): ``no_qk_norm``, q and k not
normalised.
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
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402 — JAX is imported later
from benchmarks.control_drill import judged  # noqa: E402
from benchmarks.harness import log  # noqa: E402


@contextlib.contextmanager
def _replaced(owner, name: str, value):
    kept = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield None          # the system stays the model's timed path
    finally:
        setattr(owner, name, kept)


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

    def system(p, b, ids, labels, t):
        def loss_of(float_leaves):
            return low.loss({**p, **float_leaves}, cfg, ids, labels, t, b)
        loss, g = jax.value_and_grad(loss_of)({k: p[k] for k in leaves})
        return loss, g, jnp.zeros((), jnp.int32)
    return contextlib.nullcontext(system)


def causal_mask(run=None, model=None, leaves=None):
    import jax.numpy as jnp

    from paddle_tpu.kernels import flash_attention as fa

    def allowed(q_pos, k_pos, seq, length, block):
        # a clean key is seen by every query of its block and of later
        # ones: the mask of a causal model over the two copies
        rule = fa_allowed(q_pos, k_pos, seq, length, block)
        own = jnp.logical_and(
            jnp.logical_and(q_pos < length, k_pos >= length),
            q_pos // block == (k_pos - length) // block)
        return jnp.logical_or(rule, jnp.logical_and(own, k_pos < seq))
    fa_allowed = fa.bd_allowed
    return _replaced(fa, "bd_allowed", allowed)


def unshared_positions(run=None, model=None, leaves=None):
    from paddle_tpu.nn.layers.transformer import GroupedQueryAttention
    kept = GroupedQueryAttention._qk
    return _replaced(GroupedQueryAttention, "_qk",
                     lambda self, q, k, position_ids: kept(self, q, k, None))


def no_qk_norm(run=None, model=None, leaves=None):
    from paddle_tpu.nn.layers.norm import RMSNorm
    kept = RMSNorm.forward

    def forward(self, x):
        # the heads' norms are the only ones 4-D inputs reach
        return x if x.ndim == 4 else kept(self, x)
    return _replaced(RMSNorm, "forward", forward)


def sigmoid_router(run=None, model=None, leaves=None):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.layers.moe import DroplessMoE

    def route(self, tokens):
        s = jax.nn.sigmoid(tokens.astype(jnp.float32)
                           @ self.router_weight.astype(jnp.float32))
        _, chosen = jax.lax.top_k(s + self.e_score_correction_bias,
                                  self.top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        return chosen, w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return _replaced(DroplessMoE, "route", route)


def no_inverse_t(run=None, model=None, leaves=None):
    import jax.numpy as jnp

    from paddle_tpu.models import sdar_moe
    kept = sdar_moe.block_diffusion_loss
    # the runner and the tests look the loss up on paddle_tpu.models
    import paddle_tpu.models as models

    def loss(out, labels, t):
        return kept(out, labels, jnp.ones_like(t))

    @contextlib.contextmanager
    def both():
        with _replaced(sdar_moe, "block_diffusion_loss", loss), \
                _replaced(models, "block_diffusion_loss", loss):
            yield None
    return both()


CONTROLS = {"lower_precision": lower_precision, "causal_mask": causal_mask,
            "unshared_positions": unshared_positions,
            "sigmoid_router": sigmoid_router, "no_inverse_t": no_inverse_t}
# what the CPU tests plant in their float32 comparison
FAULTS = {"no_qk_norm": no_qk_norm,
          **{k: v for k, v in CONTROLS.items() if k != "lower_precision"}}


def controls(run, names: List[str]) -> Dict[str, Dict[str, Any]]:
    from benchmarks.runners import bd_train_step as runner

    model = runner.build_model(run)
    batch = runner.make_batches(run)[0]
    runner.fit_router_bias(run, model, batch[0])
    leaves = runner.watched_leaves(run)
    # the sound reference, once: every control is held to the same
    reference = runner.reference_side(run, model, batch)
    out = {}
    for name in names:
        with {**FAULTS, **CONTROLS}[name](run, model, leaves) as system:
            out[name] = judged(run, lambda: runner.check_parity(
                run, model, batch, system=system, reference=reference))
        gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 77)
    ap.add_argument("--controls", default="",
                    help="comma-separated; default: all")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    run = harness.Run(harness.parse_args(
        ["--workload", args.workload, "--seed", str(args.seed),
         "--seconds", "1"] + (["--rehearsal"] if args.rehearsal else [])),
        time.perf_counter())
    run.sweeping = True
    if run.mix["kind"] != "bd_train_step":
        raise SystemExit(f"{args.workload} is no bd_train_step cell: "
                         "benchmarks/control_drill.py has its controls")
    names = [n for n in args.controls.split(",") if n] or list(CONTROLS)
    harness.prepare_backend(run.rehearsal, run.chips)
    device = harness.find_devices(run)
    harness.enable_cache()
    log(f"controls {names} of {args.workload} seed {args.seed} on {device}")
    results = controls(run, names)
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
