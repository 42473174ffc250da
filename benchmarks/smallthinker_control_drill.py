#!/usr/bin/env python3
"""Controls of the ``smallthinker_lm_train_step`` cells: what the
comparison that decides ``correct`` must refuse. Each control plants one
fault in the system, or computes the reference one precision down, and
goes through the cell's own comparison (``runners.smallthinker_lm_train_
step.check_parity`` on the runner's sequences, in the timed state: the
selection bias fitted, the configuration file's limits); the drill
passes when every control ends ``correct: false``. A limit's upper
reading in ``PERF.md`` is what a control read here on the chip. After
``kimi_control_drill.py``, whose ``_replaced`` is used here.

    python3 benchmarks/smallthinker_control_drill.py --workload <cell> \\
        --seed <n> [--controls a,b] [--out <dir>]

One process, set-up as the runner's up to the comparison, no window.
``--rehearsal`` walks the same flow at tiny widths on the CPU (the tests
import the faults from here).

``lower_precision``: the plain reference itself in bfloat16 at default
matmul precision in the system's place, routing by its own choice.
``no_window``: the window layers
causal over every earlier key. ``half_window``: their window half as
long. ``rope_on_global``: the global layer's q and k turned by rotary
positions too. ``router_after_attention``: the router on the experts'
input ``norm_post(h')`` in place of ``norm_in(h)``. ``silu_experts``:
SwiGLU experts in ReGLU's place.

A probe is run by name through the same comparison but is no control:
what it reads is reported and the drill does not ask it to fail.
``bf16_but_the_loss``: ``lower_precision`` with the cross-entropy taken
in float32, a program whose router and norms are bfloat16 and whose
loss is not (PERF.md section 6 has what the chip read).
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
from benchmarks.kimi_control_drill import _replaced  # noqa: E402


@contextlib.contextmanager
def _set(objects, name: str, value):
    """``obj.name = value`` on every object while the control runs."""
    kept = [getattr(o, name) for o in objects]
    for o in objects:
        setattr(o, name, value)
    try:
        yield None          # the system stays the model's timed path
    finally:
        for o, v in zip(objects, kept):
            setattr(o, name, v)


def _reference_in(run, leaves, loss_dtype=None):
    """The plain reference one precision down (a second copy of its
    module, parameters and products in bfloat16 at default precision,
    the loss in ``loss_dtype`` where given) as ``check_parity``'s system
    side, routing by its own choice."""
    import jax
    import jax.numpy as jnp

    spec = importlib.util.find_spec(
        f"benchmarks.references.{run.cell['config']}")
    low = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(low)
    low.DTYPE, low.PRECISION = jnp.bfloat16, "default"
    low.LOSS_DTYPE = loss_dtype
    cfg = run.config["model"]
    scored = cfg["moe_num_primary_experts_total"]

    def system(p, b, ids, labels):
        def loss_of(float_leaves):
            return low.loss_and_routing({**p, **float_leaves}, cfg, ids,
                                        labels, b)
        (loss, (_, _, chosen)), g = jax.value_and_grad(
            loss_of, has_aux=True)({k: p[k] for k in leaves})
        chosen = chosen.reshape(chosen.shape[0], -1, chosen.shape[-1])
        loads = jax.vmap(lambda c: jnp.bincount(
            c.reshape(-1), length=scored))(chosen)
        return loss, g, jnp.zeros((), jnp.int32), loads, chosen
    return contextlib.nullcontext(system)


def lower_precision(run, model=None, leaves=None):
    return _reference_in(run, leaves)


def bf16_but_the_loss(run, model=None, leaves=None):
    import jax.numpy as jnp
    return _reference_in(run, leaves, jnp.float32)


def _attention(model, window: bool):
    return [layer.self_attn for layer in model.layers
            if (layer.self_attn.sliding_window is not None) == window]


def no_window(run=None, model=None, leaves=None):
    return _set(_attention(model, True), "sliding_window", None)


def half_window(run=None, model=None, leaves=None):
    return _set(_attention(model, True), "sliding_window",
                model.config.sliding_window_size // 2)


def rope_on_global(run=None, model=None, leaves=None):
    return _set(_attention(model, False), "rope_theta",
                model.config.rope_theta)


def router_after_attention(run=None, model=None, leaves=None):
    """The block's forward with the router reading the experts' input."""
    import jax

    from paddle_tpu.models.smallthinker import SmallThinkerBlock

    def forward(self, x):
        with jax.named_scope("pt.attn"):
            x = x + self.self_attn(self.input_layernorm(x))
        with jax.named_scope("pt.moe_route"):
            u = self.post_attention_layernorm(x)
        y, stats = self.mlp(u)
        with jax.named_scope("pt.moe_route"):
            return x + y, stats
    return _replaced(SmallThinkerBlock, "forward", forward)


def silu_experts(run=None, model=None, leaves=None):
    return _set([layer.mlp for layer in model.layers], "gate_act", "silu")


CONTROLS = {"lower_precision": lower_precision, "no_window": no_window,
            "half_window": half_window, "rope_on_global": rope_on_global,
            "router_after_attention": router_after_attention,
            "silu_experts": silu_experts}
# what the CPU tests plant in their float32 comparison
FAULTS = {k: v for k, v in CONTROLS.items() if k != "lower_precision"}
PROBES = {"bf16_but_the_loss": bf16_but_the_loss}


def controls(run, names: List[str]) -> Dict[str, Dict[str, Any]]:
    from benchmarks.runners import smallthinker_lm_train_step as runner
    from benchmarks.runners.lm_train_step import fit_router_bias

    model = runner.build_model(run)
    batches = runner.make_batches(run)
    # the state the runner compares and times: the selection bias fitted
    fit_router_bias(run, model, batches[0][0])
    batch = runner.parity_batch(run, batches)
    leaves = list(run.config["tolerances"]["grad_rel_l2"])
    out = {}
    for name in names:
        with {**CONTROLS, **PROBES}[name](run, model, leaves) as system:
            out[name] = judged(run, lambda: runner.check_parity(
                run, model, batch, system=system))
        gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 77)
    ap.add_argument("--controls", default="",
                    help="comma-separated controls or probes; "
                    "default: every control")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    run = harness.Run(harness.parse_args(
        ["--workload", args.workload, "--seed", str(args.seed),
         "--seconds", "1"] + (["--rehearsal"] if args.rehearsal else [])),
        time.perf_counter())
    run.sweeping = True
    if run.mix["kind"] != "smallthinker_lm_train_step":
        raise SystemExit(f"{args.workload} is no smallthinker_lm_train_step "
                         "cell")
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
    passed = all(not r["correct"] for name, r in results.items()
                 if name in CONTROLS)
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
