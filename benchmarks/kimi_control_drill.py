#!/usr/bin/env python3
"""Controls of the ``kimi_lm_train_step`` cells: what the comparison that
decides ``correct`` must refuse. Each control plants one fault in the
system, or computes the reference one precision down, and goes through
the cell's own comparison (``runners.lm_train_step.check_parity`` on the
runner's sequences, the configuration file's limits); the drill passes
when every control ends ``correct: false``. A limit's upper reading in
``PERF.md`` is what a control read here on the chip. After
``bd_control_drill.py``; ``benchmarks/control_drill.py``'s ``judged`` is
used here.

    python3 benchmarks/kimi_control_drill.py --workload <cell> --seed <n> \\
        [--controls a,b] [--out chiprun_out/controls_<cell>]

One process, set-up as the runner's up to the comparison, no window.
``--rehearsal`` walks the same flow at tiny widths on the CPU (the tests
import the faults from here).

``lower_precision``: the plain reference itself in bfloat16 at default
matmul precision in the system's place. ``scalar_decay``: each KDA
head's log-decay averaged over its key channels (Mamba-2's scalar decay
in the place of a decay a channel). ``no_delta``: the delta rule's
``-beta k k^T S`` term dropped (``S_t = Diag(exp(a_t)) S_{t-1} + beta_t
k_t v_t^T``: gated linear attention). ``rope_in_mla``: the latent
attention's 64-wide q and k parts turned by rotary positions at theta
10,000 (the model is NoPE). ``softmax_router``: softmax for sigmoid.
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

    def system(p, b, ids, labels):
        def loss_of(float_leaves):
            return low.loss({**p, **float_leaves}, cfg, ids, labels, b)
        loss, g = jax.value_and_grad(loss_of)({k: p[k] for k in leaves})
        return loss, g, jnp.zeros((), jnp.int32)
    return contextlib.nullcontext(system)


def scalar_decay(run=None, model=None, leaves=None):
    import jax.numpy as jnp

    from paddle_tpu.nn.layers import kda
    kept = kda.kda_chunked

    def rule(q, k, v, g, beta, scale, chunk=64):
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        return kept(q, k, v, g, beta, scale, chunk)
    return _replaced(kda, "kda_chunked", rule)


def gated_linear_attention(q, k, v, g, beta, scale, chunk=64):
    """``S_t = Diag(exp(a_t)) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T
    q_t scale``: the delta rule without its ``-beta k k^T S`` term, a
    position at a time in blocks of ``chunk`` recomputed in the backward
    pass. A fault's system, so plain."""
    import jax
    import jax.numpy as jnp

    bsz, length, h, dk = q.shape
    f32 = jnp.float32

    def step(state, inp):
        q_t, k_t, v_t, a_t, b_t = inp
        state = state * jnp.exp(a_t)[..., None] + jnp.einsum(
            "bhk,bhv->bhkv", k_t, b_t[..., None] * v_t)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state) * scale

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    pad = -length % chunk

    def blocks(t):                      # [L / chunk, chunk, B, ..]
        t = jnp.pad(t.astype(f32), ((0, 0), (0, pad))
                    + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t, 1, 0).reshape(-1, chunk, bsz, *t.shape[2:])

    _, o = jax.lax.scan(block, jnp.zeros((bsz, h, dk, v.shape[-1]), f32),
                        tuple(blocks(t) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(o.reshape(-1, *o.shape[2:]), 0, 1)[:, :length]
    return o.astype(v.dtype)


def no_delta(run=None, model=None, leaves=None):
    from paddle_tpu.nn.layers import kda
    return _replaced(kda, "kda_chunked", gated_linear_attention)


def rope_in_mla(run=None, model=None, leaves=None):
    """q_pe and k_pe turned at theta 10,000: the latent attention's
    forward with the turn, and nothing else changed."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import maybe_flash_attention
    from paddle_tpu.nn.layers.mla import MultiHeadLatentAttention
    from paddle_tpu.nn.layers.transformer import rotate_half_rope

    def forward(self, x):
        b, t, _ = x.shape
        h, nope = self.num_heads, self.qk_nope_head_dim
        pos = jnp.arange(t)
        q = self.q_proj(x).reshape(b, t, h, self.qk_head_dim)
        q = jnp.concatenate([q[..., :nope], rotate_half_rope(
            q[..., nope:], pos, 10000.0)], -1)
        c, k_pe = jnp.split(self.kv_a_proj_with_mqa(x),
                            [self.kv_lora_rank], axis=-1)
        k_pe = rotate_half_rope(k_pe[:, :, None], pos, 10000.0)
        k_nope, v = jnp.split(
            self.kv_b_proj(self.kv_a_layernorm(c)).reshape(
                b, t, h, nope + self.v_head_dim), [nope], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_pe, (b, t, h, k_pe.shape[-1]))], -1)
        out = maybe_flash_attention(
            q, k, v, causal=True, scale=self.qk_head_dim ** -0.5,
            training=self.training, layout="bthd")
        return self.o_proj(out.reshape(b, t, h * self.v_head_dim))
    return _replaced(MultiHeadLatentAttention, "forward", forward)


@contextlib.contextmanager
def softmax_router(run=None, model=None, leaves=None):
    experts = [layer.mlp for layer in model.layers if not layer.dense]
    for mlp in experts:
        mlp.score_func = "softmax"
    try:
        yield None
    finally:
        for mlp in experts:
            mlp.score_func = "sigmoid"


CONTROLS = {"lower_precision": lower_precision,
            "scalar_decay": scalar_decay, "no_delta": no_delta,
            "rope_in_mla": rope_in_mla, "softmax_router": softmax_router}
# what the CPU tests plant in their float32 comparison
FAULTS = {k: v for k, v in CONTROLS.items() if k != "lower_precision"}


def controls(run, names: List[str]) -> Dict[str, Dict[str, Any]]:
    from benchmarks.runners import kimi_lm_train_step as runner
    from benchmarks.runners.lm_train_step import check_parity

    model = runner.build_model(run)
    batches = runner.make_batches(run)
    runner.fit_router_bias(run, model, batches[0][0])
    batch = runner.parity_batch(run, batches)
    leaves = list(run.config["tolerances"]["grad_rel_l2"])
    out = {}
    for name in names:
        with CONTROLS[name](run, model, leaves) as system:
            out[name] = judged(run, lambda: check_parity(
                run, model, batch, system=system))
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
    if run.mix["kind"] != "kimi_lm_train_step":
        raise SystemExit(f"{args.workload} is no kimi_lm_train_step cell")
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
