"""Capture an on-chip profile of one bench model's train step and
aggregate op self-times from the perfetto trace.

Usage: python tools/profile_step.py {bert|resnet} [batch]
Writes profiles/<model>/... and prints the top-30 ops by total duration
plus a category rollup (matmul/conv/copy/transpose/elementwise/other) —
the same aggregation the round-2 README profile used.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def capture(model: str, batch: int) -> str:
    import numpy as np

    import jax
    import paddle_tpu as pt
    from paddle_tpu.static import TrainStep

    outdir = os.path.join(ROOT, "profiles", model)
    os.makedirs(outdir, exist_ok=True)
    rng = np.random.default_rng(0)
    if model == "bert":
        from paddle_tpu.models import (BertConfig, BertForPretraining,
                                       pretraining_loss)
        config = BertConfig()
        seq = 512
        pt.seed(0)
        m = BertForPretraining(config)
        m.to(dtype="bfloat16")
        o = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01)
        step = TrainStep(m, o, lambda out, a, b: pretraining_loss(out, a, b))
        ids = rng.integers(0, config.vocab_size, (batch, seq)).astype("int32")
        mlm = rng.integers(0, config.vocab_size, (batch, seq)).astype("int64")
        nsp = rng.integers(0, 2, (batch,)).astype("int64")
        run = lambda: step(ids, labels=(mlm, nsp))
    else:
        import jax.numpy as jnp
        from paddle_tpu.models.resnet import resnet50
        layout = os.environ.get("PT_PROF_LAYOUT", "NCHW")
        pt.seed(0)
        m = resnet50(data_format=layout)
        m.to(dtype="bfloat16")
        o = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
        step = TrainStep(m, o, lambda out, t:
                         pt.nn.functional.cross_entropy(out, t))
        x = rng.normal(0, 1, (batch, 3, 224, 224))
        if layout == "NHWC":
            x = np.transpose(x, (0, 2, 3, 1))
        x = jnp.asarray(x, jnp.bfloat16)
        y = rng.integers(0, 1000, (batch,)).astype("int64")
        run = lambda: step(x, labels=y)

    # warm up (compile) outside the trace
    for _ in range(3):
        float(run()["loss"])
    with jax.profiler.trace(outdir):
        for _ in range(5):
            r = run()
        float(r["loss"])
    return outdir


def aggregate(outdir: str) -> None:
    # parsing/rollup shared with tools/trace_report.py:
    # paddle_tpu.observability.trace_agg (keeps the round-4 lesson in
    # one place: only the "XLA Ops" lane, hlo_category over name
    # guessing)
    from paddle_tpu.observability import trace_agg

    traces = trace_agg.find_xla_traces(outdir)
    if not traces:
        # a profiler run with no trace produced no data — exit nonzero
        print(f"no trace.json.gz under {outdir}", file=sys.stderr)
        sys.exit(2)
    events = trace_agg.load_trace_events(traces[-1])
    try:
        rollup = trace_agg.xla_op_rollup(events)
    except trace_agg.TraceFormatError as e:
        # without lane metadata the aggregation would silently revert
        # to summing Steps + Modules + Ops (the double-count the
        # round-4 rewrite removed) — refuse to print
        # authoritative-looking numbers instead
        print(str(e), file=sys.stderr)
        sys.exit(2)
    if not rollup["steps"]:
        print("warning: no 'XLA Modules' step events; reporting "
              "whole-trace totals as one step", file=sys.stderr)
    print()
    print(trace_agg.format_xla_rollup(rollup, top=30))


def main() -> None:
    sys.path.insert(0, ROOT)
    from paddle_tpu.core.place import accelerator_available
    if not accelerator_available():
        print("[profile] no accelerator device (CPU fallback would "
              "record a host-only trace); aborting", file=sys.stderr)
        sys.exit(3)
    model = sys.argv[1] if len(sys.argv) > 1 else "bert"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else \
        (8 if model == "bert" else 64)
    outdir = capture(model, batch)
    aggregate(outdir)


if __name__ == "__main__":
    main()
