"""Real-chip performance experiments (run when a TPU is reachable).

Each experiment isolates one hypothesis from the round-2 profile of the
BERT train step (34.6 ms/step wall, 31.8 ms device: 58% matmul fusions,
~19% per-buffer async copies/slices — ~1.1k copy + 1.9k slice ops/step
— 5.5% dropout-mask compare fusions, 5% loss-region reductions, 2.2%
rng-bit-generator). Usage:

    python tools/perf_lab.py leafcount   # runtime cost vs #state leaves
    python tools/perf_lab.py fused      # fused vs per-leaf opt state
    python tools/perf_lab.py batch      # batch-size sweep
    python tools/perf_lab.py hlostats   # CPU-only: copy/transpose counts
    python tools/perf_lab.py all        # all CHIP experiments (hlostats
                                        # is CPU-only and must run in its
                                        # own process: it pins the
                                        # platform to cpu before init)
"""

from __future__ import annotations

import sys
import time


def log(msg):
    print(f"[perf_lab] {msg}", file=sys.stderr, flush=True)


def _sync(x):
    import jax
    jax.block_until_ready(x)
    # remote-dispatch backends need a value fetch for a hard sync
    import numpy as np
    leaf = jax.tree.leaves(x)[0]
    np.asarray(leaf.ravel()[0])


def exp_leafcount():
    """Hypothesis: the runtime charges ~2-4us per donated buffer per
    step. Same total bytes split into N leaves, trivial update."""
    import jax
    import jax.numpy as jnp

    total = 64 * 1024 * 1024 // 4  # 64 MB of f32
    for n in (8, 64, 256, 1024):
        per = total // n
        state = {f"p{i}": jnp.zeros((per,), jnp.float32)
                 for i in range(n)}
        step_d = jax.jit(lambda s: {k: v + 1.0 for k, v in s.items()},
                         donate_argnums=(0,))
        for _ in range(3):
            state = step_d(state)
        _sync(state)
        t0 = time.perf_counter()
        iters = 50
        for _ in range(iters):
            state = step_d(state)
        _sync(state)
        dt = (time.perf_counter() - t0) / iters
        log(f"leaves={n:5d}: {dt * 1e6:8.1f} us/step "
            f"({dt * 1e6 / n:6.2f} us/leaf)")


def _repo_root():
    import os
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def exp_fused():
    """BERT step: per-leaf vs fused optimizer state, measured."""
    import os

    import jax

    os.environ["PT_BENCH_FUSED"] = ""
    sys.path.insert(0, _repo_root())
    import bench
    from paddle_tpu.core.place import accelerator_available
    on_accel = accelerator_available()
    if not on_accel:
        log("no accelerator: running the tiny CPU shape (numbers only "
            "meaningful on a real chip)")
    bench.bench_bert(on_accel=on_accel)


def exp_batch():
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   pretraining_loss)
    from paddle_tpu.static import TrainStep

    config = BertConfig()
    for batch in (4, 8, 16):
        pt.seed(0)
        model = BertForPretraining(config)
        model.to(dtype="bfloat16")
        opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01)
        step = TrainStep(model, opt,
                         lambda out, a, b: pretraining_loss(out, a, b))
        rng = np.random.default_rng(0)
        seq = 512
        ids = rng.integers(0, config.vocab_size, (batch, seq)) \
            .astype(np.int32)
        mlm = rng.integers(0, config.vocab_size, (batch, seq)) \
            .astype(np.int64)
        nsp = rng.integers(0, 2, (batch,)).astype(np.int64)
        sys.path.insert(0, _repo_root())
        from bench import warmup_and_time
        dt = warmup_and_time(lambda: step(ids, labels=(mlm, nsp)), 20)
        log(f"batch={batch}: {dt * 1e3:.1f} ms/step "
            f"{batch * seq / dt:.0f} tok/s")
        del model, step


def exp_hlostats():
    """Structural evidence WITHOUT a chip: compile small-config train
    steps on CPU and count buffer-shuffling ops (copy / transpose /
    bitcast / parameters) in the optimized HLO. The per-leaf vs fused
    optimizer-state gap and the NCHW vs NHWC transpose burden both show
    up here before a single chip-second is spent (the chip decides the
    final flag; this decides what's worth timing)."""
    import collections
    import re

    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.static import TrainStep

    jax.config.update("jax_platforms", "cpu")

    def hlo_counts(text):
        # [\w-]+ so hyphenated async/collective ops (copy-start,
        # dynamic-slice, all-reduce, rng-bit-generator) are counted —
        # on TPU HLO those carry the buffer traffic this tool exists
        # to measure. copy-start/copy-done fold into "copy".
        ops = collections.Counter()
        for m in re.finditer(
                r"^\s*(?:ROOT )?%?[\w.\-]+ = [^=]*? ([\w-]+)\(",
                text, re.M):
            name = m.group(1)
            if name in ("copy-start", "copy-done"):
                name = "copy"
            ops[name] += 1
        return ops

    def entry_params(text):
        # count parameters of the ENTRY computation only — nested
        # fusion/reduce subcomputations each carry their own
        # parameter() lines and would swamp the state-leaf count
        m = re.search(r"^ENTRY [^{]*\{(.*?)^\}", text, re.M | re.S)
        body = m.group(1) if m else text
        return len(re.findall(r"= [^=]*? parameter\(", body))

    def report(name, text):
        ops = hlo_counts(text)
        interesting = {k: ops[k] for k in
                       ("copy", "transpose", "bitcast", "fusion",
                        "convolution", "dot", "reduce", "dynamic-slice",
                        "dynamic-update-slice") if ops[k]}
        log(f"{name}: entry_params={entry_params(text)} {interesting}")
        return ops

    # --- BERT-small step: per-leaf vs fused optimizer state
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   pretraining_loss)
    config = BertConfig(num_hidden_layers=4, hidden_size=256,
                        num_attention_heads=4, intermediate_size=1024,
                        vocab_size=4096, max_position_embeddings=128)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 4096, (2, 64)).astype(np.int32)
    mlm = rng.integers(0, 4096, (2, 64)).astype(np.int64)
    nsp = rng.integers(0, 2, (2,)).astype(np.int64)
    results = {}
    for fused in (False, True):
        pt.seed(0)
        m = BertForPretraining(config)
        m.to(dtype="bfloat16")
        o = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                               fused_state=fused)
        step = TrainStep(m, o, lambda out, a, b:
                         pretraining_loss(out, a, b))
        text = step.compiled_hlo(ids, labels=(mlm, nsp))
        results[fused] = report(f"bert4L fused={fused}", text)
    cp, ct = results[False]["copy"], results[True]["copy"]
    log(f"bert4L: fused state changes HLO copies {cp} -> {ct}")

    # --- ResNet block stack: NCHW vs NHWC transpose burden
    from paddle_tpu.models.resnet import BasicBlock, ResNet
    x = rng.normal(0, 1, (2, 3, 32, 32)).astype(np.float32)
    y = rng.integers(0, 10, (2,)).astype(np.int64)
    for df in ("NCHW", "NHWC"):
        pt.seed(0)
        net = ResNet(BasicBlock, [1, 1, 1, 1], num_classes=10,
                     data_format=df)
        opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
        step = TrainStep(net, opt, lambda out, t:
                         pt.nn.functional.cross_entropy(out, t))
        data = x if df == "NCHW" else np.transpose(x, (0, 2, 3, 1))
        text = step.compiled_hlo(data, labels=y)
        report(f"resnet18-thin {df}", text)


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    known = {"leafcount", "batch", "fused", "hlostats", "all"}
    if which not in known:
        raise SystemExit(f"unknown experiment {which!r}; pick from "
                         f"{sorted(known)}")
    sys.path.insert(0, _repo_root())
    if which == "hlostats":
        # CPU-only experiment: reads compiled HLO, needs no chip
        exp_hlostats()
        return
    import jax
    from paddle_tpu.sysconfig import enable_compile_cache
    enable_compile_cache()
    log(f"backend={jax.default_backend()} devices={jax.devices()}")
    if which in ("leafcount", "all"):
        exp_leafcount()
    if which in ("batch", "all"):
        exp_batch()
    if which in ("fused", "all"):
        exp_fused()


if __name__ == "__main__":
    main()
