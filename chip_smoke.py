#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

One process, no children. It drives the two main paths once, through
the entry points a user calls, at the full width of a model the repo
supports (random weights from ``--seed``):

- ``train``: BERT-base pretraining (12x768, vocab 30522) in bf16 through
  ``static.TrainStep``, batch 16 x seq 512, twenty steps on one batch.
- ``serve``: a GPT-2-small-width decoder (12x768, vocab 50257) behind
  ``LLMEngine`` -> ``inference.Server`` -> ``Client.generate_stream`` on
  loopback, four requests checked token for token against
  ``model.generate()``.

``--chips 4`` runs instead ONLY the sharded path and what it is compared
with: BERT-base through ``ShardedTrainStep`` on a dp2 x mp2 mesh against
the one-device ``TrainStep`` on the same seed and batch.

The last stdout line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``,
with the device as JAX reports it. Any failure ends the run: that line
says ``"ok": false`` and the exit code is non-zero. Without a TPU the
run fails; ``--cpu-rehearsal`` walks the same control flow at a tiny size
on the CPU backend (and so can never print a TPU device line).

Wall times printed here are set-up facts (compilation included), not
benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import traceback
from dataclasses import dataclass, field

NEW_TOKENS = 16
PROMPT_LENS = (8, 48, 8, 48)
# The usual BERT recipe is AdamW(1e-4). With no warm-up and at full width
# that recipe's first steps DIVERGE on one fixed batch (PR 22: 11.2 ->
# 12.8 -> 16.8 on the chip, 11.2 -> 14.9 on the CPU's XLA path, so it is
# the optimizer and not a kernel): every weight moves 1e-4 along its
# gradient's sign, about a tenth of each pre-activation per matrix per
# step. A smoke must see the loss fall, so it steps at a tenth of that,
# and for long enough that the fall (about 0.03 a step on the chip)
# clears the +-0.1 that a fresh dropout mask puts on every step's loss:
# three steps read 11.22, 11.03, 11.23; twelve ended at 10.81.
LEARNING_RATE = 1e-5
TRAIN_STEPS = 20


@dataclass(frozen=True)
class Sizes:
    bert: dict = field(default_factory=dict)   # BertConfig overrides
    batch: int = 16
    seq: int = 512
    gpt: dict = field(default_factory=lambda: dict(
        vocab_size=50257, hidden_size=768, num_layers=12, num_heads=12,
        intermediate_size=3072, max_position_embeddings=1024))
    pool_blocks: int = 256


# control-flow rehearsal only: widths cut so the CPU backend and the
# Pallas interpreter finish in a minute; the vocabulary stays, so the
# first-loss window below means the same thing
REHEARSAL = Sizes(
    bert=dict(hidden_size=128, num_hidden_layers=2, num_attention_heads=2,
              intermediate_size=256, max_position_embeddings=64,
              hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0),
    batch=4, seq=64,
    gpt=dict(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
             intermediate_size=256, max_position_embeddings=128),
    pool_blocks=32)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    log(f"  ok: {what}")


def _bert_and_data(sizes: Sizes, seed: int):
    """(build, data): ``build()`` makes the flagship —
    bf16 BERT-base for pretraining + AdamW, but see LEARNING_RATE — from
    the seed, so two calls give identical weights; data is one fixed
    batch."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import BertConfig, BertForPretraining

    config = BertConfig(**sizes.bert)

    def build():
        pt.seed(seed)
        model = BertForPretraining(config)
        model.to(dtype="bfloat16")
        return model, pt.optimizer.AdamW(LEARNING_RATE, weight_decay=0.01)

    rng = np.random.default_rng(seed)
    shape = (sizes.batch, sizes.seq)
    ids = rng.integers(0, config.vocab_size, shape).astype(np.int32)
    mlm = rng.integers(0, config.vocab_size, shape).astype(np.int64)
    nsp = rng.integers(0, 2, (sizes.batch,)).astype(np.int64)
    return build, (ids, mlm, nsp)


def _run_steps(step, data, n: int) -> list:
    """n steps on the one batch, each ended by block_until_ready on the
    loss; logs wall time and the recompile tracker's trace count."""
    from paddle_tpu import observability as obs

    ids, mlm, nsp = data
    losses = []
    for i in range(n):
        t0 = time.perf_counter()
        loss = step(ids, labels=(mlm, nsp))["loss"]
        loss.block_until_ready()
        dt = time.perf_counter() - t0
        losses.append(float(loss))
        traces = obs.recompile_tracker().get(step._span_name).traces
        log(f"  {step._span_name} step {i}: loss={losses[-1]:.4f} "
            f"wall={dt:.2f}s compiles_so_far={traces}")
    return losses


def train_phase(sizes: Sizes, seed: int) -> None:
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu.models import pretraining_loss
    from paddle_tpu.static import TrainStep

    # metrics on: the skip-step guard's counter is only fed while the
    # observability switch is on, and a silent 0 would prove nothing
    pt.set_flags({"enable_metrics": True})
    try:
        build, data = _bert_and_data(sizes, seed)
        model, opt = build()
        step = TrainStep(model, opt, pretraining_loss)
        watched = "bert.encoder.layers.0.self_attn.q_proj.weight"
        before = np.asarray(step.state["params"][watched])
        losses = _run_steps(step, data, TRAIN_STEPS)
        # the guard's verdict reaches the host by a callback, or (with
        # the persistent cache asked for) rides the step's outputs
        jax.effects_barrier()
        step.flush_signals()
        skipped = obs.counter("nonfinite_steps_total").total()
        after = np.asarray(step.state["params"][watched])

        require(all(np.isfinite(losses)), "losses finite: "
                + " ".join(f"{x:.3f}" for x in losses))
        # ln(30522) + ln(2) ~ 11.0 for random weights
        require(10.0 <= losses[0] <= 12.0,
                f"first loss {losses[0]:.4f} within [10, 12]")
        require(losses[-1] < losses[0],
                f"last loss {losses[-1]:.4f} < first {losses[0]:.4f}")
        require(not np.array_equal(before, after),
                f"parameter leaf {watched} changed")
        require(skipped == 0, f"nonfinite_steps_total == 0 ({skipped})")
        if jax.default_backend() == "tpu":
            t0 = time.perf_counter()
            ids, mlm, nsp = data
            n_kernels = step.compiled_hlo(
                ids, labels=(mlm, nsp)).count("tpu_custom_call")
            log(f"  compiled step text: {n_kernels} tpu_custom_call "
                f"mentions (read in {time.perf_counter() - t0:.1f}s)")
            require(n_kernels > 0,
                    "Pallas kernels are in the compiled train step")
        else:
            log("  cpu rehearsal: no Mosaic kernels to look for")
    finally:
        pt.set_flags({"enable_metrics": False})


def serve_phase(sizes: Sizes, seed: int) -> None:
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.inference import Client, Server
    from paddle_tpu.models import GPTConfig, GPTLanguageModel
    from paddle_tpu.serving_llm import LLMEngine

    # The chip's default f32 matmul is one bf16 pass, and the engine and
    # model.generate() order their sums differently: with near-uniform
    # random-weight logits that can flip an argmax. Compare both sides at
    # full precision. Set process-wide, not as a context manager: the
    # engine steps on the server's thread, which a thread-local scope
    # would not reach.
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        pt.seed(seed)
        config = GPTConfig(**sizes.gpt)
        model = GPTLanguageModel(config)
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, config.vocab_size, (n,)).astype(np.int32)
                   for n in PROMPT_LENS]

        t0 = time.perf_counter()
        want = [np.asarray(model.generate(
            p[None], max_new_tokens=NEW_TOKENS))[0].tolist()
            for p in prompts]
        log(f"  model.generate() reference for {len(prompts)} prompts: "
            f"{time.perf_counter() - t0:.1f}s")

        eng = LLMEngine(model, block_size=16, pool_blocks=sizes.pool_blocks)
        srv = Server(None, llm_engine=eng)   # builds libptnative.so
        got = [None] * len(prompts)
        errors = []

        def ask(i: int) -> None:
            try:
                # per-chunk deadline covers the first step's compiles
                with Client(port=srv.port, timeout_s=900.0,
                            deadline_s=900.0) as cli:
                    t = time.perf_counter()
                    got[i] = [int(tok) for chunk in cli.generate_stream(
                        prompts[i], max_new_tokens=NEW_TOKENS)
                        for tok in np.asarray(chunk).ravel()]
                    log(f"  request {i} (prompt {len(prompts[i])}): "
                        f"{len(got[i])} tokens in "
                        f"{time.perf_counter() - t:.1f}s")
            except Exception as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        try:
            ask(0)
            ask(1)
            steps_before = eng._steps_total
            pair = [threading.Thread(target=ask, args=(i,)) for i in (2, 3)]
            for th in pair:
                th.start()
            for th in pair:
                th.join(timeout=1000.0)
            pair_steps = eng._steps_total - steps_before
        finally:
            srv.stop()
        if errors:
            raise errors[0]
        require(not any(th.is_alive() for th in pair)
                and not srv._thread.is_alive(),
                "client threads done and the server shut down cleanly")
        require(all(g is not None and len(g) == NEW_TOKENS for g in got),
                f"each stream yielded exactly {NEW_TOKENS} tokens")
        # 2 x 16 tokens: serial decoding would take >= 32 engine steps
        require(pair_steps < 2 * NEW_TOKENS,
                f"requests 2 and 3 were in flight together "
                f"({pair_steps} engine steps for {2 * NEW_TOKENS} tokens)")
        for i, (g, w) in enumerate(zip(got, want)):
            require(g == w, f"request {i} tokens equal model.generate(): "
                            f"{g} vs {w}")
        log(f"  engine: {eng.tokens_generated} tokens in "
            f"{eng._steps_total} steps")
        require(eng.allocator.num_used == 0, "KV allocator num_used == 0")
        eng.allocator.check()
        log("  ok: KV allocator invariants hold")
    finally:
        jax.config.update("jax_default_matmul_precision", before)


def sharded_phase(sizes: Sizes, seed: int) -> None:
    """BERT-base over a dp2 x mp2 mesh (Megatron column/row split of the
    attention and MLP projections) against one device, same seed and
    batch."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.models import pretraining_loss
    from paddle_tpu.parallel import ShardedTrainStep, create_mesh
    from paddle_tpu.static import TrainStep

    def param_rule(name: str, v) -> P:
        if len(getattr(v, "shape", ())) == 2:
            if any(t in name for t in ("q_proj", "k_proj", "v_proj",
                                       "linear1")):
                return P(None, "mp")
            if any(t in name for t in ("out_proj", "linear2")):
                return P("mp", None)
        return P()

    mesh = create_mesh({"dp": 2, "mp": 2})
    log(f"  mesh {dict(mesh.shape)} over {mesh.devices.ravel().tolist()}")
    build, data = _bert_and_data(sizes, seed)

    model, opt = build()
    sharded = ShardedTrainStep(model, opt, pretraining_loss, mesh,
                               batch_spec=P("dp"), param_rule=param_rule)
    watched = "bert.encoder.layers.0.self_attn.q_proj.weight"
    w = sharded.state["params"][watched]
    shards = w.addressable_shards
    hidden = w.shape[0]
    log(f"  {watched} {w.shape}: " + ", ".join(
        f"{s.device.id}:{s.data.shape}" for s in shards))
    require(len({s.device for s in shards}) == 4,
            "an mp-sharded weight has shards on 4 distinct devices")
    require(all(s.data.shape == (hidden, hidden // 2) for s in shards),
            f"each shard is the column half ({hidden}, {hidden // 2})")
    sharded_losses = _run_steps(sharded, data, 2)
    require(len({s.device for s in
                 sharded.state["params"][watched].addressable_shards}) == 4,
            "the weight is still on 4 devices after the steps")

    model, opt = build()
    single_losses = _run_steps(TrainStep(model, opt, pretraining_loss),
                               data, 2)

    require(all(np.isfinite(sharded_losses + single_losses)),
            "all losses finite")
    rel = abs(sharded_losses[0] - single_losses[0]) / abs(single_losses[0])
    require(rel <= 2e-2, f"first-step losses agree: sharded "
                         f"{sharded_losses[0]:.4f} vs one device "
                         f"{single_losses[0]:.4f} (rel {rel:.2e})")
    jax.effects_barrier()


def run(args, result: dict) -> None:
    import jax

    import paddle_tpu as pt
    from paddle_tpu.sysconfig import (compile_cache_stats,
                                      enable_compile_cache)

    enable_compile_cache()
    # Ask the package for its persistent-cache mode as well, naming the
    # directory already in use: the train step then keeps host callbacks
    # out of its program (XLA persists no executable that holds one; the
    # probes ride the step's outputs instead) and every executable is
    # kept, so a later run on a machine that keeps the cache compiles
    # next to nothing.
    pt.set_flags(
        {"compile_cache_dir": jax.config.jax_compilation_cache_dir})
    devices = jax.devices()
    result["device"] = {"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices)}
    log(f"jax {jax.__version__} devices: {result['device']}")
    log(f"compile cache dir: {jax.config.jax_compilation_cache_dir}")
    if args.cpu_rehearsal:
        if devices[0].platform != "cpu":
            raise RuntimeError("--cpu-rehearsal is for the CPU backend")
    elif devices[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX found {devices[0].platform!r} devices (the "
            "tiny CPU walk-through takes --cpu-rehearsal)")
    if len(devices) < args.chips:
        raise RuntimeError(
            f"--chips {args.chips} needs {args.chips} devices, JAX "
            f"found {len(devices)}")
    sizes = REHEARSAL if args.cpu_rehearsal else Sizes()

    phases = [("sharded", sharded_phase)] if args.chips == 4 else \
        [("train", train_phase), ("serve", serve_phase)]
    for name, phase in phases:
        log(f"phase {name}: start")
        t0 = time.perf_counter()
        phase(sizes, args.seed)
        log(f"phase {name}: passed in {time.perf_counter() - t0:.1f}s "
            f"(compile cache so far: {compile_cache_stats()})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path and its one-device "
                         "comparison, on a dp2 x mp2 mesh")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend: control flow "
                         "only, never a chip result")
    args = ap.parse_args(argv)
    result = {"ok": False, "device": None}
    t0 = time.perf_counter()
    try:
        run(args, result)
        result["ok"] = True
    except Exception:  # noqa: BLE001 — the run is over; report and fail
        traceback.print_exc()
    finally:
        log(f"total {time.perf_counter() - t0:.1f}s")
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
