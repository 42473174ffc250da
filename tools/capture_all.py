"""One-shot measurement campaign for when the accelerator is up.

Runs, in order of value per chip-minute (each stage independently
time-capped so a failure mid-campaign still leaves artifacts):
  1. verification       -> VERIFY_TPU.json  (compiled kernels + parity)
  2. pinned BERT        -> CAPTURE_bert_fused_b32.json   (best-guess cfg)
  3. pinned ResNet      -> CAPTURE_resnet_nhwc_b128.json (best-guess cfg)
  4. comparison configs -> per-leaf BERT, NCHW ResNet
  5. flash sweep        -> CAPTURE_flash.json

Pinned stages (PT_BENCH_* env) keep each subprocess to ONE compile+time
cycle, so a failure mid-campaign costs one bounded stage instead of
a 50-minute autotune (round-3 lesson: the unpinned bert stage timed out
at 3000s and, because partial output was discarded, left nothing).
Timeouts now preserve the stage's partial stdout/stderr — the per-config
ms/step lines bench.py logs as it goes survive a mid-stage hang.

Usage: python tools/capture_all.py [stage ...]   (default: DEFAULT_PLAN)
Each stage is a subprocess of bench.py so a wedged PJRT init or OOM
kills only that stage; stdout JSON lines are parsed and collected into
CAPTURE_SUMMARY.json.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# name -> (argv after bench.py, extra env, budget seconds[, script])
# script (default bench.py) lets a stage run a different tool — the
# profiler stages drive tools/profile_step.py.
_SKIP = {"PT_BENCH_SKIP_VALIDATE": "1"}  # verify stage covers kernels
_SPL1 = {"PT_BENCH_STEPS_PER_LOOP": "1"}  # measured ~1.0x; skip re-timing


def _bert(batch, fused, qkv):
    # The flash train gate is pinned OFF (raised above seq 512) so these
    # stages stay the XLA-attention baseline their historical artifacts
    # were captured as — the flag's default moved to 512 after the
    # in-model bert_b8_flash512 win, and an unpinned re-capture would
    # silently change what every A/B pair compares against. Flash-on
    # stages pin 512 explicitly.
    return ([], {**_SKIP, **_SPL1, "PT_BENCH_BERT_BATCH": str(batch),
                 "PT_BENCH_FUSED": fused,
                 "FLAGS_flash_attention_min_seq_train": "1024",
                 "FLAGS_fused_qkv_projection": qkv}, 900)


# Historical-default pins for the legacy stages below: their artifacts
# were captured with XLA attention (train gate above seq 512) and
# two-pass BN, and both defaults have since flipped — re-captures must
# not silently change configuration under the same artifact name.
_HIST = {"FLAGS_flash_attention_min_seq_train": "1024",
         "FLAGS_batch_norm_single_pass": "0"}

STAGES = {
    "verify": (["verify"], {}, 1200),
    "bert_fused_b32": ([], {**_SKIP, **_HIST,
                            "PT_BENCH_BERT_BATCH": "32",
                            "PT_BENCH_FUSED": "1"}, 1800),
    "resnet_nhwc_b128": (["resnet50"],
                         {**_SKIP, **_HIST,
                          "PT_BENCH_RESNET_BATCH": "128",
                          "PT_BENCH_LAYOUT": "NHWC",
                          "PT_BENCH_FUSED": "1"}, 1800),
    "bert_perleaf_b32": ([], {**_SKIP, **_HIST,
                              "PT_BENCH_BERT_BATCH": "32",
                              "PT_BENCH_FUSED": "0"}, 1200),
    "resnet_nchw_b128": (["resnet50"],
                         {**_SKIP, **_HIST,
                          "PT_BENCH_RESNET_BATCH": "128",
                          "PT_BENCH_LAYOUT": "NCHW",
                          "PT_BENCH_FUSED": "1"}, 1200),
    "flash": (["flash"], _SKIP, 1800),
    "flash_train": (["flash_train"], _SKIP, 1800),
    # LLM serving decode path: paged-KV continuous batching vs dense
    # sequential generation (tokens/s + TTFT p50/p99); small model,
    # bounded token count — cheap enough for every campaign
    "llm_decode": (["llm_decode"], _SKIP, 600),
    # serving speed tier A/Bs: copy-on-write shared-prefix KV reuse
    # (admitted-streams x + kv_blocks_used vs unshared) and chunked
    # prefill (p99 inter-token with long-prompt arrivals, on vs off).
    # Both flags are [assumed off] until these land on-chip numbers.
    "llm_prefix_reuse": (["llm_prefix_reuse"], _SKIP, 600),
    "llm_mixed_prefill": (["llm_mixed_prefill"], _SKIP, 600),
    # multi-tenant isolation: premium TTFT p99 under a weight-1 bulk
    # flood with fair share on — the loaded/unloaded ratio the
    # llm_tenant_flood chaos drill gates at 1.25x
    "llm_tenant_flood": (["llm_tenant_flood"], _SKIP, 600),
    # speculative decoding (self-draft sanity config): accepted
    # tokens/s vs non-speculative, accept-rate + verify-latency
    # partials. FLAGS_speculative_k is [assumed off] until this lands
    # an on-chip number with a real (cheap) draft.
    "llm_spec_decode": (["llm_spec_decode"], _SKIP, 600),
    # tile-size sweep for the flash kernel (only worth chip time if the
    # default-tile flash_train stage loses to XLA)
    "flash_train_t128": (["flash_train"],
                         {**_SKIP, "FLAGS_flash_block_q": "128",
                          "FLAGS_flash_block_k": "128"}, 900),
    "flash_train_t512": (["flash_train"],
                         {**_SKIP, "FLAGS_flash_block_q": "512",
                          "FLAGS_flash_block_k": "512"}, 900),
    # round-3 regression hunt: fused_state measured -26% (b32), so the
    # remaining suspects for the 121.8k -> 97.1k/b32 gap are fused QKV
    # and per-chip batch. b8_perleaf_noqkv IS the round-2 config.
    "bert_b8_perleaf_noqkv": _bert(8, "0", "0"),
    "bert_b8_perleaf_qkv": _bert(8, "0", "1"),
    "bert_b16_perleaf_noqkv": _bert(16, "0", "0"),
    "bert_b32_perleaf_noqkv": _bert(32, "0", "0"),
    "resnet_nhwc_b128_perleaf": (
        ["resnet50"], {**_SKIP, **_SPL1, "FLAGS_batch_norm_single_pass": "0",
                       "PT_BENCH_RESNET_BATCH": "128",
                       "PT_BENCH_LAYOUT": "NHWC",
                       "PT_BENCH_FUSED": "0"}, 900),
    # clean fused-state A/B partner for _perleaf (same _SPL1 pinning —
    # the older resnet_nhwc_b128 stage autotunes steps-per-loop and is
    # not comparable like-for-like)
    "resnet_nhwc_b128_fused": (
        ["resnet50"], {**_SKIP, **_SPL1, "FLAGS_batch_norm_single_pass": "0",
                       "PT_BENCH_RESNET_BATCH": "128",
                       "PT_BENCH_LAYOUT": "NHWC",
                       "PT_BENCH_FUSED": "1"}, 900),
    "resnet_nhwc_b256_perleaf": (
        ["resnet50"], {**_SKIP, **_SPL1, "FLAGS_batch_norm_single_pass": "0",
                       "PT_BENCH_RESNET_BATCH": "256",
                       "PT_BENCH_LAYOUT": "NHWC",
                       "PT_BENCH_FUSED": "0"}, 900),
    # clean NCHW partner for resnet_nhwc_b128_perleaf (same _SPL1
    # pinning). The round-3 layout pin came from the unpinned pair, and
    # the dead NCHW stage's partial 8-step timing (75.76 ms vs NHWC
    # 77.42 in the same window) contradicts it — settle the layout with
    # a like-for-like pair (VERDICT r4 task 6).
    "resnet_nchw_b128_perleaf": (
        ["resnet50"], {**_SKIP, **_SPL1, "FLAGS_batch_norm_single_pass": "0",
                       "PT_BENCH_RESNET_BATCH": "128",
                       "PT_BENCH_LAYOUT": "NCHW",
                       "PT_BENCH_FUSED": "0"}, 900),
    "resnet_nhwc_b128_s2d": (
        ["resnet50"], {**_SKIP, **_SPL1, "FLAGS_batch_norm_single_pass": "0",
                       "PT_BENCH_RESNET_BATCH": "128",
                       "PT_BENCH_LAYOUT": "NHWC", "PT_BENCH_FUSED": "0",
                       "FLAGS_resnet_space_to_depth_stem": "1"}, 900),
    # BN-stat single-pass A/B partner for resnet_nhwc_b128_perleaf
    # (same pinning; only the flag differs)
    "resnet_bn1pass": (
        ["resnet50"], {**_SKIP, **_SPL1, "PT_BENCH_RESNET_BATCH": "128",
                       "PT_BENCH_LAYOUT": "NHWC", "PT_BENCH_FUSED": "0",
                       "FLAGS_batch_norm_single_pass": "1"}, 900),
    # dispatch-gap reclaim: the bn1pass profile shows 48.2 ms device
    # vs 52.1 ms wall — the SPL1 pinning of the lever ladder never
    # amortized the ~4 ms dispatch gap; a K=8 lax.scan dispatches once
    # per 8 optimizer steps and should reclaim most of it (measured:
    # 2582.6 vs 2455.9 img/s, +5.2%)
    "resnet_bn1pass_spl8": (
        ["resnet50"], {**_SKIP, "PT_BENCH_RESNET_BATCH": "128",
                       "PT_BENCH_LAYOUT": "NHWC", "PT_BENCH_FUSED": "0",
                       "FLAGS_batch_norm_single_pass": "1",
                       "PT_BENCH_STEPS_PER_LOOP": "8"}, 900),
    # flash batch ladder: under XLA attention the ladder peaked at b8
    # (the backward's [B,H,T,T] fp32 probs scale with batch); flash
    # removes that wall and the unpinned r5 sweep found b16 at 139.7k
    # (0.5856) — measure the ladder's new top. Default flags (flash
    # 512, BTHD, Pallas LN) + auto spl retiming.
    "bert_b16_flash": ([], {**_SKIP, "PT_BENCH_BERT_BATCH": "16",
                            "PT_BENCH_FUSED": "0"}, 900),
    "bert_b32_flash": ([], {**_SKIP, "PT_BENCH_BERT_BATCH": "32",
                            "PT_BENCH_FUSED": "0"}, 900),
    "bert_b64_flash": ([], {**_SKIP, "PT_BENCH_BERT_BATCH": "64",
                            "PT_BENCH_FUSED": "0"}, 900),
    "bert_b16_flash_maskedlm": ([], {**_SKIP,
                                     "PT_BENCH_BERT_BATCH": "16",
                                     "PT_BENCH_FUSED": "0",
                                     "PT_BENCH_MASKED_LM": "1"}, 900),
    # ISSUE 8 loss-region A/B at the b16 headline: fused MLM-head+xent
    # kernel (never materializes the [B,T,V] logits) vs bert_b16_flash,
    # then the fused-Adam default candidate stacked on top of it
    "bert_b16_fusedloss": ([], {**_SKIP, "PT_BENCH_BERT_BATCH": "16",
                                "PT_BENCH_FUSED": "0",
                                "FLAGS_fused_softmax_xent": "1"}, 900),
    "bert_b16_fusedloss_fusedadam": ([], {**_SKIP,
                                          "PT_BENCH_BERT_BATCH": "16",
                                          "PT_BENCH_FUSED": "0",
                                          "FLAGS_fused_softmax_xent":
                                          "1",
                                          "FLAGS_fused_adam": "1"},
                                     900),
    # ladder midpoint: b16 139.3k > b32 136.1k — the peak may sit
    # between
    "bert_b24_flash": ([], {**_SKIP, "PT_BENCH_BERT_BATCH": "24",
                            "PT_BENCH_FUSED": "0"}, 900),
    # where do the remaining ~53% of peak go at the new headline config
    "profile_bert_b16_flash": (["bert", "16"], {}, 900,
                               "tools/profile_step.py"),
    # steps-per-loop ladder top: does K=32 add anything over K=8's
    # +1.4% at the BERT headline config
    "bert_b8_flash512_spl32": ([], {**_SKIP,
                                    "PT_BENCH_BERT_BATCH": "8",
                                    "PT_BENCH_FUSED": "0",
                                    "FLAGS_fused_qkv_projection": "0",
                                    "FLAGS_flash_attention_min_seq_train":
                                    "512",
                                    "FLAGS_attention_bthd_layout": "0",
                                    "PT_BENCH_STEPS_PER_LOOP": "32"},
                               900),
    # block remat on the HBM-bound step: recompute FLOPs ride idle MXU
    # while intermediate activations skip the HBM round-trip — A/B
    # partner is resnet_bn1pass_spl8 (identical env, only the flag)
    "resnet_remat": (
        ["resnet50"], {**_SKIP, "PT_BENCH_RESNET_BATCH": "128",
                       "PT_BENCH_LAYOUT": "NHWC", "PT_BENCH_FUSED": "0",
                       "FLAGS_batch_norm_single_pass": "1",
                       "FLAGS_resnet_block_remat": "1",
                       "PT_BENCH_STEPS_PER_LOOP": "8"}, 900),
    # stack the two stem/stat levers on top of the bn1pass win (+8.5%
    # measured): s2d alone was +0.8% (noise) — see if it adds anything
    # once BN stats no longer dominate the loop fusions
    "resnet_bn1pass_s2d": (
        ["resnet50"], {**_SKIP, **_SPL1, "PT_BENCH_RESNET_BATCH": "128",
                       "PT_BENCH_LAYOUT": "NHWC", "PT_BENCH_FUSED": "0",
                       "FLAGS_batch_norm_single_pass": "1",
                       "FLAGS_resnet_space_to_depth_stem": "1"}, 900),
    # post-bn1pass profile: where do the reclaimed ms go / what is the
    # new category budget (conv share should rise toward the HBM bound)
    "profile_resnet_bn1pass": (["resnet", "128"],
                               {"PT_PROF_LAYOUT": "NHWC",
                                "FLAGS_batch_norm_single_pass": "1"},
                               900, "tools/profile_step.py"),
    # low end of the BERT batch ladder (r5 measured b8 121.1k > b16
    # 106.4k > b32 100.6k — monotonic toward small batch, so probe b4)
    "bert_b4_perleaf_noqkv": _bert(4, "0", "0"),
    # in-model flash routing at BERT's own seq 512: the standalone r5
    # sweep says flash wins at every seq incl. 512 (8.68x), but both
    # standalone numbers at T512 are dispatch-overhead-dominated — only
    # an in-model step A/B against bert_b8_perleaf_noqkv settles the
    # train gate
    "bert_b8_flash512": ([], {**_bert(8, "0", "0")[1],
                              "FLAGS_flash_attention_min_seq_train":
                              "512",
                              "FLAGS_attention_bthd_layout": "0"}, 900),
    # BTHD-native flash layout (zero physical head transposes; the
    # kernel gathers heads in its block DMA): the layout flag is the
    # ONLY difference vs bert_b8_flash512, so the A/B stays pinnable
    # on any code version
    "bert_b8_flash_bthd": ([], {**_bert(8, "0", "0")[1],
                                "FLAGS_flash_attention_min_seq_train":
                                "512",
                                "FLAGS_attention_bthd_layout": "1"},
                           900),
    # dispatch-copy amortization at the NEW best config (flash512):
    # the only prior steps-per-loop A/B (0.95x) was at fused_b32 —
    # per-leaf b8 has far more dispatch buffers, so re-measure there
    "bert_b8_flash512_spl8": ([], {**_SKIP,
                                   "PT_BENCH_BERT_BATCH": "8",
                                   "PT_BENCH_FUSED": "0",
                                   "FLAGS_fused_qkv_projection": "0",
                                   "FLAGS_flash_attention_min_seq_train":
                                   "512",
                                   "FLAGS_attention_bthd_layout": "0",
                                   "PT_BENCH_STEPS_PER_LOOP": "8"}, 900),
    # flash512 at the b4 ladder point (only worth running if plain b4
    # lands within noise of b8)
    "bert_b4_flash512": ([], {**_bert(4, "0", "0")[1],
                              "FLAGS_flash_attention_min_seq_train":
                              "512"}, 900),
    # Pallas-vs-XLA LayerNorm at the best config (use_pallas_layer_norm
    # has been default-on [assumed] since round 2 with zero chip
    # evidence; the r5 HLO metadata probe shows the per-layer backward
    # pallas_call fusions at ~0.2 ms each). A/B partner:
    # bert_b8_flash512_spl8 — identical env, only the LN route differs.
    "bert_b8_spl8_xlaln": ([], {**_SKIP,
                                "PT_BENCH_BERT_BATCH": "8",
                                "PT_BENCH_FUSED": "0",
                                "FLAGS_fused_qkv_projection": "0",
                                "FLAGS_flash_attention_min_seq_train":
                                "512",
                                "FLAGS_attention_bthd_layout": "0",
                                "FLAGS_use_pallas_layer_norm": "0",
                                "PT_BENCH_STEPS_PER_LOOP": "8"}, 900),
    "bert_b32_remat": ([], {**_SKIP, **_SPL1,
                            "FLAGS_flash_attention_min_seq_train": "1024",
                            "PT_BENCH_BERT_BATCH": "32",
                            "PT_BENCH_FUSED": "0",
                            "FLAGS_fused_qkv_projection": "0",
                            "FLAGS_transformer_remat": "1"}, 900),
    "bert_b64_remat": ([], {**_SKIP, **_SPL1,
                            "FLAGS_flash_attention_min_seq_train": "1024",
                            "PT_BENCH_BERT_BATCH": "64",
                            "PT_BENCH_FUSED": "0",
                            "FLAGS_fused_qkv_projection": "0",
                            "FLAGS_transformer_remat": "1"}, 900),
    "bert_b8_bf16mv": ([], {**_SKIP, **_SPL1,
                            "FLAGS_flash_attention_min_seq_train": "1024",
                            "PT_BENCH_BERT_BATCH": "8",
                            "PT_BENCH_FUSED": "0",
                            "FLAGS_fused_qkv_projection": "0",
                            "FLAGS_optimizer_moment_dtype": "bfloat16"},
                       900),
    # masked-LM head restriction (reference-parity mask_pos gather):
    # A/B against bert_b{32,8}_perleaf_noqkv — SAME baseline env via
    # _bert so the comparison stays single-variable
    "bert_b32_maskedlm": ([], {**_bert(32, "0", "0")[1],
                               "PT_BENCH_MASKED_LM": "1"}, 900),
    "bert_b8_maskedlm": ([], {**_bert(8, "0", "0")[1],
                              "PT_BENCH_MASKED_LM": "1"}, 900),
    # framework-free raw-JAX RN50 comparator: same chip, no paddle_tpu
    # on the model path — separates "our overhead" from "XLA's conv
    # ceiling" for the stuck ~2260 img/s
    "rn50_floor": (["128"], {}, 900, "tools/rn50_floor.py"),
    # Profile stages pin the config they historically profiled (same
    # no-silent-config-change rule as the bench stages): profile_bert
    # is the XLA-attention transpose-layout baseline whose rollup
    # steered rounds 2-5; profile_bert_flash is the current default
    # config (flash512 + BTHD). profile_resnet is the two-pass-BN
    # baseline; profile_resnet_bn1pass the measured winner.
    "profile_bert": (["bert", "8"],
                     {"FLAGS_flash_attention_min_seq_train": "1024",
                      "FLAGS_attention_bthd_layout": "0"},
                     900, "tools/profile_step.py"),
    "profile_bert_flash": (["bert", "8"], {}, 900,
                           "tools/profile_step.py"),
    "profile_bert_b32": (["bert", "32"],
                         {"FLAGS_flash_attention_min_seq_train": "1024",
                          "FLAGS_attention_bthd_layout": "0"}, 900,
                         "tools/profile_step.py"),
    "profile_resnet": (["resnet", "128"],
                       {"PT_PROF_LAYOUT": "NHWC",
                        "FLAGS_batch_norm_single_pass": "0"}, 900,
                       "tools/profile_step.py"),
    # unpinned autotunes (the driver's default bench path)
    "bert": ([], {}, 3000),
    "resnet": (["resnet50"], {}, 3000),
}
DEFAULT_PLAN = ["verify", "bert_fused_b32", "resnet_nhwc_b128",
                "bert_perleaf_b32", "resnet_nchw_b128", "flash"]
DIAG_PLAN = ["bert_b8_perleaf_noqkv", "bert_b8_perleaf_qkv",
             "bert_b16_perleaf_noqkv", "bert_b32_perleaf_noqkv",
             "resnet_nhwc_b128_perleaf", "flash", "flash_train",
             "profile_bert", "profile_bert_b32", "profile_resnet",
             "resnet_nhwc_b256_perleaf", "resnet_nhwc_b128_s2d",
             "bert_b32_remat", "bert_b64_remat", "bert_b8_bf16mv"]
# Round-4 triage (VERDICT r3 task 5): ordered by information value per
# chip-minute so the first ~15 min of chip time settles the big
# questions — b8-vs-b32 (the 121.8k discrepancy), the ResNet levers
# (largest perf hole), and the flash train crossover — before the tail.
R4_PLAN = ["verify",                      # refresh stamped artifact
           "bert_b8_perleaf_noqkv",       # the round-2 121.8k config
           "bert_b8_perleaf_qkv",
           "resnet_nhwc_b128_perleaf",
           "resnet_nhwc_b128_s2d",
           "bert_b32_perleaf_noqkv",
           "bert_b32_maskedlm",           # ~20% FLOP cut if it holds
           "flash_train",
           "bert_b8_bf16mv",
           "bert_b8_maskedlm",
           "bert_b16_perleaf_noqkv",
           "resnet_nhwc_b128_fused",
           "resnet_nhwc_b256_perleaf",
           "bert_b32_remat",
           "bert_b64_remat",
           "flash",
           "flash_train_t128", "flash_train_t512",
           "profile_bert", "profile_bert_b32", "profile_resnet"]
# Round-5 triage (VERDICT r4 "Next round"): ResNet is the project's
# largest hole (0.14 vs ≥0.5 bar, zero profile evidence) — so the
# FIRST chip-minutes go to the ResNet rollup, then the lever ladder
# with the clean NCHW pair (task 6), then a stamped verify refresh
# (the r3 VERIFY_TPU.json predates device/kernel-hash stamping, so the
# driver's bench would otherwise revalidate), then the BERT b8-vs-b32 +
# masked-LM matrix (task 3), flash prove-or-retire (task 4), and the
# tail. The final unpinned bert/resnet stages pre-warm the driver's
# exact flows.
R5_PLAN = ["profile_resnet",
           "resnet_nhwc_b128_perleaf",
           "resnet_nchw_b128_perleaf",
           "resnet_nhwc_b128_s2d",
           "resnet_nhwc_b256_perleaf",
           "verify",
           "bert_b8_perleaf_noqkv",
           "bert_b32_perleaf_noqkv",
           "bert_b32_maskedlm",
           "bert_b8_maskedlm",
           "bert_b8_bf16mv",
           "flash_train",
           "bert_b8_perleaf_qkv",
           "bert_b16_perleaf_noqkv",
           "resnet_nhwc_b128_fused",
           "bert_b32_remat",
           "bert_b64_remat",
           "flash",
           "flash_train_t128", "flash_train_t512",
           "profile_bert_b32", "profile_bert",
           "bert", "resnet"]


def log(msg: str) -> None:
    print(f"[capture] {msg}", file=sys.stderr, flush=True)


def _text(v) -> str:
    if v is None:
        return ""
    if isinstance(v, bytes):
        return v.decode("utf-8", "replace")
    return v


def run_stage(name: str) -> dict:
    spec = STAGES[name]
    args, env, budget = spec[:3]
    script = spec[3] if len(spec) > 3 else "bench.py"
    t0 = time.time()
    log(f"stage {name}: starting (budget {budget}s)")
    stdout, stderr, rc, timed_out = "", "", None, False
    try:
        # tell bench.py its real deadline (minus a margin for start-up
        # and import) so its soft-budget bails fire BEFORE the hard kill —
        # a stage that overruns still emits its best-so-far JSON line
        stage_env = {"PT_BENCH_BUDGET_S": str(max(60, budget - 120)),
                     **env}
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, script), *args],
            capture_output=True, text=True, timeout=budget, cwd=ROOT,
            env={**os.environ, **stage_env})
        stdout, stderr, rc = r.stdout, r.stderr, r.returncode
    except subprocess.TimeoutExpired as e:
        # partial output is the whole point: bench.py logs each
        # config's ms/step to stderr as it measures
        stdout, stderr = _text(e.stdout), _text(e.stderr)
        timed_out = True
        log(f"stage {name}: TIMED OUT after {budget}s "
            f"(keeping partial output)")
    parsed = None
    for line in (stdout or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
            except json.JSONDecodeError:
                continue
    # a stage that printed its result JSON and then wedged (timeout) or
    # crashed (negative rc = signal) in PJRT teardown still produced a
    # usable measurement — but a DELIBERATE failure exit (verify prints
    # value 0.0 then sys.exit(1)) must stay not-ok so the watcher
    # retries it. Profiler stages emit a text rollup: rc==0 is their ok.
    stage_ok = (parsed is not None
                and (rc == 0 or timed_out
                     or (rc is not None and rc < 0))) or \
        (script != "bench.py" and rc == 0)
    out = {"stage": name,
           "ok": stage_ok,
           "rc": rc, "timed_out": timed_out, "parsed": parsed,
           "elapsed_s": round(time.time() - t0, 1),
           "env": env,
           # 90 lines keeps a full profiler rollup (categories + top-30
           # table) — 45 cut the category header off every profile
           # artifact this round
           "stdout_tail": (stdout or "").splitlines()[-90:],
           "stderr_tail": (stderr or "").splitlines()[-40:]}
    result_path = os.path.join(ROOT, f"CAPTURE_{name}.json")
    with open(result_path, "w") as f:
        json.dump(out, f, indent=1)
    log(f"stage {name}: rc={rc} parsed={parsed} "
        f"({out['elapsed_s']}s) -> {result_path}")
    return out


def resolve_plan(names: list) -> list:
    """Expand plan aliases ('default', 'diag') into stage lists."""
    out: list = []
    for n in names:
        if n == "default":
            out.extend(DEFAULT_PLAN)
        elif n == "diag":
            out.extend(DIAG_PLAN)
        elif n == "r4":
            out.extend(R4_PLAN)
        elif n == "r5":
            out.extend(R5_PLAN)
        else:
            out.append(n)
    return out


def main() -> None:
    wanted = resolve_plan(sys.argv[1:] or list(DEFAULT_PLAN))
    unknown = [w for w in wanted if w not in STAGES]
    if unknown:
        raise SystemExit(f"unknown stages {unknown}; pick from "
                         f"{sorted(STAGES)}")
    results = [run_stage(name) for name in wanted]
    # merge into any existing summary so a retry campaign over the
    # remaining stages doesn't erase earlier stages' records
    summary_path = os.path.join(ROOT, "CAPTURE_SUMMARY.json")
    by_stage: dict = {}
    try:
        with open(summary_path) as f:
            for r in json.load(f).get("results", []):
                by_stage[r.get("stage")] = r
    except (OSError, json.JSONDecodeError):
        pass
    for r in results:
        by_stage[r["stage"]] = r
    summary = {"when": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
               "results": list(by_stage.values())}
    with open(summary_path, "w") as f:
        json.dump(summary, f, indent=1)
    log(f"campaign done: {[(r['stage'], r['ok']) for r in results]}")
    sys.exit(0 if all(r["ok"] for r in results) else 1)


if __name__ == "__main__":
    main()
