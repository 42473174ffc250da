"""Benchmark driver.

Default: BERT-base pretraining tokens/sec on one TPU chip — prints ONE
JSON line {"metric", "value", "unit", "vs_baseline"}.
``python bench.py resnet50`` instead benches ResNet-50 images/sec
(BASELINE configs 2/4).

vs_baseline = achieved effective TFLOPs / target, where target = 0.80 x
v5e bf16 peak (197 TFLOPs) per BASELINE.json's ">=80% of A100 MFU" north
star (A100 bf16 peak 312 and v5e 197 make per-chip MFU the comparable
quantity). BERT effective FLOPs use the standard 6 * params * tokens
estimate; ResNet uses the analytic per-image conv+fc FLOP count.

Before timing, when on a real TPU, the standalone verification module
(paddle_tpu.verify — its own driver entry via __graft_entry__.verify and
its own artifact, so a timing outage does not lose the correctness run)
validates the Pallas kernels in compiled mode; `python bench.py verify`
runs just that stage.
"""

from __future__ import annotations

import json
import os
import sys
import time


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_kind() -> str:
    import jax
    try:
        return str(jax.devices()[0].device_kind)
    except Exception:  # noqa: BLE001
        return "unknown"


def emit(result: dict) -> None:
    """Print the one-line JSON result, stamped with the chip identity so
    capture artifacts are only ever auto-applied on the same hardware."""
    print(json.dumps(dict(result, device=device_kind())), flush=True)


def _on_accel_backend() -> bool:
    """One predicate for every 'is this an accelerator run' decision in
    this file (routing AND artifact placement must agree) — delegates
    to the package's canonical predicate in core.place."""
    from paddle_tpu.core.place import accelerator_available
    return accelerator_available()


def emit_partial(result: dict) -> None:
    """Best-so-far result, printed IMMEDIATELY after each timed
    candidate. Three consecutive rounds produced a null driver artifact
    because the one JSON line only appeared after the full
    select->rebuild->time pipeline survived; a mid-run failure or
    driver timeout lost everything. Now every measured number is (a) on
    stdout the moment it exists — consumers keep the LAST JSON line, so
    a later better/final emit supersedes it — and (b) mirrored
    atomically to BENCH_partial.json so even a hard kill leaves the
    number on disk.

    Only accelerator measurements may occupy BENCH_partial.json: a CPU
    invocation's resident best-so-far is a meaningless number that
    invites a wrong read in a hurried window, so non-accelerator
    results mirror to BENCH_partial_cpu.json instead (the stdout line
    is unaffected either way)."""
    res = dict(result, device=device_kind(), partial=True,
               when=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    print(json.dumps(res), flush=True)
    path = _PARTIAL_PATH if _on_accel_backend() else _PARTIAL_CPU_PATH
    tmp = path + ".tmp"
    try:
        # The file means BEST-so-far PER METRIC, across processes:
        # capture stages each run their own bench, so flat last-writer-
        # wins left a mid-stage number from whichever stage ran last
        # resident over a better earlier one — and a single slot let
        # the other bench's stage clobber it anyway. Schema: one entry
        # per metric. An entry only suppresses a new write while it is
        # (a) the same device, (b) judged >=, and (c) RECENT — older
        # than _PARTIAL_BEST_WINDOW_S it is replaced regardless, so a
        # noisy or pre-regression high from an old session cannot
        # shadow today's honest measurement forever.
        entries = {}
        try:
            with open(path) as f:
                prev = json.load(f)
            # legacy flat shape: one result dict -> one entry
            entries = prev if isinstance(prev, dict) and \
                "metric" not in prev else {prev["metric"]: prev}
        except (OSError, json.JSONDecodeError, ValueError, KeyError,
                TypeError):
            pass
        old = entries.get(res["metric"])
        # suppress only when the resident entry carries a NUMERIC
        # vs_baseline that really is >= the new one: an old entry with
        # the field missing/None used to read as 0 and shadow every
        # honest fresh re-measurement on the same device for the whole
        # window
        if isinstance(old, dict) \
                and old.get("device") == res.get("device") \
                and isinstance(old.get("vs_baseline"), (int, float)) \
                and old.get("vs_baseline") \
                >= (res.get("vs_baseline") or 0):
            import calendar
            try:
                # "when" is stamped with gmtime: parse it back as UTC
                # (mktime would shift the window by the host's offset)
                age = time.time() - calendar.timegm(time.strptime(
                    old.get("when", ""), "%Y-%m-%dT%H:%M:%SZ"))
            except (ValueError, TypeError):
                age = float("inf")
            if age < _PARTIAL_BEST_WINDOW_S:
                return
        entries[res["metric"]] = res
        with open(tmp, "w") as f:
            json.dump(entries, f)
        os.replace(tmp, path)
    except OSError:
        pass  # the stdout line is the primary channel


_PARTIAL_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_partial.json")
_PARTIAL_CPU_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_partial_cpu.json")
# how long a resident best may suppress a worse re-measurement of the
# same metric+device (one capture-session window)
_PARTIAL_BEST_WINDOW_S = 6 * 3600.0

_deadline = [None]


def budget_left() -> float:
    """Seconds before the soft deadline (PT_BENCH_BUDGET_S, default
    1200). Sweeps check this to skip optional refinement stages — the
    mandatory first measurement always runs regardless."""
    if _deadline[0] is None:
        return float("inf")
    return _deadline[0] - time.perf_counter()


def warmup_and_time(step_once, iters: int, settle_s: float = 1.0):
    """Warm up until compiles settle (donated-state layouts reach their
    fixpoint after a few calls), then time ``iters`` calls. Syncs by
    fetching the loss value — block_until_ready is not a reliable sync
    over remote-dispatch backends. Returns seconds per iteration.

    Requires TWO consecutive sub-second calls before timing: the
    donated-state layout fixpoint can trigger a recompile on call 2-3,
    and a single fast call would let that recompile land inside the
    timed region and corrupt the measurement. ``settle_s`` is the
    "settled" threshold — callers timing K-steps-per-dispatch scale it
    by K so a steady multi-step dispatch still exits early."""
    fast = 0
    for i in range(8):
        t0 = time.perf_counter()
        float(step_once()["loss"])
        dt = time.perf_counter() - t0
        log(f"warmup {i}: {dt:.2f}s")
        fast = fast + 1 if dt < settle_s else 0
        if fast >= 2:
            break
    log(f"timing {iters} steps...")
    t0 = time.perf_counter()
    for _ in range(iters):
        m = step_once()
    float(m["loss"])
    return (time.perf_counter() - t0) / iters


_capture_cache: dict = {}
_partial_logged: set = set()


def capture_value(stage: str, any_device: bool = False,
                  field: str = "value"):
    """Measured ``field`` from a prior capture campaign artifact
    (CAPTURE_<stage>.json), or None. Lets the bench apply measured
    winners — candidate ordering and flag choices — automatically when
    the diag campaign has already run on this chip; every choice made
    from an artifact is logged with its evidence. Shared with
    tools/recommend.py (one reader for the artifact contract).

    ``field="vs_baseline"`` compares the JUDGED number instead of raw
    throughput — the two diverge when configs do different work per
    token (masked-LM's honest FLOP accounting)."""
    key = (stage, any_device, field)
    if key in _capture_cache:
        return _capture_cache[key]
    val = None
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(
                __file__)), f"CAPTURE_{stage}.json")) as f:
            d = json.load(f)
        if d.get("ok") and d.get("parsed"):
            # only trust artifacts measured on THIS hardware: the files
            # are git-tracked, so a clone on a different chip would
            # otherwise inherit v5e-tuned pins
            if any_device or d["parsed"].get("device") == device_kind():
                val = d["parsed"].get(field)
                if val is not None and d["parsed"].get("partial") \
                        and field in ("value", "vs_baseline") \
                        and stage not in _partial_logged:
                    # provenance: a timed-out stage's preserved
                    # best-so-far (e.g. 8-iter selection timing) is
                    # usable but not final-30-iter quality — every pin
                    # decided from this stage inherits that caveat.
                    # Once per stage (not per cache key): recommend.py
                    # reads several fields of the same artifact
                    _partial_logged.add(stage)
                    log(f"capture {stage}: {field}={val} is from a "
                        f"PARTIAL artifact (timed-out stage's "
                        f"best-so-far, not a final measurement)")
    except (OSError, json.JSONDecodeError):
        pass
    _capture_cache[key] = val
    return val


def bert_batch_stages(b: int) -> list:
    """Flash-era capture stages whose artifacts can carry batch ``b``'s
    judged number (b8's flash-era stages predate the bert_b*_flash
    naming, so its historical names join the lookup). One list so
    bench's sweep ordering and tools/recommend.py report the SAME
    evidence set."""
    names = [f"bert_b{b}_flash", f"bert_b{b}_flash_maskedlm"]
    if b == 8:
        names += ["bert_b8_flash512_spl8", "bert_b8_flash512_spl32",
                  "bert_b8_flash_bthd", "bert_b8_flash512"]
    return names


def bert_batch_judged(b: int, any_device: bool = False):
    """Best judged (vs_baseline) capture for per-chip batch ``b``.
    Flash-config artifacts (current defaults) outrank the
    XLA-attention-era ones when both exist — the ladder reshaped under
    flash (b16 above b8, r5)."""
    vals = [capture_value(n, any_device=any_device, field="vs_baseline")
            for n in bert_batch_stages(b)]
    vals = [v for v in vals if v is not None]
    if vals:
        return max(vals)
    vals = [capture_value(f"bert_b{b}_perleaf_noqkv",
                          any_device=any_device, field="vs_baseline"),
            capture_value(f"bert_b{b}_maskedlm",
                          any_device=any_device, field="vs_baseline")]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def capture_pair(on_stage: str, off_stage: str, field: str = "value"):
    """Both stages' measured ``field``, or None unless BOTH exist (a
    pin decision needs the full pair). One helper so every capture A/B
    shares the same None handling."""
    a = capture_value(on_stage, field=field)
    b_ = capture_value(off_stage, field=field)
    return None if a is None or b_ is None else (a, b_)


def reorder_measured(opts: list, meas: dict) -> list:
    """Sort only the MEASURED entries of ``opts`` by value (desc),
    leaving unmeasured entries at their original positions — a partial
    capture campaign must never demote a proven built-in first choice
    behind a merely-measured one."""
    measured = [o for o in opts if meas.get(o) is not None]
    measured.sort(key=lambda o: -meas[o])
    it = iter(measured)
    return [next(it) if meas.get(o) is not None else o for o in opts]


def looks_oom(e: Exception) -> bool:
    s = f"{type(e).__name__}: {e}".lower()
    return "resource_exhausted" in s or "out of memory" in s or \
        "oom" in s or ("exceeds" in s and "memory" in s)


def maybe_steps_per_loop(step, stacked, dt_single: float, iters: int,
                         default_spl: int) -> float:
    """Time TrainStep.run_steps (K optimizer steps per dispatch via
    lax.scan — amortizes the remote-dispatch per-buffer copies the
    round-2 profile blamed for ~19% of the BERT step) and return the
    better per-step seconds. ``stacked`` maps K -> (args, labels);
    PT_BENCH_STEPS_PER_LOOP pins K (1 disables)."""
    spl_env = os.environ.get("PT_BENCH_STEPS_PER_LOOP")
    spl = int(spl_env) if spl_env else default_spl
    if spl <= 1:
        return dt_single
    out = stacked(spl)
    args, labels = out[0], out[1]
    kwargs = out[2] if len(out) > 2 else {}
    try:
        dt_multi = warmup_and_time(
            lambda: {"loss": step.run_steps(
                *args, labels=labels, **kwargs)["loss"][-1]},
            iters // spl + 1, settle_s=float(spl)) / spl
    except Exception as e:  # noqa: BLE001
        if not looks_oom(e):
            raise
        log(f"steps_per_loop={spl}: OOM; keeping single-step")
        return dt_single
    log(f"steps_per_loop={spl}: {dt_multi * 1e3:.2f} ms/step vs "
        f"{dt_single * 1e3:.2f} single ({dt_single / dt_multi:.2f}x)")
    return min(dt_single, dt_multi)


def bench_bert(on_accel: bool) -> None:
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   pretraining_loss)
    from paddle_tpu.static import TrainStep

    config = BertConfig()
    batch_env = os.environ.get("PT_BENCH_BERT_BATCH")
    seq = 512 if on_accel else 128

    # Masked-LM head restriction (reference parity: the reference's
    # BERT gathers mask_pos before the vocab projection — see
    # BertForPretraining.forward). PT_BENCH_MASKED_LM pins; otherwise
    # the measured capture pair FOR THAT BATCH decides (b8 and b32 have
    # their own A/B stages; other batches fall back to the b32 pair);
    # default full-positions until a chip A/B lands.
    masked_env = os.environ.get("PT_BENCH_MASKED_LM")
    n_masked = max(8, int(seq * 0.15) // 8 * 8)  # 15% rounded to 8

    def masked_for(b) -> bool:
        if masked_env is not None:
            return masked_env.strip().lower() in ("1", "true", "yes",
                                                  "on")
        if not on_accel:
            return False
        # compare the JUDGED number: masked mode's honest FLOP
        # accounting means higher tokens/sec does NOT imply higher
        # vs_baseline (it skips credited work). Flash-config pairs
        # (current defaults) take precedence over the XLA-attention-era
        # pairs when captured.
        pair = capture_pair(f"bert_b{b}_flash_maskedlm",
                            f"bert_b{b}_flash",
                            field="vs_baseline") or \
            capture_pair(f"bert_b{b}_maskedlm",
                         f"bert_b{b}_perleaf_noqkv",
                         field="vs_baseline") or \
            capture_pair("bert_b32_maskedlm", "bert_b32_perleaf_noqkv",
                         field="vs_baseline")
        on = pair is not None and pair[0] > pair[1]
        if on:
            log(f"masked-LM head for b{b} from captures "
                f"(vs_baseline {pair[0]:.3f} vs {pair[1]:.3f})")
        return on

    rng = np.random.default_rng(0)

    def make_data(b):
        ids = rng.integers(0, config.vocab_size, (b, seq)) \
            .astype(np.int32)
        nsp = rng.integers(0, 2, (b,)).astype(np.int64)
        if masked_for(b):
            pos = np.sort(rng.permuted(
                np.broadcast_to(np.arange(seq), (b, seq)), axis=1)
                [:, :n_masked], axis=1).astype(np.int32)
            mlm = rng.integers(0, config.vocab_size,
                               (b, n_masked)).astype(np.int64)
            return ids, pos, mlm, nsp
        mlm = rng.integers(0, config.vocab_size, (b, seq)) \
            .astype(np.int64)
        return ids, None, mlm, nsp

    def step_kwargs(pos):
        return {} if pos is None else {"masked_positions": pos}

    def build(fused: bool):
        pt.seed(0)
        m = BertForPretraining(config)
        m.to(dtype="bfloat16")  # LN/softmax/xent reductions stay fp32
        o = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                               fused_state=fused)
        return m, TrainStep(m, o, lambda out, mlm_, nsp_:
                            pretraining_loss(out, mlm_, nsp_))

    # Candidates are (batch, fused_state) pairs ranked best-guess-first
    # from the round-3 chip captures: per-leaf beat fused by 26% at b32
    # (CAPTURE_bert_perleaf_b32 vs _fused_b32) and round 2's proven
    # 121.8k tok/s config was (8, per-leaf). The BEST tokens/sec wins —
    # not the first batch that fits — under the 300s selection cap
    # (a tripped cap keeps the best-so-far: the proven config leads).
    # PT_BENCH_BERT_BATCH / PT_BENCH_FUSED pin their dimension.
    pin = os.environ.get("PT_BENCH_FUSED")
    fused_opts = [False, True] if on_accel else [False]
    if pin is not None and pin.strip() != "":
        val = pin.strip().lower()
        if val in ("1", "true", "yes", "on"):
            fused_opts = [True]
        elif val in ("0", "false", "no", "off"):
            fused_opts = [False]
        else:
            raise SystemExit(
                f"PT_BENCH_FUSED={pin!r}: expected 0/1/true/false")
    if batch_env:
        batch_opts = [int(batch_env)]
    else:
        # b16 first: the r5 flash ladder peaks there (147.8k tok/s
        # with the fused single-block backward); the capture-driven
        # reorder below refines from artifacts
        batch_opts = [16, 8, 32] if on_accel else [2]
    if on_accel and not batch_env:
        # diag-campaign artifacts reorder the sweep among MEASURED
        # batches only (selection still re-measures; this only decides
        # what the 300s cap protects — unmeasured proven configs keep
        # their built-in position). When EVERY batch is measured, also
        # cut to the top two: re-sweeping known losers spends the
        # driver's short window re-proving captures. Rank by the
        # JUDGED number across BOTH head modes per batch — cutting by
        # full-mode tokens/sec could drop the batch whose masked
        # config wins vs_baseline.
        meas = {b_: bert_batch_judged(b_) for b_ in batch_opts}
        if any(v is not None for v in meas.values()):
            batch_opts = reorder_measured(batch_opts, meas)
            log(f"measured batch order from captures: {meas}")
            if all(v is not None for v in meas.values()) \
                    and len(batch_opts) > 2:
                log(f"all batches measured; sweeping top-2 only "
                    f"{batch_opts[:2]}")
                batch_opts = batch_opts[:2]
    if on_accel and not (pin and pin.strip()) and len(fused_opts) > 1:
        # state-layout cut from the r3 capture pair (perleaf 97.1k vs
        # fused 77.1k at b32) — but ONLY when per-leaf wins: cutting to
        # per-leaf never drops a proven config (round 2's best was
        # per-leaf), while cutting to fused on b32 evidence alone would
        # remove (8, per-leaf) from the sweep
        pair = capture_pair("bert_fused_b32", "bert_perleaf_b32")
        if pair is not None and pair[1] >= pair[0]:
            fused_opts = [False]
            log(f"fused_state=False from captures (perleaf "
                f"{pair[1]:.0f} vs fused {pair[0]:.0f} tok/s)")
    # measured flag choices (sound A/Bs: same batch, same other flags).
    # TPU only — the artifacts are chip measurements. transformer_remat
    # is deliberately NOT auto-pinned: a remat win at b32 says nothing
    # about the small-batch candidates, and a global pin would remove
    # the no-remat configs from the sweep (tools/recommend.py surfaces
    # it for a manual default flip instead).
    if on_accel and os.environ.get("FLAGS_fused_qkv_projection") is None:
        pair = capture_pair("bert_b8_perleaf_qkv",
                            "bert_b8_perleaf_noqkv")
        if pair is not None:
            pt.set_flags({"fused_qkv_projection": pair[0] >= pair[1]})
            log(f"fused_qkv_projection={pair[0] >= pair[1]} from "
                f"captures (qkv {pair[0]:.0f} vs noqkv {pair[1]:.0f} "
                f"tok/s)")
    if on_accel and os.environ.get("FLAGS_optimizer_moment_dtype") is None:
        pair = capture_pair("bert_b8_bf16mv", "bert_b8_perleaf_noqkv")
        if pair is not None and pair[0] > pair[1]:
            pt.set_flags({"optimizer_moment_dtype": "bfloat16"})
            log(f"optimizer_moment_dtype=bfloat16 from captures "
                f"({pair[0]:.0f} vs {pair[1]:.0f} tok/s)")
    if on_accel and os.environ.get("FLAGS_fused_softmax_xent") is None:
        pair = capture_pair("bert_b16_fusedloss", "bert_b16_flash")
        if pair is not None and pair[0] > pair[1]:
            pt.set_flags({"fused_softmax_xent": True})
            log(f"fused_softmax_xent=True from captures (fusedloss "
                f"{pair[0]:.0f} vs flash {pair[1]:.0f} tok/s)")
    if on_accel and os.environ.get("FLAGS_fused_adam") is None:
        # stacked A/B: fused Adam measured on top of the fused loss
        # region, so the pin compares like against like
        pair = capture_pair("bert_b16_fusedloss_fusedadam",
                            "bert_b16_fusedloss")
        if pair is not None and pair[0] > pair[1]:
            pt.set_flags({"fused_adam": True})
            log(f"fused_adam=True from captures "
                f"({pair[0]:.0f} vs {pair[1]:.0f} tok/s)")
    candidates = [(b_, f_) for b_ in batch_opts for f_ in fused_opts]
    log(f"BERT-base pretrain, seq={seq} candidates {candidates}")

    n_params_box = [None]

    def note_params(model):
        if n_params_box[0] is None:
            n_params_box[0] = sum(
                int(np.prod(p.shape)) for p in model.parameters())

    def effective_params(masked: bool) -> float:
        """FLOP-carrying parameter count for the 6*N*T estimate. In
        masked mode the MLM head path (tied vocab matrix + transform +
        bias) only processes n_masked of seq positions, so crediting
        full 6*N*T would overstate achieved TFLOPs by the skipped
        vocab-projection share — scale that slice by the masked
        fraction instead."""
        n = float(n_params_box[0])
        if not masked:
            return n
        h, v = config.hidden_size, config.vocab_size
        head = h * v + h * h + v  # tied decoder + transform + bias
        return n - head * (1.0 - n_masked / seq)

    def result_for(tokens_per_sec: float, masked: bool) -> dict:
        achieved = tokens_per_sec * 6 * effective_params(masked) / 1e12
        return {
            "metric": "BERT-base pretrain tokens/sec/chip",
            "value": round(tokens_per_sec, 1),
            "unit": "tokens/sec",
            "vs_baseline": round(achieved / (0.8 * 197.0), 4),
            "masked_lm": masked,
        }

    best = None
    select_t0 = time.perf_counter()
    if len(candidates) > 1:
        data_cache = {}
        for i, (batch, fused) in enumerate(candidates):
            if batch not in data_cache:
                data_cache[batch] = make_data(batch)
            ids, pos, mlm, nsp = data_cache[batch]
            model = step = None
            try:
                model, step = build(fused)
                note_params(model)
                dt_c = warmup_and_time(
                    lambda: step(ids, labels=(mlm, nsp),
                                 **step_kwargs(pos)),
                    8 if on_accel else 2)
                cand_res = result_for(batch * seq / dt_c,
                                      pos is not None)
                log(f"batch={batch} fused_state={fused}: "
                    f"{dt_c * 1e3:.2f} ms/step "
                    f"({batch * seq / dt_c / 1e3:.1f}k tok/s, "
                    f"vs_baseline {cand_res['vs_baseline']})")
                # rank by the JUDGED number — tokens/sec and
                # vs_baseline diverge when masked mode differs by batch
                if best is None or cand_res["vs_baseline"] > best[3]:
                    best = (dt_c, fused, batch,
                            cand_res["vs_baseline"])
                    emit_partial(cand_res)
            except Exception as e:  # noqa: BLE001
                if not looks_oom(e):
                    raise
                log(f"batch={batch} fused={fused} OOM; skipping")
            finally:
                # drop this candidate's params/opt state before
                # building the next one — holding both doubles HBM
                model = step = None
            elapsed = time.perf_counter() - select_t0
            if (elapsed > 300 or budget_left() < 90) \
                    and i + 1 < len(candidates) and best is not None:
                # cold compiles ate the budget: better one finished
                # number than a driver timeout (round-1 failure mode).
                # Skipped candidates get measured next round from a
                # warm cache.
                log(f"selection already took {elapsed:.0f}s "
                    f"(budget_left {budget_left():.0f}s); "
                    f"skipping {candidates[i + 1:]}")
                break
        if best is None:
            raise SystemExit("every BERT candidate OOMed")
        _, fused, batch, _ = best
    else:
        batch, fused = candidates[0]
    ids, pos, mlm, nsp = make_data(batch)
    log(f"timing with batch={batch} fused_state={fused} "
        f"masked_lm={pos is not None} (winner rebuild; compile cache "
        f"makes this cheap)")
    model, step = build(fused)
    note_params(model)

    dt = warmup_and_time(lambda: step(ids, labels=(mlm, nsp),
                                      **step_kwargs(pos)),
                         30 if on_accel else 3)
    emit_partial(result_for(batch * seq / dt, pos is not None))
    if budget_left() > 120:
        dt = maybe_steps_per_loop(
            step,
            lambda K: ((np.stack([ids] * K),),
                       (np.stack([mlm] * K), np.stack([nsp] * K)),
                       step_kwargs(None if pos is None else
                                   np.stack([pos] * K))),
            dt, 30 if on_accel else 3, 8 if on_accel else 2)
    else:
        log(f"budget_left {budget_left():.0f}s: skipping "
            f"steps_per_loop re-timing (measured ~1.0x in r3)")
    tokens_per_sec = batch * seq / dt
    achieved_tflops = tokens_per_sec * 6 * \
        effective_params(pos is not None) / 1e12
    log(f"{tokens_per_sec:.0f} tok/s = {achieved_tflops:.1f} TFLOPs "
        f"({achieved_tflops / 197.0 * 100:.1f}% v5e MFU)")
    emit(result_for(tokens_per_sec, pos is not None))


def bench_resnet(on_accel: bool) -> None:
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models.resnet import resnet50
    from paddle_tpu.static import TrainStep

    batch_env = os.environ.get("PT_BENCH_RESNET_BATCH")
    hw = 224 if on_accel else 64

    import jax.numpy as jnp
    rng = np.random.default_rng(0)

    def make_data(b):
        return (rng.normal(0, 1, (b, 3, hw, hw)),
                rng.integers(0, 1000, (b,)).astype(np.int64))

    def build(df: str, fused: bool, s2d: bool, x_nchw):
        pt.seed(0)
        model = resnet50(data_format=df)
        model.s2d_stem = s2d  # per-model pin; no global flag mutation
        model.to(dtype="bfloat16")
        opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    fused_state=fused)
        step = TrainStep(model, opt,
                         lambda out, t: pt.nn.functional.cross_entropy(
                             out, t))
        # bf16 images to match the bf16 conv weights (strict dtypes,
        # like the reference's fp16 AMP path casts inputs), generated
        # directly in the compute layout — no transpose in the step
        data = x_nchw if df == "NCHW" else \
            np.transpose(x_nchw, (0, 2, 3, 1))
        return step, jnp.asarray(data, jnp.bfloat16)

    # Candidates are (batch, layout, fused, s2d_stem) ranked best-
    # guess-first from chip evidence: NHWC beat NCHW by 8% at b128
    # (CAPTURE_resnet_{nhwc,nchw}_b128); round 2's b64 was best
    # per-image; BERT said per-leaf state. Best images/sec wins under
    # the selection cap. PT_BENCH_{RESNET_BATCH,LAYOUT,FUSED} and
    # FLAGS_resnet_space_to_depth_stem pin dimensions.
    pin_layout = os.environ.get("PT_BENCH_LAYOUT")
    pin_fused = os.environ.get("PT_BENCH_FUSED")
    layouts = [pin_layout.strip().upper()] if pin_layout else \
        (["NHWC", "NCHW"] if on_accel else ["NCHW"])
    fuseds = [pin_fused.strip() in ("1", "true", "yes", "on")] \
        if pin_fused else ([False, True] if on_accel else [False])
    if on_accel and not pin_layout and len(layouts) > 1:
        # prefer the clean _SPL1 like-for-like pair (VERDICT r4 task 6:
        # the r3 unpinned pair said NHWC 1829 vs NCHW 1689 img/s, but
        # the dead NCHW stage's partial timing contradicted it in the
        # same window — the layout question is only settled by the
        # matched pair); fall back to the old unpinned pair until the
        # clean one lands
        pair = capture_pair("resnet_nhwc_b128_perleaf",
                            "resnet_nchw_b128_perleaf") or \
            capture_pair("resnet_nhwc_b128", "resnet_nchw_b128")
        if pair is not None:
            layouts = ["NHWC" if pair[0] >= pair[1] else "NCHW"]
            log(f"layout={layouts[0]} from captures "
                f"(nhwc {pair[0]:.0f} vs nchw {pair[1]:.0f} img/s)")
    if on_accel and not pin_fused and len(fuseds) > 1 \
            and layouts == ["NHWC"]:
        # clean same-flags pair only (resnet_nhwc_b128 autotunes
        # steps-per-loop, so it is NOT comparable to the _SPL1 perleaf
        # stage); pair is NHWC evidence, hence the layout gate
        pair = capture_pair("resnet_nhwc_b128_fused",
                            "resnet_nhwc_b128_perleaf")
        if pair is not None:
            fuseds = [pair[0] > pair[1]]
            log(f"fused_state={fuseds[0]} from captures "
                f"(fused {pair[0]:.0f} vs perleaf {pair[1]:.0f} img/s)")
    batches = [int(batch_env)] if batch_env else \
        ([64, 128, 256] if on_accel else [4])
    if on_accel and not batch_env:
        meas = {128: capture_value("resnet_nhwc_b128_perleaf"),
                256: capture_value("resnet_nhwc_b256_perleaf")}
        if any(v is not None for v in meas.values()):
            batches = reorder_measured(batches, meas)
            log(f"measured batch order from captures: {meas}")
    s2d_pin = pt.get_flags("resnet_space_to_depth_stem")[
        "resnet_space_to_depth_stem"]
    if on_accel and \
            os.environ.get("FLAGS_resnet_space_to_depth_stem") is None:
        pair = capture_pair("resnet_nhwc_b128_s2d",
                            "resnet_nhwc_b128_perleaf")
        if pair is not None:
            s2d_pin = bool(pair[0] > pair[1])
            log(f"s2d stem={s2d_pin} from captures "
                f"({pair[0]:.0f} vs {pair[1]:.0f} img/s)")
    if on_accel and os.environ.get("FLAGS_resnet_block_remat") is None:
        # block remat on the HBM-bound step (same pinning as its A/B
        # partner: bn1pass + spl8) — measured winner governs
        pair = capture_pair("resnet_remat", "resnet_bn1pass_spl8")
        if pair is not None:
            pt.set_flags({"resnet_block_remat": pair[0] > pair[1]})
            log(f"resnet_block_remat={pair[0] > pair[1]} from captures "
                f"(remat {pair[0]:.0f} vs no-remat {pair[1]:.0f} "
                f"img/s)")
    candidates = [(b_, df, fu, s2d_pin and df == "NHWC")
                  for b_ in batches for df in layouts for fu in fuseds]
    # keep the sweep bounded: batch dim rides the first layout/fused
    # combo; layout/fused ride the first batch
    candidates = [c for i, c in enumerate(candidates)
                  if c[0] == batches[0] or
                  (c[1] == layouts[0] and c[2] == fuseds[0])]
    log(f"ResNet-50 train, image={hw}x{hw} candidates {candidates}")

    # ResNet-50 fwd ≈ 4.1 GFLOPs/image at 224x224; train ≈ 3x fwd
    fwd_gflops = 4.1 * (hw / 224.0) ** 2

    def result_for(images_per_sec: float) -> dict:
        achieved = images_per_sec * 3 * fwd_gflops / 1e3
        return {
            "metric": "ResNet-50 train images/sec/chip",
            "value": round(images_per_sec, 1),
            "unit": "images/sec",
            "vs_baseline": round(achieved / (0.8 * 197.0), 4),
        }

    best = None
    select_t0 = time.perf_counter()
    if len(candidates) > 1:
        data_cache = {}
        for i, (batch, df, fu, s2d) in enumerate(candidates):
            if batch not in data_cache:
                data_cache[batch] = make_data(batch)
            x_nchw, y = data_cache[batch]
            step = x = None
            try:
                step, x = build(df, fu, s2d, x_nchw)
                dt_c = warmup_and_time(lambda: step(x, labels=y),
                                       8 if on_accel else 2)
                log(f"batch={batch} layout={df} fused_state={fu}: "
                    f"{dt_c * 1e3:.2f} ms/step "
                    f"({batch / dt_c:.0f} img/s)")
                if best is None or dt_c / batch < best[0] / best[4]:
                    best = (dt_c, df, fu, s2d, batch)
                    emit_partial(result_for(batch / dt_c))
            except Exception as e:  # noqa: BLE001
                if not looks_oom(e):
                    raise
                log(f"batch={batch} layout={df} OOM; skipping")
            finally:
                step = x = None
            elapsed = time.perf_counter() - select_t0
            if (elapsed > 300 or budget_left() < 90) \
                    and i + 1 < len(candidates) and best is not None:
                log(f"selection took {elapsed:.0f}s (budget_left "
                    f"{budget_left():.0f}s); skipping "
                    f"{candidates[i + 1:]}")
                break
        if best is None:
            raise SystemExit("every ResNet candidate OOMed")
        _, df, fu, s2d, batch = best
    else:
        batch, df, fu, s2d = candidates[0]
    x_nchw, y = make_data(batch)
    log(f"timing with batch={batch} layout={df} fused_state={fu} "
        f"s2d={s2d} (winner rebuild; compile cache makes this cheap)")
    step, x = build(df, fu, s2d, x_nchw)

    dt = warmup_and_time(lambda: step(x, labels=y),
                         20 if on_accel else 3)
    emit_partial(result_for(batch / dt))
    if budget_left() > 120:
        dt = maybe_steps_per_loop(
            step, lambda K: ((jnp.stack([x] * K),),
                             (np.stack([y] * K),)),
            dt, 20 if on_accel else 3, 8 if on_accel else 2)
    else:
        log(f"budget_left {budget_left():.0f}s: skipping "
            f"steps_per_loop re-timing")
    images_per_sec = batch / dt
    achieved_tflops = images_per_sec * 3 * fwd_gflops / 1e3
    log(f"{images_per_sec:.1f} images/s = {achieved_tflops:.1f} TFLOPs")
    emit(result_for(images_per_sec))


def bench_flash_attention(on_accel: bool) -> None:
    """Flash kernel vs XLA attention across sequence lengths — the
    routing evidence behind flags.flash_attention_min_seq (the Pallas
    kernel is also O(T) memory vs XLA's O(T²) scores)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels.flash_attention import flash_attention
    from paddle_tpu.ops.attention import scaled_dot_product_attention

    import functools

    rng = np.random.default_rng(0)
    b, h, d = (1, 8, 128) if on_accel else (1, 2, 128)
    seqs = (1024, 2048, 4096, 8192, 16384) if on_accel else (256,)
    if not on_accel:
        # Mosaic lowers only on TPU; CPU runs the interpreter
        flash = functools.partial(flash_attention, interpret=True)
    else:
        flash = flash_attention
    results = {}
    for t in seqs:
        q = jnp.asarray(rng.normal(0, 1, (b, h, t, d)), jnp.bfloat16)

        def run(fn):
            f = jax.jit(lambda q: jnp.sum(
                fn(q, q, q).astype(jnp.float32)))
            for _ in range(3):
                float(f(q))
            n = 10
            t0 = time.perf_counter()
            for _ in range(n):
                r = f(q)
            float(r)
            return (time.perf_counter() - t0) / n * 1e3

        def timed(fn, name):
            # the XLA path materializes [H, T, T] scores — at 16k that
            # is HBM-scale; an OOM must cost one datapoint, not the sweep
            try:
                return run(fn)
            except Exception as e:  # noqa: BLE001
                if looks_oom(e):
                    log(f"seq {t}: {name} OOM; recording None "
                        f"[{f'{type(e).__name__}: {e}'[:200]}]")
                    return None
                raise

        xla_ms = timed(scaled_dot_product_attention, "xla")
        flash_ms = timed(flash, "flash")
        results[t] = (xla_ms, flash_ms)
        if xla_ms and flash_ms:
            log(f"seq {t}: xla {xla_ms:.2f}ms  flash {flash_ms:.2f}ms  "
                f"speedup {xla_ms / flash_ms:.2f}x")
            emit_partial({
                "metric": f"flash-attention fwd speedup vs XLA @seq{t}",
                "value": round(xla_ms / flash_ms, 3),
                "unit": "x",
                "vs_baseline": round(xla_ms / flash_ms, 3),
                "seq": t,
            })
        elif flash_ms:
            log(f"seq {t}: xla OOM, flash {flash_ms:.2f}ms "
                f"(O(T) memory is the datapoint)")
        elif xla_ms:
            log(f"seq {t}: flash OOM/failed, xla {xla_ms:.2f}ms")
    # report the largest seq where BOTH ran; if XLA OOMed at the top
    # lengths, that absence is itself the flash result (O(T) memory)
    both = [t for t, (a, b) in results.items() if a and b]
    t_big = max(both) if both else seqs[0]
    xla_ms, flash_ms = results[t_big]
    speed = round(xla_ms / flash_ms, 3) if (xla_ms and flash_ms) else 0.0
    oom_lens = [t for t, (a, b) in results.items() if b and not a]
    if oom_lens:
        log(f"flash ran where XLA could not: seqs {oom_lens}")
    emit({
        "metric": f"flash-attention fwd speedup vs XLA @seq{t_big}",
        "value": speed,
        "unit": "x",
        "vs_baseline": speed,
        "seq": t_big,
    })


def bench_llm_decode(on_accel: bool) -> None:
    """LLM serving decode path (paddle_tpu/serving_llm): paged-KV
    continuous batching on the toy GPT decoder vs the dense
    GenerationMixin loop serving the same requests sequentially.
    Reports aggregate decode tokens/s plus TTFT p50/p99; vs_baseline
    is the paged/dense throughput ratio (batching is the win — one
    ragged decode step serves every running sequence)."""
    import numpy as np

    import jax.numpy as jnp

    from paddle_tpu.models import GPTLanguageModel
    from paddle_tpu.serving_llm import LLMEngine

    model = GPTLanguageModel()
    rng = np.random.default_rng(0)
    n_req, max_new = (8, 32) if on_accel else (6, 8)
    prompts = [rng.integers(0, model.config.vocab_size,
                            size=ln).astype(np.int32)
               for ln in ([8, 48] * n_req)[:n_req]]

    # warm the compile caches so both timings measure steady state
    list(np.asarray(model.generate(jnp.asarray([prompts[0]]),
                                   max_new_tokens=2)))
    warm = LLMEngine(model, block_size=16, pool_blocks=128)
    warm.add_request(prompts[0], max_new_tokens=2)
    while warm.active():
        warm.step()

    engine = LLMEngine(model, block_size=16, pool_blocks=128)
    t_add = {}
    ttft_ms = {}
    n_tok = 0
    t0 = time.perf_counter()
    for p in prompts:
        t_add[engine.add_request(p, max_new_tokens=max_new)] = \
            time.perf_counter()
    while engine.active():
        for ev in engine.step():
            if ev["type"] == "token":
                n_tok += 1
                if ev["index"] == 0:
                    ttft_ms[ev["seq_id"]] = \
                        (time.perf_counter()
                         - t_add[ev["seq_id"]]) * 1e3
    paged_s = time.perf_counter() - t0
    assert n_tok == n_req * max_new, (n_tok, n_req, max_new)
    assert engine.allocator.num_used == 0

    t0 = time.perf_counter()
    for p in prompts:
        model.generate(jnp.asarray([p]), max_new_tokens=max_new)
    dense_s = time.perf_counter() - t0

    ttfts = sorted(ttft_ms.values())
    p50 = ttfts[len(ttfts) // 2]
    p99 = ttfts[min(len(ttfts) - 1,
                    int(round(0.99 * (len(ttfts) - 1))))]
    toks_per_s = n_tok / paged_s
    ratio = round((n_tok / paged_s) / (n_tok / dense_s), 3)
    log(f"paged {paged_s:.2f}s ({toks_per_s:.1f} tok/s) vs dense "
        f"sequential {dense_s:.2f}s; ttft p50={p50:.0f}ms "
        f"p99={p99:.0f}ms")
    emit_partial({
        "metric": f"llm decode TTFT p50 ({n_req} reqs)",
        "value": round(p50, 1), "unit": "ms",
        "vs_baseline": ratio, "ttft_p99_ms": round(p99, 1),
    })
    emit({
        "metric": f"llm paged decode throughput ({n_req} reqs x "
                  f"{max_new} tokens)",
        "value": round(toks_per_s, 2),
        "unit": "tokens/s",
        "vs_baseline": ratio,
        "ttft_p50_ms": round(p50, 1),
        "ttft_p99_ms": round(p99, 1),
    })


def bench_llm_overload(on_accel: bool) -> None:
    """LLM serving under overload: a stream flood whose projected KV
    demand is 2x the pool, against the admission watermark
    (FLAGS_kv_admission_watermark=1.0). Overflow is refused at
    admission with a retry hint instead of entering preemption
    thrash; reports the reject rate and p99 TTFT of the streams that
    were admitted, and asserts the pool drains to zero — overload
    must never leak KV blocks."""
    import threading

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.inference import Client, Server
    from paddle_tpu.models import GPTLanguageModel
    from paddle_tpu.serving_llm import LLMEngine

    model = GPTLanguageModel()
    rng = np.random.default_rng(0)
    n_req, max_new, block_size = (16, 32, 16) if on_accel \
        else (12, 8, 16)
    blocks_per_req = -(-(8 + max_new) // block_size)
    # pool sized for half the flood's projected demand
    pool_blocks = n_req * blocks_per_req // 2
    prompts = [rng.integers(0, model.config.vocab_size,
                            size=8).astype(np.int32)
               for _ in range(n_req)]

    pt.set_flags({"kv_admission_watermark": 1.0})
    engine = LLMEngine(model, block_size=block_size,
                       pool_blocks=pool_blocks)
    srv = Server(None, llm_engine=engine)
    results = []
    lock = threading.Lock()

    def worker(p):
        cli = Client(port=srv.port, timeout_s=300.0)
        t0 = time.perf_counter()
        try:
            gen = cli.generate_stream(p, max_new_tokens=max_new)
            next(gen)
            ttft = (time.perf_counter() - t0) * 1e3
            n = 1 + sum(1 for _ in gen)
            with lock:
                results.append(("ok", ttft, n))
        except RuntimeError as e:
            with lock:
                results.append(("rejected", None,
                                "retry_after_ms=" in str(e)))
        finally:
            cli.close()

    try:
        # warm the compile caches outside the timed flood
        wcli = Client(port=srv.port, timeout_s=300.0)
        wcli.generate(prompts[0], max_new_tokens=2)
        wcli.close()

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker, args=(p,))
                   for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        flood_s = time.perf_counter() - t0
    finally:
        srv.stop()
        pt.set_flags({"kv_admission_watermark": 0.0})

    served = [r for r in results if r[0] == "ok"]
    rejected = [r for r in results if r[0] == "rejected"]
    assert len(served) + len(rejected) == n_req, results
    assert served, "overload flood starved every request"
    assert all(r[2] == max_new for r in served), \
        "admitted stream truncated"
    assert all(r[2] for r in rejected), "rejection lacked retry hint"
    # the zero-leak contract: however the flood resolved, the pool
    # comes back empty and internally consistent
    assert engine.allocator.num_used == 0
    engine.allocator.check()

    ttfts = sorted(r[1] for r in served)
    p99 = ttfts[min(len(ttfts) - 1,
                    int(round(0.99 * (len(ttfts) - 1))))]
    reject_rate = len(rejected) / n_req
    log(f"{n_req}-stream flood vs pool for {n_req // 2}: "
        f"{len(served)} served, {len(rejected)} refused at admission "
        f"({reject_rate:.0%}) in {flood_s:.2f}s; admitted ttft "
        f"p99={p99:.0f}ms; pool drained to 0")
    emit({
        "metric": f"llm overload admitted TTFT p99 "
                  f"({n_req}-stream flood, 2x pool demand)",
        "value": round(p99, 1),
        "unit": "ms",
        "reject_rate": round(reject_rate, 3),
        "served": len(served),
        "rejected": len(rejected),
        "flood_s": round(flood_s, 2),
    })


def bench_llm_tenant_flood(on_accel: bool) -> None:
    """Premium TTFT isolation under a sustained bulk flood with the
    multi-tenant traffic plane on (FLAGS_tenant_fair_share): a
    weight-10 premium tenant samples TTFT against a weight-1 bulk
    flood that holds the pool saturated (bulk KV budget 50%, so
    premium admission always has headroom). Reports unloaded and
    loaded premium p99 TTFT and their ratio — the number the
    llm_tenant_flood chaos drill gates at 1.25x — plus the bulk
    throughput the flood sustained while premium stayed fast."""
    import threading

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.inference import Client, Server
    from paddle_tpu.models import GPTLanguageModel
    from paddle_tpu.serving_llm import LLMEngine

    model = GPTLanguageModel()
    n_workers, n_samples = (12, 16) if on_accel else (8, 8)
    pt.set_flags({"tenant_fair_share": True,
                  "tenant_weights": "prem=10,bulk=1",
                  "tenant_kv_budget": "bulk=0.5",
                  "kv_admission_watermark": 0.9})
    engine = LLMEngine(model, block_size=4, pool_blocks=16)
    srv = Server(None, llm_engine=engine)
    b_prompt = np.arange(5, dtype=np.int32) + 3
    p_prompt = np.arange(3, 27, dtype=np.int32) % \
        model.config.vocab_size

    def premium_ttft(cli):
        t0 = time.perf_counter()
        gen = cli.generate_stream(p_prompt, max_new_tokens=4,
                                  tenant="prem",
                                  priority_class="premium")
        next(gen)
        dt = (time.perf_counter() - t0) * 1e3
        for _ in gen:
            pass
        return dt

    bulk_ok = [0]
    bulk_rejected = [0]
    lock = threading.Lock()

    def start_flood():
        stop = threading.Event()

        def bulk_worker():
            cli = Client(port=srv.port, timeout_s=300.0)
            try:
                while not stop.is_set():
                    try:
                        cli.generate(b_prompt, max_new_tokens=6,
                                     retry=False, tenant="bulk",
                                     priority_class="bulk")
                        with lock:
                            bulk_ok[0] += 1
                    except RuntimeError:
                        with lock:
                            bulk_rejected[0] += 1
                        time.sleep(0.05)   # honor the backoff hint
            finally:
                cli.close()

        threads = [threading.Thread(target=bulk_worker)
                   for _ in range(n_workers)]
        for t in threads:
            t.start()
        return stop, threads

    try:
        cli = Client(port=srv.port, timeout_s=300.0)
        # warm every composition the measurement hits: solo premium
        # AND premium prefill riding a resident bulk decode batch
        premium_ttft(cli)
        stop, threads = start_flood()
        time.sleep(0.3)
        for _ in range(2):
            premium_ttft(cli)
        stop.set()
        for t in threads:
            t.join()
        drain_by = time.perf_counter() + 10.0
        while engine.allocator.num_used and \
                time.perf_counter() < drain_by:
            time.sleep(0.02)

        baseline = sorted(premium_ttft(cli) for _ in range(n_samples))
        bulk_ok[0] = bulk_rejected[0] = 0
        stop, threads = start_flood()
        time.sleep(0.3)
        t_flood = time.perf_counter()
        loaded = sorted(premium_ttft(cli) for _ in range(n_samples))
        flood_s = time.perf_counter() - t_flood
        stop.set()
        for t in threads:
            t.join()
        cli.close()
    finally:
        srv.stop()
        pt.set_flags({"tenant_fair_share": False, "tenant_weights": "",
                      "tenant_kv_budget": "",
                      "kv_admission_watermark": 0.0})

    assert engine.allocator.num_used == 0
    engine.allocator.check()
    base_p99, load_p99 = baseline[-1], loaded[-1]
    # same 100ms noise floor as the drill: below it the ratio measures
    # interpreter jitter, not scheduling
    ratio = load_p99 / max(base_p99, 100.0)
    log(f"premium ttft p99 {base_p99:.0f}ms unloaded -> "
        f"{load_p99:.0f}ms under {n_workers}-worker bulk flood "
        f"(ratio {ratio:.2f}); flood sustained "
        f"{bulk_ok[0]} bulk streams ({bulk_rejected[0]} budget "
        f"rejections) in {flood_s:.2f}s")
    emit({
        "metric": "llm tenant flood premium TTFT p99 "
                  "(weight-10 premium vs weight-1 bulk flood)",
        "value": round(load_p99, 1),
        "unit": "ms",
        "baseline_p99_ms": round(base_p99, 1),
        "ttft_ratio": round(ratio, 3),
        "bulk_ok": bulk_ok[0],
        "bulk_rejected": bulk_rejected[0],
        "flood_s": round(flood_s, 2),
    })


def bench_llm_prefix_reuse(on_accel: bool) -> None:
    """Copy-on-write shared-prefix KV reuse (FLAGS_kv_prefix_sharing):
    K streams sharing a long preamble (the system-prompt/few-shot
    shape), flooded at ~2x the pool's UNSHARED demand behind the
    admission watermark. Unshared, half the flood is refused; with
    sharing on the watermark projects post-sharing demand, so the same
    pool admits ~Nx more streams while `kv_blocks_used` stays a
    fraction of the unshared run. vs_baseline is the admitted-streams
    ratio (shared / unshared); decode tok/s rides along to show
    sharing costs the decode path nothing (the kernel is unchanged —
    block tables already indirect)."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import GPTLanguageModel
    from paddle_tpu.serving_llm import AdmissionRejected, LLMEngine

    model = GPTLanguageModel()
    rng = np.random.default_rng(0)
    n_req, max_new, block_size, pre_len = (16, 32, 16, 512) \
        if on_accel else (8, 8, 16, 64)
    preamble = rng.integers(0, model.config.vocab_size,
                            size=pre_len).astype(np.int32)
    prompts = [list(preamble) + list(rng.integers(
        0, model.config.vocab_size, size=8)) for _ in range(n_req)]
    blocks_per_req = -(-(pre_len + 8 + max_new) // block_size)
    # pool sized for half the flood's UNSHARED projected demand
    pool_blocks = n_req * blocks_per_req // 2

    def flood(sharing: bool):
        pt.set_flags({"kv_admission_watermark": 1.0,
                      "kv_prefix_sharing": sharing})
        engine = LLMEngine(model, block_size=block_size,
                           pool_blocks=pool_blocks)
        admitted, peak, n_tok = [], 0, 0
        decode_s = 0.0
        try:
            for p in prompts:
                try:
                    admitted.append(
                        engine.add_request(p, max_new_tokens=max_new))
                except AdmissionRejected:
                    pass
                # interleave arrivals with steps so later requests
                # probe prefixes already resident, not just projected
                engine.step()
                peak = max(peak, engine.allocator.num_used)
            while engine.active():
                t0 = time.perf_counter()
                evs = engine.step()
                decode_s += time.perf_counter() - t0
                n_tok += sum(1 for ev in evs if ev["type"] == "token")
                peak = max(peak, engine.allocator.num_used)
            assert engine.scheduler.preemptions_total == 0, \
                "watermark projection must prevent preempt-thrash"
            assert engine.allocator.num_used == 0, "KV leak"
            engine.allocator.check()
        finally:
            pt.set_flags({"kv_admission_watermark": 0.0,
                          "kv_prefix_sharing": False})
        return len(admitted), peak, n_tok, decode_s

    unshared_n, unshared_peak, _, _ = flood(sharing=False)
    shared_n, shared_peak, n_tok, decode_s = flood(sharing=True)
    assert shared_n > unshared_n, (shared_n, unshared_n)
    ratio = round(shared_n / max(1, unshared_n), 3)
    toks_per_s = n_tok / decode_s if decode_s > 0 else 0.0
    log(f"{n_req}-stream flood, {pre_len}-token shared preamble, pool "
        f"{pool_blocks} blocks: unshared admits {unshared_n} "
        f"(peak {unshared_peak} blocks), shared admits {shared_n} "
        f"(peak {shared_peak} blocks) = {ratio}x; "
        f"decode {toks_per_s:.1f} tok/s; pool drained to 0")
    emit({
        "metric": f"llm prefix-reuse admitted streams "
                  f"({n_req}-stream flood, {pre_len}-token preamble)",
        "value": shared_n,
        "unit": "streams",
        "vs_baseline": ratio,
        "unshared_admitted": unshared_n,
        "kv_blocks_peak": shared_peak,
        "kv_blocks_peak_unshared": unshared_peak,
        "decode_toks_per_s": round(toks_per_s, 2),
    })


def bench_llm_mixed_prefill(on_accel: bool) -> None:
    """Chunked prefill (FLAGS_prefill_chunk_tokens): long-prompt
    arrivals during steady decode. Without chunking, each arrival's
    FULL prefill runs inside one step() and every running stream's
    inter-token gap spikes by the whole prefill; chunked, the prompt
    lands one chunk per step interleaved with decode ticks. Reports
    p99 inter-token latency (the serving_tpot_ms shape) of the steady
    streams; vs_baseline is the unchunked/chunked p99 ratio (higher =
    chunking absorbed more of the spike)."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import GPTLanguageModel
    from paddle_tpu.serving_llm import LLMEngine

    model = GPTLanguageModel()
    rng = np.random.default_rng(0)
    n_steady, long_len, max_new, chunk = (6, 512, 64, 256) \
        if on_accel else (4, 96, 24, 16)
    steady = [list(rng.integers(0, model.config.vocab_size, size=8))
              for _ in range(n_steady)]
    long_prompts = [list(rng.integers(0, model.config.vocab_size,
                                      size=long_len))
                    for _ in range(2)]

    def run(chunk_tokens: int) -> float:
        pt.set_flags({"prefill_chunk_tokens": chunk_tokens})
        engine = LLMEngine(model, block_size=16, pool_blocks=256)
        try:
            ids = {engine.add_request(p, max_new_tokens=max_new)
                   for p in steady}
            # warm the steady decode before injecting the long prompts
            for _ in range(4):
                engine.step()
            stamps = {i: [] for i in ids}
            arrivals = list(long_prompts)
            step = 0
            while engine.active():
                step += 1
                if arrivals and step % 3 == 0:
                    engine.add_request(arrivals.pop(),
                                       max_new_tokens=4)
                for ev in engine.step():
                    if ev["type"] == "token" and ev["seq_id"] in ids:
                        stamps[ev["seq_id"]].append(
                            time.perf_counter())
            assert engine.allocator.num_used == 0, "KV leak"
            engine.allocator.check()
        finally:
            pt.set_flags({"prefill_chunk_tokens": 0})
        gaps = [(b - a) * 1e3 for ts in stamps.values()
                for a, b in zip(ts, ts[1:])]
        assert gaps, "steady streams produced no inter-token gaps"
        gaps.sort()
        return gaps[min(len(gaps) - 1,
                        int(round(0.99 * (len(gaps) - 1))))]

    p99_off = run(0)
    p99_on = run(chunk)
    ratio = round(p99_off / p99_on, 3) if p99_on > 0 else 0.0
    log(f"{n_steady} steady streams + {long_len}-token arrivals: "
        f"decode p99 inter-token {p99_off:.1f}ms unchunked vs "
        f"{p99_on:.1f}ms with {chunk}-token chunks ({ratio}x)")
    emit({
        "metric": f"llm mixed-prefill decode p99 inter-token "
                  f"({long_len}-token arrivals, {chunk}-token chunks)",
        "value": round(p99_on, 1),
        "unit": "ms",
        "vs_baseline": ratio,
        "p99_unchunked_ms": round(p99_off, 1),
    })


def bench_llm_spec_decode(on_accel: bool) -> None:
    """Speculative decoding (FLAGS_speculative_k): same request set
    decoded with and without a draft proposing k tokens per step for
    the target to verify in one batched ragged multi-query paged
    forward. The CPU sanity configuration is SELF-drafting (draft ==
    target): the accept rate must be exactly 1.0 at temperature 0 and
    the output token-for-token identical — what the stage measures is
    the verify-step amortization (accepted tokens per target step),
    which is the on-chip speedup lever once a cheap draft exists.
    Reports accepted tokens/s; vs_baseline is the speculative/
    non-speculative throughput ratio, with accept-rate and
    verify-latency partials."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import GPTLanguageModel
    from paddle_tpu.serving_llm import LLMEngine

    model = GPTLanguageModel()
    rng = np.random.default_rng(0)
    n_req, max_new, spec_k = (8, 32, 4) if on_accel else (4, 12, 3)
    prompts = [rng.integers(0, model.config.vocab_size,
                            size=ln).astype(np.int32)
               for ln in ([8, 48] * n_req)[:n_req]]

    def run(k: int):
        pt.set_flags({"speculative_k": k})
        engine = LLMEngine(model, block_size=16, pool_blocks=128,
                           draft_model=model if k else None)
        toks = {}
        try:
            # warm the compile caches outside the timed window
            wid = engine.add_request(prompts[0], max_new_tokens=3)
            while engine.active():
                engine.step()
            assert engine.allocator.num_used == 0
            t0 = time.perf_counter()
            sids = [engine.add_request(p, max_new_tokens=max_new)
                    for p in prompts]
            while engine.active():
                for ev in engine.step():
                    if ev["type"] == "token":
                        toks.setdefault(ev["seq_id"],
                                        []).append(int(ev["token"]))
                    elif ev["type"] == "error":
                        raise AssertionError(f"decode error: {ev}")
            dt = time.perf_counter() - t0
        finally:
            pt.set_flags({"speculative_k": 0})
        # the zero-leak contract survives the rollback machinery
        assert engine.allocator.num_used == 0, "KV leak"
        engine.allocator.check()
        toks.pop(wid, None)
        assert sorted(len(t) for t in toks.values()) \
            == [max_new] * n_req
        return dt, [toks[s] for s in sids], engine

    base_s, base_toks, _ = run(0)
    spec_s, spec_toks, eng = run(spec_k)
    assert spec_toks == base_toks, \
        "speculative output diverged from non-speculative decode"
    accept_rate = (eng.spec_accepted_total / eng.spec_proposed_total
                   if eng.spec_proposed_total else 0.0)
    assert accept_rate == 1.0, \
        f"self-draft accept rate must be 1.0, got {accept_rate}"
    verify_ms = (eng.spec_verify_ms_total / eng.spec_verify_steps
                 if eng.spec_verify_steps else 0.0)
    n_tok = n_req * max_new
    ratio = round((n_tok / spec_s) / (n_tok / base_s), 3)
    log(f"speculative k={spec_k} self-draft: {spec_s:.2f}s "
        f"({n_tok / spec_s:.1f} tok/s) vs non-speculative "
        f"{base_s:.2f}s ({ratio}x); accept rate "
        f"{accept_rate:.2f}, verify {verify_ms:.1f}ms/step, "
        f"{eng.spec_verify_steps} verify steps for {n_tok} tokens")
    emit_partial({
        "metric": f"llm spec decode accept rate (self-draft, "
                  f"k={spec_k})",
        "value": round(accept_rate, 3), "unit": "ratio",
        "accepted_tokens": eng.spec_accepted_total,
        "proposed_tokens": eng.spec_proposed_total,
    })
    emit_partial({
        "metric": "llm spec decode verify latency",
        "value": round(verify_ms, 1), "unit": "ms",
        "verify_steps": eng.spec_verify_steps,
    })
    emit({
        "metric": f"llm speculative decode throughput ({n_req} reqs "
                  f"x {max_new} tokens, self-draft k={spec_k})",
        "value": round(n_tok / spec_s, 2),
        "unit": "tokens/s",
        "vs_baseline": ratio,
        "accept_rate": round(accept_rate, 3),
        "verify_ms_mean": round(verify_ms, 1),
    })


def bench_flash_train(on_accel: bool) -> None:
    """Training-mode flash crossover: fwd+bwd at BERT geometry (head
    dim 64, attention dropout 0.1) — the numbers that set
    flash_attention_min_seq for the flagship model, which the fwd-only
    d128 sweep does not represent (the XLA backward re-materializes the
    [T, T] probs in fp32; flash recomputes them blockwise)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels.flash_attention import flash_attention
    from paddle_tpu.ops.attention import scaled_dot_product_attention

    rng = np.random.default_rng(0)
    b, h, d = (4, 12, 64) if on_accel else (1, 2, 64)
    pd = 0.1
    seqs = (512, 1024, 2048, 4096, 8192) if on_accel else (256,)
    seed = jnp.asarray([[7]], jnp.int32)
    results = {}
    for t in seqs:
        q = jnp.asarray(rng.normal(0, 1, (b, h, t, d)), jnp.bfloat16)

        def loss_flash(q_):
            return jnp.sum(flash_attention(
                q_, q_, q_, False, None, not on_accel, pd, seed)
                .astype(jnp.float32))

        def loss_xla(q_):
            key = jax.random.PRNGKey(7)
            return jnp.sum(scaled_dot_product_attention(
                q_, q_, q_, dropout_p=pd, training=True, key=key)
                .astype(jnp.float32))

        def run(loss):
            f = jax.jit(jax.grad(loss))
            for _ in range(3):
                f(q)[0, 0, 0, 0].block_until_ready()
            n = 10
            t0 = time.perf_counter()
            for _ in range(n):
                r = f(q)
            float(r[0, 0, 0, 0])
            return (time.perf_counter() - t0) / n * 1e3

        def timed(loss, name):
            try:
                return run(loss)
            except Exception as e:  # noqa: BLE001
                if looks_oom(e):
                    log(f"seq {t}: {name} OOM; recording None "
                        f"[{f'{type(e).__name__}: {e}'[:200]}]")
                    return None
                raise

        xla_ms = timed(loss_xla, "xla")
        flash_ms = timed(loss_flash, "flash")
        results[t] = (xla_ms, flash_ms)
        if xla_ms and flash_ms:
            log(f"seq {t}: train xla {xla_ms:.2f}ms  flash "
                f"{flash_ms:.2f}ms  speedup {xla_ms / flash_ms:.2f}x")
            emit_partial({
                "metric": f"flash-attention train fwd+bwd speedup vs "
                          f"XLA @seq{t} (d64+dropout)",
                "value": round(xla_ms / flash_ms, 3),
                "unit": "x",
                "vs_baseline": round(xla_ms / flash_ms, 3),
                "seq": t,
            })
        elif flash_ms:
            log(f"seq {t}: xla OOM, flash {flash_ms:.2f}ms")
    both = [t for t, (a, c) in results.items() if a and c]
    t_big = max(both) if both else seqs[0]
    xla_ms, flash_ms = results[t_big]
    speed = round(xla_ms / flash_ms, 3) if (xla_ms and flash_ms) else 0.0
    crossover = [t for t, (a, c) in results.items()
                 if a and c and c < a]
    log(f"flash train-mode wins at seqs {crossover}")
    emit({
        "metric": f"flash-attention train fwd+bwd speedup vs XLA "
                  f"@seq{t_big} (d64+dropout)",
        "value": speed,
        "unit": "x",
        "vs_baseline": speed,
        "seq": t_big,
    })


def main() -> None:
    _deadline[0] = time.perf_counter() + float(
        os.environ.get("PT_BENCH_BUDGET_S", "1200"))

    import jax

    from paddle_tpu.sysconfig import enable_compile_cache
    enable_compile_cache()

    on_accel = _on_accel_backend()
    log(f"backend={jax.default_backend()} devices={jax.devices()}")

    which = sys.argv[1] if len(sys.argv) > 1 else "bert"

    if which == "verify":
        # standalone correctness run with its own artifact — usable even
        # when there is no time budget for a full bench
        from paddle_tpu.verify import run_verification
        res = run_verification()
        emit({
            "metric": "hardware verification (kernels + 10-step parity)",
            "value": 1.0 if res["ok"] else 0.0,
            "unit": "ok",
            "vs_baseline": 1.0 if res["ok"] else 0.0,
        })
        sys.exit(0 if res["ok"] else 1)

    for stale in (_PARTIAL_PATH, _PARTIAL_CPU_PATH):
        try:
            # a stale best-so-far from a previous run must not be
            # attributable to this one — the stdout lines are per-run,
            # the disk mirror has to be too
            os.unlink(stale)
        except OSError:
            pass

    skip_validate = os.environ.get(
        "PT_BENCH_SKIP_VALIDATE", "").strip().lower() in (
        "1", "true", "yes", "on")
    if on_accel and not skip_validate:
        # a good VERIFY_TPU.json already proves the kernels in compiled
        # mode; revalidating spends chip-minutes on known-good
        # kernels. Trust it only with an EXACT device match (same
        # rule as capture_value: tracked artifacts from another chip
        # mean nothing here) and a matching kernel-source hash (a kernel edit invalidates the verdict).
        # Unstamped pre-r4 artifacts don't skip — one revalidation
        # rewrites a stamped one.
        from paddle_tpu.verify import (default_artifact_path,
                                       kernels_source_hash)
        try:
            with open(default_artifact_path()) as f:
                v = json.load(f)
            if v.get("ok") and v.get("kernels_ok") and \
                    v.get("device") == device_kind() and \
                    v.get("kernel_hash") == kernels_source_hash():
                skip_validate = True
                log(f"skipping kernel validation: VERIFY_TPU.json ok "
                    f"(device={v['device']}, "
                    f"kernel_hash={v['kernel_hash']})")
        except (OSError, json.JSONDecodeError):
            pass
    if on_accel and not skip_validate:
        # capture campaigns set PT_BENCH_SKIP_VALIDATE after the verify
        # stage has already produced VERIFY_TPU.json — revalidating in
        # every timing stage spends chip-minutes on known-good kernels
        log("validating Pallas kernels in compiled mode "
            "(paddle_tpu.verify)...")
        from paddle_tpu.verify import validate_kernels_on_tpu
        validate_kernels_on_tpu()

    if which == "resnet50":
        bench_resnet(on_accel)
    elif which == "flash":
        bench_flash_attention(on_accel)
    elif which == "flash_train":
        bench_flash_train(on_accel)
    elif which == "llm_decode":
        bench_llm_decode(on_accel)
    elif which == "llm_overload":
        bench_llm_overload(on_accel)
    elif which == "llm_tenant_flood":
        bench_llm_tenant_flood(on_accel)
    elif which == "llm_prefix_reuse":
        bench_llm_prefix_reuse(on_accel)
    elif which == "llm_mixed_prefill":
        bench_llm_mixed_prefill(on_accel)
    elif which == "llm_spec_decode":
        bench_llm_spec_decode(on_accel)
    else:
        bench_bert(on_accel)


if __name__ == "__main__":
    main()
