"""Numerical fault tolerance + bitwise-exact resume (ISSUE 5).

Skip-step guard (non-finite grads discarded in-graph), GradScaler
dynamic loss scaling under jit, checkpoint v3 (host_state + PRNG-key
leaves), the divergence watchdog + rollback, and the offset-based
DataLoader resume path. docs/fault_tolerance.md "Numerical faults &
exact resume".
"""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import io as io_mod
from paddle_tpu import observability as obs
from paddle_tpu.amp import GradScaler, all_finite, select_update
from paddle_tpu.static import TrainStep
from paddle_tpu.testing import faults

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.configure(None)
    yield
    faults.configure(None)


@pytest.fixture(autouse=True)
def _restore_metrics_port():
    """Three tests here set ``metrics_port=-1`` (no exporter). Left
    behind, it made ``tests/test_fleet_federation.py::
    test_maybe_start_reporter_from_env`` fail whenever xdist put both
    files on one worker."""
    was = pt.get_flags(["metrics_port"])["metrics_port"]
    yield
    pt.set_flags({"metrics_port": was})


def _data(n=16, poison=False):
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (n, 4)).astype(np.float32)
    if poison:
        x[0, 0] = np.inf
    y = rng.integers(0, 2, (n,)).astype(np.int64)
    return x, y


def _linear_step(scaler=None, amp_dtype=None, seed=0):
    pt.seed(seed)
    net = pt.nn.Linear(4, 2)
    return TrainStep(
        net, pt.optimizer.SGD(learning_rate=0.1),
        lambda o, y: pt.nn.functional.cross_entropy(o, y),
        amp_dtype=amp_dtype, scaler=scaler)


# ---------------------------------------------------------------------------
# amp helpers
# ---------------------------------------------------------------------------

def test_all_finite_ignores_integer_leaves():
    tree = {"w": jnp.ones((2, 2)), "rows": jnp.arange(3),
            "nested": [jnp.zeros(4)]}
    assert bool(all_finite(tree))
    tree["nested"][0] = jnp.asarray([0.0, np.nan, 0.0, 0.0])
    assert not bool(all_finite(tree))
    # ints alone are vacuously finite
    assert bool(all_finite({"i": jnp.arange(5)}))


def test_select_update_keeps_current_on_inf():
    new = {"a": jnp.ones(3), "s": jnp.asarray(5)}
    old = {"a": jnp.zeros(3), "s": jnp.asarray(4)}
    kept = select_update(jnp.asarray(True), new, old)
    np.testing.assert_array_equal(np.asarray(kept["a"]), 0.0)
    assert int(kept["s"]) == 4
    applied = select_update(jnp.asarray(False), new, old)
    np.testing.assert_array_equal(np.asarray(applied["a"]), 1.0)


# ---------------------------------------------------------------------------
# skip-step guard (bare TrainStep, every precision)
# ---------------------------------------------------------------------------

def test_skip_guard_discards_nonfinite_update():
    step = _linear_step()
    x, y = _data()
    step(x, labels=y)
    w1 = np.asarray(step.state["params"]["weight"]).copy()
    opt1 = int(step.state["opt"]["step"])
    xp, yp = _data(poison=True)
    step(xp, labels=yp)    # inf input -> non-finite grads
    np.testing.assert_array_equal(
        np.asarray(step.state["params"]["weight"]), w1)
    # the skipped step must not advance the optimizer step counter
    assert int(step.state["opt"]["step"]) == opt1
    # clean step afterwards trains again
    step(x, labels=y)
    assert np.abs(np.asarray(step.state["params"]["weight"])
                  - w1).sum() > 0
    assert np.isfinite(np.asarray(step.state["params"]["weight"])).all()


def test_skip_guard_counts_nonfinite_steps():
    pt.set_flags({"enable_metrics": True, "metrics_port": -1})
    try:
        step = _linear_step()
        xp, yp = _data(poison=True)
        before = obs.metrics.counter("nonfinite_steps_total",
                                     always=True).value()
        step(xp, labels=yp)
        jax.effects_barrier()   # the count streams via debug.callback
        assert obs.metrics.counter("nonfinite_steps_total",
                                   always=True).value() == before + 1
        kinds = [e["kind"] for e in obs.flight_recorder().events()]
        assert "nonfinite_step" in kinds
    finally:
        pt.set_flags({"enable_metrics": False})


@pytest.mark.parametrize("cache_dir", ["", "persist"])
def test_sharded_step_counts_nonfinite_steps(cache_dir, tmp_path):
    """The sharded step's verdict reaches the counter through a host
    callback, or — with ``FLAGS_compile_cache_dir`` set, where XLA
    persists no executable that holds one — rides the step's outputs
    and is drained on the host, as in TrainStep."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import ShardedTrainStep, create_mesh

    was = pt.get_flags(["compile_cache_dir"])["compile_cache_dir"]
    pt.set_flags({"enable_metrics": True, "metrics_port": -1,
                  "compile_cache_dir":
                      str(tmp_path) if cache_dir else ""})
    try:
        pt.seed(0)
        step = ShardedTrainStep(
            pt.nn.Linear(4, 2), pt.optimizer.SGD(learning_rate=0.1),
            lambda o, y: pt.nn.functional.cross_entropy(o, y),
            create_mesh({"dp": 2, "mp": 2}, allow_submesh=True),
            batch_spec=P("dp"))
        assert step._defer_probes == bool(cache_dir)
        counter = obs.metrics.counter("nonfinite_steps_total",
                                      always=True)
        before = counter.value()
        assert set(step(*_data()[:1], labels=_data()[1])) == {"loss"}
        kept = np.asarray(step.state["params"]["weight"]).copy()
        xp, yp = _data(poison=True)
        assert set(step(xp, labels=yp)) == {"loss"}
        step.flush_signals()
        jax.effects_barrier()
        assert counter.value() == before + 1
        assert np.array_equal(step.state["params"]["weight"], kept)
        lowered = step._jitted.lower(step.state, step._place_batch(
            {"args": (xp,), "labels": (yp,), "kwargs": {}})).as_text()
        assert ("callback" in lowered.lower()) == (not cache_dir)
    finally:
        pt.set_flags({"enable_metrics": False,
                      "compile_cache_dir": was})


def test_skip_guard_opt_out_flag():
    pt.set_flags({"skip_nonfinite_steps": False})
    try:
        step = _linear_step()
        xp, yp = _data(poison=True)
        step(xp, labels=yp)
        # documented opt-out behavior: the poisoned update lands
        assert not np.isfinite(
            np.asarray(step.state["params"]["weight"])).all()
    finally:
        pt.set_flags({"skip_nonfinite_steps": True})


def test_injected_nonfinite_grad_value_fault():
    step = _linear_step()
    x, y = _data()
    faults.configure("nonfinite_grad:at=2")
    step(x, labels=y)
    w1 = np.asarray(step.state["params"]["weight"]).copy()
    step(x, labels=y)      # 2nd call: grads x NaN -> skipped
    np.testing.assert_array_equal(
        np.asarray(step.state["params"]["weight"]), w1)
    c = obs.metrics.counter("faults_injected_total", always=True)
    assert c.value(point="nonfinite_grad") >= 1


# ---------------------------------------------------------------------------
# GradScaler under jit
# ---------------------------------------------------------------------------

def test_scaler_halves_on_nonfinite_and_recovers():
    """Scale backs off after decr_every_n_nan_or_inf bad steps and
    recovers after incr_every_n_steps (growth interval) good ones —
    all compiled into the jitted step."""
    sc = GradScaler(init_loss_scaling=1024.0, incr_ratio=2.0,
                    decr_ratio=0.5, incr_every_n_steps=3,
                    decr_every_n_nan_or_inf=2)
    step = _linear_step(scaler=sc, amp_dtype="float16")
    assert "scaler" in step.state
    x, y = _data()
    xp, yp = _data(poison=True)

    w0 = np.asarray(step.state["params"]["weight"]).copy()
    step(xp, labels=yp)
    np.testing.assert_array_equal(
        np.asarray(step.state["params"]["weight"]), w0)  # skipped
    assert float(step.state["scaler"]["scale"]) == 1024.0  # 1 bad < 2
    step(xp, labels=yp)
    assert float(step.state["scaler"]["scale"]) == 512.0   # halved
    assert int(step.state["scaler"]["bad_steps"]) == 0     # reset

    # growth interval: 3 clean steps double the scale back
    for _ in range(3):
        m = step(x, labels=y)
        assert np.isfinite(float(m["loss"]))
    assert float(step.state["scaler"]["scale"]) == 1024.0
    assert int(step.state["scaler"]["good_steps"]) == 0
    assert np.isfinite(np.asarray(step.state["params"]["weight"])).all()


def test_scaler_state_checkpoints_with_fit(tmp_path):
    d = str(tmp_path / "ck")
    rng = np.random.default_rng(0)
    batches = [(rng.normal(size=(8, 4)).astype(np.float32),
                rng.integers(0, 2, (8,)).astype(np.int64))
               for _ in range(4)]
    pt.seed(0)
    net = pt.nn.Linear(4, 2)
    model = pt.hapi.Model(
        net, loss=lambda o, y: pt.nn.functional.cross_entropy(o, y),
        optimizer=pt.optimizer.SGD(learning_rate=0.1))
    model.fit(batches, epochs=1, verbose=0, ckpt_dir=d, save_steps=2,
              amp="float16")
    ck = io_mod.AsyncCheckpointer(d)
    s = ck.latest_step()
    flat = io_mod.load(os.path.join(d, f"ckpt-{s}"))
    assert "scaler/scale" in flat and "rng" in flat
    host = ck.host_state()
    assert host["global_step"] == s
    # restore into a fresh step: scaler + rng leaves land
    target = pt.hapi._ckpt_state_of(model._train_step)
    restored = io_mod.load(os.path.join(d, f"ckpt-{s}"), target)
    assert float(restored["scaler"]["scale"]) == \
        float(flat["scaler/scale"])


class _MaskedMLP(pt.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = pt.nn.Linear(8, 2)

    def forward(self, x, mask=None):
        h = self.fc(x)
        return h * mask if mask is not None else h


def test_scaler_composes_with_sharded_step_kwargs_routing():
    """fp16 scaler + skip guard inside ShardedTrainStep over the
    8-device CPU mesh, with a per-sample kwarg riding the batch-leaf
    routing (the DGC-style tree-structured contract)."""
    from paddle_tpu.parallel import ShardedTrainStep, create_mesh
    mesh = create_mesh({"dp": jax.device_count()})
    pt.seed(3)
    sc = GradScaler(init_loss_scaling=256.0, decr_every_n_nan_or_inf=1)
    step = ShardedTrainStep(
        _MaskedMLP(), pt.optimizer.SGD(learning_rate=0.1),
        lambda o, t: pt.nn.functional.cross_entropy(o, t), mesh,
        amp_dtype="float16", scaler=sc)
    assert "scaler" in step.state
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (16, 8)).astype(np.float32)
    y = rng.integers(0, 2, (16,)).astype(np.int64)
    mask = np.ones((16, 2), np.float32)
    m = step(x, labels=y, mask=mask)
    assert np.isfinite(float(m["loss"]))
    w1 = np.asarray(step.state["params"]["fc.weight"]).copy()
    xp = x.copy()
    xp[0, 0] = np.inf
    step(xp, labels=y, mask=mask)   # skipped + scale backs off
    np.testing.assert_array_equal(
        np.asarray(step.state["params"]["fc.weight"]), w1)
    assert float(step.state["scaler"]["scale"]) == 128.0
    m = step(x, labels=y, mask=mask)  # recovers
    assert np.isfinite(float(m["loss"]))
    assert np.abs(np.asarray(step.state["params"]["fc.weight"])
                  - w1).sum() > 0


# ---------------------------------------------------------------------------
# fault-spec grammar additions
# ---------------------------------------------------------------------------

def test_value_fault_spec_mul_round_trip():
    specs = faults.parse_spec(
        "nonfinite_grad:at=4,loss_spike:at=5:mul=1e8,loss_spike:mul=nan")
    assert specs[1].mul == 1e8
    assert np.isnan(specs[2].mul)
    text = faults.format_spec(specs)
    assert "mul=1e+08" in text and "mul=nan" in text
    assert faults.parse_spec(text)[1].mul == 1e8


def test_consecutive_at_entries_fire_consecutively():
    """p:at=1,p:at=2 must fire on calls 1 AND 2 — every armed entry's
    counter advances every call, even after an earlier entry fired
    (the shape a divergence-streak drill relies on)."""
    faults.configure("vp_test:at=1:mul=2,vp_test:at=2:mul=4")
    assert faults.value_mult("vp_test") == 2.0
    assert faults.value_mult("vp_test") == 4.0
    assert faults.value_mult("vp_test") == 1.0   # nothing armed fires


def test_value_points_armed_gate():
    assert not faults.value_points_armed()
    faults.configure("ckpt_write:at=99")
    assert not faults.value_points_armed()   # action point only
    faults.configure("loss_spike:at=99")
    assert faults.value_points_armed()


# ---------------------------------------------------------------------------
# checkpoint v3: host_state + PRNG-key leaves
# ---------------------------------------------------------------------------

def test_v3_prng_key_leaf_round_trip(tmp_path):
    key = jax.random.key(42)
    path = str(tmp_path / "ck")
    io_mod.save({"rng": key, "w": np.ones(3)}, path, step=1,
                host_state={"global_step": 1})
    flat = io_mod.load(path)
    assert jnp.issubdtype(flat["rng"].dtype, jax.dtypes.prng_key)
    assert float(jax.random.uniform(flat["rng"])) == \
        float(jax.random.uniform(key))
    assert io_mod.load_host_state(path) == {"global_step": 1}
    assert io_mod.verify(path) == []


def test_v2_checkpoint_without_rng_still_resumes(tmp_path):
    """A pre-v3 checkpoint (no rng/scaler leaves, no host_state) must
    restore into a v3 target — missing leaves keep the target's fresh
    values (the old approximate-resume behavior)."""
    path = str(tmp_path / "old")
    io_mod.save({"params": {"w": np.full(3, 7.0)}}, path, step=5)
    fresh_key = jax.random.key(0)
    target = {"params": {"w": np.zeros(3)}, "rng": fresh_key}
    out = io_mod.load(path, target)
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]), 7.0)
    assert float(jax.random.uniform(out["rng"])) == \
        float(jax.random.uniform(fresh_key))
    assert io_mod.load_host_state(path) is None


# ---------------------------------------------------------------------------
# DataLoader offset resume
# ---------------------------------------------------------------------------

def test_dataloader_iter_from_matches_full_iteration():
    x = np.arange(40, dtype=np.float32).reshape(20, 2)
    y = np.arange(20, dtype=np.int64)
    loader = pt.data.DataLoader(pt.data.TensorDataset(x, y),
                                batch_size=4)
    full = list(loader)
    from2 = list(loader.iter_from(2))
    assert len(full) == 5 and len(from2) == 3
    for (fx, fy), (sx, sy) in zip(full[2:], from2):
        np.testing.assert_array_equal(fx, sx)
        np.testing.assert_array_equal(fy, sy)
    assert list(loader.iter_from(0))[0][0].tobytes() == \
        full[0][0].tobytes()
    assert list(loader.iter_from(5)) == []


def test_fit_bitwise_resume_with_dropout_and_amp(tmp_path):
    """In-process version of tools/replay_check.py: interrupted +
    resumed == uninterrupted, bitwise, with the RNG stream and scaler
    state doing real work (Dropout + fp16)."""
    def make_model():
        pt.seed(0)
        net = pt.nn.Sequential(pt.nn.Linear(4, 8), pt.nn.Dropout(0.5),
                               pt.nn.Linear(8, 2))
        return net, pt.hapi.Model(
            net, loss=lambda o, y: pt.nn.functional.cross_entropy(o, y),
            optimizer=pt.optimizer.SGD(learning_rate=0.1))

    rng = np.random.default_rng(0)
    batches = [(rng.normal(size=(8, 4)).astype(np.float32),
                rng.integers(0, 2, (8,)).astype(np.int64))
               for _ in range(8)]
    net1, m1 = make_model()
    m1.fit(batches, epochs=2, verbose=0, amp="float16")
    want = {k: np.asarray(v) for k, v in net1.state_dict().items()}

    d = str(tmp_path / "ck")
    _, m2 = make_model()
    m2.fit(batches[:5], epochs=1, verbose=0, ckpt_dir=d, save_steps=1,
           amp="float16")   # dies after 5 of 16 steps
    net3, m3 = make_model()
    m3.fit(batches, epochs=2, verbose=0, ckpt_dir=d, save_steps=1,
           amp="float16")
    got = {k: np.asarray(v) for k, v in net3.state_dict().items()}
    for k in want:
        assert want[k].tobytes() == got[k].tobytes(), \
            f"{k} not bitwise-identical after resume"


# ---------------------------------------------------------------------------
# divergence watchdog + rollback
# ---------------------------------------------------------------------------

def test_divergence_watchdog_streak_semantics():
    from paddle_tpu.observability.anomaly import DivergenceWatchdog
    wd = DivergenceWatchdog(streak=2)
    wd.sample("loss", float("nan"), "nan")
    assert not wd.tripped()
    wd.sample("loss", 1.0, None)          # clean sample resets
    wd.sample("loss", float("nan"), "nan")
    assert not wd.tripped()
    wd.sample("loss", 99.0, "spike")
    assert wd.tripped()
    wd.reset()
    assert not wd.tripped()
    wd.sample("grad_norm", float("nan"), "nan")  # unwatched series
    wd.sample("grad_norm", float("nan"), "nan")
    assert not wd.tripped()


def _rollback_fit(tmp_path, spec, batches=10):
    rng = np.random.default_rng(0)
    data = [(rng.normal(size=(8, 4)).astype(np.float32),
             rng.integers(0, 2, (8,)).astype(np.int64))
            for _ in range(batches)]
    pt.seed(0)
    net = pt.nn.Linear(4, 2)
    model = pt.hapi.Model(
        net, loss=lambda o, y: pt.nn.functional.cross_entropy(o, y),
        optimizer=pt.optimizer.SGD(learning_rate=0.1))
    faults.configure(spec)
    try:
        return model.fit(data, epochs=1, verbose=0,
                         ckpt_dir=str(tmp_path / "ck"), save_steps=1), net
    finally:
        faults.configure(None)


def test_divergence_rollback_recovers(tmp_path):
    pt.set_flags({"enable_metrics": True, "metrics_port": -1,
                  "divergence_streak": 3, "rollback_budget": 2})
    try:
        before = obs.metrics.counter("rollbacks_total",
                                     always=True).value()
        _, net = _rollback_fit(
            tmp_path, "loss_spike:at=4:mul=nan,loss_spike:at=5:mul=nan,"
                      "loss_spike:at=6:mul=nan")
        assert obs.metrics.counter("rollbacks_total",
                                   always=True).value() == before + 1
        kinds = [e["kind"] for e in obs.flight_recorder().events()]
        assert "fit_rollback" in kinds and "fit_rollback_resume" in kinds
        assert all(np.isfinite(np.asarray(v)).all()
                   for v in net.state_dict().values())
    finally:
        pt.set_flags({"enable_metrics": False, "divergence_streak": 5,
                      "rollback_budget": 2})


def test_divergence_rollback_budget_exhausts(tmp_path):
    pt.set_flags({"enable_metrics": True, "metrics_port": -1,
                  "divergence_streak": 3, "rollback_budget": 1})
    try:
        relentless = ",".join(f"loss_spike:at={i}:mul=nan"
                              for i in range(1, 60))
        with pytest.raises(FloatingPointError,
                           match="rollback_budget"):
            _rollback_fit(tmp_path, relentless)
    finally:
        pt.set_flags({"enable_metrics": False, "divergence_streak": 5,
                      "rollback_budget": 2})


def test_rollback_disabled_without_metrics(tmp_path):
    """With metrics off there are no loss probes: fit must complete
    (skip guard alone) and never roll back."""
    before = obs.metrics.counter("rollbacks_total", always=True).value()
    _rollback_fit(tmp_path,
                  "nonfinite_grad:at=4,nonfinite_grad:at=5")
    assert obs.metrics.counter("rollbacks_total",
                               always=True).value() == before


# ---------------------------------------------------------------------------
# replay check (tier-1 wiring, ISSUE acceptance)
# ---------------------------------------------------------------------------

def test_replay_check_self_test_subprocess():
    """SIGKILL-mid-epoch + v3 resume must produce final weights
    bitwise-identical to an uninterrupted control run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for var in ("FLAGS_fault_spec", "FLAGS_enable_metrics",
                "FLAGS_trace_dir"):
        env.pop(var, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "replay_check.py"),
         "--self-test"],
        capture_output=True, text=True, env=env, timeout=540, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "bitwise-equal" in proc.stdout
