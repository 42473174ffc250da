"""paddle_tpu.verify must work on CPU too — a regression here would
otherwise only surface during a (rare, short) real-chip window."""

import json
import os


def test_train_parity_cpu():
    from paddle_tpu.verify import train_parity_10steps

    res = train_parity_10steps()
    assert res["ok"], res
    assert res["max_rel_err"] < 1e-4
    assert len(res["losses"]) == 10
    assert res["losses"][-1] < res["losses"][0]


def test_kernels_source_hash_stable_and_sensitive(tmp_path,
                                                  monkeypatch):
    from paddle_tpu import verify

    h1 = verify.kernels_source_hash()
    assert h1 == verify.kernels_source_hash()  # deterministic
    assert len(h1) == 16
    # sensitive to kernel-source bytes: hash a copied tree with one
    # byte changed
    import shutil
    kdir = os.path.join(os.path.dirname(verify.__file__), "kernels")
    fake = tmp_path / "kernels"
    shutil.copytree(kdir, fake, ignore=shutil.ignore_patterns(
        "__pycache__"))
    with open(fake / "flash_attention.py", "a") as f:
        f.write("\n# x\n")
    real_dirname = os.path.dirname

    def fake_dirname(p):
        # redirect the module-dir lookup to the tampered copy
        if os.path.abspath(p) == os.path.abspath(verify.__file__):
            return str(tmp_path)
        return real_dirname(p)

    monkeypatch.setattr(os.path, "dirname", fake_dirname)
    h2 = verify.kernels_source_hash()
    monkeypatch.undo()
    assert h2 != h1


def test_run_verification_writes_canonical_artifact(tmp_path):
    from paddle_tpu.verify import default_artifact_path, \
        run_verification

    assert default_artifact_path().endswith("/VERIFY_TPU.json")
    out = str(tmp_path / "v.json")
    res = run_verification(artifact_path=out)
    with open(out) as f:
        d = json.load(f)
    assert d["ok"] == res["ok"]
    assert "kernel_hash" in d and "device" in d
    # the record names the backend the checks really ran on
    assert d["backend"] == "cpu" and d["on_accel"] is False
    assert not d["kernels_ok"]  # Mosaic kernels only verify on a TPU


def test_one_accelerator_platform():
    # "tpu" is the one accelerator platform: every predicate agrees on
    # a CPU-only backend, and asking for the chip there is an error,
    # never a CPU device under a TPU name
    import pytest

    import paddle_tpu as pt
    from paddle_tpu import fluid
    from paddle_tpu.core import place

    assert place.accelerator_available() is False
    assert pt.is_compiled_with_tpu() is False
    assert fluid.is_compiled_with_cuda() is False
    assert fluid.framework.is_compiled_with_cuda() is False
    assert isinstance(pt.get_device(), pt.CPUPlace)
    with pytest.raises(RuntimeError):
        pt.TPUPlace(0).jax_device()
    before = pt.get_device()
    with pytest.raises(RuntimeError):
        pt.set_device("tpu")
    assert pt.get_device() == before  # a refused place is not selected
