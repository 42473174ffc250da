"""End to end, without a chip: the one command in rehearsal mode, each
runner kind twice in a row as fresh processes in ONE copy of the
checkout (once traced); the drill's stop-and-keep-output on a child
that fails; and the proof that a configuration, a traffic mix, a runner
and a per-layer metric are each added as new files plus entries, with
no edit of a file that is there."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import manifest as mf

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
KINDS = {}
for _w in mf.Manifest().doc["workloads"]:
    KINDS.setdefault(mf.Manifest().traffic(_w["traffic"])["kind"],
                     _w["name"])


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """What git would commit of the benchmark, elsewhere; the package
    under test is found through PYTHONPATH."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(mf.ROOT, "benchmarks"),
                    root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    return root


def env_for(extra_path: str = "") -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (extra_path, mf.ROOT, env.get("PYTHONPATH", "")) if p)
    env.pop("JAX_PLATFORMS", None)   # --rehearsal pins the CPU itself
    return env


def bench(root, *args, timeout=300):
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", *args], cwd=root,
        env=env_for(), text=True, capture_output=True, timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_twice_in_a_row_in_one_checkout(checkout, kind):
    cell = KINDS[kind]
    before = set(os.listdir(checkout))
    for trace, seed in ((0, 2 ** 31 + 7), (1, 2 ** 40 + 1)):
        proc, lines = bench(checkout, "--workload", cell, "--seed",
                            str(seed), "--seconds", "1", "--trace",
                            str(trace), "--rehearsal")
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
        doc = json.loads(lines[-1])
        want = CONTRACT_KEYS          # a rehearsal gives no breakdown
        assert set(doc) == want
        assert doc["correct"] is True and doc["failed"] == 0
        assert doc["attempted"] > 0
        assert doc["device"]["platform"] == "cpu"
        names = {m["name"] for m in mf.Manifest().metrics_of(
            cell, "per_layer" if trace else "end_to_end")}
        assert set(doc["metrics"]) <= names and doc["metrics"]
        # no CPU number under a metric's name
        assert all(m["value"] is None for m in doc["metrics"].values())
        if trace:
            assert set(doc["device"]) >= {"busy_s", "window_s"}
            assert any("trace " in ln and " bytes" in ln for ln in lines)
        else:
            assert set(doc["metrics"]) == names
    # the run leaves nothing in the checkout (its cache is the
    # package's, its scratch under TMPDIR and removed)
    assert set(os.listdir(checkout)) == before


def test_without_a_tpu_the_command_fails_and_prints_no_result(checkout):
    cell = sorted(KINDS.values())[0]
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=checkout, env=dict(env_for(), JAX_PLATFORMS="cpu"),
        text=True, capture_output=True, timeout=300)
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
    assert "no TPU" in proc.stderr


def test_with_only_the_benchmarks_files_the_command_fails(checkout):
    env = dict(os.environ, PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         sorted(KINDS.values())[0], "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearsal"],
        cwd=checkout, env=env, text=True, capture_output=True,
        timeout=300)
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_a_failed_check_names_itself_before_the_last_line(checkout,
                                                          tmp_path):
    root = tmp_path / "broken"
    shutil.copytree(checkout, root)
    cfg = root / "benchmarks" / "configs" / "bert_base.rehearsal.json"
    doc = json.loads(cfg.read_text())
    doc["tolerances"]["loss_abs"] = -1.0      # nothing can meet it
    cfg.write_text(json.dumps(doc))
    proc, lines = bench(root, "--workload", "bert_base_s512", "--seed",
                        "3", "--seconds", "1", "--trace", "0",
                        "--rehearsal")
    assert proc.returncode != 0
    last = json.loads(lines[-1])
    assert set(last) == CONTRACT_KEYS and last["correct"] is False
    assert lines[-2].startswith("[bench] FAILED CheckFailed: parity loss")
    assert "Traceback" in proc.stderr


DUMMY_RUNNER = '''
def run(run):
    run.window_starts()
    run.check(run.config["model"]["width"] == 8, "dummy config was read")
    return {"attempted": run.mix["n"], "failed": 0,
            "end_to_end": {"dummy_rate": 1.0},
            "counters": {"dummy_count": 41}, "trace_summary":
            {"busy_s": 1.0, "window_s": 2.0}}
'''
DUMMY_READER = '''
def plus(observed, counter, add):
    return observed["counters"][counter] + add
'''


def test_adding_needs_new_files_and_entries_only(checkout, tmp_path):
    root = tmp_path / "grown"
    shutil.copytree(checkout, root)
    b = root / "benchmarks"
    # new files only
    (b / "configs" / "dummy.json").write_text(
        json.dumps({"model": {"width": 8}, "reduced": []}))
    (b / "configs" / "dummy.rehearsal.json").write_text(
        json.dumps({"model": {"width": 8}}))
    (b / "traffic" / "dummy_mix.json").write_text(
        json.dumps({"kind": "dummy_kind", "n": 7, "rehearsal": {}}))
    (b / "runners" / "dummy_kind.py").write_text(DUMMY_RUNNER)
    (b / "readers" / "dummy_reader.py").write_text(DUMMY_READER)
    (b / "metrics" / "dummy.count.json").write_text(json.dumps({
        "unit": "count", "better": "higher", "source": "program_counter",
        "layer": "dummy", "moves": "dummy_rate",
        "reader": "benchmarks.readers.dummy_reader.plus",
        "args": {"counter": "dummy_count", "add": 1}}))
    # new entries only
    doc = json.loads((root / "BENCHMARK.json").read_text())
    old = json.dumps(doc, sort_keys=True)
    doc["configs"].append({"name": "dummy", "source": "none",
                           "file": "benchmarks/configs/dummy.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "dummy_cell", "config": "dummy",
                             "traffic": "dummy_mix", "chips": 1,
                             "why": "test"})
    doc["end_to_end"].append({"name": "dummy_rate", "unit": "1/s",
                              "better": "higher", "bound": 0.01,
                              "source": "host_clock",
                              "workloads": ["dummy_cell"]})
    doc["per_layer"].append({"name": "dummy.count", "unit": "count",
                             "better": "higher", "layer": "dummy",
                             "source": "program_counter",
                             "moves": "dummy_rate",
                             "workloads": ["dummy_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    for trace in (0, 1):
        proc, lines = bench(root, "--workload", "dummy_cell", "--seed",
                            "1", "--seconds", "1", "--trace", str(trace),
                            "--rehearsal")
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        out = json.loads(lines[-1])
        assert out["correct"] and out["attempted"] == 7
        assert set(out["metrics"]) == (
            {"dummy.count"} if trace else {"dummy_rate", "setup_s"})
        if trace:
            assert "readers gave a value for: dummy.count" in proc.stdout
    # every file that was there is byte for byte what it was
    for base, _, files in os.walk(checkout / "benchmarks"):
        for f in files:
            src = os.path.join(base, f)
            rel = os.path.relpath(src, checkout)
            with open(src, "rb") as x, open(root / rel, "rb") as y:
                assert x.read() == y.read(), rel
    kept = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        kept[key] = kept[key][:-1]
    assert json.dumps(kept, sort_keys=True) == old


FAILING_CHILD = '''
import sys
seed = int(sys.argv[sys.argv.index("--seed") + 1])
print("some log line of the child")
if seed == 3000000019:
    print("the check that failed and its numbers")
    print('{"correct": false, "attempted": 1, "failed": 1, '
          '"metrics": {}, "device": {"platform": "cpu"}}')
    sys.exit(1)
print('{"correct": true, "attempted": 1, "failed": 0, "metrics": '
      '{"setup_s": {"value": 1.5, "unit": "s"}, "rate": {"value": 10.0, '
      '"unit": "1/s"}}, "device": {"platform": "cpu"}}')
'''


def test_drill_stops_at_the_first_failure_and_keeps_the_output(tmp_path):
    root = tmp_path / "drilled"
    os.makedirs(root / "benchmarks")
    for name in ("drill.py", "arithmetic.py", "__init__.py"):
        shutil.copy(os.path.join(mf.ROOT, "benchmarks", name),
                    root / "benchmarks")
    (root / "child.py").write_text(FAILING_CHILD)
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": [sys.executable, "child.py"], "run_seconds": 1,
        "end_to_end": [{"name": "rate", "bound": 0.05},
                       {"name": "setup_s", "bound": 0.1}]}))
    out = root / "out"
    proc = subprocess.run(
        [sys.executable, "benchmarks/drill.py", "--workload", "any",
         "--out", str(out), "--runs", "3"], cwd=root, text=True,
        capture_output=True, timeout=120)
    assert proc.returncode == 1
    assert "set1.run0" in proc.stdout and "set1.run1" in proc.stdout
    assert "set1.run2" not in proc.stdout, "it stopped at the failure"
    assert "FAILED (2 of 8 runs made)" in proc.stdout
    kept = (out / "set1.run1.FAILED.txt").read_text()
    assert "the check that failed and its numbers" in kept
    assert "exit code 1" in kept
    report = json.loads((out / "drill.json").read_text())
    assert report["passed"] is False and len(report["runs"]) == 2
    assert "import jax" not in open(
        os.path.join(mf.ROOT, "benchmarks", "drill.py")).read()


def drill(root, *args, timeout=600):
    return subprocess.run(
        [sys.executable, "benchmarks/drill.py", *args], cwd=root,
        env=env_for(), text=True, capture_output=True, timeout=timeout)


def test_sweep_takes_every_seed_through_the_checks_in_one_process(
        checkout, tmp_path):
    out = tmp_path / "swept"
    proc = drill(checkout, "--workload", "bert_base_s512", "--sweep", "2",
                 "--seconds", "1", "--out", str(out), "--", "--rehearsal")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    report = json.loads((out / "sweep.json").read_text())
    assert report["seeds"] == 2 and not report["seeds_with_failures"]
    seeds = [r["seed"] for r in report["rows"]]
    assert seeds[0] > 2 ** 31 and seeds[1] - seeds[0] > 2 ** 31
    for key in ("parity_loss_abs", "parity_grad_rel"):
        assert report["margins"][key]["min"] <= report["margins"][key]["max"]
    assert not os.path.exists(out / "drill.json"), "a sweep makes no drill"


def test_sweep_goes_on_past_a_failed_check_and_reports_it(checkout,
                                                          tmp_path):
    root = tmp_path / "broken"
    shutil.copytree(checkout, root)
    cfg = root / "benchmarks" / "configs" / "bert_base.rehearsal.json"
    doc = json.loads(cfg.read_text())
    doc["tolerances"]["grad_rel_l2"] = -1.0      # nothing can meet it
    cfg.write_text(json.dumps(doc))
    proc = drill(root, "--workload", "bert_base_s512", "--sweep", "2",
                 "--seconds", "1", "--out", str(tmp_path / "o"), "--",
                 "--rehearsal")
    assert proc.returncode == 1
    report = json.loads((tmp_path / "o" / "sweep.json").read_text())
    assert len(report["seeds_with_failures"]) == 2, "it did not stop"
    assert all("parity gradients" in r["failures"][0]
               for r in report["rows"])
