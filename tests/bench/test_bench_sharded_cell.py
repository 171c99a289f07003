"""A set of four slaves on four chips, added as data alone (a configuration
file, a traffic file and a ``workloads`` entry), runs and is judged by the
per-slave reference with no edit to a file the benchmark already has: its
answers agree, its control differs, and a run whose timed path is broken
underneath (an answer altered, the master's merge exchange left out) is not
correct.

The run needs four devices, which JAX fixes when it starts, so it runs in a
child process on four virtual CPU devices: this file, run as a script on the
copied checkout.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parents[2] / "src"
CELL = "tiny-set4-mix"
SEED = 2**31 + 41


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _add_cell(root, bench_dir):
    """Copy the benchmark into ``root`` and add the set's cell as data."""
    shutil.copy(bench_dir.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(bench_dir, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root)
    cfg = json.loads((bench_dir / "configs" / "odys-slave.json").read_text())
    # 1,500 documents a slave: the hot keywords' lists pass the window on
    # every slave, and the mix's k of 1000 fits in it
    cfg.update(name="tiny-set4", n_docs=6000, vocab_size=400, n_sites=8)
    cfg["service"].update(ns=4, window=1024)
    (root / "bench" / "configs" / "tiny-set4.json").write_text(json.dumps(cfg))
    mix = json.loads((bench_dir / "traffic" / "mix-open.json").read_text())
    mix["rate_qps"] = 40
    (root / "bench" / "traffic" / "tiny-set-mix.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-set4", "source": "https://arxiv.org/abs/1208.4270",
                             "file": "bench/configs/tiny-set4.json", "reduced": ["n_docs"],
                             "why": "a test's cell"})
    bench["workloads"].append({"name": CELL, "config": "tiny-set4",
                               "traffic": "tiny-set-mix", "chips": 4, "why": "a test's cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return before


def test_sharded_cell_from_data_only(tmp_path):
    import bench

    before = _add_cell(tmp_path, Path(bench.__file__).resolve().parent)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, __file__, str(tmp_path)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    run = out["run"]
    assert run["correct"], run["checks"]
    assert run["checks"]["answers_differing"]["value"] == 0
    assert run["attempted"] > 20
    assert run["device"]["count"] == 4 and out["devices"] == 4
    assert out["control"]["differ_from_reference"] == 0
    assert out["control"]["differ_from_control"] > 0
    for fault in ("altered_answer", "no_exchange"):
        assert not out[fault]["correct"], fault
        assert out[fault]["checks"]["answers_differing"]["value"] > 0, fault
    after = _digests(tmp_path)
    assert {p: d for p, d in after.items() if p in before} == before


def altered_answer(svc):
    """The first answer of every batch gets a wrong last docID."""
    inner = svc.scheduler.executor

    def executor(queries, t_max, k, set_id):
        import dataclasses

        out = inner(queries, t_max, k, set_id)
        hit = out[0]
        out[0] = dataclasses.replace(hit, docids=hit.docids[:-1] + [hit.docids[-1] + 1]
                                     if hit.docids else [0])
        return out

    svc.scheduler.executor = executor


def no_exchange(svc):
    """The master's merge left out: no candidates cross the chips, so the
    answer is the first slave's own top k."""
    import jax

    from repro.core import parallel

    parallel.tournament_merge = lambda cands, axis, ns, **kw: cands
    jax.clear_caches()


def child(root: Path) -> None:
    sys.path[:0] = [str(root), str(SRC)]
    import jax

    from bench import control, harness, spec

    cell = spec.load_cell(CELL)
    out = {"devices": jax.device_count()}
    out["run"] = harness.run(cell, seed=SEED, seconds=1.0, trace=False,
                             t_process=time.perf_counter())
    out["control"] = control.control(cell, SEED + 1, 1.0)
    for fault in (altered_answer, no_exchange):
        out[fault.__name__] = harness.run(cell, seed=SEED + 2, seconds=1.0, trace=False,
                                          t_process=time.perf_counter(),
                                          service_hook=fault)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    child(Path(sys.argv[1]))
