"""A cell added as data alone (a configuration file, a traffic file and a
``workloads`` entry) is found by name and runs, with no edit to a file the
benchmark already has."""
import hashlib
import json
import shutil
import time

from bench import harness, spec


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_cell_from_data_only(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)

    cfg = json.loads((spec.BENCH / "configs" / "odys-slave.json").read_text())
    cfg.update(name="tiny-slave", n_docs=2000, vocab_size=300, n_sites=6)
    cfg["service"]["window"] = 1024
    (tmp_path / "bench" / "configs" / "tiny-slave.json").write_text(json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "short-and.json").write_text(json.dumps({
        "loop": "open", "rate_qps": 30, "zipf_s": 1.1, "max_terms": 2,
        "mix": {"multiple/10": 0.7, "limited/50": 0.3}}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-slave", "source": "https://arxiv.org/abs/1208.4270",
                             "file": "bench/configs/tiny-slave.json", "reduced": ["n_docs"],
                             "why": "a test's cell"})
    bench["workloads"].append({"name": "tiny-and", "config": "tiny-slave",
                               "traffic": "short-and", "chips": 1, "why": "a test's cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("tiny-and", root=tmp_path)
    assert cell.config["n_docs"] == 2000 and cell.traffic["rate_qps"] == 30
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    out = harness.run(cell, seed=2**31 + 1, seconds=1.0, trace=False,
                      t_process=time.perf_counter())
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    after = _digests(tmp_path)
    assert {p: d for p, d in after.items() if p in before} == before
