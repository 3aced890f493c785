"""A configuration, a traffic mix and a per-layer metric are added as new
files and entries alone: the harness finds each by its name."""
import json
import os
import shutil

from bench import spec

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _files(top):
    out = {}
    for dp, _, fs in os.walk(top):
        for f in fs:
            path = os.path.join(dp, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = fh.read()
    return out


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "repo"
    bench_dir = root / "bench"
    shutil.copytree(HERE, bench_dir,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"), root)
    before = _files(bench_dir)

    conf = json.load(open(bench_dir / "configs" / "phi3-mini-mixed.json"))
    conf["name"] = "new-model"
    (bench_dir / "configs" / "new-model.json").write_text(json.dumps(conf))
    (bench_dir / "traffic" / "new-mix.json").write_text(json.dumps(
        {"generator": "closed_loop", "clients": 2, "pool": 8,
         "shapes_seed": 1, "prompt_len": {"dist": "uniform", "min": 4,
                                          "max": 8},
         "output_len": {"dist": "uniform", "min": 2, "max": 3}}))
    (bench_dir / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0 if run else None\n")
    bench = json.load(open(root / "BENCHMARK.json"))
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": "bench/configs/new-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-cell", "config": "new-model",
                               "traffic": "new-mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "itl_p95_ms",
                               "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    b = spec.benchmark(str(root))
    cell = spec.cell(b, "new-cell")
    assert spec.config(b, cell["config"], str(root))["name"] == "new-model"
    mix = spec.traffic(cell["traffic"], str(bench_dir))
    gen = spec.generator(mix["generator"], str(bench_dir))(mix, 1, 100)
    gen.start(0.0)
    assert len(gen.due(0.0)) == 2
    names = [m["name"] for m in spec.metrics_for(b, "new-cell", "per_layer")]
    assert "new_metric" in names
    assert "batch_occupancy" not in names        # listed for other cells
    assert "decode_tick_ms" in names             # listed for every cell
    assert spec.metric_reader("new_metric", str(bench_dir))(object()) == 42.0
    after = _files(bench_dir)
    assert [p for p in before if after[p] != before[p]] == []  # no edits
