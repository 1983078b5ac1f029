"""The harness finds configurations, mixes and metrics by file name, and
every name in BENCHMARK.json resolves."""
import json

import pytest

from harness import spec


def test_discovers_items_by_file_name(tmp_path):
    for d in ("configs", "traffic", "metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "cfg_x.json").write_text(json.dumps({"scheme": "s"}))
    (tmp_path / "traffic" / "mix_y.json").write_text(json.dumps({"loop": "open"}))
    (tmp_path / "metrics" / "layer.metric_z.py").write_text(
        "def read(obs):\n    return obs['v'] * 2\n")
    assert spec.load_config("cfg_x", str(tmp_path)) == {"scheme": "s"}
    assert spec.load_traffic("mix_y", str(tmp_path)) == {"loop": "open"}
    assert spec.metric_reader("layer.metric_z", str(tmp_path))({"v": 4}) == 8
    with pytest.raises(spec.SpecError):
        spec.load_config("absent", str(tmp_path))
    with pytest.raises(spec.SpecError):
        spec.metric_reader("absent", str(tmp_path))


def test_every_name_in_the_benchmark_resolves():
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        assert spec.load_config(cell["config"])["scheme"] in ("ckks", "mlkem")
        assert spec.load_traffic(cell["traffic"])["loop"] in (
            "open", "backlog", "closed")
        assert spec.cell_metrics(bench, cell["name"], trace=False)
        assert spec.cell_metrics(bench, cell["name"], trace=True)
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
        assert all(spec.find_workload(bench, w) for w in m["workloads"])
    assert "TPU v5 lite" in spec.load_peaks()
