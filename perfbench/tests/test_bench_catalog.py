"""Discovery by name, and the benchmark's files against its contract."""

import json
import re

from perfbench import catalog

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_new_config_traffic_limits_and_metric_are_found_by_name(tmp_path):
    bench = catalog.benchmark()
    for sub in ("configs", "traffic", "limits", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "tiny.json").write_text(
        json.dumps({"hidden_size": 8}))
    (tmp_path / "traffic" / "burst.json").write_text(
        json.dumps({"name": "burst", "batch": 2}))
    (tmp_path / "limits" / "tiny.burst.json").write_text(
        json.dumps({"loss_gap": 1.0}))
    (tmp_path / "metrics" / "new_share.py").write_text(
        "def read(record):\n    return record.get('x')\n")
    new = dict(bench)
    new["configs"] = bench["configs"] + [
        {"name": "tiny", "file": "configs/tiny.json"}]
    new["workloads"] = bench["workloads"] + [
        {"name": "tiny.burst", "config": "tiny", "traffic": "burst",
         "chips": 1}]
    new["per_layer"] = bench["per_layer"] + [
        {"name": "new_share", "workloads": ["tiny.burst"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    got = catalog.benchmark(tmp_path)
    assert catalog.workload(got, "tiny.burst")["traffic"] == "burst"
    assert catalog.config(got, "tiny", tmp_path) == {"hidden_size": 8}
    assert catalog.traffic("burst", tmp_path)["batch"] == 2
    assert catalog.limits("tiny.burst", tmp_path) == {"loss_gap": 1.0}
    assert catalog.metric_reader("new_share", tmp_path)({"x": 3}) == 3
    names = [m["name"] for m in catalog.metrics_for(got, "tiny.burst",
                                                    "per_layer")]
    assert "new_share" in names and "mfu" not in names


def test_benchmark_files_exist_and_names_are_well_formed():
    bench = catalog.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        conf = catalog.config(bench, c["name"])
        assert conf["source"] == c["source"]
        assert sorted(conf["published"]) == sorted(c["reduced"])
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert catalog.traffic(w["traffic"])["name"] == w["traffic"]
        limits = catalog.limits(w["name"])
        assert set(limits) == {"loss_gap", "grad_gap", "change_gap"}
        for kind in ("end_to_end", "per_layer"):
            assert catalog.metrics_for(bench, w["name"], kind)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert {"setup_s", "train_tokens_per_s", "plan_s"} == e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert callable(catalog.metric_reader(m["name"]))
        layers.setdefault(m["layer"], []).append(m["name"])
    assert set(layers) == {"analysis", "search", "apply", "model step",
                           "kernels", "device"}
