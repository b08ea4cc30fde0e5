"""BENCHMARK.json keeps to its contract, every name it gives resolves to
a file, and a new cell, traffic mix or metric needs only new files and
entries."""
import json
import re
import shutil

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.benchmark()


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (spec.ROOT / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its 43,200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_names_units_and_entry_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["traffic"]]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names), names
    assert len({c["name"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_config_holds_to_its_source_but_for_what_is_reduced():
    """Each configuration file differs from its source's values only in
    the keys ``reduced`` lists, and the program runs what the file says:
    the vocabulary padded as the source pads it, the head tied or not."""
    for c in BENCH["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg["source_values"]) >= set(c["reduced"])
        for k, v in cfg["source_values"].items():
            assert (cfg[k] != v) == (k in c["reduced"]), k
        p, m = cfg["program"], cfg["pad_vocab_size_multiple"]
        assert p["vocab"] == -(-cfg["vocab_size"] // m) * m
        assert p["tie_embeddings"] == cfg["tie_embeddings"]
        assert (p["n_layers"], p["d_model"]) == (cfg["n_layer"], cfg["d_model"])


def test_four_chip_share():
    n = len(BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, n // 2)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_and_reports_enough(workload):
    cell = spec.cell(workload)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], e2e)
        assert callable(spec.reader(m["name"]).read)
    assert spec.driver(cell.driver).Driver
    assert set(cell.limits["limits"])
    assert cell.config["program"]["name"]


def test_every_config_is_used_and_every_metric_is_reported():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    cells = [spec.cell(w["name"]) for w in BENCH["workloads"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert any(m["name"] in {x["name"] for x in c.end_to_end + c.per_layer}
                   for c in cells), m["name"]


def test_peaks_table_has_the_v5e_and_refuses_others():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_new_cell_traffic_and_metric_need_only_new_files(tmp_path):
    """Copy the benchmark, add a configuration, a traffic mix, their
    cell, its limits and a per-layer metric as new files and entries in
    BENCHMARK.json, change no file under bench/: the harness finds them
    by name."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.ROOT / "bench", root / "bench")
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}

    (root / "bench" / "traffic" / "grads-dp4-int8.json").write_text(
        json.dumps({"driver": "reduce", "mesh": "4x1", "sample_calls": 4,
                    "flare": {"compression": "int8"}}))
    (root / "bench" / "limits" / "grads-dp4-int8.mamba2-other.json").write_text(
        json.dumps({"limits": {"reduce_rel_err": 0.05}}))
    (root / "bench" / "metrics" / "reduce.calls_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx['counts']['calls'])\n")
    config = json.loads(
        (root / "bench" / "configs" / "mamba2-370m.json").read_text())
    config["program"] = dict(config["program"], name="mamba2-other")
    (root / "bench" / "configs" / "mamba2-other.json").write_text(
        json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "mamba2-other", "source": "https://example.org/other",
         "file": "bench/configs/mamba2-other.json", "reduced": [],
         "why": "another configuration"})
    bench["workloads"].append(
        {"name": "grads-dp4-int8.mamba2-other", "config": "mamba2-other",
         "traffic": "grads-dp4-int8", "chips": 4, "why": "int8 transport"})
    for m in bench["end_to_end"]:
        if "reduce" in m["name"]:
            m["workloads"].append("grads-dp4-int8.mamba2-other")
    bench["per_layer"].append(
        {"name": "reduce.calls_in_window", "unit": "calls",
         "better": "higher", "source": "host_clock",
         "layer": "gradient reducer (core/engine.py)",
         "moves": "reduce_busbw_GBps"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.cell("grads-dp4-int8.mamba2-other", root=root)
    assert cell.driver == "reduce"
    assert cell.config["program"]["name"] == "mamba2-other"
    assert cell.traffic["flare"] == {"compression": "int8"}
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "reduce_busbw_GBps"}
    # no workloads key: reported wherever what it moves is reported
    names = {m["name"] for m in cell.per_layer}
    assert "reduce.calls_in_window" in names
    assert "reduce.calls_in_window" in {
        m["name"] for m in spec.cell("grads-dp4.mamba2-370m", root=root)
        .per_layer}
    assert spec.reader("reduce.calls_in_window", root).read(
        {"counts": {"calls": 7}}) == 7.0
    after = {p: p.read_bytes() for p in before}
    assert after == before
