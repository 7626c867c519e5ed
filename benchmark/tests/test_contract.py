"""BENCHMARK.json against the parts of its contract that can be checked
without a chip: names, lengths, files found by name, metrics that name
their cells, and cells that are nothing but data files."""
import os
import re

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines():
    rows = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] \
        + BENCH["per_layer"]
    names = [r["name"] for r in rows]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for r in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(r["unit"]) and r["better"] in ("lower", "higher")
        assert r["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for r in rows:
        for key in ("why", "layer", "source"):
            if key in r:
                assert 1 <= len(r[key]) <= 200 and "\n" not in r[key] \
                    and "\t" not in r[key]
    for r in BENCH["end_to_end"]:
        assert set(r) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= r["bound"] <= 0.1
        assert r["source"] in ("host_clock", "device_trace")
    for r in BENCH["per_layer"]:
        assert set(r) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_cell_is_found_by_name_and_reports_enough():
    e2e = {r["name"]: r for r in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for w in BENCH["workloads"]:
        spec = harness.Spec(w["name"])          # finds every file by name
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert spec.cell["chips"] == w["chips"]
        mine = [r["name"] for r in spec.metric_rows("end_to_end")]
        assert "setup_s" in mine and len(mine) >= 2
        layer = spec.metric_rows("per_layer")
        assert layer
        for r in layer:         # moves a metric this cell reports
            assert r["moves"] in mine, (w["name"], r["name"])
            meta = harness.load_json(spec.metrics_dir, r["name"] + ".json")
            assert os.path.exists(os.path.join(
                harness.HERE, "readers", meta["reader"] + ".py"))
            assert meta["layer"] == r["layer"] and meta["moves"] == r["moves"]


def test_configs_name_their_file_and_reduce_no_width():
    for c in BENCH["configs"]:
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        body = harness.load_json(harness.ROOT, c["file"])
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"hidden|intermediate|_dim$|_rank$|head",
                                 key)
        assert os.path.exists(os.path.join(
            harness.HERE, "reference", body["reference"] + ".py"))


def test_roofline_and_mfu_metrics_are_named_as_such():
    for r in BENCH["per_layer"]:
        if "roofline" in r["name"] or "mfu" in r["name"]:
            assert r["unit"] == "%" and r["better"] == "higher"
    mfu_moves = {r["moves"] for r in BENCH["per_layer"] if "mfu" in r["name"]}
    roof_moves = {r["moves"] for r in BENCH["per_layer"]
                  if "roofline" in r["name"]}
    assert roof_moves <= mfu_moves      # a whole-step share beside each
