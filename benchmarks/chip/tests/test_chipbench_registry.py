"""The chip benchmark finds its pieces by name, and BENCHMARK.json keeps
to the limits its contract sets."""
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import registry  # noqa: E402

BENCH = registry.benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_pieces_found_by_name(w):
    cfg = registry.config(w["config"])
    assert cfg["name"] == w["config"]
    assert callable(registry.loop(registry.traffic(w["traffic"])["loop"]).run)
    assert callable(registry.quality_reference(
        cfg["quality_reference"]).partition)
    assert callable(registry.graph_family(cfg["graph"]["family"]).generate)
    entry = {c["name"]: c for c in BENCH["configs"]}[w["config"]]
    assert entry["file"] == f"benchmarks/chip/configs/{w['config']}.json"
    assert entry["reduced"] == cfg["reduced"]
    assert set(cfg["limits"]) >= {"unanswered", "bad_labels", "cut_gap",
                                  "flag_gap", "slack_used", "cut_over_ref"}


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader(kind):
    for m in BENCH[kind]:
        assert callable(registry.metric_reader(m["name"]))


@pytest.mark.parametrize("lookup,name", [
    (registry.config, "no-such-config"),
    (registry.traffic, "no-such-traffic"),
    (registry.metric_reader, "no_such_metric"),
    (registry.graph_family, "no_such_family"),
    (registry.loop, "no_such_loop"),
    (registry.quality_reference, "no_such_reference"),
    (registry.config, "../../BENCHMARK"),
    (registry.peaks, "TPU v0 imaginary"),
])
def test_unknown_name_is_an_error(lookup, name):
    with pytest.raises(registry.UnknownName):
        lookup(name)


def test_unknown_workload_is_an_error():
    with pytest.raises(registry.UnknownName):
        registry.workload(BENCH, "nope.batch")


def test_peaks_keyed_by_device_kind():
    v5e = registry.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with open(registry.BENCH_DIR / "peaks.json") as f:
        assert "TPU v5e" in json.load(f)["source"]


ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


@pytest.mark.parametrize("kind", sorted(ENTRY_KEYS))
def test_benchmark_entries_have_exactly_their_keys(kind):
    required, optional = ENTRY_KEYS[kind]
    for entry in BENCH[kind]:
        assert required <= set(entry) <= required | optional, entry["name"]
        for key in ("why", "layer", "source"):
            text = entry.get(key)
            if key in required and isinstance(text, str):
                assert 1 <= len(text) <= 200 and "\n" not in text \
                    and "\t" not in text, (entry["name"], key)


def test_benchmark_names_units_and_bounds():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.fullmatch(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= \
        max(1, len(BENCH["workloads"]) // 2)
