"""BENCHMARK.json, and everything it names, found by name."""
from __future__ import annotations

import json
import re

import pytest

from bench_helpers import ROOT
from bench import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return run.load_spec()


def test_spec_keys_and_names(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in spec["configs"]] \
        + [w["name"] for w in spec["workloads"]] \
        + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_resolves(spec):
    e2e = {m["name"] for m in spec["end_to_end"]}
    for cell in spec["workloads"]:
        ctx = run.resolve(spec, cell["name"])
        conf = ctx["config"]
        assert conf["name"] == cell["config"]
        assert {m["name"] for m in ctx["end_to_end"]} - {"setup_s"}
        assert ctx["per_layer"]
        for m in ctx["per_layer"]:
            assert callable(run.load_reader(m["name"]))
        arch, ref = run.arch_modules(conf)
        assert hasattr(arch, "make_params") and hasattr(ref, "served_gaps")
        assert e2e >= {m["name"] for m in ctx["end_to_end"]}


def test_config_entries_match_files(spec):
    for entry in spec["configs"]:
        conf = json.loads((ROOT / entry["file"]).read_text())
        assert conf["name"] == entry["name"]
        assert conf["source"] == entry["source"]
        assert conf["reduced"] == entry["reduced"]
        assert all(key in conf for key in conf["reduced"])
        # where the program's model departs from the published one, the file
        # holds what is run and names the published value beside it
        for key, published in conf["departures"]["published"].items():
            assert key not in conf["reduced"] and conf[key] != published


def test_new_files_are_found_without_edits(tmp_path):
    """A throwaway configuration, mix and metric reader, added as files
    plus entries, are picked up by name."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    conf = json.loads((ROOT / "bench/configs/opt-1.3b.json").read_text())
    conf["name"] = "throwaway"
    (bench / "configs" / "throwaway.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "bench/traffic/decode.json").read_text())
    mix["clients"] = 1
    (bench / "traffic" / "solo.json").write_text(json.dumps(mix))
    (bench / "metrics" / "answer.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec = run.load_spec()
    spec["configs"].append({"name": "throwaway",
                            "file": "bench/configs/throwaway.json"})
    spec["workloads"].append({"name": "throwaway.solo",
                              "config": "throwaway", "traffic": "solo",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "answer", "unit": "%",
                              "better": "higher", "layer": "test",
                              "moves": "decode_tok_s",
                              "source": "program_counter",
                              "workloads": ["throwaway.solo"]})
    ctx = run.resolve(spec, "throwaway.solo", bench=bench)
    assert ctx["config"]["name"] == "throwaway"
    assert ctx["mix"]["clients"] == 1
    assert "answer" in [m["name"] for m in ctx["per_layer"]]
    assert run.load_reader("answer", bench=bench)(None) == 42.0
    other = run.resolve(spec, "opt-1.3b.decode")
    assert "answer" not in [m["name"] for m in other["per_layer"]]


def test_unknown_names_are_errors(spec):
    with pytest.raises(SystemExit):
        run.resolve(spec, "no-such-cell")
    with pytest.raises(SystemExit):
        run.load_peaks("TPU v99")


def test_peaks_table():
    peaks = run.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops"] == 197e12 and peaks["int8_ops"] == 393e12
    assert peaks["hbm_bytes_s"] == 819e9 and peaks["hbm_bytes"] == 16e9
    assert "TPU v5e" in peaks["source"]
