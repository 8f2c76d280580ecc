"""Configuration files, BENCHMARK.json and the files the harness finds by name."""
import json
import re
from pathlib import Path

import pytest

from bench import check, modelcfg
from bench.harness import plan
from bench.traffic import variants

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

PUBLISHED = {  # widths as published; depth is the only cut
    "yi-9b": dict(hidden_size=4096, intermediate_size=11008, num_attention_heads=32,
                  num_key_value_heads=4, head_dim=128, vocab_size=64000,
                  num_hidden_layers=48),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_configuration_gives_published_widths_with_the_cut_in_reduced(name):
    m = modelcfg.load(ROOT / "bench" / "configs" / f"{name}.json")
    pub = PUBLISHED[name]
    changed = sorted(k for k, v in pub.items() if m[k] != v)
    assert changed == sorted(m["reduced"])
    for entry in BENCH["configs"]:
        if entry["name"] == name:
            assert entry["file"] == f"bench/configs/{name}.json"
            assert sorted(entry["reduced"]) == changed
    for k in m["reduced"]:
        assert m["published"][k] == pub[k] and m[k] < pub[k]
    cfg = modelcfg.arch_config(m)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff, cfg.vocab) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["head_dim"], pub["intermediate_size"], pub["vocab_size"])
    assert cfg.padded_vocab == cfg.vocab


def test_benchmark_file_follows_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves_by_name(cell):
    p = plan(cell["name"], BENCH)
    reported = {m["name"] for m in p["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2 and p["per_layer"]
    for m in p["per_layer"]:
        assert m["moves"] in reported
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for m in p["end_to_end"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    expected = {check.number_name(v["w_bits"], v["kv_bits"], v["temperature"] == 0.0)
                for v in variants(p["mix"])}
    limits = p["limits"]["checks"]
    assert set(limits) == expected
    for c in limits.values():  # each limit lies above its sound readings, below the control's
        assert c["lower"] is None or c["lower"] < c["limit"]
        assert c["upper"] is None or c["limit"] < c["upper"]
        assert 0 < c["limit"] and c["readings"]
    eng = p["mix"]["engine"]
    assert eng["max_slots"] >= 1 and eng["prefill_chunk"] >= 1
