"""Blocks found by name (``bench/blocks/<block>.py``): the harness, the
weights and the reference ask the block a configuration names, and the dense
block reads exactly what it read before it had a module of its own."""
import hashlib
import json
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import blocks, modelcfg, reference
from bench.harness import plan
from bench.weights import make_weights

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).with_name("fixtures")


def _toy_block():
    """A block in which a layer adds its one weight vector to the stream."""
    calls = []
    mod = types.ModuleType("bench.blocks.toy")

    def validate(model):
        if "hidden_size" not in model:
            raise ValueError("toy needs hidden_size")

    def arch_config(model):
        calls.append("arch_config")
        return ("toy-arch", model["hidden_size"])

    def make_weights(model, seed):
        calls.append("make_weights")
        d, v, n = model["hidden_size"], model["vocab_size"], model["num_hidden_layers"]
        key = jax.random.PRNGKey(seed)
        return {
            "embed": jax.random.normal(key, (v, d), jnp.float32).astype(jnp.bfloat16),
            "unembed": jnp.eye(d, v, dtype=jnp.bfloat16),
            "final_norm": jnp.ones(d, jnp.float32),
            "blocks": {"shift": jnp.arange(n * d, dtype=jnp.float32).reshape(n, d)},
        }

    def layer_weights(blocks_, li, bits):
        calls.append(("layer_weights", int(li), bits))
        return blocks_["shift"][li]

    def layer(x, p, model, kv_bits):
        calls.append(("layer", kv_bits))
        return x + p

    for f in (validate, arch_config, make_weights, layer_weights, layer):
        setattr(mod, f.__name__, f)
    return mod, calls


def test_a_block_added_by_name_drives_the_config_the_weights_and_the_reference(
        monkeypatch):
    mod, calls = _toy_block()
    monkeypatch.setitem(sys.modules, "bench.blocks.toy", mod)
    m = {"block": "toy", "name": "t", "hidden_size": 8, "vocab_size": 256,
         "num_hidden_layers": 2, "rms_norm_eps": 1e-6}
    assert modelcfg.arch_config(m) == ("toy-arch", 8)
    w = make_weights(m, 3)
    tokens = np.arange(5, dtype=np.int32)
    out = reference.logits(w, m, [tokens], [np.array([4])], 16, 16, pad=8)[0]
    x = np.asarray(w["embed"][4], np.float32) + np.arange(8) + np.arange(8, 16)
    want = x / np.sqrt(np.mean(x * x) + 1e-6)
    np.testing.assert_allclose(out[0, :8], want, rtol=1e-5)
    assert calls == ["arch_config", "make_weights", ("layer_weights", 0, 16),
                     ("layer", 16), ("layer_weights", 1, 16), ("layer", 16)]
    with pytest.raises(ValueError, match="toy needs hidden_size"):
        blocks.of(m).validate({"block": "toy"})


def test_an_unknown_block_fails_in_plan_and_names_the_missing_file(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = dict(bench["configs"][0], file=str(tmp_path / "m.json"))
    model = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
    (tmp_path / "m.json").write_text(json.dumps({**model, "block": "nosuch"}))
    bench["configs"] = [cfg]
    with pytest.raises(ValueError, match=r"bench/blocks/nosuch\.py"):
        plan(bench["workloads"][0]["name"], bench)
    (tmp_path / "m.json").write_text(json.dumps({**model, "block": "../dense"}))
    with pytest.raises(ValueError, match="names no block module"):
        plan(bench["workloads"][0]["name"], bench)


def test_the_dense_block_reads_what_it_read_before_its_module():
    """Weights (every leaf, bit for bit) and reference logits at four
    precisions for ``tiny.MODEL``, against values recorded from the harness
    as it stood before the dense block moved into ``bench/blocks/dense.py``."""
    want = json.loads((FIXTURES / "dense_tiny.json").read_text())
    rec = np.load(FIXTURES / "dense_tiny.npz")
    w = make_weights(tiny.MODEL, want["seed"])
    got = {jax.tree_util.keystr(p): hashlib.sha256(np.asarray(v).tobytes()).hexdigest()
           for p, v in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert got == want["leaves"]
    for name in ("w16kv16", "w16kv8", "w8kv8", "w4kv4"):
        wb, kb = (int(x) for x in name[1:].split("kv"))
        out = reference.logits(w, tiny.MODEL, [rec["tokens"]], [rec["positions"]],
                               wb, kb)[0]
        np.testing.assert_array_equal(out, rec[name])
