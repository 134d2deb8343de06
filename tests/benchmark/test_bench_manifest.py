"""BENCHMARK.json against its contract, and the reference against the program."""

import json
import os

import numpy as np
import pytest

from odbench import manifest, reference

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO, BENCH)


def test_manifest_is_sound(man):
    assert manifest.problems(man) == []
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= man.raw["run_seconds"] <= 51


def test_every_name_finds_its_files(man):
    for w in man.raw["workloads"]:
        cell = man.cell(w["name"])
        assert cell.chips == w["chips"]
        assert os.path.exists(os.path.join(BENCH, "drivers", cell.traffic["kind"] + ".py"))
        tiny = man.cell(w["name"], rehearse=True)
        assert tiny.config["hidden_size"] < cell.config["hidden_size"]
    for m in man.raw["per_layer"]:
        spec = man.metric_file(m["name"])
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert "workloads" not in spec  # the manifest alone says which cells
        read, _ = man.reader(m["name"])
        assert callable(read)


def _names(folder):
    return sorted(
        f[: -len(".json")] for f in os.listdir(os.path.join(BENCH, folder))
        if f.endswith(".json") and not f.endswith(".sweep.json")
    )


@pytest.mark.parametrize("name", _names("metrics"))
def test_every_metric_file_has_its_reader(man, name):
    """Also the files of a cell that is not in the manifest (PERF.md 7)."""
    spec = man.metric_file(name)
    assert spec["name"] == name and manifest.UNIT.match(spec["unit"])
    assert spec["better"] in ("lower", "higher") and spec["source"] in manifest.SOURCES
    read, _ = man.reader(name)
    assert callable(read)


@pytest.mark.parametrize("name", _names("traffic"))
def test_every_mix_has_its_driver(man, name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        mix = json.load(f)
    assert callable(man.driver(mix["kind"]).run)


def test_readers_return_nothing_when_there_is_nothing_to_read(man):
    cell = man.cell(man.raw["workloads"][0]["name"])
    for m in man.raw["per_layer"]:
        read, params = man.reader(m["name"])
        assert read({"counters": {}, "cell": cell, "peak": None}, params) is None


@pytest.mark.parametrize("bad, word", [
    (lambda r: r["workloads"][0].update(chips=2), "chips"),
    (lambda r: r["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda r: r["per_layer"][0].update(moves="nope"), "moves"),
    (lambda r: r["per_layer"][1].pop("workloads"), "not reported"),
    (lambda r: [w.update(chips=4) for w in r["workloads"][:2]], "25%"),
    (lambda r: r["configs"].append(dict(r["configs"][0], name="orphan")), "no cell"),
    (lambda r: r["end_to_end"][0].update(unit="tokens per s"), "unit"),
    (lambda r: r["workloads"][0].update(name="a b"), "characters"),
])
def test_manifest_checks_catch(man, bad, word):
    broken = manifest.Manifest(REPO, BENCH)
    broken.raw = json.loads(json.dumps(man.raw))
    bad(broken.raw)
    assert any(word in p for p in manifest.problems(broken)), manifest.problems(broken)


def test_reference_agrees_with_the_programs_forward(tiny_cfg):
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models.llama import causal_lm_loss, forward, init_params

    cfg = dict(tiny_cfg.to_dict())
    params = init_params(jax.random.key(3), tiny_cfg)
    ids = jax.random.randint(jax.random.key(4), (2, 24), 0, tiny_cfg.vocab_size)
    want = forward(params, ids, tiny_cfg, compute_dtype=jnp.float32, attn_impl="xla", remat=False)
    got = jax.jit(lambda p, i: reference.forward(p, i, cfg))(params, ids)
    # float32 both: only the order of accumulation differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
    loss, gnorm = jax.jit(lambda p, i: reference.loss_and_grad_norm(p, i, i, cfg))(params, ids)
    np.testing.assert_allclose(float(loss), float(causal_lm_loss(want, ids)), rtol=1e-5)
    grads = jax.jit(jax.grad(lambda p: causal_lm_loss(
        forward(p, ids, tiny_cfg, compute_dtype=jnp.float32, attn_impl="xla", remat=False), ids)))(params)
    want_norm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))))
    np.testing.assert_allclose(float(gnorm), want_norm, rtol=1e-4)


def test_reference_is_causal_and_tied():
    import jax

    cfg = dict(hidden_size=32, intermediate_size=64, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=50,
               tie_word_embeddings=True, rms_norm_eps=1e-5, rope_theta=1e4)
    from opendiloco_tpu.models.llama import LlamaConfig, init_params

    params = init_params(jax.random.key(0), LlamaConfig.from_dict(cfg))
    assert "lm_head" not in params
    a = np.arange(12).reshape(1, 12) % 50
    b = a.copy()
    b[0, 8:] = 7  # a later token changes no earlier logit
    fwd = jax.jit(lambda p, i: reference.forward(p, i, cfg))
    la, lb = fwd(params, a), fwd(params, b)
    np.testing.assert_allclose(np.asarray(la)[0, :8], np.asarray(lb)[0, :8], rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(la)[0, 8:], np.asarray(lb)[0, 8:])
