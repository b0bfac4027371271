"""The benchmark is driven by data: every cell, configuration, mix, limit,
metric and model file is found by name, and a new cell, mix or
architecture is new files only."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import spec, weights

ROOT = spec.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_with_a_reader_for_each_metric(cell):
    c = spec.cell(cell)
    assert c.end_to_end and c.per_layer
    assert "setup_s" in {m.name for m in c.end_to_end}
    assert len(c.end_to_end) >= 2
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.reader(m.name))
    assert "max_logit_gap" in c.limits


def test_names_units_and_keys_keep_the_contract():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).exists()
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size"))
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_a_new_mix_and_cell_are_new_files_only(tmp_path):
    """Copy the benchmark, add a traffic file, a limit file and a cell
    entry, and read the new cell without touching any existing file."""
    for path in BENCH["paths"][:1]:
        shutil.copytree(ROOT / path, tmp_path / path)
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "granite-3-2b.chat-bursty",
                               "config": "granite-3-2b",
                               "traffic": "chat-bursty", "chips": 1,
                               "why": "bursts"})
    bench["end_to_end"][0]["workloads"].append("granite-3-2b.chat-bursty")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    chat = json.loads((tmp_path / "bench/traffic/chat.json").read_text())
    (tmp_path / "bench/traffic/chat-bursty.json").write_text(
        json.dumps(dict(chat, arrivals="bursty", burst=8)))
    (tmp_path / "bench/limits/granite-3-2b.chat-bursty.json").write_text(
        json.dumps({"max_logit_gap": 0.1}))
    c = spec.cell("granite-3-2b.chat-bursty", root=tmp_path)
    assert c.traffic["arrivals"] == "bursty"
    assert [m.name for m in c.end_to_end] == ["ttft_p50_ms", "setup_s"]
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_new_metric_is_a_new_reader_file(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    (tmp_path / "bench/metrics/tokens_total.py").write_text(
        "def read(run, variant):\n    return 42.0\n")
    assert spec.reader("tokens_total.batch", root=tmp_path)(None) == 42.0
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric", root=tmp_path)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_weights_have_the_engine_layout(name):
    """The benchmark's tree, at the model file's small size, matches the
    program's parameter declarations."""
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    from bench import sut
    from repro.models import registry
    from repro.sharding.params import abstract_tree

    cfg = spec.load_json(ROOT / "bench" / "configs" / f"{name}.json")
    cfg = spec.model(cfg).small(cfg)
    want = abstract_tree(registry.decls(sut.model_config(cfg)))
    got = weights._nest({p: s for p, (s, _) in weights.shapes(cfg).items()})
    assert (jax.tree.map(lambda a: tuple(a.shape), want)
            == jax.tree.map(tuple, got, is_leaf=lambda x: isinstance(x, tuple)))


# a model file with dense equations, logits doubled, two more leaves
# (drawn by the plain init tags) and only the first layer attending
DOUBLED = """
_dense_shapes, _dense_logits = shapes, logits
_dense_flops, _dense_layers = tokens_flops, attention_layers


def shapes(cfg):
    out = dict(_dense_shapes(cfg))
    out["extra/bias"] = ((cfg["hidden_size"],), "zeros")
    out["extra/scale"] = ((64, 64), ("normal", 0.5))
    return out


def logits(cfg, params, tokens, quant=False):
    return 2.0 * _dense_logits(cfg, params, tokens, quant)


def tokens_flops(cfg, tokens, attended):
    return 2.0 * _dense_flops(cfg, tokens, attended)


def attention_layers(cfg):
    return _dense_layers(cfg)[:1]
"""


def test_a_new_configuration_is_new_files_only(tmp_path, monkeypatch):
    """Copy the benchmark, add a configuration that names new equations,
    their model file, a limit file and a cell entry: every entry point
    reaches the new file, and no existing file changes."""
    import jax.numpy as jnp

    from bench import flops, reference, sut
    from bench.kernels import paged_attn

    for path in BENCH["paths"][:1]:
        shutil.copytree(ROOT / path, tmp_path / path)
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    base = spec.load_json(ROOT / "bench/configs/granite-3-2b.json")
    base = spec.model(base).small(base)
    cfg = dict(base, name="granite-doubled", equations="dense-doubled")
    (tmp_path / "bench/configs/granite-doubled.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/models/dense-doubled.py").write_text(
        (ROOT / "bench/models/dense.py").read_text() + DOUBLED)
    (tmp_path / "bench/limits/granite-doubled.decode.json").write_text(
        json.dumps({"max_logit_gap": 0.1}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "granite-doubled", "source": "test",
                             "file": "bench/configs/granite-doubled.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "granite-doubled.decode",
                               "config": "granite-doubled",
                               "traffic": "decode", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "ROOT", tmp_path)

    c = spec.cell("granite-doubled.decode", root=tmp_path)
    assert c.config == cfg
    assert sut.model_config(cfg).n_layers == 4
    tree = weights.shapes(cfg)
    assert tree["extra/bias"] == ((128,), "zeros")
    params = weights.make(cfg, 3, jnp.float32)
    assert float(jnp.abs(params["extra"]["bias"]).max()) == 0.0
    assert 0.4 < float(params["extra"]["scale"].std()) < 0.6
    tokens = jnp.arange(8, dtype=jnp.int32)
    assert jnp.array_equal(reference.logits(cfg, params, tokens),
                           2.0 * reference.logits(base, params, tokens))
    assert flops.tokens_flops(cfg, 3, 40) == 2 * flops.tokens_flops(base, 3, 40)
    one, four = paged_attn.cost(cfg, 3, 40), paged_attn.cost(base, 3, 40)
    assert one == (four[0] / 4, four[1] / 4)
    assert all(p.read_bytes() == b for p, b in before.items())


@pytest.mark.parametrize("entry", ["cell", "shapes", "logits", "flops", "cost",
                                   "program"])
def test_unknown_equations_raise_naming_the_missing_file(tmp_path, entry):
    from bench import flops, reference, sut
    from bench.kernels import paged_attn

    cfg = dict(spec.load_json(ROOT / "bench/configs/granite-3-2b.json"),
               equations="no-such-equations")
    calls = {
        "shapes": lambda: weights.shapes(cfg),
        "logits": lambda: reference.logits(cfg, {}, None),
        "flops": lambda: flops.tokens_flops(cfg, 1, 1),
        "cost": lambda: paged_attn.cost(cfg, 1, 1),
        "program": lambda: sut.model_config(cfg),
    }
    if entry == "cell":
        shutil.copytree(ROOT / "bench", tmp_path / "bench")
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        (tmp_path / "bench/configs/granite-3-2b.json").write_text(json.dumps(cfg))
        calls["cell"] = lambda: spec.cell("granite-3-2b.chat", root=tmp_path)
    with pytest.raises(FileNotFoundError, match="no-such-equations.py"):
        calls[entry]()


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    out = _run(["--workload", "granite-3-2b.chat", "--seed", "1",
                "--seconds", "1", "--trace", "0"], ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_without_the_program_it_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has no
    system under test to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path)
    out = _run(["--workload", "granite-3-2b.chat", "--seed", "1",
                "--seconds", "1", "--trace", "0"], tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
