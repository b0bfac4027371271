"""The benchmark is driven by data: every cell, configuration, mix, limit
and metric is found by name, and a new cell or mix is new files only."""

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


def test_weights_have_the_engine_layout():
    """The benchmark's tree matches the program's parameter declarations."""
    sys.path.insert(0, str(ROOT / "src"))
    from bench import sut
    from repro.models import registry
    from repro.sharding.params import abstract_tree

    for name in ("granite-3-2b", "stablelm-3b"):
        cfg = spec.load_json(ROOT / "bench" / "configs" / f"{name}.json")
        want = abstract_tree(registry.decls(sut.model_config(cfg)))
        got = weights._nest({p: s for p, (s, _) in weights.shapes(cfg).items()})
        import jax
        assert (jax.tree.map(lambda a: tuple(a.shape), want)
                == jax.tree.map(tuple, got, is_leaf=lambda x: isinstance(x, tuple)))


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
