"""The harness finds every configuration, traffic mix and per-layer
metric by its name, and refuses to run without a chip."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.resolve(ROOT, cell)
    w = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    entry = next(e for e in MANIFEST["configs"] if e["name"] == w["config"])
    assert c.config == json.loads((ROOT / entry["file"]).read_text())
    assert c.traffic == json.loads(
        (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json")
        .read_text())
    assert Path(c.family.__file__) == (
        ROOT / "chipbench" / "families" / f"{c.config['family']}.py")
    assert hasattr(c.family, "Model")
    for m in c.per_layer:
        reader = c.readers[m["name"]]
        assert Path(reader.__file__) == (
            ROOT / "chipbench" / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer


def test_every_file_the_manifest_names_exists():
    for entry in MANIFEST["configs"]:
        assert (ROOT / entry["file"]).is_file()
        assert entry["file"].startswith(tuple(MANIFEST["paths"]))
    for m in MANIFEST["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
    for w in MANIFEST["workloads"]:
        assert (ROOT / "chipbench" / "traffic"
                / f"{w['traffic']}.json").is_file()


def test_a_new_config_is_found_without_editing_the_harness(tmp_path):
    src = json.loads((ROOT / "chipbench" / "configs"
                      / "vgg16-s2-int8.json").read_text())
    src["img_h"] = src["img_w"] = 224
    new = tmp_path / "vgg16-s1-int8.json"
    new.write_text(json.dumps(src))
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({"name": "vgg16-s1-int8", "source": "x",
                                "file": str(new), "reduced": [],
                                "why": "x"})
    manifest["workloads"].append({"name": "vgg16s1.closed32",
                                  "config": "vgg16-s1-int8",
                                  "traffic": "closed32", "chips": 1,
                                  "why": "x"})
    cell = harness.resolve(ROOT, "vgg16s1.closed32", manifest)
    assert cell.config == src and cell.config_name == "vgg16-s1-int8"
    assert cell.family.__name__ == "chipbench.families.cnn"


def test_unknown_workload_is_refused():
    with pytest.raises(harness.Refused, match="unknown workload"):
        harness.resolve(ROOT, "no.such.cell")


def _run_py(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", str(2**31 + 17), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu():
    r = _run_py(ROOT)
    assert r.returncode == 2, r.stderr
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_compile_cache_keeps_writing_under_a_size_limit(tmp_path):
    """An environment that limits the cache's size makes JAX evict by
    access-time files; a directory seeded with an entry that lacks its
    file must still take every new program."""
    cache = tmp_path / ".jax_cache"
    cache.mkdir()
    (cache / "jit_seeded-0-cache").write_bytes(b"x" * 10)
    code = ("import pathlib, sys; import jax, jax.numpy as jnp; "
            "from chipbench import harness; "
            "harness.compile_cache(pathlib.Path(sys.argv[1])); "
            "jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
               JAX_COMPILATION_CACHE_MAX_SIZE=str(1 << 30))
    r = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    written = [p.name for p in cache.iterdir()
               if p.name.startswith("jit__lambda")]
    assert written, (sorted(p.name for p in cache.iterdir()), r.stderr)
