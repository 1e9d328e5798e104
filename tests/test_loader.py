"""kubeai_tpu.loader edge cases (previously untested): atomic staging
(a failed load leaves NO partial destination), re-stage no-ops, evict
of a missing dest, stage_remote keying, and the --warm-compile-cache
CLI plumbing."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubeai_tpu import loader  # noqa: E402


def _mkmodel(d):
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "config.json"), "w") as f:
        f.write("{}")
    with open(os.path.join(d, "model.safetensors"), "w") as f:
        f.write("fake-weights")


def test_load_copies_file_source(tmp_path):
    src = str(tmp_path / "src")
    dest = str(tmp_path / "dest")
    _mkmodel(src)
    loader.load(f"file://{src}", dest)
    assert sorted(os.listdir(dest)) == ["config.json", "model.safetensors"]


def test_failed_load_leaves_no_partial_dest(tmp_path):
    # Missing source: copytree raises mid-load; the destination must
    # not exist afterwards (a crashed load must never look complete)
    # and the tmp staging dir must be cleaned up.
    dest = str(tmp_path / "dest")
    with pytest.raises(FileNotFoundError):
        loader.load(f"file://{tmp_path}/does-not-exist", dest)
    assert not os.path.exists(dest)
    assert [d for d in os.listdir(tmp_path) if ".tmp." in d] == []


def test_restage_of_populated_dest_is_noop(tmp_path):
    src = str(tmp_path / "src")
    dest = str(tmp_path / "dest")
    _mkmodel(src)
    loader.load(f"file://{src}", dest)
    marker = os.path.join(dest, "marker.txt")
    with open(marker, "w") as f:
        f.write("existing content survives")
    # Change the source; the populated dest must NOT be re-staged.
    with open(os.path.join(src, "model.safetensors"), "w") as f:
        f.write("changed")
    loader.load(f"file://{src}", dest)
    assert os.path.exists(marker)
    with open(os.path.join(dest, "model.safetensors")) as f:
        assert f.read() == "fake-weights"


def test_evict_missing_dest_is_harmless(tmp_path, caplog):
    with caplog.at_level("INFO", logger="kubeai_tpu.loader"):
        loader.evict(str(tmp_path / "absent"))
    assert any("already absent" in m for m in caplog.messages)


def test_evict_removes_dest(tmp_path):
    dest = str(tmp_path / "d")
    _mkmodel(dest)
    loader.evict(dest)
    assert not os.path.exists(dest)


def test_stage_remote_passthroughs(tmp_path):
    # file:// strips the scheme; plain paths pass through untouched —
    # neither goes through load().
    assert loader.stage_remote("file:///models/x", str(tmp_path)) == "/models/x"
    assert loader.stage_remote("/models/y", str(tmp_path)) == "/models/y"


def test_stage_remote_keys_dest_by_url(tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr(loader, "load", lambda url, dest: calls.append((url, dest)))
    d1 = loader.stage_remote("hf://org/model", str(tmp_path), prefix="m-")
    d2 = loader.stage_remote("hf://org/model", str(tmp_path), prefix="m-")
    d3 = loader.stage_remote("hf://org/model-v2", str(tmp_path), prefix="m-")
    assert d1 == d2  # same URL -> same dest (load() dedupes staging)
    assert d1 != d3  # changed URL can never reuse a stale download
    assert os.path.basename(d1).startswith("m-")
    assert len(calls) == 3


def test_cli_evict(tmp_path):
    dest = str(tmp_path / "d")
    _mkmodel(dest)
    loader.main(["--evict", dest])
    assert not os.path.exists(dest)


def test_cli_requires_dest(tmp_path):
    with pytest.raises(SystemExit):
        loader.main([f"file://{tmp_path}"])


def test_cli_warm_passes_engine_args_through(tmp_path, monkeypatch):
    src = str(tmp_path / "src")
    dest = str(tmp_path / "dest")
    _mkmodel(src)
    seen = {}
    monkeypatch.setattr(
        loader, "warm_compile_cache",
        lambda d, engine_args=None: seen.update(dest=d, args=engine_args),
    )
    loader.main([
        "--warm-compile-cache", f"file://{src}", dest,
        "--max-seq-len", "512", "--max-slots", "4",
    ])
    assert seen["dest"] == dest
    assert seen["args"] == ["--max-seq-len", "512", "--max-slots", "4"]
    assert os.path.isdir(dest)  # staging still happened


def test_warm_compile_cache_requires_cache_env(tmp_path, monkeypatch, caplog):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with caplog.at_level("INFO", logger="kubeai_tpu.loader"):
        assert loader.warm_compile_cache(str(tmp_path)) is None
    assert any("skipping compile warm" in m for m in caplog.messages)
