"""The engine's command line, held to what ships with it: every TPUEngine
manifest under deploy/models/ and every documented `python -m
kubeai_tpu.engine.server` line parses, and options that were removed are
refused wherever a user could still write them (the engine then does not
start: better than serving on a switch that does nothing)."""

import pathlib
import re
import shlex
import sys

import pytest
import yaml

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import bench  # noqa: E402
from kubeai_tpu.engine.core import EngineConfig  # noqa: E402
from kubeai_tpu.engine.server import make_engine_arg_parser  # noqa: E402

MANIFESTS = sorted(
    p for p in (ROOT / "deploy" / "models").glob("*.yaml")
    if yaml.safe_load(p.read_text())["spec"].get("engine") == "TPUEngine"
)
DOCS = [ROOT / "README.md", ROOT / "docs" / "quickstart.md"]


def _accepted(argv: list[str]):
    try:
        return make_engine_arg_parser().parse_args(argv)
    except SystemExit:
        pytest.fail(f"the engine refuses to start on {argv}")


def test_there_are_manifests_to_check():
    assert len(MANIFESTS) >= 5


@pytest.mark.parametrize("path", MANIFESTS, ids=lambda p: p.stem)
def test_engine_accepts_the_args_of_a_shipped_manifest(path):
    spec = yaml.safe_load(path.read_text())["spec"]
    # As the controller's pod plan composes it: the model, then spec.args.
    _accepted(["--model", spec["url"], *map(str, spec.get("args", []))])


@pytest.mark.parametrize("path", DOCS, ids=lambda p: p.name)
def test_engine_accepts_the_command_lines_of_a_document(path):
    text = path.read_text().replace("\\\n", " ")
    lines = re.findall(r"^python -m kubeai_tpu\.engine\.server (.*)$", text, re.M)
    assert lines, f"{path.name} shows no engine command line"
    for line in lines:
        _accepted(shlex.split(line, comments=True))


@pytest.mark.parametrize(
    "where,option",
    [
        ("EngineConfig", "speculate_tokens"), ("EngineConfig", "decode_kernel"),
        ("server", "--speculate-tokens"), ("server", "--decode-kernel"),
        ("bench", "--speculate"), ("bench", "--decode-kernel"),
    ],
)
def test_a_removed_option_is_refused(where, option, monkeypatch, capsys):
    if where == "EngineConfig":
        with pytest.raises(TypeError, match=option):
            EngineConfig(**{option: 2})
        return
    with pytest.raises(SystemExit) as e:
        if where == "server":
            make_engine_arg_parser().parse_args(["--model", "m", option, "2"])
        else:
            monkeypatch.setattr(sys, "argv", ["bench.py", "--tiny", option, "2"])
            bench.main()
    assert e.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err
