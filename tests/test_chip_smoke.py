"""CPU rehearsal of chip_smoke.py: the whole script at its --tiny size,
in this process, and the two refusals that keep a CPU number from ever
being read as a chip's."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tiny_runs_both_phases(chip_smoke, capsys):
    assert chip_smoke.main(["--tiny"]) == 0
    out = capsys.readouterr().out
    assert "train: losses=" in out and "serve: routes" in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last["ok"] is True and last["device"]["platform"] == "cpu"


def test_tiny_four_devices_runs_ddp_only(chip_smoke, capsys):
    assert chip_smoke.main(["--tiny", "--chips", "4"]) == 0
    out = capsys.readouterr().out
    assert "ddp: dp=4 losses=" in out and "train:" not in out
    assert json.loads(out.strip().splitlines()[-1])["ok"] is True


def test_tiny_is_refused_on_a_tpu(chip_smoke, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "_platform", lambda: "tpu")
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(["--tiny"])
    assert e.value.code not in (0, None) and "--tiny" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_full_size_is_refused_without_a_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""      # no result line
