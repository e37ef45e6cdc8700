"""``chip_smoke.py``'s phases on the CPU at the smoke config.

The script's ``main`` runs qwen2-vl-2b at published widths on one TPU; here
the same phase function runs the reduced config, so a change that breaks
the serving main path (closed loop, open loop, sync == async tokens,
finite logits) fails tier-1 before it costs chip time.
"""
import importlib.util
import pathlib

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_at_smoke_size(chip_smoke, capsys):
    out = chip_smoke.run_phases("qwen2-vl-2b", smoke=True)
    assert sorted(out["tokens"]) == list(range(chip_smoke.N_REQUESTS))
    assert all(len(t) == chip_smoke.NEW_TOKENS
               for t in out["tokens"].values())
    lines = capsys.readouterr().out.splitlines()
    phases = [ln.split(":")[0] for ln in lines if ln.startswith("phase ")]
    assert phases == ["phase build", "phase closed_loop", "phase open_loop",
                      "phase finite_logits", "phase report"]
    assert any(ln.startswith("peak_bytes_in_use: ") for ln in lines)
    assert not any('"ok"' in ln for ln in lines)


def test_failed_phase_raises(chip_smoke, monkeypatch):
    """No phase swallows a failure: a cache too short for the requests
    is refused at submit and the error reaches the caller."""
    monkeypatch.setattr(chip_smoke, "CACHE_LEN", 32)
    with pytest.raises(ValueError, match="cache_len"):
        chip_smoke.run_phases("qwen2-vl-2b", smoke=True)


def test_main_refuses_without_tpu(chip_smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert chip_smoke.main() != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "needs a TPU" in captured.err
