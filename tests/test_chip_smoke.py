"""chip_smoke.py off the chip: its phases on the CPU at small sizes, and
its refusal to report a result when JAX finds no TPU."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_reduced

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_papernet_phase_backends_agree(chip_smoke, capsys):
    """bsp and async over the DES on 8 workers: the Pallas reduction and
    the jnp reference reach the same parameters."""
    out = chip_smoke.papernet_phase(get_reduced("papernet"), steps=2)
    for policy in ("bsp", "async"):
        r = out[policy]
        assert r["max_param_diff"] <= chip_smoke.PARAM_RTOL * r["max_param"]
        assert r["compiled_kernel"] is False    # interpreted off the TPU
    printed = capsys.readouterr().out
    assert "bsp/pallas: losses" in printed
    assert "async/python: losses" in printed


def test_papernet_phase_fails_on_disagreement(chip_smoke, monkeypatch):
    monkeypatch.setattr(chip_smoke, "_max_diff", lambda a, b: 1.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="bsp"):
        chip_smoke.papernet_phase(get_reduced("papernet"), steps=1)


def test_sharded_phase_ltp_matches_plain(chip_smoke):
    cfg = get_reduced("smollm_360m").replace(dtype="float32")
    out = chip_smoke.sharded_phase(cfg, jax.devices()[:1], batch=4, seq=32)
    assert out["max_rel_diff"] <= chip_smoke.LOSS_RTOL
    assert len(out["losses"]["ltp"]) == len(out["losses"]["plain"]) == 3


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_fails_without_tpu(chip_smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "needs a TPU" in captured.err
