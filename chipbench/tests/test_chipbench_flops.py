"""The shape-derived FLOP count that train_mfu divides by the peak."""
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def test_mamba2_370m_per_token_count_matches_the_hand_count():
    spec = importlib.util.spec_from_file_location(
        "flops_mamba2", BENCH / "flops" / "mamba2-370m.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = json.loads((BENCH / "configs" / "mamba2-370m.json").read_text())
    # per layer, per token (D 1024, Di 2048, H 32, P 64, N 128, Q 256):
    #   in-proj 2*1024*4384 = 8,978,432   conv 2*4*2304 = 18,432
    #   C.B 2*256*128 = 65,536            intra 2*256*32*64 = 1,048,576
    #   states + read-out 4*32*64*128 = 1,048,576
    #   state passing 2*32*64*128/256 = 2,048   out-proj 2*2048*1024 = 4,194,304
    #   = 15,355,904; x 48 layers = 737,083,392
    # head 2*1024*50288 = 102,989,824; forward 840,073,216; x 3 (fwd + bwd)
    assert mod.per_token(m["model"]) == 2_520_219_648


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_fail():
    import pytest

    from chipbench.metrics._shared import peak

    assert peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    assert peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peak("TPU v9 imaginary", "bf16_flops_per_s")
