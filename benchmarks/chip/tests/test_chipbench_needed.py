"""The bytes each kernel's work needs, counted from its operands."""
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import needed  # noqa: E402


def test_cdc_reads_each_byte_and_writes_a_bit():
    assert needed.cdc_bytes(1 << 20) == (1 << 20) + (1 << 17)
    assert needed.cdc_bytes(9) == 9 + 2


def test_gf256_plain_encode_and_decode():
    rng = np.random.default_rng(0)
    P = rng.integers(1, 256, (5, 6), dtype=np.uint8)
    B = rng.integers(0, 256, (6, 1000), dtype=np.uint8)
    assert needed.gf256_bytes(P, B, code_k=6) == (6 + 5) * 1000
    D = rng.integers(1, 256, (6, 6), dtype=np.uint8)
    assert needed.gf256_bytes(D, B, code_k=6) == 12 * 1000


def test_gf256_fused_decode_counts_each_set_at_its_own_width():
    rng = np.random.default_rng(1)
    k, widths = 6, (1000, 300, 40)
    G = len(widths)
    A = np.zeros((G * k, G * k), np.uint8)
    B = np.zeros((G * k, max(widths)), np.uint8)
    for g, w in enumerate(widths):
        A[g * k:(g + 1) * k, g * k:(g + 1) * k] = rng.integers(1, 256, (k, k))
        B[g * k:(g + 1) * k, :w] = rng.integers(1, 256, (k, w))
    assert needed.gf256_bytes(A, B, code_k=k) == 2 * k * sum(widths)
    # a dense square operand of the same shape is no fused decode
    dense = rng.integers(1, 256, (G * k, G * k), dtype=np.uint8)
    assert needed.gf256_bytes(dense, B, code_k=k) == 2 * G * k * max(widths)
