"""bench/kernel_bytes.py: bytes per element and the roofline share."""
from types import SimpleNamespace

import pytest

from bench import kernel_bytes as kb
from bench.trace import Event, HloOp


def test_bytes_per_element():
    assert kb.bytes_per_element("ef_correct_stats", 3) == 12.0
    assert kb.bytes_per_element("bucket_stats", 3) == 4.0
    assert kb.bytes_per_element("codebook_encode_pack_residual", 3) == 8.375
    assert kb.bytes_per_element("uniform_encode_pack", 4) == 4.5
    assert kb.bytes_per_element("codebook_decode_reduce", 3) is None


def test_elements_is_the_bucket_tile():
    assert kb.elements([1, 8192 * 128, 8192 * 128, 8]) == 8192 * 128


def test_roofline_share_of_two_calls():
    n = 1 << 20
    op = HloOp(opcode="custom-call", scope="", kernel="ef_correct_stats",
               operand_elements=[1, n, n])
    # two calls, each 12 B x n at 1e12 B/s needs 12.58 us; each took 25.17 us
    dur = 2 * 12 * n / 1e12 * 1e9
    evs = [Event(0, "ef_correct_stats.1", 0.0, dur, "sync", op),
           Event(0, "ef_correct_stats.2", dur, dur, "sync", op)]
    ctx = SimpleNamespace(workload={"bits": 3}, peaks={"hbm_bytes_per_s": 1e12},
                          kernel_events=lambda pat: evs)
    assert kb.roofline_pct(ctx, "stats") == pytest.approx(50.0)
    empty = SimpleNamespace(workload={"bits": 3}, peaks={"hbm_bytes_per_s": 1e12},
                            kernel_events=lambda pat: [])
    assert kb.roofline_pct(empty, "stats") is None
